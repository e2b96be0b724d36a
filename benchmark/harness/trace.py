"""Reduction of a profiler trace (``.xplane.pb``) to device busy time,
idle share and a breakdown.

- The traced window is the benchmark's own host span
  ``bench.trace_window`` (``jax.profiler.TraceAnnotation``).
- Device busy time is, per chip, the union of the intervals of the
  events on its ``XLA Ops`` line (planes ``/device:TPU:<n>``), clipped
  to the window, averaged over the chips that ran anything.
- ``device_ops``: the ops that took most device time (seconds, summed
  over the window and averaged over those chips).
- ``idle_gaps``: the longest gaps in the first busy chip's union, each
  named by the benchmark host span (``bench.*``) that overlaps it most:
  what the host was doing while the device waited.

``reduce_lines`` is pure and is what the tests check; ``load`` is the
only part that reads the file.
"""

from __future__ import annotations

import dataclasses
import re

WINDOW_SPAN = "bench.trace_window"
SPAN_PREFIX = "bench."
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = re.compile(r"^XLA Ops$")
TOP = 10


@dataclasses.dataclass
class Line:
    plane: str
    name: str
    events: list  # (name, start_ns, duration_ns)


@dataclasses.dataclass
class Reduction:
    busy_s: float
    window_s: float
    n_devices: int
    device_ops: list   # [[name, seconds], ...]
    idle_gaps: list    # [[host span, seconds], ...]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def load(path: str) -> list:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    return [Line(plane.name, line.name,
                 [(ev.name, float(ev.start_ns), float(ev.duration_ns))
                  for ev in line.events])
            for plane in data.planes for line in plane.lines]


def union(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def op_name(event_name: str) -> str:
    """A TPU op event is named by its whole HLO instruction
    (``%fusion.1 = s32[1250048,4]{...} fusion(...), kind=...``): keep the
    instruction's name and its result type, without the layout."""
    if " = " not in event_name:
        return event_name
    name, rest = event_name.split(" = ", 1)
    return f"{name.lstrip('%')} {rest.split('{', 1)[0].split(' ', 1)[0]}"


def _overlap(a0, a1, b0, b1) -> float:
    return max(0.0, min(a1, b1) - max(a0, b0))


def _is_ops(line: Line) -> bool:
    return bool(DEVICE_PLANE.match(line.plane) and OPS_LINE.match(line.name))


def reduce_lines(lines: list) -> Reduction:
    host = [(n, s, s + d) for ln in lines if not _is_ops(ln)
            for n, s, d in ln.events if n.startswith(SPAN_PREFIX)]
    windows = [(s, e) for n, s, e in host if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    w0, w1 = windows[0]
    spans = [(n, s, e) for n, s, e in host if n != WINDOW_SPAN]
    per_device = {}
    op_ns = {}
    for ln in lines:
        if not _is_ops(ln):
            continue
        for n, s, d in ln.events:
            s0, e0 = max(s, w0), min(s + d, w1)
            if e0 > s0:
                per_device.setdefault(ln.plane, []).append((s0, e0))
                short = op_name(n)
                op_ns[short] = op_ns.get(short, 0.0) + (e0 - s0)
    if not per_device:
        raise ValueError("no device op ran inside the traced window")
    merged = {p: union(iv) for p, iv in per_device.items()}
    n_dev = len(merged)
    busy_ns = sum(e - s for m in merged.values() for s, e in m) / n_dev
    ops = sorted(op_ns.items(), key=lambda kv: -kv[1])[:TOP]
    first = merged[sorted(merged)[0]]
    edges = [w0] + [x for s, e in first for x in (s, e)] + [w1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    named = []
    for g0, g1 in gaps[:TOP]:
        best, label = 0.0, "none"
        for n, s, e in spans:
            ov = _overlap(g0, g1, s, e)
            if ov > best:
                best, label = ov, n
        named.append([label, (g1 - g0) / 1e9])
    return Reduction(busy_s=busy_ns / 1e9, window_s=(w1 - w0) / 1e9,
                     n_devices=n_dev,
                     device_ops=[[n, t / n_dev / 1e9] for n, t in ops],
                     idle_gaps=named)
