"""Device idle time charged to what the caller's thread was doing, and
device time per jitted step, from the lines of a profiler trace
(``trace.load``).

- ``idle_by_span``: every idle nanosecond of the traced window (the
  complement of the first busy chip's union of ``XLA Ops`` intervals,
  as ``trace.reduce_lines`` computes it) goes to one name: the innermost
  ``ratelimiter.stream.*`` span open at that instant on the caller's
  thread, else the innermost ``bench.*`` span, else ``none``.  The
  caller's thread is the host line that holds ``bench.stream_call``:
  host lines are per OS thread and need not carry distinct names.
- ``shares``: those seconds grouped by the layer that caused the wait,
  in % of the window; the four groups add up to the device idle share.
- ``device_steps``: device time of the ``XLA Ops`` events summed per
  ``XLA Modules`` event that contains each (the jitted step that ran
  it), averaged over the chips that ran anything.

Pure functions of the lines; ``benchmark/idle_by_span.py`` runs them on
a cell's traced window.
"""

from __future__ import annotations

import bisect
import dataclasses
import re

from harness.trace import DEVICE_PLANE, OPS_LINE, WINDOW_SPAN, union

PROGRAM = "ratelimiter.stream."
BENCH = "bench."
CALLER_SPAN = "bench.stream_call"
MODULES_LINE = "XLA Modules"
TOP = 10

# Which layer each stage of the caller's thread belongs to: ``assign``
# is the caller waiting for the slot walk (an inline walk nests
# ``index`` inside it); every other name is unexplained.
GROUPS = {
    "index": ("assign", "index"),
    "dispatch": ("elect", "clear", "plan", "route", "pack", "layout",
                 "enqueue"),
    "drain": ("drain_wait",),
}


@dataclasses.dataclass
class Attribution:
    window_s: float
    idle_s: float
    idle_by_span: dict   # name -> idle seconds
    program_spans: int   # ratelimiter.stream.* events on the caller's line

    def shares(self) -> dict:
        """Idle seconds per group in % of the window: ``index``,
        ``dispatch``, ``drain`` and ``unexplained`` (the rest:
        ``ratelimiter.stream.call``, ``bench.*``, ``none``)."""
        out = {g: 0.0 for g in GROUPS}
        out["unexplained"] = 0.0
        stage_group = {PROGRAM + s: g for g, ss in GROUPS.items()
                       for s in ss}
        for name, secs in self.idle_by_span.items():
            out[stage_group.get(name, "unexplained")] += secs
        return {g: 100.0 * s / self.window_s for g, s in out.items()}


def _is_device(line) -> bool:
    return bool(DEVICE_PLANE.match(line.plane))


def _is_ops(line) -> bool:
    return _is_device(line) and bool(OPS_LINE.match(line.name))


def _window(lines) -> tuple:
    for ln in lines:
        if _is_ops(ln):
            continue
        for n, s, d in ln.events:
            if n == WINDOW_SPAN:
                return s, s + d
    raise ValueError(f"no {WINDOW_SPAN} span in the trace")


def _first_busy(lines, w0: float, w1: float) -> list:
    per_device = {}
    for ln in lines:
        if not _is_ops(ln):
            continue
        for _, s, d in ln.events:
            s0, e0 = max(s, w0), min(s + d, w1)
            if e0 > s0:
                per_device.setdefault(ln.plane, []).append((s0, e0))
    if not per_device:
        raise ValueError("no device op ran inside the traced window")
    return union(per_device[sorted(per_device)[0]])


def caller_line(lines):
    """The host line holding the most ``bench.stream_call`` events."""
    best, most = None, 0
    for ln in lines:
        if _is_ops(ln):
            continue
        k = sum(1 for n, _, _ in ln.events if n == CALLER_SPAN)
        if k > most:
            best, most = ln, k
    if best is None:
        raise ValueError(f"no {CALLER_SPAN} span in the trace")
    return best


def leaf_timeline(events, w0: float, w1: float) -> list:
    """``[(t0, t1, name), ...]`` covering ``[w0, w1)`` in order: at each
    instant the innermost open ``ratelimiter.stream.*`` span, else the
    innermost ``bench.*`` span, else ``none``.  Spans of one thread
    nest; the stack follows starts and ends."""
    spans = sorted(((s, s + d, n) for n, s, d in events
                    if n.startswith(PROGRAM) or n.startswith(BENCH)),
                   key=lambda x: (x[0], -x[1]))
    out = []
    stack = []  # (end, name), innermost last
    cur = w0

    def label() -> str:
        for _, n in reversed(stack):
            if n.startswith(PROGRAM):
                return n
        return stack[-1][1] if stack else "none"

    def emit(upto: float) -> None:
        nonlocal cur
        upto = min(upto, w1)
        if upto > cur:
            out.append((cur, upto, label()))
            cur = upto

    for s, e, n in spans:
        while stack and stack[-1][0] <= s:
            emit(stack[-1][0])
            stack.pop()
        emit(s)
        stack.append((e, n))
    while stack:
        emit(stack[-1][0])
        stack.pop()
    emit(w1)
    return out


def idle_by_span(lines) -> Attribution:
    w0, w1 = _window(lines)
    busy = _first_busy(lines, w0, w1)
    edges = [w0] + [x for s, e in busy for x in (s, e)] + [w1]
    idle = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    caller = caller_line(lines)
    leaves = leaf_timeline(caller.events, w0, w1)
    by = {}
    i = 0
    for g0, g1 in idle:
        while i < len(leaves) and leaves[i][1] <= g0:
            i += 1
        j = i
        while j < len(leaves) and leaves[j][0] < g1:
            t0, t1, name = leaves[j]
            ov = min(g1, t1) - max(g0, t0)
            if ov > 0:
                by[name] = by.get(name, 0.0) + ov / 1e9
            j += 1
    return Attribution(
        window_s=(w1 - w0) / 1e9,
        idle_s=sum(g1 - g0 for g0, g1 in idle) / 1e9,
        idle_by_span=dict(sorted(by.items(), key=lambda kv: -kv[1])),
        program_spans=sum(1 for n, _, _ in caller.events
                          if n.startswith(PROGRAM)))


def module_name(event_name: str) -> str:
    """An ``XLA Modules`` event without its trailing program id."""
    return re.sub(r"\(\d+\)$", "", event_name)


def device_steps(lines) -> list:
    """``[[step, seconds], ...]``, the ``TOP`` steps with most device
    time in the window; op time outside any module is ``none``."""
    w0, w1 = _window(lines)
    planes = {}
    for ln in lines:
        if _is_device(ln):
            planes.setdefault(ln.plane, []).append(ln)
    step_ns = {}
    ran = 0
    for lines_of in planes.values():
        mods = sorted((s, s + d, module_name(n)) for ln in lines_of
                      if ln.name == MODULES_LINE for n, s, d in ln.events)
        starts = [m[0] for m in mods]
        busy = False
        for ln in lines_of:
            if not OPS_LINE.match(ln.name):
                continue
            for _, s, d in ln.events:
                s0, e0 = max(s, w0), min(s + d, w1)
                if e0 <= s0:
                    continue
                busy = True
                k = bisect.bisect_right(starts, s) - 1
                step = (mods[k][2] if k >= 0 and s < mods[k][1]
                        else "none")
                step_ns[step] = step_ns.get(step, 0.0) + (e0 - s0)
        ran += busy
    if not ran:
        raise ValueError("no device op ran inside the traced window")
    top = sorted(step_ns.items(), key=lambda kv: -kv[1])[:TOP]
    return [[n, t / ran / 1e9] for n, t in top]
