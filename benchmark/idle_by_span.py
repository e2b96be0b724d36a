"""Where the device waits, by program span: run one stream cell with
its traced window as ``run.py --trace 1`` does, keep the trace, and
print one JSON line: run.py's result line under ``result`` and, under
``attribution``, what ``harness/attribution.py`` reads from the trace.

    python benchmark/idle_by_span.py --workload <cell> --seed <n> \
        --seconds <s>

``attribution`` holds the window's idle seconds charged to the
innermost span on the caller's thread (``idle_by_span``), those in %
of the window grouped by layer (``shares``; the four add up to
``device_idle_share``), device seconds per jitted step
(``device_steps``), ``chunks_per_call`` (the
``ratelimiter.stream.assign`` timer's count over the window's calls)
and the decisions/s of the window's calls made with the profiler on
and after it stopped (``traced_decisions_per_s``,
``untraced_decisions_per_s``).  Like run.py it refuses any device but
a TPU.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time

import run  # puts the benchmark and the program on sys.path
from harness import attribution, device, spec, stream, trace


class _KeepingProfiler(device.Profiler):
    """The benchmark's profiler, which also keeps the trace's lines and
    when it ran."""

    lines = None
    on = off = None

    def start(self) -> None:
        super().start()
        _KeepingProfiler.on = time.perf_counter()

    def stop(self) -> None:
        _KeepingProfiler.off = time.perf_counter()
        super().stop()

    def reduce(self):
        files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if files:
            _KeepingProfiler.lines = trace.load(files[0])
        return super().reduce()


def _rate(calls) -> float | None:
    if not calls:
        return None
    return sum(n for _, _, n in calls) / (calls[-1][1] - calls[0][0])


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    args = parser.parse_args(argv)
    process_start = time.monotonic() - max(
        device.process_age_s(), time.monotonic() - run._T_IMPORT)
    cell = spec.load_cell(args.workload)
    if cell.traffic["driver"] != "stream":
        print(f"refused: {args.workload} is not a stream cell",
              file=sys.stderr)
        return 2
    try:
        devices = device.require_tpu(cell.chips)
    except device.NoAccelerator as exc:
        print(f"refused: {exc}", file=sys.stderr)
        return 2
    peaks = spec.peaks_for(devices[0].device_kind)
    device.enable_compile_cache()
    calls = []  # (start, end, ids) of every call, in order

    def timed_build(config, clock):
        storage, limiter = stream.build_limiter(config, clock)
        inner = limiter.try_acquire_stream_ids

        def timed(key_ids, *a, **kw):
            t0 = time.perf_counter()
            got = inner(key_ids, *a, **kw)
            calls.append((t0, time.perf_counter(), len(key_ids)))
            return got

        limiter.try_acquire_stream_ids = timed
        return storage, limiter

    device.Profiler = _KeepingProfiler
    outcome = run.run_cell(cell, args.seed, args.seconds, True, devices,
                           process_start, build=timed_build)
    line = run.result_line(cell, outcome, True, peaks)
    lines = _KeepingProfiler.lines
    att = attribution.idle_by_span(lines)
    shares = att.shares()
    assign = outcome.readings.timer("ratelimiter.stream.assign")
    on, off = _KeepingProfiler.on, _KeepingProfiler.off
    out = {
        "idle_by_span": att.idle_by_span,
        "shares": shares,
        "device_idle_share": 100.0 * att.idle_s / att.window_s,
        "idle_unexplained_share": shares["unexplained"],
        "program_spans": att.program_spans,
        "device_steps": attribution.device_steps(lines),
        "chunks_per_call": (assign.count / outcome.info["window_calls"]
                            if assign else None),
        "traced_decisions_per_s": _rate(
            [c for c in calls if c[0] >= on and c[1] <= off]),
        "untraced_decisions_per_s": _rate(
            [c for c in calls if c[0] >= off]),
    }
    print(json.dumps({"result": line, "attribution": out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
