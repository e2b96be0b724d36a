"""Run one benchmark cell once, on the chip, and print one JSON line.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is looked up in ``BENCHMARK.json``; its configuration, traffic
mix and per-layer readers are files of their own under ``benchmark/``
(``harness/spec.py``).  The run refuses any device but a TPU, keeps
JAX's compile cache where the program's rule puts it, runs the program
with its defaults, and checks the window's decisions against the frozen
reference.  ``--trace 0`` reports the cell's end-to-end metrics;
``--trace 1`` reports its per-layer metrics, read from the program's
timers and a profiler trace of part of the window.  The last stdout
line is the result; the compared numbers and their limits are also the
last lines of stderr.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

_T_IMPORT = time.monotonic()
BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
for _path in (ROOT, BENCH):
    if _path not in sys.path:
        sys.path.insert(0, _path)

from harness import device, spec  # noqa: E402


def run_cell(cell, seed: int, seconds: float, trace: bool, devices,
             process_start: float, **driver_kw):
    driver = importlib.import_module("harness." + cell.traffic["driver"])
    return driver.run(cell, seed, seconds, trace, devices, process_start,
                      **driver_kw)


def result_line(cell, outcome, trace: bool, peaks: dict) -> dict:
    metrics = {}
    if not trace:
        for m in cell.end_to_end:
            if m["name"] not in outcome.end_to_end:
                raise spec.SpecError(f"driver {cell.traffic['driver']!r} "
                                     f"does not measure {m['name']!r}")
            metrics[m["name"]] = {"value": outcome.end_to_end[m["name"]],
                                  "unit": m["unit"]}
    else:
        outcome.readings.peaks = peaks
        for m in cell.per_layer:
            value = spec.load_reader(m["name"])(outcome.readings)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    line = {"correct": bool(outcome.correct), "attempted": outcome.attempted,
            "failed": outcome.failed, "metrics": metrics,
            "device": outcome.device}
    if trace and outcome.breakdown:
        line["breakdown"] = outcome.breakdown
    line["info"] = outcome.info
    line["checks"] = outcome.checks
    return line


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    process_start = time.monotonic() - max(device.process_age_s(),
                                           time.monotonic() - _T_IMPORT)
    cell = spec.load_cell(args.workload)
    try:
        devices = device.require_tpu(cell.chips)
    except device.NoAccelerator as exc:
        print(f"refused: {exc}", file=sys.stderr)
        return 2
    peaks = spec.peaks_for(devices[0].device_kind)
    device.enable_compile_cache()
    outcome = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                       devices, process_start)
    line = result_line(cell, outcome, bool(args.trace), peaks)
    for name, c in outcome.checks.items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
