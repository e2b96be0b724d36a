"""The control: the reference computed in int32, one precision below the
i64 the configurations state, put in the program's place.  It decides
every call of a run (the same prefill, warm-up and window calls, from
the same seed) in closed form with int32 state, and the run's own check
holds those decisions against the i64 reference.

At the cells' own size, on the chip's host (``--calls``: the window's
calls, as many as a run makes):

    python3 benchmark/tests/control.py --workload <cell> --seeds 1,2,3 \
        --calls N
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for _p in (os.path.dirname(BENCH), BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from harness import stream  # noqa: E402
from harness.check_stream import StreamCheck  # noqa: E402
from reference.config import RateLimitConfig  # noqa: E402
from reference.groups import groups_for  # noqa: E402


def closed_form_decisions(groups, ids, now_ms):
    keys, inverse, counts = np.unique(ids, return_inverse=True,
                                      return_counts=True)
    allowed = groups.apply(keys, counts, now_ms)
    order = np.argsort(inverse, kind="stable")
    rank = np.empty(len(ids), np.int64)
    starts = np.cumsum(counts) - counts
    rank[order] = np.arange(len(ids)) - np.repeat(starts, counts)
    return rank < allowed[inverse]


def stream_control(cell, seed: int, window_calls: int) -> dict:
    config, traffic = cell.config, cell.traffic
    n_calls = window_calls + traffic["warmup_calls"]
    store = stream.call_store(seed, config, traffic, n_calls)
    control = groups_for(config["algorithm"],
                         RateLimitConfig(**config["policy"]),
                         config["keys"]["count"], np.int32)
    check = StreamCheck(config, seed)
    todo = stream.plan(traffic, n_calls)

    def calls():
        for _ in range(n_calls + (1 if traffic.get("prefill") else 0)):
            key, now = next(todo)
            yield (store[key], now,
                   closed_form_decisions(control, store[key], now))

    check.replay(calls())
    check.close()
    return check.checks()


def main(argv=None) -> int:
    from harness import spec

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--calls", type=int, default=150)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.monotonic()
        checks = stream_control(cell, seed, args.calls)
        print(json.dumps({"control": args.workload, "seed": seed,
                          "checks": checks,
                          "seconds": time.monotonic() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
