"""Latency-SLO scenario with LOCAL device attachment and REALISTIC load
(VERDICT r3 #6): 16 threads over many distinct keys with the negative
cache disabled, so essentially every request misses host-side state and
crosses the device boundary through the micro-batcher.

The <=1 ms p99 target (BASELINE.md) is a local-attachment claim.  This
drives the full batcher round trip per request: submit ->
size-or-deadline flush -> device step -> future.  bench.py starts it
with JAX_PLATFORMS=cpu (its parent holds the chip) and records the
output as latency_slo_local; verify.sh runs it the same way.

Run from the repo root (subprocess of bench.py).
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(assert_meets: bool = False) -> int:
    # Latency runs want prompt GIL handoff between submitters, flusher
    # and drain (default 5 ms slices add multi-ms scheduling tails).
    sys.setswitchinterval(0.001)

    from ratelimiter_tpu import RateLimitConfig
    from ratelimiter_tpu.algorithms import SlidingWindowRateLimiter
    from ratelimiter_tpu.bench.harness import bench_threaded
    from ratelimiter_tpu.metrics import MeterRegistry
    from ratelimiter_tpu.storage import TpuBatchedStorage

    # Cache OFF: every decision must cross the device boundary — the
    # worst-case shape for the 1 ms target (cache hits would be ~100 ns).
    sw_cfg = RateLimitConfig(max_permits=1_000_000, window_ms=60_000,
                             enable_local_cache=False)
    storage = TpuBatchedStorage(num_slots=1 << 14, max_delay_ms=0.3)
    limiter = SlidingWindowRateLimiter(storage, sw_cfg, MeterRegistry())

    # Pre-compile the dedicated small-shape step (r6: micro-batches
    # bucket at the 32-lane floor instead of padding to 256), then warm
    # every batch shape the 16-thread run can produce (the batcher
    # buckets lane counts, so a handful of sizes covers them).
    storage.warm_micro_shapes()
    for i in range(200):
        limiter.try_acquire(f"warm-{i % 64}")

    # Decomposition probes (sequential, untimed threads):
    # (a) one synchronous acquire = flush deadline + one device step,
    # (b) one direct engine dispatch+drain at a 16-lane shape = the
    #     device step alone.
    t0 = time.perf_counter()
    for i in range(50):
        limiter.try_acquire(f"probe-a-{i}")
    acquire_ms = (time.perf_counter() - t0) / 50 * 1000
    import numpy as np

    eng = storage.engine
    slots = list(range(16))
    lids = [0] * 16
    perms = [1] * 16
    h = eng.sw_acquire_dispatch(slots, lids, perms, 1_000_000)
    eng.sw_acquire_drain(h, 16)
    t0 = time.perf_counter()
    for i in range(50):
        h = eng.sw_acquire_dispatch(slots, lids, perms, 1_000_000 + i)
        eng.sw_acquire_drain(h, 16)
    step_ms = (time.perf_counter() - t0) / 50 * 1000

    # The closed-loop generator SHARES the host with the serving stack:
    # on a many-core box 16 threads is the realistic interactive load,
    # but on a 1-2 core CI container that many spinning submitters
    # saturate the core and the bench degenerates into a capacity
    # measurement (every request queues behind 15 others) instead of
    # the latency SLO it exists to check.  Scale the offered concurrency
    # to the hardware: 2x cores, clamped to [2, 16].
    try:
        cores = len(os.sched_getaffinity(0))
    except (AttributeError, OSError):  # pragma: no cover - non-linux
        cores = os.cpu_count() or 1
    n_threads = max(2, min(16, 2 * cores))
    keys_per = 256  # n_threads*256 distinct keys; each request a new one
    res = bench_threaded(
        limiter,
        keys_per_thread=lambda t: [f"slo-u{t}-{i}" for i in range(keys_per)],
        n_threads=n_threads,
        requests_per_thread=4_000,
    )
    lat = res["request_latency"]
    res["device"] = "cpu-in-process"
    res["target_p99_ms"] = 1.0
    res["meets_target"] = bool(lat["p99_us"] < 1000.0)
    # Per-stage decomposition from the request-lifecycle histograms
    # (observability/trace.py): where each request's milliseconds went —
    # queue wait / batch assembly / device step / resolve.  ROADMAP
    # item 3's gate reads queue_wait from exactly this surface.
    stages = {}
    scrape = storage.registry.scrape()
    for name in ("queue_wait", "assembly", "device", "resolve", "total"):
        snap = scrape.get(f"ratelimiter.latency.{name}")
        if snap and snap["count"]:
            stages[name] = {
                "p50_ms": round(snap["p50_us"] / 1000.0, 3),
                "p99_ms": round(snap["p99_us"] / 1000.0, 3),
                "mean_ms": round(snap["mean_us"] / 1000.0, 3),
                "count": int(snap["count"]),
            }
    print("per-stage decomposition (p50 / p99 ms):", file=sys.stderr)
    for name, row in stages.items():
        print(f"  {name:<10} {row['p50_ms']:>8.3f} {row['p99_ms']:>8.3f}",
              file=sys.stderr)
    res["decomposition"] = {
        "stages": stages,
        "batcher_max_delay_ms": 0.3,
        "single_acquire_ms": round(acquire_ms, 3),
        "device_step_16_lanes_ms": round(step_ms, 3),
        "note": ("multi-key, cache-off: every request rides a device "
                 "micro-batch; p99 ~= flush deadline + one device step + "
                 "queue depth under 16-thread load.  The step time here "
                 "is the CPU backend's dispatch+execute+fetch for a "
                 "16-lane micro-batch — the floor the 1 ms target is "
                 "judged against in this environment; a local-attached "
                 "TPU swaps it for its own dispatch + ~10-30 us PCIe "
                 "round trip."),
    }
    storage.close()
    print(json.dumps(res))
    if assert_meets:
        # CI gate (verify.sh): the 1 ms p99 target must hold on CPU, and
        # the decomposition must show assembly is no longer the dominant
        # stage (the r11 double-buffer/staged-dispatch claim).
        if not res["meets_target"]:
            print(f"FAIL: p99 {lat['p99_us']:.0f} us > 1000 us target",
                  file=sys.stderr)
            return 1
        # "No longer dominant": pre-r11 assembly sat at 0.88-1.02 ms
        # p50, ~3x every other stage.  Post-fix it runs at parity with
        # queue wait (~0.1 ms), so a hair's win either way is noise —
        # fail only if assembly CLEARLY dominates again (>1.25x the
        # largest other stage) or regresses toward the old absolute
        # level (>0.45 ms p50, half the pre-fix figure).
        asm = stages.get("assembly", {}).get("p50_ms", 0.0)
        others = max((stages[s]["p50_ms"] for s in stages
                      if s not in ("total", "assembly")), default=0.0)
        if asm > max(1.25 * others, 0.2) or asm > 0.45:
            print(f"FAIL: assembly is again the dominant stage "
                  f"({asm} ms p50 vs {others} ms largest other)",
                  file=sys.stderr)
            return 1
        print(f"ok: p99 {lat['p99_us']:.0f} us <= 1000 us; assembly "
              f"p50 {asm} ms (largest other stage {others} ms)",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--assert-meets", action="store_true",
                    help="exit nonzero unless p99 <= 1 ms on CPU and "
                         "assembly is not the dominant stage")
    args = ap.parse_args()
    sys.exit(main(assert_meets=args.assert_meets))
