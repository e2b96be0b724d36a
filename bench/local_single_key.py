"""Scenario 1 with LOCAL device attachment: the reference's regime.

The reference's published 80,192 req/s (README.md single-key sliding
window, cache on) lives in a regime where the storage round trip
(~0.8 ms Redis RTT) is far below the 100 ms local-cache TTL.  bench.py
starts this script with JAX_PLATFORMS=cpu (its parent holds the chip)
and records the output as sw_single_key_threaded_local: the same
limiter + micro-batcher code on an in-process CPU device.

Run from the repo root (subprocess of bench.py).
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> None:
    from ratelimiter_tpu import RateLimitConfig
    from ratelimiter_tpu.algorithms import SlidingWindowRateLimiter
    from ratelimiter_tpu.bench.harness import bench_threaded
    from ratelimiter_tpu.metrics import MeterRegistry
    from ratelimiter_tpu.storage import TpuBatchedStorage

    sw_cfg = RateLimitConfig(max_permits=100, window_ms=60_000,
                             enable_local_cache=True, local_cache_ttl_ms=100)
    storage = TpuBatchedStorage(num_slots=1 << 12, max_delay_ms=0.3)
    limiter = SlidingWindowRateLimiter(storage, sw_cfg, MeterRegistry())

    # Warm the batcher's compile shapes + the cache path untimed.
    for _ in range(50):
        limiter.try_acquire("hot-key")

    t0 = time.perf_counter()
    for _ in range(3):
        limiter.try_acquire("rtt-probe-key")
    rtt_ms = (time.perf_counter() - t0) / 3 * 1000

    res = bench_threaded(
        limiter,
        keys_per_thread=lambda t: ["hot-key"],
        n_threads=10,
        requests_per_thread=10_000,
    )
    res["device_round_trip_ms"] = round(rtt_ms, 2)
    res["device"] = "cpu-in-process"
    res["note"] = ("same limiter/batcher code as sw_single_key_threaded, "
                   "zero-RTT attachment: the regime where the local cache "
                   "TTL (100 ms) >> storage round trip, as the reference "
                   "operates (BASELINE.md 80,192 req/s target)")
    storage.close()
    print(json.dumps(res))


if __name__ == "__main__":
    sys.exit(main())
