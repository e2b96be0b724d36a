"""Round-5 stream profiling: where do headline / scenario-3 passes spend?

Mirrors bench.py's scenario 2 (TB 1M Zipf) and scenario 3 (SW 10M
uniform) shapes, runs the warmup/plan-settling discipline, then prints
per-chunk stream_stats records with the dispatch sub-phase timer
(dispatch_s) so host_s stops being a mystery number.

Usage:  python bench/profile_stream_r5.py [headline|sc3|both] [reps]
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


def main() -> None:
    which = sys.argv[1] if len(sys.argv) > 1 else "both"
    reps = int(sys.argv[2]) if len(sys.argv) > 2 else 2

    from ratelimiter_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    from ratelimiter_tpu import RateLimitConfig
    from ratelimiter_tpu.algorithms import (
        SlidingWindowRateLimiter,
        TokenBucketRateLimiter,
    )
    from ratelimiter_tpu.bench.harness import uniform_stream, zipf_stream
    from ratelimiter_tpu.metrics import MeterRegistry
    from ratelimiter_tpu.ops.pallas.block_scatter import align_slots
    from ratelimiter_tpu.storage import TpuBatchedStorage
    from ratelimiter_tpu.utils.link import measure_link

    up_bps, rtt_s, down_bps = measure_link()
    print(f"link: up {up_bps / (1 << 20):.1f} MB/s rtt {rtt_s * 1e3:.0f} ms "
          f"down {down_bps / (1 << 20):.1f} MB/s", flush=True)

    rng = np.random.default_rng(42)
    B, K = 1 << 19, 8
    n = B * K * 4  # 16.7M, bench parity

    def run(name, storage, limiter, key_ids, permits=None):
        nn = len(key_ids)
        storage.set_link_profile(up_bps, rtt_s, down_bps)
        print(f"== {name}: warmup ==", flush=True)
        for i in range(4):
            t0 = time.perf_counter()
            limiter.try_acquire_stream_ids(key_ids, permits, batch=B,
                                           subbatches=K)
            print(f"  warm {i}: {time.perf_counter() - t0:.3f} s "
                  f"plans={storage._chunk_plans}", flush=True)
        for r in range(reps):
            storage.stream_stats = stats = []
            t0 = time.perf_counter()
            limiter.try_acquire_stream_ids(key_ids, permits, batch=B,
                                           subbatches=K)
            wall = time.perf_counter() - t0
            storage.stream_stats = None
            print(f"-- {name} pass {r}: wall {wall:.3f} s "
                  f"({nn / wall / 1e6:.2f} M/s)", flush=True)
            for rec in stats:
                print("   " + json.dumps(rec), flush=True)

    if which in ("headline", "both"):
        storage = TpuBatchedStorage(num_slots=align_slots(2_000_000))
        tb = TokenBucketRateLimiter(
            storage,
            RateLimitConfig(max_permits=100, window_ms=60_000,
                            refill_rate=50.0),
            MeterRegistry())
        run("headline", storage, tb, zipf_stream(rng, 1_000_000, n))
        storage.close()

    if which in ("burst",):
        storage = TpuBatchedStorage(num_slots=align_slots(2_000_000))
        tb = TokenBucketRateLimiter(
            storage,
            RateLimitConfig(max_permits=100, window_ms=60_000,
                            refill_rate=100.0),
            MeterRegistry())
        n5 = B * K * 3
        perms = rng.integers(1, 101, size=n5).astype(np.int64)
        run("burst", storage, tb,
            uniform_stream(rng, 1_000_000, n5), perms)
        storage.close()

    if which in ("strs",):
        storage = TpuBatchedStorage(num_slots=align_slots(2_000_000))
        tb = TokenBucketRateLimiter(
            storage,
            RateLimitConfig(max_permits=100, window_ms=60_000,
                            refill_rate=50.0),
            MeterRegistry())
        storage.set_link_profile(up_bps, rtt_s, down_bps)
        ids = zipf_stream(rng, 1_000_000, 2_000_000)
        keys = [f"k{i}" for i in ids]
        tb.try_acquire_many(keys, None)  # warm shapes
        for i in range(3):
            storage.stream_stats = stats = []
            t0 = time.perf_counter()
            tb.try_acquire_many(keys, None)
            wall = time.perf_counter() - t0
            storage.stream_stats = None
            print(f"  strs pass {i}: {len(keys) / wall / 1e6:.2f} M/s "
                  f"(wall {wall:.3f} s)", flush=True)
            for rec in stats:
                print("   " + json.dumps(rec), flush=True)
        storage.close()

    if which in ("sc3", "both"):
        storage = TpuBatchedStorage(num_slots=align_slots(12_500_000))
        sw = SlidingWindowRateLimiter(
            storage,
            RateLimitConfig(max_permits=100, window_ms=60_000,
                            enable_local_cache=False),
            MeterRegistry())
        run("sc3", storage, sw, uniform_stream(rng, 10_000_000, n))
        storage.close()


if __name__ == "__main__":
    main()
