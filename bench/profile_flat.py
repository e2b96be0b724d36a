"""Measure the flat mega-batch step on the real device: XLA scatter vs the
Pallas block-scatter, TB and SW, at the bench stream shape (4M requests,
1M slots, Zipf keys).

Run from /root/repo:  python bench/profile_flat.py [--small] [--noblock]
"""

from __future__ import annotations

import os
import sys
import time

if "--noblock" in sys.argv:
    os.environ["RATELIMITER_BLOCK_SCATTER"] = "0"

import jax
import numpy as np

S = 1 << 20
B = 1 << 22
if "--small" in sys.argv:
    S, B = 1 << 14, 1 << 16

sys.path.insert(0, "/root/repo")
from ratelimiter_tpu.core.config import RateLimitConfig  # noqa: E402
from ratelimiter_tpu.engine.engine import DeviceEngine  # noqa: E402
from ratelimiter_tpu.engine.state import LimiterTable  # noqa: E402
from ratelimiter_tpu.ops.pallas import block_scatter  # noqa: E402


def run(engine, algo, slots, lids, permits, now0):
    fn = (engine.sw_flat_dispatch if algo == "sw"
          else engine.tb_flat_dispatch)
    t0 = time.perf_counter()
    np.asarray(fn(slots, lids, permits, now0))
    print(f"  {algo} compile+run: {time.perf_counter() - t0:.1f}s", flush=True)
    times = []
    for i in range(4):
        t0 = time.perf_counter()
        np.asarray(fn(slots, lids, permits, now0 + 1 + i))
        times.append(time.perf_counter() - t0)
    ms = min(times) * 1000
    print(f"  {algo} flat B={len(slots)}: {ms:.1f} ms -> "
          f"{len(slots)/min(times)/1e6:.1f}M dec/s "
          f"(all: {[f'{t*1000:.0f}' for t in times]})", flush=True)


def main():
    print(f"platform={jax.devices()[0].platform} S={S} B={B} "
          f"block_scatter_flag={block_scatter._FLAG}", flush=True)
    rng = np.random.default_rng(0)
    table = LimiterTable()
    lid_sw = table.register(RateLimitConfig(max_permits=100, window_ms=60_000))
    lid_tb = table.register(RateLimitConfig(max_permits=50, window_ms=5000,
                                            refill_rate=10.0))
    engine = DeviceEngine(S, table)
    print("block_scatter enabled:",
          block_scatter.enabled((S, 4), B), flush=True)

    slots = (rng.zipf(1.1, size=B).astype(np.int64) % S).astype(np.int32)
    run(engine, "tb", slots, lid_tb, None, 1_000_000)
    run(engine, "sw", slots, lid_sw, None, 1_000_000)
    permits = rng.integers(1, 100, B).astype(np.int32)
    run(engine, "tb", slots, lid_tb, permits, 2_000_000)


if __name__ == "__main__":
    main()
