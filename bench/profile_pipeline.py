"""Profile the link-adaptive chunk-plan election on the live link
(VERDICT r3 #1 development harness — run from the repo root).

Reproduces the headline scenario (1M-key TB Zipf stream) and scenario 5
(weighted burst), printing per-pass phase breakdowns and the elected
plans, with the plan election togglable for A/B:

    python bench/profile_pipeline.py [--no-plan] [--n N_REQUESTS]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--no-plan", action="store_true")
    ap.add_argument("--n", type=int, default=1 << 24)
    ap.add_argument("--scenario", default="zipf",
                    choices=["zipf", "burst", "uniform10m"])
    ap.add_argument("--passes", type=int, default=2)
    args = ap.parse_args()

    from ratelimiter_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    from ratelimiter_tpu import RateLimitConfig
    from ratelimiter_tpu.bench.harness import uniform_stream, zipf_stream
    from ratelimiter_tpu.storage import TpuBatchedStorage

    rng = np.random.default_rng(42)
    if args.scenario == "zipf":
        num_keys, algo = 1_000_000, "tb"
        cfg = RateLimitConfig(max_permits=100, window_ms=60_000,
                              refill_rate=50.0)
        ids = zipf_stream(rng, num_keys, args.n)
        perms = None
        slots = num_keys * 2
    elif args.scenario == "burst":
        num_keys, algo = 1_000_000, "tb"
        cfg = RateLimitConfig(max_permits=100, window_ms=60_000,
                              refill_rate=100.0)
        ids = uniform_stream(rng, num_keys, args.n)
        perms = rng.integers(1, 101, size=args.n).astype(np.int64)
        slots = num_keys * 2
    else:
        num_keys, algo = 10_000_000, "sw"
        cfg = RateLimitConfig(max_permits=100, window_ms=60_000,
                              enable_local_cache=False)
        ids = uniform_stream(rng, num_keys, args.n)
        perms = None
        slots = int(num_keys * 1.25)

    from ratelimiter_tpu.ops.pallas.block_scatter import align_slots

    st = TpuBatchedStorage(num_slots=align_slots(max(slots, 1 << 16)))
    lid = st.register_limiter(algo, cfg)
    if not args.no_plan:
        prof = st.probe_link()
        print(f"link: {prof[0] / 1e6:.1f} MB/s up, "
              f"rtt {prof[1] * 1e3:.1f} ms", flush=True)

    for p in range(args.passes + 2):
        st.stream_stats = stats = []
        t0 = time.perf_counter()
        out = st.acquire_stream_ids(algo, lid, ids, perms)
        wall = time.perf_counter() - t0
        st.stream_stats = None
        agg = {
            "chunks": len(stats),
            "assign_s": round(sum(r.get("assign_s", 0) for r in stats), 3),
            "walk_s": round(max((r.get("walk_s", 0) for r in stats),
                                default=0), 3),
            "host_s": round(sum(r.get("host_s", 0) for r in stats), 3),
            "fetch_s": round(sum(r.get("fetch_s", 0) for r in stats), 3),
            "wire_mb": round(sum(r.get("wire_bytes", 0)
                                 for r in stats) / 1e6, 2),
        }
        print(f"pass {p}: wall {wall:.3f}s  "
              f"{args.n / wall / 1e6:.2f}M/s  {agg}", flush=True)
        print(f"  plans: {st._chunk_plans}", flush=True)
    print(json.dumps({"allowed": int(out.sum())}))
    st.close()


if __name__ == "__main__":
    main()
