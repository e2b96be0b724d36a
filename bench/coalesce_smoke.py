"""Zipf key-coalescing smoke bench (the v5 ingest perf gate).

Repeat-heavy Zipf traffic with per-key-uniform weights is the wire-speed
ingestion shape (ISSUE 18): the coalesced digest folds every within-chunk
repeat into ONE weighted decision per unique key, so device work scales
with uniques instead of requests.  This bench A/Bs the SAME stream with
``RATELIMITER_COALESCE`` on and off (fresh storage each arm, identical
clocks) and checks both claims:

- **perf**: coalesced decisions/s >= 1.0x the uncoalesced path on the
  Zipf chunk (best-of-2 per arm — the digest must never lose to the
  rank-major scan it replaces on the traffic it exists for);
- **exactness**: ZERO mismatches against the sequential oracle replay
  (``semantics/oracle.py``) — coalescing is an encoding, not a policy.

``--assert-ratio`` turns both checks into hard gates (run by verify.sh).
Emits one JSON line; bench.py records it as ``coalesce_smoke``.
Run with cwd=repo root:  python bench/coalesce_smoke.py
Env: BENCH_SCALE=small shrinks the stream (CI).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

N_KEYS = 2000          # distinct keys under the Zipf
ZIPF_A = 1.1


def run_arm(coalesce: bool, ids, perms, reps: int) -> dict:
    """One arm: fresh storage, fixed clock schedule, timed stream."""
    import numpy as np

    import ratelimiter_tpu.storage.tpu as tpu_mod
    from ratelimiter_tpu.core.config import RateLimitConfig
    from ratelimiter_tpu.storage.tpu import TpuBatchedStorage

    tpu_mod._COALESCE = coalesce
    now = [1_753_000_000_000]
    st = TpuBatchedStorage(num_slots=1 << 13, clock_ms=lambda: now[0])
    cfg = RateLimitConfig(max_permits=40, window_ms=1000, refill_rate=25.0)
    lid = st.register_limiter("tb", cfg)
    # Warm on a SEPARATE limiter: keyspaces are per-lid, so compiles
    # fire without mutating the state the oracle replays from scratch.
    lid_warm = st.register_limiter("tb", cfg)
    try:
        st.acquire_stream_ids("tb", lid_warm, ids[:4096], perms[:4096])
        outs = []
        t0 = time.perf_counter()
        for _ in range(reps):
            outs.append(np.asarray(
                st.acquire_stream_ids("tb", lid, ids, perms)))
            now[0] += 500
        wall = time.perf_counter() - t0
    finally:
        st.close()
    n = reps * len(ids)
    return {
        "coalesce": coalesce,
        "decisions": n,
        "wall_s": round(wall, 4),
        "decisions_per_sec": round(n / wall, 1),
        "outs": outs,
    }


def oracle_replay(ids, perms, reps: int, got_per_rep) -> int:
    """Sequential per-request replay; returns the mismatch count."""
    from ratelimiter_tpu.core.config import RateLimitConfig
    from ratelimiter_tpu.semantics import TokenBucketOracle

    cfg = RateLimitConfig(max_permits=40, window_ms=1000, refill_rate=25.0)
    oracle = TokenBucketOracle(cfg)
    now = 1_753_000_000_000
    bad = 0
    for rep in range(reps):
        got = got_per_rep[rep]
        for j, k in enumerate(ids):
            want = oracle.try_acquire(f"id:{k}", int(perms[j]),
                                      now).allowed
            bad += int(bool(got[j]) != want)
        now += 500
    return bad


def main() -> None:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    parser = argparse.ArgumentParser()
    parser.add_argument("--assert-ratio", action="store_true",
                        help="gate coalesced >= 1.0x uncoalesced AND zero "
                             "oracle mismatches")
    args = parser.parse_args()

    import numpy as np

    from ratelimiter_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    small = os.environ.get("BENCH_SCALE", "small") == "small"
    n = 1 << 15 if small else 1 << 18
    reps = 2 if small else 4
    oracle_reps = reps if small else 1

    rng = np.random.default_rng(18)
    ids = (rng.zipf(ZIPF_A, n) % N_KEYS).astype(np.int64)
    # Per-key-deterministic weight: every repeat carries the same
    # permits, so every chunk takes the coalesced digest.
    perms = (ids % 4 + 1).astype(np.int64)

    # Best-of-2 per arm; the uncoalesced arm runs first so its compiles
    # never land inside the coalesced arm's timing.
    off = max((run_arm(False, ids, perms, reps) for _ in range(2)),
              key=lambda r: r["decisions_per_sec"])
    on = max((run_arm(True, ids, perms, reps) for _ in range(2)),
             key=lambda r: r["decisions_per_sec"])

    # Bit-identity: the two arms must agree on every request of every
    # rep, and the coalesced arm must agree with the sequential oracle.
    for rep in range(reps):
        np.testing.assert_array_equal(on["outs"][rep], off["outs"][rep])
    mismatches = oracle_replay(ids, perms, oracle_reps, on["outs"])

    ratio = on["decisions_per_sec"] / max(off["decisions_per_sec"], 1.0)
    out = {
        "bench": "coalesce_smoke",
        "note": ("CPU in-process: coalesced digest vs rank-major scan on "
                 f"Zipf({ZIPF_A}) traffic with per-key-uniform weights"),
        "n_per_rep": n,
        "reps": reps,
        "zipf_a": ZIPF_A,
        "n_keys": N_KEYS,
        "coalesced_decisions_per_sec": on["decisions_per_sec"],
        "uncoalesced_decisions_per_sec": off["decisions_per_sec"],
        "coalesce_ratio": round(ratio, 3),
        "oracle_requests_checked": oracle_reps * n,
        "oracle_mismatches": mismatches,
    }
    print(json.dumps(out))
    if args.assert_ratio:
        assert mismatches == 0, (
            f"{mismatches} coalesced decisions diverged from the "
            "sequential oracle replay")
        assert ratio >= 1.0, (
            f"coalesced stream fell to {ratio:.2f}x of the uncoalesced "
            f"path ({on['decisions_per_sec']:.0f}/s vs "
            f"{off['decisions_per_sec']:.0f}/s) on Zipf traffic — the "
            "1.0x floor failed")


if __name__ == "__main__":
    main()
