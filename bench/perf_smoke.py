"""Fast perf smokes (CPU, small shapes) — CI guards.

ISSUE r6: the virtual-mesh scaling curve silently anti-scaled for two
rounds (19.5M/s at 1 shard -> 4.3M/s at 8 in r05, before PR 1) because nothing
failed when the sharding machinery regressed.  This smoke runs the TB
Zipf stream at EVERY shard count of the virtual mesh (1/2/4/8) and
asserts MONOTONICITY (ISSUE r8): each point must reach at least
``MARGIN`` x the next-smaller point, and 8 shards at least
``MARGIN_END`` x of 1 shard — a scaling inversion anywhere on the
curve fails CI loudly instead of waiting for the next full bench
round.  (The pre-r8 smoke only checked 2 shards, which is exactly why
the 4- and 8-shard inversions lived for two rounds.)

Each point runs in its OWN subprocess (matching bench.py's discipline:
backend state, donated-buffer history, and virtual-device count must
not leak between points), with one full warmup pass and best-of-3
timed passes; the 0.9 margin absorbs CI timer noise — the threshold is
meant to catch structural regressions (a serialized per-shard walk, a
lost pipeline overlap, a reintroduced cross-shard barrier), not 5%
jitter.  The stream is the headline shape scaled down (4M Zipf
decisions over 1M keys: multi-chunk, so the per-shard pipelines
actually overlap).

ISSUE r7 adds a RELAY-ELECTION smoke (interpret-safe, also its own
subprocess): on a CPU backend no Pallas relay path may be elected (the
fused kernel is TPU-or-interpret only), the engine's elected sorted
digest dispatch must not run measurably slower than the raw XLA step
it wraps, and every disk-cached per-path election artifact
(pallas_elect_*.json) must be self-consistent — the recorded verdict
must equal what its own recorded A/B times imply, so an election can
never silently pin a measured-slower backend.

Prints one JSON line; exit code 1 on any violation.  Run from the repo
root (verify.sh invokes it):  python bench/perf_smoke.py
With --point N it runs a single N-shard point; with --relay-election
it runs the election smoke (the subprocess modes).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: Each shard count must reach MARGIN x the next-smaller count.
MARGIN = 0.9
#: ...and the full curve must not sag: 8 shards vs 1 shard.
MARGIN_END = 0.95
POINTS = (1, 2, 4, 8)
# Both child modes measure the CPU backend (a virtual 8-device mesh);
# they are pinned at spawn.
_CPU_CHILD_ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def run_point(n_shards: int) -> None:
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()

    import time

    import jax
    import numpy as np

    sys.path.insert(0, _REPO)
    from ratelimiter_tpu.core.config import RateLimitConfig
    from ratelimiter_tpu.engine.state import LimiterTable
    from ratelimiter_tpu.storage import TpuBatchedStorage
    from ratelimiter_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    cfg = RateLimitConfig(max_permits=100, window_ms=60_000,
                          refill_rate=50.0)
    clock = lambda: 100_000  # noqa: E731 — frozen: identical decisions
    rng = np.random.default_rng(11)
    key_ids = (rng.zipf(1.1, size=1 << 22).astype(np.int64) % 1_000_000)
    num_slots = 1 << 21
    if n_shards == 1:
        storage = TpuBatchedStorage(num_slots=num_slots, clock_ms=clock)
    else:
        from ratelimiter_tpu.parallel import ShardedDeviceEngine
        from ratelimiter_tpu.parallel.mesh import make_mesh

        engine = ShardedDeviceEngine(
            slots_per_shard=num_slots // n_shards,
            table=LimiterTable(),
            mesh=make_mesh(jax.devices()[:n_shards]))
        storage = TpuBatchedStorage(engine=engine, clock_ms=clock)
    lid = storage.register_limiter("tb", cfg)
    storage.acquire_stream_ids("tb", lid, key_ids, None)  # warm shapes
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        storage.acquire_stream_ids("tb", lid, key_ids, None)
        best = min(best, time.perf_counter() - t0)
    storage.close()
    print(json.dumps({"n_shards": n_shards,
                      "decisions_per_sec": len(key_ids) / best}))


def run_relay_election() -> None:
    """Relay-election smoke: elected path never slower than XLA on this
    (CPU) backend, and cached election artifacts self-consistent."""

    import functools
    import glob
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    sys.path.insert(0, _REPO)
    from ratelimiter_tpu.core.config import RateLimitConfig
    from ratelimiter_tpu.engine.engine import DeviceEngine
    from ratelimiter_tpu.engine.state import LimiterTable
    from ratelimiter_tpu.ops import relay
    from ratelimiter_tpu.ops.pallas import election, relay_step
    from ratelimiter_tpu.utils.compile_cache import (
        cache_dir,
        enable_compile_cache,
    )

    enable_compile_cache()
    out = {"smoke": "relay_election"}

    # 1. The fused Pallas path must not be live on a plain CPU backend.
    table = LimiterTable()
    lid = table.register(RateLimitConfig(
        max_permits=20, window_ms=60_000, refill_rate=5.0))
    eng = DeviceEngine(num_slots=1 << 15, table=table)
    fused_live = eng._relay_fused_ok("tb", 1 << 14)
    interpret = relay_step.interpret_mode()
    out["fused_live_on_cpu"] = bool(fused_live)
    out["interpret_override"] = bool(interpret)
    ok_live = interpret or not fused_live

    # 2. The elected dispatch (whatever the engine chose) must not be
    # slower than the raw XLA digest step on identical traffic.  Same
    # computation either way on CPU, so the generous 1.5x margin only
    # catches a structural mistake (e.g. interpret-mode Pallas leaking
    # into a non-test process).
    rb = eng.rank_bits
    u = 1 << 14
    slots = np.arange(u, dtype=np.uint32) * ((1 << 15) // u)
    uw = (slots << np.uint32(rb + 1)) | np.uint32(2)
    raw = jax.jit(functools.partial(
        relay.tb_relay_counts, rank_bits=rb, out_dtype=jnp.uint8))
    state = jnp.array(eng.tb_packed)
    tarr = table.device_arrays

    def best_of(fn, reps=5):
        fn()  # warm/compile
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best

    t_elected = best_of(lambda: np.asarray(eng.tb_relay_counts_dispatch(
        uw, np.int32(lid), 1_000_000, np.uint8, slots_sorted=True)))
    t_xla = best_of(lambda: np.asarray(raw(
        state, tarr, jnp.asarray(uw), jnp.int32(lid),
        jnp.int64(1_000_000))[1]))
    out["elected_s"] = round(t_elected, 6)
    out["xla_s"] = round(t_xla, 6)
    ok_speed = t_elected <= 1.5 * t_xla

    # 3. Cached election artifacts: verdict == what the recorded A/B
    # implies.  (env-off/interpret records carry no timings — skipped.)
    bad_records = []
    base = cache_dir()
    for path in sorted(glob.glob(os.path.join(
            base, "pallas_elect_*.json"))):
        try:
            with open(path, "r", encoding="utf-8") as fh:
                rec = json.load(fh)
        except Exception:  # noqa: BLE001 — corrupt artifact: re-measured
            continue
        if "pallas_s" not in rec or "xla_s" not in rec:
            continue
        margin = float(rec.get("margin", election.DEFAULT_MARGIN))
        implied = rec["pallas_s"] <= margin * rec["xla_s"]
        if bool(rec.get("elected", rec.get("micro_win"))) != implied:
            bad_records.append(os.path.basename(path))
    out["election_artifacts_checked"] = len(
        glob.glob(os.path.join(base, "pallas_elect_*.json")))
    out["inconsistent_artifacts"] = bad_records
    out["ok"] = bool(ok_live and ok_speed and not bad_records)
    print(json.dumps(out))
    if not out["ok"]:
        print(f"RELAY ELECTION SMOKE FAILED: live_ok={ok_live} "
              f"speed_ok={ok_speed} bad={bad_records}", file=sys.stderr)
        sys.exit(1)


def main() -> int:
    if "--point" in sys.argv:
        run_point(int(sys.argv[sys.argv.index("--point") + 1]))
        return 0
    if "--relay-election" in sys.argv:
        run_relay_election()
        return 0
    dps = {}
    for s in POINTS:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--point", str(s)],
            capture_output=True, timeout=540, text=True, cwd=_REPO,
            env=_CPU_CHILD_ENV)
        if proc.returncode != 0 or not proc.stdout.strip():
            print(f"PERF SMOKE FAILED: point {s} rc={proc.returncode} "
                  f"stderr={proc.stderr[-400:]!r}", file=sys.stderr)
            return 1
        dps[s] = json.loads(proc.stdout.strip().splitlines()[-1])[
            "decisions_per_sec"]
    ratios = {f"{b}v{a}": dps[b] / dps[a]
              for a, b in zip(POINTS, POINTS[1:])}
    end_ratio = dps[POINTS[-1]] / dps[POINTS[0]]
    ok = (all(r >= MARGIN for r in ratios.values())
          and end_ratio >= MARGIN_END)
    # Relay-election smoke (its own subprocess: the engine + election
    # caches must resolve fresh, exactly as a service boot would).
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--relay-election"],
        capture_output=True, timeout=540, text=True, cwd=_REPO,
            env=_CPU_CHILD_ENV)
    relay_ok = proc.returncode == 0 and bool(proc.stdout.strip())
    try:
        relay_out = json.loads(proc.stdout.strip().splitlines()[-1])
    except Exception:  # noqa: BLE001 — crash before the JSON line
        relay_out = {"error": proc.stderr[-400:]}
    print(json.dumps({
        "smoke": "sharded_scaling_monotonic",
        "dps": {str(s): round(dps[s], 1) for s in POINTS},
        "ratios": {k: round(r, 3) for k, r in ratios.items()},
        "end_ratio_8v1": round(end_ratio, 3),
        "margin": MARGIN,
        "margin_end": MARGIN_END,
        "ok": ok,
        "relay_election": relay_out,
    }))
    if not ok:
        print(f"PERF SMOKE FAILED: sharded scaling not monotone — "
              f"ratios={ {k: round(r, 2) for k, r in ratios.items()} } "
              f"(each must be >= {MARGIN}), 8v1={end_ratio:.2f} "
              f"(must be >= {MARGIN_END}) — sharded dispatch regressed",
              file=sys.stderr)
        return 1
    if not relay_ok:
        print(f"PERF SMOKE FAILED: relay election smoke "
              f"rc={proc.returncode} stderr={proc.stderr[-400:]!r}",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
