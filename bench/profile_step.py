"""Decompose the stream decision step into component op timings on the
real device.

The VERDICT r1 mandate: profile first, record where the milliseconds go.
Each op is wrapped in a fori_loop of REPS iterations with an
iteration-dependent input tweak (prevents CSE/hoisting) so the per-op time
dominates the ~100 ms fixed D2H fetch latency of this platform; the loop
carries a data dependency so iterations serialize.  Only a tiny reduction
is fetched.

Run from /root/repo:   python bench/profile_step.py [--small]

``--host-stages`` instead runs the HOST pipeline decomposition (r6): a
small int-key and str-key stream through TpuBatchedStorage with a meter
registry, printing the per-stage timers the storage now records
(ratelimiter.stream.pack / index / layout / enqueue / fetch) — where a
stream chunk's milliseconds go before and after the device.
"""

from __future__ import annotations

import json
import os
import sys
import time


import jax
import jax.numpy as jnp
import numpy as np

S = 1 << 20          # slot-array rows
B_FLAT = 1 << 22     # flat mega-batch (= K*B of the stream path)
K, B = 8, 1 << 19    # stream scan shape
REPS = 8

if "--small" in sys.argv:
    S, B_FLAT, K, B, REPS = 1 << 14, 1 << 16, 4, 1 << 14, 2


def bench(name, make_fn, *args):
    """jit(make_fn), run once (compile), then time one call incl. the tiny
    fetch. make_fn must fold REPS iterations internally."""
    fn = jax.jit(make_fn)
    t0 = time.perf_counter()
    r = np.asarray(fn(*args))
    compile_s = time.perf_counter() - t0
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        r = np.asarray(fn(*args))
        times.append(time.perf_counter() - t0)
    per_op_ms = (min(times) * 1000) / REPS
    print(f"{name:34s} {per_op_ms:9.2f} ms/op   (compile {compile_s:.1f}s, "
          f"checksum {r!r})", flush=True)
    return per_op_ms


def host_stages():
    """Per-stage host pipeline timers over a small stream pair (int +
    str keys), printed as one JSON line per scenario."""
    import numpy as np

    sys.path.insert(0, "/root/repo")
    from ratelimiter_tpu import RateLimitConfig
    from ratelimiter_tpu.metrics import MeterRegistry
    from ratelimiter_tpu.storage import TpuBatchedStorage

    rng = np.random.default_rng(5)
    n = 1 << 20
    ids = (rng.zipf(1.1, size=n).astype(np.int64) % 100_000)
    keys = [f"k{i}" for i in ids]
    for kind in ("ints", "strs"):
        reg = MeterRegistry()
        storage = TpuBatchedStorage(num_slots=1 << 18,
                                    meter_registry=reg)
        lid = storage.register_limiter(
            "tb", RateLimitConfig(max_permits=100, window_ms=60_000,
                                  refill_rate=50.0))
        def go():
            if kind == "ints":
                return storage.acquire_stream_ids("tb", lid, ids, None)
            return storage.acquire_stream_strs("tb", lid, keys)
        go()  # warm compile shapes
        t0 = time.perf_counter()
        go()
        wall = time.perf_counter() - t0
        stages = {
            name.split(".")[-1]: reg.timer(name).snapshot()
            for name in ("ratelimiter.stream.pack",
                         "ratelimiter.stream.index",
                         "ratelimiter.stream.layout",
                         "ratelimiter.stream.enqueue",
                         "ratelimiter.stream.fetch")}
        print(json.dumps({
            "scenario": f"host_stages_{kind}", "n": n,
            "wall_s": round(wall, 4),
            "decisions_per_sec": round(n / wall, 1),
            "stage_totals_ms": {
                k: round(v["mean_us"] * v["count"] / 1000, 3)
                for k, v in stages.items()},
            "stage_counts": {k: v["count"] for k, v in stages.items()},
            "note": ("stage totals span the warmup pass too (compiles "
                     "land in its enqueue) — compare stages against "
                     "each other, not against wall_s"),
        }), flush=True)
        storage.close()


def main():
    if "--host-stages" in sys.argv:
        host_stages()
        return
    print(f"platform={jax.devices()[0].platform} S={S} B_flat={B_FLAT} "
          f"K={K} B={B} reps={REPS}", flush=True)
    rng = np.random.default_rng(0)
    results = {}

    # Zipf-ish slot ids, sorted variants for the scatter/gather candidates.
    raw = rng.zipf(1.1, size=B_FLAT).astype(np.int64) % S
    slots = jnp.asarray(raw.astype(np.int32))
    sorted_slots = jnp.asarray(np.sort(raw.astype(np.int32)))
    packed4 = jnp.zeros((S, 4), dtype=jnp.int32)
    vals4 = jnp.asarray(rng.integers(0, 1 << 30, (B_FLAT, 4), dtype=np.int32))
    permits = jnp.ones(B_FLAT, dtype=jnp.int32)

    # -- fetch floor ---------------------------------------------------------
    tiny = jnp.zeros((8,), jnp.int32)
    t0 = time.perf_counter()
    np.asarray(tiny + 1)
    t0 = time.perf_counter()
    np.asarray(tiny + 2)
    print(f"{'fetch floor (tiny)':34s} {1000*(time.perf_counter()-t0):9.2f} ms",
          flush=True)

    # -- sort variants -------------------------------------------------------
    def f_argsort2(s):
        def body(i, acc):
            order = jnp.argsort(s ^ i, stable=True)
            inv = jnp.argsort(order)
            return acc + order[0] + inv[0]
        return jax.lax.fori_loop(0, REPS, body, jnp.int32(0))
    results["argsort_x2"] = bench("argsort+inv (2 argsorts)", f_argsort2, slots)

    def f_laxsort(s, p):
        def body(i, acc):
            iota = jnp.arange(s.shape[0], dtype=jnp.int32)
            ss, pp, order = jax.lax.sort((s ^ i, p, iota), num_keys=1,
                                         is_stable=True)
            return acc + ss[0] + pp[0] + order[0]
        return jax.lax.fori_loop(0, REPS, body, jnp.int32(0))
    results["laxsort_3op"] = bench("lax.sort 3-operand", f_laxsort, slots, permits)

    # -- gather --------------------------------------------------------------
    def f_gather(st, s):
        def body(i, acc):
            rows = st[(s + i) & (S - 1)]
            return acc + rows[0, 0] + rows[-1, -1]
        return jax.lax.fori_loop(0, REPS, body, jnp.int32(0))
    results["gather_rows4"] = bench("row gather 4-lane (random)", f_gather,
                                    packed4, slots)
    results["gather_rows4_sorted"] = bench("row gather 4-lane (sorted)",
                                           f_gather, packed4, sorted_slots)

    def f_gather1(st, s):
        flat = st[:, 0]
        def body(i, acc):
            return acc + flat[(s + i) & (S - 1)].sum()
        return jax.lax.fori_loop(0, REPS, body, jnp.int32(0))
    results["gather_1lane"] = bench("gather 1-lane i32 (random)", f_gather1,
                                    packed4, slots)

    # -- scatter variants ----------------------------------------------------
    def f_scatter(st, s, v):
        def body(i, carry):
            widx = jnp.where(s >= 0, (s + i) & (S - 1), S)
            return carry.at[widx].set(v + i, mode="drop")
        return jax.lax.fori_loop(0, REPS, body, st)[0].sum()
    results["scatter_rows4"] = bench("row scatter 4-lane (random)", f_scatter,
                                     packed4, slots, vals4)
    results["scatter_rows4_sorted"] = bench("row scatter 4-lane (sorted)",
                                            f_scatter, packed4, sorted_slots,
                                            vals4)

    def f_scatter_sorted_flags(st, s, v):
        import jax.lax as lax
        def body(i, carry):
            widx = jnp.where(s >= 0, (s + i) & (S - 1), S)
            dnums = lax.ScatterDimensionNumbers(
                update_window_dims=(1,), inserted_window_dims=(0,),
                scatter_dims_to_operand_dims=(0,))
            return lax.scatter(carry, widx[:, None], v + i, dnums,
                               indices_are_sorted=True, unique_indices=False,
                               mode=lax.GatherScatterMode.FILL_OR_DROP)
        return jax.lax.fori_loop(0, REPS, body, st)[0].sum()
    results["scatter_sorted_hint"] = bench("row scatter (sorted=True hint)",
                                           f_scatter_sorted_flags, packed4,
                                           sorted_slots, vals4)

    # -- elementwise / scan costs -------------------------------------------
    def f_cumsum(p):
        x = p.astype(jnp.int64)
        def body(i, acc):
            return acc + jax.lax.associative_scan(jnp.add, x + i)[-1]
        return jax.lax.fori_loop(0, REPS, body, jnp.int64(0))
    results["assoc_cumsum_i64"] = bench("associative cumsum i64", f_cumsum,
                                        permits)

    def f_packbits(s):
        def body(i, acc):
            return acc + jnp.packbits((s + i) > 0).astype(jnp.int32)[0]
        return jax.lax.fori_loop(0, REPS, body, jnp.int32(0))
    results["packbits"] = bench("packbits", f_packbits, slots)

    # -- the real steps ------------------------------------------------------
    sys.path.insert(0, "/root/repo")
    from ratelimiter_tpu.core.config import RateLimitConfig
    from ratelimiter_tpu.engine.state import LimiterTable
    from ratelimiter_tpu.ops.packed import tb_scan_bits
    from ratelimiter_tpu.ops.token_bucket import make_tb_packed, tb_step_p

    table = LimiterTable()
    lid = table.register(RateLimitConfig(max_permits=50, window_ms=5000,
                                         refill_rate=10.0))
    tarr = table.device_arrays

    state = make_tb_packed(S)
    slots_kb = jnp.asarray(raw.astype(np.int32)[: K * B].reshape(K, B))
    now_k = jnp.full((K,), 1_000_000, dtype=np.int64)

    scan = jax.jit(tb_scan_bits)
    t0 = time.perf_counter()
    st2, bits = scan(state, tarr, slots_kb, jnp.int32(lid), None, now_k)
    np.asarray(bits)
    print(f"{'tb_scan_bits compile+run':34s} {time.perf_counter()-t0:9.2f} s",
          flush=True)
    times = []
    for i in range(3):
        t0 = time.perf_counter()
        st2, bits = scan(st2, tarr, slots_kb, jnp.int32(lid), None,
                         now_k + i + 1)
        np.asarray(bits)
        times.append(time.perf_counter() - t0)
    ms = min(times) * 1000
    print(f"{'tb_scan_bits (K=%d,B=%d)' % (K, B):34s} {ms:9.2f} ms/dispatch "
          f"-> {K*B/min(times)/1e6:.1f}M dec/s", flush=True)
    results["tb_scan_bits_ms"] = ms

    # flat mega-batch: one sorted batch of K*B with equal timestamps
    flat = jax.jit(tb_step_p, donate_argnums=0)
    slots_flat = jnp.asarray(raw.astype(np.int32)[: K * B])
    pf = jnp.ones(K * B, dtype=jnp.int64)
    t0 = time.perf_counter()
    st3, out = flat(st2, tarr, slots_flat, jnp.int32(lid), pf,
                    jnp.int64(2_000_000))
    np.asarray(out.allowed)
    print(f"{'tb_step_p flat compile+run':34s} {time.perf_counter()-t0:9.2f} s",
          flush=True)
    times = []
    for i in range(3):
        t0 = time.perf_counter()
        st3, out = flat(st3, tarr, slots_flat, jnp.int32(lid), pf,
                        jnp.int64(2_000_100 + i))
        np.asarray(out.allowed)
        times.append(time.perf_counter() - t0)
    ms = min(times) * 1000
    print(f"{'tb_step_p flat (B=%d)' % (K*B,):34s} {ms:9.2f} ms/dispatch "
          f"-> {K*B/min(times)/1e6:.1f}M dec/s", flush=True)
    results["tb_step_flat_ms"] = ms

    print(json.dumps(results), flush=True)


if __name__ == "__main__":
    main()
