"""Device-only chained-step benchmark (VERDICT r3 #4).

Chains K decision steps inside ONE jit over donated device state and
fetches a single checksum — so the measurement contains the decision
step itself and no per-step wire.  This converts ARCHITECTURE §8b's
"~300M decisions/s device headroom" from cost-model arithmetic into a
measurement on this hardware, and gives the Pallas kernels a verdict:
run the same harness with RATELIMITER_PALLAS=1/0, one process at a time
(the kernels bind at import, and a chip serves one process at a time).

Two chained steps are measured:
- ``relay``: the unit-permit relay words step (ops/relay.py:
  tb_relay_bits) — the streaming hot path's dominant dispatch.  No
  sort, no solver; slots rotate per step so every iteration touches a
  different 512K-slot subset of the 1M-slot state.
- ``flat``: the sorted flat step with weighted permits (ops/flat.py:
  tb_flat_bits) — the path that exercises the Pallas sandwich solver
  and (via scatter_rows_sorted) the block-scatter kernel.

Prints ONE JSON line.  Run with cwd=repo root.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


def main() -> None:
    from ratelimiter_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    import jax
    import jax.numpy as jnp

    from ratelimiter_tpu import RateLimitConfig
    from ratelimiter_tpu.engine.engine import DeviceEngine
    from ratelimiter_tpu.engine.state import LimiterTable
    from ratelimiter_tpu.ops import flat, relay

    num_slots = 1 << 20
    B = 1 << 19
    table = LimiterTable()
    lid = table.register(RateLimitConfig(
        max_permits=100, window_ms=60_000, refill_rate=50.0))
    eng = DeviceEngine(num_slots, table)
    rb = eng.rank_bits
    tarr = table.device_arrays
    lid_dev = jnp.int32(lid)

    # RTT floor so the fetch's fixed cost can be subtracted out.
    tiny = jax.jit(lambda v: v.sum())
    np.asarray(tiny(jnp.zeros(8, jnp.int32)))  # compile + settle
    t0 = time.perf_counter()
    for _ in range(3):
        np.asarray(tiny(jnp.zeros(8, jnp.int32)))
    rtt_s = (time.perf_counter() - t0) / 3

    base = jnp.arange(B, dtype=jnp.int32) * (num_slots // B)

    def relay_chain(K):
        def run(packed, now0):
            def body(i, carry):
                packed, acc = carry
                slots = (base + i * jnp.int32(7919)) % num_slots
                words = (slots.astype(jnp.uint32)
                         << np.uint32(rb + 1)) | np.uint32(1)
                packed, bits = relay.tb_relay_bits(
                    packed, tarr, words, lid_dev, now0 + i, rank_bits=rb)
                return packed, acc + jnp.sum(bits.astype(jnp.int64))
            packed, acc = jax.lax.fori_loop(0, K, body,
                                            (packed, jnp.int64(0)))
            return packed, acc
        return jax.jit(run, donate_argnums=0)

    # Weighted flat with duplicates: base has stride 2, so
    # (base >> 3) * 8 maps every 4 consecutive lanes to one slot —
    # 4-deep segments driving the segmented solver through real work.
    perms = jnp.asarray(
        (np.random.default_rng(5).integers(1, 9, B)).astype(np.int32))

    def flat_chain(K):
        def run(packed, now0):
            def body(i, carry):
                packed, acc = carry
                slots = ((base >> 3) * 8 + i * jnp.int32(7919)) % num_slots
                packed, bits = flat.tb_flat_bits(
                    packed, tarr, slots, lid_dev, perms, now0 + i)
                return packed, acc + jnp.sum(bits.astype(jnp.int64))
            packed, acc = jax.lax.fori_loop(0, K, body,
                                            (packed, jnp.int64(0)))
            return packed, acc
        return jax.jit(run, donate_argnums=0)

    def measure(make_chain, packed0):
        # Calibrate with a short chain, then re-run sized for ~2-4 s of
        # device time so the round trip amortizes away.
        K0 = 8
        fn = make_chain(K0)
        packed, acc = fn(packed0, jnp.int64(1_000_000))
        int(np.asarray(acc))  # settle compile + first run
        t0 = time.perf_counter()
        packed, acc = fn(packed, jnp.int64(2_000_000))
        int(np.asarray(acc))
        dt0 = time.perf_counter() - t0
        per_step = max((dt0 - rtt_s) / K0, 1e-5)
        K = int(min(max(2.0 / per_step, K0), 1024))
        fn = make_chain(K)
        packed, acc = fn(packed, jnp.int64(3_000_000))
        int(np.asarray(acc))  # compile the real K untimed
        t0 = time.perf_counter()
        packed, acc = fn(packed, jnp.int64(4_000_000))
        checksum = int(np.asarray(acc))
        dt = time.perf_counter() - t0
        dev_s = max(dt - rtt_s, 1e-9)
        return {
            "steps": K, "lanes_per_step": B,
            "decisions": K * B,
            "wall_s": round(dt, 4),
            "device_s": round(dev_s, 4),
            "decisions_per_sec": round(K * B / dev_s, 1),
            "ns_per_decision": round(dev_s / (K * B) * 1e9, 3),
            "checksum": checksum,
        }

    # Digest counts step, slot-SORTED (presorted dense block sweep) vs
    # unsorted (XLA per-index scatter) — the r4 sorted-digest change's
    # on-device verdict.  One uword per unique, count 1; slots fixed per
    # chain (strided ascending for sorted, a fixed permutation for
    # unsorted — HBM has no cache to warm either way).
    uslots_sorted = np.arange(B, dtype=np.uint32) * (num_slots // B)
    uslots_shuf = np.random.default_rng(9).permutation(
        uslots_sorted).astype(np.uint32)

    def digest_chain(slots_np, sorted_flag):
        uw = jnp.asarray((slots_np << np.uint32(rb + 1))
                         | np.uint32(1 << 1))

        def make(K):
            def run(packed, now0):
                def body(i, carry):
                    packed, acc = carry
                    packed, counts = relay.tb_relay_counts(
                        packed, tarr, uw, lid_dev, now0 + i,
                        rank_bits=rb, out_dtype=jnp.uint8,
                        slots_sorted=sorted_flag)
                    return packed, acc + jnp.sum(
                        counts.astype(jnp.int64))
                packed, acc = jax.lax.fori_loop(0, K, body,
                                                (packed, jnp.int64(0)))
                return packed, acc
            return jax.jit(run, donate_argnums=0)
        return make

    # Micro-batch step at the batcher's bucket shapes (VERDICT r4 #3):
    # K chained relay steps in one jit — the per-step figure is the
    # DEVICE term of a local-attached deployment's per-request latency
    # floor (flush deadline + this + PCIe round trip), measured instead
    # of projected.  Measured at 256 lanes (the r4 figure) AND at the
    # r6 _MICRO_FLOOR (32 lanes — the shape interactive micro-batches
    # actually dispatch at now).
    def micro_chain_lanes(K, mb):
        mbase = jnp.arange(mb, dtype=jnp.int32) * (num_slots // mb)

        def run(packed, now0):
            def body(i, carry):
                packed, acc = carry
                slots = (mbase + i * jnp.int32(7919)) % num_slots
                words = (slots.astype(jnp.uint32)
                         << np.uint32(rb + 1)) | np.uint32(1)
                packed, bits = relay.tb_relay_bits(
                    packed, tarr, words, lid_dev, now0 + i, rank_bits=rb)
                return packed, acc + jnp.sum(bits.astype(jnp.int64))
            packed, acc = jax.lax.fori_loop(0, K, body,
                                            (packed, jnp.int64(0)))
            return packed, acc
        return jax.jit(run, donate_argnums=0)

    def measure_micro(mb=256):
        from ratelimiter_tpu.ops.token_bucket import make_tb_packed

        # 32K chained steps: a 256-lane step is sub-microsecond on TPU
        # (a 512-step chain vanished inside the remote link's RTT jitter), so
        # the chain must run tens of ms to measure above it.
        K = 32768
        fn = micro_chain_lanes(K, mb)
        # Fresh state: eng.tb_packed is the relay chain's (donated there).
        packed, acc = fn(make_tb_packed(num_slots), jnp.int64(1_000_000))
        int(np.asarray(acc))  # compile + settle
        t0 = time.perf_counter()
        packed, acc = fn(packed, jnp.int64(2_000_000))
        checksum = int(np.asarray(acc))
        dt = time.perf_counter() - t0
        per_step_us = max(dt - rtt_s, 1e-9) / K * 1e6
        return {"steps": K, "lanes_per_step": mb,
                "us_per_step": round(per_step_us, 3),
                "checksum": checksum,
                "note": ("device term of the local-attachment per-"
                         "request floor: flush deadline + this + "
                         "interconnect round trip")}

    from ratelimiter_tpu.ops.pallas import block_scatter, solver

    out = {
        "pallas_flag": os.environ.get("RATELIMITER_PALLAS", "1"),
        "solver_live": bool(solver.settle()),
        "block_scatter_live": bool(block_scatter.settle()),
        "rtt_ms": round(rtt_s * 1000, 1),
        "microbatch_256": measure_micro(256),
        "microbatch_32": measure_micro(32),
        "relay": measure(relay_chain, eng.tb_packed),
    }
    # Local-SLO floor guard (ISSUE r6 satellite): the micro-batch device
    # step must sit below the 0.697 ms figure the r5 SLO decomposition
    # attributed to the device — a regression here silently re-opens the
    # p50 miss, so it fails the bench loudly instead.
    slo_floor_ms = 0.697
    out["micro_step_slo"] = {
        "floor_ms": slo_floor_ms,
        "us_per_step_32": out["microbatch_32"]["us_per_step"],
        "meets": bool(out["microbatch_32"]["us_per_step"] / 1000.0
                      < slo_floor_ms),
    }
    assert out["micro_step_slo"]["meets"], (
        f"32-lane micro step {out['microbatch_32']['us_per_step']} us "
        f">= SLO floor {slo_floor_ms} ms")
    # Later chains start from fresh state (prior chains donated theirs).
    from ratelimiter_tpu.ops.token_bucket import make_tb_packed

    # Steady-state micro-loop recompile guard (r11 satellite): warm the
    # double-buffered staged shapes (both in-flight buffers), then drive
    # a steady interactive loop at jittered lane counts inside the
    # warmed buckets and assert ZERO new XLA compiles fire — a compile
    # inside the steady loop is a multi-hundred-ms p99 spike the warmup
    # exists to prevent.
    from ratelimiter_tpu.engine.engine import MICRO_STAGE_ROWS

    eng.tb_packed = make_tb_packed(num_slots)  # relay chain donated it
    eng.warm_micro_shapes(sizes=(32, 64, 128))
    compiles_before = eng.micro_compile_count()
    bufs = []
    for cap in (32, 64, 128, 32):  # the double buffer's two halves
        b = np.empty((MICRO_STAGE_ROWS, cap), dtype=np.int64)
        b[0] = -1
        b[1] = lid
        b[2] = 1
        bufs.append(b)
    steps = 200
    t0 = time.perf_counter()
    for i in range(steps):
        b = bufs[i % len(bufs)]
        algo = "tb" if i % 2 else "sw"
        n = 1 + (i * 13) % b.shape[1]
        b[0, :n] = (np.arange(n) * 7919 + i) % num_slots
        b[3, 0] = 3_000_000 + i
        h = eng.micro_staged_dispatch(algo, b, n)
        eng.micro_staged_drain(algo, h, n)
        b[0, :n] = -1
    dt = time.perf_counter() - t0
    compiles_after = eng.micro_compile_count()
    out["micro_staged"] = {
        "steps": steps,
        "ms_per_dispatch_drain": round(dt / steps * 1000, 3),
        "compiles_before": compiles_before,
        "compiles_after": compiles_after,
        "recompiled": bool(compiles_after != compiles_before),
    }
    assert not out["micro_staged"]["recompiled"], (
        f"steady-state micro loop recompiled: {compiles_before} -> "
        f"{compiles_after} staged-step executables (warm_micro_shapes "
        "no longer covers the batcher's dispatch buckets)")

    out["flat_weighted"] = measure(flat_chain, make_tb_packed(num_slots))
    out["digest_sorted"] = measure(digest_chain(uslots_sorted, True),
                                   make_tb_packed(num_slots))
    out["digest_unsorted"] = measure(digest_chain(uslots_shuf, False),
                                     make_tb_packed(num_slots))

    # Fused Pallas relay step (ops/pallas/relay_step.py): the same
    # sorted digest traffic through the single-pass gather+update+
    # scatter kernel, directly comparable to digest_sorted (composed
    # XLA + presorted sweep) and to the relay words step.
    from ratelimiter_tpu.ops.pallas import election as pallas_election
    from ratelimiter_tpu.ops.pallas import relay_step as fused_relay

    out["relay_fused_live"] = bool(fused_relay.settle())
    if fused_relay.enabled((num_slots, 4), B, rb):
        uw_f = jnp.asarray((uslots_sorted << np.uint32(rb + 1))
                           | np.uint32(1 << 1))

        def fused_chain(K):
            def run(packed, now0):
                def body(i, carry):
                    packed, acc = carry
                    packed, counts = fused_relay.tb_relay_counts_fused(
                        packed, tarr, uw_f, lid_dev, now0 + i,
                        rank_bits=rb,
                        interpret=fused_relay.interpret_mode())
                    return packed, acc + jnp.sum(counts.astype(jnp.int64))
                packed, acc = jax.lax.fori_loop(0, K, body,
                                                (packed, jnp.int64(0)))
                return packed, acc
            return jax.jit(run, donate_argnums=0)

        out["digest_fused"] = measure(fused_chain, make_tb_packed(num_slots))

    # Per-path election records + the elected-never-slower gate
    # (VERDICT #7): the backend the engine actually dispatches for the
    # sorted relay/digest step must not be measurably slower than the
    # XLA path on this device.  1.10 margin absorbs run-to-run noise;
    # a real inversion (an election serving a slower kernel) fails the
    # bench loudly.
    out["pallas_elections"] = pallas_election.report()
    serves_fused = out["relay_fused_live"] and "digest_fused" in out
    elected = out["digest_fused"] if serves_fused else out["digest_sorted"]
    out["relay_election_check"] = {
        "elected_backend": "pallas_fused" if serves_fused else "xla",
        "elected_ns_per_unique": elected["ns_per_decision"],
        "xla_sorted_ns_per_unique": out["digest_sorted"][
            "ns_per_decision"],
        "xla_relay_words_ns_per_lane": out["relay"]["ns_per_decision"],
        "ok": bool(elected["ns_per_decision"]
                   <= 1.10 * out["digest_sorted"]["ns_per_decision"]),
    }
    assert out["relay_election_check"]["ok"], (
        f"elected relay step {elected['ns_per_decision']} ns/unique is "
        f"slower than the XLA sorted digest "
        f"{out['digest_sorted']['ns_per_decision']} ns/unique — the "
        f"per-path election served a losing backend")
    print(json.dumps(out))


if __name__ == "__main__":
    main()
