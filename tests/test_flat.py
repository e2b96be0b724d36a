"""Differential tests for the flat mega-batch steps (ops/flat.py) and the
Pallas dense block-scatter (ops/pallas/block_scatter.py).

The flat step must decide exactly like K sequential scan sub-batches at the
same timestamp — that equivalence is what lets the stream path trade the
lax.scan for one big sorted batch.  The block-scatter must write exactly
like the XLA drop-mode scatter it replaces.
"""

import numpy as np
import pytest

from ratelimiter_tpu.core.config import RateLimitConfig
from ratelimiter_tpu.engine.engine import DeviceEngine
from ratelimiter_tpu.engine.state import LimiterTable


@pytest.fixture()
def table():
    t = LimiterTable()
    t.register(RateLimitConfig(max_permits=5, window_ms=1000))          # 1 sw
    t.register(RateLimitConfig(max_permits=10, window_ms=1000,
                               refill_rate=5.0))                        # 2 tb
    t.register(RateLimitConfig(max_permits=3, window_ms=500,
                               refill_rate=2.0))                        # 3 tb
    return t


def _flat_bits(engine, algo, slots, lids, permits, now):
    fn = (engine.sw_flat_dispatch if algo == "sw"
          else engine.tb_flat_dispatch)
    bits = np.asarray(fn(slots, lids, permits, now))
    return np.unpackbits(bits)[: len(slots)].astype(bool)


def _sequential_truth(table, algo, lid_per_req, slots, permits, now, k):
    """K successive plain acquires over fresh state — the scan semantics."""
    eng = DeviceEngine(num_slots=64, table=table)
    fn = eng.sw_acquire if algo == "sw" else eng.tb_acquire
    b = len(slots) // k
    out = []
    for i in range(k):
        sl = slots[i * b:(i + 1) * b]
        ld = lid_per_req[i * b:(i + 1) * b]
        pm = (np.ones(b, np.int64) if permits is None
              else permits[i * b:(i + 1) * b].astype(np.int64))
        out.append(fn(sl, ld, pm, now)["allowed"])
    return np.concatenate(out), eng


@pytest.mark.parametrize("algo,lid", [("sw", 1), ("tb", 2)])
@pytest.mark.parametrize("unit_permits", [True, False])
def test_flat_matches_sequential_subbatches(table, algo, lid, unit_permits):
    """Hot duplicate segments spanning 'sub-batch' boundaries: the flat
    batch must reproduce the sequential decisions bit-for-bit, and leave
    identical state."""
    rng = np.random.default_rng(10)
    k, b = 4, 24
    n = k * b
    slots = rng.integers(0, 6, n).astype(np.int32)  # heavy duplication
    permits = None if unit_permits else rng.integers(1, 3, n).astype(np.int32)
    now = 7_000

    expect, seq_eng = _sequential_truth(
        table, algo, [lid] * n, slots, permits, now, k)

    flat_eng = DeviceEngine(num_slots=64, table=table)
    got = _flat_bits(flat_eng, algo, slots, lid, permits, now)
    np.testing.assert_array_equal(got, expect)
    # State convergence: both engines hold the same rows afterwards.
    np.testing.assert_array_equal(
        flat_eng.read_rows(algo, np.arange(64)),
        seq_eng.read_rows(algo, np.arange(64)))


def test_flat_multi_lid_and_padding(table):
    """Per-request limiter ids + padding lanes (-1) in one flat batch."""
    rng = np.random.default_rng(11)
    n = 64
    slots = rng.integers(0, 8, n).astype(np.int32)
    slots[::9] = -1  # padding / force-deny lanes
    lids = np.where(slots % 2 == 0, 2, 3).astype(np.int32)
    permits = rng.integers(1, 3, n).astype(np.int32)
    now = 9_000

    # Truth: single plain batched acquire (same semantics as flat n=k*b, k=1).
    eng = DeviceEngine(num_slots=64, table=table)
    expect = eng.tb_acquire(slots, lids, permits.astype(np.int64),
                            now)["allowed"]

    flat_eng = DeviceEngine(num_slots=64, table=table)
    got = _flat_bits(flat_eng, "tb", slots, lids, permits, now)
    np.testing.assert_array_equal(got, expect)
    assert not got[slots == -1].any()


def test_flat_unit_permits_closed_form_segment_caps(table):
    """A single hot key with more requests than capacity: exactly cap
    requests pass, in arrival order (closed-form rank solve)."""
    flat_eng = DeviceEngine(num_slots=64, table=table)
    n = 32
    slots = np.zeros(n, dtype=np.int32)
    got = _flat_bits(flat_eng, "tb", slots, 2, None, 5_000)
    assert got[:10].all() and not got[10:].any()  # lid 2: cap 10

    got = _flat_bits(flat_eng, "sw", slots, 1, None, 5_000)
    assert got[:5].all() and not got[5:].any()    # lid 1: max 5


# ---------------------------------------------------------------------------
# Pallas block-scatter (interpret mode on CPU)
# ---------------------------------------------------------------------------

def _xla_truth(state, slots, mask, rows):
    out = state.copy()
    out[slots[mask]] = rows[mask]
    return out


@pytest.mark.parametrize("lanes", [4, 6])
def test_block_scatter_matches_xla(lanes):
    from ratelimiter_tpu.ops.pallas import block_scatter as bs

    rng = np.random.default_rng(12)
    S, B = 4 * bs.T, 4 * bs.T
    state = rng.integers(-(1 << 30), 1 << 30, (S, lanes)).astype(np.int32)
    # Sorted batch with duplicates + padding; mask = last-of-segment & valid.
    slots = np.sort(rng.choice(S, size=B - 7, replace=True)).astype(np.int32)
    slots = np.concatenate([np.full(7, -1, np.int32), slots])
    valid = slots >= 0
    last = np.r_[slots[:-1] != slots[1:], True]
    mask = valid & last
    rows = rng.integers(-(1 << 30), 1 << 30, (B, lanes)).astype(np.int32)

    import jax.numpy as jnp

    got = np.asarray(bs.scatter_rows(
        jnp.asarray(state), jnp.asarray(slots), jnp.asarray(mask),
        jnp.asarray(rows), interpret=True))
    np.testing.assert_array_equal(got, _xla_truth(state, slots, mask, rows))


def test_block_scatter_dense_and_empty_edges():
    """Every slot written (update count == block size everywhere), and the
    zero-updates case (all masked out)."""
    from ratelimiter_tpu.ops.pallas import block_scatter as bs

    import jax.numpy as jnp

    S = 2 * bs.T
    state = np.arange(S * 4, dtype=np.int32).reshape(S, 4)
    slots = np.arange(S, dtype=np.int32)
    rows = -np.arange(S * 4, dtype=np.int32).reshape(S, 4)
    got = np.asarray(bs.scatter_rows(
        jnp.asarray(state), jnp.asarray(slots),
        jnp.asarray(np.ones(S, bool)), jnp.asarray(rows), interpret=True))
    np.testing.assert_array_equal(got, rows)

    got = np.asarray(bs.scatter_rows(
        jnp.asarray(state), jnp.asarray(slots),
        jnp.asarray(np.zeros(S, bool)), jnp.asarray(rows), interpret=True))
    np.testing.assert_array_equal(got, state)


def test_flat_step_through_block_scatter_interpret(table, monkeypatch):
    """The full flat TB step with the Pallas scatter enabled (interpret):
    decisions and state identical to the XLA-scatter flat step."""
    from ratelimiter_tpu.ops.pallas import block_scatter as bs

    rng = np.random.default_rng(13)
    n = 4 * bs.T
    S = 4 * bs.T
    big = LimiterTable()
    big.register(RateLimitConfig(max_permits=5, window_ms=1000))
    lid = big.register(RateLimitConfig(max_permits=4, window_ms=1000,
                                       refill_rate=2.0))
    slots = rng.integers(0, 40, n).astype(np.int32)

    ref_eng = DeviceEngine(num_slots=S, table=big)
    expect = _flat_bits(ref_eng, "tb", slots, lid, None, 6_000)

    monkeypatch.setattr(bs, "_FLAG", True)
    monkeypatch.setattr(bs, "_INTERPRET", True)
    monkeypatch.setattr(bs, "_probe_ok", None)
    pal_eng = DeviceEngine(num_slots=S, table=big)
    assert bs.enabled((S, 4), n)  # geometry passes; probe runs interpreted
    got = _flat_bits(pal_eng, "tb", slots, lid, None, 6_000)
    np.testing.assert_array_equal(got, expect)
    np.testing.assert_array_equal(
        pal_eng.read_rows("tb", np.arange(S)),
        ref_eng.read_rows("tb", np.arange(S)))


def test_stream_strs_matches_acquire_many():
    """String-key streaming == chunked acquire_many on the same stream
    (same index namespace, same kernels, pipelining must not change
    decisions)."""
    from ratelimiter_tpu.storage import TpuBatchedStorage

    cfg = RateLimitConfig(max_permits=6, window_ms=1000, refill_rate=4.0)
    rng = np.random.default_rng(14)
    n = 600
    keys = [f"user-{k}" for k in rng.integers(0, 35, n)]
    permits = rng.integers(1, 3, n).astype(np.int64)
    clock = lambda: 88_000  # noqa: E731

    s1 = TpuBatchedStorage(num_slots=256, clock_ms=clock)
    lid1 = s1.register_limiter("tb", cfg)
    expect = np.empty(n, dtype=bool)
    for i in range(0, n, 64):
        chunk = keys[i:i + 64]
        expect[i:i + len(chunk)] = s1.acquire_many(
            "tb", [lid1] * len(chunk), chunk,
            list(permits[i:i + len(chunk)]))["allowed"]
    s1.close()

    s2 = TpuBatchedStorage(num_slots=256, clock_ms=clock)
    lid2 = s2.register_limiter("tb", cfg)
    got = s2.acquire_stream_strs("tb", lid2, keys, permits,
                                 batch=64, subbatches=2)
    s2.close()
    np.testing.assert_array_equal(got, expect)


def test_stream_strs_shares_namespace_with_scalar_path():
    """Stream-consumed string keys are the same buckets the scalar path
    sees."""
    from ratelimiter_tpu.storage import TpuBatchedStorage

    clock = lambda: 44_000  # noqa: E731
    s = TpuBatchedStorage(num_slots=64, clock_ms=clock)
    lid = s.register_limiter("tb", RateLimitConfig(
        max_permits=3, window_ms=1000, refill_rate=0.001))
    got = s.acquire_stream_strs("tb", lid, ["alice"] * 5, None,
                                batch=8, subbatches=1)
    assert got.tolist() == [True, True, True, False, False]
    out = s.acquire("tb", lid, "alice", 1)
    s.close()
    assert not out["allowed"]


def test_try_acquire_many_routes_large_calls_to_stream(monkeypatch):
    """Above the size threshold the limiters stream; decisions must be the
    same either way (cache-less SW and TB)."""
    from ratelimiter_tpu.algorithms import (
        SlidingWindowRateLimiter,
        TokenBucketRateLimiter,
    )
    from ratelimiter_tpu.algorithms import sliding_window as swmod
    from ratelimiter_tpu.algorithms import token_bucket as tbmod
    from ratelimiter_tpu.metrics import MeterRegistry
    from ratelimiter_tpu.storage import TpuBatchedStorage

    monkeypatch.setattr(swmod, "_STREAM_MIN", 64)
    monkeypatch.setattr(tbmod, "_STREAM_MIN", 64)
    rng = np.random.default_rng(15)
    n = 300
    keys = [f"u{k}" for k in rng.integers(0, 20, n)]
    clock = lambda: 66_000  # noqa: E731

    results = {}
    for threshold_hit in (False, True):
        st = TpuBatchedStorage(num_slots=256, clock_ms=clock)
        sw = SlidingWindowRateLimiter(
            st, RateLimitConfig(max_permits=8, window_ms=1000,
                                enable_local_cache=False),
            MeterRegistry(), clock_ms=clock)
        tb = TokenBucketRateLimiter(
            st, RateLimitConfig(max_permits=5, window_ms=1000,
                                refill_rate=1.0),
            MeterRegistry(), clock_ms=clock)
        if threshold_hit:
            got_sw = sw.try_acquire_many(keys)           # n >= 64: streams
            got_tb = tb.try_acquire_many(keys)
        else:
            got_sw = np.concatenate(
                [sw.try_acquire_many(keys[i:i + 50]) for i in range(0, n, 50)])
            got_tb = np.concatenate(
                [tb.try_acquire_many(keys[i:i + 50]) for i in range(0, n, 50)])
        results[threshold_hit] = (got_sw, got_tb)
        st.close()
    np.testing.assert_array_equal(results[False][0], results[True][0])
    np.testing.assert_array_equal(results[False][1], results[True][1])


@pytest.mark.parametrize("lanes", [4, 6])
def test_block_scatter_presorted_matches_xla(lanes):
    """The presorted entry (no compaction sort: caller-sorted unique
    slots, padding at the tail — the host-sorted digest layout) against
    XLA drop-scatter truth, in interpret mode."""
    from ratelimiter_tpu.ops.pallas import block_scatter as bs

    import jax.numpy as jnp

    rng = np.random.default_rng(21)
    S, B = 4 * bs.T, 4 * bs.T
    for trial in range(4):
        state = rng.integers(-(1 << 30), 1 << 30, (S, lanes)).astype(
            np.int32)
        u = int(rng.integers(1, B - 1))
        live = np.sort(rng.choice(S, size=u, replace=False)).astype(
            np.int32)
        # Digest padding decodes to slot >= S, at the tail.
        slots = np.concatenate([live, np.full(B - u, S + 5, np.int32)])
        mask = np.r_[np.ones(u, bool), np.zeros(B - u, bool)]
        rows = rng.integers(-(1 << 30), 1 << 30, (B, lanes)).astype(
            np.int32)
        got = np.asarray(bs.scatter_rows_presorted(
            jnp.asarray(state), jnp.asarray(slots), jnp.asarray(mask),
            jnp.asarray(rows), interpret=True))
        np.testing.assert_array_equal(
            got, _xla_truth(state, slots, mask, rows), err_msg=str(trial))
    # Edges: everything written; nothing written.
    state = np.arange(S * lanes, dtype=np.int32).reshape(S, lanes)
    slots = np.arange(S, dtype=np.int32)
    rows = -state
    got = np.asarray(bs.scatter_rows_presorted(
        jnp.asarray(state), jnp.asarray(slots),
        jnp.asarray(np.ones(S, bool)), jnp.asarray(rows), interpret=True))
    np.testing.assert_array_equal(got, rows)
    got = np.asarray(bs.scatter_rows_presorted(
        jnp.asarray(state), jnp.asarray(slots),
        jnp.asarray(np.zeros(S, bool)), jnp.asarray(rows), interpret=True))
    np.testing.assert_array_equal(got, state)
