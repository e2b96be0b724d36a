"""Sharded engine on the 8-device CPU mesh.

- differential vs oracle through the full limiter stack (TpuBatchedStorage
  wired to a ShardedDeviceEngine),
- exact equivalence sharded-vs-single-device on an identical stream,
- shard routing invariants and the psum metrics totals.
"""

import random

import numpy as np

import jax

from ratelimiter_tpu import RateLimitConfig
from ratelimiter_tpu.algorithms import SlidingWindowRateLimiter, TokenBucketRateLimiter
from ratelimiter_tpu.engine.engine import DeviceEngine
from ratelimiter_tpu.engine.state import LimiterTable
from ratelimiter_tpu.metrics import MeterRegistry
from ratelimiter_tpu.parallel import ShardedDeviceEngine, shard_of_key
from ratelimiter_tpu.semantics import SlidingWindowOracle, TokenBucketOracle
from ratelimiter_tpu.storage import TpuBatchedStorage

T0 = 1_753_000_000_000


class FakeClock:
    def __init__(self, t=T0):
        self.t = t

    def __call__(self):
        return self.t


def test_mesh_has_8_devices():
    assert len(jax.devices()) == 8


def test_sharded_slot_index_routing():
    eng_table = LimiterTable()
    engine = ShardedDeviceEngine(slots_per_shard=32, table=eng_table)
    idx = engine.make_slot_index()
    for i in range(100):
        key = (1, f"user{i}")
        slot, _ = idx.assign(key)
        assert slot // 32 == shard_of_key(key, engine.n_shards)
        assert idx.get(key) == slot


def test_sharded_equivalent_to_single_device():
    rng = random.Random(9)
    cfg_sw = RateLimitConfig(max_permits=12, window_ms=1500, enable_local_cache=False)
    cfg_tb = RateLimitConfig(max_permits=20, window_ms=2000, refill_rate=25.0)

    t1 = LimiterTable()
    single = DeviceEngine(num_slots=256, table=t1)
    lid_sw1, lid_tb1 = t1.register(cfg_sw), t1.register(cfg_tb)

    t2 = LimiterTable()
    sharded = ShardedDeviceEngine(slots_per_shard=32, table=t2)
    lid_sw2, lid_tb2 = t2.register(cfg_sw), t2.register(cfg_tb)
    assert (lid_sw1, lid_tb1) == (lid_sw2, lid_tb2)

    # Identical slot usage on both engines: map key i -> slot i (single) and
    # key i -> (shard_of i, local i) (sharded). Decisions must agree exactly.
    keys = list(range(40))
    sh_index = sharded.make_slot_index()
    sh_slot = {k: sh_index.assign(("k", k))[0] for k in keys}

    now = T0
    for step in range(25):
        now += rng.randrange(0, 900)
        n = rng.randrange(1, 64)
        ks = [rng.choice(keys) for _ in range(n)]
        perms = [rng.randrange(1, 4) for _ in range(n)]
        a = single.sw_acquire(ks, [lid_sw1] * n, perms, now)
        b = sharded.sw_acquire([sh_slot[k] for k in ks], [lid_sw2] * n, perms, now)
        np.testing.assert_array_equal(a["allowed"], b["allowed"])
        np.testing.assert_array_equal(a["observed"], b["observed"])
        a = single.tb_acquire(ks, [lid_tb1] * n, perms, now)
        b = sharded.tb_acquire([sh_slot[k] for k in ks], [lid_tb2] * n, perms, now)
        np.testing.assert_array_equal(a["allowed"], b["allowed"])
        np.testing.assert_array_equal(a["remaining"], b["remaining"])
        # psum totals: allowed count across all shards == batch-wide truth.
        assert sharded.last_step_totals[1] == n


def test_full_stack_on_sharded_engine_vs_oracle():
    clock = FakeClock()
    table = LimiterTable()
    engine = ShardedDeviceEngine(slots_per_shard=64, table=table)
    storage = TpuBatchedStorage(engine=engine, max_delay_ms=0.2, clock_ms=clock)
    cfg_sw = RateLimitConfig(max_permits=10, window_ms=1000, enable_local_cache=False)
    cfg_tb = RateLimitConfig(max_permits=30, window_ms=2000, refill_rate=40.0)
    sw = SlidingWindowRateLimiter(storage, cfg_sw, MeterRegistry(), clock_ms=clock)
    tb = TokenBucketRateLimiter(storage, cfg_tb, MeterRegistry(), clock_ms=clock)
    osw, otb = SlidingWindowOracle(cfg_sw), TokenBucketOracle(cfg_tb)

    rng = random.Random(13)
    keys = [f"u{i}" for i in range(24)]
    for step in range(40):
        clock.t += rng.randrange(0, 500)
        n = rng.randrange(1, 48)
        ks = [rng.choice(keys) for _ in range(n)]
        perms = [rng.randrange(1, 5) for _ in range(n)]
        got = sw.try_acquire_many(ks, perms)
        for j in range(n):
            assert got[j] == osw.try_acquire(ks[j], perms[j], clock.t).allowed, (step, j)
        got = tb.try_acquire_many(ks, perms)
        for j in range(n):
            assert got[j] == otb.try_acquire(ks[j], perms[j], clock.t).allowed, (step, j)
        if rng.random() < 0.15:
            k = rng.choice(keys)
            sw.reset(k)
            osw.reset(k, clock.t)
            tb.reset(k)
            otb.reset(k, clock.t)
        k = rng.choice(keys)
        assert sw.get_available_permits(k) == osw.get_available_permits(k, clock.t)
        assert tb.get_available_permits(k) == otb.get_available_permits(k, clock.t)
    storage.close()


def test_native_shard_route_matches_numpy():
    """The C routing pass must be bit-identical to shard_of_int_keys +
    stable argsort (scalar and stream paths must agree on shards)."""
    import numpy as np
    import pytest

    from ratelimiter_tpu.engine.native_index import shard_route
    from ratelimiter_tpu.parallel.sharded import shard_of_int_keys

    if shard_route(np.asarray([1], dtype=np.int64), 2) is None:
        pytest.skip("native library unavailable")
    rng = np.random.default_rng(3)
    for n_sh in (1, 2, 3, 8):
        keys = rng.integers(-(1 << 62), 1 << 62, 5000)
        shard, order, counts = shard_route(keys, n_sh)
        want = shard_of_int_keys(keys, n_sh)
        np.testing.assert_array_equal(shard, want)
        np.testing.assert_array_equal(order,
                                      np.argsort(want, kind="stable"))
        np.testing.assert_array_equal(counts,
                                      np.bincount(want, minlength=n_sh))


def test_sharded_str_stream_matches_single_device():
    """The r6 sharded STRING stream (hash once -> fingerprint routing ->
    per-shard fps assigns, pipelined) must decide bit-identically to the
    single-device string stream AND stay consistent with interleaved
    scalar calls on the same keys."""
    clock = FakeClock()
    cfg = RateLimitConfig(max_permits=5, window_ms=60_000, refill_rate=1.0)

    eng = ShardedDeviceEngine(slots_per_shard=256, table=LimiterTable())
    st_sharded = TpuBatchedStorage(engine=eng, clock_ms=clock)
    st_single = TpuBatchedStorage(num_slots=2048, clock_ms=clock)
    lid_s = st_sharded.register_limiter("tb", cfg)
    lid_f = st_single.register_limiter("tb", cfg)
    assert st_sharded._index["tb"].supports_batch_strs

    rng = np.random.default_rng(5)
    ids = rng.zipf(1.3, size=8000).astype(np.int64) % 300
    keys = [f"user-{i}" for i in ids]
    for _ in range(2):  # second pass exercises staging-buffer reuse
        a = st_sharded.acquire_stream_strs("tb", lid_s, keys)
        b = st_single.acquire_stream_strs("tb", lid_f, keys)
        np.testing.assert_array_equal(a, b)
        clock.t += 700
    # Scalar interleave: both storages agree afterward too.
    ra = st_sharded.acquire("tb", lid_s, "user-7", 1)
    rb = st_single.acquire("tb", lid_f, "user-7", 1)
    assert ra["allowed"] == rb["allowed"]
    a = st_sharded.acquire_stream_strs("tb", lid_s, keys[:1000])
    b = st_single.acquire_stream_strs("tb", lid_f, keys[:1000])
    np.testing.assert_array_equal(a, b)
    st_sharded.close()
    st_single.close()


def test_host_route_matches_shard_hash():
    """The host C router, the one route of the sharded stream, bins int
    keys by the splitmix shard hash and string fingerprints by their h1
    stream — exactly what ``shard_of_key`` computes for scalar traffic —
    in arrival order within each shard, including the all-one-shard,
    empty-shard and empty-chunk edge cases."""
    import numpy as np

    from ratelimiter_tpu.engine.native_index import fnv_fingerprint_h1
    from ratelimiter_tpu.parallel.sharded import shard_of_int_keys

    route = TpuBatchedStorage._route_host
    n_sh = 8
    rng = np.random.default_rng(11)

    def expect(shard, order, counts, want):
        np.testing.assert_array_equal(shard, want)
        np.testing.assert_array_equal(order,
                                      np.argsort(want, kind="stable"))
        np.testing.assert_array_equal(counts,
                                      np.bincount(want, minlength=n_sh))

    # Int keys (negative ids wrap through uint64 like the scalar hash).
    keys = rng.integers(-(1 << 62), 1 << 62, 4096).astype(np.int64)
    shard, order, counts, kst = route(keys, None, None, n_sh)
    expect(shard, order, counts, shard_of_int_keys(keys, n_sh))
    np.testing.assert_array_equal(kst, keys[order])
    for k in keys[:64].tolist():
        assert shard_of_key(k, n_sh) == shard_of_int_keys(
            np.asarray([k]), n_sh)[0]

    # String fingerprints route by h1, as shard_of_key's string branch.
    names = [f"user-{i}" for i in range(512)]
    h1 = np.asarray([fnv_fingerprint_h1(n.encode(), 0) for n in names],
                    dtype=np.uint64)
    h2 = rng.integers(0, 1 << 63, len(names)).astype(np.uint64)
    shard, order, counts, h1s, h2s = route(None, h1, h2, n_sh)
    expect(shard, order, counts,
           np.asarray([shard_of_key((0, n), n_sh) for n in names]))
    np.testing.assert_array_equal(h1s, h1[order])
    np.testing.assert_array_equal(h2s, h2[order])

    # All-one-shard, a chunk smaller than the mesh, an empty chunk.
    k1 = np.full(300, 424242, dtype=np.int64)
    expect(*route(k1, None, None, n_sh)[:3], shard_of_int_keys(k1, n_sh))
    k2 = np.asarray([7], dtype=np.int64)
    s2, o2, c2, _ = route(k2, None, None, n_sh)
    assert c2.sum() == 1 and (c2 == 0).sum() == n_sh - 1
    s0, o0, c0, _ = route(np.asarray([], dtype=np.int64), None, None, n_sh)
    assert len(s0) == 0 and len(o0) == 0 and c0.sum() == 0


def test_sharded_stream_pipelining_invariant_under_concurrency(monkeypatch):
    """Per-shard pipelines (r8): decisions must be IDENTICAL whether the
    lanes run deeply pipelined (lookahead + concurrent bounded drains)
    or fully serialized chunk-by-chunk — on a many-chunk Zipf stream
    with EVICTION pressure, so the per-shard stream-order clear path
    (evictions cleared in a shard's own device stream ahead of the
    dispatch reusing the slots) is what keeps them equal."""
    from ratelimiter_tpu.storage import tpu as tpu_mod

    monkeypatch.setattr(tpu_mod, "_RELAY_CHUNK", 2048)
    monkeypatch.setattr(tpu_mod, "_RELAY_CHUNK_MAX", 2048)

    rng = np.random.default_rng(17)
    ids = rng.zipf(1.15, size=40_000).astype(np.int64) % 16_000
    cfg = RateLimitConfig(max_permits=8, window_ms=60_000, refill_rate=2.0)

    def run(lookahead, inflight):
        monkeypatch.setattr(tpu_mod, "_SHARD_LOOKAHEAD", lookahead)
        monkeypatch.setattr(tpu_mod, "_SHARD_DRAIN_INFLIGHT", inflight)
        clock = FakeClock()
        engine = ShardedDeviceEngine(slots_per_shard=512,
                                     table=LimiterTable())
        st = TpuBatchedStorage(engine=engine, clock_ms=clock)
        lid = st.register_limiter("tb", cfg)
        outs = []
        for _ in range(2):  # uniques (16K) >> slots (4K): constant churn
            outs.append(st.acquire_stream_ids("tb", lid, ids, None))
            clock.t += 1500
        st.close()
        return outs

    pipelined = run(2, 2)
    serial = run(0, 1)
    for a, b in zip(pipelined, serial):
        np.testing.assert_array_equal(a, b)


def test_sharded_pipelined_stream_matches_single_device_multichunk(
        monkeypatch):
    """Multi-chunk sharded int stream (per-shard single-device
    dispatches, concurrent drains) must decide bit-identically to the
    flat single-device stream on an eviction-free workload."""
    from ratelimiter_tpu.storage import tpu as tpu_mod

    monkeypatch.setattr(tpu_mod, "_RELAY_CHUNK", 4096)
    monkeypatch.setattr(tpu_mod, "_RELAY_CHUNK_MAX", 4096)

    rng = np.random.default_rng(23)
    ids = rng.zipf(1.2, size=32_000).astype(np.int64) % 3000
    cfg = RateLimitConfig(max_permits=12, window_ms=10_000,
                          refill_rate=20.0)

    clock_a, clock_b = FakeClock(), FakeClock()
    engine = ShardedDeviceEngine(slots_per_shard=2048,
                                 table=LimiterTable())
    st_sharded = TpuBatchedStorage(engine=engine, clock_ms=clock_a)
    st_single = TpuBatchedStorage(num_slots=1 << 14, clock_ms=clock_b)
    lid_a = st_sharded.register_limiter("tb", cfg)
    lid_b = st_single.register_limiter("tb", cfg)
    for _ in range(2):
        a = st_sharded.acquire_stream_ids("tb", lid_a, ids, None)
        b = st_single.acquire_stream_ids("tb", lid_b, ids, None)
        np.testing.assert_array_equal(a, b)
        clock_a.t += 900
        clock_b.t += 900
    # Per-shard dispatch routes are in the decision trace.
    paths = {r.get("path") for r in st_sharded.trace.snapshot()["recent"]}
    assert any(p and p.startswith("sharded|") for p in paths), paths
    st_sharded.close()
    st_single.close()


def test_sharded_stream_routes_every_chunk_on_host(monkeypatch):
    """The route pass is a rule, not an election: every chunk of a
    large sharded stream is binned once by the host router on the
    caller (one ``route`` span per chunk, no election verdict in the
    flight recorder), and the stream decides as the single-device one."""
    from ratelimiter_tpu.storage import tpu as tpu_mod

    monkeypatch.setattr(tpu_mod, "_RELAY_CHUNK", 1 << 16)
    monkeypatch.setattr(tpu_mod, "_RELAY_CHUNK_MAX", 1 << 16)
    rng = np.random.default_rng(29)
    ids = rng.zipf(1.2, size=200_000).astype(np.int64) % 10_000
    cfg = RateLimitConfig(max_permits=50, window_ms=60_000,
                          refill_rate=10.0)
    calls = []
    real = TpuBatchedStorage._route_host

    def counted(*args):
        calls.append(len(args[0]))
        return real(*args)

    monkeypatch.setattr(TpuBatchedStorage, "_route_host",
                        staticmethod(counted))
    engine = ShardedDeviceEngine(slots_per_shard=4096, table=LimiterTable())
    st = TpuBatchedStorage(engine=engine, clock_ms=FakeClock())
    single = TpuBatchedStorage(num_slots=1 << 16, clock_ms=FakeClock())
    try:
        got = st.acquire_stream_ids("tb", st.register_limiter("tb", cfg),
                                    ids, None)
        want = single.acquire_stream_ids(
            "tb", single.register_limiter("tb", cfg), ids, None)
        route = st.registry.meters()["ratelimiter.stream.route"]
        events = [e for e in st._recorder.events()
                  if e.get("kind") == "sharded.route_elect"]
    finally:
        st.close()
        single.close()
    np.testing.assert_array_equal(got, want)
    assert calls == [1 << 16] * 3 + [200_000 - 3 * (1 << 16)]
    assert route.count() == len(calls)
    assert events == []
