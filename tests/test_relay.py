"""Differential tests for the relay steps (ops/relay.py) and the native
index's duplicate-structure outputs
(native/slot_index.cpp:assign_batch_uniques).

The relay paths must decide exactly like the sorted flat step on the
same batch and leave identical device state — that equivalence is what
lets the stream path delete the device-side sort/scan.  The C++ words
must match a straightforward Python reconstruction of ranks and last
flags, including the clamp sentinel.
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


def _truth_structure(slots):
    """(rank, uidx, unique slots in first-appearance order, counts)."""
    seen, order, cnt = {}, [], {}
    rank = np.empty(len(slots), dtype=np.int32)
    uidx = np.empty(len(slots), dtype=np.int32)
    for i, s in enumerate(slots):
        if s not in seen:
            seen[s] = len(order)
            order.append(s)
        r = cnt.get(s, 0)
        cnt[s] = r + 1
        rank[i] = r
        uidx[i] = seen[s]
    return rank, uidx, np.asarray(order), np.asarray(
        [cnt[s] for s in order])


def _make_words(slots, rank_bits):
    rank, uidx, _, counts = _truth_structure(slots)
    clamp = (1 << rank_bits) - 1
    # True last occurrence (the C++ words path flags the actual last
    # position regardless of clamping).
    last = rank + 1 == counts[uidx]
    return (np.asarray(slots, np.uint32) << np.uint32(rank_bits + 1)
            | (np.minimum(rank, clamp).astype(np.uint32) << np.uint32(1))
            | last.astype(np.uint32))


def _make_uwords(slots, rank_bits):
    _, _, order, counts = _truth_structure(slots)
    clamp = (1 << rank_bits) - 1
    return (order.astype(np.uint32) << np.uint32(rank_bits + 1)
            | np.minimum(counts, clamp).astype(np.uint32) << np.uint32(1))


def _flat(engine, algo, slots, lid, now):
    fn = (engine.sw_flat_dispatch if algo == "sw"
          else engine.tb_flat_dispatch)
    return np.unpackbits(np.asarray(
        fn(slots, np.int32(lid), None, now)))[: len(slots)].astype(bool)


def _relay(engine, algo, slots, lid, now):
    words = _make_words(slots, engine.rank_bits)
    fn = (engine.sw_relay_dispatch if algo == "sw"
          else engine.tb_relay_dispatch)
    return np.unpackbits(np.asarray(
        fn(words, np.int32(lid), now)))[: len(slots)].astype(bool)


def _digest(engine, algo, slots, lid, now, out_dtype=np.uint8):
    rank, uidx, order, _ = _truth_structure(slots)
    uwords = _make_uwords(slots, engine.rank_bits)
    fn = (engine.sw_relay_counts_dispatch if algo == "sw"
          else engine.tb_relay_counts_dispatch)
    counts = np.asarray(fn(uwords, np.int32(lid), now, out_dtype))
    return rank < counts[: len(order)].astype(np.int32)[uidx]


def _state(engine, algo):
    return np.asarray(engine.sw_packed if algo == "sw"
                      else engine.tb_packed)


@pytest.mark.parametrize("algo,lid", [("sw", 1), ("tb", 2), ("tb", 3)])
def test_relay_matches_flat(table, algo, lid):
    """Duplicate-heavy random batches across window/refill boundaries:
    relay bits and digest counts must reproduce the sorted flat step's
    decisions bit-for-bit and leave identical state."""
    rng = np.random.default_rng(11)
    engines = [DeviceEngine(num_slots=64, table=table) for _ in range(3)]
    for now in (1_000_000, 1_000_123, 1_000_750, 1_004_000):
        slots = rng.integers(0, 9, 240).astype(np.int32)
        a = _flat(engines[0], algo, slots, lid, now)
        b = _relay(engines[1], algo, slots, lid, now)
        c = _digest(engines[2], algo, slots, lid, now)
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, c)
        np.testing.assert_array_equal(
            _state(engines[0], algo), _state(engines[1], algo))
        np.testing.assert_array_equal(
            _state(engines[0], algo), _state(engines[2], algo))


@pytest.mark.parametrize("algo,lid", [("sw", 1), ("tb", 3)])
def test_relay_clamped_ranks(table, algo, lid):
    """One segment longer than the rank clamp: decisions and state must
    still match the flat step.  The sentinel is deny-only ONLY when the
    clamp exceeds max_permits (here clamp 7 > max_permits 5 and 3 —
    exactly the precondition relay_usable() enforces)."""
    import functools

    import jax
    from ratelimiter_tpu.ops import relay

    rb = 3  # forced small clamp; engines would derive 24 at 64 slots
    eng = DeviceEngine(num_slots=64, table=table)
    slots = np.zeros(32, dtype=np.int32)  # one 32-long segment
    now = 1_000_000
    a = _flat(eng, algo, slots, lid, now)

    bits_fn = jax.jit(functools.partial(
        relay.sw_relay_bits if algo == "sw" else relay.tb_relay_bits,
        rank_bits=rb))
    counts_fn = jax.jit(functools.partial(
        relay.sw_relay_counts if algo == "sw" else relay.tb_relay_counts,
        rank_bits=rb))
    state0 = (eng.sw_packed if algo == "sw" else eng.tb_packed) * 0
    arrays = table.device_arrays

    st_b, bits = bits_fn(state0, arrays, _make_words(slots, rb),
                         np.int32(lid), now)
    b = np.unpackbits(np.asarray(bits))[:32].astype(bool)
    rank, uidx, order, _ = _truth_structure(slots)
    st_c, counts = counts_fn(state0, arrays, _make_uwords(slots, rb),
                             np.int32(lid), now)
    c = rank < np.asarray(counts)[: len(order)].astype(np.int32)[uidx]
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(a, c)
    truth_state = _state(eng, algo)
    np.testing.assert_array_equal(truth_state[:1], np.asarray(st_b)[:1])
    np.testing.assert_array_equal(truth_state[:1], np.asarray(st_c)[:1])


@pytest.mark.parametrize("algo,lid", [("sw", 1), ("tb", 2), ("tb", 3)])
def test_relay_digest_both_backends_match_flat(table, algo, lid,
                                               monkeypatch):
    """The digest parity of test_relay_matches_flat, run on BOTH digest
    backends: the composed-XLA step and the fused Pallas relay kernel
    (interpret mode, elected through the real engine dispatch).  Both
    must reproduce the sorted flat step bit-for-bit and leave identical
    state."""
    from ratelimiter_tpu.ops.pallas import election
    from ratelimiter_tpu.ops.pallas import relay_step as rs

    monkeypatch.setattr(rs, "_INTERPRET", True)
    monkeypatch.setattr(rs, "_probe_ok", None)
    election.reset_for_tests()
    try:
        rng = np.random.default_rng(11)
        num_slots = 512  # fused floor: >= 2 Pallas blocks
        e_flat = DeviceEngine(num_slots=num_slots, table=table)
        e_xla = DeviceEngine(num_slots=num_slots, table=table)
        e_fused = DeviceEngine(num_slots=num_slots, table=table)
        e_xla._relay_fused_ok = lambda algo, u: False  # force composed
        assert e_fused._relay_fused_ok(algo, num_slots)
        rb = e_fused.rank_bits
        dispatch_of = {
            e_xla: (e_xla.sw_relay_counts_dispatch if algo == "sw"
                    else e_xla.tb_relay_counts_dispatch),
            e_fused: (e_fused.sw_relay_counts_dispatch if algo == "sw"
                      else e_fused.tb_relay_counts_dispatch),
        }

        def digest_sorted(engine, slots, now):
            rank, uidx, order, counts = _truth_structure(slots)
            perm = np.argsort(order)
            inv = np.empty_like(perm)
            inv[perm] = np.arange(len(perm))
            clamp = (1 << rb) - 1
            uw = np.full(num_slots, 0xFFFFFFFF, dtype=np.uint32)
            uw[:len(order)] = (
                (order[perm].astype(np.uint32) << np.uint32(rb + 1))
                | (np.minimum(counts[perm], clamp).astype(np.uint32)
                   << np.uint32(1)))
            out = np.asarray(dispatch_of[engine](
                uw, np.int32(lid), now, np.uint8, slots_sorted=True))
            return rank < out[:len(order)].astype(np.int32)[inv[uidx]]

        for now in (1_000_000, 1_000_123, 1_000_750, 1_004_000):
            slots = rng.integers(0, 9, 240).astype(np.int32)
            a = _flat(e_flat, algo, slots, lid, now)
            b = digest_sorted(e_xla, slots, now)
            c = digest_sorted(e_fused, slots, now)
            np.testing.assert_array_equal(a, b)
            np.testing.assert_array_equal(a, c)
            np.testing.assert_array_equal(
                _state(e_flat, algo), _state(e_xla, algo))
            np.testing.assert_array_equal(
                _state(e_flat, algo), _state(e_fused, algo))
        assert any(len(k) > 2 and k[2] == "fused"
                   for k in e_fused._relay_counts)
    finally:
        election.reset_for_tests()


def test_relay_usable_gate():
    """A policy whose max_permits exceeds the clamp must disable relay."""
    t = LimiterTable()
    t.register(RateLimitConfig(max_permits=5, window_ms=1000))
    eng = DeviceEngine(num_slots=1 << 20, table=t)  # rank_bits 10, clamp 1023
    assert eng.relay_usable()
    t.register(RateLimitConfig(max_permits=2000, window_ms=1000))
    assert not eng.relay_usable()


def test_native_uniques_match_truth():
    """C++ duplicate structure == Python reconstruction, including count
    clamping, for all three key flavors."""
    from ratelimiter_tpu.engine.native_index import (
        NativeSlotIndex, native_available)

    if not native_available():
        pytest.skip("native index unavailable")
    rng = np.random.default_rng(5)
    rb = 3
    for flavor in ("int", "str", "multi"):
        ix_u = NativeSlotIndex(256)
        ix_ref = NativeSlotIndex(256)
        keys = rng.integers(0, 17, 400)
        if flavor == "int":
            uwords, uidx, rank, _ = ix_u.assign_batch_ints_uniques(keys, 1, rb)
            slots, _ = ix_ref.assign_batch_ints(keys, 1)
        elif flavor == "str":
            skeys = [f"k{v}" for v in keys]
            uwords, uidx, rank, _ = ix_u.assign_batch_strs_uniques(
                skeys, 1, rb)
            slots, _ = ix_ref.assign_batch_strs(skeys, 1)
        else:
            lids = rng.integers(1, 4, 400)
            uwords, uidx, rank, _ = ix_u.assign_batch_ints_multi_uniques(
                keys, lids, rb)
            slots, _ = ix_ref.assign_batch_ints_multi(keys, lids)
        np.testing.assert_array_equal(uwords, _make_uwords(slots, rb),
                                      err_msg=flavor)
        t_rank, t_uidx, _, _ = _truth_structure(slots)
        np.testing.assert_array_equal(rank, t_rank, err_msg=flavor)
        np.testing.assert_array_equal(uidx, t_uidx, err_msg=flavor)


@pytest.mark.parametrize("force_mode", ["digest", "bits"])
def test_stream_relay_modes_match_batch_path(monkeypatch, force_mode):
    """Storage-level: the relay stream (either mode) must decide exactly
    like acquire_many_ids over the same chunks at the same timestamps."""
    import ratelimiter_tpu.storage.tpu as tpu_mod
    from ratelimiter_tpu.storage.tpu import TpuBatchedStorage

    if force_mode == "bits":
        # Disable digest election so the per-request reconstruction runs.
        monkeypatch.setattr(
            TpuBatchedStorage, "_stream_relay",
            _forced_bits_stream(TpuBatchedStorage._stream_relay))
    rng = np.random.default_rng(21)
    now = [5_000_000]
    st_a = TpuBatchedStorage(num_slots=1 << 12, clock_ms=lambda: now[0])
    st_b = TpuBatchedStorage(num_slots=1 << 12, clock_ms=lambda: now[0])
    cfg = RateLimitConfig(max_permits=6, window_ms=1000, refill_rate=4.0)
    lid_a = st_a.register_limiter("tb", cfg)
    lid_b = st_b.register_limiter("tb", cfg)
    monkeypatch.setattr(tpu_mod, "_RELAY_CHUNK", 256)
    monkeypatch.setattr(tpu_mod, "_RELAY_CHUNK_MAX", 256)
    for rep in range(4):
        ids = rng.integers(0, 40, 700)
        a = st_a.acquire_stream_ids("tb", lid_a, ids, None, batch=256,
                                    subbatches=1)
        res = np.empty(700, dtype=bool)
        for i in range(0, 700, 256):
            res[i:i + 256] = st_b.acquire_many_ids(
                "tb", lid_b, ids[i:i + 256],
                np.ones(len(ids[i:i + 256]), np.int64))["allowed"]
        np.testing.assert_array_equal(a, res, err_msg=f"rep {rep}")
        now[0] += 237
    st_a.close()
    st_b.close()


@pytest.mark.parametrize("algo", ["sw", "tb"])
def test_stream_relay_soak_vs_oracle(algo):
    """Randomized multi-pass soak: the relay stream (mode elected per
    chunk) against the executable oracle, with duplicate-heavy traffic,
    window rolls, refills, and resets between passes."""
    import random

    from ratelimiter_tpu.semantics import (
        SlidingWindowOracle,
        TokenBucketOracle,
    )
    from ratelimiter_tpu.storage.tpu import TpuBatchedStorage

    now = [3_000_000]
    st = TpuBatchedStorage(num_slots=1 << 12, clock_ms=lambda: now[0])
    if algo == "sw":
        cfg = RateLimitConfig(max_permits=6, window_ms=1000,
                              enable_local_cache=False)
        oracle = SlidingWindowOracle(cfg)
    else:
        cfg = RateLimitConfig(max_permits=8, window_ms=1500,
                              refill_rate=5.0)
        oracle = TokenBucketOracle(cfg)
    lid = st.register_limiter(algo, cfg)
    rng = np.random.default_rng(77)
    pyrng = random.Random(77)
    for step in range(12):
        now[0] += pyrng.randrange(0, 900)
        ids = rng.integers(0, 30, 400)
        got = st.acquire_stream_ids(algo, lid, ids, None)
        for j, k in enumerate(ids):
            want = oracle.try_acquire(f"id:{k}", 1, now[0]).allowed
            assert got[j] == want, (algo, step, j)
        if pyrng.random() < 0.3:
            k = int(pyrng.choice(list(ids)))
            st.reset_key(algo, lid, k)  # int user key, same namespace
            oracle.reset(f"id:{k}", now[0])
    st.close()


@pytest.mark.parametrize("algo", ["sw", "tb"])
def test_resident_lid_map_survives_eviction_churn(monkeypatch, algo):
    """Multi-tenant digest with device-resident lids: a slot evicted and
    reassigned to a key of a DIFFERENT tenant must get its new lid
    re-uploaded (tracked by _lid_known, invalidated via _clear_slots) —
    decisions must match the chunked batch path exactly throughout."""
    import ratelimiter_tpu.storage.tpu as tpu_mod
    from ratelimiter_tpu.storage.tpu import TpuBatchedStorage

    now = [2_000_000]
    # Tiny slot table so the stream constantly evicts and reassigns.
    st_a = TpuBatchedStorage(num_slots=32, clock_ms=lambda: now[0])
    st_b = TpuBatchedStorage(num_slots=32, clock_ms=lambda: now[0])
    if algo == "sw":
        cfgs = [RateLimitConfig(max_permits=3 + i, window_ms=1000,
                                enable_local_cache=False) for i in range(3)]
    else:
        cfgs = [RateLimitConfig(max_permits=3 + i, window_ms=1000,
                                refill_rate=2.0 + i) for i in range(3)]
    lids_a = np.asarray([st_a.register_limiter(algo, c) for c in cfgs])
    lids_b = np.asarray([st_b.register_limiter(algo, c) for c in cfgs])
    monkeypatch.setattr(tpu_mod, "_RELAY_CHUNK", 64)
    monkeypatch.setattr(tpu_mod, "_RELAY_CHUNK_MAX", 64)
    rng = np.random.default_rng(5)
    for rep in range(6):
        # 24 live (lid,key) pairs per rep, window sliding by 8 each rep:
        # old pairs evict (32-slot table) and their slots get reassigned
        # to pairs of OTHER tenants across reps — the lid re-upload path.
        pairs = rng.integers(rep * 8, rep * 8 + 24, 256)
        ids = pairs
        tl = pairs % 3
        a = st_a.acquire_stream_ids(algo, lids_a[tl], ids, None)
        res = np.empty(256, dtype=bool)
        for i in range(0, 256, 64):
            chunk_lids = lids_b[tl[i:i + 64]]
            got = st_b.acquire_stream_ids(
                algo, chunk_lids, ids[i:i + 64], np.ones(64, np.int64))
            res[i:i + 64] = got
        np.testing.assert_array_equal(a, res, err_msg=f"rep {rep}")
        now[0] += 173
    st_a.close()
    st_b.close()


@pytest.mark.parametrize("force_mode", ["digest", "bits"])
@pytest.mark.parametrize("multi_lid", [False, True])
def test_sharded_relay_matches_single_device(monkeypatch, force_mode,
                                             multi_lid):
    """The sharded relay stream (8-device CPU mesh, either wire mode,
    single- and multi-tenant) must decide exactly like the single-device
    relay on the same stream at the same timestamps."""
    import ratelimiter_tpu.storage.tpu as tpu_mod
    from ratelimiter_tpu.parallel import ShardedDeviceEngine
    from ratelimiter_tpu.storage.tpu import TpuBatchedStorage

    now = [7_000_000]
    table_s, table_f = LimiterTable(), LimiterTable()
    cfgs = [RateLimitConfig(max_permits=4 + i, window_ms=1000,
                            refill_rate=3.0 + i) for i in range(3)]
    lids_s = [table_s.register(c) for c in cfgs]
    lids_f = [table_f.register(c) for c in cfgs]
    eng = ShardedDeviceEngine(slots_per_shard=64, table=table_s)
    st_s = TpuBatchedStorage(engine=eng, clock_ms=lambda: now[0])
    st_f = TpuBatchedStorage(num_slots=1 << 12, table=table_f,
                             clock_ms=lambda: now[0])
    monkeypatch.setattr(tpu_mod, "_RELAY_CHUNK", 128)
    monkeypatch.setattr(tpu_mod, "_RELAY_CHUNK_MAX", 128)
    if force_mode == "bits":
        for e in (eng, st_f.engine):
            monkeypatch.setattr(type(e), "counts_dtype",
                                lambda self: None, raising=True)
    rng = np.random.default_rng(33)
    for rep in range(3):
        ids = rng.integers(0, 60, 500)
        if multi_lid:
            larr_s = np.asarray(lids_s)[rng.integers(0, 3, 500)]
            larr_f = np.asarray(lids_f)[(larr_s - lids_s[0])]
            a = st_s.acquire_stream_ids("tb", larr_s, ids, None)
            b = st_f.acquire_stream_ids("tb", larr_f, ids, None)
        else:
            a = st_s.acquire_stream_ids("tb", lids_s[1], ids, None)
            b = st_f.acquire_stream_ids("tb", lids_f[1], ids, None)
        np.testing.assert_array_equal(a, b, err_msg=f"rep {rep}")
        now[0] += 321
    st_s.close()
    st_f.close()


def _forced_bits_stream(orig):
    def wrapper(self, algo, lid, assign_uniques, n, lid_arr=None):
        eng = self.engine
        real = eng.counts_dtype

        eng.counts_dtype = lambda: None  # digest never elected
        try:
            return orig(self, algo, lid, assign_uniques, n, lid_arr)
        finally:
            eng.counts_dtype = real
    return wrapper


def test_held_pins_block_concurrent_eviction():
    """The assign->dispatch window contract: pinned slots must survive a
    concurrent assign's eviction pressure (the concurrent assign either
    finds other victims or refuses), for the native and Python indexes."""
    from ratelimiter_tpu.engine.native_index import (
        NativeSlotIndex, native_available)
    from ratelimiter_tpu.engine.slots import SlotIndex

    indexes = [SlotIndex(4)]
    if native_available():
        indexes.append(NativeSlotIndex(4))
    for ix in indexes:
        slots = [ix.assign((1, k))[0] for k in range(4)]  # full table
        ix.pin_batch(np.asarray(slots[:3], dtype=np.int32))
        # Only the unpinned slot may be evicted.
        s, ev = ix.assign((1, 99))
        assert ev == slots[3] and s == slots[3], (type(ix).__name__, s, ev)
        ix.pin_batch(np.asarray([s], dtype=np.int32))
        with pytest.raises(RuntimeError):
            ix.assign((1, 100))  # everything pinned now
        ix.unpin_batch(np.asarray(slots[:3] + [s], dtype=np.int32))
        s2, ev2 = ix.assign((1, 100))  # unpinned again: eviction works
        assert ev2 is not None


# ---------------------------------------------------------------------------
# Weighted-permit relay (ops/relay.py:*_relay_weighted)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("algo", ["sw", "tb"])
def test_stream_weighted_matches_batch_path(monkeypatch, algo):
    """The weighted relay stream must decide exactly like acquire_many_ids
    over the same chunks at the same timestamps — including mixed
    single/multi segments and the skip recurrence."""
    import ratelimiter_tpu.storage.tpu as tpu_mod
    from ratelimiter_tpu.storage.tpu import TpuBatchedStorage

    rng = np.random.default_rng(31)
    now = [5_000_000]
    st_a = TpuBatchedStorage(num_slots=1 << 12, clock_ms=lambda: now[0])
    st_b = TpuBatchedStorage(num_slots=1 << 12, clock_ms=lambda: now[0])
    if algo == "sw":
        cfg = RateLimitConfig(max_permits=6, window_ms=1000,
                              enable_local_cache=False)
    else:
        cfg = RateLimitConfig(max_permits=9, window_ms=1000,
                              refill_rate=4.0)
    lid_a = st_a.register_limiter(algo, cfg)
    lid_b = st_b.register_limiter(algo, cfg)
    monkeypatch.setattr(tpu_mod, "_RELAY_CHUNK", 256)
    monkeypatch.setattr(tpu_mod, "_RELAY_CHUNK_MAX", 256)
    for rep in range(4):
        ids = rng.integers(0, 40, 768)
        perms = rng.integers(1, 11, 768).astype(np.int64)
        a = st_a.acquire_stream_ids(algo, lid_a, ids, perms)
        res = np.empty(768, dtype=bool)
        for i in range(0, 768, 256):
            res[i:i + 256] = st_b.acquire_many_ids(
                algo, lid_b, ids[i:i + 256],
                perms[i:i + 256])["allowed"]
        np.testing.assert_array_equal(a, res, err_msg=f"rep {rep}")
        now[0] += 431
    st_a.close()
    st_b.close()


def test_stream_weighted_skip_semantics():
    """A denied large request consumes nothing — a later smaller request
    of the SAME key in the SAME chunk can still pass (the reference's
    Lua semantics; a prefix-sum closed form would get this wrong)."""
    from ratelimiter_tpu.storage.tpu import TpuBatchedStorage

    now = [9_000_000]
    st = TpuBatchedStorage(num_slots=1 << 12, clock_ms=lambda: now[0])
    cfg = RateLimitConfig(max_permits=10, window_ms=1000, refill_rate=1.0)
    lid = st.register_limiter("tb", cfg)
    ids = np.asarray([7, 7, 7], dtype=np.int64)
    perms = np.asarray([8, 5, 2], dtype=np.int64)
    got = st.acquire_stream_ids("tb", lid, ids, perms)
    np.testing.assert_array_equal(got, [True, False, True])
    st.close()


@pytest.mark.parametrize("algo", ["sw", "tb"])
def test_stream_weighted_fallback_deep_segments(monkeypatch, algo):
    """A chunk whose deepest segment exceeds _WREL_MAX_R must take the
    sorted-flat fallback and still match the batch path exactly."""
    import ratelimiter_tpu.storage.tpu as tpu_mod
    from ratelimiter_tpu.storage.tpu import TpuBatchedStorage

    monkeypatch.setattr(tpu_mod, "_WREL_MAX_R", 4)
    monkeypatch.setattr(tpu_mod, "_RELAY_CHUNK", 128)
    monkeypatch.setattr(tpu_mod, "_RELAY_CHUNK_MAX", 128)
    rng = np.random.default_rng(41)
    now = [6_000_000]
    st_a = TpuBatchedStorage(num_slots=1 << 12, clock_ms=lambda: now[0])
    st_b = TpuBatchedStorage(num_slots=1 << 12, clock_ms=lambda: now[0])
    if algo == "sw":
        cfg = RateLimitConfig(max_permits=7, window_ms=1000,
                              enable_local_cache=False)
    else:
        cfg = RateLimitConfig(max_permits=12, window_ms=1000,
                              refill_rate=6.0)
    lid_a = st_a.register_limiter(algo, cfg)
    lid_b = st_b.register_limiter(algo, cfg)
    # Hot key: ~1/3 of traffic -> segments far deeper than the forced cap.
    ids = np.where(rng.random(384) < 0.34, 3,
                   rng.integers(0, 30, 384)).astype(np.int64)
    perms = rng.integers(1, 9, 384).astype(np.int64)
    a = st_a.acquire_stream_ids(algo, lid_a, ids, perms)
    res = np.empty(384, dtype=bool)
    for i in range(0, 384, 128):
        res[i:i + 128] = st_b.acquire_many_ids(
            algo, lid_b, ids[i:i + 128], perms[i:i + 128])["allowed"]
    np.testing.assert_array_equal(a, res)
    st_a.close()
    st_b.close()


@pytest.mark.parametrize("algo", ["sw", "tb"])
def test_stream_weighted_soak_vs_oracle(algo):
    """Randomized weighted soak against the executable oracle: mixed
    permits, duplicate-heavy traffic, rolls/refills, resets."""
    import random

    from ratelimiter_tpu.semantics import (
        SlidingWindowOracle,
        TokenBucketOracle,
    )
    from ratelimiter_tpu.storage.tpu import TpuBatchedStorage

    now = [3_000_000]
    st = TpuBatchedStorage(num_slots=1 << 12, clock_ms=lambda: now[0])
    if algo == "sw":
        cfg = RateLimitConfig(max_permits=6, window_ms=1000,
                              enable_local_cache=False)
        oracle = SlidingWindowOracle(cfg)
    else:
        cfg = RateLimitConfig(max_permits=8, window_ms=1500,
                              refill_rate=5.0)
        oracle = TokenBucketOracle(cfg)
    lid = st.register_limiter(algo, cfg)
    rng = np.random.default_rng(87)
    pyrng = random.Random(87)
    for step in range(12):
        now[0] += pyrng.randrange(0, 900)
        ids = rng.integers(0, 30, 400)
        perms = rng.integers(1, 7, 400).astype(np.int64)
        got = st.acquire_stream_ids(algo, lid, ids, perms)
        for j, k in enumerate(ids):
            want = oracle.try_acquire(f"id:{k}", int(perms[j]),
                                      now[0]).allowed
            assert got[j] == want, (algo, step, j)
        if pyrng.random() < 0.3:
            k = int(pyrng.choice(list(ids)))
            st.reset_key(algo, lid, k)
            oracle.reset(f"id:{k}", now[0])
    st.close()


@pytest.mark.parametrize("algo", ["sw", "tb"])
def test_stream_weighted_strs_matches_batch_path(monkeypatch, algo):
    """String-key weighted streams run the same weighted relay loop; the
    decisions must match acquire_many on identical chunks."""
    import ratelimiter_tpu.storage.tpu as tpu_mod
    from ratelimiter_tpu.storage.tpu import TpuBatchedStorage

    rng = np.random.default_rng(53)
    now = [4_000_000]
    st_a = TpuBatchedStorage(num_slots=1 << 12, clock_ms=lambda: now[0])
    st_b = TpuBatchedStorage(num_slots=1 << 12, clock_ms=lambda: now[0])
    if algo == "sw":
        cfg = RateLimitConfig(max_permits=6, window_ms=1000,
                              enable_local_cache=False)
    else:
        cfg = RateLimitConfig(max_permits=9, window_ms=1000,
                              refill_rate=4.0)
    lid_a = st_a.register_limiter(algo, cfg)
    lid_b = st_b.register_limiter(algo, cfg)
    monkeypatch.setattr(tpu_mod, "_RELAY_CHUNK", 256)
    monkeypatch.setattr(tpu_mod, "_RELAY_CHUNK_MAX", 256)
    for rep in range(3):
        keys = [f"u{int(k)}" for k in rng.integers(0, 35, 512)]
        perms = rng.integers(1, 11, 512).astype(np.int64)
        a = st_a.acquire_stream_strs(algo, lid_a, keys, perms)
        res = np.empty(512, dtype=bool)
        for i in range(0, 512, 256):
            got = st_b.acquire_many(
                algo, [lid_b] * 256, keys[i:i + 256],
                list(perms[i:i + 256]))
            res[i:i + 256] = got["allowed"]
        np.testing.assert_array_equal(a, res, err_msg=f"rep {rep}")
        now[0] += 433
    st_a.close()
    st_b.close()


def test_sorted_digest_stream_matches_unsorted(monkeypatch):
    """Slot-sorted digest dispatches (u >= _SORT_UNIQUES_MIN triggers the
    C radix sort + uidx remap + presorted scatter path) decide exactly
    like the unsorted path on the same stream."""
    import ratelimiter_tpu.storage.tpu as tpu_mod
    from ratelimiter_tpu.engine.native_index import native_available
    from ratelimiter_tpu.storage import TpuBatchedStorage

    if not native_available():
        pytest.skip("needs the native library")
    now = [1_000_000]
    rng = np.random.default_rng(8)
    n = 1 << 15
    # Zipf-ish duplication with > 4096 uniques per chunk.
    ids = rng.integers(0, 12_000, n).astype(np.int64)

    # Force the sorted path on CPU (the device sweep itself is gated to
    # TPU; the XLA fallback scatter is order-blind, so this exercises
    # sort + uidx remap + dispatch + reconstruction end to end).
    monkeypatch.setattr(tpu_mod, "_presorted_scatter_usable",
                        lambda eng, algo, padded: True)

    def run(sort_min):
        monkeypatch.setattr(tpu_mod, "_SORT_UNIQUES_MIN", sort_min)
        st = TpuBatchedStorage(num_slots=1 << 15, clock_ms=lambda: now[0])
        lid = st.register_limiter("tb", RateLimitConfig(
            max_permits=5, window_ms=60_000, refill_rate=1.0))
        outs = [st.acquire_stream_ids("tb", lid, ids, None)
                for _ in range(2)]
        st.close()
        return outs

    sorted_outs = run(1 << 12)   # sorting active
    unsorted_outs = run(1 << 62)  # threshold unreachable: never sorts
    for a, b in zip(sorted_outs, unsorted_outs):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("host_parallel", [4, 0])
def test_partitioned_sorted_digest_stream_matches_unsorted(monkeypatch,
                                                           host_parallel):
    """The sorted digest stream on a partitioned index: each partition
    slot-sorts its uniques inside its own walk job, so no chunk takes the
    serial sort (``sort`` counts 0, ``index_sort`` one per chunk, the
    prefetched chunk's too), and the decisions equal the never-sort
    run's and the oracle's.  A single index (``host_parallel=0``) keeps
    the serial sort, one per chunk."""
    import ratelimiter_tpu.storage.tpu as tpu_mod
    from ratelimiter_tpu.engine.native_index import native_available
    from ratelimiter_tpu.semantics import TokenBucketOracle
    from ratelimiter_tpu.storage import TpuBatchedStorage

    if not native_available():
        pytest.skip("needs the native library")
    rng = np.random.default_rng(8)
    n = 1 << 15
    # Zipf-ish duplication with > 4096 uniques per chunk.
    ids = rng.integers(0, 12_000, n).astype(np.int64)
    cfg = RateLimitConfig(max_permits=5, window_ms=60_000, refill_rate=1.0)
    # Force the sorted path on CPU, as in the test above, and cut the
    # first chunk short so the second walks on the prefetch worker.
    monkeypatch.setattr(tpu_mod, "_presorted_scatter_usable",
                        lambda eng, algo, padded: True)
    monkeypatch.setattr(tpu_mod, "_RELAY_CHUNK", 1 << 13)
    calls = 2

    def run(sort_min):
        monkeypatch.setattr(tpu_mod, "_SORT_UNIQUES_MIN", sort_min)
        now = [1_000_000]
        st = TpuBatchedStorage(num_slots=1 << 15,
                               host_parallel=host_parallel,
                               clock_ms=lambda: now[0])
        assert st._host_parallel == host_parallel
        lid = st.register_limiter("tb", cfg)
        st.stream_stats = stats = []
        outs = []
        for _ in range(calls):
            outs.append(st.acquire_stream_ids("tb", lid, ids, None))
            now[0] += 700
        meters = st.registry.meters()
        counts = {s: meters[f"ratelimiter.stream.{s}"].count()
                  for s in ("sort", "index_sort")}
        st.close()
        return outs, [r["mode"] for r in stats], counts

    outs, modes, counts = run(1 << 12)  # sorting active
    never, never_modes, never_counts = run(1 << 62)  # never sorts
    assert len(modes) == 2 * calls  # two chunks per call
    assert modes == ["digest-sorted"] * len(modes)
    assert "digest-sorted" not in never_modes
    assert never_counts == {"sort": 0, "index_sort": 0}
    want = ({"sort": 0, "index_sort": len(modes)} if host_parallel
            else {"sort": len(modes), "index_sort": 0})
    assert counts == want
    oracle = TokenBucketOracle(cfg)
    now = 1_000_000
    for c, (a, b) in enumerate(zip(outs, never)):
        np.testing.assert_array_equal(a, b, err_msg=f"call {c}")
        expect = [oracle.try_acquire(f"id:{k}", 1, now).allowed for k in ids]
        np.testing.assert_array_equal(a, expect, err_msg=f"call {c}")
        now += 700


@pytest.mark.parametrize("algo", ["sw", "tb"])
def test_uniform_chunk_sorted_digest_matches_words_mode(monkeypatch, algo):
    """A chunk of distinct keys (u/n = 1, the uniform cell's shape) with
    no link profile goes to the sorted digest step — the walk job sorts
    it and the tile sweep (interpret mode) writes its rows — and decides
    bit-identically to words mode, leaving the same state."""
    import ratelimiter_tpu.storage.tpu as tpu_mod
    from ratelimiter_tpu.engine.native_index import native_available
    from ratelimiter_tpu.ops.pallas import block_scatter
    from ratelimiter_tpu.storage import TpuBatchedStorage

    if not native_available():
        pytest.skip("needs the native library")
    monkeypatch.setattr(block_scatter, "_INTERPRET", True)
    monkeypatch.setattr(block_scatter, "_probe_ok", None)
    monkeypatch.setattr(tpu_mod, "_SORT_UNIQUES_MIN", 256)
    now = [1_000_000]
    rng = np.random.default_rng(19)
    cfg = (RateLimitConfig(max_permits=2, window_ms=1000,
                           enable_local_cache=False) if algo == "sw"
           else RateLimitConfig(max_permits=2, window_ms=1000,
                                refill_rate=1.0))
    stores = []
    for _ in range(2):
        st = TpuBatchedStorage(num_slots=1 << 12, clock_ms=lambda: now[0])
        stores.append((st, st.register_limiter(algo, cfg)))
    (srt, lid_s), (words, lid_w) = stores
    srt.stream_stats = stats = []
    bits = _forced_bits_stream(TpuBatchedStorage._stream_relay)
    for rep in range(3):
        ids = rng.permutation(3000)[:1024]  # every id distinct
        a = srt.acquire_stream_ids(algo, lid_s, ids, None)
        monkeypatch.setattr(TpuBatchedStorage, "_stream_relay", bits)
        b = words.acquire_stream_ids(algo, lid_w, ids, None)
        monkeypatch.undo()
        monkeypatch.setattr(block_scatter, "_INTERPRET", True)
        monkeypatch.setattr(tpu_mod, "_SORT_UNIQUES_MIN", 256)
        np.testing.assert_array_equal(a, b, err_msg=f"rep {rep}")
        now[0] += 400
    assert [r["mode"] for r in stats] == ["digest-sorted"] * 3
    np.testing.assert_array_equal(_state(srt.engine, algo),
                                  _state(words.engine, algo))
    for st, _ in stores:
        st.close()


def test_sort_uniques_parity():
    """rl_sort_uniques: words end up slot-ascending, the multiset of
    words is preserved, and the remapped uidx points every request at
    its original word."""
    from ratelimiter_tpu.engine.native_index import (
        native_available,
        sort_uniques,
    )

    if not native_available():
        pytest.skip("needs the native library")
    rng = np.random.default_rng(4)
    rb = 9
    for _ in range(10):
        u = int(rng.integers(2, 5000))
        n = u * 3
        slots = rng.choice(1 << 20, size=u, replace=False).astype(np.uint32)
        counts = rng.integers(1, 7, u).astype(np.uint32)
        uwords = (slots << np.uint32(rb + 1)) | (counts << np.uint32(1))
        uidx = rng.integers(0, u, n).astype(np.int32)
        orig_words = uwords.copy()
        orig_word_of_req = orig_words[uidx]
        uw = uwords.copy()
        ui = uidx.copy()
        assert sort_uniques(uw, rb, ui)
        # Cast BEFORE diff: uint32 diff wraps modulo 2^32, which made
        # this assertion pass for any permutation.
        sorted_slots = (uw >> np.uint32(rb + 1)).astype(np.int64)
        assert (np.diff(sorted_slots) > 0).all()
        np.testing.assert_array_equal(np.sort(uw), np.sort(orig_words))
        np.testing.assert_array_equal(uw[ui], orig_word_of_req)
