"""Host-parallel partitioned slot index (engine/partitioned.py).

Decision equivalence vs the single-LRU native index under ample
capacity, the scalar/vector interface contract, and checkpoint
round-trips with the geometry guards.
"""

import numpy as np
import pytest

from ratelimiter_tpu.core.config import RateLimitConfig
from ratelimiter_tpu.engine.native_index import native_available
from ratelimiter_tpu.storage.tpu import TpuBatchedStorage

pytestmark = pytest.mark.skipif(not native_available(),
                                reason="native index unavailable")


def test_auto_host_parallel_election(monkeypatch):
    """r7: TpuBatchedStorage auto-elects host_parallel=min(cores, 8)
    for large single-device tables; explicit kwargs always win; small
    tables, few cores, and checkpointable deployments stay single-LRU."""
    import ratelimiter_tpu.storage.tpu as tpu_mod
    from ratelimiter_tpu.engine.partitioned import PartitionedSlotIndex

    def with_cores(n):
        monkeypatch.setattr(tpu_mod.os, "sched_getaffinity",
                            lambda pid: set(range(n)), raising=False)

    with_cores(6)
    st = TpuBatchedStorage(num_slots=1 << 16)
    # 6 does not divide 2^16: the election walks down to 4 partitions.
    assert st._host_parallel == 4
    assert isinstance(st._index["tb"], PartitionedSlotIndex)
    st.close()
    # Explicit kwarg wins — both directions.
    st = TpuBatchedStorage(num_slots=1 << 16, host_parallel=0)
    assert st._host_parallel == 0
    st.close()
    st = TpuBatchedStorage(num_slots=1 << 16, host_parallel=2)
    assert st._host_parallel == 2
    assert st._index["tb"].n_parts == 2
    st.close()
    # Cores capped at 8; non-dividing counts walk down.
    with_cores(64)
    st = TpuBatchedStorage(num_slots=1 << 16)
    assert st._host_parallel == 8
    st.close()
    # Small tables and <= 2 cores stay single-LRU.
    st = TpuBatchedStorage(num_slots=1 << 12)
    assert st._host_parallel == 0
    st.close()
    with_cores(2)
    st = TpuBatchedStorage(num_slots=1 << 16)
    assert st._host_parallel == 0
    st.close()
    # Checkpointable keeps the enumerable Python index.
    with_cores(6)
    st = TpuBatchedStorage(num_slots=1 << 16, checkpointable=True)
    assert st._host_parallel == 0
    st.close()


def test_partitioned_stream_matches_plain():
    now = [9_000_000]
    st_p = TpuBatchedStorage(num_slots=1 << 12, host_parallel=4,
                             clock_ms=lambda: now[0])
    st_n = TpuBatchedStorage(num_slots=1 << 12, clock_ms=lambda: now[0])
    cfg = RateLimitConfig(max_permits=7, window_ms=1000, refill_rate=5.0)
    lid_p = st_p.register_limiter("tb", cfg)
    lid_n = st_n.register_limiter("tb", cfg)
    from ratelimiter_tpu.engine.partitioned import PartitionedSlotIndex

    assert isinstance(st_p._index["tb"], PartitionedSlotIndex)
    rng = np.random.default_rng(8)
    for rep in range(3):
        ids = rng.integers(0, 200, 900)
        a = st_p.acquire_stream_ids("tb", lid_p, ids, None)
        b = st_n.acquire_stream_ids("tb", lid_n, ids, None)
        np.testing.assert_array_equal(a, b, err_msg=f"rep {rep}")
        now[0] += 411
    # Every partitioned walk feeds the route/merge timers; a single
    # index has no such passes.
    for st, walks in ((st_p, True), (st_n, False)):
        meters = st.registry.meters()
        n_index = meters["ratelimiter.stream.index"].count()
        assert n_index > 0
        for name in ("index_route", "index_merge"):
            got = meters[f"ratelimiter.stream.{name}"].count()
            assert got == (n_index if walks else 0), name
    st_p.close()
    st_n.close()


def test_partitioned_multi_lid_digest_matches_plain():
    """Multi-tenant digest mode with a partitioned index: the per-unique
    lid lane must be mapped through uidx (partition-major unique order),
    not positionally."""
    now = [9_500_000]
    st_p = TpuBatchedStorage(num_slots=1 << 12, host_parallel=4,
                             clock_ms=lambda: now[0])
    st_n = TpuBatchedStorage(num_slots=1 << 12, clock_ms=lambda: now[0])
    cfgs = [RateLimitConfig(max_permits=3 + i, window_ms=1000,
                            refill_rate=2.0 + i) for i in range(4)]
    lids_p = np.asarray([st_p.register_limiter("tb", c) for c in cfgs])
    lids_n = np.asarray([st_n.register_limiter("tb", c) for c in cfgs])
    rng = np.random.default_rng(17)
    for rep in range(3):
        ids = rng.integers(0, 150, 800)
        tl = rng.integers(0, 4, 800)
        a = st_p.acquire_stream_ids("tb", lids_p[tl], ids, None)
        b = st_n.acquire_stream_ids("tb", lids_n[tl], ids, None)
        np.testing.assert_array_equal(a, b, err_msg=f"rep {rep}")
        now[0] += 333
    st_p.close()
    st_n.close()


def test_partitioned_scalar_and_batch_share_namespace():
    from ratelimiter_tpu.engine.partitioned import PartitionedSlotIndex

    ix = PartitionedSlotIndex(1 << 10, 4)
    s1, _ = ix.assign((3, 42))
    slots, _ = ix.assign_batch_ints(np.asarray([42, 42, 7]), 3)
    assert slots[0] == s1 and slots[1] == s1 and slots[2] != s1
    assert ix.get((3, 7)) == slots[2]
    assert len(ix) == 2
    assert ix.remove((3, 42)) == s1
    assert ix.get((3, 42)) is None
    uw, uidx, rank, _ = ix.assign_batch_ints_uniques(
        np.asarray([7, 7, 42]), 3, 8)
    assert len(uw) == 2
    np.testing.assert_array_equal(rank, [0, 1, 0])
    # Word slot fields must be the GLOBAL slots; uniques may merge in
    # partition order, so map through uidx rather than positionally.
    got_slots = (uw >> np.uint32(9)).astype(np.int64)
    assert got_slots[uidx[0]] == ix.get((3, 7))
    assert got_slots[uidx[2]] == ix.get((3, 42))
    assert uidx[0] == uidx[1] != uidx[2]
    ix.close()


def test_partitioned_export_into_flat_native():
    """export_keys from a host-partitioned storage produces the flat 'fp'
    payload (global slots), importable into a flat native target that
    then continues with identical decisions."""
    now = [6_000_000]
    st_p = TpuBatchedStorage(num_slots=1 << 10, host_parallel=2,
                             clock_ms=lambda: now[0])
    cfg = RateLimitConfig(max_permits=4, window_ms=1000, refill_rate=3.0)
    lid = st_p.register_limiter("tb", cfg)
    rng = np.random.default_rng(12)
    ids = rng.integers(0, 120, 500)
    st_p.acquire_stream_ids("tb", lid, ids, None)
    dump = st_p.export_keys()
    assert dump["algos"]["tb"]["kind"] == "fp"

    st_f = TpuBatchedStorage(num_slots=1 << 11, clock_ms=lambda: now[0])
    lid_f = st_f.register_limiter("tb", cfg)
    assert lid_f == lid
    st_f.import_keys(dump)
    now[0] += 77
    ids2 = rng.integers(0, 120, 500)
    a = st_p.acquire_stream_ids("tb", lid, ids2, None)
    b = st_f.acquire_stream_ids("tb", lid_f, ids2, None)
    np.testing.assert_array_equal(a, b)
    st_p.close()
    st_f.close()


def test_partitioned_checkpoint_round_trip(tmp_path):
    now = [4_000_000]
    st = TpuBatchedStorage(num_slots=1 << 10, host_parallel=2,
                           clock_ms=lambda: now[0])
    cfg = RateLimitConfig(max_permits=5, window_ms=1000, refill_rate=2.0)
    lid = st.register_limiter("tb", cfg)
    rng = np.random.default_rng(3)
    ids = rng.integers(0, 100, 400)
    st.acquire_stream_ids("tb", lid, ids, None)
    path = str(tmp_path / "ckpt")
    st.save_checkpoint(path)

    # Same-geometry restore continues identically to the original.
    st2 = TpuBatchedStorage(num_slots=1 << 10, host_parallel=2,
                            table=st.table, clock_ms=lambda: now[0])
    st2.restore_checkpoint(path)
    now[0] += 100
    ids2 = rng.integers(0, 100, 400)
    a = st.acquire_stream_ids("tb", lid, ids2, None)
    b = st2.acquire_stream_ids("tb", lid, ids2, None)
    np.testing.assert_array_equal(a, b)

    # Geometry mismatches are refused, not silently orphaned.
    st3 = TpuBatchedStorage(num_slots=1 << 10, host_parallel=4,
                            table=st.table, clock_ms=lambda: now[0])
    with pytest.raises(ValueError, match="partition"):
        st3.restore_checkpoint(path)
    st4 = TpuBatchedStorage(num_slots=1 << 10, table=st.table,
                            clock_ms=lambda: now[0])
    with pytest.raises(ValueError, match="partition"):
        st4.restore_checkpoint(path)
    # ...and a flat fingerprint dump cannot enter a partitioned index.
    path2 = str(tmp_path / "ckpt_flat")
    st4.acquire_stream_ids("tb", lid, ids, None)
    st4.save_checkpoint(path2)
    with pytest.raises(ValueError, match="host-partitioned"):
        st2.restore_checkpoint(path2)
    for s in (st, st2, st3, st4):
        s.close()


def test_partial_failure_releases_sibling_pins():
    """One partition exhausting capacity mid-batch must release the pins
    the other (successful) partitions took — their results never reach
    the caller, so nothing else could unpin them — and carry every
    eviction the batch applied as ``pending_clears``: on the caller's
    thread (a short batch) and across ranges on the pool (one longer
    than the range grain)."""
    from ratelimiter_tpu.engine.errors import SlotCapacityError
    from ratelimiter_tpu.engine.partitioned import (
        _RANGE_GRAIN,
        PartitionedSlotIndex,
    )
    from ratelimiter_tpu.parallel.sharded import shard_of_int_keys

    ix = PartitionedSlotIndex(4, n_parts=2)  # 2 slots per partition
    keys = np.arange(64, dtype=np.int64)
    part = shard_of_int_keys(keys, 2)
    p0 = keys[part == 0]
    p1 = keys[part == 1]
    # Fill partition 0 and pin both its slots (as in-flight windows).
    for k in p0[:2]:
        ix.assign((0, int(k)), hold_pin=True)
    # Mixed batch: a fresh partition-0 key must fail (-2, all pinned),
    # while partition-1 keys succeed and get pinned.
    batch = np.asarray([int(p0[2]), int(p1[0]), int(p1[1])], dtype=np.int64)
    with pytest.raises(RuntimeError):
        ix.assign_batch_ints(batch, lid=0, hold_pins=True)
    # Partition 1's pins must be gone: both its slots evictable again.
    s1, ev1 = ix.assign((0, int(p1[2])))
    s2, ev2 = ix.assign((0, int(p1[3])))
    assert {s1, s2} == {2, 3}  # both partition-1 slots reachable
    ix.close()

    # Ranged: partition 1 is full of unpinned keys that a long batch of
    # fresh partition-1 keys evicts, while one fresh partition-0 key
    # fails behind two pinned slots.
    for family in ("slots", "uniques", "uniques-sorted"):
        ix = PartitionedSlotIndex(8, n_parts=2)  # 4 slots per partition
        for k in p0[:4]:
            ix.assign((0, int(k)), hold_pin=True)
        for k in p1[:4]:
            ix.assign((0, int(k)))
        fresh = np.resize(p1[4:8], 3 * _RANGE_GRAIN + 17)
        batch = np.concatenate([fresh[:_RANGE_GRAIN + 5], [p0[4]],
                                fresh[_RANGE_GRAIN + 5:]]).astype(np.int64)
        with pytest.raises(SlotCapacityError) as err:
            if family == "slots":
                ix.assign_batch_ints(batch, lid=0, hold_pins=True)
            else:
                ix.assign_batch_ints_uniques(
                    batch, 0, 8, hold_pins=True,
                    sort_slots=family == "uniques-sorted")
        # Every partition-1 slot was evicted once (global slots 4..7).
        assert sorted(err.value.pending_clears.tolist()) == [4, 5, 6, 7]
        got = {ix.assign((0, int(k)))[0] for k in p1[8:12]}
        assert got == {4, 5, 6, 7}, family  # no pin left behind
        ix.close()


@pytest.mark.parametrize("family", ["ints_uniques", "ints_multi_uniques",
                                    "strs_uniques"])
def test_sort_slots_sorts_each_partition_in_its_walk(family):
    """``sort_slots=True``: every partition slot-sorts its uniques in its
    own walk job, so the merged digest is strictly ascending by global
    slot, while each request's slot, count and rank, the evictions and
    the pins held equal the unsorted call's on a twin index fed the same
    batches.  Some batches leave partitions empty; pins are released one
    batch late, as the stream loop releases them, so later walks evict
    around them."""
    from ratelimiter_tpu.engine.native_index import hash_str_keys
    from ratelimiter_tpu.engine.partitioned import PartitionedSlotIndex
    from ratelimiter_tpu.parallel.sharded import shard_of_int_keys

    n_parts, spp, rank_bits, lid = 4, 64, 7, 3
    ixs = [PartitionedSlotIndex(n_parts * spp, n_parts) for _ in range(2)]
    rng = np.random.default_rng(sum(map(ord, family)))
    pool = np.arange(2000, dtype=np.int64)
    if family == "strs_uniques":
        h1, _ = hash_str_keys([f"u{k}" for k in pool], lid)
        part = (h1 % np.uint64(n_parts)).astype(np.int64)
    else:
        part = shard_of_int_keys(pool, n_parts)
    # 80 keys per partition over a 64-slot partition: later batches evict.
    by_part = [pool[part == p][:80] for p in range(n_parts)]
    held, n_evicted = None, 0
    for step, parts in enumerate([(0, 2), (0, 1, 2, 3), (1,), (0, 1, 2, 3),
                                  (2, 3), (0, 1, 2, 3)]):
        distinct = np.concatenate([rng.choice(by_part[p], 25, replace=False)
                                   for p in parts])
        keys = rng.choice(distinct, 3 * len(distinct))
        lids = (keys % 3).astype(np.uint64)  # one tenant per key
        got = []
        for ix, sort_slots in zip(ixs, (True, False)):
            kw = dict(hold_pins=True, sort_slots=sort_slots)
            if family == "ints_uniques":
                res = ix.assign_batch_ints_uniques(keys, lid, rank_bits, **kw)
            elif family == "ints_multi_uniques":
                res = ix.assign_batch_ints_multi_uniques(keys, lids,
                                                         rank_bits, **kw)
            else:
                res = ix.assign_batch_strs_uniques(
                    [f"u{k}" for k in keys], lid, rank_bits, **kw)
            got.append(res)
            assert (ix.last_phase_s()[2] is not None) == sort_slots
        (uw_s, uidx_s, rank_s, ev_s), (uw_u, uidx_u, rank_u, ev_u) = got
        slots = (uw_s >> np.uint32(rank_bits + 1)).astype(np.int64)
        assert (np.diff(slots) > 0).all(), f"step {step}"
        np.testing.assert_array_equal(uw_s[uidx_s], uw_u[uidx_u],
                                      err_msg=f"step {step}")
        np.testing.assert_array_equal(rank_s, rank_u, err_msg=f"step {step}")
        np.testing.assert_array_equal(ev_s, ev_u, err_msg=f"step {step}")
        assert sorted(uw_s.tolist()) == sorted(uw_u.tolist())
        n_evicted += len(ev_s)
        if held is not None:
            for ix in ixs:
                ix.unpin_batch(held)
        held = slots.astype(np.int32)
    assert n_evicted > 0  # the walks evicted around held pins
    # Both indexes still hold the last batch's pins on the same slots: a
    # batch of 64 fresh keys in one partition fails on both, and once
    # unpinned, fills the same slots on both.
    fresh = pool[part == 0][80:80 + spp]
    fresh_keys = ([f"u{k}" for k in fresh] if family == "strs_uniques"
                  else fresh)
    for ix in ixs:
        with pytest.raises(RuntimeError):
            if family == "strs_uniques":
                ix.assign_batch_strs_uniques(fresh_keys, lid, rank_bits)
            else:
                ix.assign_batch_ints_uniques(fresh_keys, lid, rank_bits)
        ix.unpin_batch(held)
    filled = [ix.assign_batch_ints_uniques(fresh, lid, rank_bits)
              if family != "strs_uniques"
              else ix.assign_batch_strs_uniques(fresh_keys, lid, rank_bits)
              for ix in ixs]
    np.testing.assert_array_equal(filled[0][3], filled[1][3])
    assert len(filled[0][0]) == spp
    for ix in ixs:
        ix.close()


def _reference_walk(twins, family, keys, lids, rank_bits):
    """What the partitioned index computes, composed from the plain
    parts: a numpy stable-argsort route, one NativeSlotIndex walk per
    partition on twin indexes, and a numpy merge back to request order
    with each partition's global slot base folded in."""
    from ratelimiter_tpu.engine.native_index import hash_str_keys
    from ratelimiter_tpu.parallel.sharded import shard_of_int_keys

    n_parts = len(twins)
    spp = twins[0].num_slots
    n = len(keys)
    if family == "strs_uniques":
        h1, h2 = (x.copy() for x in hash_str_keys(keys, 3))
        part = (h1 % np.uint64(n_parts)).astype(np.int64)
    else:
        part = shard_of_int_keys(keys, n_parts)
    order = np.argsort(part, kind="stable")
    offs = np.concatenate([[0], np.cumsum(np.bincount(
        part, minlength=n_parts))])
    out = [np.empty(n, dtype=np.int32) for _ in range(2)]
    uwords, clears, u = [], [], 0
    for p, twin in enumerate(twins):
        pos = order[offs[p]:offs[p + 1]]
        if not len(pos):
            continue
        if family == "ints":
            res = twin.assign_batch_ints(keys[pos], 3)
        elif family == "ints_multi":
            res = twin.assign_batch_ints_multi(keys[pos], lids[pos])
        elif family == "ints_uniques":
            res = twin.assign_batch_ints_uniques(keys[pos], 3, rank_bits)
        elif family == "ints_multi_uniques":
            res = twin.assign_batch_ints_multi_uniques(
                keys[pos], lids[pos], rank_bits)
        else:
            res = twin.assign_batch_fps_uniques(h1[pos], h2[pos], rank_bits)
        clears.extend(p * spp + int(e) for e in res[-1])
        if len(res) == 2:  # slots family
            out[0][pos] = res[0] + p * spp
            continue
        uwords.append(res[0] + np.uint32(p * spp << (rank_bits + 1)))
        out[0][pos] = res[1] + u
        out[1][pos] = res[2]
        u += len(res[0])
    if family in ("ints", "ints_multi"):
        return out[0], np.asarray(clears, dtype=np.int64)
    return (np.concatenate(uwords) if uwords else np.empty(0, np.uint32),
            out[0], out[1], np.asarray(clears, dtype=np.int64))


_GRAIN = 1 << 16  # engine/partitioned.py:_RANGE_GRAIN, checked below


@pytest.mark.parametrize("n_parts", [8, 6])
@pytest.mark.parametrize("skew", ["zipf", "uniform"])
@pytest.mark.parametrize("n", [1, 100, _GRAIN - 1, _GRAIN + 1,
                               3 * _GRAIN + 17])
@pytest.mark.parametrize("family", ["ints_uniques", "ints_multi_uniques",
                                    "ints", "ints_multi", "strs_uniques"])
def test_ranged_walk_matches_reference_composition(family, n, skew,
                                                   n_parts):
    """Array for array, the ranged route/walk/merge equals the reference
    composition, chunk after chunk on a table small enough that uniform
    chunks evict from the third on."""
    from ratelimiter_tpu.engine.native_index import NativeSlotIndex
    from ratelimiter_tpu.engine.partitioned import (
        _RANGE_GRAIN,
        PartitionedSlotIndex,
    )

    assert _RANGE_GRAIN == _GRAIN
    rng = np.random.default_rng(n * 31 + n_parts)
    space = 8 * n + 64  # distinct keys over the chunks outgrow the table
    spp = (3 * n) // (2 * n_parts) + 8  # holds one chunk's uniques
    ix = PartitionedSlotIndex(spp * n_parts, n_parts)
    twins = [NativeSlotIndex(spp) for _ in range(n_parts)]
    rank_bits = 7
    evicted = []
    for chunk in range(3):
        if skew == "zipf":
            ids = (rng.zipf(1.1, n) - 1) % space
        else:
            ids = rng.integers(0, space, n)
        ids = ids.astype(np.int64)
        lids = rng.integers(0, 3, n).astype(np.uint64)
        want = _reference_walk(
            twins, family, [f"u{k}" for k in ids]
            if family == "strs_uniques" else ids, lids, rank_bits)
        if family == "ints":
            got = ix.assign_batch_ints(ids, 3)
        elif family == "ints_multi":
            got = ix.assign_batch_ints_multi(ids, lids)
        elif family == "ints_uniques":
            got = ix.assign_batch_ints_uniques(ids, 3, rank_bits)
        elif family == "ints_multi_uniques":
            got = ix.assign_batch_ints_multi_uniques(ids, lids, rank_bits)
        else:
            got = ix.assign_batch_strs_uniques(
                [f"u{k}" for k in ids], 3, rank_bits)
        assert len(got) == len(want)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(
                np.asarray(g, dtype=np.int64), np.asarray(w, np.int64),
                err_msg=f"chunk {chunk}")
        evicted.append(len(want[-1]))
        route_s, merge_s, sort_s = ix.last_phase_s()
        assert route_s >= 0 and merge_s >= 0 and sort_s is None
    if skew == "uniform" and n >= 100:
        assert evicted[2] > 0, evicted  # the table was small enough
    ix.close()
