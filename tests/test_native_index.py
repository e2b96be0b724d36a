"""Native C++ slot index: semantic equivalence with the Python SlotIndex and
end-to-end use through the TPU storage (incl. the int-key fast path)."""

import random

import numpy as np
import pytest

from ratelimiter_tpu.engine.native_index import native_available

pytestmark = pytest.mark.skipif(
    not native_available(), reason="native slot index unavailable (no g++?)")


def make_native(n):
    from ratelimiter_tpu.engine.native_index import NativeSlotIndex

    return NativeSlotIndex(n)


def test_scalar_parity_with_python_index():
    from ratelimiter_tpu.engine.slots import SlotIndex

    rng = random.Random(3)
    py, nat = SlotIndex(32), make_native(32)
    key_to_slot_py, key_to_slot_nat = {}, {}
    keys = [(rng.randrange(3), f"user{rng.randrange(60)}") for _ in range(500)]
    for i, key in enumerate(keys):
        op = rng.random()
        if op < 0.8:
            sp, _ = py.assign(key)
            sn, _ = nat.assign(key)
            key_to_slot_py[key], key_to_slot_nat[key] = sp, sn
        elif op < 0.9:
            assert (py.get(key) is None) == (nat.get(key) is None)
        else:
            rp, rn = py.remove(key), nat.remove(key)
            assert (rp is None) == (rn is None)
        assert len(py) == len(nat), f"step {i}"
    # Same keys resident (slot numbering may differ; membership must not).
    for key in set(keys):
        assert (py.get(key) is None) == (nat.get(key) is None), key


def test_batch_ints_identity_and_eviction():
    nat = make_native(16)
    slots, ev = nat.assign_batch_ints(np.arange(16), lid=0)
    assert len(set(slots.tolist())) == 16 and len(ev) == 0
    # Same keys again: identical slots, no evictions.
    slots2, ev2 = nat.assign_batch_ints(np.arange(16), lid=0)
    np.testing.assert_array_equal(slots, slots2)
    assert len(ev2) == 0
    # 8 new keys evict the 8 least-recent.
    slots3, ev3 = nat.assign_batch_ints(np.arange(100, 108), lid=0)
    assert len(ev3) == 8
    assert len(nat) == 16


def test_lid_isolation():
    nat = make_native(8)
    s1, _ = nat.assign((1, 42))
    s2, _ = nat.assign((2, 42))
    assert s1 != s2
    assert nat.get((1, 42)) == s1 and nat.get((2, 42)) == s2


def test_same_batch_oversubscription_raises():
    nat = make_native(4)
    with pytest.raises(RuntimeError):
        nat.assign_batch_ints(np.arange(10), lid=0)


def test_tpu_storage_int_key_fast_path_matches_oracle():
    from ratelimiter_tpu import RateLimitConfig
    from ratelimiter_tpu.algorithms import TokenBucketRateLimiter
    from ratelimiter_tpu.metrics import MeterRegistry
    from ratelimiter_tpu.semantics import TokenBucketOracle
    from ratelimiter_tpu.storage import TpuBatchedStorage

    T0 = 1_753_000_000_000

    class FakeClock:
        def __init__(self):
            self.t = T0

        def __call__(self):
            return self.t

    clock = FakeClock()
    storage = TpuBatchedStorage(num_slots=1024, max_delay_ms=0.1, clock_ms=clock)
    cfg = RateLimitConfig(max_permits=12, window_ms=1500, refill_rate=20.0)
    limiter = TokenBucketRateLimiter(storage, cfg, MeterRegistry(), clock_ms=clock)
    oracle = TokenBucketOracle(cfg)
    rng = np.random.default_rng(4)
    for step in range(25):
        clock.t += int(rng.integers(0, 500))
        n = int(rng.integers(1, 40))
        ids = rng.integers(0, 30, size=n)
        perms = rng.integers(1, 14, size=n)
        got = limiter.try_acquire_ids(ids, perms)
        for j in range(n):
            want = oracle.try_acquire(int(ids[j]), int(perms[j]), clock.t).allowed
            assert got[j] == want, (step, j)
    storage.close()


def test_fp_dump_restore_preserves_lru_and_survives_bad_input():
    """Fingerprint restore rebuilds the exact LRU recency order; an invalid
    or oversized dump refuses but leaves the index empty-and-usable."""
    import numpy as np
    import pytest

    from ratelimiter_tpu.engine.native_index import (
        NativeSlotIndex,
        native_available,
    )

    if not native_available():
        pytest.skip("no native index")
    ix = NativeSlotIndex(8)
    for k in range(8):
        ix.assign((1, k))
    ix.assign((1, 2))  # key 2 -> MRU; LRU victim is key 0
    h1, h2, slots = ix.dump_fp()

    ix2 = NativeSlotIndex(8)
    ix2.restore_fp(h1, h2, slots)
    # Same eviction order: assigning a NEW key must evict the dump's LRU
    # tail (last dump entry).  No get() here — get touches the LRU.
    ix2_lru_before = len(ix2)
    _, evicted = ix2.assign((1, 99))
    assert evicted == slots[-1] and len(ix2) == ix2_lru_before

    # Oversized dump refused; index stays usable.
    big = NativeSlotIndex(4)
    with pytest.raises(ValueError):
        big.restore_fp(h1, h2, slots)
    s, ev = big.assign((1, 7))
    assert s >= 0 and ev is None

    # Duplicate-slot dump refused; index stays usable.
    bad = NativeSlotIndex(8)
    dup = slots.copy()
    dup[1] = dup[0]
    with pytest.raises(ValueError):
        bad.restore_fp(h1, h2, dup)
    s, ev = bad.assign((1, 7))
    assert s >= 0 and ev is None


def test_fp_rebalance_import_preserves_recency_order():
    """import_keys of an fp export keeps the source's eviction order in the
    target (MRU-first dump is assigned in reverse)."""
    import numpy as np
    import pytest

    from ratelimiter_tpu.engine import checkpoint as ck
    from ratelimiter_tpu.engine.native_index import native_available
    from ratelimiter_tpu.storage import TpuBatchedStorage
    from ratelimiter_tpu import RateLimitConfig

    if not native_available():
        pytest.skip("no native index")
    clock = lambda: 95_000  # noqa: E731
    cfg = RateLimitConfig(max_permits=4, window_ms=60_000, refill_rate=0.001)
    src = TpuBatchedStorage(num_slots=8, clock_ms=clock)
    lid = src.register_limiter("tb", cfg)
    src.acquire_stream_ids("tb", lid, np.arange(8, dtype=np.int64), None,
                           batch=8, subbatches=1)
    src.acquire_stream_ids("tb", lid, np.asarray([0], dtype=np.int64), None,
                           batch=8, subbatches=1)  # key 0 -> MRU; LRU = key 1
    dump = ck.export_keys(src)
    src.close()

    dst = TpuBatchedStorage(num_slots=8, clock_ms=clock)
    dst.register_limiter("tb", cfg)
    ck.import_keys(dst, dump)
    index = dst._index["tb"]
    # Source LRU tail = last fp in the MRU-first dump; lookup_fps does not
    # touch the LRU (get would).
    fp = dump["algos"]["tb"]
    lru_victim_slot = int(index.lookup_fps(fp["h1"][-1:], fp["h2"][-1:])[0])
    _, evicted = index.assign((lid, 99))
    dst.close()
    assert evicted == lru_victim_slot


def test_batch_recency_is_first_occurrence_granular():
    """Documented contract: within ONE batch call, repeat hits of a key do
    not re-touch the LRU — recency among same-batch keys follows first
    occurrence.  Batch [A, B, A] therefore leaves B most-recent; a later
    eviction takes A's slot (not B's, as per-occurrence touching would)."""
    import numpy as np
    import pytest

    from ratelimiter_tpu.engine.native_index import (
        NativeSlotIndex,
        native_available,
    )

    if not native_available():
        pytest.skip("no native index")
    ix = NativeSlotIndex(2)
    slots, ev = ix.assign_batch_ints(np.asarray([7, 8, 7], dtype=np.int64), 1)
    assert slots[0] == slots[2] and len(ev) == 0
    # Table full; next NEW key evicts the batch's first-touched key (7).
    _, evicted = ix.assign((1, 9))
    assert evicted == slots[0]
    assert ix.get((1, 8)) is not None


@pytest.mark.parametrize("kind", ["native", "python"])
def test_remove_while_pinned_defers_free(kind):
    """ADVICE r2: an admin remove() racing a stream's assign->dispatch pin
    window must NOT hand the slot to a new key until the pin drops — and
    the reassignment must report the slot as its own eviction so the
    (possibly stale) device state is cleared before reuse."""
    if kind == "native":
        ix = make_native(2)
    else:
        from ratelimiter_tpu.engine.slots import SlotIndex

        ix = SlotIndex(2)
    s_a, _ = ix.assign((0, 1), hold_pin=True)  # stream holds the pin
    s_b, _ = ix.assign((0, 2))
    assert ix.remove((0, 1)) == s_a  # admin reset while pinned
    # Capacity is 2: key 3 must NOT receive the pinned slot s_a.
    s_c, ev_c = ix.assign((0, 3))
    assert s_c != s_a
    assert ev_c == s_b  # LRU eviction of the only unpinned entry
    # Pin drops (dispatch enqueued): the slot becomes reusable, but its
    # next assignment reports it as its own eviction (clear before use).
    ix.unpin_batch(np.asarray([s_a], dtype=np.int32))
    s_d, ev_d = ix.assign((0, 4))
    assert s_d == s_a and ev_d == s_a


@pytest.mark.parametrize("kind", ["native", "python"])
def test_remove_while_pinned_all_pinned_raises(kind):
    """With every slot pinned (one via remove-deferral), a new key's
    assignment must fail loudly, not hand out a pinned slot."""
    if kind == "native":
        ix = make_native(1)
    else:
        from ratelimiter_tpu.engine.slots import SlotIndex

        ix = SlotIndex(1)
    s_a, _ = ix.assign((0, 1), hold_pin=True)
    ix.remove((0, 1))
    with pytest.raises(RuntimeError):
        ix.assign((0, 2))
    ix.unpin_batch(np.asarray([s_a], dtype=np.int32))
    s_b, ev_b = ix.assign((0, 2))
    assert s_b == s_a and ev_b == s_a


def test_dirty_slot_repinned_is_skipped():
    """A dirty slot that was RE-pinned after listing (queued micro-batch
    request) must not be handed out until that pin also drops."""
    ix = make_native(2)
    s_a, _ = ix.assign((0, 1), hold_pin=True)
    s_b, _ = ix.assign((0, 2))
    ix.remove((0, 1))
    ix.unpin_batch(np.asarray([s_a], dtype=np.int32))  # s_a now dirty
    ix.pin_batch(np.asarray([s_a], dtype=np.int32))    # re-pinned
    s_c, ev_c = ix.assign((0, 3))
    assert s_c == s_b and ev_c == s_b  # LRU eviction, not the dirty slot
    ix.unpin_batch(np.asarray([s_a], dtype=np.int32))
    s_d, ev_d = ix.assign((0, 4))
    assert s_d == s_a and ev_d == s_a  # dirty handout clears first


def test_restore_defers_pinned_unmapped_slot():
    """restore_fp with a live pin on a slot absent from the dump: the slot
    must not reach the clean free list — it surfaces dirty at last unpin."""
    ix = make_native(2)
    s_a, _ = ix.assign((0, 1), hold_pin=True)  # pinned by an in-flight window
    s_b, _ = ix.assign((0, 2))
    h1, h2, slots = ix.dump_fp()
    keep = slots != s_a  # dump without the pinned slot's entry
    ix.restore_fp(h1[keep], h2[keep], slots[keep])
    # Only key 2 is mapped; the pinned slot must not be assigned clean.
    s_c, ev_c = ix.assign((0, 3))
    assert s_c != s_a
    ix.unpin_batch(np.asarray([s_a], dtype=np.int32))
    s_d, ev_d = ix.assign((0, 4))
    assert s_d == s_a and ev_d == s_a  # dirty: cleared before reuse


def test_restore_remaps_pinned_slot_cleanly():
    """restore_fp where the pinned slot IS in the dump: the mapping wins —
    the slot must never surface on the dirty list at unpin (two keys would
    share it)."""
    ix = make_native(2)
    s_a, _ = ix.assign((0, 1), hold_pin=True)
    ix.assign((0, 2))
    h1, h2, slots = ix.dump_fp()
    ix.restore_fp(h1, h2, slots)  # s_a re-mapped to key 1
    ix.unpin_batch(np.asarray([s_a], dtype=np.int32))
    assert ix.get((0, 1)) == s_a
    # Capacity full: a new key's assignment must EVICT (clearing state),
    # never receive s_a as a "free" slot while key 1 still maps to it.
    s_c, ev_c = ix.assign((0, 9))
    assert ev_c is not None and ev_c == s_c
    assert len(ix) == 2


def test_strpack_native_matches_numpy_packer():
    """The optional CPython-API string packer must produce byte-identical
    (buffer, offsets) to the numpy join packer — including empty keys,
    unicode, 300-char keys, and embedded NULs (where the join path takes
    its slow per-key fallback)."""
    import ratelimiter_tpu.engine.native_index as ni

    if ni._load_strpack() is None:
        pytest.skip("strpack unavailable (no Python headers/libpython)")
    cases = [
        ["hello", "", "wörld", "a" * 300, "nul\x00byte", "k123"],
        [f"user-{i}" for i in range(257)],
        [""],
    ]
    sp = ni._strpack
    for keys in cases:
        b1, o1 = ni._pack_str_keys(keys)
        ni._strpack, ni._strpack_failed = None, True
        try:
            b2, o2 = ni._pack_str_keys(keys)
        finally:
            ni._strpack, ni._strpack_failed = sp, False
        np.testing.assert_array_equal(o1, o2)
        np.testing.assert_array_equal(b1, b2)
    # Non-str items: the native packer declines and the fallback handles.
    b, o = ni._pack_str_keys(["a", b"raw-bytes", "c"])
    assert bytes(b) == b"araw-bytesc" and list(o) == [0, 1, 10, 11]


def test_strpack_rejects_size_drift():
    """rl_strlist_pack re-checks the list size and total bytes the
    buffers were allocated for (the GIL can drop between the sizing pass
    and the pack, so drift must be an error, never a heap overflow)."""
    import numpy as np

    from ratelimiter_tpu.engine import native_index as ni

    sp = ni._load_strpack()
    if sp is None:
        pytest.skip("strpack unavailable")
    keys = ["abc", "defg"]
    total = sp.rl_strlist_total(keys)
    assert total == 7
    buf = np.empty(total, dtype=np.uint8)
    offs = np.empty(3, dtype=np.int64)
    assert sp.rl_strlist_pack2(keys, buf.ctypes.data, offs.ctypes.data,
                              2, total) == 0
    assert bytes(buf) == b"abcdefg" and offs.tolist() == [0, 3, 7]
    # List "grew" after sizing -> error.
    assert sp.rl_strlist_pack2(keys, buf.ctypes.data, offs.ctypes.data,
                              1, total) == -1
    # Content outgrew the buffer -> error before any overflow.
    assert sp.rl_strlist_pack2(keys, buf.ctypes.data, offs.ctypes.data,
                              2, total - 1) == -1


def test_weighted_layout_matches_numpy_reference():
    """rl_weighted_layout/rl_weighted_decide vs the numpy layout they
    replace (storage/tpu.py fallback): identical sorted words, offsets,
    permit scatter, and decisions on random duplicate structures."""
    from ratelimiter_tpu.engine.native_index import (
        weighted_decide,
        weighted_layout,
    )

    if not native_available():
        pytest.skip("needs the native library")
    rng = np.random.default_rng(11)
    rb = 12
    for trial in range(20):
        n = int(rng.integers(1, 2000))
        keys = rng.integers(0, max(n // 3, 1), n)
        # Build uwords/uidx/rank the way the walk does: first-appearance
        # order, count field = segment size.
        uniq, uidx = np.unique(keys, return_inverse=True)
        first = np.sort(np.unique(uidx, return_index=True)[1])
        remap = np.empty(len(uniq), dtype=np.int64)
        remap[uidx[first]] = np.arange(len(uniq))
        uidx = remap[uidx].astype(np.int32)
        counts = np.bincount(uidx).astype(np.int64)
        rank = np.zeros(n, dtype=np.int32)
        seen: dict = {}
        for i, ui in enumerate(uidx):
            rank[i] = seen.get(ui, 0)
            seen[ui] = rank[i] + 1
        u = len(uniq)
        slots = rng.permutation(u).astype(np.uint32)
        uwords = ((slots << np.uint32(rb + 1))
                  | (counts.astype(np.uint32) << np.uint32(1)))
        perms = rng.integers(1, 200, n).astype(np.int64)
        r_max = int(counts.max())
        r_b = 2
        while r_b < r_max:
            r_b *= 2
        # numpy reference (the fallback path)
        order = np.argsort(-counts, kind="stable")
        spos_ref = np.empty(u, dtype=np.int64)
        spos_ref[order] = np.arange(u)
        hist = np.bincount(counts, minlength=r_b + 1)
        k_r = u - np.cumsum(hist[:r_b])
        roff_ref = np.zeros(r_b, dtype=np.int64)
        np.cumsum(k_r[:-1], out=roff_ref[1:])
        pos_ref = roff_ref[rank] + spos_ref[uidx]
        plen = n + u + 16
        pr_ref = np.zeros(plen, dtype=np.uint8)
        pr_ref[pos_ref] = perms
        uw_ref = uwords[order]
        # native
        uw_nat = np.full(u, 0xFFFFFFFF, dtype=np.uint32)
        spos_nat = np.empty(u, dtype=np.int32)
        roff_nat = np.empty(r_b, dtype=np.int64)
        pr_nat = np.zeros(plen, dtype=np.uint8)
        assert weighted_layout(np.ascontiguousarray(uwords), rb, uidx,
                               rank, perms, r_b, uw_nat, spos_nat,
                               roff_nat, pr_nat)
        np.testing.assert_array_equal(uw_nat, uw_ref, err_msg=str(trial))
        np.testing.assert_array_equal(spos_nat, spos_ref.astype(np.int32))
        np.testing.assert_array_equal(roff_nat, roff_ref)
        np.testing.assert_array_equal(pr_nat, pr_ref)
        # decide: random bitmask, both reconstructions agree
        bits = rng.integers(0, 256, (plen + 7) // 8).astype(np.uint8)
        flat = np.unpackbits(bits)
        want = flat[pos_ref].astype(bool)
        got = weighted_decide(bits, roff_nat, spos_nat, uidx, rank)
        np.testing.assert_array_equal(got, want)


def test_rebuild_words_into_matches_numpy():
    """rl_rebuild_words vs ops/relay.rebuild_words on random duplicate
    structures, including over-clamp segments."""
    from ratelimiter_tpu.engine.native_index import rebuild_words_into
    from ratelimiter_tpu.ops.relay import rebuild_words

    if not native_available():
        pytest.skip("needs the native library")
    rng = np.random.default_rng(13)
    for rb in (3, 7, 12):
        n = 5000
        keys = rng.integers(0, 600, n)
        uniq, uidx = np.unique(keys, return_inverse=True)
        first = np.sort(np.unique(uidx, return_index=True)[1])
        remap = np.empty(len(uniq), dtype=np.int64)
        remap[uidx[first]] = np.arange(len(uniq))
        uidx = remap[uidx].astype(np.int32)
        counts = np.bincount(uidx)
        rank = np.zeros(n, dtype=np.int32)
        seen: dict = {}
        for i, ui in enumerate(uidx):
            rank[i] = seen.get(ui, 0)
            seen[ui] = rank[i] + 1
        rmask = (1 << rb) - 1
        slots = rng.permutation(len(uniq)).astype(np.uint32)
        uwords = ((slots << np.uint32(rb + 1))
                  | (np.minimum(counts, rmask).astype(np.uint32)
                     << np.uint32(1)))
        want = rebuild_words(uwords, uidx, rank, rb)
        out = np.empty(n, dtype=np.uint32)
        assert rebuild_words_into(np.ascontiguousarray(uwords), uidx,
                                  rank, rb, out)
        np.testing.assert_array_equal(out, want, err_msg=f"rb={rb}")


def test_shard_route_matches_numpy_reference():
    """rl_shard_route vs the numpy reference (splitmix hash + stable
    argsort): identical shard ids, order, counts."""
    import ratelimiter_tpu.engine.native_index as ni
    from ratelimiter_tpu.parallel.sharded import shard_of_int_keys

    rng = np.random.default_rng(21)
    for n_shards in (1, 2, 8):
        keys = rng.integers(-(1 << 40), 1 << 40, size=4096)
        want_shard = shard_of_int_keys(keys, n_shards)
        want_order = np.argsort(want_shard, kind="stable")
        want_counts = np.bincount(want_shard, minlength=n_shards)
        r = ni.shard_route(keys, n_shards)
        assert r is not None
        np.testing.assert_array_equal(r[0], want_shard)
        np.testing.assert_array_equal(r[1], want_order)
        np.testing.assert_array_equal(r[2], want_counts)


@pytest.mark.parametrize("n", [0, 2, 70_000])
@pytest.mark.parametrize("n_ranges", [1, 3, 8])
@pytest.mark.parametrize("n_shards", [1, 2, 3, 4, 8])
def test_shard_route_ranges_matches_numpy_reference(n_shards, n_ranges, n):
    """The sharded loop's ranged route into reused buffers vs the numpy
    reference (shard_of_int_keys + stable argsort + keys[order] +
    bincount), for power-of-two and other shard counts, batches shorter
    than the range count (empty ranges) and empty ones: the same shard
    per key, each shard's keys and request indices in arrival order,
    the same counts, and each range's first position in every shard.
    Stale buffer contents and a longer buffer than the batch change
    nothing."""
    import ratelimiter_tpu.engine.native_index as ni
    from ratelimiter_tpu.parallel.sharded import shard_of_int_keys

    rng = np.random.default_rng(1000 * n_shards + 10 * n_ranges + n % 7)
    keys = rng.integers(-(1 << 62), 1 << 62, size=n)
    want_shard = shard_of_int_keys(keys, n_shards)
    want_order = np.argsort(want_shard, kind="stable")
    want_counts = np.bincount(want_shard, minlength=n_shards)
    bounds = np.arange(n_ranges + 1, dtype=np.int64) * n // n_ranges
    cap = n + 5
    part = np.full(cap, 0xAB, dtype=np.uint8)
    local = np.full((n_ranges, n_shards), -7, dtype=np.int64)
    offs = np.full(n_shards + 1, -7, dtype=np.int64)
    kst = np.full(cap, -7, dtype=np.int64)
    order = np.full(cap, -7, dtype=np.int64)
    ni.shard_route_ranges(keys, n_shards, bounds, part[:n], local, offs,
                          kst[:n], order[:n])
    np.testing.assert_array_equal(part[:n], want_shard)
    np.testing.assert_array_equal(order[:n], want_order)
    np.testing.assert_array_equal(kst[:n], keys[want_order])
    np.testing.assert_array_equal(np.diff(offs), want_counts)
    assert offs[0] == 0
    for r in range(n_ranges):
        np.testing.assert_array_equal(local[r], np.bincount(
            want_shard[:bounds[r]], minlength=n_shards))
    assert (kst[n:] == -7).all() and (order[n:] == -7).all()


def test_route_hashes_gather_matches_numpy():
    import ratelimiter_tpu.engine.native_index as ni

    rng = np.random.default_rng(22)
    h1 = rng.integers(0, 1 << 63, size=4096).astype(np.uint64)
    h2 = rng.integers(0, 1 << 63, size=4096).astype(np.uint64)
    for n_shards in (2, 5):
        want_shard = (h1 % np.uint64(n_shards)).astype(np.int32)
        want_order = np.argsort(want_shard, kind="stable")
        s, o, c = ni.route_hashes(h1, n_shards)
        np.testing.assert_array_equal(s, want_shard)
        np.testing.assert_array_equal(o, want_order)
        s2, o2, c2, h1s, h2s = ni.route_hashes_gather(h1, h2, n_shards)
        np.testing.assert_array_equal(o2, want_order)
        np.testing.assert_array_equal(h1s, h1[want_order])
        np.testing.assert_array_equal(h2s, h2[want_order])


def _cuts(n):
    """Ways to cut a batch of n requests into ranges: one range; ranges
    of one request and of none; uneven ranges."""
    return [np.asarray(c, dtype=np.int64) for c in (
        [0, n], [0, 1, 1, n // 2, n - 1, n], [0, n // 4, 3 * n // 5, n])]


@pytest.mark.parametrize("n_parts", [1, 2, 6, 7, 8, 16])
def test_range_route_matches_shard_route_and_route_hashes(n_parts):
    """The ranged route, however the batch is cut into ranges, gives
    each partition the same requests in the same order, with the same
    counts, as the one-pass stable routes: shard_route for int keys
    (power-of-two counts masked, others by %) and route_hashes for
    fingerprints; a second lane travels with the first."""
    import ratelimiter_tpu.engine.native_index as ni

    rng = np.random.default_rng(40 + n_parts)
    n = 5000
    keys = rng.integers(-(1 << 40), 1 << 40, size=n)
    h1 = rng.integers(0, 1 << 64, size=n, dtype=np.uint64)
    lids = rng.integers(0, 1 << 63, size=n).astype(np.uint64)
    for bounds in _cuts(n):
        for lane, hashed in ((keys, True), (h1, False)):
            want = (ni.shard_route(lane, n_parts) if hashed
                    else ni.route_hashes(lane, n_parts))
            part, local, offs, copies = ni.route_ranges(
                (lane, lids), hashed, n_parts, bounds)
            np.testing.assert_array_equal(part, want[0])
            np.testing.assert_array_equal(np.diff(offs), want[2])
            np.testing.assert_array_equal(copies[0], lane[want[1]])
            np.testing.assert_array_equal(copies[1], lids[want[1]])
            # Range r's first position in partition p: the requests of
            # partition p in the ranges before it.
            for r in range(len(bounds) - 1):
                np.testing.assert_array_equal(local[r], np.bincount(
                    want[0][:bounds[r]], minlength=n_parts))


@pytest.mark.parametrize("n_parts", [3, 8])
def test_range_merge_inverts_range_route(n_parts):
    """rl_merge_ranges brings per-partition outputs back to request
    order: merging each partition's slice of the routed lane (as int32)
    reproduces the lane, plus the per-partition addend on the first
    output only; an empty partition passes None."""
    import ratelimiter_tpu.engine.native_index as ni

    from ratelimiter_tpu.parallel.sharded import shard_of_int_keys

    rng = np.random.default_rng(50 + n_parts)
    keys = rng.integers(0, 1 << 30, size=6000)  # fits an int32 output
    keys = keys[shard_of_int_keys(keys, n_parts) != 1]  # partition 1 empty
    n = len(keys)
    for bounds in _cuts(n):
        part, local, offs, (routed,) = ni.route_ranges(
            (keys,), True, n_parts, bounds)
        assert offs[2] == offs[1]
        lo = [routed[offs[p]:offs[p + 1]].astype(np.int32)
              if offs[p + 1] > offs[p] else None for p in range(n_parts)]
        neg = [None if x is None else -x for x in lo]
        add = np.arange(n_parts, dtype=np.int32) * 1000
        out0, out1 = ni.merge_ranges(part, bounds, local, offs, lo, add,
                                     neg)
        np.testing.assert_array_equal(out0, keys + add[part])
        np.testing.assert_array_equal(out1, -keys)
        one, none = ni.merge_ranges(part, bounds, local, offs, lo,
                                    np.zeros(n_parts, dtype=np.int32))
        np.testing.assert_array_equal(one, keys)
        assert none is None


def test_str_fingerprint_python_mirror_and_shard_agreement():
    """fnv_fingerprint_h1 (the Python mirror shard_of_key routes
    strings with) must equal the native hashers' h1 — and therefore
    scalar and batched string traffic agree on every key's shard."""
    import ratelimiter_tpu.engine.native_index as ni
    from ratelimiter_tpu.parallel.sharded import shard_of_key

    keys = ["alice", "", "wörld", "x" * 300, "k42"]
    lid = 7
    fp = ni.hash_str_keys(keys, lid)
    assert fp is not None
    for i, k in enumerate(keys):
        assert ni.fnv_fingerprint_h1(k.encode(), lid) == int(fp[0][i])
        assert shard_of_key((lid, k), 8) == int(fp[0][i]) % 8


def test_fps_uniques_matches_bytes_uniques():
    """The fingerprint uniques walk (string fast path) must produce the
    exact structure the packed-bytes walk does, and interoperate with
    scalar lookups on the same keys."""
    import ratelimiter_tpu.engine.native_index as ni

    keys = ["a", "b", "a", "c", "b", "a"]
    lid, rb = 5, 8
    ix_fp, ix_by = make_native(16), make_native(16)
    fp = ni.hash_str_keys(keys, lid)
    uw1, ui1, rk1, ev1 = ix_fp.assign_batch_fps_uniques(
        fp[0].copy(), fp[1].copy(), rb)
    packed, offs = ni._pack_str_keys(keys)
    uw2 = np.empty(len(keys), dtype=np.uint32)
    ui2 = np.empty(len(keys), dtype=np.int32)
    rk2 = np.empty(len(keys), dtype=np.int32)
    ev2 = np.empty(len(keys), dtype=np.int32)
    u = ix_by._lib.rl_index_assign_bytes_uniques(
        ix_by._h, packed.ctypes.data, offs.ctypes.data, len(keys),
        lid, rb, uw2.ctypes.data, ui2.ctypes.data, rk2.ctypes.data,
        ev2.ctypes.data)
    np.testing.assert_array_equal(uw1, uw2[:u])
    np.testing.assert_array_equal(ui1, ui2)
    np.testing.assert_array_equal(rk1, rk2)
    # Interop: scalar gets resolve the fp-assigned keys.
    for k in set(keys):
        assert ix_fp.get((lid, k)) is not None


def test_relay_decide_pos_matches_two_pass():
    import ratelimiter_tpu.engine.native_index as ni

    rng = np.random.default_rng(23)
    for dt in (np.uint8, np.uint16):
        u, n = 300, 2000
        counts = rng.integers(0, 200, u).astype(dt)
        uidx = rng.integers(0, u, n).astype(np.int32)
        rank = rng.integers(0, 250, n).astype(np.int32)
        pos = rng.permutation(n).astype(np.int64)
        want = np.zeros(n, dtype=bool)
        got_dense = ni.relay_decide(counts, uidx, rank)
        want[pos] = got_dense
        out = np.zeros(n, dtype=bool)
        alw = ni.relay_decide_pos(counts, uidx, rank, pos, out)
        np.testing.assert_array_equal(out, want)
        assert alw == int(got_dense.sum())


def test_sharded_index_remove_while_pinned_defers_globally():
    """ShardedSlotIndex (satellite r6 #4): the global pin_batch /
    unpin_batch used by the stream's assign->dispatch window must defer
    a removed-while-pinned slot per SHARD — the slot is never handed to
    a new key until the global unpin, and its reassignment reports it
    as its own eviction."""
    from ratelimiter_tpu.parallel.sharded import ShardedSlotIndex

    ix = ShardedSlotIndex(slots_per_shard=2, n_shards=2)
    # Find two keys on the same shard so capacity pressure is local.
    shard_keys: dict = {}
    i = 0
    while len(shard_keys.get(0, [])) < 3:
        from ratelimiter_tpu.parallel.sharded import shard_of_key

        k = (0, f"key-{i}")
        if shard_of_key(k, 2) == 0:
            shard_keys.setdefault(0, []).append(k)
        i += 1
    k_a, k_b, k_c = shard_keys[0][:3]
    s_a, _ = ix.assign(k_a)
    ix.pin_batch(np.asarray([s_a], dtype=np.int32))  # stream window pin
    s_b, _ = ix.assign(k_b)
    assert ix.remove(k_a) == s_a  # admin remove while pinned
    s_c, ev_c = ix.assign(k_c)  # shard 0 full: must NOT take s_a
    assert s_c != s_a and ev_c == s_b
    ix.unpin_batch(np.asarray([s_a], dtype=np.int32))
    s_d, ev_d = ix.assign(k_b)  # next assignment reuses the dirty slot
    assert s_d == s_a and ev_d == s_a


def test_sharded_index_pins_under_concurrent_batched_assign_remove():
    """Concurrency soak (satellite r6 #4): global pins held across
    per-shard batched assigns must keep their slots stable while other
    threads churn the same shards with batched assigns and removes.
    Asserts the pinned keys' mappings never move while pinned and that
    all pins drain (everything evictable afterward)."""
    import threading

    from ratelimiter_tpu.parallel.sharded import ShardedSlotIndex

    ix = ShardedSlotIndex(slots_per_shard=64, n_shards=2)
    # Pin a handful of keys through the same path the streams use:
    # per-shard batched assign with hold_pins, then global bookkeeping.
    pinned_keys = np.arange(8, dtype=np.int64)
    held = []
    for s in range(2):
        from ratelimiter_tpu.parallel.sharded import shard_of_int_keys

        mine = pinned_keys[shard_of_int_keys(pinned_keys, 2) == s]
        if not len(mine):
            continue
        slots, _ = ix._sub[s].assign_batch_ints(mine, 3, hold_pins=True)
        held.append((s, mine, slots + np.int32(s * 64)))
    stop = threading.Event()
    errs: list = []

    def churn(seed):
        rng = np.random.default_rng(seed)
        try:
            while not stop.is_set():
                batch = rng.integers(100, 100_000, size=64)
                for s in range(2):
                    from ratelimiter_tpu.parallel.sharded import (
                        shard_of_int_keys,
                    )

                    mine = batch[shard_of_int_keys(batch, 2) == s]
                    if len(mine):
                        ix._sub[s].assign_batch_ints(mine, 3)
                for k in rng.integers(100, 100_000, size=8):
                    ix.remove((3, int(k)))
        except Exception as exc:  # noqa: BLE001 — surfaced below
            errs.append(exc)

    threads = [threading.Thread(target=churn, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    try:
        import time as _t

        deadline = _t.monotonic() + 1.5
        while _t.monotonic() < deadline:
            for s, mine, gslots in held:
                for k, g in zip(mine, gslots):
                    assert ix.get((3, int(k))) == int(g), \
                        "pinned slot moved under concurrent churn"
            _t.sleep(0.01)
    finally:
        stop.set()
        for t in threads:
            t.join()
    assert not errs, errs
    # Release the pins through the sharded index's global unpin.
    for s, mine, gslots in held:
        ix.unpin_batch(np.ascontiguousarray(gslots, dtype=np.int32))
    # Everything is now evictable: a flood of fresh keys fully turns
    # over both shards without raising (no leaked pin refcounts).
    for k in range(200_000, 200_000 + 256):
        ix.assign((3, k))
    for s, mine, gslots in held:
        for k in mine:
            assert ix.get((3, int(k))) is None
