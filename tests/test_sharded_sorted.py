"""The sharded relay stream on the one-chip sorted-sweep step, on a mesh
of four of the CPU test devices: decisions equal the single-device
stream and the oracle, each shard's digest is slot-sorted in its lane
and written by the tile sweep (interpret mode) where the sweep supports
its uniques, the lanes' spans and counters, and the one sharded-storage
factory."""

import threading

import jax
import numpy as np
import pytest

from ratelimiter_tpu import RateLimitConfig
from ratelimiter_tpu.engine.native_index import native_available
from ratelimiter_tpu.engine.state import LimiterTable
from ratelimiter_tpu.ops.pallas import block_scatter
from ratelimiter_tpu.parallel import ShardedDeviceEngine, make_mesh
from ratelimiter_tpu.parallel.sharded import shard_of_int_keys
from ratelimiter_tpu.semantics import TokenBucketOracle
from ratelimiter_tpu.storage import TpuBatchedStorage, sharded_storage
from ratelimiter_tpu.storage import tpu as tpu_mod

T0 = 1_760_000_000_000
KEYS = 4000
CFG = RateLimitConfig(max_permits=50, window_ms=60_000, refill_rate=10.0)

pytestmark = pytest.mark.skipif(not native_available(),
                                reason="needs the native slot index")


@pytest.fixture
def sweep(monkeypatch):
    """The tile sweep in interpret mode, gated by ``supported()`` alone."""
    monkeypatch.setattr(block_scatter, "_INTERPRET", True)
    monkeypatch.setattr(block_scatter, "_probe_ok", None)
    monkeypatch.setattr(tpu_mod, "_SORT_UNIQUES_MIN", 1)


def _devices():
    return jax.devices()[:4]


def _zipf_calls(seed, n_calls, n_ids, keys=KEYS):
    rng = np.random.default_rng(seed)
    p = np.arange(1, keys + 1, dtype=np.float64) ** -1.1
    return [rng.choice(keys, size=n_ids, p=p / p.sum()).astype(np.int64)
            for _ in range(n_calls)]


def _paths(storage):
    return [(r["path"], r["shard"])
            for r in storage.trace.snapshot(last=4096)["recent"]]


def _shards(ids):
    return shard_of_int_keys(ids, 4)


def _no_shard3(calls):
    """Every call without the keys of shard 3: its lane gets nothing."""
    return [ids[_shards(ids) != 3] for ids in calls]


def _few_on_shard0(calls):
    """Shard 0 sees only its 300 hottest keys: under 512 uniques, so its
    digest pads below the sweep's 1024-update floor (``supported()``
    says no) and takes XLA's row write."""
    keep = np.flatnonzero(_shards(np.arange(KEYS)) == 0)[:300]
    out = []
    for ids in calls:
        on0 = _shards(ids) == 0
        out.append(ids[~on0 | np.isin(ids, keep)])
    return out


@pytest.mark.parametrize("shape", ["every_shard", "empty_shard",
                                   "below_supported"])
def test_sharded_sorted_stream_matches_single_device_and_oracle(
        sweep, shape):
    calls = _zipf_calls(29, 6, 1 << 14)
    calls = {"every_shard": calls, "empty_shard": _no_shard3(calls),
             "below_supported": _few_on_shard0(calls)}[shape]
    now = [T0]
    sharded = sharded_storage(5000, _devices(), clock_ms=lambda: now[0])
    single = TpuBatchedStorage(num_slots=1 << 13, clock_ms=lambda: now[0])
    lid_a = sharded.register_limiter("tb", CFG)
    lid_b = single.register_limiter("tb", CFG)
    oracle = TokenBucketOracle(CFG)
    try:
        for c, ids in enumerate(calls):
            got = sharded.acquire_stream_ids("tb", lid_a, ids, None)
            want = single.acquire_stream_ids("tb", lid_b, ids, None)
            np.testing.assert_array_equal(got, want, err_msg=f"call {c}")
            np.testing.assert_array_equal(
                got, [oracle.try_acquire(f"id:{k}", 1, now[0]).allowed
                      for k in ids.tolist()], err_msg=f"call {c}")
            now[0] += 100
        paths = _paths(sharded)
    finally:
        sharded.close()
        single.close()
    assert len(paths) == len(calls) * (3 if shape == "empty_shard" else 4)
    for path, shard in paths:
        unsorted = shape == "below_supported" and shard == 0
        assert path == ("sharded|digest" if unsorted
                        else "sharded|digest-sorted"), (path, shard)
    if shape == "empty_shard":
        assert 3 not in {s for _, s in paths}


def test_relay_shard_dispatch_sorted_equals_unsorted_write(sweep):
    """The same digest, dispatched once in slot order through the sweep
    and once in a shuffled order through XLA's write, gives the same
    per-unique counts and leaves the same shard state."""
    rng = np.random.default_rng(3)
    engines = [ShardedDeviceEngine(slots_per_shard=4096,
                                   table=LimiterTable(),
                                   mesh=make_mesh(_devices()))
               for _ in range(2)]
    rows = rng.integers(-(1 << 31), 1 << 31, (4 * 4096, 4), dtype=np.int64)
    for eng in engines:
        lid = eng.table.register(CFG)
        eng.write_rows("tb", np.arange(4 * 4096), rows.astype(np.int32))
    rb = engines[0].rank_bits
    shard, u = 2, 1500
    slots = np.sort(rng.choice(4096, size=u, replace=False))
    counts = rng.integers(1, 60, size=u)
    uw = ((slots.astype(np.uint32) << np.uint32(rb + 1))
          | (counts.astype(np.uint32) << np.uint32(1)))
    perm = rng.permutation(u)
    pad = np.full(2048 - u, 0xFFFFFFFF, dtype=np.uint32)
    now = T0 + 12_345
    a = engines[0].relay_shard_dispatch(
        "tb", shard, "counts", np.r_[uw, pad], lid, now, np.uint8,
        slots_sorted=True)
    b = engines[1].relay_shard_dispatch(
        "tb", shard, "counts", np.r_[uw[perm], pad], lid, now, np.uint8)
    np.testing.assert_array_equal(np.asarray(a)[:u][perm],
                                  np.asarray(b)[:u])
    for s in range(4):
        np.testing.assert_array_equal(np.asarray(engines[0]._parts["tb"][s]),
                                      np.asarray(engines[1]._parts["tb"][s]))


def test_sharded_storage_aligns_slots_and_serves_build_storage(
        monkeypatch):
    from ratelimiter_tpu.service import wiring
    from ratelimiter_tpu.service.props import AppProperties

    st = sharded_storage(5000, _devices())
    try:
        assert st.engine.n_shards == 4
        assert st.engine.slots_per_shard == 1280  # align_slots(1250)
        assert [d.id for d in st.engine.mesh.devices.flat] == \
            [d.id for d in _devices()]
    finally:
        st.close()
    built = []

    def spy(*args, **kw):
        built.append(args)
        return sharded_storage(*args, **kw)

    monkeypatch.setattr(wiring, "sharded_storage", spy)
    st = wiring.build_storage(AppProperties({
        "storage.backend": "tpu", "storage.num_slots": "5000",
        "parallel.shard": "auto"}))
    try:
        assert len(built) == 1 and built[0][0] == 5000
        assert st.engine.n_shards == len(jax.devices())
        assert st.engine.slots_per_shard == 768  # align_slots(625)
    finally:
        st.close()


def test_shard_straggle_fed_once_per_multi_shard_chunk(monkeypatch):
    """Four chunks over four shards feed the straggle four times and the
    ``shard`` span once per active lane; a chunk whose keys all live on
    one shard feeds no straggle; the per-shard counters add up to the
    keys routed to each shard, by the shard hash."""
    monkeypatch.setattr(tpu_mod, "_RELAY_CHUNK", 4096)
    monkeypatch.setattr(tpu_mod, "_RELAY_CHUNK_MAX", 4096)
    st = sharded_storage(5000, _devices(), clock_ms=lambda: T0)
    lid = st.register_limiter("tb", CFG)
    ids = _zipf_calls(7, 1, 1 << 14)[0]
    one = np.flatnonzero(_shards(np.arange(KEYS)) == 1)[:50]
    lone = np.repeat(one, 20)  # one chunk, one shard
    try:
        st.acquire_stream_ids("tb", lid, ids, None)
        st.acquire_stream_ids("tb", lid, lone, None)
        meters = st.registry.meters()
    finally:
        st.close()
    timer = lambda name: meters[f"ratelimiter.stream.{name}"].count()
    assert timer("route") == 5
    assert timer("shard_straggle") == 4
    assert timer("shard") == 4 * 4 + 1
    want = np.bincount(_shards(np.r_[ids, lone]), minlength=4)
    got = [meters[f"ratelimiter.stream.shard_lanes.{s}"].count()
           for s in range(4)]
    assert got == want.tolist()
    assert meters["ratelimiter.stream.shard_uniques.1"].count() >= 50


def test_single_device_stream_has_no_shard_lanes():
    """The one-chip stream starts no shard lane and feeds no ``shard*``
    timer or counter: what the sharded loop adds stays off its path."""
    before = set(threading.enumerate())
    st = TpuBatchedStorage(num_slots=1 << 13, clock_ms=lambda: T0)
    lid = st.register_limiter("tb", CFG)
    try:
        st.acquire_stream_ids("tb", lid, _zipf_calls(5, 1, 1 << 14)[0],
                              None)
        started = [t.name for t in threading.enumerate()
                   if t not in before]
        meters = st.registry.meters()
    finally:
        st.close()
    assert started and not [n for n in started if n.startswith("shard")], \
        started
    shard_meters = {k: m for k, m in meters.items()
                    if k.startswith("ratelimiter.stream.shard")}
    assert set(shard_meters) == {"ratelimiter.stream.shard",
                                 "ratelimiter.stream.shard_straggle"}
    assert all(m.count() == 0 for m in shard_meters.values())
    assert meters["ratelimiter.stream.index"].count() > 0


def _route_buffers(meters):
    return tuple(meters[f"ratelimiter.stream.route_buffers.{k}"].count()
                 for k in ("reused", "allocated"))


def test_sharded_route_buffers_reused_across_calls(monkeypatch):
    """Consecutive calls on one sharded storage route into buffer sets
    kept from earlier calls: a call of four chunks (two in flight
    beyond the oldest) gives its four sets back and the next call takes
    all four again; a call whose chunks outgrow every kept set
    allocates new ones.  Every call decides as the single-device
    storage on the same ids."""
    monkeypatch.setattr(tpu_mod, "_RELAY_CHUNK", 4096)
    monkeypatch.setattr(tpu_mod, "_RELAY_CHUNK_MAX", 4096)
    now = [T0]
    sharded = sharded_storage(5000, _devices(), clock_ms=lambda: now[0])
    single = TpuBatchedStorage(num_slots=1 << 13, clock_ms=lambda: now[0])
    lid_a = sharded.register_limiter("tb", CFG)
    lid_b = single.register_limiter("tb", CFG)
    calls = _zipf_calls(31, 2, 1 << 14) + _zipf_calls(32, 1, 1 << 15)
    seen = []
    try:
        for c, ids in enumerate(calls):
            if c == 2:  # larger chunks than any kept set holds
                monkeypatch.setattr(tpu_mod, "_RELAY_CHUNK", 8192)
                monkeypatch.setattr(tpu_mod, "_RELAY_CHUNK_MAX", 8192)
            got = sharded.acquire_stream_ids("tb", lid_a, ids, None)
            want = single.acquire_stream_ids("tb", lid_b, ids, None)
            np.testing.assert_array_equal(got, want, err_msg=f"call {c}")
            seen.append(_route_buffers(sharded.registry.meters()))
            now[0] += 100
    finally:
        sharded.close()
        single.close()
    assert seen[0] == (0, 4)
    assert seen[1] == (4, 4)
    assert seen[2][0] >= 4 and seen[2][1] > 4
    assert sum(seen[2]) == 4 + 4 + 4  # one take per chunk


def test_sharded_route_buffers_given_back_after_a_lane_fails(monkeypatch):
    """A call whose shard dispatch fails still gives back every route
    set it took, once its lanes and drains are done; the next call
    takes them again and decides as a fresh single-device storage on
    keys the failed call never touched."""
    monkeypatch.setattr(tpu_mod, "_RELAY_CHUNK", 4096)
    monkeypatch.setattr(tpu_mod, "_RELAY_CHUNK_MAX", 4096)
    now = [T0]
    sharded = sharded_storage(20000, _devices(), clock_ms=lambda: now[0])
    single = TpuBatchedStorage(num_slots=1 << 13, clock_ms=lambda: now[0])
    lid_a = sharded.register_limiter("tb", CFG)
    lid_b = single.register_limiter("tb", CFG)
    eng = sharded.engine
    real = eng.relay_shard_dispatch
    n_calls = [0]

    def fail_second(*args, **kw):
        n_calls[0] += 1
        if n_calls[0] == 2:
            raise RuntimeError("injected sharded dispatch")
        return real(*args, **kw)

    monkeypatch.setattr(eng, "relay_shard_dispatch", fail_second)
    first, second = _zipf_calls(33, 2, 1 << 14)
    second = second + KEYS  # keys the failed call never touched
    try:
        with pytest.raises(RuntimeError, match="injected sharded dispatch"):
            sharded.acquire_stream_ids("tb", lid_a, first, None)
        reused, taken = _route_buffers(sharded.registry.meters())
        assert reused == 0 and taken >= 1
        now[0] += 100
        got = sharded.acquire_stream_ids("tb", lid_a, second, None)
        want = single.acquire_stream_ids("tb", lid_b, second, None)
        after = _route_buffers(sharded.registry.meters())
    finally:
        sharded.close()
        single.close()
    np.testing.assert_array_equal(got, want)
    assert after == (min(taken, 4), taken + 4 - min(taken, 4))
