"""TpuBatchedStorage end-to-end: limiter classes over the device backend.

The same SlidingWindowRateLimiter / TokenBucketRateLimiter classes that run
per-op over InMemoryStorage here route whole decisions through the batched
device path — and must still match the oracle exactly.  Also covers the
slot index (LRU eviction, pinning, reuse-after-clear) and the micro-batcher
under real thread concurrency (the reference's 20-thread smoke test,
SlidingWindowRateLimiterTest.java:135-176, done for real).
"""

import random
import threading

import numpy as np
import pytest

from ratelimiter_tpu import RateLimitConfig
from ratelimiter_tpu.algorithms import SlidingWindowRateLimiter, TokenBucketRateLimiter
from ratelimiter_tpu.engine.slots import SlotIndex
from ratelimiter_tpu.metrics import MeterRegistry
from ratelimiter_tpu.semantics import SlidingWindowOracle, TokenBucketOracle
from ratelimiter_tpu.storage import TpuBatchedStorage

T0 = 1_753_000_000_000


class FakeClock:
    def __init__(self, t=T0):
        self.t = t

    def __call__(self):
        return self.t


# ---------------------------------------------------------------------------
# SlotIndex
# ---------------------------------------------------------------------------

def test_slot_index_assign_and_lru_eviction():
    idx = SlotIndex(num_slots=2)
    s_a, ev = idx.assign("a")
    assert ev is None
    s_b, ev = idx.assign("b")
    assert ev is None and s_a != s_b
    idx.get("a")  # touch: b becomes LRU
    s_c, ev = idx.assign("c")
    assert ev == s_b and s_c == s_b
    assert idx.get("b") is None
    assert idx.get("a") == s_a


def test_slot_index_pinning():
    idx = SlotIndex(num_slots=2)
    s_a, _ = idx.assign("a")
    s_b, _ = idx.assign("b")
    s_c, ev = idx.assign("c", pinned={s_a})
    assert ev == s_b  # LRU would be a, but it's pinned
    with pytest.raises(RuntimeError):
        idx.assign("d", pinned={s_a, s_c})


def test_slot_index_remove():
    idx = SlotIndex(num_slots=2)
    s_a, _ = idx.assign("a")
    assert idx.remove("a") == s_a
    assert idx.remove("a") is None
    s_b, ev = idx.assign("b")
    assert ev is None  # freed slot reused without eviction


# ---------------------------------------------------------------------------
# Differential: limiter classes over the TPU backend vs oracle
# ---------------------------------------------------------------------------

def test_sw_tpu_backend_differential():
    clock = FakeClock()
    storage = TpuBatchedStorage(num_slots=512, max_delay_ms=0.2, clock_ms=clock)
    cfg = RateLimitConfig(max_permits=20, window_ms=1000, enable_local_cache=False)
    limiter = SlidingWindowRateLimiter(storage, cfg, MeterRegistry(), clock_ms=clock)
    oracle = SlidingWindowOracle(cfg)
    rng = random.Random(5)
    keys = [f"u{i}" for i in range(6)]
    for step in range(50):
        clock.t += rng.randrange(0, 400)
        n = rng.randrange(1, 32)
        batch = [rng.choice(keys) for _ in range(n)]
        permits = [rng.randrange(1, 3) for _ in range(n)]
        got = limiter.try_acquire_many(batch, permits)
        for j in range(n):
            want = oracle.try_acquire(batch[j], permits[j], clock.t).allowed
            assert got[j] == want, (step, j)
        if rng.random() < 0.2:
            k = rng.choice(keys)
            limiter.reset(k)
            oracle.reset(k, clock.t)
        k = rng.choice(keys)
        assert limiter.get_available_permits(k) == oracle.get_available_permits(k, clock.t)
    storage.close()


def test_tb_tpu_backend_differential():
    clock = FakeClock()
    storage = TpuBatchedStorage(num_slots=512, max_delay_ms=0.2, clock_ms=clock)
    cfg = RateLimitConfig(max_permits=15, window_ms=2000, refill_rate=10.0)
    limiter = TokenBucketRateLimiter(storage, cfg, MeterRegistry(), clock_ms=clock)
    oracle = TokenBucketOracle(cfg)
    rng = random.Random(6)
    keys = [f"u{i}" for i in range(6)]
    for step in range(50):
        clock.t += rng.randrange(0, 600)
        n = rng.randrange(1, 32)
        batch = [rng.choice(keys) for _ in range(n)]
        permits = [rng.randrange(1, 18) for _ in range(n)]
        got = limiter.try_acquire_many(batch, permits)
        for j in range(n):
            want = oracle.try_acquire(batch[j], permits[j], clock.t).allowed
            assert got[j] == want, (step, j)
        k = rng.choice(keys)
        assert limiter.get_available_permits(k) == oracle.get_available_permits(k, clock.t)
    storage.close()


def test_single_acquire_through_batcher():
    clock = FakeClock()
    storage = TpuBatchedStorage(num_slots=64, max_delay_ms=0.1, clock_ms=clock)
    cfg = RateLimitConfig(max_permits=3, window_ms=60_000, enable_local_cache=False)
    limiter = SlidingWindowRateLimiter(storage, cfg, MeterRegistry(), clock_ms=clock)
    clock.t = (T0 // 60_000) * 60_000
    results = [limiter.try_acquire("u") for _ in range(5)]
    assert results == [True, True, True, False, False]
    storage.close()


def test_negative_cache_on_tpu_backend():
    clock = FakeClock((T0 // 60_000) * 60_000)
    storage = TpuBatchedStorage(num_slots=64, max_delay_ms=0.1, clock_ms=clock)
    cfg = RateLimitConfig(max_permits=2, window_ms=60_000,
                          enable_local_cache=True, local_cache_ttl_ms=10_000)
    registry = MeterRegistry()
    limiter = SlidingWindowRateLimiter(storage, cfg, registry, clock_ms=clock)
    assert limiter.try_acquire("u")
    assert limiter.try_acquire("u")
    assert not limiter.try_acquire("u")  # device-backed rejection, caches count
    hits0 = registry.counter("ratelimiter.cache.hits").count()
    assert not limiter.try_acquire("u")  # short-circuited host-side
    assert registry.counter("ratelimiter.cache.hits").count() == hits0 + 1
    storage.close()


# ---------------------------------------------------------------------------
# Concurrency (the reference's disabled 20-thread test, for real)
# ---------------------------------------------------------------------------

def test_concurrent_threads_never_exceed_limit():
    storage = TpuBatchedStorage(num_slots=64, max_delay_ms=0.3)
    cfg = RateLimitConfig(max_permits=10, window_ms=60_000, enable_local_cache=False)
    limiter = SlidingWindowRateLimiter(storage, cfg, MeterRegistry())
    n_threads, per_thread = 20, 10
    allowed = np.zeros(n_threads, dtype=np.int64)
    barrier = threading.Barrier(n_threads)

    def worker(i):
        barrier.wait()
        for _ in range(per_thread):
            if limiter.try_acquire("shared"):
                allowed[i] += 1

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # 200 requests against a 10/window limit: exactly 10 allowed.
    assert allowed.sum() == 10
    storage.close()


# ---------------------------------------------------------------------------
# Eviction under slot pressure
# ---------------------------------------------------------------------------

def test_eviction_reuses_slots_cleanly():
    clock = FakeClock()
    storage = TpuBatchedStorage(num_slots=8, max_delay_ms=0.1, clock_ms=clock)
    cfg = RateLimitConfig(max_permits=2, window_ms=60_000, enable_local_cache=False)
    limiter = SlidingWindowRateLimiter(storage, cfg, MeterRegistry(), clock_ms=clock)
    clock.t = (T0 // 60_000) * 60_000
    # Drain key k0's budget, then push enough distinct keys to evict it.
    assert limiter.try_acquire("k0")
    assert limiter.try_acquire("k0")
    assert not limiter.try_acquire("k0")
    for i in range(1, 9):
        assert limiter.try_acquire(f"k{i}")
    # k0 was evicted (LRU): it starts fresh — a documented consequence of
    # finite slot capacity; operators size num_slots >= active keys.
    assert limiter.try_acquire("k0")
    storage.close()


def test_legacy_contract_still_works_on_tpu_storage():
    clock = FakeClock()
    storage = TpuBatchedStorage(num_slots=16, clock_ms=clock)
    assert storage.increment_and_expire("c", 1000) == 1
    assert storage.get("c") == 1
    storage.set("c", 7, 1000)
    assert storage.compare_and_set("c", 7, 9)
    storage.z_add("z", 1.0, "m")
    assert storage.z_count("z", 0, 2) == 1
    assert storage.is_available()
    storage.close()


def test_stream_permits_over_i32_denied_not_wrapped():
    """The stream path carries permits as i32 lanes; a value past 2^31-1
    would wrap negative and turn a reject into an allow-with-credit.  It
    must be DENIED (identical to the i64 batch path, where any permits
    above int32 exceeds every limiter's max_permits) — and must not
    consume or credit tokens for neighbouring requests."""
    import numpy as np

    storage = TpuBatchedStorage(num_slots=64, clock_ms=lambda: 10_000)
    lid = storage.register_limiter(
        "tb", RateLimitConfig(max_permits=5, window_ms=1000, refill_rate=1.0))
    got = storage.acquire_stream_ids(
        "tb", lid, np.asarray([1, 1, 1], dtype=np.int64),
        np.asarray([1, 1 << 31, 4], dtype=np.int64), batch=16, subbatches=1)
    # 1 allowed; oversized denied; 4 still allowed (bucket untouched by #2).
    assert got.tolist() == [True, False, True]
    # Batch-path agreement on a fresh key.
    batch = storage.acquire_many_ids(
        "tb", lid, np.asarray([2], dtype=np.int64),
        np.asarray([1 << 31], dtype=np.int64))
    assert not batch["allowed"][0]
    storage.close()


# The cells' chunk constants (storage/tpu.py) scaled down by this factor,
# so a call of 2^21 / _PIN_SCALE ids cuts like a 2^21-id benchmark call.
_PIN_SCALE = 128


def _pin_scale(monkeypatch, tpu_mod):
    for name, value in (("_RELAY_CHUNK", 1 << 19),
                        ("_RELAY_CHUNK_MAX", 1 << 24),
                        ("_RELAY_WIRE_BUDGET_DIGEST", 16 << 20),
                        ("_RELAY_WIRE_BUDGET_WORDS", 16 << 20),
                        ("_RELAY_WIRE_BUDGET_WEIGHTED", 48 << 20),
                        ("_SORT_UNIQUES_MIN", 1 << 12)):
        monkeypatch.setattr(tpu_mod, name, value // _PIN_SCALE)


def _zipf_ids(rng, n, keys, s=1.1):
    """Rank-ordered Zipf(s) ids over ``keys`` keys (id 0 the hottest)."""
    p = np.arange(1, keys + 1, dtype=np.float64) ** -s
    return rng.choice(keys, size=n, p=p / p.sum()).astype(np.int64)


# family -> (path, per call: [(chunk size, mode), ...]) for the second
# and third calls of one storage.  Zipf 4096 + 12288 and uniform
# 2 x 4096 are the cells' 524,288 + 1,572,864 and 2 x 524,288 scaled.
_PINNED_CHUNKS = {
    "tb-ints-zipf": ("relay", [
        [(4096, "digest-sorted"), (12288, "digest-sorted")],
        [(4096, "digest-sorted"), (12288, "digest-sorted")]]),
    "sw-ints-uniform": ("relay", [
        [(4096, "digest-sorted"), (4096, "digest-sorted")],
        [(4096, "digest-sorted"), (4096, "digest-sorted")]]),
    "tb-weighted": ("relay_w", [
        [(4096, "weighted"), (62914, "weighted"), (64062, "weighted")],
        [(4096, "weighted"), (62914, "weighted"), (64062, "weighted")]]),
    "tb-multi-lid": ("relay", [
        [(4096, "digest"), (42737, "digest"), (18703, "digest")],
        [(4096, "digest"), (58153, "digest"), (3287, "digest")]]),
    "sw-strs": ("relay", [
        [(4096, "bits"), (31775, "bits"), (29665, "bits")],
        [(4096, "bits"), (31775, "bits"), (29665, "bits")]]),
}


@pytest.mark.parametrize("family", ["tb-ints-zipf", "sw-ints-uniform",
                                    "tb-weighted", "tb-multi-lid",
                                    "sw-strs"])
def test_stream_chunk_sequence_pinned(monkeypatch, family):
    """The stream loops' chunk sequence, pinned: each call's chunk sizes
    and modes on the second and third calls of one storage, at the
    cells' constants scaled down by _PIN_SCALE.  The Zipf and uniform
    families force the sorted digest step the cells run on the chip
    (the tile sweep serves both there); multi-lid and string keys take
    the bytes rule; weighted runs its own loop.  A change to the growth
    schedule or the mode rule shows here as a different sequence."""
    import ratelimiter_tpu.storage.tpu as tpu_mod
    from ratelimiter_tpu.engine.native_index import native_available

    if not native_available():
        pytest.skip("needs the native library")
    _pin_scale(monkeypatch, tpu_mod)
    if family in ("tb-ints-zipf", "sw-ints-uniform"):
        monkeypatch.setattr(tpu_mod, "_presorted_scatter_usable",
                            lambda eng, algo, padded: True)
    algo = family[:2]
    now = [T0]
    rng = np.random.default_rng(sum(map(ord, family)))
    zipf_keys = (1 << 20) // _PIN_SCALE
    uniform_keys = 10_000_000 // _PIN_SCALE
    st = TpuBatchedStorage(num_slots=1 << 17, clock_ms=lambda: now[0])
    cfg = (RateLimitConfig(max_permits=100, window_ms=60_000,
                           enable_local_cache=False) if algo == "sw"
           else RateLimitConfig(max_permits=50, window_ms=60_000,
                                refill_rate=10.0))
    lid = st.register_limiter(algo, cfg)
    lid2 = st.register_limiter(algo, RateLimitConfig(
        max_permits=5, window_ms=60_000, refill_rate=1.0))

    def call():
        if family == "tb-ints-zipf":
            return st.acquire_stream_ids(
                algo, lid, _zipf_ids(rng, (1 << 21) // _PIN_SCALE,
                                     zipf_keys))
        if family == "sw-ints-uniform":
            return st.acquire_stream_ids(
                algo, lid, rng.integers(0, uniform_keys,
                                        (1 << 20) // _PIN_SCALE))
        if family == "tb-weighted":
            n = 1 << 17
            return st.acquire_stream_ids(
                algo, lid, rng.integers(0, 1 << 15, n),
                rng.integers(1, 4, n))
        if family == "tb-multi-lid":
            n = 1 << 16
            return st.acquire_stream_ids(
                algo, np.where(rng.random(n) < 0.5, lid, lid2),
                _zipf_ids(rng, n, zipf_keys))
        keys = [f"user:{k}" for k in rng.integers(0, uniform_keys,
                                                  1 << 16)]
        return st.acquire_stream_strs(algo, lid, keys)

    seen = []
    for _ in range(3):
        st.stream_stats = stats = []
        call()
        seen.append([(r["path"], r["n"], r["mode"]) for r in stats])
        now[0] += 100
    st.close()
    path, want = _PINNED_CHUNKS[family]
    for got, calls in zip(seen[1:], want):
        assert got == [(path, c, m) for c, m in calls], (family, seen)


_BOUNDARY_C = 256


@pytest.mark.parametrize("n", [_BOUNDARY_C - 1, _BOUNDARY_C, _BOUNDARY_C + 1,
                               3 * _BOUNDARY_C + 5])
@pytest.mark.parametrize("weighted", [False, True])
def test_chunk_plan_pipelined_preserves_decisions(monkeypatch, weighted, n):
    """Chunk boundaries of the growth schedule, with the next chunk's
    walk prefetched: a call one short of, at, one past and well past
    the first chunk cuts where the schedule says, and every decision of
    every call equals the oracle's (keys repeat across boundaries)."""
    import ratelimiter_tpu.storage.tpu as tpu_mod
    from ratelimiter_tpu.semantics import TokenBucketOracle

    c = _BOUNDARY_C
    monkeypatch.setattr(tpu_mod, "_RELAY_CHUNK", c)
    monkeypatch.setattr(tpu_mod, "_RELAY_CHUNK_MAX", 2 * c)
    now = [1_000_000]
    rng = np.random.default_rng(3)
    cfg = RateLimitConfig(max_permits=20, window_ms=60_000, refill_rate=1.0)
    st = TpuBatchedStorage(num_slots=4096, clock_ms=lambda: now[0])
    lid = st.register_limiter("tb", cfg)
    oracle = TokenBucketOracle(cfg)
    want_sizes = {c - 1: [c - 1], c: [c], c + 1: [c, 1],
                  3 * c + 5: [c, 2 * c, 5]}[n]
    for call in range(3):
        ids = rng.integers(0, 40, n).astype(np.int64)
        perms = rng.integers(1, 5, n).astype(np.int64) if weighted else None
        st.stream_stats = stats = []
        got = st.acquire_stream_ids("tb", lid, ids, perms)
        assert [r["n"] for r in stats] == want_sizes, stats
        for j, k in enumerate(ids):
            p = int(perms[j]) if weighted else 1
            assert got[j] == oracle.try_acquire(f"id:{k}", p,
                                                now[0]).allowed, (call, j)
        now[0] += 700
    st.close()


def test_rate_aware_mode_election(monkeypatch):
    """_elect_digest_mode: a chunk the sorted sweep serves is elected by
    device seconds alone (per unique against per lane); any other falls
    back to bytes.  The cells' own chunk shapes (PERF.md §5) go to
    digest when sorted; under the bytes rule the uniform chunk goes to
    words and the Zipf chunk, a sixth of it distinct, stays digest."""
    import ratelimiter_tpu.storage.tpu as tpu_mod
    from ratelimiter_tpu.storage.tpu import _elect_digest_mode

    dig_bpu, words_bpr = 6.0, 4.125
    u, cn = 900_000, 1_000_000  # u/n = 0.9: wire alone says words
    assert not _elect_digest_mode(u, cn, 0, dig_bpu, words_bpr,
                                  False)  # bytes-only fallback: words
    # Sweep engaged: 25 ns per unique beats 60 ns per lane.
    assert _elect_digest_mode(u, cn, 0, dig_bpu, words_bpr, True)
    # Zipf chunk 1 (188,052 uniques of 1,572,864) and a uniform chunk
    # (510,815 of 524,288).
    for u_c, cn_c in ((188_052, 1_572_864), (510_815, 524_288)):
        assert _elect_digest_mode(u_c, cn_c, 0, dig_bpu, words_bpr, True)
    assert _elect_digest_mode(188_052, 1_572_864, 0, dig_bpu, words_bpr,
                              False)
    assert not _elect_digest_mode(510_815, 524_288, 0, dig_bpu, words_bpr,
                                  False)
    # A dearer sorted step per unique than per lane flips it to words.
    monkeypatch.setattr(tpu_mod, "_DEVICE_S_PER_UNIQUE_SORTED", 70e-9)
    assert not _elect_digest_mode(u, cn, 0, dig_bpu, words_bpr, True)
    # Multi-lid bytes (10 B/unique vs 8.125 B/request) with the delta
    # charge: dedup-poor chunks stay words, dedup-rich ones go digest.
    assert not _elect_digest_mode(950_000, cn, 950_000, 10.0, 8.125, False)
    assert _elect_digest_mode(cn // 3, cn, cn // 3, 10.0, 8.125, False)


def test_drain_set_error_propagation_and_backpressure():
    """_DrainSet: finish() re-raises the first drain error once all
    drains land; finish(swallow=True) waits but never raises (the
    primary-exception path); submit() bounds in-flight drains."""
    import concurrent.futures as cf
    import threading
    import time as _time

    from ratelimiter_tpu.storage.tpu import _DrainSet

    pool = cf.ThreadPoolExecutor(4)
    try:
        ds = _DrainSet(pool, inflight=2)
        done = []

        def ok(i):
            _time.sleep(0.01)
            done.append(i)

        def boom(i):
            raise RuntimeError(f"drain {i} failed")

        ds.submit(ok, 1)
        ds.submit(boom, 2)
        ds.submit(ok, 3)
        with pytest.raises(RuntimeError, match="drain 2 failed"):
            ds.finish()
        assert sorted(done) == [1, 3]  # every drain ran to completion
        ds.finish()  # cleared: a second finish is a no-op
        # swallow=True: waits, never raises.
        ds.submit(boom, 4)
        ds.finish(swallow=True)
        # Backpressure: with inflight=2, the third submit must WAIT on
        # the oldest live drain (released by a timer thread) instead of
        # queueing unboundedly — measured by the submit's block time.
        gate = threading.Event()
        slow_done = []

        def slow(i):
            gate.wait(5.0)
            slow_done.append(i)

        ds.submit(slow, 1)
        ds.submit(slow, 2)
        threading.Timer(0.2, gate.set).start()
        t0 = _time.perf_counter()
        ds.submit(slow, 3)  # blocks on live[0] until the gate opens
        blocked = _time.perf_counter() - t0
        ds.finish()
        assert sorted(slow_done) == [1, 2, 3]
        assert blocked >= 0.15, blocked  # the cap actually held
    finally:
        pool.shutdown(wait=False)


# ---------------------------------------------------------------------------
# Clock-regression clamp observability
# ---------------------------------------------------------------------------

def test_backward_clock_clamped_and_counted():
    """A wall clock stepping backwards (NTP) is absorbed by the monotonic
    stamp clamp — and now COUNTED in ratelimiter.time.backward_clamp so
    the event is observable instead of silent."""
    clock = FakeClock()
    registry = MeterRegistry()
    storage = TpuBatchedStorage(num_slots=64, max_delay_ms=0.1,
                                clock_ms=clock, meter_registry=registry)
    try:
        meter = registry.counter("ratelimiter.time.backward_clamp")
        assert storage._monotonic_now() == T0
        clock.t = T0 - 5_000  # NTP step backwards
        assert storage._monotonic_now() == T0  # clamped, not regressed
        assert storage.backward_clamps == 1
        assert meter.count() == 1
        clock.t = T0 - 1  # still behind: every regressed read counts
        assert storage._monotonic_now() == T0
        assert storage.backward_clamps == 2
        assert meter.count() == 2
        clock.t = T0 + 7
        assert storage._monotonic_now() == T0 + 7  # clock caught up
        assert storage.backward_clamps == 2

        # Decisions keep flowing at the clamped stamp: a regressed batch
        # must not roll windows backwards or zero live counts.
        lid = storage.register_limiter("sw", RateLimitConfig(
            max_permits=3, window_ms=60_000, enable_local_cache=False))
        clock.t = ((T0 + 7) // 60_000) * 60_000 + 120_000  # fresh window
        allowed = [storage.acquire("sw", lid, "ntp", 1)["allowed"]
                   for _ in range(3)]
        clock.t -= 90_000  # regress past a window boundary
        denied = storage.acquire("sw", lid, "ntp", 1)["allowed"]
        assert allowed == [True, True, True] and not denied
        assert storage.backward_clamps >= 3
    finally:
        storage.close()
