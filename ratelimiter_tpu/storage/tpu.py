"""TpuBatchedStorage — the TPU-resident storage backend.

The BASELINE.json north star realized: behind the ``RateLimitStorage``
plugin boundary, ``tryAcquire()`` calls are micro-batched on the host and
dispatched to a TPU-resident counter array, replacing the reference's
per-request Redis round-trip (~800 us each, ARCHITECTURE.md latency model)
with one device step per thousands of decisions.

Two protocols on one object:

1. The **batched decision protocol** (``register_limiter`` / ``acquire`` /
   ``acquire_many`` / ``available_many`` / ``reset_key``): the hot path.
   Algorithm classes detect ``supports_device_batching`` and route whole
   decisions here; the sliding-window estimate and token-bucket refill run
   as device kernels (ops/sliding_window.py, ops/token_bucket.py) with
   decisions bit-identical to ``semantics/oracle.py``.

2. The **legacy 10-method contract** (storage/RateLimitStorage.java:10-70):
   fully implemented for interface parity.  Generic counters/zsets/ad-hoc
   scripts execute host-side against an embedded ``InMemoryStorage`` (the
   exact same decision math — the device path exists for *registered*
   limiters, just as Redis Lua scripts exist for deployed workloads).

Key -> slot assignment and eviction live in ``SlotIndex``; cleared slots are
zeroed in the dispatch stream ahead of their reuse.
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
from concurrent.futures import Future
from typing import Callable, Dict, List, Sequence, Tuple

import numpy as np
from jax.profiler import TraceAnnotation

from ratelimiter_tpu.core.config import RateLimitConfig
from ratelimiter_tpu.engine.batcher import MicroBatcher
from ratelimiter_tpu.engine.errors import (
    OverloadedError,
    consume_pending_clears,
)
from ratelimiter_tpu.engine.engine import DeviceEngine
from ratelimiter_tpu.engine.state import LimiterTable
from ratelimiter_tpu.storage.base import RateLimitStorage
from ratelimiter_tpu.storage.memory import InMemoryStorage
from ratelimiter_tpu.utils.logging import get_logger

log = get_logger("storage.tpu")


# Per-dispatch lane cap for the SORTED flat step (ops/flat.py): its
# sort/associative-scan ops compile super-linearly on XLA:TPU
# (bench/profile_compile.py), so dispatches are cut to this size and
# pipelined instead.  The unit-permit relay step (ops/relay.py) has no
# sort/scan and takes no cap.
_FLAT_MAX_LANES = 1 << 19

# Relay-path growth schedule, the one chunk plan of the stream loops: the
# first chunk probes the stream's duplicate structure at the floor size;
# each later chunk sizes itself to a per-dispatch wire budget at the
# previous chunk's bytes per request, within [_RELAY_CHUNK,
# _RELAY_CHUNK_MAX].  Zipf dedup improves with chunk size (u/cn drops),
# so skewed streams run a floor chunk and then one large one (a 2^21-id
# Zipf call: 524,288 + 1,572,864); duplicate-poor 2^20-id calls run two
# floor chunks.  The budgets date from a remote-link deployment and were
# kept because they give the cells these shapes.
_RELAY_CHUNK = 1 << 19
_RELAY_CHUNK_MAX = 1 << 24
_RELAY_WIRE_BUDGET_DIGEST = 16 << 20
_RELAY_WIRE_BUDGET_WORDS = 16 << 20

# Slot-sort threshold for digest dispatches: at or above this many
# uniques the C index re-sorts the chunk's uniques by slot (O(u) radix +
# O(n) uidx remap, ~2-4 ms on a 1M-unique chunk) so the device scatter
# runs as the dense presorted block sweep instead of XLA's ~45 ns/index
# generic scatter (measured 3.5x cheaper at 512K rows — ROUND_NOTES r4).
_SORT_UNIQUES_MIN = 1 << 12

# Mode-election amortization for the resident-lid delta upload: a (slot,
# lid) pair is paid ONCE and then serves every later digest chunk that
# touches the slot, so the election charges it at 1/4 — without this a
# churn-heavy pass (every lid fresh) elects words mode, words mode never
# uploads lids, and the stream is stuck paying 8.125 B/request forever
# instead of reaching the ~6 B/unique resident steady state.
_DELTA_AMORT = 4

# Weighted relay wire budget: the rank-major layout has no sort/scan
# compile ceiling and ~1.5-4 B/request wire cost, so chunks amortize
# best when the whole pass is a handful of dispatches.
_RELAY_WIRE_BUDGET_WEIGHTED = 48 << 20

# Concurrent in-flight drains: enough to overlap every in-flight fetch,
# small enough to bound queued result buffers.
_DRAIN_WORKERS = 4
_DRAIN_INFLIGHT = 4
# Per-shard stream pipelining (r8): how many chunks the routing pass may
# run ahead of the oldest still-assembling chunk.  Each lane additionally
# bounds its own drain queue (see _ShardLane), so total staging memory is
# O(lookahead + drain bound) chunks.
_SHARD_LOOKAHEAD = 2
# Undrained dispatches a single shard lane may hold before its submit
# blocks (and flags shard.drain_saturated to the flight recorder).
_SHARD_DRAIN_INFLIGHT = 2
# The sharded loop's route of a chunk runs over at most this many
# request ranges, side by side, one per _RANGE_GRAIN requests
# (engine/partitioned.py); the caller's lanes and chips wait for it.
_ROUTE_RANGES = 8
# Route buffer sets the storage keeps between calls: every set of a call
# of up to four chunks (a sharded stream's first call, before its chunk
# size is learned, cuts four; later calls of the same shape cut one).
_ROUTE_SETS_KEPT = 4
# Device step seconds the words-vs-digest rule charges a chunk the
# sorted sweep serves (v5e, ROUND_NOTES r4): the relay words step per
# lane, the sorted digest step per unique.
_DEVICE_S_PER_LANE = 60e-9
_DEVICE_S_PER_UNIQUE_SORTED = 25e-9

# Auto-elected host-parallel partitioned index (VERDICT r5 next-round
# #2): the C slot walk is DRAM-latency-bound and was the headline
# bench's largest single CPU term, while the partitioned index built to
# split it sat unused outside its own tests.  Storage construction now
# elects host_parallel = min(cores, 8) by itself when the native index
# is available, the engine is single-device, the host has more than two
# cores, and the table is large enough that streaming walks dominate
# (small tables keep the single-LRU index: interactive/test workloads
# are not walk-bound, and per-partition LRU slightly changes eviction
# order — not a trade worth making for a 4K-slot table).  An explicit
# ``host_parallel=`` kwarg always wins (0 disables).
_HOST_PARALLEL_AUTO_MIN_SLOTS = 1 << 16
_HOST_PARALLEL_AUTO_MAX = 8

# Weighted relay: longest rank-major permit matrix the scan step accepts.
# A chunk whose deepest segment exceeds this (heavy duplication — Zipf
# bursts) dispatches through the sorted flat step instead; duplicate-poor
# weighted traffic (the burst batch-acquire scenario) stays on the relay.
_WREL_MAX_R = 64

# Zipf key coalescing: chunks whose repeated keys carry segment-uniform
# permits dispatch ONE weighted decision per unique key
# (ops/relay.py:*_relay_weighted_counts) and reconstruct per-request
# booleans host-side, so device work and wire bytes scale with uniques
# instead of requests.  Opt-out knob for A/B runs (bench/coalesce_smoke.py).
_COALESCE = os.environ.get("RATELIMITER_COALESCE", "1") != "0"


def _bucket_pow2(n: int) -> int:
    from ratelimiter_tpu.parallel.sharded import _bucket

    return _bucket(n, floor=4096)


def _bucket_fine(n: int, floor: int = 4096) -> int:
    """Quarter-octave bucketing: next multiple of octave/4 (for n in
    (2^(L-1), 2^L] the step is 2^(L-3)) — 4 compile shapes per octave
    instead of 1.  Worst-case padding ~25% just above a power of two,
    ~12% at the octave top, vs ~100% for plain pow2 rounding (used where
    a lane's bytes dominate the wire)."""
    if n <= floor:
        return floor
    step = 1 << (int(n - 1).bit_length() - 3)
    return -(-n // step) * step


# Injectable per-process clock offset (chaos conductor, ARCHITECTURE
# §17): every default now-source in this process reads wall time PLUS
# this skew, so cross-cell clock skew and step jumps are testable
# against a real clock instead of dodged with order-only policies.
# Seeded from RATELIMITER_CLOCK_SKEW_MS so a spawned hostproc/edgeproc
# can boot skewed; mutable at runtime via set_clock_skew_ms (a control
# op or an in-process actor).  Storages built with an explicit
# ``clock_ms=`` are unaffected — their clock is the caller's problem.
_CLOCK_SKEW_MS: int = int(os.environ.get("RATELIMITER_CLOCK_SKEW_MS",
                                         "0") or "0")


def set_clock_skew_ms(skew_ms: int) -> int:
    """Set this process's injected clock offset (ms, may be negative);
    returns the previous value.  Takes effect on the next clock read —
    a forward step is a "jump", a standing offset is "skew"."""
    global _CLOCK_SKEW_MS
    prev = _CLOCK_SKEW_MS
    _CLOCK_SKEW_MS = int(skew_ms)
    return prev


def clock_skew_ms() -> int:
    return _CLOCK_SKEW_MS


def _wall_clock_ms() -> int:
    return time.time_ns() // 1_000_000 + _CLOCK_SKEW_MS


def _elect_digest_mode(u: int, cn: int, n_delta: int, digest_bpu: float,
                       words_bpr: float, srt_ok: bool) -> bool:
    """Words-vs-digest election for one chunk.  A chunk the sorted sweep
    serves is elected by device seconds alone (per unique against per
    lane); any other chunk by wire bytes, the multi-tenant lid deltas
    charged at 1/_DELTA_AMORT.  cdt presence is the caller's gate."""
    if srt_ok:
        return u * _DEVICE_S_PER_UNIQUE_SORTED <= cn * _DEVICE_S_PER_LANE
    return digest_bpu * u + 8 * n_delta / _DELTA_AMORT <= words_bpr * cn


def _grown_chunk(budget: float, wire_b: float, cn: int) -> int:
    """The growth schedule's next chunk size: ``budget`` bytes at the
    chunk just dispatched's bytes per request, within [_RELAY_CHUNK,
    _RELAY_CHUNK_MAX]."""
    return int(min(max(budget / max(wire_b / cn, 1e-3), _RELAY_CHUNK),
                   _RELAY_CHUNK_MAX))


def _sort_affordable() -> bool:
    """Whether to slot-sort a digest chunk's uniques:
    ``RATELIMITER_SORT_UNIQUES=never`` turns the sort off (read per call
    so tests take effect immediately); anything else sorts."""
    return os.environ.get("RATELIMITER_SORT_UNIQUES", "auto") != "never"


class _DrainSet:
    """In-flight drain tracker: every dispatched chunk's drain is
    submitted to the storage's drain pool IMMEDIATELY, so the ~RTT-sized
    fetch cycles of consecutive chunks overlap instead of serializing
    (measured on the pre-PR-1 remote link: 3 chunk cycles fetched serially
    688 ms, concurrently 295 ms — the fetch wait sleeps, it does not
    spin, so the C walk keeps the core).  ``finish()`` blocks until
    every drain has landed and re-raises the first drain error;
    ``finish(swallow=True)`` is for paths already propagating a primary
    exception (drain errors are then secondary)."""

    __slots__ = ("_pool", "_futs", "_inflight", "_on_block", "_wait_span")

    def __init__(self, pool, inflight: int = _DRAIN_INFLIGHT,
                 on_block=None, wait_span=contextlib.nullcontext):
        self._pool = pool
        self._futs: list = []
        self._inflight = inflight
        # Saturation hook (r8): called once each time submit must wait
        # out an old drain — the per-shard lanes feed it to the flight
        # recorder so a drain-bound shard is diagnosable.
        self._on_block = on_block
        # Factory of the span around every wait of the caller on drains
        # (backpressure and finish).
        self._wait_span = wait_span

    def submit(self, fn, *args) -> None:
        self._futs.append(self._pool.submit(fn, *args))
        # Backpressure: bound queued result buffers (and link credit)
        # by waiting out the oldest live drain past the cap.
        live = [f for f in self._futs if not f.done()]
        if len(live) > self._inflight:
            if self._on_block is not None:
                self._on_block()
            with self._wait_span():
                live[0].result()

    def finish(self, swallow: bool = False) -> None:
        if not self._futs:
            return
        err = None
        with self._wait_span():
            for f in self._futs:
                try:
                    f.result()
                except Exception as exc:  # noqa: BLE001 — re-raised below
                    if err is None:
                        err = exc
        self._futs.clear()
        if err is not None and not swallow:
            raise err


class _Span:
    """One stage of a stream pass, on the profiler's clock and in the
    stage's timer: a ``jax.profiler.TraceAnnotation`` named
    ``ratelimiter.stream.<stage>`` (written only while a profiler
    session is active; ``chunk``, and on a shard lane ``shard``, ride
    along as its metadata) around
    the interval the stage's timer records (``timer`` None: a span with
    no timer, or observability off).  ``end()`` closes it early, where
    the next stage starts inside the same block; ``t0``/``t1`` keep the
    interval for callers that need it."""

    __slots__ = ("_ann", "_timer", "t0", "t1")

    def __init__(self, name: str, timer, chunk: int | None,
                 shard: int | None = None):
        if chunk is None:
            self._ann = TraceAnnotation(name)
        elif shard is None:
            self._ann = TraceAnnotation(name, chunk=chunk)
        else:
            self._ann = TraceAnnotation(name, chunk=chunk, shard=shard)
        self._timer = timer
        self.t1 = None

    def __enter__(self) -> "_Span":
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def end(self) -> None:
        if self.t1 is None:
            self.t1 = time.perf_counter()
            self._ann.__exit__(None, None, None)
            if self._timer is not None:
                self._timer.record_us((self.t1 - self.t0) * 1e6)

    def __exit__(self, *exc) -> bool:
        self.end()
        return False

    @property
    def secs(self) -> float:
        return self.t1 - self.t0


class _StagingPool:
    """Reusable host staging buffers for dispatch uploads (r6).

    Streaming chunks used to allocate AND memset a fresh padded numpy
    buffer per dispatch (``np.full`` of up to 64 MB — real milliseconds
    of page faults + fill per chunk on the 1-core bench host).  The pool
    recycles them: ``take`` returns a C-contiguous array of the exact
    requested shape with UNSPECIFIED contents — the caller overwrites
    its valid region and re-fills only its own padding; ``give``
    returns a buffer once the dispatch that consumed it has been
    DRAINED (results fetched => the upload was consumed; handing it
    back earlier could race the async host->device transfer).  Shapes
    recur because every dispatch lane count is bucketed.  Bounded by
    retained bytes; a miss just allocates."""

    __slots__ = ("_free", "_lock", "_bytes", "_max_bytes")

    def __init__(self, max_bytes: int = 256 << 20):
        self._free: Dict[tuple, list] = {}
        self._lock = threading.Lock()
        self._bytes = 0
        self._max_bytes = int(max_bytes)

    def take(self, shape, dtype) -> np.ndarray:
        shape = (shape,) if isinstance(shape, int) else tuple(shape)
        key = (shape, np.dtype(dtype).str)
        with self._lock:
            lst = self._free.get(key)
            if lst:
                arr = lst.pop()
                self._bytes -= arr.nbytes
                return arr
        return np.empty(shape, dtype=dtype)

    def give(self, arr) -> None:
        if arr is None:
            return
        key = (arr.shape, arr.dtype.str)
        with self._lock:
            if self._bytes + arr.nbytes > self._max_bytes:
                return  # over budget: let the GC have it
            self._free.setdefault(key, []).append(arr)
            self._bytes += arr.nbytes


class _RouteSet:
    """One chunk's route buffers (:meth:`TpuBatchedStorage._route_host`),
    each at least as long as the chunk: its keys in shard order, their
    indices in the chunk, each key's shard, each range's first position
    in every shard, and the shards' offsets."""

    __slots__ = ("keys", "order", "part", "local", "offs")

    def __init__(self, cap: int, n_sh: int):
        self.keys = np.empty(cap, dtype=np.int64)
        self.order = np.empty(cap, dtype=np.int64)
        self.part = np.empty(cap, dtype=np.uint8)
        self.local = np.empty((_ROUTE_RANGES, n_sh), dtype=np.int64)
        self.offs = np.empty(n_sh + 1, dtype=np.int64)

    def __len__(self) -> int:
        return len(self.keys)


class _RoutePool:
    """The route buffer sets the sharded relay loop reuses across calls.

    Routing a 2^21-id chunk into fresh arrays faults in ~34 MB of new
    pages, so the route's cost would follow the process's page-fault and
    allocator state (PERF.md §7.6).  ``take`` hands out the smallest free set that
    holds the chunk, or allocates one sized to it; ``give`` takes a
    call's sets back once nothing reads them any more (every lane's job
    joined and every drain finished) and keeps the
    ``_ROUTE_SETS_KEPT`` largest.  Counters
    ``ratelimiter.stream.route_buffers.reused`` / ``.allocated`` (None
    without a registry) count the takes of each kind."""

    __slots__ = ("_n_sh", "_free", "_lock", "reused", "allocated")

    def __init__(self, n_sh: int, registry=None):
        self._n_sh = n_sh
        self._free: list = []
        self._lock = threading.Lock()
        self.reused = self.allocated = None
        if registry is not None:
            self.reused = registry.counter(
                "ratelimiter.stream.route_buffers.reused",
                "Sharded stream chunks routed into a kept buffer set")
            self.allocated = registry.counter(
                "ratelimiter.stream.route_buffers.allocated",
                "Sharded stream chunks routed into a new buffer set")

    def take(self, n: int) -> _RouteSet:
        with self._lock:
            fits = [b for b in self._free if len(b) >= n]
            best = min(fits, key=len) if fits else None
            if best is not None:
                self._free.remove(best)
        if best is not None:
            if self.reused is not None:
                self.reused.add(1)
            return best
        if self.allocated is not None:
            self.allocated.add(1)
        return _RouteSet(n, self._n_sh)

    def give(self, sets) -> None:
        with self._lock:
            self._free.extend(sets)
            self._free.sort(key=len, reverse=True)
            del self._free[_ROUTE_SETS_KEPT:]


class _ShardLane:
    """One shard's fully independent dispatch pipeline (r8).

    The pre-r8 sharded stream prepared ALL shards' host work on one
    worker and barriered them into a single mesh-wide dispatch per
    chunk — every shard waited for the slowest sibling's layout, the
    multi-device launch rendezvoused all devices, and the request lane
    padded to the BUSIEST shard's bucket.  A lane decomposes that: it
    owns

    - ``pipe``  — one FIFO worker running assign -> eviction-clear ->
      layout -> per-shard dispatch.  FIFO == per-shard stream order, so
      a shard's clears always enter its device stream ahead of the
      dispatch that reuses the slots, with NO cross-shard barrier (a
      key never migrates shards, so nothing else needs one);
    - ``staging`` — the shard's own staging-buffer pool (per-shard
      upload shapes recur per lane, and sibling lanes never contend on
      its lock);
    - ``drains`` — the shard's own bounded drain queue on its own
      fetch worker; past the in-flight bound, submit blocks THIS lane
      only and flags saturation to the flight recorder;
    - ``n_lanes`` / ``n_uniques`` — the shard's counters of requests
      and distinct keys walked (``ratelimiter.stream.shard_lanes.<s>``,
      ``ratelimiter.stream.shard_uniques.<s>``; None without a
      registry), the skew a hot key puts on its shard.

    Chunk N+1 of shard A assembles while chunk N of shard B is still in
    flight — the inversion fix for r05's (remote link, before PR 1) sharded_scaling curve.
    """

    __slots__ = ("shard", "pipe", "drain_pool", "staging", "drains",
                 "saturated", "n_lanes", "n_uniques")

    def __init__(self, shard: int, recorder=None, inflight: int | None = None,
                 registry=None):
        import concurrent.futures as cf

        if inflight is None:
            inflight = _SHARD_DRAIN_INFLIGHT

        self.shard = shard
        self.pipe = cf.ThreadPoolExecutor(
            1, thread_name_prefix=f"shard{shard}-pipe")
        self.drain_pool = cf.ThreadPoolExecutor(
            1, thread_name_prefix=f"shard{shard}-drain")
        self.staging = _StagingPool(max_bytes=64 << 20)
        self.saturated = 0
        self.n_lanes = self.n_uniques = None
        if registry is not None:
            self.n_lanes = registry.counter(
                f"ratelimiter.stream.shard_lanes.{shard}",
                f"Stream requests routed to shard {shard}")
            self.n_uniques = registry.counter(
                f"ratelimiter.stream.shard_uniques.{shard}",
                f"Distinct keys per chunk summed, shard {shard}")

        def on_block():
            self.saturated += 1
            if recorder is not None:
                recorder.record("shard.drain_saturated",
                                coalesce_ms=1000.0, shard=self.shard)

        self.drains = _DrainSet(self.drain_pool, inflight=inflight,
                                on_block=on_block)

    def close(self) -> None:
        self.pipe.shutdown(wait=False)
        self.drain_pool.shutdown(wait=False)


def _presorted_scatter_usable(eng, algo: str, padded: int) -> bool:
    """Whether a digest dispatch at this padded lane count can use the
    dense presorted block sweep (module-level so tests can force the
    sorted path onto the XLA fallback)."""
    from ratelimiter_tpu.ops.pallas import block_scatter

    shape = (eng.sw_packed if algo == "sw" else eng.tb_packed).shape
    return block_scatter.enabled(shape, padded)


def _route_chunk(key_ids: np.ndarray, n_shards: int):
    """(shard, stable order, per-shard counts) — C fast path with a
    bit-identical numpy fallback."""
    from ratelimiter_tpu.engine.native_index import shard_route

    r = shard_route(key_ids, n_shards)
    if r is not None:
        return r
    from ratelimiter_tpu.parallel.sharded import shard_of_int_keys

    shard = shard_of_int_keys(key_ids, n_shards)
    order = np.argsort(shard, kind="stable")
    return shard, order, np.bincount(shard, minlength=n_shards)


def _pad_tail(arr: np.ndarray, size: int, fill, dtype) -> np.ndarray:
    """Contiguous cast + right-pad with ``fill`` up to ``size``."""
    arr = np.ascontiguousarray(arr, dtype=dtype)
    if len(arr) < size:
        arr = np.concatenate(
            [arr, np.full(size - len(arr), fill, dtype=dtype)])
    return arr


class TpuBatchedStorage(RateLimitStorage):
    supports_device_batching = True

    def __init__(
        self,
        num_slots: int = 1 << 20,
        max_batch: int = 8192,
        max_delay_ms: float = 0.5,
        max_inflight: int = 4,
        max_pending: int = 0,
        queue_deadline_ms: float = 0.0,
        clock_ms: Callable[[], int] = _wall_clock_ms,
        engine: DeviceEngine | None = None,
        table: LimiterTable | None = None,
        checkpointable: bool = False,
        meter_registry=None,
        host_parallel: int | None = None,
        trace_sample: int = 0,
        obs_slo_ms: float = 0.0,
        observability: bool = True,
        recorder=None,
        adaptive_flush: bool = True,
        flush_floor_ms: float = 0.05,
        serving_cache: bool = False,
        serving_cache_ttl_ms: float = 50.0,
        serving_cache_max_keys: int = 65536,
        serving_cache_unconfirmed_cap: int = 64,
        serving_cache_guard_ms: float = 5.0,
        usage_max_tenants: int = 256,
        telemetry_max_clients: int = 1024,
        lineage_capacity: int = 256,
        table_capacity: int = 0,
    ):
        self._clock_ms = clock_ms
        # Observability (ARCHITECTURE §13).  The stage/latency histograms
        # are UNCONDITIONAL: a storage built without a registry gets a
        # private one (log2-bucket timers are O(1) lock-free records, so
        # always-on is affordable — gated <=2% of the headline stream by
        # bench/observability_overhead.py).  ``observability=False`` is
        # the explicit opt-out that the overhead bench measures against.
        self._obs = bool(observability)
        if meter_registry is None and self._obs:
            from ratelimiter_tpu.metrics import MeterRegistry

            meter_registry = MeterRegistry()
        self.registry = meter_registry
        if self._obs:
            from ratelimiter_tpu.observability import flight_recorder

            self._recorder = (recorder if recorder is not None
                              else flight_recorder())
            if obs_slo_ms and obs_slo_ms > 0:
                self._recorder.set_slo_ms(obs_slo_ms)
        else:
            self._recorder = None
        # The storage-latency histogram the reference documents but never
        # ships (ARCHITECTURE notes; SURVEY §5.5): per-dispatch wall time.
        self._latency = (
            meter_registry.timer(
                "ratelimiter.storage.latency",
                "Device dispatch latency (per micro-batch)")
            if self._obs else None
        )
        # Per-stage pipeline timers (r6, unconditional since the
        # observability PR): where a stream chunk's seconds go — route
        # (shard binning), pack (string hashing), index (slot walk),
        # sort (a digest chunk's serial slot sort), index_route,
        # index_merge and index_sort (the partitioned index's route and
        # merge passes and its slowest partition-local slot sort, inside
        # the walk), assign (the caller's wait for the walk and, on the
        # relay path, the sort), layout (host dispatch prep), enqueue
        # (device dispatch call), fetch (the blocking result read),
        # decide (host reconstruction after the fetch), drain_wait (the
        # caller blocked on drains); on a sharded engine also shard (one
        # shard lane's whole job for one chunk) and shard_straggle (per
        # chunk with two or more shards, the slowest lane's job minus
        # the median lane's).  Each is fed by the span of the same name
        # (_span), except sort, index_route, index_merge, index_sort and
        # shard_straggle, which have no span.
        self._stage_timers = None
        if self._obs:
            self._stage_timers = {
                s: meter_registry.timer(
                    f"ratelimiter.stream.{s}",
                    f"Stream pipeline {s} stage (us per chunk)")
                for s in ("route", "pack", "index", "sort", "index_route",
                          "index_merge", "index_sort", "assign", "layout",
                          "enqueue", "fetch", "decide", "drain_wait",
                          "shard", "shard_straggle")}
        # Reusable dispatch staging buffers shared by every stream loop.
        self._staging = _StagingPool()
        if engine is not None and table is None:
            table = engine.table
        # table_capacity pre-sizes the policy table (ratelimiter.table.
        # capacity): an implicit mid-traffic grow is decision-safe but
        # recompiles the step for the new table shape — see
        # LimiterTable._grow.
        self.table = table if table is not None else LimiterTable(
            capacity=table_capacity if table_capacity > 0 else 64)
        self.engine = engine if engine is not None else DeviceEngine(num_slots, self.table)
        if host_parallel is None:  # auto-elect (explicit kwarg wins; 0 off)
            host_parallel = self._auto_host_parallel(checkpointable)
        self._host_parallel = (int(host_parallel)
                               if host_parallel and host_parallel > 1 else 0)
        self._configs: Dict[int, Tuple[str, RateLimitConfig]] = {}
        # Policy-update listeners (control plane, ARCHITECTURE §15):
        # parties holding a policy-derived mirror — the degraded host
        # limiter's oracles, the lease manager's clamps — subscribe here
        # and are told (lid, algo, config, generation) AFTER the device
        # row moved.  The hybrid serving cache is handled inline (its
        # invalidation must precede the row write, like reset_key).
        self._policy_listeners: List[Callable] = []
        # Standby-promotion window flag: decisions are refused (typed,
        # retryable) while promote_from_replica swaps the indexes.
        self._promoting = False
        # Fencing (replication/orchestrator.py): a monotonically-bumped
        # epoch installed by failover before a replacement starts serving.
        # _fence_all refuses every decision; _fenced_shards scopes the
        # fence to the named shards of a sharded engine (survivor traffic
        # keeps flowing).  Both cost one falsy check on the hot path
        # until a fence is actually installed.
        self._fence_epoch = 0
        self._fence_all = False
        self._fenced_shards: frozenset = frozenset()
        self.fence_rejected = 0
        # Scoped fence epochs (ARCHITECTURE §14b): token-lease revocation
        # is keyed off lease_scope_epoch(lid, key), not the global fence
        # epoch, so a single-shard promotion revokes only the leases whose
        # keys route to the promoted shard.  _shard_fence_epochs is a
        # per-shard ratchet (never cleared by lift_fence — revoking a
        # lease is always safe; resurrecting one never is);
        # _full_fence_epoch moves only on whole-storage fences.
        self._shard_fence_epochs: Dict[int, int] = {}
        self._full_fence_epoch = 0
        # Distributed fence lease (cross-host failover, ARCHITECTURE
        # §10c): the orchestrator grants this storage the right to serve
        # at a fence epoch for a bounded TTL and renews it while probes
        # answer.  A storage whose lease EXPIRES — partitioned from its
        # orchestrator and from the standby-relayed renewal path — SELF-
        # FENCES: it stops deciding within one TTL of the last renewal,
        # which is what bounds a partitioned zombie's over-admission
        # without any quorum machinery.  _lease_deadline_ms == 0 means no
        # lease is installed; the hot-path cost is then one falsy check.
        self._lease_epoch = 0
        self._lease_deadline_ms = 0
        self.lease_self_fenced = False
        # The engine decides the index shape: flat LRU for single device,
        # per-shard LRU (key pinned to shard by hash) for a sharded engine.
        # The native index checkpoints at fingerprint level by default;
        # checkpointable=True swaps in enumerable KEYED Python indexes —
        # needed only for dumps that must re-hash keys in a different
        # geometry (cross-shard rebalance; engine/checkpoint.py).
        def make_index():
            # host_parallel=T partitions the host index over T native
            # sub-indexes with per-partition LRU (the trade the
            # device-sharded index already makes) so batch assignment
            # scales across cores instead of serializing on one DRAM
            # probe stream.  Single-device engines only; checkpointable
            # deployments keep the enumerable Python index.
            if host_parallel > 1:
                if checkpointable:
                    raise ValueError(
                        "host_parallel requires fingerprint checkpoints; "
                        "it cannot combine with checkpointable=True "
                        "(which needs the keyed Python index)")
                if hasattr(self.engine, "n_shards"):
                    raise ValueError(
                        "host_parallel applies to single-device engines; "
                        "the sharded engine already partitions the host "
                        "index per device shard")
                if self.engine.num_slots % host_parallel:
                    raise ValueError(
                        f"num_slots ({self.engine.num_slots}) must divide "
                        f"evenly by host_parallel ({host_parallel})")
                from ratelimiter_tpu.engine.native_index import (
                    native_available,
                )

                if native_available():
                    from ratelimiter_tpu.engine.partitioned import (
                        PartitionedSlotIndex,
                    )

                    return PartitionedSlotIndex(self.engine.num_slots,
                                                host_parallel)
                raise RuntimeError(
                    "host_parallel requires the native slot index "
                    "(C++ build unavailable)")
            index = self.engine.make_slot_index()
            if not checkpointable:
                return index
            if hasattr(index, "_sub"):
                if not all(hasattr(s, "_map") for s in index._sub):
                    # Native sub-indexes are fingerprint-only; checkpoints
                    # need the enumerable Python subs.
                    index = type(index)(index.slots_per_shard,
                                        index.n_shards, native=False)
                return index
            if not hasattr(index, "_map"):
                from ratelimiter_tpu.engine.slots import SlotIndex

                index = SlotIndex(self.engine.num_slots)
            return index

        self._index = {"sw": make_index(), "tb": make_index()}
        # Host mirror of which slots' lids the device lid map knows
        # (per algo, allocated on first digest-multi stream).
        self._lid_known: Dict[str, np.ndarray] = {}
        # Per-algo locks serializing _lid_known reads/marks + their
        # dispatch against _clear_slots (clear-wins: an eviction
        # concurrent with a mark must leave known=False so the lid is
        # re-uploaded).  Per algo so sw and tb clears never serialize
        # against each other.
        self._lid_locks = {"sw": threading.Lock(), "tb": threading.Lock()}
        self._host = InMemoryStorage(clock_ms=clock_ms)  # legacy-contract ops
        from ratelimiter_tpu.utils.tracing import DecisionTrace

        self.trace = DecisionTrace()
        # Fleet telemetry plane (observability/telemetry.py): fleet-true
        # ratelimiter.decisions.* counters + the per-tenant usage ring
        # (fed from micro drains, stream chunks, sheds, degraded-path
        # decisions, and client telemetry reports), and the trace-id
        # lineage ring sampled ids accumulate hops in.  Both are part of
        # the always-on observability layer (None with it off).
        self.telemetry = None
        self.lineage = None
        if self._obs:
            from ratelimiter_tpu.observability import (
                TelemetryPlane,
                TraceLineage,
            )

            self.telemetry = TelemetryPlane(
                meter_registry, clock_ms=clock_ms,
                max_clients=telemetry_max_clients)
            self.telemetry.usage.max_tenants = max(int(usage_max_tenants),
                                                   1)
            self.lineage = TraceLineage(capacity=lineage_capacity,
                                        sample_n=int(trace_sample))
        # Request-lifecycle tracer (observability/trace.py): the batcher
        # stamps enqueue/assembly/device/resolve and this aggregates them
        # into the ratelimiter.latency.* histograms, sampling 1-in-N full
        # traces into the enriched DecisionTrace ring.
        self._tracer = None
        if self._obs:
            from ratelimiter_tpu.observability import LatencyTracer

            self._tracer = LatencyTracer(
                meter_registry, trace=self.trace,
                sample_n=int(trace_sample), recorder=self._recorder,
                lineage=self.lineage)
        # Optional stream instrumentation (VERDICT r2 #1): when a caller
        # sets this to a list, the streaming loops append one record per
        # chunk — {mode, n, u, wire_bytes, assign_s, host_s, fetch_s} — so
        # a bench can show WHERE the seconds of a pass went (e.g. a
        # multi-second fetch_s on one chunk = a mid-timing compile).
        self.stream_stats: list | None = None
        # The sharded relay loop's learned chunk size per stream shape
        # (key kind, algo, multi-lid, banded n): a later call starts at
        # the size the last one grew to.
        self._sharded_chunks: Dict[tuple, int] = {}
        # Batch timestamps are clamped monotonically non-decreasing: a wall
        # clock stepping backwards (NTP) must not roll windows backwards —
        # the slot model keeps only (curr, prev) buckets, and a regressed
        # stamp would read as a window change and zero live counts.  (The
        # reference has the same hazard unmitigated: window keys + TTLs
        # both misbehave under clock regression.)
        self._last_stamp = 0
        self._stamp_lock = threading.Lock()
        # Clock-regression observability: the clamp silently absorbs a
        # backward wall-clock jump — count each absorbed regression so an
        # NTP step (or a broken injected clock) is visible in metrics
        # instead of only as mysteriously-frozen windows.
        self.backward_clamps = 0
        self._backward_clamp_counter = (
            meter_registry.counter(
                "ratelimiter.time.backward_clamp",
                "Wall-clock regressions absorbed by the monotonic batch-"
                "timestamp clamp")
            if meter_registry is not None else None)

        def _stamp() -> int:
            with self._stamp_lock:
                now = self._clock_ms()
                if now < self._last_stamp:
                    self.backward_clamps += 1
                    if self._backward_clamp_counter is not None:
                        self._backward_clamp_counter.increment()
                else:
                    self._last_stamp = now
                return self._last_stamp

        self._monotonic_now = _stamp

        # Hybrid host-side serving tier (cache/hybrid.py, Apt-Serve
        # shape): answers hot repeat-reject and safely-under-limit keys
        # host-side from exact adopted per-key state, device-confirmed
        # asynchronously.  OFF by default (ratelimiter.cache.hybrid.*
        # wires it); None costs one falsy check per acquire.
        self._serving = None
        if serving_cache:
            from ratelimiter_tpu.cache.hybrid import HybridServingCache

            self._serving = HybridServingCache(
                clock_ms=lambda: self._monotonic_now(),
                ttl_ms=serving_cache_ttl_ms,
                max_keys=serving_cache_max_keys,
                unconfirmed_cap=serving_cache_unconfirmed_cap,
                guard_ms=serving_cache_guard_ms,
                registry=meter_registry if self._obs else None,
            )

        # Dispatch/drain split (engine + batcher): the flusher only enqueues
        # device work; the drainer fetches — several batches in flight at
        # once, so fetch latency overlaps the next dispatches.
        def _dispatcher(fn):
            def run(s, l, p):
                stamp = _stamp()
                return (fn(s, l, p, stamp), time.perf_counter(), stamp,
                        np.asarray(l, dtype=np.int64))

            return run

        # Staged fast path (r11): the batcher hands over its pre-packed
        # combined staging buffer; dispatch is stamp + one upload + one
        # cached jit call, with the pack/layout sub-stages timed into
        # the ratelimiter.latency.assembly.* histograms.
        def _staged_dispatcher(algo):
            micro_ok = hasattr(self.engine, "micro_staged_dispatch")

            def run(buf, n):
                tracer = self._tracer
                t0 = time.perf_counter()
                stamp = _stamp()
                buf[3, 0] = stamp
                t1 = time.perf_counter()
                handle = self.engine.micro_staged_dispatch(algo, buf, n)
                if tracer is not None:
                    t2 = time.perf_counter()
                    tracer.record_sub("pack", (t1 - t0) * 1e6)
                    tracer.record_sub("layout", (t2 - t1) * 1e6)
                # Copy the lid lanes out for per-tenant accounting at
                # drain time: the staging buffer recycles once the drain
                # completes, so the drainer must not hold a view.
                return (handle, t1, stamp, buf[1, :n].copy())

            return run if micro_ok else None

        def _drainer(algo, fn, staged_fn=None):
            def run(handle_t0, n):
                handle, t0, stamp, lids = handle_t0
                out = fn(handle, n)
                dt_us = (time.perf_counter() - t0) * 1e6
                self._record_dispatch(algo, n, int(out["allowed"].sum()),
                                      dt_us)
                if self.telemetry is not None:
                    # Per-tenant fleet accounting: one bincount pass per
                    # batch, never per decision.
                    self.telemetry.note_batch(lids, out["allowed"],
                                              now_ms=stamp)
                if self._serving is not None:
                    # The hybrid serving tier needs the dispatch stamp to
                    # adopt exact per-key state (cache/hybrid.py).
                    out["stamp"] = np.full(n, stamp, dtype=np.int64)
                return out

            return run

        def _staged_drainer(algo):
            return _drainer(
                algo, lambda h, n: self.engine.micro_staged_drain(
                    algo, h, n))

        # The legacy list drains decode the same fused handle layout as
        # the staged path, so one drainer per algo serves both: the
        # flusher dispatches staged, dispatch_direct dispatches lists,
        # and either handle round-trips through (handle, t0, stamp).
        staged = {a: f for a, f in (("sw", _staged_dispatcher("sw")),
                                    ("tb", _staged_dispatcher("tb")))
                  if f is not None}
        # Adaptive flush control (engine/flush_control.py): ON by
        # default; the controller's applied deadline/size trigger stay
        # hard-clamped within [flush_floor_ms, max_delay_ms] /
        # [_MICRO_FLOOR-ish, max_batch].
        self._flush_controller = None
        if adaptive_flush:
            from ratelimiter_tpu.engine.flush_control import (
                AdaptiveFlushController,
            )

            self._flush_controller = AdaptiveFlushController(
                base_delay_ms=max_delay_ms,
                floor_ms=min(flush_floor_ms, max_delay_ms)
                if max_delay_ms > 0 else flush_floor_ms,
                cap_ms=max(max_delay_ms, flush_floor_ms),
                size_floor=32,
                size_cap=max_batch,
                meter_registry=meter_registry if self._obs else None,
            )
        self._batcher = MicroBatcher(
            dispatch={
                "sw": _dispatcher(self.engine.sw_acquire_dispatch),
                "tb": _dispatcher(self.engine.tb_acquire_dispatch),
            },
            drain={
                "sw": (_staged_drainer("sw") if "sw" in staged
                       else _drainer("sw", self.engine.sw_acquire_drain)),
                "tb": (_staged_drainer("tb") if "tb" in staged
                       else _drainer("tb", self.engine.tb_acquire_drain)),
            },
            dispatch_staged=staged or None,
            clear={
                "sw": lambda slots: self._clear_slots("sw", slots),
                "tb": lambda slots: self._clear_slots("tb", slots),
            },
            max_batch=max_batch,
            max_delay_ms=max_delay_ms,
            max_inflight=max_inflight,
            max_pending=max_pending,
            deadline_ms=queue_deadline_ms,
            controller=self._flush_controller,
            meter_registry=meter_registry,
            tracer=self._tracer,
            recorder=self._recorder,
        )

    def _auto_host_parallel(self, checkpointable: bool) -> int:
        """Elected partition count for the host slot index (see the
        _HOST_PARALLEL_AUTO_* notes): min(cores, 8), walked down to the
        largest count dividing num_slots; 0 (single index) when the
        engine is sharded, the table is small, the native library is
        missing, the host has <= 2 cores, or checkpoints need the
        enumerable Python index."""
        if checkpointable or hasattr(self.engine, "n_shards"):
            return 0
        if self.engine.num_slots < _HOST_PARALLEL_AUTO_MIN_SLOTS:
            return 0
        from ratelimiter_tpu.engine.native_index import native_available

        if not native_available():
            return 0
        try:
            cores = len(os.sched_getaffinity(0))
        except (AttributeError, OSError):  # pragma: no cover - non-linux
            cores = os.cpu_count() or 1
        if cores <= 2:
            return 0
        t = min(cores, _HOST_PARALLEL_AUTO_MAX)
        while t > 1 and self.engine.num_slots % t:
            t -= 1
        return t if t > 1 else 0

    # ------------------------------------------------------------------------
    # Batched decision protocol (the hot path)
    # ------------------------------------------------------------------------
    def register_limiter(self, algo: str, config: RateLimitConfig) -> int:
        """Register a limiter policy; returns its limiter id (device table row)."""
        if algo not in ("sw", "tb"):
            raise ValueError(f"unknown algorithm kind: {algo!r}")
        config.validate()
        lid = self.table.register(config)
        self._configs[lid] = (algo, config)
        if self._serving is not None:
            self._serving.register(lid, algo, config)
        return lid

    # ------------------------------------------------------------------------
    # Live policy updates (control/, ARCHITECTURE §15)
    # ------------------------------------------------------------------------
    def set_policy(self, lid: int, config: RateLimitConfig,
                   generation: int | None = None) -> int:
        """Live-update one limiter's policy; returns the policy
        generation the update installed.

        Semantics: every decision stamped BEFORE this call returns was
        evaluated under the old row, every decision after under the new
        one — pending micro-batch traffic is flushed first so the
        generation boundary is exact (a queued request never silently
        jumps generations between submit and dispatch).  The device row
        moves via three scalar updates (LimiterTable.set_policy —
        window/algo shape immutable), so no recompile and no table
        re-upload.  The hybrid serving tier forgets the lid's adopted
        state BEFORE the row moves (a host serve racing the update must
        not answer from the old policy), and registered policy
        listeners (degraded limiter, lease manager) are notified after.

        ``generation`` is for replication only: a standby replaying the
        primary's updates installs the primary's stamps.
        """
        entry = self._configs.get(int(lid))
        if entry is None:
            raise KeyError(f"no limiter registered under lid={lid}")
        algo, _old = entry
        config.validate()
        if self._serving is not None:
            self._serving.update_policy(int(lid), algo, config)
        self._batcher.flush()
        gen = self.table.set_policy(int(lid), config,
                                    generation=generation)
        self._configs[int(lid)] = (algo, config)
        for listener in self._policy_listeners:
            try:
                listener(int(lid), algo, config, gen)
            except Exception:  # noqa: BLE001 — a broken mirror must not
                # poison the actuation path; the listener logs itself.
                log.exception("policy listener failed for lid=%d", lid)
        return gen

    def add_policy_listener(self, listener) -> None:
        """Subscribe ``listener(lid, algo, config, generation)`` to live
        policy updates (called after the device row moved)."""
        self._policy_listeners.append(listener)

    def policy_info(self) -> Dict:
        """Policy-generation metadata (the fence_info analog): the
        table-wide monotonic generation plus each lid's row stamp."""
        return {
            "generation": self.table.generation,
            "lids": {int(lid): {
                "algo": algo,
                "generation": self.table.row_generation(lid),
                "max_permits": cfg.max_permits,
                "window_ms": cfg.window_ms,
                "refill_rate": cfg.refill_rate,
            } for lid, (algo, cfg) in self._configs.items()},
        }

    def acquire(self, algo: str, lid: int, key: str, permits: int,
                deadline_ms: float | None = None,
                trace_id: int = 0) -> dict:
        """Single decision through the micro-batcher (blocks until the batch
        containing this request lands; bounded by max_delay_ms).

        ``deadline_ms`` overrides the storage-wide queue-deadline budget
        for this request (admission control; engine/batcher.py)."""
        return self.acquire_async(algo, lid, key, permits,
                                  deadline_ms=deadline_ms,
                                  trace_id=trace_id).result()

    def acquire_async(self, algo: str, lid: int, key: str, permits: int,
                      deadline_ms: float | None = None,
                      trace_id: int = 0):
        """Future-returning :meth:`acquire` — the pipelining ingress
        primitive (service/sidecar.py): a connection handler submits
        every frame of a pipelined batch before resolving any, so all
        of them coalesce into the same micro-batch flush instead of
        paying one batcher round trip each.

        ``trace_id``: a 64-bit trace id carried end to end (0 = mint
        one here when lineage sampling is armed) — sampled ids record
        batcher/shard/resolve hops (observability/telemetry.py).

        With the hybrid serving tier enabled, a tracked key's decision
        may resolve host-side immediately (see cache/hybrid.py): a pure
        reject touches no device at all; a mutating decision rides the
        next micro-batch asynchronously as its device confirmation."""
        lin = self.lineage
        if not trace_id and lin is not None and lin.sample_n > 0:
            from ratelimiter_tpu.observability.telemetry import (
                mint_trace_id,
            )

            trace_id = mint_trace_id()
        serving = self._serving
        if serving is not None:
            fut = self._serve_host_side(algo, lid, key, permits)
            if fut is not None:
                return fut
        t0 = time.perf_counter() if self._tracer is not None else 0.0
        slot = self._assign_slot(algo, lid, key, hold_pin=True)
        if self._tracer is not None:
            self._tracer.record_sub(
                "index", (time.perf_counter() - t0) * 1e6)
        # The pin (taken atomically inside the assign) holds until the
        # submit registers the slot in pending_slots.
        try:
            with self._pins_released(self._index[algo], [slot]):
                fut = self._batcher.submit(algo, slot, lid, permits,
                                           deadline_ms=deadline_ms,
                                           trace_id=trace_id)
        except OverloadedError:
            if self.telemetry is not None:
                self.telemetry.note_shed(lid, 1)
            raise
        if serving is not None:
            serving.watch_miss(algo, lid, key, permits, slot, fut)
        return fut

    def _serve_host_side(self, algo: str, lid: int, key: str, permits: int):
        """Hybrid-tier serve attempt: a resolved Future, or None (miss).

        The fence/promotion checks run BEFORE the tier is consulted — a
        host-served decision must refuse exactly where a device dispatch
        would.  A host-served mutating decision is forwarded through the
        normal batcher path under the tier's lock (so device order ==
        serve order per key) and confirmed by its drain callback."""
        self._check_not_promoting()
        if self._fenced_shards:
            self._check_fence_keys([lid], [key])
        serving = self._serving
        with serving.lock:
            served = serving.serve(algo, lid, key, permits)
            if served is None:
                return None
            out, predicted = served
            if predicted is not None:  # mutated host-side: confirm async
                slot = self._assign_slot(algo, lid, key, hold_pin=True)
                with self._pins_released(self._index[algo], [slot]):
                    cfut = self._batcher.submit(algo, slot, lid, permits)
                serving.watch_confirm(algo, lid, key, predicted, slot,
                                      cfut)
        fut: Future = Future()
        fut.set_result(out)
        return fut

    def acquire_async_many(self, algo: str, lid: int,
                           keys: Sequence[str], permits=None,
                           deadline_ms: float | None = None):
        """Bulk :meth:`acquire_async` for a pipelined burst sharing one
        limiter: the keys hash in one windowed C pass off the interned
        UTF-8 buffers and map in one batched slot walk
        (native/str_pack.cpp:rl_strlist_hash_fp ->
        rl_index_assign_fps/engine/native_index.py:assign_batch_strs),
        then submit in one vectorized staging-buffer write — zero
        per-request Python on the index/layout half of assembly.
        Returns one Future per key; decisions ride the next micro-batch
        flush together.  Falls back to per-key submits without the
        native index.  The hybrid tier is bypassed (burst callers want
        coalescing, not per-key host serves)."""
        self._check_not_promoting()
        if self._fenced_shards:
            self._check_fence_keys([lid] * len(keys), keys)
        n = len(keys)
        if permits is None:
            permits = np.ones(n, dtype=np.int64)
        index = self._index[algo]
        if not hasattr(index, "assign_batch_strs"):
            return [self.acquire_async(algo, lid, k, int(p),
                                       deadline_ms=deadline_ms)
                    for k, p in zip(keys, permits)]
        t0 = time.perf_counter() if self._tracer is not None else 0.0
        with self._evictions_cleared(algo):
            slots, clears = index.assign_batch_strs(
                list(keys), lid,
                pinned=self._batcher.pending_slots(algo),
                hold_pins=True)
        if self._tracer is not None:
            self._tracer.record_sub(
                "index", (time.perf_counter() - t0) * 1e6)
        for evicted in clears:
            self._batcher.add_clear(algo, int(evicted))
        try:
            with self._pins_released(index, slots):
                return self._batcher.submit_many(
                    algo, slots, np.full(n, lid, dtype=np.int64), permits,
                    deadline_ms=deadline_ms)
        except OverloadedError:
            if self.telemetry is not None:
                self.telemetry.note_shed(lid, n)
            raise

    def acquire_async_block(self, algo: str, lid: int, data, offsets,
                            permits=None,
                            deadline_ms: float | None = None,
                            trace_id: int = 0):
        """Columnar :meth:`acquire_async_many`: the caller hands the v5
        batch frame's key column verbatim (data uint8[klen] packed UTF-8
        + offsets i64[n+1]) and gets ONE future resolving to
        ``{"allowed": bool[n], ...}`` lane slices — zero per-request
        Python objects end to end (native assign_batch_bytes ->
        batcher.submit_block).  Returns None when this storage can't take
        the columnar shortcut (Python index, or shard fences that need
        the key strings) — the caller falls back to the decoded-string
        path with identical decisions."""
        self._check_not_promoting()
        if self._fenced_shards:
            return None  # fence checks need the decoded keys
        index = self._index[algo]
        if not hasattr(index, "assign_batch_bytes"):
            return None
        n = len(offsets) - 1
        if permits is None:
            permits = np.ones(n, dtype=np.int64)
        t0 = time.perf_counter() if self._tracer is not None else 0.0
        with self._evictions_cleared(algo):
            slots, clears = index.assign_batch_bytes(
                data, offsets, lid,
                pinned=self._batcher.pending_slots(algo),
                hold_pins=True)
        if self._tracer is not None:
            self._tracer.record_sub(
                "index", (time.perf_counter() - t0) * 1e6)
        for evicted in clears:
            self._batcher.add_clear(algo, int(evicted))
        try:
            with self._pins_released(index, slots):
                return self._batcher.submit_block(
                    algo, slots, np.full(n, lid, dtype=np.int64), permits,
                    deadline_ms=deadline_ms, trace_id=trace_id)
        except OverloadedError:
            if self.telemetry is not None:
                self.telemetry.note_shed(lid, n)
            raise

    def acquire_many(
        self, algo: str, lid_per_req: Sequence[int], keys: Sequence[str],
        permits: Sequence[int],
    ) -> Dict[str, np.ndarray]:
        """Whole-batch synchronous decision (the vectorized/bench path)."""
        self._check_not_promoting()
        if self._fenced_shards:
            self._check_fence_keys(lid_per_req, keys)
        index = self._index[algo]
        lid0 = lid_per_req[0] if lid_per_req else 0
        uniform_lid = all(l == lid0 for l in lid_per_req)
        if uniform_lid and hasattr(index, "assign_batch_strs"):
            # Native fast path: flush queued traffic first, then one C call
            # maps the whole batch; same-batch keys are generation-pinned and
            # slots of requests queued since the flush are pin-protected.
            self._batcher.flush()
            with self._evictions_cleared(algo):
                slots, clears = index.assign_batch_strs(
                    list(keys), lid0,
                    pinned=self._batcher.pending_slots(algo),
                    hold_pins=True)
            with self._pins_released(index, slots):
                return self._batcher.dispatch_direct(
                    algo, slots, list(lid_per_req), list(permits),
                    list(clears))
        pinned = self._batcher.pending_slots(algo)
        slots: List[int] = []
        clears: List[int] = []
        # try/finally from the FIRST assign: a mid-loop raise ("all slots
        # pinned") must release the pins earlier iterations took — and
        # clear the evictions they applied (the index already remapped
        # those slots; see _evictions_cleared).
        try:
            try:
                for lid, key in zip(lid_per_req, keys):
                    slot, evicted = index.assign((lid, key), pinned=pinned,
                                                 hold_pin=True)
                    if evicted is not None:
                        clears.append(evicted)
                    pinned.add(slot)
                    slots.append(slot)
            except Exception:
                if clears:
                    self._clear_slots(algo, clears)
                raise
            return self._batcher.dispatch_direct(
                algo, slots, list(lid_per_req), list(permits), clears)
        finally:
            self._unpin_held(
                index, [np.asarray(slots, dtype=np.int32)] if slots else [])

    def acquire_many_ids(
        self, algo: str, lid: int, key_ids: np.ndarray, permits: np.ndarray,
    ) -> Dict[str, np.ndarray]:
        """Int-key whole-batch decision — the hyperscale hot path.

        Integer user/tenant ids skip string hashing entirely: one C call for
        slot assignment, one device dispatch for the decisions.
        """
        self._check_not_promoting()
        if self._fenced_shards:
            self._check_fence_int_keys(key_ids)
        index = self._index[algo]
        if hasattr(index, "assign_batch_ints"):
            self._batcher.flush()
            with self._evictions_cleared(algo):
                slots, clears = index.assign_batch_ints(
                    np.ascontiguousarray(key_ids, dtype=np.int64), lid,
                    pinned=self._batcher.pending_slots(algo),
                    hold_pins=True)
            clears = list(clears)
        else:
            pinned = self._batcher.pending_slots(algo)
            slots = []
            clears = []
            # try/finally from the FIRST assign (see acquire_many): a
            # mid-loop raise must release earlier iterations' pins and
            # clear their applied evictions.
            try:
                try:
                    for k in np.asarray(key_ids):
                        slot, evicted = index.assign((lid, int(k)),
                                                     pinned=pinned,
                                                     hold_pin=True)
                        if evicted is not None:
                            clears.append(evicted)
                        pinned.add(slot)
                        slots.append(slot)
                except Exception:
                    if clears:
                        self._clear_slots(algo, clears)
                    raise
                slots = np.asarray(slots, dtype=np.int32)
                lids = np.full(len(slots), lid, dtype=np.int32)
                return self._batcher.dispatch_direct(algo, slots, lids,
                                                     permits, clears)
            finally:
                self._unpin_held(
                    index,
                    [np.asarray(slots, dtype=np.int32)] if len(slots)
                    else [])
        lids = np.full(len(slots), lid, dtype=np.int32)
        with self._pins_released(index, slots):
            return self._batcher.dispatch_direct(algo, slots, lids, permits,
                                                 clears)

    def acquire_stream_ids(
        self,
        algo: str,
        lid,
        key_ids: np.ndarray,
        permits: np.ndarray | None = None,
        *,
        batch: int = 1 << 14,
        subbatches: int = 4,
    ) -> np.ndarray:
        """Whole-stream int-key decisions, pipelined — the hyperscale path.

        The stream is cut into super-batches of ``subbatches * batch``
        requests.  For each: one C call assigns slots, one device dispatch
        runs ``subbatches`` sequential decision steps (lax.scan), and only
        the bit-packed allow/deny mask comes back — while it is in flight
        the next super-batch is being indexed and dispatched, so transfer
        latency overlaps device compute.  Decisions are identical to
        ``acquire_many_ids`` called per sub-batch (tests/test_packed.py);
        permits above 2^31-1 — above any limiter's max_permits, which is
        bounded to int32 — are denied without touching state, exactly as
        the i64 batch path rejects them.

        ``lid`` is either one limiter id for the whole stream (the device
        reads that policy row once — zero table gathers) or an int array of
        per-request limiter ids (multi-tenant stream).  Both modes index a
        bucket under the same (lid, key) namespace as ``acquire_many_ids``
        and ``acquire``, so paths can be mixed freely.  ``permits=None``
        means one permit per request (the permits upload is skipped; the
        device materializes ones).  Returns bool[n] allowed.

        The call is the ``ratelimiter.stream.call`` span; its stages are
        its children (ARCHITECTURE §13a).
        """
        with self._span("call"):
            return self._stream_ids(algo, lid, key_ids, permits, batch,
                                    subbatches)

    def _stream_ids(self, algo, lid, key_ids, permits, batch,
                    subbatches) -> np.ndarray:
        self._check_not_promoting()
        if self._fenced_shards:
            self._check_fence_int_keys(key_ids)
        multi_lid = np.ndim(lid) != 0
        if multi_lid:
            lid_arr = np.ascontiguousarray(lid, dtype=np.int64)
            if lid_arr.size and ((lid_arr < 0) | (lid_arr >= len(self.table))).any():
                raise ValueError("limiter ids out of range")
        # The stream paths carry permits as i32 lanes; a value past 2^31-1
        # would wrap negative and read as an ALLOW (a negative "request"
        # credits tokens) where the i64 batch path rejects it.  max_permits
        # always fits int32 (Java-int parity bound in core/config.py), so
        # any such request is above every limiter's cap: force-deny it by
        # dispatching its lane as padding (slot -1) — decision identical to
        # the batch path's reject, state untouched.
        oversize = None
        if permits is not None:
            permits = np.asarray(permits)
            if permits.size and int(permits.min(initial=0)) < np.iinfo(
                    np.int32).min:
                raise ValueError("permits below int32 range")
            over = permits > np.iinfo(np.int32).max
            if over.any():
                oversize = over

        index = self._index[algo]
        if hasattr(index, "_sub") and getattr(index, "supports_batch_ints", False):
            # Sharded engine: route keys to shards host-side, one shard_map'd
            # scan dispatch per super-batch, zero cross-shard device traffic.
            self._batcher.flush()
            return self._stream_sharded(
                algo, lid, np.ascontiguousarray(key_ids, dtype=np.int64),
                permits, batch, subbatches, index, multi_lid,
                lid_arr if multi_lid else None, oversize)
        if not hasattr(index, "assign_batch_ints"):
            # Python-index fallback: plain per-batch path, same decisions.
            n = len(key_ids)
            out = np.empty(n, dtype=bool)
            p = np.ones(n, dtype=np.int64) if permits is None \
                else np.asarray(permits)
            for i in range(0, n, batch):
                chunk = key_ids[i:i + batch]
                if multi_lid:
                    chunk_lids = lid_arr[i:i + batch]
                    pinned = self._batcher.pending_slots(algo)
                    slots, clears = [], []
                    try:
                        for l, k in zip(chunk_lids, chunk):
                            s, ev = index.assign((int(l), int(k)),
                                                 pinned=pinned)
                            if ev is not None:
                                clears.append(ev)
                            pinned.add(s)
                            slots.append(s)
                    except Exception:  # mid-loop raise: clear applied evs
                        if clears:
                            self._clear_slots(algo, clears)
                        raise
                    res = self._batcher.dispatch_direct(
                        algo, slots, list(chunk_lids), list(p[i:i + batch]),
                        clears)
                    out[i:i + batch] = res["allowed"]
                else:
                    out[i:i + batch] = self.acquire_many_ids(
                        algo, lid, chunk, p[i:i + batch])["allowed"]
            return out

        self._batcher.flush()
        key_ids = np.ascontiguousarray(key_ids, dtype=np.int64)
        if oversize is not None:
            permits = np.where(oversize, 1, permits)  # lanes masked, see above

        if (permits is not None and not multi_lid and oversize is None
                and hasattr(index, "assign_batch_ints_uniques")
                and permits.size
                and int(permits.min()) >= 1
                and int(permits.max()) <= self.engine.weighted_permit_cap):
            # Weighted-permit relay (ops/relay.py:*_relay_weighted): the
            # index's duplicate structure splits segments into closed-form
            # singles and a short rank-major scan — no device sort, no
            # solver, chunks grow to the wire budget.  Requests with
            # permits < 1 or above the word capacity keep the flat path's
            # semantics and routing.
            rb = self.engine.rank_bits

            def assign_uniques_w(start, chunk_n):
                with self._evictions_cleared(algo):
                    return index.assign_batch_ints_uniques(
                        key_ids[start:start + chunk_n], lid, rb,
                        pinned=self._batcher.pending_slots(algo),
                        hold_pins=True)

            return self._stream_weighted(
                algo, lid, assign_uniques_w, len(key_ids),
                np.ascontiguousarray(permits, dtype=np.int64), index)

        if (permits is None
                and hasattr(index, "assign_batch_ints_uniques")
                and self.engine.relay_usable()):
            # Unit-permit relay path (ops/relay.py): the index hands the
            # device the duplicate structure it computed while assigning
            # slots, deleting the device-side sort/scan entirely.
            rb = self.engine.rank_bits

            def assign_uniques(start, chunk_n, **kw):
                chunk = key_ids[start:start + chunk_n]
                with self._evictions_cleared(algo):
                    if multi_lid:
                        return index.assign_batch_ints_multi_uniques(
                            chunk, lid_arr[start:start + chunk_n], rb,
                            pinned=self._batcher.pending_slots(algo),
                            hold_pins=True, **kw)
                    return index.assign_batch_ints_uniques(
                        chunk, lid, rb,
                        pinned=self._batcher.pending_slots(algo),
                        hold_pins=True, **kw)

            return self._stream_relay(algo, lid, assign_uniques, len(key_ids),
                                      lid_arr if multi_lid else None)

        def assign(start, chunk_n):
            chunk = key_ids[start:start + chunk_n]
            with self._evictions_cleared(algo):
                if multi_lid:
                    return index.assign_batch_ints_multi(
                        chunk, lid_arr[start:start + chunk_n],
                        pinned=self._batcher.pending_slots(algo),
                        hold_pins=True)
                return index.assign_batch_ints(
                    chunk, lid, pinned=self._batcher.pending_slots(algo),
                    hold_pins=True)

        return self._stream_flat(algo, lid, assign, len(key_ids), permits,
                                 oversize, batch, subbatches,
                                 lid_arr if multi_lid else None)

    def _stream_relay(self, algo, lid, assign_uniques, n,
                      lid_arr=None, key_kind="ints") -> np.ndarray:
        """Relay streaming loop (unit permits): per chunk, one C call
        assigns slots AND produces the duplicate structure — per-unique
        (slot | segment count) words plus host-side (unique-index, rank)
        per request (native/slot_index.cpp:assign_batch_uniques).  The
        dispatch is chosen per chunk by measured traffic:

        - **segment digest** (skewed traffic): upload one uint32 per
          UNIQUE slot, device returns one allowed-count per unique, host
          reconstructs per-request booleans as ``rank < n_allowed[uidx]``
          (one numpy gather).  Bytes shrink by the duplicate factor —
          4-10x on the Zipf/multi-tenant scenarios — and the device
          gathers/scatters only unique rows.
        - **per-request words** (uniform traffic, duplicate-poor): the
          (slot|rank|last) words are reconstructed in numpy from the same
          digest output and dispatched through the bit-mask relay step.

        Both decide identically to the sorted flat path on the same
        chunking (tests/test_relay.py).  Chunks are ``_RELAY_CHUNK``
        requests (growing to the wire budget) and pipeline three-deep so
        fetches ride in the shadow of later chunks' host work + upload."""
        from ratelimiter_tpu.engine.native_index import rebuild_words_into
        from ratelimiter_tpu.ops.relay import rebuild_words, wire_costs

        multi_lid = lid_arr is not None
        eng = self.engine
        rb = eng.rank_bits
        cdt = eng.counts_dtype()
        digest_bpu, words_bpr = wire_costs(multi_lid,
                                           resident_lids=True)
        bits_dispatch = (eng.sw_relay_dispatch if algo == "sw"
                         else eng.tb_relay_dispatch)
        counts_dispatch = (eng.sw_relay_counts_dispatch if algo == "sw"
                           else eng.tb_relay_counts_dispatch)
        def clear(slots):
            self._clear_slots(algo, slots)
        out = np.empty(n, dtype=bool)
        drains = _DrainSet(self._drain_pool(),
                           wait_span=lambda: self._span("drain_wait"))

        # Cumulative walk seconds of the call, wherever each walk ran
        # (the stream_stats records carry it).
        walk_s = [0.0]
        rec_lock = threading.Lock()  # drains write rec and the recorder
        # A partitioned index sorts each partition's uniques inside its
        # own walk job when asked; a single index leaves the sort to
        # walk() below, on one thread.
        index = self._index[algo]
        parted = hasattr(index, "last_phase_s")

        def timed_assign(s0, cnt, chunk, **kw):
            """The walk; also whether the index slot-sorted its uniques."""
            with self._span("index", chunk) as timed:
                r = assign_uniques(s0, cnt, **kw)
            presorted = self._record_index_phases(index)
            walk_s[0] += timed.secs
            return r, presorted

        def sortable(u):
            """Whether a digest chunk of ``u`` uniques is slot-sorted:
            sorting pays off when EITHER sorted device path engages —
            the tile sweep, or (scalar-lid dispatches only) the fused
            Pallas relay step the engine elects per device
            (ops/pallas/relay_step.py).  Given a chunk's request count,
            the upper bound on its uniques, it says whether the chunk
            could go to the sorted step."""
            fused_ok = (not multi_lid
                        and hasattr(eng, "_relay_fused_ok")
                        and eng._relay_fused_ok(algo, _bucket_pow2(u)))
            return (u >= _SORT_UNIQUES_MIN
                    and _sort_affordable()
                    and (fused_ok or _presorted_scatter_usable(
                        eng, algo, _bucket_pow2(u))))

        def sort(uwords, uidx):
            """Slot-sort a chunk's uniques in place (uidx remapped).  It
            feeds the ``sort`` timer and has no span of its own: the
            caller's time in it counts to the span around it."""
            from ratelimiter_tpu.engine.native_index import sort_uniques

            t0 = time.perf_counter()
            done = sort_uniques(uwords, rb, uidx)
            if self._stage_timers is not None:
                self._stage_timers["sort"].record_us(
                    (time.perf_counter() - t0) * 1e6)
            return done

        def walk(s0, cnt, chunk):
            """The chunk's walk and its slot sort, both on whichever
            thread walks, so a prefetched chunk sorts behind the device.
            A partitioned index sorts where the chunk could go to the
            sorted digest step (each partition in its own job); a single
            index sorts after its walk where the chunk is sure to.  The
            last field says whether the uniques are sorted."""
            if parted:
                walked, srt = timed_assign(
                    s0, cnt, chunk,
                    sort_slots=cdt is not None and sortable(cnt))
                return (*walked, srt)
            (uwords, uidx, rank, clears), _ = timed_assign(s0, cnt, chunk)
            u = len(uwords)
            srt = (cdt is not None and sortable(u)
                   and _elect_digest_mode(u, cnt, 0, digest_bpu, words_bpr,
                                          True)
                   and sort(uwords, uidx))
            return uwords, uidx, rank, clears, srt

        def drain(mode, handle, start, count, extra, t0, rec, bufs, chunk):
            try:
                with self._span("fetch", chunk) as fetch:
                    arr = np.asarray(handle)  # the one blocking fetch
                with self._span("decide", chunk):
                    if mode == "bits":
                        got = np.unpackbits(arr)[:count].astype(bool)
                    else:  # digest: reconstruct from per-unique counts
                        from ratelimiter_tpu.engine.native_index import (
                            relay_decide,
                        )

                        uidx, rank, u = extra
                        got = relay_decide(arr[:u], uidx, rank)
                    out[start:start + count] = got
                n_allowed = int(got.sum())
                with rec_lock:
                    if rec is not None:
                        rec["fetch_s"] = round(fetch.secs, 6)
                    self._record_dispatch(algo, count, n_allowed,
                                          (fetch.t1 - t0) * 1e6,
                                          path=f"relay|{mode}",
                                          lid=None if multi_lid else lid)
            finally:
                # Staging buffers are reusable only after the fetch: the
                # upload that read them is certainly consumed by then.
                for b in bufs:
                    self._staging.give(b)

        # The growth schedule: a _RELAY_CHUNK first chunk, then
        # _grown_chunk after each dispatch.
        chunk_size = _RELAY_CHUNK
        start = 0
        ci = 0  # the chunk's index in this call: every span's metadata
        fut = None  # prefetched next-chunk assignment (holds pins)
        try:
            while start < n:
                cn = min(chunk_size, n - start)
                with self._span("assign", ci) as waited:
                    if fut is not None:
                        uwords, uidx, rank, clears, presorted = fut.result()
                        fut = None
                    else:
                        uwords, uidx, rank, clears, presorted = walk(
                            start, cn, ci)
                t_assign = waited.secs
                u = len(uwords)
                pack_s = (getattr(self._index[algo], "str_pack_s", None)
                          if key_kind == "strs" else None)
                if pack_s is not None and self._stage_timers is not None:
                    # Timed inside the index's assign (on whichever
                    # thread walked), so it has no span here.
                    self._stage_timers["pack"].record_us(pack_s * 1e6)
                rec = self._stream_rec("relay", n=int(cn), u=int(u),
                                       assign_s=t_assign)
                if rec is not None:
                    if self._host_parallel:
                        # The walk-term split: assign_s is the EXPOSED
                        # main-thread time while the C walk itself fans
                        # out over this many partitions (walk_s stays
                        # the true cumulative walk seconds).
                        rec["host_parallel"] = self._host_parallel
                    if pack_s is not None:
                        rec["pack_s"] = round(pack_s, 6)
                with self._span("clear", ci):  # the slots pinned below
                    uslots_all = (uwords >> np.uint32(rb + 1)).astype(
                        np.int32)
                with self._pins_released(self._index[algo], uslots_all):
                    if len(clears):
                        with self._span("clear", ci):
                            clear(list(clears))
                    with self._span("elect", ci):
                        l_chunk = (lid_arr[start:start + cn] if multi_lid
                                   else None)
                        # Mode election: steady-state digest cost per unique plus
                        # this chunk's (slot, lid) delta uploads charged at
                        # 1/_DELTA_AMORT (they are an investment — once resident,
                        # every later chunk reads the lid from the device map).
                        fresh = None
                        n_delta = 0
                        if cdt is not None and multi_lid:
                            with self._lid_locks[algo]:
                                known = self._lid_known.setdefault(
                                    algo, np.zeros(eng.num_slots, dtype=bool))
                                uslots = uslots_all.astype(np.int64)
                                fresh = ~known[uslots]
                            from ratelimiter_tpu.parallel.sharded import _bucket as _bkt
                            n_delta = _bkt(max(int(fresh.sum()), 1), floor=8)
                        # One sorted-eligibility verdict drives BOTH the
                        # mode election's device rate and the dispatch path
                        # below — they must never disagree.
                        srt_ok = sortable(u)
                        digest = cdt is not None and _elect_digest_mode(
                            u, cn, n_delta, digest_bpu, words_bpr, srt_ok)
                    now = self._monotonic_now()
                    with self._span("layout", ci) as lay:
                        if digest:
                            # Slot-sorted digest: the C index sorts the uniques
                            # in place (uidx remapped — reconstruction is order-
                            # agnostic) so the device write is a tile sweep.
                            # srt_ok (shared with the election above) already
                            # gates on the sweep actually engaging — on the
                            # XLA fallback the scatter is order-blind and the
                            # sort would be pure overhead.  The walk job
                            # has sorted already where it could tell.
                            srt = srt_ok and (presorted
                                              or sort(uwords, uidx))
                            size = _bucket_pow2(u)
                            uw = self._staging.take((size,), np.uint32)
                            uw[:u] = uwords
                            uw[u:] = 0xFFFFFFFF
                            if multi_lid:
                                # Tenant ids live RESIDENT on device (a slot's lid is
                                # immutable while assigned): upload only the (slot,
                                # lid) pairs the device doesn't know yet — fresh
                                # assignments and post-eviction reuse, tracked in
                                # _lid_known and invalidated by _clear_slots.  Per-
                                # unique lids map through uidx (NOT positional: a
                                # partitioned index merges uniques partition-major).
                                from ratelimiter_tpu.parallel.sharded import _bucket

                                first = rank == 0
                                ulids = np.zeros(u, dtype=np.int32)
                                ulids[uidx[first]] = l_chunk[first]
                                # Re-read fresh, mark, and dispatch under the lock
                                # shared with _clear_slots: an eviction racing the
                                # mark must win (forcing a later re-upload), never
                                # lose to a stale known=True.
                                with self._lid_locks[algo]:
                                    if srt:  # uwords were re-ordered in place
                                        uslots = (uwords >> np.uint32(rb + 1)
                                                  ).astype(np.int64)
                                    fresh = ~known[uslots]
                                    n_delta = int(fresh.sum())
                                    dsize = _bucket(max(n_delta, 1), floor=8)
                                    d_slots = _pad_tail(uslots[fresh], dsize, -1,
                                                        np.int32)
                                    d_lids = _pad_tail(ulids[fresh], dsize, 0,
                                                       np.int32)
                                    resident = (eng.sw_relay_counts_resident_dispatch
                                                if algo == "sw"
                                                else eng.tb_relay_counts_resident_dispatch)
                                    lay.end()
                                    with self._span("enqueue", ci):
                                        counts = resident(uw, d_slots, d_lids,
                                                          now, cdt,
                                                          slots_sorted=srt)
                                    # Mark AFTER the dispatch: a raise must not
                                    # leave slots "known" with no lid uploaded.
                                    known[uslots[fresh]] = True
                                    n_delta = dsize  # charge the padded lane
                            else:
                                lay.end()
                                with self._span("enqueue", ci):
                                    counts = counts_dispatch(
                                        uw, lid, now, cdt, slots_sorted=srt)
                            # The label says which row write the step used:
                            # a sorted one (the tile sweep, or the fused
                            # relay step where elected) or XLA's scatter.
                            item = ("digest-sorted" if srt else "digest",
                                    counts, start, cn, (uidx, rank, u),
                                    lay.t0, rec, [uw], ci)
                        else:
                            size = _bucket_pow2(cn)
                            words = self._staging.take((size,), np.uint32)
                            words[cn:] = 0xFFFFFFFF
                            if not rebuild_words_into(uwords, uidx, rank, rb,
                                                      words[:cn]):
                                words[:cn] = rebuild_words(uwords, uidx, rank, rb)
                            lid_lane = lid if not multi_lid else _pad_tail(
                                l_chunk, size, 0, np.int32)
                            lay.end()
                            with self._span("enqueue", ci):
                                bits = bits_dispatch(words, lid_lane, now)
                            item = ("bits", bits, start, cn, None, lay.t0,
                                    rec, [words], ci)
                    if rec is not None:
                        rec["dispatch_s"] = round(
                            time.perf_counter() - lay.t0, 6)
                wire_b = (digest_bpu * u + 8 * n_delta if digest
                          else words_bpr * cn)
                if rec is not None:
                    rec["mode"] = item[0]
                    rec["wire_bytes"] = int(wire_b)
                    rec["walk_s"] = round(walk_s[0], 6)  # cumulative
                    rec["host_s"] = round(time.perf_counter() - waited.t1, 6)
                # Grow the next chunk toward the wire budget at this chunk's
                # bytes per request (skewed streams compact hard in digest
                # mode, so their chunks grow fast).
                chunk_size = _grown_chunk(
                    _RELAY_WIRE_BUDGET_DIGEST if digest
                    else _RELAY_WIRE_BUDGET_WORDS, wire_b, cn)
                start += cn
                ci += 1
                if start < n:
                    # Prefetch the next chunk's assignment on the worker: it
                    # runs (GIL-free C walk) while this chunk's drain blocks
                    # in its (GIL-free) fetch on the drain pool.
                    fut = self._assign_pool().submit(
                        walk, start, min(chunk_size, n - start), ci)
                # Concurrent drain: this chunk's fetch overlaps the next
                # chunk's walk and the other in-flight fetches.
                drains.submit(drain, *item)
            drains.finish()  # propagate any drain error before returning
        finally:
            if fut is not None:
                self._abort_prefetch(
                    algo, self._index[algo], fut,
                    lambda res: (res[0] >> np.uint32(rb + 1)).astype(
                        np.int32))
            drains.finish(swallow=True)  # no-op on the normal path
        return out

    def _stream_weighted(self, algo, lid, assign_uniques, n, permits,
                          index, key_kind="ints") -> np.ndarray:
        """Weighted-permit relay streaming loop.

        Per chunk, one C call assigns slots and hands back the duplicate
        structure (uidx, rank); the host sorts segments by occurrence
        count DESCENDING and lays the permits out rank-major compacted
        (all rank-0 permits, then rank-1, ... — 1 B/request with zero
        padding waste, plus 4 B/unique of words), so each rank step's
        active segments are a PREFIX and the device reads its permits
        with one contiguous ``dynamic_slice``.  A short ``lax.scan``
        over rank steps then runs the exact skip recurrence of the
        sorted flat step.  No sort, no solver, no super-linear compile
        shapes, so chunks grow to the wire budget and pipeline
        three-deep exactly like the unit-permit relay.  A chunk whose
        deepest
        segment exceeds ``_WREL_MAX_R`` (heavy duplication — the scan
        would be long and mostly masked) falls back to sorted flat
        dispatches for that chunk.  Decisions are bit-identical to
        ``_stream_flat`` on the same chunking (tests/test_relay.py)."""
        eng = self.engine
        rb = eng.rank_bits
        cdt = eng.counts_dtype()
        dispatch = (eng.sw_weighted_dispatch if algo == "sw"
                    else eng.tb_weighted_dispatch)
        wc_dispatch = (eng.sw_weighted_counts_dispatch if algo == "sw"
                       else eng.tb_weighted_counts_dispatch)
        flat_dispatch = (eng.sw_flat_dispatch if algo == "sw"
                         else eng.tb_flat_dispatch)
        # The CSR mask needs true counts; the word count field clamps at
        # (1 << rank_bits) - 1, so deeper chunks must fall back.
        r_cap = min(_WREL_MAX_R, (1 << rb) - 1)
        out = np.empty(n, dtype=bool)
        drains = _DrainSet(self._drain_pool())
        rec_lock = threading.Lock()

        def drain(kind, handle, start, count, extra, t0, rec):
            with self._span("fetch") as fetch:
                arr = np.asarray(handle)
                if kind == "weighted":
                    arr = np.unpackbits(arr)
                elif kind != "flat":
                    arr = np.ascontiguousarray(arr)
            if kind == "weighted_coal":
                # Coalesced digest: per-unique allowed counts; the
                # prefix-allow closed form makes ``rank < counts[uidx]``
                # the exact arrival-order reconstruction (same C helper
                # as the unit-permit digest drain).
                from ratelimiter_tpu.engine.native_index import relay_decide

                uidx, rank, u = extra
                got = relay_decide(arr[:u], uidx, rank)
            elif kind == "weighted_native":
                from ratelimiter_tpu.engine.native_index import (
                    weighted_decide,
                )

                roff, spos32, uidx, rank = extra
                got = weighted_decide(arr, roff, spos32, uidx, rank)
            elif kind == "weighted":
                pos = extra  # roff[rank] + spos per request
                got = arr[pos].astype(bool)
            else:  # flat-fallback slice
                got = np.unpackbits(arr)[:count].astype(bool)
            out[start:start + count] = got
            dt_us = (time.perf_counter() - t0) * 1e6
            n_allowed = int(got.sum())
            with rec_lock:
                if rec is not None:
                    rec["fetch_s"] = round(
                        rec.get("fetch_s", 0) + fetch.secs, 6)
                self._record_dispatch(algo, count, n_allowed, dt_us,
                                      path=f"relay_w|{kind}", lid=lid)

        walk_s = [0.0]  # cumulative walk seconds, as in _stream_relay

        def timed_assign(s0, cnt):
            with self._span("index") as timed:
                r = assign_uniques(s0, cnt)
            self._record_index_phases(index)
            walk_s[0] += timed.secs
            return r

        # The growth schedule of _stream_relay, at the weighted budget.
        chunk_size = _RELAY_CHUNK
        start = 0
        fut = None  # prefetched next-chunk assignment (holds pins)
        try:
            while start < n:
                cn = min(chunk_size, n - start)
                t_a0 = time.perf_counter()
                if fut is not None:
                    uwords, uidx, rank, clears = fut.result()
                    fut = None
                else:
                    uwords, uidx, rank, clears = timed_assign(start, cn)
                t_assign = time.perf_counter() - t_a0
                u = len(uwords)
                uslots = (uwords >> np.uint32(rb + 1)).astype(np.int32)
                p_chunk = permits[start:start + cn]
                rec = self._stream_rec("relay_w", n=int(cn), u=int(u),
                                       assign_s=t_assign)
                with self._pins_released(index, uslots):
                    if len(clears):
                        self._clear_slots(algo, list(clears))
                    r_max = int(rank.max()) + 1 if cn else 1
                    now = self._monotonic_now()
                    t0 = time.perf_counter()
                    wlane = None
                    if _COALESCE and cdt is not None and cn:
                        # Segment-uniform weight probe: coalescing needs
                        # every repeat of a key to carry the same permits
                        # within the chunk (the closed form consumes
                        # n_allowed * w at once).  One scatter + one
                        # compare over the chunk — cheap next to the scan
                        # it deletes.  Mixed-weight chunks keep the exact
                        # rank-major scan path below, bit-identical either
                        # way.
                        wfirst = np.zeros(max(u, 1), dtype=np.uint8)
                        firsts = rank == 0
                        wfirst[uidx[firsts]] = p_chunk[firsts]
                        if not np.any(wfirst[uidx] != p_chunk):
                            wlane = wfirst
                    if wlane is not None:
                        u_b = _bucket_fine(max(u, 1))
                        uw_pad = _pad_tail(uwords, u_b, 0xFFFFFFFF,
                                           np.uint32)
                        w_pad = _pad_tail(wlane, u_b, 0, np.uint8)
                        handle = wc_dispatch(uw_pad, w_pad, lid, now, cdt)
                        drains.submit(drain, "weighted_coal", handle,
                                      start, cn, (uidx, rank, u), t0, rec)
                        csize = np.dtype(cdt).itemsize
                        wire_b = (5 + csize) * u_b
                        if rec is not None:
                            rec["mode"] = "weighted_coal"
                            rec["wire_bytes"] = int(wire_b)
                    elif r_max <= r_cap:
                        # Count-descending rank-major layout: segments sorted
                        # by occurrence count so each rank step's active set
                        # is a prefix — permits ship compacted (1 B/request,
                        # zero padding) and the device reads each step with
                        # one contiguous dynamic_slice (ops/relay.py:
                        # _weighted_step_w).  Counts come straight from the
                        # words' count field — unclamped here, since the true
                        # r_max (from the rank scratch) fit under r_cap.
                        # The layout itself is one C pass over structure the
                        # probe walk already produced (rl_weighted_layout,
                        # VERDICT r3 #2); the numpy argsort/bincount/scatter
                        # below is the library-less fallback, bit-identical.
                        from ratelimiter_tpu.engine.native_index import (
                            weighted_layout,
                        )

                        r_b = 2
                        while r_b < r_max:
                            r_b *= 2
                        u_b = _bucket_fine(max(u, 1))
                        uw_pad = np.full(u_b, 0xFFFFFFFF, dtype=np.uint32)
                        spos32 = np.empty(max(u, 1), dtype=np.int32)
                        roff = np.empty(r_b, dtype=np.int64)
                        perms_rank = np.zeros(_bucket_fine(cn) + u_b,
                                              dtype=np.uint8)
                        p64 = np.ascontiguousarray(p_chunk, dtype=np.int64)
                        if weighted_layout(uwords, rb, uidx, rank, p64, r_b,
                                           uw_pad, spos32, roff, perms_rank):
                            handle = dispatch(uw_pad, perms_rank, roff, lid,
                                              now, r_b)
                            drains.submit(
                                drain, "weighted_native", handle, start,
                                cn, (roff, spos32, uidx, rank), t0, rec)
                        else:
                            counts = ((uwords >> np.uint32(1))
                                      & np.uint32((1 << rb) - 1)).astype(
                                          np.int64)
                            order = np.argsort(-counts, kind="stable")
                            spos = np.empty(max(u, 1), dtype=np.int64)
                            spos[order] = np.arange(u, dtype=np.int64)
                            # k_r = number of segments with count > r; roff
                            # is its exclusive prefix sum.
                            hist = np.bincount(counts, minlength=r_b + 1)
                            k_r = u - np.cumsum(hist[:r_b])
                            roff = np.zeros(r_b, dtype=np.int64)
                            np.cumsum(k_r[:-1], out=roff[1:])
                            uw_pad = _pad_tail(uwords[order], u_b, 0xFFFFFFFF,
                                               np.uint32)
                            pos = roff[rank] + spos[uidx]
                            perms_rank[pos] = p_chunk
                            handle = dispatch(uw_pad, perms_rank, roff, lid,
                                              now, r_b)
                            drains.submit(drain, "weighted", handle, start,
                                          cn, pos, t0, rec)
                        wire_b = (4 * u_b + len(perms_rank)
                                  + len(perms_rank) // 8)
                        if rec is not None:
                            rec["mode"] = "weighted"
                            rec["wire_bytes"] = int(wire_b)
                    else:
                        # Heavy duplication: sorted flat dispatches for this
                        # chunk (<= _FLAT_MAX_LANES lanes each, as the sort
                        # compile ceiling requires).
                        slots_req = uslots[uidx]
                        for off in range(0, cn, _FLAT_MAX_LANES):
                            sl = min(_FLAT_MAX_LANES, cn - off)
                            size = _bucket_pow2(sl)
                            s_pad = _pad_tail(slots_req[off:off + sl], size,
                                              -1, np.int32)
                            p_pad = _pad_tail(p_chunk[off:off + sl], size, 1,
                                              np.uint8)
                            bits = flat_dispatch(s_pad, lid, p_pad, now)
                            drains.submit(drain, "flat", bits, start + off,
                                          sl, None, t0, rec)
                        wire_b = 5.0 * cn
                        if rec is not None:
                            rec["mode"] = "flat_fb"
                            rec["wire_bytes"] = int(wire_b)
                if rec is not None:
                    rec["walk_s"] = round(walk_s[0], 6)  # cumulative
                    rec["host_s"] = round(
                        time.perf_counter() - t_a0 - t_assign, 6)
                chunk_size = _grown_chunk(_RELAY_WIRE_BUDGET_WEIGHTED, wire_b,
                                          cn)
                start += cn
                if start < n:
                    # Prefetch the next chunk's assignment (see _stream_relay).
                    fut = self._assign_pool().submit(
                        timed_assign, start, min(chunk_size, n - start))
            drains.finish()  # propagate any drain error before returning
        finally:
            if fut is not None:
                self._abort_prefetch(
                    algo, index, fut,
                    lambda res: (res[0] >> np.uint32(rb + 1)).astype(
                        np.int32))
            drains.finish(swallow=True)  # no-op on the normal path
        return out

    def _stream_flat(self, algo, lid, assign, n, permits, oversize,
                     batch, subbatches, lid_arr=None) -> np.ndarray:
        """Common flat-streaming loop: per super-batch, one host slot
        assignment (``assign(start, count) -> (slots, clears)``), one FLAT
        device dispatch (ops/flat.py — every request in a dispatch shares
        its timestamp, so the flat sorted batch decides identically to
        ``subbatches`` sequential scan steps), and a pipelined bitmask
        fetch that overlaps the next super-batch's indexing + dispatch.

        The sorted step's lane count is capped at ``_FLAT_MAX_LANES``:
        its sort/scan ops have XLA:TPU compile times that grow
        super-linearly with lane count (~30 s at 512K lanes, ~4 min at
        2M, unusable at 4M — bench/profile_compile.py).  A super-batch
        larger than the cap dispatches as ONE ``lax.scan`` of
        cap-sized sub-batches instead (ops/packed.py) — same sorted step
        compiled once at the cap, but a single dispatch + fetch round
        trip per super-batch, which measures ~1.6x faster than chaining
        capped flat dispatches on the pre-PR-1 remote link."""
        multi_lid = lid_arr is not None
        super_n = int(subbatches) * int(batch)
        k_scan = 0
        if super_n > _FLAT_MAX_LANES:
            # Bounded by the stream length: a short stream must not pad
            # up to the requested super-batch's worth of dead lanes.
            k_scan = min(-(-super_n // _FLAT_MAX_LANES),
                         max(-(-n // _FLAT_MAX_LANES), 1))
            super_n = k_scan * _FLAT_MAX_LANES
            if k_scan == 1:
                k_scan = 0  # plain flat dispatch at the cap
        eng = self.engine
        if k_scan:
            dispatch = (eng.sw_scan_dispatch if algo == "sw"
                        else eng.tb_scan_dispatch)
        else:
            dispatch = (eng.sw_flat_dispatch if algo == "sw"
                        else eng.tb_flat_dispatch)
        def clear(slots):
            self._clear_slots(algo, slots)
        # When every permit in the stream fits a byte (the common case —
        # permits above max_permits are pointless), the permits lane ships
        # as uint8: 5 B/request on the wire instead of 8.  The device step
        # upcasts, decisions unchanged.
        p_dtype = np.int32
        if (permits is not None and permits.size
                and int(permits.min()) >= 0 and int(permits.max()) <= 255):
            p_dtype = np.uint8

        out = np.empty(n, dtype=bool)
        drains = _DrainSet(self._drain_pool())
        rec_lock = threading.Lock()

        def drain(handle, start, count, t0, rec):
            with self._span("fetch") as fetch:
                arr = np.asarray(handle)  # the one blocking fetch
            dt_us = (fetch.t1 - t0) * 1e6
            if k_scan:  # uint8[k, cap//8]
                got = np.unpackbits(arr, axis=1).reshape(-1)[:count]
                got = got.astype(bool)
            else:  # uint8[super_n//8]
                got = np.unpackbits(arr)[:count].astype(bool)
            out[start:start + count] = got
            n_allowed = int(got.sum())
            with rec_lock:
                if rec is not None:
                    rec["fetch_s"] = round(fetch.secs, 6)
                self._record_dispatch(algo, count, n_allowed, dt_us,
                                      path="flat|scan" if k_scan
                                      else "flat|sorted",
                                      lid=None if multi_lid else lid)

        index = self._index[algo]

        def timed_assign(start, count):
            r = assign(start, count)
            self._record_index_phases(index)
            return r

        fut = None  # prefetched next-chunk assignment (holds pins)
        try:
            for start in range(0, n, super_n):
                cn = min(super_n, n - start)
                # The tail super-batch shrinks to its own sub-batch count so a
                # partial chunk doesn't ship k_scan's worth of padding lanes.
                k_i = (min(k_scan, -(-cn // _FLAT_MAX_LANES)) if k_scan else 0)
                pad_n = k_i * _FLAT_MAX_LANES if k_i else super_n
                with self._span("index") as waited:
                    if fut is not None:
                        slots, clears = fut.result()
                        fut = None
                    else:
                        slots, clears = timed_assign(start, cn)
                t_assign = waited.secs
                lanes = 4 + (np.dtype(p_dtype).itemsize
                             if permits is not None else 0) + (
                    4 if multi_lid else 0)
                rec = self._stream_rec(
                    "flat", mode="scan" if k_i else "flat", n=int(cn),
                    assign_s=t_assign, wire_bytes=int(pad_n * lanes))
                raw_slots = slots
                with self._pins_released(index, raw_slots):
                    if len(clears):
                        clear(list(clears))
                    slots = _pad_tail(slots, pad_n, -1, np.int32)
                    if oversize is not None:
                        slots[:cn][oversize[start:start + cn]] = -1  # deny
                    lid_flat = lid if not multi_lid else _pad_tail(
                        lid_arr[start:start + cn], pad_n, 0, np.int32)
                    p_flat = None if permits is None else _pad_tail(
                        permits[start:start + cn], pad_n, 1, p_dtype)
                    now = self._monotonic_now()
                    with self._span("enqueue") as enq:
                        if k_i:
                            bits = dispatch(
                                slots.reshape(k_i, _FLAT_MAX_LANES),
                                lid_flat if not multi_lid
                                else lid_flat.reshape(k_i, _FLAT_MAX_LANES),
                                None if p_flat is None
                                else p_flat.reshape(k_i, _FLAT_MAX_LANES),
                                np.full(k_i, now, dtype=np.int64))
                        else:
                            bits = dispatch(slots, lid_flat, p_flat, now)
                    t0 = enq.t0
                if rec is not None:
                    rec["host_s"] = round(time.perf_counter() - waited.t1, 6)
                nxt = start + super_n
                if nxt < n:
                    # Prefetch the next super-batch's assignment (see
                    # _stream_relay).
                    fut = self._assign_pool().submit(
                        timed_assign, nxt, min(super_n, n - nxt))
                # Concurrent drain (see _stream_relay): the fetch cycle
                # overlaps later super-batches' walks and fetches.
                drains.submit(drain, bits, start, cn, t0, rec)
            drains.finish()  # propagate any drain error before returning
        finally:
            if fut is not None:
                self._abort_prefetch(
                    algo, self._index[algo], fut,
                    lambda res: np.asarray(res[0], dtype=np.int32))
            drains.finish(swallow=True)  # no-op on the normal path
        return out

    def acquire_stream_strs(
        self,
        algo: str,
        lid: int,
        keys: Sequence[str],
        permits: np.ndarray | None = None,
        *,
        batch: int = 1 << 14,
        subbatches: int = 4,
    ) -> np.ndarray:
        """Whole-stream STRING-key decisions, pipelined — the end-to-end
        analog of :meth:`acquire_stream_ids` (VERDICT r1 #3).

        Per super-batch: one C call hashes+assigns the whole key chunk
        (``assign_batch_strs``), one flat device dispatch decides it, and
        the bit-packed fetch overlaps the next chunk's host work — so the
        Python/ctypes string handling rides in the fetch shadow instead of
        serializing with it.  Decisions are identical to ``acquire_many``
        on the same chunks (same index namespace, same kernels).  Returns
        bool[n] allowed.
        """
        self._check_not_promoting()
        if self._fenced_shards:
            self._check_fence_keys([lid] * len(keys), keys)
        index = self._index[algo]
        oversize = None
        if permits is not None:
            permits = np.asarray(permits)
            if permits.size and int(permits.min(initial=0)) < np.iinfo(
                    np.int32).min:
                raise ValueError("permits below int32 range")
            over = permits > np.iinfo(np.int32).max
            if over.any():
                oversize = over
        if (hasattr(index, "_sub")
                and getattr(index, "supports_batch_strs", False)
                and permits is None
                and hasattr(self.engine, "relay_usable")
                and self.engine.relay_usable()):
            # Sharded engine, string keys (r6): hash each chunk ONCE
            # (fingerprints straight off the UTF-8 buffers), route by
            # h1 — the same quantity shard_of_key's string branch
            # computes, so scalar and stream traffic agree on every
            # key's shard — and run the shard-parallel pipelined relay.
            self._batcher.flush()
            return self._stream_relay_sharded(
                algo, lid, keys if isinstance(keys, list) else list(keys),
                index, False, None, key_kind="strs")
        if not hasattr(index, "assign_batch_strs"):
            # Python-index / sharded fallback: chunked batch path, same
            # decisions (no pipelining).
            n = len(keys)
            out = np.empty(n, dtype=bool)
            for i in range(0, n, batch):
                chunk = list(keys[i:i + batch])
                p = ([1] * len(chunk) if permits is None
                     else list(permits[i:i + batch]))
                res = self.acquire_many(algo, [lid] * len(chunk), chunk, p)
                out[i:i + len(chunk)] = res["allowed"]
            return out

        self._batcher.flush()
        if oversize is not None:
            permits = np.where(oversize, 1, permits)

        # Chunking passes a WINDOW (start, count) into the whole key
        # sequence — the index hashes straight out of it (zero per-key
        # Python objects on the list fast path; the r5 loop copied a
        # fresh list slice per chunk).
        if (permits is not None and oversize is None
                and hasattr(index, "assign_batch_strs_uniques")
                and permits.size
                and int(permits.min()) >= 1
                and int(permits.max()) <= self.engine.weighted_permit_cap):
            # Weighted relay for string keys — same loop as the int path,
            # only the assign closure differs (see acquire_stream_ids).
            rb = self.engine.rank_bits

            def assign_uniques_w(start, chunk_n):
                with self._evictions_cleared(algo):
                    return index.assign_batch_strs_uniques(
                        keys, lid, rb,
                        pinned=self._batcher.pending_slots(algo),
                        hold_pins=True, start=start, count=chunk_n)

            return self._stream_weighted(
                algo, lid, assign_uniques_w, len(keys),
                np.ascontiguousarray(permits, dtype=np.int64), index,
                key_kind="strs")

        if (permits is None
                and hasattr(index, "assign_batch_strs_uniques")
                and self.engine.relay_usable()):
            rb = self.engine.rank_bits

            def assign_uniques(start, chunk_n, **kw):
                with self._evictions_cleared(algo):
                    return index.assign_batch_strs_uniques(
                        keys, lid, rb,
                        pinned=self._batcher.pending_slots(algo),
                        hold_pins=True, start=start, count=chunk_n, **kw)

            return self._stream_relay(algo, lid, assign_uniques, len(keys),
                                      key_kind="strs")

        def assign(start, chunk_n):
            with self._evictions_cleared(algo):
                return index.assign_batch_strs(
                    keys, lid,
                    pinned=self._batcher.pending_slots(algo),
                    hold_pins=True, start=start, count=chunk_n)

        return self._stream_flat(algo, lid, assign, len(keys), permits,
                                 oversize, batch, subbatches)

    def _stream_sharded(self, algo, lid, key_ids, permits, batch, subbatches,
                        index, multi_lid, lid_arr,
                        oversize=None) -> np.ndarray:
        """Sharded-engine streaming: per-super-batch host routing (key ->
        shard by the deterministic splitmix hash), per-shard native slot
        assignment, one shard_map'd FLAT dispatch (ops/flat.py — the
        sub-batch dimension is gone: all requests in a dispatch share its
        timestamp, so each shard decides its whole slice as one sorted
        batch), pipelined bitmask fetch.  Decisions are identical to the
        flat single-device stream on the same per-key request order."""
        eng = self.engine
        if (permits is None and hasattr(eng, "relay_usable")
                and eng.relay_usable()
                and all(hasattr(s, "assign_batch_ints_uniques")
                        for s in index._sub)):
            return self._stream_relay_sharded(algo, lid, key_ids, index,
                                              multi_lid, lid_arr)
        if oversize is not None:
            permits = np.where(oversize, 1, permits)  # lanes masked; the
            # oversized requests dispatch as padding (slot -1) below.
        n_sh, sps = eng.n_shards, eng.slots_per_shard
        # Same per-dispatch lane cap as _stream_flat: the per-shard slice
        # is what the sorted step compiles over, and _bucket rounds the
        # busiest shard's count up to a power of two, so budget half the
        # single-device lanes per shard to keep the bucketed b_loc at or
        # under _FLAT_MAX_LANES even with hash imbalance.
        super_n = min(int(subbatches) * int(batch),
                      (_FLAT_MAX_LANES // 2) * n_sh)
        dispatch = (eng.sw_flat_sharded_dispatch if algo == "sw"
                    else eng.tb_flat_sharded_dispatch)
        def clear(slots):
            self._clear_slots(algo, slots)
        n = len(key_ids)
        out = np.empty(n, dtype=bool)
        drains = _DrainSet(self._drain_pool())
        rec_lock = threading.Lock()

        def drain(handle, start, cnt, shard, cols, b_loc, t0):
            arr = np.asarray(handle)  # uint8[n_sh, b_loc//8]
            dt_us = (time.perf_counter() - t0) * 1e6
            bits = np.unpackbits(arr, axis=1)[:, :b_loc].astype(bool)
            got = bits[shard, cols]
            out[start:start + cnt] = got
            n_allowed = int(got.sum())
            with rec_lock:
                self._record_dispatch(algo, cnt, n_allowed, dt_us,
                                      path="sharded|flat",
                                      lid=None if multi_lid else lid)

        pool = self._shard_pool(n_sh)
        try:
            for start in range(0, n, super_n):
                self._stream_sharded_chunk(
                    algo, lid, key_ids, permits, oversize, index, multi_lid,
                    lid_arr, start, super_n, n_sh, sps, pool, dispatch,
                    clear, drains, drain)
            drains.finish()
        finally:
            drains.finish(swallow=True)  # no-op on the normal path
        return out

    def _stream_sharded_chunk(self, algo, lid, key_ids, permits, oversize,
                              index, multi_lid, lid_arr, start, super_n,
                              n_sh, sps, pool, dispatch, clear, drains,
                              drain) -> None:
        """One super-batch of the sharded FLAT stream (split out so the
        loop in :meth:`_stream_sharded` can wrap drain lifetime cleanly)."""
        chunk = key_ids[start:start + super_n]
        cn = len(chunk)
        clears: list = []
        pins_by_shard: dict = {}
        for g in self._batcher.pending_slots(algo):
            pins_by_shard.setdefault(g // sps, set()).add(g % sps)
        l_chunk = lid_arr[start:start + cn] if multi_lid else None
        # One routing pass (see _stream_relay_sharded); per-shard C
        # calls run on the pool against contiguous slices.
        shard, order, counts = _route_chunk(chunk, n_sh)
        offs = np.zeros(n_sh + 1, dtype=np.int64)
        np.cumsum(counts, out=offs[1:])
        kst = chunk[order]
        l_st = l_chunk[order] if multi_lid else None

        def assign_shard(s):
            lo, hi = int(offs[s]), int(offs[s + 1])
            if lo == hi:
                return None
            sub = index._sub[s]
            if multi_lid:
                return sub.assign_batch_ints_multi(
                    kst[lo:hi], l_st[lo:hi],
                    pinned=pins_by_shard.get(s), hold_pins=True)
            return sub.assign_batch_ints(
                kst[lo:hi], lid, pinned=pins_by_shard.get(s),
                hold_pins=True)

        # Pins of successful shards accumulate in held as results are
        # collected; the finally releases them on ANY raise (a leaked
        # pin would make its slot permanently unevictable).
        local_sorted = np.empty(cn, dtype=np.int32)
        held: list = []
        try:
            futs = [pool.submit(assign_shard, s) for s in range(n_sh)]
            err = None
            for s, f in enumerate(futs):
                try:
                    r = f.result()
                except Exception as exc:  # noqa: BLE001
                    err = err if err is not None else exc
                    # Partial-failure lanes still evicted: globalize
                    # into the pooled clears, cleared below.
                    clears.extend(consume_pending_clears(exc, s * sps))
                    continue
                if r is None:
                    continue
                sl, ev = r
                local_sorted[offs[s]:offs[s + 1]] = sl
                held.append(s * sps + sl.astype(np.int64))
                clears.extend(s * sps + int(e) for e in ev)
            if err is not None:
                # Successful shards' assignments are already in the
                # index: their evicted slots must be zeroed even
                # though no dispatch happens (ADVICE r3).
                if clears:
                    clear(clears)
                raise err
            if clears:
                clear(clears)
            local = np.empty(cn, dtype=np.int32)
            local[order] = local_sorted
            # Column of each request within its shard row (arrival
            # order — the stable per-slot segment order the flat step
            # sorts by).
            cols = np.empty(cn, dtype=np.int64)
            cols[order] = np.arange(cn) - offs[shard[order]]
            from ratelimiter_tpu.parallel.sharded import _bucket

            b_loc = _bucket(int(counts.max(initial=1)))
            slots_mat = np.full((n_sh, b_loc), -1, dtype=np.int32)
            slots_mat[shard, cols] = local
            if oversize is not None:
                ov = oversize[start:start + cn]
                slots_mat[shard[ov], cols[ov]] = -1  # force-deny
            lid_sb = lid
            if multi_lid:
                lid_mat = np.zeros((n_sh, b_loc), dtype=np.int32)
                lid_mat[shard, cols] = l_chunk
                lid_sb = lid_mat
            p_sb = None
            if permits is not None:
                p_mat = np.ones((n_sh, b_loc), dtype=np.int32)
                p_mat[shard, cols] = permits[start:start + cn]
                p_sb = p_mat
            now = self._monotonic_now()
            t0 = time.perf_counter()
            bits = dispatch(slots_mat, lid_sb, p_sb, now)
        finally:
            self._unpin_held(index, held)
        # Concurrent drain (see _stream_relay): fetch cycles overlap.
        drains.submit(drain, bits, start, cn, shard, cols, b_loc, t0)

    def _stream_relay_sharded(self, algo, lid, key_ids, index, multi_lid,
                              lid_arr, key_kind="ints") -> np.ndarray:
        """Sharded relay streaming over fully independent per-shard
        pipelines (r8; ROADMAP item 1).

        Per chunk the main thread does ONE routing pass (the host C
        router, :meth:`_route_host`, in parallel request ranges into a
        buffer set of the storage's :class:`_RoutePool`, given back when
        the call's drains have finished) and hands each shard its
        contiguous slice; from there everything is per-shard: slot
        assignment and the slot sort of its uniques, eviction clears,
        layout into the lane's own staging buffer, a SINGLE-DEVICE
        dispatch on the shard's own device
        (``ShardedDeviceEngine.relay_shard_dispatch``), and a bounded
        per-lane drain queue.  There is no cross-shard
        barrier anywhere; the only ordering constraint is per-shard
        stream order, enforced by each lane's FIFO worker — which is
        also the clear path: a shard's eviction clears enter its device
        stream ahead of the dispatch that reuses those slots, and a key
        never migrates shards, so nothing else needs ordering.

        The r6/r7 loop instead barriered every chunk into one mesh-wide
        shard_map dispatch: every shard waited for the slowest sibling's
        layout, the multi-device launch rendezvoused all devices, and
        the lane padding followed the busiest shard — r05 (before PR 1) measured
        the result anti-scaling 19.5M -> 4.3M decisions/s from 1 -> 8
        shards on the CPU mesh.

        The device step is the one-chip relay loop's: a shard whose
        digest the tile sweep can write (``block_scatter.enabled`` on
        the shard's table and padded uniques) slot-sorts its uniques in
        its own job, right after its walk (the same C sort a partition
        of the one-chip index runs), and dispatches the sorted step;
        any other shard keeps XLA's row write.  Digest or words is
        elected PER SHARD by the one-chip rule, ``_elect_digest_mode``,
        from that shard's uniques and lanes.  Every dispatch records its
        route as ``sharded|digest-sorted`` / ``sharded|digest`` /
        ``sharded|words`` with its shard id in the decision trace and
        latency histograms.  Each lane's job is the span
        ``ratelimiter.stream.shard`` (shard and chunk as its metadata;
        its ``index``, ``layout`` and ``enqueue`` spans nest in it);
        per chunk with two or more shards the ``shard_straggle`` timer
        takes the slowest job's seconds minus the median job's; the
        per-shard counters ``shard_lanes.<s>`` and ``shard_uniques.<s>``
        count what each lane walked; ``route`` stays the caller's
        binning stage; and a lane whose drain queue blocks flags
        ``shard.drain_saturated`` to the flight recorder.  Decisions are
        bit-identical to the single-device stream and to the oracle on
        the same per-key request order (per-key order is per-shard
        order)."""
        from ratelimiter_tpu.engine.native_index import (
            hash_str_keys,
            relay_decide_pos,
            rebuild_words_into,
            sort_uniques,
        )
        from ratelimiter_tpu.ops.pallas import block_scatter
        from ratelimiter_tpu.ops.relay import rebuild_words, wire_costs
        from ratelimiter_tpu.parallel.sharded import _bucket

        eng = self.engine
        n_sh, sps = eng.n_shards, eng.slots_per_shard
        rb = eng.rank_bits
        cdt = eng.counts_dtype()
        digest_bpu, words_bpr = wire_costs(multi_lid)
        shard_shape = eng.shard_state_shape(algo)
        n = len(key_ids)
        out = np.empty(n, dtype=bool)
        if n == 0:
            return out
        lanes = self._shard_lanes(n_sh)
        route_pool = self._route_pool(n_sh)
        held: list = []  # this call's route sets, given back at its end
        stop = threading.Event()
        errors: list = []  # (chunk_i, shard, exc); first in stream order wins
        err_lock = threading.Lock()

        def fail(ci, s, exc):
            with err_lock:
                errors.append((ci, s, exc))
            stop.set()

        def shard_task(ci, s, *args):
            """One shard lane's job for one chunk, on the lane's FIFO
            worker, as the ``shard`` span; its seconds feed the chunk's
            straggle (``finalize``)."""
            with self._span("shard", ci, s) as job:
                shard_job(ci, s, *args)
            ctx = args[-1]
            ctx["job"][s] = job.secs

        def shard_job(ci, s, start, now, keys_s, h1_s, h2_s, pos_s, l_s,
                      pins_s, ctx):
            """Everything one shard does for one chunk.  Never raises:
            failures land in ``errors`` and set ``stop`` (sibling lanes
            stop dispatching; evictions an already-applied assignment
            made are still cleared)."""
            if stop.is_set():
                return
            lane = lanes[s]
            sub = index._sub[s]
            ns = len(pos_s)
            buf = None
            pinned_local = None
            dispatched = False
            try:
                with self._span("index", ci, s) as walk:
                    try:
                        if key_kind != "ints":
                            uw, uidx, rank, ev = sub.assign_batch_fps_uniques(
                                h1_s, h2_s, rb, pinned=pins_s, hold_pins=True)
                        elif multi_lid:
                            uw, uidx, rank, ev = (
                                sub.assign_batch_ints_multi_uniques(
                                    keys_s, l_s, rb, pinned=pins_s,
                                    hold_pins=True))
                        else:
                            uw, uidx, rank, ev = sub.assign_batch_ints_uniques(
                                keys_s, lid, rb, pinned=pins_s, hold_pins=True)
                    except Exception as exc:  # noqa: BLE001
                        # Lanes that assigned before the failure are already
                        # remapped in the index: their evicted slots must be
                        # zeroed even though nothing dispatches (ADVICE r3).
                        pc = consume_pending_clears(exc, 0)
                        if len(pc):
                            self._clear_shard(algo, s, pc)
                        raise
                    pinned_local = (uw >> np.uint32(rb + 1)).astype(
                        np.int32)
                    # The mode and, for a digest the sweep can write,
                    # the slot sort, in this job right after the walk.
                    u = len(uw)
                    u_pad = _bucket(max(u, 1))
                    srt_ok = (cdt is not None
                              and u >= _SORT_UNIQUES_MIN
                              and _sort_affordable()
                              and block_scatter.enabled(shard_shape,
                                                        u_pad))
                    digest = cdt is not None and _elect_digest_mode(
                        u, ns, 0, digest_bpu, words_bpr, srt_ok)
                    srt = digest and srt_ok and sort_uniques(uw, rb, uidx)
                ctx["walk"][s] = walk.secs
                if len(ev):
                    # Stream-order clear path: this lane is a FIFO, so
                    # the clear precedes this chunk's dispatch in this
                    # shard's device stream.
                    self._clear_shard(algo, s, ev)
                ctx["u"][s] = u
                if lane.n_lanes is not None:
                    lane.n_lanes.add(ns)
                    lane.n_uniques.add(u)
                with self._span("layout", ci, s) as lay:
                    if digest:
                        buf = lane.staging.take((u_pad,), np.uint32)
                        buf[:u] = uw
                        buf[u:] = 0xFFFFFFFF
                        lid_lane = lid
                        if multi_lid:
                            first = rank == 0
                            ulids = np.zeros(u_pad, dtype=np.int32)
                            ulids[uidx[first]] = l_s[first]
                            lid_lane = ulids
                        ctx["wire"][s] = digest_bpu * u
                    else:
                        b_pad = _bucket(max(ns, 1))
                        buf = lane.staging.take((b_pad,), np.uint32)
                        if not rebuild_words_into(uw, uidx, rank, rb,
                                                  buf[:ns]):
                            buf[:ns] = rebuild_words(uw, uidx, rank, rb)
                        buf[ns:] = 0xFFFFFFFF
                        lid_lane = lid
                        if multi_lid:
                            lid_lane = np.zeros(b_pad, dtype=np.int32)
                            lid_lane[:ns] = l_s
                        ctx["wire"][s] = words_bpr * ns
                    mode = (("digest-sorted" if srt else "digest")
                            if digest else "words")
                    ctx["modes"][s] = mode
                ctx["layout"][s] = lay.secs
                if stop.is_set():  # a sibling failed after our assign
                    return
                with self._span("enqueue", ci, s) as enq:
                    if digest:
                        handle = eng.relay_shard_dispatch(
                            algo, s, "counts", buf, lid_lane, now, cdt,
                            slots_sorted=srt)
                    else:
                        handle = eng.relay_shard_dispatch(
                            algo, s, "bits", buf, lid_lane, now)
                    dispatched = True
                t0 = enq.t0
                ctx["enq"][s] = enq.secs
            except Exception as exc:  # noqa: BLE001
                fail(ci, s, exc)
                return
            finally:
                # Pins release once the dispatch entered the shard's
                # stream (or on any failure) — see _pins_released.
                if pinned_local is not None and hasattr(sub, "unpin_batch"):
                    sub.unpin_batch(pinned_local)
                if not dispatched and buf is not None:
                    lane.staging.give(buf)

            def drain(handle=handle, mode=mode, buf=buf, u=u, uidx=uidx,
                      rank=rank, pos_s=pos_s, ns=ns, s=s, start=start,
                      t0=t0, ctx=ctx):
                try:
                    with self._span("fetch", ci, s) as fetch:
                        arr = np.asarray(handle)
                    if mode != "words":
                        # Fused reconstruct + unscatter straight into the
                        # output suffix (one C pass).
                        alw = relay_decide_pos(arr[:u], uidx, rank, pos_s,
                                               out[start:])
                    else:
                        bits = np.unpackbits(arr)[:ns].astype(bool)
                        out[start + pos_s] = bits
                        alw = int(bits.sum())
                    rec = ctx["rec"]
                    if rec is not None:
                        with ctx["lock"]:
                            rec["fetch_s"] = round(
                                max(rec.get("fetch_s", 0.0), fetch.secs), 6)
                    self._record_dispatch(algo, ns, int(alw),
                                          (fetch.t1 - t0) * 1e6,
                                          path=f"sharded|{mode}", shard=s,
                                          lid=None if multi_lid else lid)
                finally:
                    lane.staging.give(buf)

            lane.drains.submit(drain)

        # Chunk sizing: learned steady-state size per stream shape (the
        # lanes' host work is already off the critical path, so giant
        # chunks win).
        shape_key = (key_kind, algo, bool(multi_lid),
                     _bucket_fine(n, floor=_RELAY_CHUNK))
        chunk = self._sharded_chunks.get(shape_key, _RELAY_CHUNK)
        inflight: list = []
        ci = 0
        start = 0

        def finalize(ctx):
            """Join one chunk's shard tasks, record its straggle, fold
            its per-shard seconds into the chunk record, and re-learn
            the chunk size from its measured bytes/request."""
            nonlocal chunk
            for f in ctx["futs"]:
                f.result()  # tasks never raise; surfaces executor faults
            jobs = ctx["job"][~np.isnan(ctx["job"])]
            if len(jobs) >= 2 and self._stage_timers is not None:
                self._stage_timers["shard_straggle"].record_us(
                    (jobs.max() - np.median(jobs)) * 1e6)
            wire_b = float(ctx["wire"].sum())
            rec = ctx["rec"]
            if rec is not None:
                modes = [m for m in ctx["modes"] if m]
                with ctx["lock"]:
                    rec.update(
                        u=int(ctx["u"].sum()),
                        mode=(modes[0] if len(set(modes)) == 1
                              else "mixed"),
                        wire_bytes=int(wire_b),
                        route_s=round(float(ctx["route_s"]), 6),
                        assign_s=round(float(ctx["walk"].max()), 6),
                        shard_walk_s=[round(float(x), 6)
                                      for x in ctx["walk"]],
                        shard_n=[int(x) for x in ctx["shard_n"]],
                        layout_s=round(float(ctx["layout"].sum()), 6),
                        dispatch_s=round(float(ctx["enq"].sum()), 6),
                        host_s=round(float(ctx["route_s"])
                                     + float(ctx["layout"].sum())
                                     + float(ctx["enq"].sum()), 6),
                    )
                    if ctx["pack_s"]:
                        rec["pack_s"] = round(ctx["pack_s"], 6)
            if wire_b > 0 and ctx["cn"]:
                digesty = sum(1 for m in ctx["modes"]
                              if m and m != "words")
                mody = max(sum(1 for m in ctx["modes"] if m), 1)
                chunk = _grown_chunk(_RELAY_WIRE_BUDGET_DIGEST
                                     if 2 * digesty >= mody
                                     else _RELAY_WIRE_BUDGET_WORDS,
                                     wire_b, ctx["cn"])

        try:
            while start < n and not stop.is_set():
                cn = min(chunk, n - start)
                pack_s = 0.0
                h1st = h2st = kst = None
                if key_kind == "ints":
                    with self._span("route", ci) as route:
                        bufs = route_pool.take(cn)
                        held.append(bufs)
                        order, counts, kst = self._route_host(
                            key_ids[start:start + cn], None, None, n_sh,
                            bufs)
                else:
                    with self._span("pack") as pack:
                        fp = hash_str_keys(key_ids, lid, start, cn)
                        if fp is None:
                            raise RuntimeError(
                                "native string hashing unavailable "
                                "mid-stream (mutated key list?)")
                    pack_s = pack.secs
                    with self._span("route", ci) as route:
                        order, counts, h1st, h2st = self._route_host(
                            None, fp[0], fp[1], n_sh)
                route_s = route.secs
                offs = np.zeros(n_sh + 1, dtype=np.int64)
                np.cumsum(counts, out=offs[1:])
                l_chunk = lid_arr[start:start + cn] if multi_lid else None
                pins = self._batcher.pending_slots_sharded(algo, sps)
                now = self._monotonic_now()
                rec = self._stream_rec("relay_sharded", n=int(cn))
                ctx = {
                    "cn": cn, "rec": rec, "lock": threading.Lock(),
                    "walk": np.zeros(n_sh), "layout": np.zeros(n_sh),
                    "enq": np.zeros(n_sh), "wire": np.zeros(n_sh),
                    "job": np.full(n_sh, np.nan),
                    "u": np.zeros(n_sh, np.int64),
                    "modes": [None] * n_sh, "shard_n": counts,
                    "route_s": route_s, "pack_s": pack_s, "futs": [],
                }
                inflight.append(ctx)  # before submitting: joined on error
                for s in range(n_sh):
                    lo, hi = int(offs[s]), int(offs[s + 1])
                    if lo == hi:
                        continue
                    pos_s = order[lo:hi]
                    ctx["futs"].append(lanes[s].pipe.submit(
                        shard_task, ci, s, start, now,
                        kst[lo:hi] if kst is not None else None,
                        h1st[lo:hi] if h1st is not None else None,
                        h2st[lo:hi] if h2st is not None else None,
                        pos_s,
                        l_chunk[pos_s] if multi_lid else None,
                        pins.get(s), ctx))
                start += cn
                ci += 1
                # Bounded look-ahead: route at most _SHARD_LOOKAHEAD
                # chunks beyond the oldest still-assembling one (bounds
                # staging memory and the learned-size feedback lag).
                while len(inflight) > _SHARD_LOOKAHEAD:
                    finalize(inflight.pop(0))
            while inflight:
                finalize(inflight.pop(0))
            if not stop.is_set():
                for lane in lanes:
                    lane.drains.finish()
        finally:
            while inflight:
                try:
                    finalize(inflight.pop(0))
                except Exception:  # noqa: BLE001 — primary error wins
                    pass
            for lane in lanes:
                lane.drains.finish(swallow=True)  # no-op when healthy
            # Every job that read a set's keys is joined and every drain
            # that read its order has finished.
            route_pool.give(held)
        if errors:
            errors.sort(key=lambda e: (e[0], e[1]))
            raise errors[0][2]
        self._sharded_chunks[shape_key] = chunk
        return out

    @staticmethod
    def _route_host(kchunk, h1, h2, n_sh, bufs=None):
        """One chunk's shard routing on the host: ``(order, counts, keys
        in shard order)`` for int keys (``kchunk``), ``(order, counts, h1
        sorted, h2 sorted)`` for string fingerprints; ``order`` holds
        each shard's request indices in arrival order, shard-major.

        Int keys route in parallel request ranges (at most
        ``_ROUTE_RANGES``, one per ``_RANGE_GRAIN`` keys) into ``bufs``,
        a :class:`_RouteSet` at least as long as the chunk (None: a new
        one), and the outputs are views of it; without the native
        library, numpy routes them.  The on-mesh route pass lost to the
        host on a 2x2 v5e host at the stream's chunk sizes and was
        deleted (PERF.md)."""
        from ratelimiter_tpu.engine.native_index import (
            native_available,
            route_hashes_gather,
            shard_route_ranges,
        )

        if h1 is not None:
            _, order, counts, h1s, h2s = route_hashes_gather(h1, h2, n_sh)
            return order, counts, h1s, h2s
        from ratelimiter_tpu.engine.partitioned import _RANGE_GRAIN

        kchunk = np.ascontiguousarray(kchunk, dtype=np.int64)
        n = len(kchunk)
        if bufs is None:
            bufs = _RouteSet(n, n_sh)
        order, kst = bufs.order[:n], bufs.keys[:n]
        if n_sh <= 256 and native_available():  # a uint8 shard lane
            n_ranges = max(1, min(_ROUTE_RANGES, -(-n // _RANGE_GRAIN)))
            bounds = np.arange(n_ranges + 1, dtype=np.int64) * n // n_ranges
            shard_route_ranges(kchunk, n_sh, bounds, bufs.part[:n],
                               bufs.local[:n_ranges], bufs.offs, kst, order)
            return order, np.diff(bufs.offs), kst
        _, o, counts = _route_chunk(kchunk, n_sh)
        order[:] = o
        np.take(kchunk, o, out=kst)
        return order, counts, kst

    def _clear_shard(self, algo: str, s: int, local_slots) -> None:
        """Per-shard eviction clears (r8): zero LOCAL slots in shard
        ``s``'s own device stream (``ShardedDeviceEngine.clear_shard``).
        Mirrors :meth:`_clear_slots`' resident-lid invalidation — the
        sharded digest path keeps no resident lids today, but the guard
        preserves the invariant if it ever does."""
        local_slots = [int(x) for x in local_slots]
        if not local_slots:
            return
        known = self._lid_known.get(algo)
        if known is None:
            self.engine.clear_shard(algo, s, local_slots)
            return
        with self._lid_locks[algo]:
            self.engine.clear_shard(algo, s, local_slots)
            base = s * self.engine.slots_per_shard
            known[np.asarray(local_slots, dtype=np.int64) + base] = False

    def _route_pool(self, n_sh: int) -> _RoutePool:
        """The sharded loop's route buffer pool (lazily created; see
        :class:`_RoutePool`)."""
        pool = getattr(self, "_route_pool_obj", None)
        if pool is None:
            pool = _RoutePool(n_sh, registry=self.registry if self._obs
                              else None)
            self._route_pool_obj = pool
        return pool

    def _shard_lanes(self, n_sh: int):
        """The per-shard pipeline lanes (lazily created; see
        :class:`_ShardLane`)."""
        lanes = getattr(self, "_shard_lanes_obj", None)
        if lanes is None:
            lanes = [_ShardLane(s, recorder=self._recorder,
                                registry=self.registry if self._obs
                                else None)
                     for s in range(n_sh)]
            self._shard_lanes_obj = lanes
        return lanes

    def available_many(
        self, algo: str, lid: int, keys: Sequence[str]
    ) -> np.ndarray:
        """Read-only availablePermits; unknown keys are computed host-side
        (absent state: full availability)."""
        _, config = self._configs[lid]
        index = self._index[algo]
        known: List[Tuple[int, int]] = []  # (position, slot)
        out = np.empty(len(keys), dtype=np.int64)
        for i, key in enumerate(keys):
            slot = index.get((lid, key))
            if slot is None:
                out[i] = config.max_permits
            else:
                known.append((i, slot))
        if known:
            # Flush queued mutations so the read observes them.
            self._batcher.flush()
            now = self._monotonic_now()
            slots = [s for _, s in known]
            if algo == "sw":
                vals = self.engine.sw_available(slots, [lid] * len(slots), now)
            else:
                vals = self.engine.tb_available(slots, [lid] * len(slots), now)
            for (i, _), v in zip(known, vals):
                out[i] = v
        return out

    def reset_key(self, algo: str, lid: int, key: str) -> None:
        """Admin reset: flush pending, clear the slot, then release it.

        Order matters: the slot is zeroed while still mapped to the old key,
        and only then returned to the free list — so no other key can be
        assigned the slot before it is clean (a zeroed slot reads as absent).
        """
        index = self._index[algo]
        if self._serving is not None:
            # Mid-stream policy reset: the hybrid tier must forget its
            # adopted state BEFORE the device clear so a concurrent
            # serve can't answer from pre-reset counters.
            self._serving.invalidate(algo, lid, key)
        if index.get((lid, key)) is None:
            return
        self._batcher.flush()
        slot = index.get((lid, key))
        if slot is None:
            return
        self._clear_slots(algo, [slot])
        index.remove((lid, key))

    # ------------------------------------------------------------------------
    # Token leases (leases/): atomic bulk reserve / credit
    # ------------------------------------------------------------------------
    def lease_reserve(self, algo: str, lid: int, key: str,
                      requested: int) -> Dict:
        """Atomically charge up to ``requested`` permits for one key
        against the live device counters — the grant side of a token
        lease (leases/manager.py).  Pending micro-batch traffic is
        flushed first so the grant observes every decision already
        admitted.  Runs the fused RESERVE kernel (ops/lease.py) on the
        single-device engine, the exclusive host round trip on the
        sharded mesh.  Returns ``{"granted", "ws", "stamp"}`` —
        ``ws`` is the charged window start (sliding window; 0 for the
        token bucket), which :meth:`lease_credit` must present.

        The same fence/promotion checks guard this as every decision
        surface: a fenced storage refuses with ``FencedError``, which
        the lease manager converts into lease revocation."""
        self._check_not_promoting()
        if self._fenced_shards:
            self._check_fence_keys([lid], [key])
        if self._serving is not None:
            # A leased key's state mutates outside the hybrid tier's
            # watch: its adopted snapshot is stale the moment the
            # reserve lands.
            self._serving.invalidate(algo, lid, key)
        self._batcher.flush()
        slot = self._assign_slot(algo, lid, key, hold_pin=True)
        with self._pins_released(self._index[algo], [slot]):
            now = self._monotonic_now()
            granted, ws = self.engine.lease_reserve(
                algo, [slot], [int(lid)], [int(requested)], now)
        return {"granted": int(granted[0]), "ws": int(ws[0]),
                "stamp": int(now)}

    def lease_credit(self, algo: str, lid: int, key: str, credit: int,
                     grant_ws: int) -> Dict:
        """Return ``credit`` unused reserved permits for one key (lease
        renewal/release).  A key whose slot was evicted credits nothing
        — its charge was cleared with the slot.  Returns ``{"credited",
        "stamp"}`` (the stamp makes the operation replayable against
        the oracle bit-for-bit — leases/manager.py records it)."""
        self._check_not_promoting()
        if self._fenced_shards:
            self._check_fence_keys([lid], [key])
        index = self._index[algo]
        if index.get((lid, key)) is None:
            return {"credited": 0, "stamp": 0}
        if self._serving is not None:
            self._serving.invalidate(algo, lid, key)
        self._batcher.flush()
        slot = index.get((lid, key))
        if slot is None:
            return {"credited": 0, "stamp": 0}
        now = self._monotonic_now()
        credited = self.engine.lease_credit(
            algo, [slot], [int(lid)], [int(credit)], [int(grant_ws)], now)
        return {"credited": int(credited[0]), "stamp": int(now)}

    def flush(self) -> None:
        self._batcher.flush()

    def warm_micro_shapes(self) -> None:
        """Pre-compile the small-shape micro-batch step for both algos
        (engine/engine.py:warm_micro_shapes): call once at service boot
        so the first interactive request doesn't pay an XLA compile.
        No-op on engines without micro shapes (the sharded engine
        buckets at its own floor)."""
        if hasattr(self.engine, "warm_micro_shapes"):
            self.engine.warm_micro_shapes()

    @staticmethod
    def _unpin_held(index, held) -> None:
        """Release pins accumulated as a list of slot arrays — the finally
        half of :meth:`_pins_released` for loops that take pins shard by
        shard and must release whatever was taken on any exception path."""
        if held and hasattr(index, "unpin_batch"):
            index.unpin_batch(np.concatenate(held))

    @contextlib.contextmanager
    def _evictions_cleared(self, algo: str):
        """A failed batch assignment still applied evictions for the lanes
        that succeeded before the failure (engine/errors.py
        SlotCapacityError.pending_clears): those slots are already
        remapped to new keys in the index, so zero their device state
        before the error propagates — exactly as the success path clears
        evictions ahead of reuse.  Clears once (the attribute is consumed)
        however many handlers the raise passes through."""
        try:
            yield
        except Exception as exc:  # noqa: BLE001 — always re-raised
            pc = getattr(exc, "pending_clears", None)
            if pc is not None and len(pc):
                # Clear FIRST, null after: a clear-time failure must
                # propagate with the clears still attached so an outer
                # handler could retry (zeroing is idempotent).
                self._clear_slots(algo, [int(s) for s in pc])
                exc.pending_clears = None
            raise

    @contextlib.contextmanager
    def _pins_released(self, index, slots):
        """Release pins taken ATOMICALLY inside an assign
        (``hold_pins=True``) once the enclosed dispatch is enqueued.

        The pins close an eviction race: without them, concurrent scalar
        traffic under eviction pressure could reassign-and-clear a slot
        BETWEEN the batch's slot assignment and its dispatch entering
        the device stream, making the batch write stale state into
        another key's slot.  Pinning after the assign returned would
        leave the same gap, which is why the indexes pin under the same
        lock hold as the assignment.  (Dispatches serialize in program
        order, so anything cleared AFTER the enqueue stays correct.)"""
        try:
            yield
        finally:
            if hasattr(index, "unpin_batch") and len(slots):
                with self._span("clear"):
                    index.unpin_batch(slots)

    def _clear_slots(self, algo: str, slots) -> None:
        """Single choke point for zeroing evicted/reset slots.

        Besides the device-state clear, it invalidates the host's record
        of which slots' tenant ids the device lid map knows — a cleared
        slot can be reassigned to a different (lid, key), so its resident
        lid must be re-uploaded on next digest use."""
        if not len(slots):
            return
        if self._serving is not None:
            # A cleared slot's key state is gone on device; any hybrid
            # tier entry tracking it is stale the moment the clear is in
            # the stream (eviction paths also invalidate at remap time —
            # see _assign_slot — this is the stream/direct-path backstop).
            self._serving.invalidate_slots(algo, slots)
        if self._lid_known.get(algo) is None:
            # No resident-lid tracking for this algo: nothing to
            # invalidate, so don't serialize against digest dispatches.
            (self.engine.sw_clear if algo == "sw"
             else self.engine.tb_clear)(list(slots))
            return
        with self._lid_locks[algo]:
            (self.engine.sw_clear if algo == "sw"
             else self.engine.tb_clear)(list(slots))
            known = self._lid_known.get(algo)
            if known is not None:
                known[np.asarray(slots, dtype=np.int64)] = False

    def _record_dispatch(self, algo: str, n: int, allowed: int,
                         dt_us: float, path: str = "micro",
                         lid=None, **extra) -> None:
        """Latency histogram + enriched decision trace + SLO anomaly
        hook for a completed dispatch.  ``path`` names the dispatch
        route (micro / relay|digest / flat / sharded|...);
        ``extra`` carries enrichments like the shard id.  ``lid`` (a
        single-tenant dispatch's limiter id) feeds the per-tenant usage
        ring; mixed-tenant micro batches feed it from their drainer
        instead."""
        if not self._obs:
            return
        self._latency.record_us(dt_us)
        if lid is not None and self.telemetry is not None:
            self.telemetry.note_server(int(lid), n, allowed)
        lin = self.lineage
        if (lin is not None and lin.sample_n > 0 and path != "micro"):
            # Stream chunks: mint one trace id per dispatch; a sampled
            # one records its shard/path hop and enriches the trace
            # entry — the per-shard-lane leg of the lineage.
            from ratelimiter_tpu.observability.telemetry import (
                mint_trace_id,
                trace_hex,
            )

            tid = mint_trace_id()
            if lin.sampled(tid):
                lin.record(tid, "shard", path=path,
                           shard=extra.get("shard", 0), algo=algo,
                           batch=n, device_us=round(dt_us, 1))
                extra = dict(extra, trace=trace_hex(tid))
        self.trace.record(algo, n, allowed, dt_us, path=path, **extra)
        rec = self._recorder
        if rec is not None and rec.slo_us > 0.0 and dt_us > rec.slo_us:
            rec.anomaly("slow_dispatch", dt_us,
                        algo=algo, batch=n, path=path, **extra)

    def _record_index_phases(self, index) -> bool:
        """Feed the ``index_route``, ``index_merge`` and ``index_sort``
        timers from the partitioned index's last call on this thread
        (the thread that just walked); a single index has no such
        passes.  Returns whether that call slot-sorted its uniques."""
        phases = getattr(index, "last_phase_s", None)
        if phases is None:
            return False
        route_s, merge_s, sort_s = phases()
        if self._stage_timers is not None:
            self._stage_timers["index_route"].record_us(route_s * 1e6)
            self._stage_timers["index_merge"].record_us(merge_s * 1e6)
            if sort_s is not None:
                self._stage_timers["index_sort"].record_us(sort_s * 1e6)
        return sort_s is not None

    def _span(self, stage: str, chunk: int | None = None,
              shard: int | None = None) -> _Span:
        """The span of one stream stage, ``ratelimiter.stream.<stage>``
        (ARCHITECTURE §13a), feeding the stage's timer where it has one
        (no timer with observability off)."""
        t = self._stage_timers
        return _Span(f"ratelimiter.stream.{stage}",
                     None if t is None else t.get(stage), chunk, shard)

    def _stream_rec(self, path: str, **fields):
        """One optional per-chunk instrumentation record: appends to
        ``stream_stats`` (None = off) and returns the dict so the caller
        can keep enriching it as the chunk progresses.  Floats round to
        us precision; the single choke point for what used to be four
        copy-pasted append blocks."""
        if self.stream_stats is None:
            return None
        rec = {"path": path}
        for k, v in fields.items():
            rec[k] = round(v, 6) if isinstance(v, float) else v
        self.stream_stats.append(rec)
        return rec

    # ------------------------------------------------------------------------
    # Checkpoint / resume (engine/checkpoint.py; SURVEY.md §5.4)
    # ------------------------------------------------------------------------
    def save_checkpoint(self, path: str) -> None:
        """Flush pending work and snapshot device state + key->slot maps."""
        from ratelimiter_tpu.engine import checkpoint as ckpt

        self._batcher.flush()
        self.engine.block_until_ready()
        ckpt.save_checkpoint(path, self.engine, ckpt.dump_slot_indexes(self))

    def restore_checkpoint(self, path: str) -> None:
        from ratelimiter_tpu.engine import checkpoint as ckpt

        data = ckpt.load_checkpoint(path)
        self._batcher.flush()
        ckpt.restore_engine_state(self.engine, data)
        ckpt.restore_slot_indexes(self, data["meta"]["index"])
        # The device lid map is not checkpointed: forget what the device
        # "knows" so the next digest-multi dispatch re-uploads lids.
        self._lid_known.clear()

    def promote_from_replica(self, index_dump: Dict) -> None:
        """Failover promotion hook (replication/standby.py).

        The standby's engine already holds the replicated rows; what it
        lacks is ADDRESSING — its key->slot indexes are empty so no
        traffic could route into half-replicated state.  Promotion
        rebuilds the indexes from the last replicated journal frame
        (native fingerprint dumps restore at native speed, exactly as
        checkpoint restore does) and clears the host's resident-lid
        mirror — the shadow device's lid map was never populated, so
        the first digest-multi dispatch must re-upload tenant ids.
        After this returns the storage serves decisions bit-identical
        to the oracle for every key at or before the replicated epoch.

        A decision racing the restore must never see a half-applied
        index (it could assign a fresh slot that collides with another
        key's replicated row): the promotion window REFUSES decisions
        with the typed, retryable ``PromotionInProgressError`` — the
        window is one index restore, microseconds to low milliseconds.
        """
        from ratelimiter_tpu.engine import checkpoint as ckpt

        self._promoting = True
        try:
            self._batcher.flush()
            if self._serving is not None:
                # Every adopted snapshot predates the index swap.
                self._serving.invalidate_all()
            ckpt.restore_slot_indexes(self, index_dump)
            self._lid_known.clear()
            self.engine.block_until_ready()
        finally:
            self._promoting = False

    # ------------------------------------------------------------------------
    # Fencing (replication/orchestrator.py)
    # ------------------------------------------------------------------------
    def fence(self, epoch: int, shards=None) -> int:
        """Install a fence at a monotonic ``epoch``: this storage (or the
        named ``shards`` of its sharded engine) refuses every further
        decision with the typed :class:`FencedError`.

        Failover calls this on the storage being REPLACED before its
        standby is promoted, so a zombie primary — declared dead on a
        false positive but actually still running — cannot keep admitting
        traffic in parallel with the replacement.  The epoch must strictly
        exceed the last installed one (a stale orchestrator instance
        replaying an old fence must not regress a newer decision); a
        non-monotonic epoch raises ``ValueError`` and changes nothing.
        """
        epoch = int(epoch)
        if epoch <= self._fence_epoch:
            raise ValueError(
                f"fence epoch {epoch} is not past the installed epoch "
                f"{self._fence_epoch}; fencing is monotonic")
        self._fence_epoch = epoch
        if shards is None:
            self._fence_all = True
            self._full_fence_epoch = epoch
            # An explicit fence supersedes the serving lease: the lease
            # expiry check is moot once every decision is refused.
            self._lease_deadline_ms = 0
        else:
            self._fenced_shards = self._fenced_shards | frozenset(
                int(q) for q in shards)
            for q in shards:
                self._shard_fence_epochs[int(q)] = epoch
        if self._recorder is not None:
            self._recorder.record(
                "fence.installed", epoch=epoch,
                shards=(sorted(self._fenced_shards) if shards is not None
                        else "all"))
        return epoch

    def lift_fence(self, epoch: int, shards=None) -> None:
        """Lift the fence (operator action after the false-dead primary is
        verified quiesced).  ``epoch`` must be at or past the installed
        fence epoch — a stale lift is refused the same way a stale fence
        is."""
        if int(epoch) < self._fence_epoch:
            raise ValueError(
                f"lift epoch {epoch} is behind the installed fence epoch "
                f"{self._fence_epoch}")
        if shards is None:
            self._fence_all = False
            self._fenced_shards = frozenset()
            # Operator re-arm: a lift also clears a lease self-fence (the
            # operator verified no replacement owns this keyspace); the
            # next orchestrator grant re-installs the lease.
            self.lease_self_fenced = False
        else:
            self._fenced_shards = self._fenced_shards - frozenset(
                int(q) for q in shards)
        if self._recorder is not None:
            self._recorder.record("fence.lifted", epoch=int(epoch))

    def fence_info(self) -> Dict:
        # The epoch reported here stamps token leases (leases/manager.py)
        # — it must cover the SERVING-lease epoch too, so a client lease
        # granted under generation E is revoked after a promotion hands
        # the keyspace to a replacement carrying E+1.
        return {"epoch": max(self._fence_epoch, self._lease_epoch),
                "all": self._fence_all,
                "shards": sorted(self._fenced_shards),
                "shard_epochs": dict(self._shard_fence_epochs),
                "rejected": self.fence_rejected}

    def lease_scope_epoch(self, lid: int, key) -> int:
        """The revocation epoch a token lease on ``(lid, key)`` must be
        checked against (leases/manager.py).  For an unsharded engine
        this is the global ``fence_info()`` epoch — identical semantics
        to before scoping existed.  For a sharded engine, a scoped fence
        (single-shard promotion) only advances the epoch of keys that
        ROUTE to the fenced shard, so survivors renew without a bounce
        and failover cost is O(affected aggregators), not O(clients)."""
        n_sh = getattr(self.engine, "n_shards", None)
        if n_sh is None:
            return max(self._fence_epoch, self._lease_epoch)
        base = max(self._full_fence_epoch, self._lease_epoch)
        if not self._shard_fence_epochs:
            return base
        from ratelimiter_tpu.parallel.sharded import shard_of_key

        q = shard_of_key((int(lid), key), int(n_sh))
        return max(base, self._shard_fence_epochs.get(int(q), 0))

    # ------------------------------------------------------------------------
    # Serving lease: the distributed fence (replication/control.py)
    # ------------------------------------------------------------------------
    def grant_serving_lease(self, epoch: int, ttl_ms: float) -> Dict:
        """Install or renew the serving lease: this storage may decide
        until ``ttl_ms`` from NOW (its own clock — the grant is relative,
        so orchestrator/primary wall clocks need not be synchronized).

        ``epoch`` is the fence generation the grant belongs to; a grant
        must never regress it (a stale orchestrator instance replaying
        an old generation cannot extend a zombie), and a grant can never
        resurrect a fenced storage — once ``fence()`` ran or the lease
        expired, only the operator ``lift_fence`` path re-arms serving.
        """
        epoch = int(epoch)
        if self._fence_all:
            raise ValueError(
                "storage is fenced; a serving lease cannot resurrect it "
                "(operator lift_fence first)")
        if epoch < self._lease_epoch:
            raise ValueError(
                f"serving-lease epoch {epoch} is behind the installed "
                f"epoch {self._lease_epoch}; grants are monotonic")
        self._lease_epoch = epoch
        self._lease_deadline_ms = int(self._clock_ms()) + int(ttl_ms)
        return self.serving_lease_info()

    def release_serving_lease(self) -> Dict:
        """Voluntarily drop the serving lease (graceful stop — the
        SIGTERM/drain path in ``replication/hostproc.py``).  NOT a
        fence: the storage simply stops claiming the keyspace, so the
        orchestrator reads a clean hand-back (``installed: False``)
        instead of a TTL runout, and a later ``grant_serving_lease`` at
        the same-or-newer epoch re-arms serving without an operator
        ``lift_fence``.  Distinguishes "stopped on purpose" from the
        self-fenced zombie the expiry path produces."""
        self._lease_deadline_ms = 0
        if self._recorder is not None:
            self._recorder.record("lease.released",
                                  epoch=self._lease_epoch)
        return self.serving_lease_info()

    def serving_lease_info(self) -> Dict:
        now = int(self._clock_ms())
        installed = bool(self._lease_deadline_ms)
        return {
            "epoch": self._lease_epoch,
            "installed": installed,
            "ttl_remaining_ms": (max(self._lease_deadline_ms - now, 0)
                                 if installed else 0),
            "expired": bool(installed and now >= self._lease_deadline_ms),
            "self_fenced": self.lease_self_fenced,
        }

    def _lease_expired_fence(self) -> None:
        """The serving lease ran out: self-fence.  The orchestrator that
        granted it is either dead or partitioned from us AND from the
        standby relay — either way a replacement may be serving, and the
        decisions we would admit past this point are exactly the
        unbounded half of the split-brain.  Everything admitted BEFORE
        this point is the documented over-admission window: at most one
        lease TTL of traffic, per key at most ``max_permits`` per window
        (the storage/degraded.py bound)."""
        self._fence_all = True
        self._fence_epoch = max(self._fence_epoch, self._lease_epoch)
        self._full_fence_epoch = max(self._full_fence_epoch,
                                     self._fence_epoch)
        self._lease_deadline_ms = 0
        self.lease_self_fenced = True
        if self._recorder is not None:
            self._recorder.record("fence.lease_expired",
                                  epoch=self._lease_epoch)
        self._fence_reject("serving lease expired; orchestrator "
                           "unreachable — a replacement may own this "
                           "keyspace")

    def _fence_reject(self, detail: str):
        self.fence_rejected += 1
        from ratelimiter_tpu.storage.errors import FencedError

        raise FencedError(
            f"storage fenced at epoch {self._fence_epoch} ({detail}): a "
            "failover replacement owns this keyspace; this instance must "
            "not decide")

    def _check_fence_int_keys(self, key_ids) -> None:
        """Shard-scoped fence check for int-key batch/stream paths (only
        reached when a shard fence is installed)."""
        n_sh = getattr(self.engine, "n_shards", None)
        if n_sh is None:
            return
        from ratelimiter_tpu.parallel.sharded import shard_of_int_keys

        shards = shard_of_int_keys(
            np.ascontiguousarray(key_ids, dtype=np.int64), int(n_sh))
        hit = sorted(q for q in self._fenced_shards if (shards == q).any())
        if hit:
            self._fence_reject(f"request routes to fenced shard(s) {hit}")

    def _check_fence_keys(self, lid_per_req, keys) -> None:
        """Shard-scoped fence check for string-key batch paths."""
        n_sh = getattr(self.engine, "n_shards", None)
        if n_sh is None:
            return
        from ratelimiter_tpu.parallel.sharded import shard_of_key

        for lid, key in zip(lid_per_req, keys):
            q = shard_of_key((int(lid), key), int(n_sh))
            if q in self._fenced_shards:
                self._fence_reject(
                    f"key routes to fenced shard {q}")

    def export_keys(self) -> Dict:
        """Geometry-free export of all live per-key state (the rebalance
        counterpart to checkpoints; engine/checkpoint.py:export_keys —
        which flushes pending traffic itself)."""
        from ratelimiter_tpu.engine import checkpoint as ckpt

        return ckpt.export_keys(self)

    def import_keys(self, dump: Dict) -> None:
        """Import an export into THIS storage's geometry (slots assigned by
        this storage's own index/shard hash — this is the rebalance)."""
        from ratelimiter_tpu.engine import checkpoint as ckpt

        self._batcher.flush()
        ckpt.import_keys(self, dump)
        self._lid_known.clear()  # imported slots carry unknown lids

    # ------------------------------------------------------------------------
    # Legacy 10-method contract (host-side, embedded InMemoryStorage)
    # ------------------------------------------------------------------------
    def increment_and_expire(self, key: str, ttl_ms: int) -> int:
        return self._host.increment_and_expire(key, ttl_ms)

    def get(self, key: str) -> int:
        return self._host.get(key)

    def set(self, key: str, value: int, ttl_ms: int) -> None:
        self._host.set(key, value, ttl_ms)

    def compare_and_set(self, key: str, expect: int, update: int) -> bool:
        return self._host.compare_and_set(key, expect, update)

    def delete(self, key: str) -> None:
        self._host.delete(key)

    def z_add(self, key: str, score: float, member: str) -> None:
        self._host.z_add(key, score, member)

    def z_remove_range_by_score(self, key: str, min_score: float, max_score: float) -> int:
        return self._host.z_remove_range_by_score(key, min_score, max_score)

    def z_count(self, key: str, min_score: float, max_score: float) -> int:
        return self._host.z_count(key, min_score, max_score)

    def eval_script(self, script: str, keys: List[str], args: List[int]):
        return self._host.eval_script(script, keys, args)

    def is_available(self) -> bool:
        """Health check: a trivial device round-trip must succeed."""
        try:
            self.engine.block_until_ready()
            return True
        except Exception:  # noqa: BLE001
            return False

    def close(self) -> None:
        self._batcher.close()
        for attr in ("_shard_pool_obj", "_assign_pool_obj",
                     "_drain_pool_obj"):
            pool = getattr(self, attr, None)
            if pool is not None:
                pool.shutdown(wait=False)
        for lane in getattr(self, "_shard_lanes_obj", None) or ():
            lane.close()
        for index in self._index.values():
            if hasattr(index, "close"):
                index.close()

    def _abort_prefetch(self, algo, index, fut, slots_of) -> None:
        """Consume an ORPHANED prefetched assignment (an exception escaped
        before the main loop took it): the index already applied it — its
        evicted slots map to new keys and must be cleared on device
        before any reuse, exactly as the in-loop path clears them — and
        its held pins must be released.  ``slots_of(result)`` extracts
        the pinned slot array from the assign result (whose last element
        is always the clears list)."""
        try:
            res = fut.result()
        except Exception:  # noqa: BLE001 — failed assign holds nothing
            return
        try:
            clears = res[-1]
            if len(clears):
                self._clear_slots(algo, list(clears))
        finally:
            slots = slots_of(res)
            if slots is not None and len(slots):
                self._unpin_held(index, [slots])

    def _assign_pool(self):
        """One-worker pool that prefetches the NEXT chunk's slot
        assignment while the main thread blocks in a device fetch (the
        fetch wait releases the GIL and the C walk releases it too, so
        on any host the assign rides in the fetch shadow)."""
        pool = getattr(self, "_assign_pool_obj", None)
        if pool is None:
            import concurrent.futures as cf

            pool = cf.ThreadPoolExecutor(1, thread_name_prefix="assignpf")
            self._assign_pool_obj = pool
        return pool

    def _drain_pool(self):
        """Drain workers: device fetches block here CONCURRENTLY so
        their per-fetch round trips overlap (see _DrainSet).  The fetch
        wait sleeps in the runtime, so these threads cost no CPU beyond
        the drains' own numpy post-processing."""
        pool = getattr(self, "_drain_pool_obj", None)
        if pool is None:
            import concurrent.futures as cf

            pool = cf.ThreadPoolExecutor(_DRAIN_WORKERS,
                                         thread_name_prefix="drain")
            self._drain_pool_obj = pool
        return pool

    def _shard_pool(self, n_sh: int):
        """Thread pool for per-shard C index calls (lazily created),
        sized to the SMALLER of shard count and usable cores (r8): the
        calls release the GIL, so real cores overlap them, but
        oversubscribing one core with n_sh walk threads only buys
        scheduler churn and inflated per-walk walls (the r05 run, before PR 1,
        8-shard assign_s pathology)."""
        pool = getattr(self, "_shard_pool_obj", None)
        if pool is None:
            import concurrent.futures as cf

            try:
                cores = len(os.sched_getaffinity(0))
            except (AttributeError, OSError):  # pragma: no cover
                cores = os.cpu_count() or 1
            pool = cf.ThreadPoolExecutor(max(1, min(n_sh, cores)),
                                         thread_name_prefix="shardidx")
            self._shard_pool_obj = pool
        return pool

    # ------------------------------------------------------------------------
    def _check_not_promoting(self) -> None:
        """Refuse decisions while a standby promotion is swapping the
        key->slot indexes, and refuse them FOREVER once this storage is
        whole-fenced (two attribute checks on the hot path; see
        :meth:`promote_from_replica` and :meth:`fence`).  With a serving
        lease installed (cross-host topology) this is also where expiry
        bites: the first decision past the lease deadline self-fences —
        every dispatch surface funnels through here, so a partitioned
        zombie's in-flight dispatches lose the race within one check."""
        if self._fence_all:
            self._fence_reject("whole-storage fence")
        if self._lease_deadline_ms \
                and int(self._clock_ms()) >= self._lease_deadline_ms:
            self._lease_expired_fence()
        if self._promoting:
            from ratelimiter_tpu.storage.errors import (
                PromotionInProgressError,
            )

            raise PromotionInProgressError(
                "standby promotion in progress: the key->slot index is "
                "being rebuilt; retry after the promotion window")

    def _assign_slot(self, algo: str, lid: int, key: str,
                     hold_pin: bool = False) -> int:
        self._check_not_promoting()
        if self._fenced_shards:
            self._check_fence_keys([lid], [key])
        index = self._index[algo]
        pinned = self._batcher.pending_slots(algo)
        slot, evicted = index.assign((lid, key), pinned=pinned,
                                     hold_pin=hold_pin)
        if evicted is not None:
            if self._serving is not None:
                # Invalidate at REMAP time, not clear time: the evicted
                # key's index entry is already gone, so a hybrid-tier
                # serve from its adopted state would track a key the
                # device is about to forget.
                self._serving.invalidate_slots(algo, [evicted])
            self._batcher.add_clear(algo, evicted)
        return slot


def sharded_storage(num_slots: int, devices, table: LimiterTable | None = None,
                    **storage_kw) -> TpuBatchedStorage:
    """The sharded deployment's storage: a :class:`TpuBatchedStorage`
    over a :class:`ShardedDeviceEngine` with one shard per device of
    ``devices`` (a 1-D mesh in their order; shard ``s``'s state lives on
    ``devices[s]``).  A table of ``num_slots`` slots in all is cut into
    ``align_slots(ceil(num_slots / len(devices)))`` slots per shard,
    whole tiles of the tile sweep, so every shard's digest step can take
    the sorted row write.  ``storage_kw`` go to the storage.  The one
    factory of a sharded storage: the service, the chip smoke and the
    benchmark build it here; a storage built without an engine never
    shards."""
    from ratelimiter_tpu.ops.pallas.block_scatter import align_slots
    from ratelimiter_tpu.parallel import ShardedDeviceEngine, make_mesh

    devices = list(devices)
    engine = ShardedDeviceEngine(
        slots_per_shard=align_slots(-(-int(num_slots) // len(devices))),
        table=table if table is not None else LimiterTable(),
        mesh=make_mesh(devices))
    return TpuBatchedStorage(engine=engine, **storage_kw)
