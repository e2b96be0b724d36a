"""Single-device decision engine.

Owns the device-resident slot state for both algorithms, the jitted step
functions (donated state buffers — updates happen in place in HBM), and the
batch padding discipline (power-of-two buckets so XLA compiles a handful of
shapes, then every flush hits the cache).

This is the device half of ``TpuBatchedStorage``; the host half (key->slot
index + micro-batcher) lives in engine/slots.py and engine/batcher.py.
"""

from __future__ import annotations

import functools
import threading
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ratelimiter_tpu.engine.state import LimiterTable, SWState, TBState
from ratelimiter_tpu.ops.flat import sw_flat_bits, tb_flat_bits
from ratelimiter_tpu.ops.relay import (
    sw_relay_bits,
    sw_relay_counts,
    tb_relay_bits,
    tb_relay_counts,
)
from ratelimiter_tpu.ops.packed import (
    decode_sw_fused,
    decode_tb_fused,
    sw_scan_bits,
    sw_step_fused,
    tb_scan_bits,
    tb_step_fused,
)
from ratelimiter_tpu.ops.sliding_window import (
    make_sw_packed,
    sw_pack_state,
    sw_peek_p,
    sw_reset_p,
    sw_unpack_state,
)
from ratelimiter_tpu.ops.token_bucket import (
    make_tb_packed,
    tb_pack_state,
    tb_peek_p,
    tb_reset_p,
    tb_unpack_state,
)

_MIN_BATCH = 256
# Micro-batch floor (r6): interactive traffic through the micro-batcher
# produces 1-100-request batches, and padding them to 256 lanes made the
# device step ~0.7 ms on the CPU backend — most of the local-SLO p50 miss
# (r05, before PR 1, latency_slo_local: p50 1558 us vs the 1000 us target).
# Small batches now bucket at {32, 64, 128} before joining the pow2
# ladder; three extra compile shapes, device step cost proportional to
# lanes.  Streams never see these shapes (their chunks are >= 2^19).
_MICRO_FLOOR = 32

# Staged micro-batch layout (r11): one i64[4, B] host buffer carries the
# whole batch — row 0 slots (pad -1), row 1 limiter ids (pad 0), row 2
# permits (pad 1), row 3 lane 0 the batch timestamp.  One device_put per
# dispatch instead of four: on the CPU backend each small-array put costs
# ~50-70 us of runtime overhead regardless of size, and four of them were
# most of the 0.88 ms assembly stage the latency SLO missed on.
MICRO_STAGE_ROWS = 4


def _sw_micro_step_combined(state, tarrs, staged):
    return sw_step_fused(state, tarrs,
                         staged[0].astype(jnp.int32),
                         staged[1].astype(jnp.int32),
                         staged[2], staged[3, 0])


def _tb_micro_step_combined(state, tarrs, staged):
    return tb_step_fused(state, tarrs,
                         staged[0].astype(jnp.int32),
                         staged[1].astype(jnp.int32),
                         staged[2], staged[3, 0])


# Module-level jitted singletons, NOT per-engine closures: jax's tracing
# and executable caches key on the underlying function identity, so every
# DeviceEngine in a process shares one compile per (algo, bucket, table
# shape) — a per-engine closure would re-trace (~0.3 s) and possibly
# re-compile on every storage construction.
_MICRO_STEPS = {
    "sw": jax.jit(_sw_micro_step_combined, donate_argnums=0),
    "tb": jax.jit(_tb_micro_step_combined, donate_argnums=0),
}


def _step(base, **static):
    """``base`` with its static arguments bound, under ``base``'s name:
    ``jax.jit`` names a program after its callable's ``__name__``, which
    a bare ``functools.partial`` lacks, so a device trace would show
    every such step as ``jit__unknown``."""
    step = functools.partial(base, **static)
    step.__name__ = base.__name__
    return step


def _bucket_size(n: int) -> int:
    size = _MICRO_FLOOR
    while size < n:
        size *= 2
    return size


def _pad_i32(x: np.ndarray, size: int, fill: int) -> jnp.ndarray:
    out = np.full(size, fill, dtype=np.int32)
    out[: len(x)] = x
    return jnp.asarray(out)


def _pad_i64(x: np.ndarray, size: int, fill: int) -> jnp.ndarray:
    out = np.full(size, fill, dtype=np.int64)
    out[: len(x)] = x
    return jnp.asarray(out)


class DeviceEngine:
    """Batched decision engine over TPU-resident counter arrays."""

    # Replication (replication/log.py) works at this engine's packed-row
    # granularity; the sharded engine partitions state differently and is
    # not journaled yet.
    supports_replication = True

    def __init__(self, num_slots: int, table: LimiterTable):
        self.num_slots = int(num_slots)
        self.table = table
        # Optional dirty-slot journal (engine/state.py:SlotJournal): when
        # attached, every mutation path marks the slots it touches before
        # dispatching, so a replication log can ship per-epoch deltas.
        # None (the default) keeps the hot path at one attribute check.
        self.journal = None
        # The step functions donate the state buffers (in-place HBM updates),
        # so every access — including read-only peeks, which must not grab a
        # reference that a concurrent step is about to invalidate — is
        # serialized through this lock.
        self._lock = threading.RLock()
        # State lives packed (i32 lanes — see ops/{sliding_window,token_bucket})
        # for gather/scatter speed; the sw_state/tb_state properties expose the
        # i64 field view for checkpointing and inspection.
        self.sw_packed = make_sw_packed(self.num_slots)
        self.tb_packed = make_tb_packed(self.num_slots)
        # Fused steps return all outputs in one array — one D2H transfer per
        # batch instead of four (the transfer-latency fix; ops/packed.py).
        # The micro path runs them through the COMBINED staged form
        # (_micro_step: one i64[4, B] upload carries slots/lids/permits/
        # now) so the list and staged dispatch surfaces share one
        # compiled executable per (algo, bucket).
        self._sw_scan = jax.jit(sw_scan_bits, donate_argnums=0)
        self._tb_scan = jax.jit(tb_scan_bits, donate_argnums=0)
        self._sw_flat = jax.jit(sw_flat_bits, donate_argnums=0)
        self._tb_flat = jax.jit(tb_flat_bits, donate_argnums=0)
        # Relay word layout (ops/relay.py): slot_bits must cover num_slots
        # with the all-ones padding word left over; the remaining bits of
        # the uint32 carry the duplicate rank + last flag.
        self.slot_bits = max(int(self.num_slots).bit_length(), 1)
        self.rank_bits = 31 - self.slot_bits
        self._sw_relay = jax.jit(_step(
            sw_relay_bits, rank_bits=self.rank_bits), donate_argnums=0)
        self._tb_relay = jax.jit(_step(
            tb_relay_bits, rank_bits=self.rank_bits), donate_argnums=0)
        self._relay_counts = {}  # (algo, out_dtype name, sorted) -> jitted step
        self._relay_weighted = {}  # (algo, r_steps) -> jitted weighted step
        # Largest per-request permits the weighted relay carries (uint8
        # CSR permits lane); larger permits take the sorted flat path.
        self.weighted_permit_cap = 255
        # Resident tenant-id map per algo (ops/relay.py:*_relay_counts_
        # resident): one slot = one (limiter, key), so a slot's lid is
        # immutable while assigned; the digest-multi path uploads only
        # the deltas and reads policies from this array.
        self.sw_lid_map = jnp.zeros(self.num_slots, dtype=jnp.int32)
        self.tb_lid_map = jnp.zeros(self.num_slots, dtype=jnp.int32)
        self._relay_resident = {}  # (algo, out_dtype name, sorted) -> jitted step
        self._sw_peek = jax.jit(sw_peek_p)
        self._tb_peek = jax.jit(tb_peek_p)
        # Settle the Pallas probes NOW, before any step kernel compiles,
        # so a probe never compiles nested inside another program's
        # lowering, and a probe failure on a TPU raises here, at init.
        from ratelimiter_tpu.ops import pallas as pallas_kernels

        pallas_kernels.settle_all()
        self._sw_reset = jax.jit(sw_reset_p, donate_argnums=0)
        self._tb_reset = jax.jit(tb_reset_p, donate_argnums=0)

    # -- dirty-slot journal hooks (replication) --------------------------------
    # Each hook takes the HOST lane array plus (optionally) the same
    # array already converted for the dispatch: a device journal
    # (engine/state.py:DeviceSlotJournal) marks from the device copy —
    # zero extra host work or upload — while the host journal keeps its
    # numpy path (handing it a device array would force a sync fetch).
    def _mark(self, algo: str, slots, dev=None) -> None:
        j = self.journal
        if j is not None:
            j.mark(algo, dev if dev is not None
                   and getattr(j, "device", False) else slots)

    def _mark_words(self, algo: str, words, dev=None) -> None:
        """Mark from relay uwords (slot in the high bits; padding words
        decode past num_slots and are filtered by the journal)."""
        j = self.journal
        if j is not None:
            j.mark_words(algo, dev if dev is not None
                         and getattr(j, "device", False) else words,
                         self.rank_bits)

    # -- i64 field view (checkpoint/compat) ------------------------------------
    @property
    def sw_state(self) -> SWState:
        return sw_unpack_state(self.sw_packed)

    @sw_state.setter
    def sw_state(self, state: SWState) -> None:
        if self.journal is not None:
            self.journal.mark_all("sw")
        self.sw_packed = sw_pack_state(
            SWState(*(jnp.asarray(f) for f in state)))

    @property
    def tb_state(self) -> TBState:
        return tb_unpack_state(self.tb_packed)

    @tb_state.setter
    def tb_state(self, state: TBState) -> None:
        if self.journal is not None:
            self.journal.mark_all("tb")
        self.tb_packed = tb_pack_state(
            TBState(*(jnp.asarray(f) for f in state)))

    # -- acquire --------------------------------------------------------------
    # Each step is split into DISPATCH (enqueue on device, state updated,
    # returns a lazy output handle — engine lock held only here) and DRAIN
    # (the blocking device->host fetch + decode, outside the lock).  The
    # split is what lets the micro-batcher keep several batches in flight:
    # the next dispatch runs while previous fetches are still on the wire.

    def _acquire_dispatch(self, algo: str, slots, limiter_ids, permits,
                          now_ms: int):
        """List-surface dispatch: stage the batch into a combined buffer
        and run the same staged step the micro-batcher's flusher uses —
        one upload, one cached executable per (algo, bucket)."""
        n = len(slots)
        size = _bucket_size(n)
        staged = np.empty((MICRO_STAGE_ROWS, size), dtype=np.int64)
        staged[0] = -1
        staged[1] = 0
        staged[2] = 1
        staged[0, :n] = np.asarray(slots, dtype=np.int64)
        staged[1, :n] = np.asarray(limiter_ids, dtype=np.int64)
        staged[2, :n] = np.asarray(permits, dtype=np.int64)
        staged[3, 0] = now_ms
        return self.micro_staged_dispatch(algo, staged, n)

    def sw_acquire_dispatch(self, slots, limiter_ids, permits, now_ms: int):
        """Dispatch a sliding-window batch; returns a lazy fused handle
        (pass to :meth:`sw_acquire_drain` with the batch length)."""
        return self._acquire_dispatch("sw", slots, limiter_ids, permits,
                                      now_ms)

    @staticmethod
    def sw_acquire_drain(handle, n: int):
        return decode_sw_fused(np.asarray(handle)[:, :n])

    def sw_acquire(self, slots, limiter_ids, permits, now_ms: int):
        """Batched sliding-window tryAcquire. Returns dict of numpy arrays
        (allowed, mutated, observed, cache_value), trimmed to the input size."""
        handle = self.sw_acquire_dispatch(slots, limiter_ids, permits, now_ms)
        return self.sw_acquire_drain(handle, len(slots))

    def tb_acquire_dispatch(self, slots, limiter_ids, permits, now_ms: int):
        return self._acquire_dispatch("tb", slots, limiter_ids, permits,
                                      now_ms)

    @staticmethod
    def tb_acquire_drain(handle, n: int):
        return decode_tb_fused(np.asarray(handle)[:, :n])

    def tb_acquire(self, slots, limiter_ids, permits, now_ms: int):
        handle = self.tb_acquire_dispatch(slots, limiter_ids, permits, now_ms)
        return self.tb_acquire_drain(handle, len(slots))

    # -- staged micro-batch dispatch (double-buffered assembly, r11) ----------
    # The micro-batcher packs requests into an i64[4, cap] staging buffer
    # AT SUBMIT TIME (engine/batcher.py:_Pending), so by flush time the
    # batch is already laid out and dispatch is one upload + one cached
    # jit call.  Layout: MICRO_STAGE_ROWS doc at the top of this module.

    def micro_staged_dispatch(self, algo: str, staged: np.ndarray, n: int):
        """Dispatch a pre-staged micro-batch: ``staged`` is the combined
        i64[4, cap] host buffer (cap a pow2 >= _MICRO_FLOOR, padding lanes
        already holding their fill values, timestamp at [3, 0]); ``n`` is
        the live lane count.  Returns the lazy fused handle for
        :meth:`micro_staged_drain`.  The device copy happens outside the
        engine lock so a staged upload overlaps a concurrent dispatch."""
        size = _bucket_size(n)
        if size != staged.shape[1]:
            staged = np.ascontiguousarray(staged[:, :size])
        self._mark(algo, staged[0, :n])
        step = _MICRO_STEPS[algo]
        # The staged numpy buffer goes to the jit call DIRECTLY (~30 us
        # vs ~100 us via an explicit device_put first — the §6b
        # committed-array trap).  On CPU the call may ALIAS the host
        # memory zero-copy: the caller must not mutate the buffer until
        # the batch's results were fetched (the batcher recycles staging
        # buffers at drain time for exactly this reason).
        with self._lock:
            if algo == "sw":
                self.sw_packed, packed = step(
                    self.sw_packed, self.table.device_arrays, staged)
            else:
                self.tb_packed, packed = step(
                    self.tb_packed, self.table.device_arrays, staged)
        return packed

    @staticmethod
    def micro_staged_drain(algo: str, handle, n: int):
        decode = decode_sw_fused if algo == "sw" else decode_tb_fused
        return decode(np.asarray(handle)[:, :n])

    @staticmethod
    def micro_compile_count() -> int:
        """Number of compiled micro-step signatures (staged path,
        process-wide — the steps are module-level singletons), for the
        no-recompile steady-state assertion in bench/device_only.py."""
        return sum(fn._cache_size() for fn in _MICRO_STEPS.values())

    # -- scan dispatch (K sub-batches, bit-packed decisions) -------------------
    # The hyperscale streaming path: one device dispatch for K*B decisions,
    # returning a lazy uint8[K, ceil(B/8)] handle — the caller fetches it
    # (np.asarray) outside the lock, overlapping the next dispatch.

    def sw_scan_dispatch(self, slots_kb, lids, permits_kb, now_k):
        return self._scan_dispatch("sw", slots_kb, lids, permits_kb, now_k)

    def tb_scan_dispatch(self, slots_kb, lids, permits_kb, now_k):
        return self._scan_dispatch("tb", slots_kb, lids, permits_kb, now_k)

    def _scan_dispatch(self, algo, slots_kb, lids, permits_kb, now_k):
        slots_host = slots_kb
        slots_kb = jnp.asarray(np.ascontiguousarray(slots_kb, dtype=np.int32))
        self._mark(algo, slots_host, dev=slots_kb)
        if np.ndim(lids) == 0:
            lids = jnp.asarray(np.int32(lids))
        else:
            lids = jnp.asarray(np.ascontiguousarray(lids, dtype=np.int32))
        if permits_kb is not None:
            pdt = (np.uint8 if getattr(permits_kb, "dtype", None) == np.uint8
                   else np.int32)
            permits_kb = jnp.asarray(
                np.ascontiguousarray(permits_kb, dtype=pdt))
        now_k = jnp.asarray(np.ascontiguousarray(now_k, dtype=np.int64))
        with self._lock:
            if algo == "sw":
                self.sw_packed, bits = self._sw_scan(
                    self.sw_packed, self.table.device_arrays,
                    slots_kb, lids, permits_kb, now_k)
            else:
                self.tb_packed, bits = self._tb_scan(
                    self.tb_packed, self.table.device_arrays,
                    slots_kb, lids, permits_kb, now_k)
        return bits

    # -- flat mega-batch dispatch (ops/flat.py) --------------------------------
    # The streaming hot path: one flat sorted batch per dispatch (all
    # requests share the dispatch timestamp), bit-packed decisions back.

    def sw_flat_dispatch(self, slots, lids, permits, now_ms):
        return self._flat_dispatch("sw", slots, lids, permits, now_ms)

    def tb_flat_dispatch(self, slots, lids, permits, now_ms):
        return self._flat_dispatch("tb", slots, lids, permits, now_ms)

    def _flat_dispatch(self, algo, slots, lids, permits, now_ms):
        slots_host = slots
        slots = jnp.asarray(np.ascontiguousarray(slots, dtype=np.int32))
        self._mark(algo, slots_host, dev=slots)
        if np.ndim(lids) == 0:
            lids = jnp.asarray(np.int32(lids))
        else:
            lids = jnp.asarray(np.ascontiguousarray(lids, dtype=np.int32))
        if permits is not None:
            # uint8 lanes (all permits <= 255) ship 4x fewer bytes; the
            # step upcasts to i64 internally either way.
            pdt = (np.uint8 if getattr(permits, "dtype", None) == np.uint8
                   else np.int32)
            permits = jnp.asarray(np.ascontiguousarray(permits, dtype=pdt))
        now = jnp.int64(now_ms)
        with self._lock:
            if algo == "sw":
                self.sw_packed, bits = self._sw_flat(
                    self.sw_packed, self.table.device_arrays,
                    slots, lids, permits, now)
            else:
                self.tb_packed, bits = self._tb_flat(
                    self.tb_packed, self.table.device_arrays,
                    slots, lids, permits, now)
        return bits

    # -- relay dispatch (ops/relay.py) -----------------------------------------
    # The unit-permit streaming hot path: slot + duplicate-rank + last flag
    # packed into one uint32 per request by the host index; the device step
    # is gather + elementwise + masked scatter + packbits (no sort/scan).

    def relay_usable(self) -> bool:
        from ratelimiter_tpu.ops import relay as relay_ops

        return relay_ops.relay_usable(self.rank_bits,
                                      self.table.max_permits_registered)

    def sw_relay_dispatch(self, words, lids, now_ms):
        return self._relay_dispatch("sw", words, lids, now_ms)

    def tb_relay_dispatch(self, words, lids, now_ms):
        return self._relay_dispatch("tb", words, lids, now_ms)

    def _relay_dispatch(self, algo, words, lids, now_ms):
        """words uint32[B] (padding 0xFFFFFFFF); lids scalar or i32[B];
        returns a lazy uint8[B/8] arrival-order allow bitmask handle."""
        words_host = words
        words = jnp.asarray(np.ascontiguousarray(words, dtype=np.uint32))
        self._mark_words(algo, words_host, dev=words)
        if np.ndim(lids) == 0:
            lids = jnp.asarray(np.int32(lids))
        else:
            lids = jnp.asarray(np.ascontiguousarray(lids, dtype=np.int32))
        now = jnp.int64(now_ms)
        with self._lock:
            if algo == "sw":
                self.sw_packed, bits = self._sw_relay(
                    self.sw_packed, self.table.device_arrays, words, lids, now)
            else:
                self.tb_packed, bits = self._tb_relay(
                    self.tb_packed, self.table.device_arrays, words, lids, now)
        return bits

    def counts_dtype(self):
        from ratelimiter_tpu.ops import relay as relay_ops

        return relay_ops.counts_dtype(self.table.max_permits_registered)

    def _relay_fused_ok(self, algo: str, u_padded: int) -> bool:
        """Whether a scalar-lid sorted digest dispatch of ``u_padded``
        lanes takes the fused Pallas relay step (geometry + probe +
        measured election; ops/pallas/relay_step.py)."""
        from ratelimiter_tpu.ops.pallas import relay_step

        shape = (self.sw_packed if algo == "sw" else self.tb_packed).shape
        return relay_step.enabled(shape, u_padded, self.rank_bits)

    # -- weighted relay dispatch (ops/relay.py:*_relay_weighted) ---------------
    def sw_weighted_dispatch(self, uwords, perms_rank, roff, lid,
                             now_ms, r_steps):
        return self._weighted_dispatch("sw", uwords, perms_rank, roff,
                                       lid, now_ms, r_steps)

    def tb_weighted_dispatch(self, uwords, perms_rank, roff, lid,
                             now_ms, r_steps):
        return self._weighted_dispatch("tb", uwords, perms_rank, roff,
                                       lid, now_ms, r_steps)

    def _weighted_dispatch(self, algo, uwords, perms_rank, roff, lid,
                           now_ms, r_steps):
        """uwords uint32[U] (slot | count; padding 0xFFFFFFFF; segments
        in count-descending order), perms_rank uint8[N+U] rank-major
        compacted permits, roff i32[R] per-rank offsets; returns the
        lazy uint8[r_steps, U/8] decision-bit handle (bit [r, j] = r-th
        request of count-sorted segment j)."""
        from ratelimiter_tpu.ops.relay import (
            sw_relay_weighted,
            tb_relay_weighted,
        )

        uwords_host = uwords
        uwords = jnp.asarray(np.ascontiguousarray(uwords, dtype=np.uint32))
        self._mark_words(algo, uwords_host, dev=uwords)
        key = (algo, int(r_steps))
        fn = self._relay_weighted.get(key)
        if fn is None:
            base = sw_relay_weighted if algo == "sw" else tb_relay_weighted
            fn = jax.jit(_step(
                base, rank_bits=self.rank_bits, r_steps=int(r_steps)),
                donate_argnums=0)
            self._relay_weighted[key] = fn
        perms_rank = jnp.asarray(
            np.ascontiguousarray(perms_rank, dtype=np.uint8))
        roff = jnp.asarray(np.ascontiguousarray(roff, dtype=np.int32))
        lid = jnp.asarray(np.int32(lid))
        now = jnp.int64(now_ms)
        with self._lock:
            if algo == "sw":
                self.sw_packed, bits = fn(
                    self.sw_packed, self.table.device_arrays, uwords,
                    perms_rank, roff, lid, now)
            else:
                self.tb_packed, bits = fn(
                    self.tb_packed, self.table.device_arrays, uwords,
                    perms_rank, roff, lid, now)
        return bits

    def sw_weighted_counts_dispatch(self, uwords, wlane, lid, now_ms,
                                    out_dtype):
        return self._weighted_counts_dispatch("sw", uwords, wlane, lid,
                                              now_ms, out_dtype)

    def tb_weighted_counts_dispatch(self, uwords, wlane, lid, now_ms,
                                    out_dtype):
        return self._weighted_counts_dispatch("tb", uwords, wlane, lid,
                                              now_ms, out_dtype)

    def _weighted_counts_dispatch(self, algo, uwords, wlane, lid, now_ms,
                                  out_dtype):
        """Coalesced weighted digest dispatch
        (ops/relay.py:*_relay_weighted_counts): uwords uint32[U] (slot |
        clamped count; padding 0xFFFFFFFF), wlane uint8[U] the segment's
        uniform per-request weight; returns the lazy out_dtype[U]
        per-unique allowed-count handle (the host reconstructs
        ``rank < counts[uidx]``).  Only valid when every repeat of a key
        inside the chunk carries the same weight — the stream loop
        elects this per chunk and falls back to the scan otherwise."""
        from ratelimiter_tpu.ops.relay import (
            sw_relay_weighted_counts,
            tb_relay_weighted_counts,
        )

        uwords_host = uwords
        uwords = jnp.asarray(np.ascontiguousarray(uwords, dtype=np.uint32))
        self._mark_words(algo, uwords_host, dev=uwords)
        jdt = jnp.uint8 if out_dtype == np.uint8 else jnp.uint16
        key = (algo, out_dtype().dtype.name, "wcounts")
        fn = self._relay_counts.get(key)
        if fn is None:
            base = (sw_relay_weighted_counts if algo == "sw"
                    else tb_relay_weighted_counts)
            fn = jax.jit(_step(
                base, rank_bits=self.rank_bits, out_dtype=jdt),
                donate_argnums=0)
            self._relay_counts[key] = fn
        wlane = jnp.asarray(np.ascontiguousarray(wlane, dtype=np.uint8))
        lid = jnp.asarray(np.int32(lid))
        now = jnp.int64(now_ms)
        with self._lock:
            if algo == "sw":
                self.sw_packed, counts = fn(
                    self.sw_packed, self.table.device_arrays, uwords,
                    wlane, lid, now)
            else:
                self.tb_packed, counts = fn(
                    self.tb_packed, self.table.device_arrays, uwords,
                    wlane, lid, now)
        return counts

    def sw_relay_counts_dispatch(self, uwords, lids, now_ms, out_dtype,
                                 slots_sorted=False):
        return self._relay_counts_dispatch("sw", uwords, lids, now_ms,
                                           out_dtype,
                                           slots_sorted=slots_sorted)

    def tb_relay_counts_dispatch(self, uwords, lids, now_ms, out_dtype,
                                 slots_sorted=False):
        return self._relay_counts_dispatch("tb", uwords, lids, now_ms,
                                           out_dtype,
                                           slots_sorted=slots_sorted)

    def sw_relay_counts_resident_dispatch(self, uwords, delta_slots,
                                          delta_lids, now_ms, out_dtype,
                                          slots_sorted=False):
        return self._relay_resident_dispatch("sw", uwords, delta_slots,
                                             delta_lids, now_ms, out_dtype,
                                             slots_sorted=slots_sorted)

    def tb_relay_counts_resident_dispatch(self, uwords, delta_slots,
                                          delta_lids, now_ms, out_dtype,
                                          slots_sorted=False):
        return self._relay_resident_dispatch("tb", uwords, delta_slots,
                                             delta_lids, now_ms, out_dtype,
                                             slots_sorted=slots_sorted)

    def _relay_resident_dispatch(self, algo, uwords, delta_slots, delta_lids,
                                 now_ms, out_dtype, slots_sorted=False):
        """Digest dispatch with device-resident lids: uwords uint32[U];
        delta (slot, lid) i32 pairs for slots whose lid the device doesn't
        know yet (padding slot = -1).  Returns the lazy counts handle."""
        from ratelimiter_tpu.ops.relay import (
            sw_relay_counts_resident,
            tb_relay_counts_resident,
        )

        uwords_host = uwords
        uwords = jnp.asarray(np.ascontiguousarray(uwords, dtype=np.uint32))
        self._mark_words(algo, uwords_host, dev=uwords)

        jdt = jnp.uint8 if out_dtype == np.uint8 else jnp.uint16
        key = (algo, out_dtype().dtype.name, bool(slots_sorted))
        fn = self._relay_resident.get(key)
        if fn is None:
            base = (sw_relay_counts_resident if algo == "sw"
                    else tb_relay_counts_resident)
            fn = jax.jit(_step(
                base, rank_bits=self.rank_bits, out_dtype=jdt,
                slots_sorted=bool(slots_sorted)),
                donate_argnums=(0, 1))
            self._relay_resident[key] = fn
        delta_slots = jnp.asarray(
            np.ascontiguousarray(delta_slots, dtype=np.int32))
        delta_lids = jnp.asarray(
            np.ascontiguousarray(delta_lids, dtype=np.int32))
        now = jnp.int64(now_ms)
        with self._lock:
            if algo == "sw":
                self.sw_packed, self.sw_lid_map, counts = fn(
                    self.sw_packed, self.sw_lid_map,
                    self.table.device_arrays, uwords, delta_slots,
                    delta_lids, now)
            else:
                self.tb_packed, self.tb_lid_map, counts = fn(
                    self.tb_packed, self.tb_lid_map,
                    self.table.device_arrays, uwords, delta_slots,
                    delta_lids, now)
        return counts

    def _relay_counts_dispatch(self, algo, uwords, lids, now_ms, out_dtype,
                               slots_sorted=False):
        """uwords uint32[U] (slot | clamped count; padding 0xFFFFFFFF);
        returns a lazy out_dtype[U] per-unique allowed-count handle.
        ``slots_sorted`` (host sorted the uniques by slot): the step runs
        the FUSED Pallas relay kernel (ops/pallas/relay_step.py — one
        memory-resident gather+update+scatter pass) when the measured
        per-path election picked it on this device, else the composed
        XLA step with the dense presorted block sweep."""
        uwords_host = uwords
        uwords = jnp.asarray(np.ascontiguousarray(uwords, dtype=np.uint32))
        self._mark_words(algo, uwords_host, dev=uwords)
        jdt = jnp.uint8 if out_dtype == np.uint8 else jnp.uint16
        fused = bool(slots_sorted) and np.ndim(lids) == 0 and (
            self._relay_fused_ok(algo, len(uwords)))
        key = (algo, out_dtype().dtype.name,
               "fused" if fused else bool(slots_sorted))
        fn = self._relay_counts.get(key)
        if fn is None:
            if fused:
                from ratelimiter_tpu.ops.pallas import relay_step

                base = (relay_step.sw_relay_counts_fused if algo == "sw"
                        else relay_step.tb_relay_counts_fused)
                fn = jax.jit(_step(
                    base, rank_bits=self.rank_bits, out_dtype=jdt,
                    interpret=relay_step.interpret_mode()),
                    donate_argnums=0)
            else:
                base = sw_relay_counts if algo == "sw" else tb_relay_counts
                fn = jax.jit(_step(
                    base, rank_bits=self.rank_bits, out_dtype=jdt,
                    slots_sorted=bool(slots_sorted)),
                    donate_argnums=0)
            self._relay_counts[key] = fn
        if np.ndim(lids) == 0:
            lids = jnp.asarray(np.int32(lids))
        else:
            lids = jnp.asarray(np.ascontiguousarray(lids, dtype=np.int32))
        now = jnp.int64(now_ms)
        with self._lock:
            if algo == "sw":
                self.sw_packed, counts = fn(
                    self.sw_packed, self.table.device_arrays, uwords, lids,
                    now)
            else:
                self.tb_packed, counts = fn(
                    self.tb_packed, self.table.device_arrays, uwords, lids,
                    now)
        return counts

    # -- lease RESERVE / CREDIT (ops/lease.py; leases/) ------------------------
    # The lease flavor of the decision dispatch: charge (or return) a
    # per-key permit budget in one gather -> roll/refill -> greedy grant
    # -> scatter pass, atomically under the same engine lock every other
    # dispatch serializes through.  Rare by design (one reserve amortizes
    # over a whole client-side budget), so these run synchronously —
    # dispatch + fetch in one call.

    def lease_reserve(self, algo: str, slots, limiter_ids, requested,
                      now_ms: int):
        """Atomically grant up to ``requested[i]`` permits against each
        slot's live counters.  Returns ``(granted i64[n], ws i64[n])``
        where ``ws`` is the window the charge landed in (sliding window;
        zeros for the token bucket) — a later :meth:`lease_credit` must
        present it."""
        from ratelimiter_tpu.ops import lease as lease_ops

        n = len(slots)
        size = _bucket_size(n)
        self._mark(algo, np.asarray(slots))
        step = lease_ops.RESERVE_STEPS[algo]
        slots_p = _pad_i32(np.asarray(slots, dtype=np.int32), size, -1)
        lids_p = _pad_i32(np.asarray(limiter_ids, dtype=np.int32), size, 0)
        req_p = _pad_i64(np.asarray(requested, dtype=np.int64), size, 0)
        with self._lock:
            if algo == "sw":
                self.sw_packed, granted, ws = step(
                    self.sw_packed, self.table.device_arrays,
                    slots_p, lids_p, req_p, jnp.int64(now_ms))
            else:
                self.tb_packed, granted, ws = step(
                    self.tb_packed, self.table.device_arrays,
                    slots_p, lids_p, req_p, jnp.int64(now_ms))
        return np.asarray(granted)[:n], np.asarray(ws)[:n]

    def lease_credit(self, algo: str, slots, limiter_ids, credit, grant_ws,
                     now_ms: int) -> np.ndarray:
        """Return unused reserved permits (lease renewal/release).
        ``grant_ws`` is the per-lane window stamp :meth:`lease_reserve`
        returned (sliding window: a rolled window drops the credit — the
        charge already ages out with the window).  Returns the permits
        actually credited per lane."""
        from ratelimiter_tpu.ops import lease as lease_ops

        n = len(slots)
        size = _bucket_size(n)
        self._mark(algo, np.asarray(slots))
        step = lease_ops.CREDIT_STEPS[algo]
        slots_p = _pad_i32(np.asarray(slots, dtype=np.int32), size, -1)
        lids_p = _pad_i32(np.asarray(limiter_ids, dtype=np.int32), size, 0)
        cr_p = _pad_i64(np.asarray(credit, dtype=np.int64), size, 0)
        ws_p = _pad_i64(np.asarray(grant_ws, dtype=np.int64), size, 0)
        with self._lock:
            if algo == "sw":
                self.sw_packed, credited = step(
                    self.sw_packed, self.table.device_arrays,
                    slots_p, lids_p, cr_p, ws_p, jnp.int64(now_ms))
            else:
                self.tb_packed, credited = step(
                    self.tb_packed, self.table.device_arrays,
                    slots_p, lids_p, cr_p, ws_p, jnp.int64(now_ms))
        return np.asarray(credited)[:n]

    # -- read-only ------------------------------------------------------------
    def sw_available(self, slots, limiter_ids, now_ms: int) -> np.ndarray:
        n = len(slots)
        size = _bucket_size(n)
        with self._lock:
            out = self._sw_peek(
                self.sw_packed,
                self.table.device_arrays,
                _pad_i32(np.asarray(slots, dtype=np.int32), size, 0),
                _pad_i32(np.asarray(limiter_ids, dtype=np.int32), size, 0),
                jnp.int64(now_ms),
            )
        return np.asarray(out)[:n]

    def tb_available(self, slots, limiter_ids, now_ms: int) -> np.ndarray:
        n = len(slots)
        size = _bucket_size(n)
        with self._lock:
            out = self._tb_peek(
                self.tb_packed,
                self.table.device_arrays,
                _pad_i32(np.asarray(slots, dtype=np.int32), size, 0),
                _pad_i32(np.asarray(limiter_ids, dtype=np.int32), size, 0),
                jnp.int64(now_ms),
            )
        return np.asarray(out)[:n]

    # -- reset ----------------------------------------------------------------
    def sw_clear(self, slots: Sequence[int]) -> None:
        self._mark("sw", slots)
        size = _bucket_size(max(len(slots), 1))
        with self._lock:
            self.sw_packed = self._sw_reset(
                self.sw_packed, _pad_i32(np.asarray(slots, dtype=np.int32), size, -1))

    def tb_clear(self, slots: Sequence[int]) -> None:
        self._mark("tb", slots)
        size = _bucket_size(max(len(slots), 1))
        with self._lock:
            self.tb_packed = self._tb_reset(
                self.tb_packed, _pad_i32(np.asarray(slots, dtype=np.int32), size, -1))

    # -- raw packed-row access (export/import rebalance; engine/checkpoint.py)
    def read_rows(self, algo: str, slots) -> np.ndarray:
        """Packed state rows for the given slots (host numpy i32[n, lanes])."""
        with self._lock:
            packed = self.sw_packed if algo == "sw" else self.tb_packed
            return np.asarray(packed[jnp.asarray(
                np.ascontiguousarray(slots, dtype=np.int32))])

    def write_rows(self, algo: str, slots, rows: np.ndarray) -> None:
        """Overwrite packed state rows (import side of a rebalance)."""
        self._mark(algo, slots)
        with self._lock:
            idx = jnp.asarray(np.ascontiguousarray(slots, dtype=np.int32))
            vals = jnp.asarray(np.ascontiguousarray(rows, dtype=np.int32))
            if algo == "sw":
                self.sw_packed = self.sw_packed.at[idx].set(vals)
            else:
                self.tb_packed = self.tb_packed.at[idx].set(vals)

    def warm_micro_shapes(self, algos=("sw", "tb"),
                          sizes=(32, 64, 128)) -> None:
        """Pre-compile the small-shape micro steps so an interactive
        deployment's first micro-batch doesn't pay an XLA compile inside
        a caller's latency budget.  Warms the legacy list path at the
        _MICRO_FLOOR bucket AND the staged combined path at every size in
        ``sizes`` — dispatched twice per size from two distinct staging
        buffers, mirroring the batcher's double-buffered assembly, so the
        steady-state micro loop never compiles (asserted by
        bench/device_only.py).  Warm batches are all padding lanes
        (slot -1): every kernel masks them out and the journal filters
        them, so no state or replication traffic is touched.

        Sizes that are not dispatch buckets are ROUNDED UP to their
        bucket (pow2 ladder from the 32-lane floor) and deduped: a warm
        dispatch whose n is below its buffer width would slice down and
        silently compile a lane count the batcher never produces —
        warming the wrong executable while the real buckets still
        compile inside the first request's latency budget."""
        sizes = sorted({_bucket_size(max(int(n), 1)) for n in sizes})
        for algo in algos:
            for size in sizes:
                # Both in-flight buffers of the double-buffered assembly:
                # identical shape (the compile cache is keyed on it), but
                # dispatching from two distinct host arrays proves the
                # staged path is buffer-identity-agnostic at warm time.
                for _ in range(2):
                    staged = np.empty((MICRO_STAGE_ROWS, size),
                                      dtype=np.int64)
                    staged[0] = -1
                    staged[1] = 0
                    staged[2] = 1
                    staged[3, 0] = 0
                    # n == size so the dispatch buckets AT this size
                    # (a smaller n would slice down to the floor bucket
                    # and warm only that one shape).
                    self.micro_staged_drain(
                        algo,
                        self.micro_staged_dispatch(algo, staged, size),
                        size)

    def block_until_ready(self) -> None:
        with self._lock:
            jax.block_until_ready((self.sw_packed, self.tb_packed))

    def make_slot_index(self):
        # Prefer the C++ index (tens of M ops/s); identical semantics to the
        # Python SlotIndex (tests/test_native_index.py proves equivalence).
        from ratelimiter_tpu.engine.native_index import (
            NativeSlotIndex,
            native_available,
        )

        if native_available():
            return NativeSlotIndex(self.num_slots)
        from ratelimiter_tpu.engine.slots import SlotIndex

        return SlotIndex(self.num_slots)
