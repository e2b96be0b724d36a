"""Host-parallel slot index: T native sub-indexes, one worker thread each.

The C hash probe is DRAM-latency-bound (~54 ns/request single-threaded —
bench notes in ARCHITECTURE.md), which caps the host at ~18M assigns/s
while the relay device step and the wire could go faster.  Partitioning
the key space over T native sub-indexes (same splitmix64 routing as the
device-sharded index) lets T ctypes calls run truly in parallel — the C
calls release the GIL — so batch assignment scales with memory
parallelism instead of serializing on one probe stream.

A batch call runs in three phases (ARCHITECTURE.md, "Host parallelism"):
route (each request's partition, then each partition's keys copied into
one contiguous buffer in arrival order) and merge (the walks' outputs
back to request order) run in C over contiguous request ranges side by
side; between them each partition's C walk runs on its own buffer, on
the pool.  A batch shorter than ``_RANGE_GRAIN`` routes and merges on
the caller's thread.

Semantics: identical to ShardedSlotIndex's host side — eviction is
per-partition LRU (a key's slot never migrates between partitions), and
global slot id = partition * slots_per_part + local slot.  This is the
same recency trade the device-sharded deployment already makes; the
single-LRU NativeSlotIndex remains the default.

Used by TpuBatchedStorage(host_parallel=T) on single-device engines; the
sharded engine keeps its own per-shard routing (one partition per device
shard).
"""

from __future__ import annotations

import concurrent.futures as cf
import threading
import time
from typing import Hashable, Optional, Set, Tuple

import numpy as np

from ratelimiter_tpu.engine.errors import consume_pending_clears
from ratelimiter_tpu.engine.native_index import (
    NativeSlotIndex,
    hash_str_keys,
    merge_ranges,
    route_ranges,
    sort_uniques,
)

# Requests per range of the route and merge passes: a batch is cut into
# ranges of at least this many requests, at most one per partition.
_RANGE_GRAIN = 1 << 16


def _part_of_key(key, n_parts: int) -> int:
    from ratelimiter_tpu.parallel.sharded import shard_of_key

    return shard_of_key(key, n_parts)


class PartitionedSlotIndex:
    """Drop-in NativeSlotIndex with T-way host parallelism.

    Exposes the same vectorized surface (assign_batch_ints[_multi],
    assign_batch_strs, the *_uniques relay family) plus the scalar
    SlotIndex contract.  Fingerprint dump/restore enumerates per
    partition, so checkpoints carry the exact per-partition LRU orders.
    """

    def __init__(self, num_slots: int, n_parts: int = 4):
        if num_slots % n_parts:
            raise ValueError("num_slots must divide evenly by n_parts")
        if not 0 < n_parts <= 256:
            raise ValueError("n_parts must be in 1..256")
        self.num_slots = int(num_slots)
        self.n_parts = int(n_parts)
        self.slots_per_part = self.num_slots // self.n_parts
        self._parts = [NativeSlotIndex(self.slots_per_part)
                       for _ in range(self.n_parts)]
        self._pool = cf.ThreadPoolExecutor(
            self.n_parts, thread_name_prefix="slotidx")
        self._tls = threading.local()

    def close(self) -> None:
        self._pool.shutdown(wait=False)

    def last_phase_s(self) -> Optional[
            Tuple[float, float, Optional[float]]]:
        """(route, merge, sort) seconds of this thread's last vectorized
        call, for the stream loop's ``index_route``/``index_merge``/
        ``index_sort`` timers; sort is the slowest partition's slot sort,
        None unless the call handed back slot-sorted uniques.  None
        before the first call."""
        return getattr(self._tls, "phase_s", None)

    # -- scalar interface ------------------------------------------------------
    def _local_pins(self, pinned, part):
        if not pinned:
            return None
        spp = self.slots_per_part
        return {s % spp for s in pinned if s // spp == part}

    def get(self, key: Hashable) -> Optional[int]:
        p = _part_of_key(key, self.n_parts)
        local = self._parts[p].get(key)
        return None if local is None else p * self.slots_per_part + local

    def assign(self, key: Hashable,
               pinned: Optional[Set[int]] = None,
               hold_pin: bool = False) -> Tuple[int, Optional[int]]:
        p = _part_of_key(key, self.n_parts)
        base = p * self.slots_per_part
        local, evicted = self._parts[p].assign(
            key, pinned=self._local_pins(pinned, p), hold_pin=hold_pin)
        return base + local, None if evicted is None else base + evicted

    def remove(self, key: Hashable) -> Optional[int]:
        p = _part_of_key(key, self.n_parts)
        local = self._parts[p].remove(key)
        return None if local is None else p * self.slots_per_part + local

    def __len__(self) -> int:
        return sum(len(p) for p in self._parts)

    # -- vectorized interface --------------------------------------------------
    def _walk(self, lanes, hashed, pinned, run, unpin_of):
        """Route a batch by partition and run every partition's walk on
        the pool (GIL released inside the C calls).

        ``lanes`` are one or two 64-bit request lanes (keys, lids or
        fingerprints); ``lanes[0]`` routes, through splitmix64 when
        ``hashed`` (int keys) or as it is (fingerprint h1s).  The route
        runs over request ranges side by side (native_index.route_ranges)
        and leaves each partition's requests in one contiguous slice in
        arrival order.  ``run(p, *slices, pins)`` walks partition p.
        ``unpin_of(result) -> local slots`` must be given when the run
        holds pins, so a partial failure releases them.  Returns the
        routing the merge needs — (partition lane, range bounds, each
        range's first position in every partition, partition offsets,
        route seconds) — and the per-partition results (None where
        empty)."""
        t0 = time.perf_counter()
        n = len(lanes[0])
        n_ranges = max(1, min(self.n_parts, -(-n // _RANGE_GRAIN)))
        bounds = np.arange(n_ranges + 1, dtype=np.int64) * n // n_ranges
        part, local, offs, copies = route_ranges(lanes, hashed,
                                                 self.n_parts, bounds)
        route_s = time.perf_counter() - t0
        futs = []
        for p in range(self.n_parts):
            lo, hi = int(offs[p]), int(offs[p + 1])
            if lo == hi:
                futs.append(None)
                continue
            futs.append(self._pool.submit(
                run, p, *(c[lo:hi] for c in copies),
                self._local_pins(pinned, p)))
        return ((part, bounds, local, offs, route_s),
                self._collect(futs, unpin_of))

    def _clears(self, results) -> np.ndarray:
        """Every partition's evictions as global slots, partition-major."""
        spp = self.slots_per_part
        evs = [res[-1] + np.int32(p * spp)
               for p, res in enumerate(results) if res is not None]
        return (np.concatenate(evs) if evs
                else np.empty(0, dtype=np.int32))

    def _merge_slots(self, routed, results):
        """(slots i32[n], clears) in request order from per-partition
        (slots, evictions)."""
        t0 = time.perf_counter()
        *routing, route_s = routed
        base = (np.arange(self.n_parts, dtype=np.int32)
                * np.int32(self.slots_per_part))
        out, _ = merge_ranges(
            *routing, [None if res is None else res[0] for res in results],
            base)
        clears = self._clears(results)
        self._tls.phase_s = (route_s, time.perf_counter() - t0, None)
        return out, clears

    def _walk_uniques(self, lanes, hashed, pinned, run, rank_bits,
                      hold_pins, sort_slots):
        """The *_uniques families' walk and merge.  With ``sort_slots``
        every partition's job slot-sorts its uniques in place (its uidx
        remapped) right after its walk, so the merge below comes out
        sorted by global slot."""
        sort_s = None
        if sort_slots:
            sort_s = [None] * self.n_parts
            walk = run

            def run(p, *args):
                res = walk(p, *args)
                t0 = time.perf_counter()
                if sort_uniques(res[0], rank_bits, res[1]):
                    sort_s[p] = time.perf_counter() - t0
                return res

        routed, results = self._walk(
            lanes, hashed, pinned, run,
            self._uslots_of(rank_bits) if hold_pins else None)
        return self._merge_uniques(routed, results, rank_bits, sort_s)

    def _merge_uniques(self, routed, results, rank_bits, sort_s):
        """(uwords, uidx i32[n], rank i32[n], clears) from per-partition
        (uwords, uidx, rank, evictions): uwords concatenated
        partition-major with each partition's slot base folded into the
        slot field, uidx offset by the uniques of the partitions before.
        ``sort_s`` holds each partition's sort seconds (None where it
        did not sort); the merge is sorted when every walked partition
        sorted."""
        t0 = time.perf_counter()
        *routing, route_s = routed
        spp = self.slots_per_part
        sizes = [0 if res is None else len(res[0]) for res in results]
        uoff = np.zeros(self.n_parts, dtype=np.int32)
        np.cumsum(sizes[:-1], out=uoff[1:])
        uwords = np.empty(sum(sizes), dtype=np.uint32)
        for p, res in enumerate(results):
            if res is not None:
                # Slot rides in bits rank_bits+1.., so adding
                # base << (rank_bits+1) re-addresses it globally.
                np.add(res[0], np.uint32(p * spp << (rank_bits + 1)),
                       out=uwords[uoff[p]:uoff[p] + sizes[p]])
        uidx, rank = merge_ranges(
            *routing, [None if res is None else res[1] for res in results],
            uoff, [None if res is None else res[2] for res in results])
        clears = self._clears(results)
        sorted_s = None
        if sort_s is not None:
            walked = [s for s, res in zip(sort_s, results) if res is not None]
            if None not in walked:
                sorted_s = max(walked, default=0.0)
        self._tls.phase_s = (route_s, time.perf_counter() - t0, sorted_s)
        return uwords, uidx, rank, clears

    def _collect(self, futs, unpin_of):
        """Gather per-partition futures; if any partition raised, release
        the pins the SUCCESSFUL partitions took (their results never reach
        the caller, so nothing else could unpin them), surface EVERY
        eviction the batch applied — successful partitions' lists plus the
        failing partitions' partial ones — as global ``pending_clears`` on
        the re-raised error, and re-raise.  Without that, slots the C
        index already remapped to new keys would keep stale device state
        (ADVICE r3)."""
        results, err = [], None
        spp = self.slots_per_part
        clears: list = []
        for p, f in enumerate(futs):
            if f is None:
                results.append(None)
                continue
            try:
                results.append(f.result())
            except Exception as exc:  # noqa: BLE001 — re-raised below
                err = err if err is not None else exc
                clears.extend(consume_pending_clears(exc, p * spp))
                results.append(None)
        if err is not None:
            for p, res in enumerate(results):
                if res is None:
                    continue
                if unpin_of is not None:
                    self._parts[p].unpin_batch(unpin_of(res))
                # Every assign result ends with its eviction list.
                clears.extend(p * spp + int(e) for e in res[-1])
            try:  # keep the original type; just carry the clears
                err.pending_clears = (np.asarray(clears, dtype=np.int64)
                                      if clears else None)
            except AttributeError:  # exotic __slots__ exception: best effort
                pass
            raise err
        return results

    @staticmethod
    def _uslots_of(rank_bits):
        return lambda res: (res[0] >> np.uint32(rank_bits + 1)).astype(
            np.int32)

    def assign_batch_ints(self, keys: np.ndarray, lid: int,
                          pinned: Optional[Set[int]] = None,
                          hold_pins: bool = False):
        keys = np.ascontiguousarray(keys, dtype=np.int64)

        def run(p, keys_p, pins):
            return self._parts[p].assign_batch_ints(
                keys_p, lid, pinned=pins, hold_pins=hold_pins)

        routed, results = self._walk(
            (keys,), True, pinned, run,
            (lambda res: res[0]) if hold_pins else None)
        return self._merge_slots(routed, results)

    def assign_batch_ints_multi(self, keys: np.ndarray, lids: np.ndarray,
                                pinned: Optional[Set[int]] = None,
                                hold_pins: bool = False):
        keys = np.ascontiguousarray(keys, dtype=np.int64)
        lids = np.ascontiguousarray(lids, dtype=np.uint64)

        def run(p, keys_p, lids_p, pins):
            return self._parts[p].assign_batch_ints_multi(
                keys_p, lids_p, pinned=pins, hold_pins=hold_pins)

        routed, results = self._walk(
            (keys, lids), True, pinned, run,
            (lambda res: res[0]) if hold_pins else None)
        return self._merge_slots(routed, results)

    def assign_batch_ints_uniques(self, keys: np.ndarray, lid: int,
                                  rank_bits: int,
                                  pinned: Optional[Set[int]] = None,
                                  hold_pins: bool = False,
                                  sort_slots: bool = False):
        keys = np.ascontiguousarray(keys, dtype=np.int64)

        def run(p, keys_p, pins):
            return self._parts[p].assign_batch_ints_uniques(
                keys_p, lid, rank_bits, pinned=pins, hold_pins=hold_pins)

        return self._walk_uniques((keys,), True, pinned, run, rank_bits,
                                  hold_pins, sort_slots)

    def assign_batch_ints_multi_uniques(self, keys: np.ndarray,
                                        lids: np.ndarray, rank_bits: int,
                                        pinned: Optional[Set[int]] = None,
                                        hold_pins: bool = False,
                                        sort_slots: bool = False):
        keys = np.ascontiguousarray(keys, dtype=np.int64)
        lids = np.ascontiguousarray(lids, dtype=np.uint64)

        def run(p, keys_p, lids_p, pins):
            return self._parts[p].assign_batch_ints_multi_uniques(
                keys_p, lids_p, rank_bits, pinned=pins,
                hold_pins=hold_pins)

        return self._walk_uniques((keys, lids), True, pinned, run,
                                  rank_bits, hold_pins, sort_slots)

    # Strings: hash the whole window ONCE natively (fingerprints straight
    # off the interned UTF-8 buffers), route by h1 — the exact quantity
    # shard_of_key's string branch computes scalar-side, so both paths
    # agree on a key's partition — and feed each partition its
    # fingerprint slice: the per-partition walks then do zero hashing.
    def _fps(self, keys, lid, start, count):
        n = (len(keys) - start) if count is None else count
        fp = hash_str_keys(keys, lid, start, n)
        if fp is None:
            raise ValueError(f"bad key window: start={start} count={count}")
        return fp

    def assign_batch_strs(self, keys, lid: int,
                          pinned: Optional[Set[int]] = None,
                          hold_pins: bool = False,
                          start: int = 0, count: int | None = None):
        def run(p, h1, h2, pins):
            return self._parts[p].assign_batch_fps(
                h1, h2, pinned=pins, hold_pins=hold_pins)

        routed, results = self._walk(
            self._fps(keys, lid, start, count), False, pinned, run,
            (lambda res: res[0]) if hold_pins else None)
        return self._merge_slots(routed, results)

    def assign_batch_strs_uniques(self, keys, lid: int, rank_bits: int,
                                  pinned: Optional[Set[int]] = None,
                                  hold_pins: bool = False,
                                  start: int = 0,
                                  count: int | None = None,
                                  sort_slots: bool = False):
        def run(p, h1, h2, pins):
            return self._parts[p].assign_batch_fps_uniques(
                h1, h2, rank_bits, pinned=pins, hold_pins=hold_pins)

        return self._walk_uniques(self._fps(keys, lid, start, count), False,
                                  pinned, run, rank_bits, hold_pins,
                                  sort_slots)

    # -- fingerprint enumeration (checkpoint/restore) --------------------------
    def dump_fp(self):
        """Per-partition (h1, h2, local slots) stacked with partition slot
        bases folded in; concatenation order is partition-major so
        restore_fp can split it back exactly."""
        h1s, h2s, slots = [], [], []
        for p, part in enumerate(self._parts):
            h1, h2, sl = part.dump_fp()
            h1s.append(h1)
            h2s.append(h2)
            slots.append(sl + np.int32(p * self.slots_per_part))
        return (np.concatenate(h1s) if h1s else np.empty(0, np.uint64),
                np.concatenate(h2s) if h2s else np.empty(0, np.uint64),
                np.concatenate(slots) if slots else np.empty(0, np.int32))

    def pin_batch(self, slots) -> None:
        slots = np.ascontiguousarray(slots, dtype=np.int32)
        part = slots // self.slots_per_part
        for q, sub in enumerate(self._parts):
            m = part == q
            if m.any():
                sub.pin_batch(slots[m] - np.int32(q * self.slots_per_part))

    def unpin_batch(self, slots) -> None:
        slots = np.ascontiguousarray(slots, dtype=np.int32)
        part = slots // self.slots_per_part
        for q, sub in enumerate(self._parts):
            m = part == q
            if m.any():
                sub.unpin_batch(slots[m] - np.int32(q * self.slots_per_part))

    # NOTE: no restore_fp here on purpose — fingerprints don't carry their
    # key's partition routing, so only the checkpoint path (which stores
    # per-partition payloads) can restore; a flat fingerprint dump is
    # rejected at the checkpoint layer (engine/checkpoint.py).

    def lookup_fps(self, h1: np.ndarray, h2: np.ndarray) -> np.ndarray:
        # Fingerprints don't carry the partition; probe every partition
        # (restore/rebalance path only — not on the hot path).
        out = np.full(len(h1), -1, dtype=np.int32)
        for p, sub in enumerate(self._parts):
            local = sub.lookup_fps(h1, h2)
            hit = (out == -1) & (local >= 0)
            out[hit] = local[hit] + p * self.slots_per_part
        return out
