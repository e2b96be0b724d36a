"""Multi-chip decision engine: slot state sharded over a device mesh.

``shard_map`` over a 1-D mesh runs the *same* single-device step
(ops/sliding_window.py, ops/token_bucket.py) independently on every shard's
partition of the slot array.  Keys are pinned to shards by hash, so a
request batch is routed host-side into per-shard sub-batches of identical
shape ``(n_shards, B)`` — SPMD with zero cross-shard traffic on the hot
path (the Redis-Cluster-hash-slots analog; SURVEY.md §2 "Parallelism
strategies").  The only collective is a ``psum`` over the mesh that
aggregates per-step allow/deny totals for metrics.

The global state lives as ``(n_shards, S_local)`` arrays with
``NamedSharding(P('shard', None))`` — on a real TPU slice each row is
resident in one chip's HBM and updates happen entirely chip-locally over
ICI-free code; the same program runs unchanged on the CPU test mesh.
"""

from __future__ import annotations

import contextlib
import functools
import threading
import zlib
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ratelimiter_tpu.engine.slots import SlotIndex
from ratelimiter_tpu.engine.state import LimiterTable
from ratelimiter_tpu.ops.sliding_window import (
    SWOut,
    sw_pack_state,
    sw_peek_p,
    sw_reset_p,
    sw_step_p,
    sw_unpack_state,
)
from ratelimiter_tpu.ops.token_bucket import (
    TBOut,
    tb_pack_state,
    tb_peek_p,
    tb_reset_p,
    tb_step_p,
    tb_unpack_state,
)
from ratelimiter_tpu.parallel.mesh import SHARD_AXIS, make_mesh


def shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the replication checker off: every spec
    here is explicit, so the checker adds nothing, and the flat step's
    duplicate solver lowers a ``while_loop``."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


_MIN_BATCH = 256


def _bucket(n: int, floor: int = _MIN_BATCH) -> int:
    size = floor
    while size < n:
        size *= 2
    return size


def shard_of_int_keys(key_ids, n_shards: int):
    """Vectorized deterministic shard hash for int64 user keys (splitmix64
    finalizer).  The scalar path routes int keys through this same function,
    so stream and scalar calls always agree on a key's shard."""
    x = np.asarray(key_ids).astype(np.uint64)
    with np.errstate(over="ignore"):
        x = (x + np.uint64(0x9E3779B97F4A7C15))
        x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        x = x ^ (x >> np.uint64(31))
    return (x % np.uint64(n_shards)).astype(np.int64)


def shard_of_key(key, n_shards: int) -> int:
    """Deterministic, process-independent key -> shard hash, so a multi-host
    router and this engine always agree.  Int user keys use the vectorizable
    splitmix hash (same as the int stream path).  String/bytes user keys
    route by the h1 stream of their index FINGERPRINT (r6): the same hash
    the slot index keys on, so the batched string stream can hash a chunk
    once natively and both route and assign from the result — scalar calls
    compute the identical h1 here in Python.  Everything else (exotic key
    types, which have no batch path) keeps crc32-of-repr.

    The string branch changed from crc32-of-repr in r6; sharded checkpoint
    dumps carry a shard-hash version so a dump written under the old
    routing is refused (or placement-checked) instead of silently
    orphaning entries (engine/checkpoint.py:SHARD_HASH_VERSION)."""
    user = key[1] if isinstance(key, tuple) and len(key) == 2 else key
    if isinstance(user, (int, np.integer)):
        return int(shard_of_int_keys(np.asarray([user]), n_shards)[0])
    lid = key[0] if isinstance(key, tuple) and len(key) == 2 else 0
    if isinstance(user, (str, bytes)) and isinstance(lid, (int, np.integer)):
        from ratelimiter_tpu.engine.native_index import fnv_fingerprint_h1

        data = user.encode() if isinstance(user, str) else user
        return fnv_fingerprint_h1(data, int(lid)) % n_shards
    return zlib.crc32(repr(key).encode()) % n_shards


class ShardedSlotIndex:
    """Key -> global slot with per-shard LRU sub-indexes.

    Global slot id = shard * slots_per_shard + local slot; eviction is
    shard-local (a key's state never migrates between shards).
    """

    def __init__(self, slots_per_shard: int, n_shards: int,
                 native: bool = True):
        self.slots_per_shard = int(slots_per_shard)
        self.n_shards = int(n_shards)
        self.num_slots = self.slots_per_shard * self.n_shards
        sub_cls = SlotIndex
        if native:
            from ratelimiter_tpu.engine.native_index import (
                NativeSlotIndex,
                native_available,
            )

            if native_available():
                sub_cls = NativeSlotIndex
        self._sub = [sub_cls(self.slots_per_shard) for _ in range(self.n_shards)]
        # The sharded stream path needs per-shard vectorized assignment.
        self.supports_batch_ints = all(
            hasattr(s, "assign_batch_ints") for s in self._sub)
        # The sharded STRING stream additionally needs native fingerprint
        # hashing (hash once -> route by h1 -> per-shard fps assign; the
        # h1 routing is what shard_of_key's string branch computes
        # scalar-side, so both paths agree on a key's shard).
        from ratelimiter_tpu.engine.native_index import str_hash_available

        self.supports_batch_strs = (
            str_hash_available()
            and all(hasattr(s, "assign_batch_fps_uniques")
                    for s in self._sub))

    def _split(self, global_slot: int):
        return divmod(global_slot, self.slots_per_shard)

    def get(self, key):
        shard = shard_of_key(key, self.n_shards)
        local = self._sub[shard].get(key)
        return None if local is None else shard * self.slots_per_shard + local

    def assign(self, key, pinned=None, hold_pin=False):
        shard = shard_of_key(key, self.n_shards)
        local_pinned = None
        if pinned:
            local_pinned = {
                s % self.slots_per_shard
                for s in pinned
                if s // self.slots_per_shard == shard
            }
        local, evicted = self._sub[shard].assign(key, pinned=local_pinned,
                                                 hold_pin=hold_pin)
        base = shard * self.slots_per_shard
        return base + local, None if evicted is None else base + evicted

    def remove(self, key):
        shard = shard_of_key(key, self.n_shards)
        local = self._sub[shard].remove(key)
        return None if local is None else shard * self.slots_per_shard + local

    def __len__(self):
        return sum(len(s) for s in self._sub)

    def pin_batch(self, slots) -> None:
        slots = np.ascontiguousarray(slots, dtype=np.int32)
        shard = slots // self.slots_per_shard
        for q, sub in enumerate(self._sub):
            m = shard == q
            if m.any() and hasattr(sub, "pin_batch"):
                sub.pin_batch(slots[m] - np.int32(q * self.slots_per_shard))

    def unpin_batch(self, slots) -> None:
        slots = np.ascontiguousarray(slots, dtype=np.int32)
        shard = slots // self.slots_per_shard
        for q, sub in enumerate(self._sub):
            m = shard == q
            if m.any() and hasattr(sub, "unpin_batch"):
                sub.unpin_batch(slots[m] - np.int32(q * self.slots_per_shard))


# ---------------------------------------------------------------------------
# Sharded step construction
# ---------------------------------------------------------------------------

def build_sharded_sw_step(mesh):
    """shard_map'd sliding-window step over (n_shards, S_local, 6) packed
    state and (n_shards, B) batches; returns (state, out, global totals)."""

    def local_step(state, table, slots, lids, permits, now):
        new_state, out = sw_step_p(state[0], table, slots[0], lids[0],
                                   permits[0], now)
        n_allowed = jnp.sum(out.allowed.astype(jnp.int64))
        n_total = jnp.sum((slots[0] >= 0).astype(jnp.int64))
        totals = jax.lax.psum(jnp.stack([n_allowed, n_total]), SHARD_AXIS)
        return new_state[None], SWOut(*(f[None] for f in out)), totals

    return shard_map(
        local_step,
        mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(), P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS), P()),
        out_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P()),
    )


def build_sharded_tb_step(mesh):
    def local_step(state, table, slots, lids, permits, now):
        new_state, out = tb_step_p(state[0], table, slots[0], lids[0],
                                   permits[0], now)
        n_allowed = jnp.sum(out.allowed.astype(jnp.int64))
        n_total = jnp.sum((slots[0] >= 0).astype(jnp.int64))
        totals = jax.lax.psum(jnp.stack([n_allowed, n_total]), SHARD_AXIS)
        return new_state[None], TBOut(*(f[None] for f in out)), totals

    return shard_map(
        local_step,
        mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(), P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS), P()),
        out_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P()),
    )


def build_sharded_scan(mesh, step_p, lids_scalar: bool, has_permits: bool):
    """shard_map'd K-sub-batch scan with bit-packed decisions.

    Shapes: state (n_shards, S_local, L) packed; slots (n_shards, K, B);
    lids 0-d or (n_shards, K, B); permits None or (n_shards, K, B);
    now (K,).  Returns (state, bits (n_shards, K, ceil(B/8))).
    """
    from ratelimiter_tpu.ops.packed import _scan

    lid_spec = P() if lids_scalar else P(SHARD_AXIS)
    if has_permits:
        def local_scan(state, table, slots, lids, permits, now):
            st, bits = _scan(step_p, state[0], table, slots[0],
                             lids if lids_scalar else lids[0],
                             permits[0], now)
            return st[None], bits[None]

        in_specs = (P(SHARD_AXIS), P(), P(SHARD_AXIS), lid_spec,
                    P(SHARD_AXIS), P())
    else:
        def local_scan(state, table, slots, lids, now):
            st, bits = _scan(step_p, state[0], table, slots[0],
                             lids if lids_scalar else lids[0],
                             None, now)
            return st[None], bits[None]

        in_specs = (P(SHARD_AXIS), P(), P(SHARD_AXIS), lid_spec, P())
    return shard_map(
        local_scan,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=(P(SHARD_AXIS), P(SHARD_AXIS)),
    )


def build_sharded_flat(mesh, flat_fn, lids_scalar: bool, has_permits: bool):
    """shard_map'd FLAT mega-batch with bit-packed decisions (ops/flat.py —
    payload sorts, closed-form solve, block-scatter, per shard).

    Shapes: state (n_shards, S_local, L); slots (n_shards, B) local ids
    (-1 padding); lids 0-d or (n_shards, B); permits None or (n_shards, B);
    now i64 scalar.  Returns (state, bits (n_shards, ceil(B/8))).
    """
    lid_spec = P() if lids_scalar else P(SHARD_AXIS)
    if has_permits:
        def local_flat(state, table, slots, lids, permits, now):
            st, bits = flat_fn(state[0], table, slots[0],
                               lids if lids_scalar else lids[0],
                               permits[0], now)
            return st[None], bits[None]

        in_specs = (P(SHARD_AXIS), P(), P(SHARD_AXIS), lid_spec,
                    P(SHARD_AXIS), P())
    else:
        def local_flat(state, table, slots, lids, now):
            st, bits = flat_fn(state[0], table, slots[0],
                               lids if lids_scalar else lids[0],
                               None, now)
            return st[None], bits[None]

        in_specs = (P(SHARD_AXIS), P(), P(SHARD_AXIS), lid_spec, P())
    return shard_map(
        local_flat,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=(P(SHARD_AXIS), P(SHARD_AXIS)),
    )


def build_sharded_relay(mesh, relay_fn, lids_scalar: bool):
    """shard_map'd relay step (ops/relay.py — no sort/scan; the host
    index supplies the duplicate structure).  Works for both flavors:
    bits (words (n_shards, B) -> uint8 (n_shards, B/8)) and counts
    (uwords (n_shards, U) -> out_dtype (n_shards, U)).

    State stays (n_shards, S_local, L); each shard decides its slice with
    LOCAL slot ids; zero cross-shard device traffic.
    """
    lid_spec = P() if lids_scalar else P(SHARD_AXIS)

    def local_relay(state, table, words, lids, now):
        st, out = relay_fn(state[0], table, words[0],
                           lids if lids_scalar else lids[0], now)
        return st[None], out[None]

    return shard_map(
        local_relay,
        mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(), P(SHARD_AXIS), lid_spec, P()),
        out_specs=(P(SHARD_AXIS), P(SHARD_AXIS)),
    )


def build_sharded_peek(mesh, peek_fn):
    def local_peek(state, table, slots, lids, now):
        out = peek_fn(state[0], table, slots[0], lids[0], now)
        return out[None]

    return shard_map(
        local_peek,
        mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(), P(SHARD_AXIS), P(SHARD_AXIS), P()),
        out_specs=P(SHARD_AXIS),
    )


def build_sharded_reset(mesh, reset_fn):
    def local_reset(state, slots):
        return reset_fn(state[0], slots[0])[None]

    return shard_map(
        local_reset,
        mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(SHARD_AXIS)),
        out_specs=P(SHARD_AXIS),
    )


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------

class ShardedDeviceEngine:
    """Drop-in DeviceEngine with state sharded over a mesh.

    Public surface is identical (global slot ids in, numpy decisions out);
    host-side routing scatters each request to its shard's row and unscatters
    the results.  Exposes ``last_step_totals`` = (allowed, total) aggregated
    across all shards by the on-device psum.

    **Per-shard state parts (r8).**  The canonical state is a LIST of
    single-device arrays, one ``(1, S_local, L)`` part committed to each
    mesh device; the mesh-wide ``(n_shards, S_local, L)`` array every
    shard_map path consumes is assembled on demand with
    ``jax.make_array_from_single_device_arrays`` (zero-copy metadata)
    and cached until a part changes.  That representation is what makes
    the per-shard stream pipelines possible: ``relay_shard_dispatch``
    runs ONE shard's relay step as an independent single-device XLA
    execution on that shard's own device — no mesh collective, no
    multi-device launch rendezvous, no waiting for sibling shards'
    layouts — so shard A can be assembling chunk N+1 while shard B's
    chunk N is still in flight.  Locking: each shard has its own lock;
    whole-mesh operations (the shard_map dispatch/peek/clear paths,
    read/write_rows, state (re)assembly) take every shard lock in
    ascending order, so a per-shard dispatch never races a global step
    and lock order is deadlock-free.
    """

    # Per-shard replication (replication/sharded.py): every dispatch path
    # marks its touched slots (global ids) into an attached journal, so a
    # ShardedReplicationLog can cut per-shard epoch deltas.  The flat
    # ReplicationLog refuses this engine — shard streams must ship
    # independently so one shard can be promoted without the world.
    supports_replication = True

    def __init__(self, slots_per_shard: int, table: LimiterTable, mesh=None):
        self.mesh = mesh if mesh is not None else make_mesh()
        self.n_shards = self.mesh.devices.size
        self.slots_per_shard = int(slots_per_shard)
        self.num_slots = self.n_shards * self.slots_per_shard
        self.table = table
        # Dirty-slot journal (engine/state.py): None (default) keeps the
        # hot path at one attribute check per dispatch.
        self.journal = None
        self._lock = threading.RLock()
        self.last_step_totals = (0, 0)
        # Monotone stamp so concurrent drains (the batcher's drain pool
        # completes batches in arbitrary order) can't regress
        # last_step_totals to an older batch.
        self._totals_seq = 0
        self._totals_seen = 0

        self._state_sharding = NamedSharding(self.mesh, P(SHARD_AXIS, None, None))
        self._devices = list(self.mesh.devices.flat)
        # Per-shard locks (r8): per-shard dispatch/clear take ONLY their
        # shard's lock; every whole-mesh path takes all of them ascending
        # via _exclusive().  RLocks so the packed-property assembly can
        # run inside an already-exclusive section.
        self._shard_locks = [threading.RLock() for _ in range(self.n_shards)]
        # Per-device colocated copies of the limiter table (keyed by the
        # TableArrays instance, which is rebuilt on any config change) so
        # per-shard dispatches never re-ship the table per call.
        self._table_parts: tuple = (None, {})

        def zero_parts(lanes):
            return [
                jax.device_put(
                    jnp.zeros((1, self.slots_per_shard, lanes),
                              dtype=jnp.int32), d)
                for d in self._devices
            ]

        # Packed-resident per-shard state (same codec as DeviceEngine),
        # held as canonical single-device parts + a lazily assembled
        # mesh-wide view.
        self._parts = {"sw": zero_parts(6), "tb": zero_parts(4)}
        self._packed_cache = {"sw": None, "tb": None}

        # Settle the Pallas probes before any shard_map step compiles
        # (same reason as DeviceEngine: never nested in another
        # program's lowering, and a probe failure raises at init).
        from ratelimiter_tpu.ops import pallas as pallas_kernels

        pallas_kernels.settle_all()
        self._sw_step = jax.jit(build_sharded_sw_step(self.mesh), donate_argnums=0)
        self._tb_step = jax.jit(build_sharded_tb_step(self.mesh), donate_argnums=0)
        self._sw_peek = jax.jit(build_sharded_peek(self.mesh, sw_peek_p))
        self._tb_peek = jax.jit(build_sharded_peek(self.mesh, tb_peek_p))
        self._sw_reset = jax.jit(build_sharded_reset(self.mesh, sw_reset_p), donate_argnums=0)
        self._tb_reset = jax.jit(build_sharded_reset(self.mesh, tb_reset_p), donate_argnums=0)
        self._scan_fns = {}

    # -- per-shard state parts (r8) --------------------------------------------
    @contextlib.contextmanager
    def _exclusive(self):
        """Hold every shard lock (ascending = deadlock-free against the
        per-shard paths, which take exactly one)."""
        for lk in self._shard_locks:
            lk.acquire()
        try:
            yield
        finally:
            for lk in reversed(self._shard_locks):
                lk.release()

    def _assembled(self, algo: str):
        """The mesh-wide (n_shards, S_local, L) view of the per-shard
        parts — zero-copy assembly, cached until a part changes."""
        with self._exclusive():
            arr = self._packed_cache[algo]
            if arr is None:
                parts = self._parts[algo]
                shape = (self.n_shards,) + tuple(parts[0].shape[1:])
                arr = jax.make_array_from_single_device_arrays(
                    shape, self._state_sharding, list(parts))
                self._packed_cache[algo] = arr
            return arr

    def _set_packed(self, algo: str, arr) -> None:
        """Decompose a mesh-sharded result back into canonical parts
        (zero-copy: each addressable shard IS the part)."""
        with self._exclusive():
            shards = sorted(arr.addressable_shards,
                            key=lambda s: s.index[0].start)
            self._parts[algo] = [s.data for s in shards]
            self._packed_cache[algo] = arr

    @property
    def sw_packed(self):
        return self._assembled("sw")

    @sw_packed.setter
    def sw_packed(self, arr) -> None:
        self._set_packed("sw", arr)

    @property
    def tb_packed(self):
        return self._assembled("tb")

    @tb_packed.setter
    def tb_packed(self, arr) -> None:
        self._set_packed("tb", arr)

    def _table_for(self, shard: int):
        """Colocated table arrays for one shard's device (cache keyed by
        the TableArrays instance — any registration rebuilds it).  Called
        BEFORE taking the shard lock (it takes the engine lock; lock
        order is engine > shard)."""
        src = self.table.device_arrays
        with self._lock:
            cache_src, per_dev = self._table_parts
            if cache_src is not src:
                per_dev = {}
                self._table_parts = (src, per_dev)
            tab = per_dev.get(shard)
            if tab is None:
                tab = jax.device_put(src, self._devices[shard])
                per_dev[shard] = tab
            return tab

    def shard_state_shape(self, algo: str) -> tuple:
        """``(slots_per_shard, lanes)``: one shard's packed state, the
        table a per-shard step writes."""
        return tuple(self._parts[algo][0].shape[1:])

    def _shard_relay_fn(self, algo: str, flavor: str, lids_scalar: bool,
                        out_dtype, slots_sorted: bool = False):
        from ratelimiter_tpu.ops import relay as relay_ops

        key = ("shard_relay", algo, flavor, lids_scalar,
               None if out_dtype is None else np.dtype(out_dtype).name,
               slots_sorted)
        fn = self._scan_fns.get(key)
        if fn is None:
            if flavor == "bits":
                base = (relay_ops.sw_relay_bits if algo == "sw"
                        else relay_ops.tb_relay_bits)
                local = functools.partial(base, rank_bits=self.rank_bits)
            else:
                base = (relay_ops.sw_relay_counts if algo == "sw"
                        else relay_ops.tb_relay_counts)
                jdt = (jnp.uint8 if np.dtype(out_dtype) == np.uint8
                       else jnp.uint16)
                local = functools.partial(base, rank_bits=self.rank_bits,
                                          out_dtype=jdt,
                                          slots_sorted=slots_sorted)

            def stepped(state, table, words, lids, now):
                st, out = local(state[0], table, words, lids, now)
                return st[None], out

            fn = jax.jit(stepped, donate_argnums=0)
            self._scan_fns[key] = fn
        return fn

    def relay_shard_dispatch(self, algo: str, shard: int, flavor: str,
                             words, lids, now_ms: int, out_dtype=None,
                             slots_sorted: bool = False):
        """ONE shard's relay step as an independent single-device XLA
        execution on that shard's own device (r8) — the per-shard stream
        pipelines' dispatch.  ``words`` carries LOCAL slot ids in the
        same word layout as the mesh-wide relay (``rank_bits``); padding
        is 0xFFFFFFFF.  ``slots_sorted`` (a counts dispatch whose
        uniques the host sorted by local slot): the step writes its rows
        with ``scatter_rows_presorted``, the tile sweep wherever
        ``block_scatter`` supports the shard's table and padded uniques,
        else XLA's row write — the one-chip digest step's rule.  Only
        this shard's lock is held: sibling shards dispatch, drain and
        assemble concurrently.  Returns the lazy per-shard handle (uint8
        bits or per-unique counts)."""
        self._mark_words_shard(algo, shard, words)
        dev = self._devices[shard]
        words_dev = jax.device_put(
            np.ascontiguousarray(words, dtype=np.uint32), dev)
        lids_scalar = np.ndim(lids) == 0
        if lids_scalar:
            lids_dev = jnp.asarray(np.int32(lids))
        else:
            lids_dev = jax.device_put(
                np.ascontiguousarray(lids, dtype=np.int32), dev)
        fn = self._shard_relay_fn(algo, flavor, lids_scalar, out_dtype,
                                  bool(slots_sorted))
        tab = self._table_for(shard)
        now = jnp.int64(now_ms)
        with self._shard_locks[shard]:
            # Donation invalidates the assembled view's buffer for this
            # shard — drop the cache before the step.
            self._packed_cache[algo] = None
            state, out = fn(self._parts[algo][shard], tab, words_dev,
                            lids_dev, now)
            self._parts[algo][shard] = state
        return out

    def clear_shard(self, algo: str, shard: int, local_slots) -> None:
        """Zero LOCAL slots on one shard's device — the per-shard stream
        pipelines' eviction-clear path.  Stream order is the caller's
        job (each shard pipeline is a FIFO, so a shard's clears land
        before the dispatch that reuses the slots, with no cross-shard
        barrier)."""
        local_slots = np.asarray(list(local_slots), dtype=np.int32)
        if not len(local_slots):
            return
        j = self.journal
        if j is not None:
            j.mark(algo, local_slots.astype(np.int64)
                   + shard * self.slots_per_shard)
        padded = np.full(_bucket(len(local_slots), floor=64), -1,
                         dtype=np.int32)
        padded[:len(local_slots)] = local_slots
        key = ("shard_reset", algo)
        fn = self._scan_fns.get(key)
        if fn is None:
            reset_fn = sw_reset_p if algo == "sw" else tb_reset_p

            def reset1(state, slots):
                return reset_fn(state[0], slots)[None]

            fn = jax.jit(reset1, donate_argnums=0)
            self._scan_fns[key] = fn
        slots_dev = jax.device_put(padded, self._devices[shard])
        with self._shard_locks[shard]:
            self._packed_cache[algo] = None
            self._parts[algo][shard] = fn(self._parts[algo][shard],
                                          slots_dev)

    def _mark_words_shard(self, algo: str, shard: int, words) -> None:
        """Journal one shard's relay words (host-side decode: LOCAL slot
        in the high bits -> global id; padding decodes past
        slots_per_shard and is dropped by the journal's bounds filter)."""
        j = self.journal
        if j is None:
            return
        loc = (np.asarray(words).astype(np.uint64)
               >> np.uint64(self.rank_bits + 1)).astype(np.int64)
        base = shard * self.slots_per_shard
        j.mark(algo, np.where(loc < self.slots_per_shard, loc + base, -1))

    # -- dirty-slot journal hooks (per-shard replication) ----------------------
    # Same host/device split as DeviceEngine's hooks: a device journal
    # marks from the dispatch's own uploaded matrix (one async device op,
    # zero extra bytes); the host journal gets the host copy.
    def _mark_mat(self, algo: str, mat, dev=None) -> None:
        j = self.journal
        if j is not None:
            j.mark_matrix(algo, dev if dev is not None
                          and getattr(j, "device", False) else mat,
                          self.slots_per_shard)

    def _mark_words_mat(self, algo: str, wmat, dev=None) -> None:
        j = self.journal
        if j is not None:
            j.mark_words_matrix(algo, dev if dev is not None
                                and getattr(j, "device", False) else wmat,
                                self.rank_bits, self.slots_per_shard)

    def _mark_global(self, algo: str, slots) -> None:
        j = self.journal
        if j is not None:
            j.mark(algo, slots)

    # -- i64 field view (checkpoint/compat) ------------------------------------
    @property
    def sw_state(self):
        return sw_unpack_state(self.sw_packed)

    @sw_state.setter
    def sw_state(self, state) -> None:
        if self.journal is not None:
            self.journal.mark_all("sw")
        self.sw_packed = jax.device_put(
            sw_pack_state(type(state)(*(jnp.asarray(f) for f in state))),
            self._state_sharding)

    @property
    def tb_state(self):
        return tb_unpack_state(self.tb_packed)

    @tb_state.setter
    def tb_state(self, state) -> None:
        if self.journal is not None:
            self.journal.mark_all("tb")
        self.tb_packed = jax.device_put(
            tb_pack_state(type(state)(*(jnp.asarray(f) for f in state))),
            self._state_sharding)

    def make_slot_index(self) -> ShardedSlotIndex:
        return ShardedSlotIndex(self.slots_per_shard, self.n_shards)

    # -- scan dispatch (sharded streaming; mirrors DeviceEngine's) ------------
    def sw_scan_dispatch(self, slots_skb, lids, permits_skb, now_k):
        return self._scan_dispatch("sw", slots_skb, lids, permits_skb, now_k)

    def tb_scan_dispatch(self, slots_skb, lids, permits_skb, now_k):
        return self._scan_dispatch("tb", slots_skb, lids, permits_skb, now_k)

    def _scan_fn(self, algo: str, lids_scalar: bool, has_permits: bool):
        key = (algo, lids_scalar, has_permits)
        fn = self._scan_fns.get(key)
        if fn is None:
            step_p = sw_step_p if algo == "sw" else tb_step_p
            fn = jax.jit(
                build_sharded_scan(self.mesh, step_p, lids_scalar, has_permits),
                donate_argnums=0)
            self._scan_fns[key] = fn
        return fn

    # -- flat mega-batch dispatch (the streaming hot path; ops/flat.py) -------
    def sw_flat_sharded_dispatch(self, slots_sb, lids, permits_sb, now_ms):
        return self._flat_dispatch("sw", slots_sb, lids, permits_sb, now_ms)

    def tb_flat_sharded_dispatch(self, slots_sb, lids, permits_sb, now_ms):
        return self._flat_dispatch("tb", slots_sb, lids, permits_sb, now_ms)

    def _flat_fn(self, algo: str, lids_scalar: bool, has_permits: bool):
        from ratelimiter_tpu.ops.flat import sw_flat_bits, tb_flat_bits

        key = ("flat", algo, lids_scalar, has_permits)
        fn = self._scan_fns.get(key)
        if fn is None:
            flat = sw_flat_bits if algo == "sw" else tb_flat_bits
            fn = jax.jit(
                build_sharded_flat(self.mesh, flat, lids_scalar, has_permits),
                donate_argnums=0)
            self._scan_fns[key] = fn
        return fn

    def _flat_dispatch(self, algo, slots_sb, lids, permits_sb, now_ms):
        """slots_sb: i32[n_shards, B_local] LOCAL slot ids (-1 padding);
        lids scalar or i32[n_shards, B_local]; permits likewise or None;
        now_ms scalar.  Returns a lazy uint8[n_shards, ceil(B/8)] handle."""
        slots_host = slots_sb
        slots_sb = jnp.asarray(np.ascontiguousarray(slots_sb, dtype=np.int32))
        self._mark_mat(algo, slots_host, dev=slots_sb)
        lids_scalar = np.ndim(lids) == 0
        if lids_scalar:
            lids = jnp.asarray(np.int32(lids))
        else:
            lids = jnp.asarray(np.ascontiguousarray(lids, dtype=np.int32))
        has_permits = permits_sb is not None
        now = jnp.int64(now_ms)
        fn = self._flat_fn(algo, lids_scalar, has_permits)
        with self._lock, self._exclusive():
            state = self.sw_packed if algo == "sw" else self.tb_packed
            if has_permits:
                permits_sb = jnp.asarray(
                    np.ascontiguousarray(permits_sb, dtype=np.int32))
                state, bits = fn(state, self.table.device_arrays,
                                 slots_sb, lids, permits_sb, now)
            else:
                state, bits = fn(state, self.table.device_arrays,
                                 slots_sb, lids, now)
            if algo == "sw":
                self.sw_packed = state
            else:
                self.tb_packed = state
        return bits

    # -- relay dispatch (ops/relay.py, per shard) ------------------------------
    # Word layout is per-SHARD: slot_bits covers slots_per_shard, so the
    # rank field is wider than the single-device engine would get at the
    # same total capacity.

    @property
    def slot_bits(self) -> int:
        return max(int(self.slots_per_shard).bit_length(), 1)

    @property
    def rank_bits(self) -> int:
        return 31 - self.slot_bits

    def relay_usable(self) -> bool:
        from ratelimiter_tpu.ops import relay as relay_ops

        return relay_ops.relay_usable(self.rank_bits,
                                      self.table.max_permits_registered)

    def counts_dtype(self):
        from ratelimiter_tpu.ops import relay as relay_ops

        return relay_ops.counts_dtype(self.table.max_permits_registered)

    def sw_relay_sharded_dispatch(self, words_sb, lids, now_ms):
        return self._relay_dispatch("sw", "bits", words_sb, lids, now_ms,
                                    None)

    def tb_relay_sharded_dispatch(self, words_sb, lids, now_ms):
        return self._relay_dispatch("tb", "bits", words_sb, lids, now_ms,
                                    None)

    def sw_relay_counts_sharded_dispatch(self, uwords_sb, lids, now_ms,
                                         out_dtype):
        return self._relay_dispatch("sw", "counts", uwords_sb, lids, now_ms,
                                    out_dtype)

    def tb_relay_counts_sharded_dispatch(self, uwords_sb, lids, now_ms,
                                         out_dtype):
        return self._relay_dispatch("tb", "counts", uwords_sb, lids, now_ms,
                                    out_dtype)

    def _relay_fn(self, algo, flavor, lids_scalar, out_dtype):
        import functools

        from ratelimiter_tpu.ops import relay as relay_ops

        key = ("relay", algo, flavor, lids_scalar,
               None if out_dtype is None else out_dtype().dtype.name)
        fn = self._scan_fns.get(key)
        if fn is None:
            if flavor == "bits":
                base = (relay_ops.sw_relay_bits if algo == "sw"
                        else relay_ops.tb_relay_bits)
                local = functools.partial(base, rank_bits=self.rank_bits)
            else:
                base = (relay_ops.sw_relay_counts if algo == "sw"
                        else relay_ops.tb_relay_counts)
                jdt = jnp.uint8 if out_dtype == np.uint8 else jnp.uint16
                local = functools.partial(base, rank_bits=self.rank_bits,
                                          out_dtype=jdt)
            fn = jax.jit(build_sharded_relay(self.mesh, local, lids_scalar),
                         donate_argnums=0)
            self._scan_fns[key] = fn
        return fn

    def _relay_dispatch(self, algo, flavor, words_sb, lids, now_ms,
                        out_dtype):
        """words_sb: uint32[n_shards, B_local] relay words with LOCAL slot
        ids (0xFFFFFFFF padding); lids scalar or i32[n_shards, B_local].
        Returns a lazy (n_shards, B/8) bits or (n_shards, B) counts
        handle."""
        words_host = words_sb
        words_sb = jnp.asarray(
            np.ascontiguousarray(words_sb, dtype=np.uint32))
        self._mark_words_mat(algo, words_host, dev=words_sb)
        lids_scalar = np.ndim(lids) == 0
        if lids_scalar:
            lids = jnp.asarray(np.int32(lids))
        else:
            lids = jnp.asarray(np.ascontiguousarray(lids, dtype=np.int32))
        now = jnp.int64(now_ms)
        fn = self._relay_fn(algo, flavor, lids_scalar, out_dtype)
        with self._lock, self._exclusive():
            state = self.sw_packed if algo == "sw" else self.tb_packed
            state, out = fn(state, self.table.device_arrays,
                            words_sb, lids, now)
            if algo == "sw":
                self.sw_packed = state
            else:
                self.tb_packed = state
        return out

    def _scan_dispatch(self, algo, slots_skb, lids, permits_skb, now_k):
        """slots_skb: i32[n_shards, K, B_local] LOCAL slot ids (-1 padding);
        lids: scalar or i32[n_shards, K, B_local]; permits likewise or None;
        now_k: i64[K].  Returns a lazy uint8[n_shards, K, ceil(B/8)] handle."""
        slots_host = slots_skb
        slots_skb = jnp.asarray(np.ascontiguousarray(slots_skb, dtype=np.int32))
        self._mark_mat(algo, slots_host, dev=slots_skb)
        lids_scalar = np.ndim(lids) == 0
        if lids_scalar:
            lids = jnp.asarray(np.int32(lids))
        else:
            lids = jnp.asarray(np.ascontiguousarray(lids, dtype=np.int32))
        has_permits = permits_skb is not None
        now_k = jnp.asarray(np.ascontiguousarray(now_k, dtype=np.int64))
        fn = self._scan_fn(algo, lids_scalar, has_permits)
        with self._lock, self._exclusive():
            state = self.sw_packed if algo == "sw" else self.tb_packed
            if has_permits:
                permits_skb = jnp.asarray(
                    np.ascontiguousarray(permits_skb, dtype=np.int32))
                state, bits = fn(state, self.table.device_arrays,
                                 slots_skb, lids, permits_skb, now_k)
            else:
                state, bits = fn(state, self.table.device_arrays,
                                 slots_skb, lids, now_k)
            if algo == "sw":
                self.sw_packed = state
            else:
                self.tb_packed = state
        return bits

    # -- routing --------------------------------------------------------------
    def _route(self, slots, fill_extra=None):
        """Scatter global-slot requests into (n_shards, B) rows.

        Returns (mat_local_slots, row_of_req, col_of_req, B).
        """
        slots = np.asarray(slots, dtype=np.int64)
        # Padding slots (< 0, e.g. warmup batches) route to shard 0 as local
        # padding: every kernel masks negative slots out.
        shard = np.clip(slots, 0, None) // self.slots_per_shard
        local = np.where(slots < 0, -1, slots % self.slots_per_shard)
        counts = np.bincount(shard, minlength=self.n_shards)
        B = _bucket(max(int(counts.max(initial=0)), 1))
        order = np.argsort(shard, kind="stable")
        offsets = np.zeros(self.n_shards + 1, dtype=np.int64)
        np.cumsum(counts, out=offsets[1:])
        cols = np.empty(len(slots), dtype=np.int64)
        cols[order] = np.arange(len(slots)) - offsets[shard[order]]
        mat = np.full((self.n_shards, B), -1, dtype=np.int32)
        mat[shard, cols] = local
        return mat, shard, cols, B

    def _route_batch(self, slots, limiter_ids, permits):
        mat, shard, cols, B = self._route(slots)
        lids = np.zeros((self.n_shards, B), dtype=np.int32)
        perms = np.ones((self.n_shards, B), dtype=np.int64)
        lids[shard, cols] = np.asarray(limiter_ids, dtype=np.int32)
        perms[shard, cols] = np.asarray(permits, dtype=np.int64)
        return mat, lids, perms, shard, cols

    # -- public API (mirrors DeviceEngine, incl. the dispatch/drain split
    # that lets the micro-batcher pipeline fetches against dispatches) ------
    def sw_acquire_dispatch(self, slots, limiter_ids, permits, now_ms: int):
        mat, lids, perms, shard, cols = self._route_batch(slots, limiter_ids, permits)
        self._mark_mat("sw", mat)
        with self._lock, self._exclusive():
            new_state, out, totals = self._sw_step(
                self.sw_packed, self.table.device_arrays,
                jnp.asarray(mat), jnp.asarray(lids), jnp.asarray(perms),
                jnp.int64(now_ms))
            self.sw_packed = new_state
            self._totals_seq += 1
            seq = self._totals_seq
        return (out, totals, shard, cols, seq)

    def sw_acquire_drain(self, handle, n: int):
        out, totals, shard, cols, seq = handle
        totals = np.asarray(totals)
        self._set_totals(seq, (int(totals[0]), int(totals[1])))
        return {
            "allowed": np.asarray(out.allowed)[shard, cols],
            "mutated": np.asarray(out.mutated)[shard, cols],
            "observed": np.asarray(out.observed)[shard, cols],
            "cache_value": np.asarray(out.cache_value)[shard, cols],
        }

    def _set_totals(self, seq: int, totals) -> None:
        with self._lock, self._exclusive():
            if seq > self._totals_seen:
                self._totals_seen = seq
                self.last_step_totals = totals

    def sw_acquire(self, slots, limiter_ids, permits, now_ms: int):
        handle = self.sw_acquire_dispatch(slots, limiter_ids, permits, now_ms)
        return self.sw_acquire_drain(handle, len(slots))

    def tb_acquire_dispatch(self, slots, limiter_ids, permits, now_ms: int):
        mat, lids, perms, shard, cols = self._route_batch(slots, limiter_ids, permits)
        self._mark_mat("tb", mat)
        with self._lock, self._exclusive():
            new_state, out, totals = self._tb_step(
                self.tb_packed, self.table.device_arrays,
                jnp.asarray(mat), jnp.asarray(lids), jnp.asarray(perms),
                jnp.int64(now_ms))
            self.tb_packed = new_state
            self._totals_seq += 1
            seq = self._totals_seq
        return (out, totals, shard, cols, seq)

    def tb_acquire_drain(self, handle, n: int):
        out, totals, shard, cols, seq = handle
        totals = np.asarray(totals)
        self._set_totals(seq, (int(totals[0]), int(totals[1])))
        return {
            "allowed": np.asarray(out.allowed)[shard, cols],
            "observed": np.asarray(out.observed)[shard, cols],
            "remaining": np.asarray(out.remaining)[shard, cols],
        }

    def tb_acquire(self, slots, limiter_ids, permits, now_ms: int):
        handle = self.tb_acquire_dispatch(slots, limiter_ids, permits, now_ms)
        return self.tb_acquire_drain(handle, len(slots))

    def sw_available(self, slots, limiter_ids, now_ms: int) -> np.ndarray:
        mat, shard, cols, B = self._route(slots)
        lids = np.zeros((self.n_shards, B), dtype=np.int32)
        lids[shard, cols] = np.asarray(limiter_ids, dtype=np.int32)
        mat = np.maximum(mat, 0)  # peek clamps; padding read is discarded
        with self._lock, self._exclusive():
            out = self._sw_peek(self.sw_packed, self.table.device_arrays,
                                jnp.asarray(mat), jnp.asarray(lids), jnp.int64(now_ms))
        return np.asarray(out)[shard, cols]

    def tb_available(self, slots, limiter_ids, now_ms: int) -> np.ndarray:
        mat, shard, cols, B = self._route(slots)
        lids = np.zeros((self.n_shards, B), dtype=np.int32)
        lids[shard, cols] = np.asarray(limiter_ids, dtype=np.int32)
        mat = np.maximum(mat, 0)
        with self._lock, self._exclusive():
            out = self._tb_peek(self.tb_packed, self.table.device_arrays,
                                jnp.asarray(mat), jnp.asarray(lids), jnp.int64(now_ms))
        return np.asarray(out)[shard, cols]

    def sw_clear(self, slots: Sequence[int]) -> None:
        mat, _, _, _ = self._route(slots)
        self._mark_mat("sw", mat)
        with self._lock, self._exclusive():
            self.sw_packed = self._sw_reset(self.sw_packed, jnp.asarray(mat))

    def tb_clear(self, slots: Sequence[int]) -> None:
        mat, _, _, _ = self._route(slots)
        self._mark_mat("tb", mat)
        with self._lock, self._exclusive():
            self.tb_packed = self._tb_reset(self.tb_packed, jnp.asarray(mat))

    # -- raw packed-row access (export/import rebalance; replication cuts) ----
    def read_rows(self, algo: str, slots) -> np.ndarray:
        """Packed rows for GLOBAL slot ids — device-side gather, so a
        per-shard replication cut fetches only its dirty rows instead of
        round-tripping the whole (n_shards, S_local, L) array.  Inputs
        are padded to a power of two so cut-to-cut count jitter reuses
        a handful of gather compilations."""
        slots = np.asarray(slots, dtype=np.int64)
        n = len(slots)
        if n == 0:
            packed = self.sw_packed if algo == "sw" else self.tb_packed
            return np.empty((0, packed.shape[-1]), dtype=np.int32)
        size = _bucket(n, floor=256)
        padded = np.zeros(size, dtype=np.int64)
        padded[:n] = slots
        shard = jnp.asarray(padded // self.slots_per_shard, dtype=jnp.int32)
        local = jnp.asarray(padded % self.slots_per_shard, dtype=jnp.int32)
        with self._lock, self._exclusive():
            packed = self.sw_packed if algo == "sw" else self.tb_packed
            rows = packed[shard, local]
        return np.asarray(rows)[:n]

    def write_rows(self, algo: str, slots, rows: np.ndarray) -> None:
        self._mark_global(algo, slots)
        slots = np.asarray(slots, dtype=np.int64)
        shard = jnp.asarray(slots // self.slots_per_shard, dtype=jnp.int32)
        local = jnp.asarray(slots % self.slots_per_shard, dtype=jnp.int32)
        vals = jnp.asarray(np.ascontiguousarray(rows, dtype=np.int32))
        with self._lock, self._exclusive():
            packed = self.sw_packed if algo == "sw" else self.tb_packed
            # Device-side scatter (no full-array host roundtrip), then
            # re-constrain to the shard placement.
            new = jax.device_put(packed.at[shard, local].set(vals),
                                 self._state_sharding)
            if algo == "sw":
                self.sw_packed = new
            else:
                self.tb_packed = new

    # -- lease RESERVE / CREDIT (ops/lease.py; leases/) ------------------------
    # The sharded mesh reserves via a read-rows -> host arithmetic ->
    # write-rows round trip under the exclusive lock set (atomic against
    # every other dispatch path — both read_rows and write_rows re-enter
    # the same RLocks).  Lease ops are rare by design (one reserve
    # amortizes over a whole client-side budget), so the host round trip
    # is off every hot path; the single-device engine runs the fused
    # device kernel instead (engine/engine.py:lease_reserve).  Callers
    # pass UNIQUE slots per call (the lease manager reserves one key at
    # a time); the host mirrors process lanes independently.

    def lease_reserve(self, algo: str, slots, limiter_ids, requested,
                      now_ms: int):
        from ratelimiter_tpu.ops import lease as lease_ops

        slots = np.asarray(slots, dtype=np.int64)
        with self._lock, self._exclusive():
            rows = self.read_rows(algo, slots)
            granted, ws, new_rows, changed = lease_ops.host_reserve_rows(
                algo, rows, np.asarray(limiter_ids, dtype=np.int64),
                np.asarray(requested, dtype=np.int64),
                self.table.host_policy, int(now_ms))
            if changed.any():
                self.write_rows(algo, slots[changed], new_rows[changed])
        return granted, ws

    def lease_credit(self, algo: str, slots, limiter_ids, credit, grant_ws,
                     now_ms: int) -> np.ndarray:
        from ratelimiter_tpu.ops import lease as lease_ops

        slots = np.asarray(slots, dtype=np.int64)
        with self._lock, self._exclusive():
            rows = self.read_rows(algo, slots)
            credited, new_rows, changed = lease_ops.host_credit_rows(
                algo, rows, np.asarray(limiter_ids, dtype=np.int64),
                np.asarray(credit, dtype=np.int64),
                np.asarray(grant_ws, dtype=np.int64),
                self.table.host_policy, int(now_ms))
            if changed.any():
                self.write_rows(algo, slots[changed], new_rows[changed])
        return credited

    def block_until_ready(self) -> None:
        with self._lock, self._exclusive():
            jax.block_until_ready((self.sw_packed, self.tb_packed))
