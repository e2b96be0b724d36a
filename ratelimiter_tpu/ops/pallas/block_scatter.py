"""Pallas TPU tile sweep for sorted-unique row updates.

XLA's generic scatter on TPU costs ~45-90 ns per index, far above the
HBM-bandwidth floor for the same bytes.  But the sorted step's scatter
has structure XLA cannot exploit: the batch is sorted by slot and
carries at most one surviving write per slot (the segment-last row of
each sorted duplicate run).  That makes the scatter a SWEEP over the
table in its own layout:

- A narrow table ``(S, L)`` lives on the TPU as ``{0,1:T(8,128)}``:
  slots are the minor dimension, so ``state.T`` is ``(L, S)`` row-major,
  the same bytes, and one (8, 128) tile holds the rows of 128
  consecutive slots.
- Each grid step takes a lane-dense block of ``wb`` slots.  Its updates
  are the window ``[start[i], start[i+1])`` of the sorted update lane
  (a ``searchsorted`` of the block boundaries, scalar-prefetched).  A
  window holds at most ``wb`` updates, so one ``wb + 128``-lane window
  of the update arrays, at a tile-aligned element offset, covers it:
  the slots in SMEM, the rows lane-major in VMEM.
- A loop over the window applies each update with one masked store to
  the 128-slot tile it falls in: the update's row is rotated from its
  lane in the update window to the slot's lane, and only that lane is
  stored.  Updates are unique, so order does not matter, and the loop
  runs ``_UNROLL`` independent updates per iteration.

HBM traffic: read and write the table once, plus the updates — bound by
the table's bytes and a few vector ops per update.  Below a batch of
about one update per 512 table rows, or of 1024 updates, XLA's
per-index scatter is cheaper than moving the whole table, and
:func:`supported` says no (the crossovers are in PERF.md §6).  The
state output aliases the state input (in place in HBM, composing with
the caller's donated buffers).

Mosaic survival rules baked in (learned on v5e, see also
ops/pallas/solver.py): explicit 32-bit literals, traced with 64-bit
types off.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

T = 256          # slot alignment of tables sized by align_slots
_TILE = 128      # slots per (8, 128) tile of the transposed table
_TB = 16384      # slots per sweep block (PERF.md §6: the A/B)
_UNROLL = 16     # updates per loop iteration (same A/B)
_ROWS_PER_UPDATE = 512  # fewer updates than rows / this: XLA wins (same)
_MIN_BATCH = 1024       # and below this many, on any table (same)

_FLAG = os.environ.get("RATELIMITER_BLOCK_SCATTER", "1") == "1"
_INTERPRET = os.environ.get("RATELIMITER_BLOCK_SCATTER_INTERPRET", "0") == "1"
_probe_ok: bool | None = None


def _width(batch: int) -> int:
    """Sweep block width (slots) for a batch: at most half of it, so that
    a window (at most one block's width of updates) fits one update
    window of ``width + _TILE`` lanes."""
    return min(_TB, batch // 2 // _TILE * _TILE)


def _kernel(start_ref, state_ref, slot_ref, rows_ref, out_ref, *, n):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    i = pl.program_id(0)
    lo, hi = start_ref[i], start_ref[i + 1]
    base = i * out_ref.shape[1]
    off = _window_offset(lo, n, rows_ref.shape[1])
    out_ref[...] = state_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, (out_ref.shape[0], _TILE), 1)

    def update(j):
        k = j - off
        slot = slot_ref[0, k]
        row = rows_ref[:, pl.ds(pl.multiple_of(k & -_TILE, _TILE), _TILE)]
        # k and j share their lane (off is tile-aligned): rotate it to
        # the slot's lane and store that lane alone.
        row = pltpu.roll(row, (slot - j) & (_TILE - 1), 1)
        # Both starts int32, also where interpret mode discharges this
        # store with 64-bit types on.
        dst = (pl.ds(jnp.int32(0), row.shape[0]),
               pl.ds(pl.multiple_of((slot - base) & -_TILE, _TILE), _TILE))
        pltpu.store(out_ref.at[dst], row, mask=lane == (slot & (_TILE - 1)))

    def group(g, carry):
        # _UNROLL independent updates per iteration let their loads,
        # rotations and stores overlap.  Past the window's end an update
        # repeats its last one, which stores the same lane again.
        j0 = lo + g * _UNROLL
        for u in range(_UNROLL):
            update(jnp.minimum(j0 + u, hi - 1))
        return carry

    jax.lax.fori_loop(0, pl.cdiv(hi - lo, _UNROLL), group, jnp.int32(0))


def _window_offset(lo, n, w):
    """First lane of the ``w``-lane update window of a block whose
    updates start at ``lo``: tile-aligned, and inside the ``n`` lanes."""
    from jax.experimental import pallas as pl

    return pl.multiple_of(jnp.minimum(lo & -_TILE, n - w), _TILE)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _sweep(state_t, starts, keys, rows_t, interpret: bool = False):
    """state_t (L, S) i32, the table in its own layout; starts
    (cdiv(S, wb) + 1,) i32 window starts; keys (1, B) i32 sorted live
    slots (sentinel S after them); rows_t (L, B) i32 lane-major rows."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    lanes, s_rows = state_t.shape
    n = keys.shape[1]
    wb = _width(n)
    w = wb + _TILE

    def window(i, st):
        return (0, _window_offset(st[i], n, w))

    el = pl.Element
    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(pl.cdiv(s_rows, wb),),
        in_specs=[
            pl.BlockSpec((lanes, wb), lambda i, st: (0, i)),
            pl.BlockSpec((el(1), el(w)), window, memory_space=pltpu.SMEM),
            pl.BlockSpec((el(lanes), el(w)), window),
        ],
        out_specs=pl.BlockSpec((lanes, wb), lambda i, st: (0, i)),
    )
    return pl.pallas_call(
        functools.partial(_kernel, n=n),
        grid_spec=spec,
        out_shape=jax.ShapeDtypeStruct(state_t.shape, state_t.dtype),
        input_output_aliases={1: 0},  # state buffer updated in place
        interpret=interpret,
    )(starts, state_t, keys, rows_t)


def scatter_rows(state, sorted_slots, write_mask, rows,
                 interpret: bool | None = None):
    """Drop-in for the XLA drop-mode scatter over sorted-unique writes.

    state i32[S, L]; sorted_slots i32[B] ascending (padding < 0 first);
    write_mask bool[B] with at most one True per slot; rows i32[B, L].
    """
    if interpret is None:
        interpret = _INTERPRET
    s_rows, lanes = state.shape
    # Trace with 64-bit disabled: every value here is explicit int32, but
    # under jax_enable_x64 the grid/BlockSpec index plumbing emits i64
    # index arithmetic that crashes the TPU compiler outright (any
    # grid-ful pallas_call does, even a block copy — found on v5e).
    with jax.enable_x64(False):
        key = jnp.where(write_mask, sorted_slots, jnp.int32(s_rows))
        ops = jax.lax.sort(
            (key,) + tuple(rows[:, j] for j in range(lanes)), num_keys=1)
        upd_rows_t = jnp.stack(ops[1:], axis=0)  # (L, B), lane-major
        return _windowed_call(state, ops[0], upd_rows_t, interpret)


def _windowed_call(state, key_sorted, upd_rows_t, interpret):
    """Shared tail of both entry points: the window starts of every
    sweep block over the sorted key lane, then the sweep over the
    transposed table (a bitcast of its TPU layout)."""
    s_rows, _ = state.shape
    wb = _width(key_sorted.shape[0])
    bounds = jnp.minimum(
        jnp.arange(-(-s_rows // wb) + 1, dtype=jnp.int32) * wb, s_rows)
    starts = jnp.searchsorted(key_sorted, bounds).astype(jnp.int32)
    return _sweep(state.T, starts, key_sorted.reshape(1, -1), upd_rows_t,
                  interpret=interpret).T


def scatter_rows_presorted(state, sorted_slots, write_mask, rows,
                           interpret: bool | None = None):
    """:func:`scatter_rows` minus the compaction sort, for callers whose
    live updates already arrive sorted by slot with every masked-out
    lane at the TAIL (the host-sorted digest path — the C index sorts
    uniques before dispatch).  Skipping the ``lax.sort`` removes both
    its runtime and its super-linear XLA:TPU compile cliff, so this
    path has no practical lane-count ceiling."""
    if interpret is None:
        interpret = _INTERPRET
    s_rows, lanes = state.shape
    with jax.enable_x64(False):
        # Masked lanes are at the tail, so mapping them to the sentinel
        # (s_rows) preserves ascending order.
        key = jnp.where(write_mask, sorted_slots, jnp.int32(s_rows))
        return _windowed_call(state, key, rows.T, interpret)


def align_slots(n: int) -> int:
    """Smallest multiple of T at or above ``n`` — the num_slots
    alignment that lets the sweep engage (supported() requires whole
    128-slot tiles).  Benchmarks and deployments that want the presorted
    digest path should size their tables with this."""
    return -(-int(n) // T) * T


def supported(state_shape, batch: int) -> bool:
    """Static gate on the shapes: whole tiles of table and batch, and
    enough updates that sweeping the table beats XLA's per-index
    scatter (at least ``_MIN_BATCH``, and one per ``_ROWS_PER_UPDATE``
    rows: the crossovers measured on v5e)."""
    try:
        from jax.experimental import pallas as pl  # noqa: F401
    except Exception:  # noqa: BLE001
        return False
    rows = state_shape[0]
    return (rows % _TILE == 0 and rows >= _TILE and batch % _TILE == 0
            and batch >= _MIN_BATCH and batch * _ROWS_PER_UPDATE >= rows)


def _probe() -> bool:
    """One-time self-check on this platform: tiny scatter vs XLA truth."""
    global _probe_ok
    if _probe_ok is None:
        from ratelimiter_tpu.ops.pallas import (
            probe_failed,
            refuse_interpret_on_tpu,
        )

        refuse_interpret_on_tpu("block_scatter", _INTERPRET,
                                "RATELIMITER_BLOCK_SCATTER_INTERPRET")
        try:
            rng = np.random.default_rng(7)
            s = jnp.asarray(rng.integers(0, 1 << 30, (2 * T, 3), np.int32))
            slots = np.sort(rng.choice(2 * T, size=2 * T, replace=True))
            mask = np.r_[np.diff(slots) != 0, True]
            rows = rng.integers(-(1 << 30), 1 << 30, (2 * T, 3), np.int32)
            got = np.asarray(scatter_rows(
                s, jnp.asarray(slots.astype(np.int32)), jnp.asarray(mask),
                jnp.asarray(rows), interpret=_INTERPRET))
            want = np.asarray(s).copy()
            want[slots[mask]] = rows[mask]
            ok = bool((got == want).all())
        except Exception as exc:  # noqa: BLE001 — verdict below
            ok = probe_failed("block_scatter",
                              f"{type(exc).__name__}: {exc}")
        else:
            if not ok:
                ok = probe_failed("block_scatter",
                                  "mismatch against the XLA scatter")
        _probe_ok = ok
    return _probe_ok


def settle() -> bool:
    """Resolve the support probe eagerly (engine init calls this before
    any step kernel compiles — a probe firing lazily inside another
    program's lowering would nest compiles).  Respects the
    RATELIMITER_BLOCK_SCATTER kill switch: disabled means no Pallas
    compile at all."""
    if not _FLAG:
        return False
    if not (_INTERPRET or jax.default_backend() == "tpu"):
        return False
    return _probe()


def enabled(state_shape, batch: int) -> bool:
    if not _FLAG or not supported(state_shape, batch):
        return False
    if not (_INTERPRET or jax.default_backend() == "tpu"):
        return False
    return _probe()
