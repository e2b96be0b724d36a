"""Pallas TPU dense block-scatter for sorted-unique row updates.

XLA's generic scatter on TPU costs ~45 ns/index, far above the
HBM-bandwidth floor for the same bytes.  But the sorted step's scatter
has structure XLA cannot exploit: the batch is sorted by slot and
carries at most one surviving write per slot (the segment-last row of
each sorted duplicate run).  That makes the scatter expressible as a
DENSE sweep:

    for each aligned block of T consecutive state rows:
        the updates touching it sit in a contiguous window of the
        (compacted, slot-sorted) update array, at most T long
        -> load block + window into VMEM, select per row, write back

Pipeline:
1. Compact: one payload-carrying ``lax.sort`` moves masked-out lanes to
   the tail (key = slot for live updates, S sentinel otherwise), leaving
   live updates sorted by slot and unique; the update array is then
   TRANSPOSED (XLA-side) so the kernel reads (row-vector slots,
   lane-major rows) — rank-2 friendly shapes for Mosaic.
2. Window map: ``searchsorted`` of the T-aligned block boundaries over
   the compacted keys, divided down to block granularity — per state
   block i a scalar sigma[i] such that update-blocks [sigma[i],
   sigma[i]+1] cover every update for block i (<= T updates; any exact
   window start spans at most two aligned T-blocks).
3. One ``pallas_call`` over the S/T state blocks: per window the kernel
   builds the (T, T) match matrix t_slot == w_slot and SELECTS each
   row's matching update by two exact f32 matmuls over the update's
   16-bit halves (at most one match per row, so every dot-product has
   at most one nonzero term — exact in f32 regardless of magnitude).
   Slots are unique and the two windows are disjoint, so summing the
   per-window selections composes them.

HBM traffic: read S + 2B rows, write S rows — bandwidth-bound instead
of per-index-bound.  The state output aliases the state input (in-place
in HBM, composing with the caller's donated buffers).

Mosaic survival rules baked in (learned on v5e, see also
ops/pallas/solver.py): rank-2 everything, no 1-D slices/gathers,
explicit 32-bit literals under jax_enable_x64.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

T = 256          # state rows per block; S must divide by this

_FLAG = os.environ.get("RATELIMITER_BLOCK_SCATTER", "1") == "1"
_INTERPRET = os.environ.get("RATELIMITER_BLOCK_SCATTER_INTERPRET", "0") == "1"
_probe_ok: bool | None = None


def _select_window(eq_f, rows_ref):
    """Per-target-row selected update values for one window.

    eq_f: f32[T, T] 0/1 match matrix (at most one 1 per row).
    rows_ref: i32[lanes, T] window rows, lane-major.
    Returns (vals u32[T, lanes] — zeros where unmatched, hits f32-exact
    via 16-bit halves; match f32[T, 1] row match counts).
    """
    rows = rows_ref[...]
    # 16-bit halves in SIGNED i32 arithmetic (Mosaic crashes on
    # uint32 casts/bitcasts): both halves land in [0, 65535], exact in
    # f32; the left-shift recombine wraps into the sign bit, which is
    # exactly the original bit pattern.
    lo = (rows & jnp.int32(0xFFFF)).astype(jnp.float32)
    hi = ((rows >> jnp.int32(16)) & jnp.int32(0xFFFF)).astype(jnp.float32)
    dn = (((1,), (1,)), ((), ()))  # contract window axis of both
    # HIGHEST precision: the TPU's default bf16 matmul passes would
    # round the 16-bit halves; the 3-pass f32 mode keeps them exact.
    lo_s = jax.lax.dot_general(eq_f, lo, dn,
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)
    hi_s = jax.lax.dot_general(eq_f, hi, dn,
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)
    match = jnp.sum(eq_f, axis=1, keepdims=True)
    vals = ((hi_s.astype(jnp.int32) << jnp.int32(16))
            | lo_s.astype(jnp.int32))
    return vals, match


def _kernel(sigma_ref, state_ref, sl_a_ref, sl_b_ref, rw_a_ref, rw_b_ref,
            out_ref, *, lanes):
    del lanes  # shapes carry it
    from jax.experimental import pallas as pl

    block = state_ref[...]                       # (T, lanes)
    t_slot = (jnp.int32(T) * pl.program_id(0)
              + jax.lax.broadcasted_iota(jnp.int32, (T, 1), 0))
    eq_a = (sl_a_ref[...] == t_slot).astype(jnp.float32)   # (T, T)
    eq_b = (sl_b_ref[...] == t_slot).astype(jnp.float32)
    va, ma = _select_window(eq_a, rw_a_ref)
    vb, mb = _select_window(eq_b, rw_b_ref)
    # Windows are disjoint and slots unique: at most one nonzero term.
    vals = va | vb
    anym = (ma + mb) > 0.0
    out_ref[...] = jnp.where(anym, vals, block)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _block_scatter(state, upd_slots, upd_rows_t, sigma,
                   interpret: bool = False):
    """state (S, L) i32; upd_slots (1, B) i32 compacted sorted keys;
    upd_rows_t (L, B) i32 lane-major rows; sigma (S/T,) i32 aligned
    window starts (units of T)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s_rows, lanes = state.shape
    grid = s_rows // T
    kernel = functools.partial(_kernel, lanes=lanes)
    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((T, lanes), lambda i, sig: (i, 0)),
            pl.BlockSpec((1, T), lambda i, sig: (0, sig[i])),
            pl.BlockSpec((1, T), lambda i, sig: (0, sig[i] + 1)),
            pl.BlockSpec((lanes, T), lambda i, sig: (0, sig[i])),
            pl.BlockSpec((lanes, T), lambda i, sig: (0, sig[i] + 1)),
        ],
        out_specs=pl.BlockSpec((T, lanes), lambda i, sig: (i, 0)),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=spec,
        out_shape=jax.ShapeDtypeStruct(state.shape, state.dtype),
        input_output_aliases={1: 0},  # state buffer updated in place
        interpret=interpret,
    )(sigma, state, upd_slots, upd_slots, upd_rows_t, upd_rows_t)


def scatter_rows(state, sorted_slots, write_mask, rows,
                 interpret: bool | None = None):
    """Drop-in for the XLA drop-mode scatter over sorted-unique writes.

    state i32[S, L]; sorted_slots i32[B] ascending (padding < 0 first);
    write_mask bool[B] with at most one True per slot; rows i32[B, L].
    """
    if interpret is None:
        interpret = _INTERPRET
    s_rows, lanes = state.shape
    n = sorted_slots.shape[0]
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
    """Shared tail of both entry points: block-aligned window map over
    the sorted key lane, then the pallas_call."""
    s_rows, _ = state.shape
    n = key_sorted.shape[0]
    bounds = jnp.arange(s_rows // T, dtype=jnp.int32) * T
    starts = jnp.searchsorted(key_sorted, bounds).astype(jnp.int32)
    sigma = jnp.clip(starts // T, 0, n // T - 2)
    return _block_scatter(state, key_sorted.reshape(1, n), upd_rows_t,
                          sigma, interpret=interpret)


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
    """Smallest multiple of the block size T at or above ``n`` — the
    num_slots alignment that lets the dense sweeps engage (supported()
    requires state rows %% T == 0).  Benchmarks and deployments that
    want the presorted digest path should size their tables with
    this."""
    return -(-int(n) // T) * T


def supported(state_shape, batch: int) -> bool:
    """Static geometry gate: aligned table, window-coverable batch."""
    try:
        from jax.experimental import pallas as pl  # noqa: F401
    except Exception:  # noqa: BLE001
        return False
    s_rows = state_shape[0]
    return (s_rows % T == 0 and s_rows // T >= 1
            and batch >= 2 * T and batch % T == 0)


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


def _measure_ab() -> dict:
    """Timed A/B of the dense sweep vs XLA's drop-mode scatter at a
    representative sorted-unique digest shape (chained inside one jit,
    one fetched checksum — the device_rates.py method)."""
    import time

    s_rows, b, k_steps = 1 << 17, 1 << 15, 8
    rng = np.random.default_rng(3)
    slots = np.sort(rng.choice(s_rows, size=b, replace=False)
                    ).astype(np.int32)
    mask = np.ones(b, dtype=bool)
    slots_j, mask_j = jnp.asarray(slots), jnp.asarray(mask)
    rows = jnp.asarray(rng.integers(-(1 << 30), 1 << 30, (b, 4), np.int32))

    def xla_scatter(state, rows):
        widx = jnp.where(mask_j, slots_j, jnp.int32(s_rows))
        return state.at[widx].set(rows, mode="drop")

    def pallas_scatter(state, rows):
        return scatter_rows_presorted(state, slots_j, mask_j, rows,
                                      interpret=_INTERPRET)

    def best_of(fn):
        import functools as ft

        @ft.partial(jax.jit, donate_argnums=0)
        def chain(state, rows):
            def body(i, st):
                return fn(st, rows + i.astype(jnp.int32))

            st = jax.lax.fori_loop(0, k_steps, body, state)
            return st, jnp.sum(st[:8].astype(jnp.int64))

        st, acc = chain(jnp.zeros((s_rows, 4), jnp.int32), rows)
        int(np.asarray(acc))  # compile + settle
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            st, acc = chain(st, rows)
            int(np.asarray(acc))
            best = min(best, time.perf_counter() - t0)
        return best / k_steps

    return {"pallas_s": best_of(pallas_scatter),
            "xla_s": best_of(xla_scatter),
            "updates": b, "state_rows": s_rows}


def _elected() -> bool:
    """Measured per-path election (ops/pallas/election.py): the sweep
    only serves where it beats XLA's per-index scatter on THIS device."""
    from ratelimiter_tpu.ops.pallas import election

    return election.measured_election("block_scatter", _measure_ab,
                                      interpret=_INTERPRET)


def settle() -> bool:
    """Resolve the support probe (and the measured election) eagerly
    (engine init calls this before any step kernel compiles — a probe
    firing lazily inside another program's lowering would nest remote
    compiles).  Respects the RATELIMITER_BLOCK_SCATTER kill switch:
    disabled means no Pallas compile at all."""
    if not _FLAG:
        return False
    if not (_INTERPRET or jax.default_backend() == "tpu"):
        return False
    return _probe() and _elected()


def enabled(state_shape, batch: int) -> bool:
    if not _FLAG or not supported(state_shape, batch):
        return False
    if not (_INTERPRET or jax.default_backend() == "tpu"):
        return False
    return _probe() and _elected()
