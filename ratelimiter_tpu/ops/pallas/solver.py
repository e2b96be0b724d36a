"""Pallas TPU kernel for the segmented threshold-recurrence solver.

The XLA implementation (ops/segments.py:solve_threshold_recurrence) runs the
sandwich iteration as a ``lax.while_loop`` whose per-iteration buffers round-
trip through HBM.  This kernel keeps the whole sorted batch resident in VMEM
and iterates in place: one launch, log-depth masked segmented scans on the
VPU, no HBM traffic between iterations.

Arithmetic: int32 with saturating adds.  Exactness argument:

- Sliding window (w == 1): all quantities are counts bounded by the batch
  size and max_permits; thresholds are clamped to SAT, and any count beyond
  SAT would reject anyway.
- Token bucket: the condition  W + req <= v1  has every term a multiple of
  2**TOKEN_FP_SHIFT (req = permits * 1000 * 2**s), so both sides can be
  right-shifted by s exactly (callers pass u' = (v1 - req) >> s and
  w' = req >> s = permits * 1000).  Within-segment sums can still overflow
  int32 for pathological hot segments, so the scan saturates at SAT
  (sized so 2*SAT fits int32 — the clamp runs after each add) while
  thresholds clip to SAT-1; a saturated prefix therefore always compares
  greater and correctly rejects.  min(a+b, SAT) is associative over
  non-negatives, so saturation commutes with the scan.

The kernel is gated: ``solve_threshold_recurrence_auto`` tries the Pallas
path when enabled (RATELIMITER_PALLAS=1) and the platform supports it,
falling back to the XLA implementation otherwise — decisions are identical
(differential-tested in tests/test_pallas_solver.py).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from ratelimiter_tpu.ops import segments as _xla

# Saturation ceiling: 2*SAT must fit int32 so two adjacent saturated
# lanes can add without wrapping (the scan clamps AFTER the add), and
# thresholds are clipped to SAT-1 so a saturated prefix always rejects.
SAT = (1 << 30) - 1


def _ensure_stack() -> None:
    """Raise Python's recursion limit for kernel lowering.

    Mosaic's jaxpr lowering recurses per equation and pltpu.roll's
    tracing recurses with the shift amount, so the log-depth unroll
    needs ~n/2 frames at the largest shift — ~16K at the 32K-lane
    dispatch ceiling, far past the default 1000.  The raise is sticky
    (process-global): lowering continues inside jit internals after this
    frame returns, so a scoped save/restore cannot cover it.  CPython
    3.12 keeps Python-to-Python calls off the C stack, so the depth is
    safe on default 8 MB thread stacks.
    """
    import sys

    if sys.version_info < (3, 12):
        # Pre-3.12 CPython keeps Python calls on the C stack: a 100K
        # limit could convert a clean RecursionError (-> XLA fallback
        # via the probe) into a segfault.  Leave the default; the probe
        # will fail and the XLA solver serves instead.
        return
    if sys.getrecursionlimit() < 100000:
        sys.setrecursionlimit(100000)


def _solver_kernel(u_ref, w_ref, segfirst_ref, inc_ref, *, n: int):
    """Whole-batch solver in one VMEM block.

    u, w: i32[1, n]; segfirst: i32[1, n] — index of each element's segment
    head; inc (out): i32[1, n].
    """
    # Everything stays (1, n): Mosaic's TPU lowering handles 2D slices,
    # concats, and reductions, while rank-1 forms of the same ops hit
    # NotImplemented/recursion walls (found empirically on v5e).
    u = u_ref[...]
    w = w_ref[...]
    seg_first = segfirst_ref[...]
    idx = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)

    def seg_cumsum_excl(x):
        """Saturating segmented EXCLUSIVE scan.

        The exclusive sum is computed directly — shift x down one lane
        within its segment, then run the masked Hillis-Steele inclusive
        scan over the shifted values — so saturation clamps the
        exclusive prefix itself.  (Deriving it as inclusive-minus-own
        would UNDERestimate clamped prefixes by the element's own
        weight, admitting requests a saturated prefix must reject.)
        Values never leave the segment, so magnitudes stay
        segment-local.
        """
        import numpy as np

        from jax.experimental.pallas import tpu as pltpu

        # Circular roll (a supported Mosaic primitive; concatenate
        # recurses in lowering).  The wrap-around lanes land at
        # idx < d, where idx - d < 0 <= seg_first masks them off.
        # Literals must be explicit 32-bit under jax_enable_x64: a
        # weak python int turns the shift into an i64 scalar
        # (tpu.dynamic_rotate verification error) and an i64 `where`
        # arm sends Mosaic's convert-element-type lowering into
        # infinite recursion.
        prev_ok = (idx - 1) >= seg_first
        v = jnp.where(prev_ok, pltpu.roll(x, np.int32(1), 1), jnp.int32(0))
        d = 1
        while d < n:  # static log2(n) unroll
            shifted = pltpu.roll(v, np.int32(d), 1)
            ok = (idx - d) >= seg_first
            v = jnp.minimum(v + jnp.where(ok, shifted, jnp.int32(0)),
                            jnp.int32(SAT))
            d *= 2
        return v

    def step(x):
        s = seg_cumsum_excl(jnp.minimum(w * x, SAT))
        return (s <= u).astype(jnp.int32)

    def cond(carry):
        lo, hi, it = carry
        # Reduce through i32: Mosaic only converts 32-bit reductions to
        # scalars (a bool `any` trips a float64 path on TPU).
        diff = jnp.max(jnp.abs(lo - hi))
        return jnp.logical_and(diff > 0, it < n + 2)

    def body(carry):
        lo, hi, it = carry
        return step(hi), step(lo), it + 1

    lo0 = jnp.zeros((1, n), jnp.int32)
    hi0 = jnp.ones((1, n), jnp.int32)
    lo, _, _ = jax.lax.while_loop(cond, body, (lo0, hi0, jnp.int32(0)))
    inc_ref[...] = lo


@functools.partial(jax.jit, static_argnames=("interpret",))
def pallas_solve(u32, w32, seg_first, interpret: bool = False):
    """Run the Pallas solver on i32 inputs shaped [n].

    Inputs are right-padded to a lane-aligned width (Mosaic mishandles
    tiny/unaligned rank-2 shapes): padded lanes carry u = -1 (never
    pass), and their seg_first is +inf-ish so the masked scan leaves
    them inert; padding sits at the tail, so it can never feed a real
    lane (the scan only looks backward).
    """
    from jax.experimental import pallas as pl

    _ensure_stack()
    n = u32.shape[0]
    n_pad = max(256, -(-n // 128) * 128)
    if n_pad != n:
        pad = n_pad - n
        u32 = jnp.concatenate([u32, jnp.full((pad,), -1, jnp.int32)])
        w32 = jnp.concatenate([w32, jnp.zeros((pad,), jnp.int32)])
        seg_first = jnp.concatenate(
            [seg_first, jnp.full((pad,), SAT, jnp.int32)])
    kernel = functools.partial(_solver_kernel, n=n_pad)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((1, n_pad), jnp.int32),
        interpret=interpret,
    )(u32.reshape(1, n_pad), w32.reshape(1, n_pad),
      seg_first.reshape(1, n_pad))
    return out[0, :n]


def seg_first_index(first: jnp.ndarray) -> jnp.ndarray:
    """Index of each element's segment head (i32), from the boolean
    first-occurrence mask."""
    n = first.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    return jax.lax.associative_scan(jnp.maximum, jnp.where(first, idx, 0))


# ---------------------------------------------------------------------------
# Auto dispatcher
# ---------------------------------------------------------------------------

_PALLAS_FLAG = os.environ.get("RATELIMITER_PALLAS", "1") == "1"
# Interpret-mode override so the Pallas path can be exercised on CPU in tests.
_PALLAS_INTERPRET = os.environ.get("RATELIMITER_PALLAS_INTERPRET", "0") == "1"
# Single-launch lane ceiling: the log-depth unroll's temporaries grow with
# lane count and the TPU compiler falls over past 16K lanes (measured on
# v5e with the exclusive-scan kernel); larger batches take the XLA solver.
# The micro-batcher's buckets (<= max_batch 8192) sit comfortably under
# the ceiling — exactly the traffic the VMEM-resident iteration helps.
_PALLAS_MAX_LANES = 1 << 14
_pallas_ok: bool | None = None


def _pallas_supported() -> bool:
    global _pallas_ok
    if _pallas_ok is None:
        if not (_PALLAS_INTERPRET or jax.default_backend() == "tpu"):
            _pallas_ok = False
            return False
        from ratelimiter_tpu.ops.pallas import (
            probe_failed,
            refuse_interpret_on_tpu,
        )

        refuse_interpret_on_tpu("solver", _PALLAS_INTERPRET,
                                "RATELIMITER_PALLAS_INTERPRET")
        try:
            test = jnp.asarray([5, 5, -1], dtype=jnp.int32)
            w = jnp.ones(3, dtype=jnp.int32)
            sf = jnp.zeros(3, dtype=jnp.int32)
            out = pallas_solve(test, w, sf, interpret=_PALLAS_INTERPRET)
            got = list(jax.device_get(out))
        except Exception as exc:  # noqa: BLE001 — verdict below
            _pallas_ok = probe_failed("solver",
                                      f"{type(exc).__name__}: {exc}")
        else:
            _pallas_ok = got == [1, 1, 0] or probe_failed(
                "solver", f"mismatch: got {got}, want [1, 1, 0]")
    return _pallas_ok


# ---------------------------------------------------------------------------
# Measured micro-batch election (r6; generalized into
# ops/pallas/election.py in r7 — this module keeps only its measure
# function and delegates the verdict/caching/override machinery).
#
# r05's A/B (a remote-link run before PR 1) put the Pallas solver at x0.91 of the XLA path on the
# micro-batch traffic it exists to serve — a supported kernel is not
# necessarily a WINNING kernel, and which one wins varies by device
# generation and toolchain.  The auto dispatcher runs a one-time timed
# A/B at a representative micro-batch shape (duplicate segments,
# batcher-bucket lanes) and disables the Pallas path when XLA wins; the
# verdict is disk-cached per (platform, device kind, path) next to the
# compile cache.  RATELIMITER_PALLAS_ELECT=on|off|auto overrides (per
# path: RATELIMITER_PALLAS_ELECT_MICRO).  Interpret mode skips the
# election (it exists to exercise the kernel, not to win).


def _measure_micro_ab() -> dict:
    """Best-of-5 wall of one micro-batch solve, Pallas vs XLA, at the
    shape the kernel serves (8192 lanes, 4-deep segments)."""
    import time

    import numpy as np

    n = 8192
    rng = np.random.default_rng(17)
    seg = np.sort(rng.integers(0, n // 4, n))
    first = np.ones(n, dtype=bool)
    first[1:] = seg[1:] != seg[:-1]
    u = jnp.asarray(rng.integers(0, 100, n).astype(np.int64))
    w = jnp.asarray(rng.integers(1, 5, n).astype(np.int64))
    first_j = jnp.asarray(first)

    def run_pallas(u, w, first):
        sf = seg_first_index(first)
        u32 = jnp.clip(u, -1, SAT - 1).astype(jnp.int32)
        w32 = jnp.clip(w, 0, SAT).astype(jnp.int32)
        return pallas_solve(u32, w32, sf,
                            interpret=_PALLAS_INTERPRET).astype(jnp.int64)

    def run_xla(u, w, first):
        return _xla.solve_threshold_recurrence(u, w, first)

    def best_of(fn):
        f = jax.jit(fn)
        jax.block_until_ready(f(u, w, first_j))  # compile + settle
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(f(u, w, first_j))
            best = min(best, time.perf_counter() - t0)
        return best

    return {"pallas_s": best_of(run_pallas), "xla_s": best_of(run_xla),
            "lanes": n}


def _micro_election() -> bool:
    """True when the Pallas solver should serve micro-batches on this
    device (measured; cached in-process and on disk by the shared
    per-path election — ops/pallas/election.py, path ``micro``)."""
    from ratelimiter_tpu.ops.pallas import election

    return election.measured_election("micro", _measure_micro_ab,
                                      interpret=_PALLAS_INTERPRET)


def settle() -> bool:
    """Resolve the support probe (and the micro-batch election) eagerly
    — engine init calls this before any step kernel compiles; a probe
    firing lazily inside another program's lowering would nest remote
    compiles.  Respects the RATELIMITER_PALLAS kill switch: disabled
    means no Pallas compile at all.  Returns whether the Pallas solver
    will actually SERVE (supported AND elected)."""
    if not _PALLAS_FLAG:
        return False
    if not _pallas_supported():
        return False
    return _micro_election()


def solve_threshold_recurrence_auto(u, w, first, shift: int = 0):
    """Drop-in for segments.solve_threshold_recurrence with optional Pallas.

    Inputs are int64 (engine convention).  ``shift`` right-shifts u and w
    into the int32 domain; exact when every weight is a multiple of
    2**shift (token bucket: shift=TOKEN_FP_SHIFT since req_fp =
    permits * 1000 * 2**shift — the arithmetic shift floors u, and
    W <= u  <=>  W>>s <= floor(u/2**s) for W a multiple of 2**s).
    Sliding window uses shift=0.
    """
    if (_PALLAS_FLAG and u.shape[0] <= _PALLAS_MAX_LANES
            and _pallas_supported() and _micro_election()):
        u_s = jnp.right_shift(u, shift) if shift else u
        w_s = jnp.right_shift(w, shift) if shift else w
        # Thresholds clip BELOW the saturation ceiling so a saturated
        # prefix sum (== SAT) compares greater and correctly rejects.
        u32 = jnp.clip(u_s, -1, SAT - 1).astype(jnp.int32)
        w32 = jnp.clip(w_s, 0, SAT).astype(jnp.int32)
        sf = seg_first_index(first)
        out = pallas_solve(u32, w32, sf, interpret=_PALLAS_INTERPRET)
        return out.astype(jnp.int64)
    return _xla.solve_threshold_recurrence(u, w, first)
