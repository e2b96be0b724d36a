"""Transfer-minimal step variants: fused outputs and packed-bit scan steps.

The decision kernels are transfer-bound, not compute-bound: over a remote
TPU a device->host fetch costs ~100 ms of fixed latency regardless of size,
so the four separate output arrays of ``sw_step``/``tb_step`` cost four
round trips per micro-batch.  Two remedies, both pure wrappers around the
exact same decision math (differential-tested in tests/test_packed.py):

1. **Fused outputs** (``sw_step_fused`` / ``tb_step_fused``): all per-request
   outputs stacked into ONE ``i64[3, B]`` array — one fetch instead of four.
   Used by the engine's dict-returning acquire API.

2. **Scan-of-batches with bit-packed decisions** (``sw_scan_bits`` /
   ``tb_scan_bits``): K consecutive micro-batches executed in one dispatch
   via ``lax.scan`` (sequential semantics *across* sub-batches, exactly like
   K successive flushes), returning only the allow/deny decisions packed to
   1 bit each — ``uint8[K, B/8]``.  One dispatch + one ~K*B/8-byte fetch per
   K*B decisions.  This is the hyperscale hot path: the host learns
   allow/deny (all `tryAcquire` returns — RateLimiter.java:16-26) and
   nothing else; counts/remaining stay device-resident and are served by
   the peek kernels on demand.

Within each wrapper the underlying step is the single source of truth —
these functions contain no decision logic of their own.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ratelimiter_tpu.ops.sliding_window import sw_step_p
from ratelimiter_tpu.ops.token_bucket import tb_step_p

# -- fused full-output steps (one i64[3, B] fetch) ---------------------------
# All wrappers operate on the engine's packed-resident state form
# (i32[S, 6] sliding window, i32[S, 4] token bucket — see the ops modules).


def sw_step_fused(state, table, slots, limiter_ids, permits, now):
    """Row 0: allowed | mutated<<1;  row 1: observed;  row 2: cache_value."""
    state, out = sw_step_p(state, table, slots, limiter_ids, permits, now)
    flags = out.allowed.astype(jnp.int64) | (out.mutated.astype(jnp.int64) << 1)
    return state, jnp.stack([flags, out.observed, out.cache_value])


def tb_step_fused(state, table, slots, limiter_ids, permits, now):
    """Row 0: allowed;  row 1: observed;  row 2: remaining."""
    state, out = tb_step_p(state, table, slots, limiter_ids, permits, now)
    return state, jnp.stack(
        [out.allowed.astype(jnp.int64), out.observed, out.remaining])


def decode_sw_fused(arr):
    """numpy i64[3, B] -> dict matching DeviceEngine.sw_acquire's contract."""
    flags = arr[0]
    return {
        "allowed": (flags & 1).astype(bool),
        "mutated": (flags & 2).astype(bool),
        "observed": arr[1],
        "cache_value": arr[2],
    }


def decode_tb_fused(arr):
    return {
        "allowed": (arr[0] & 1).astype(bool),
        "observed": arr[1],
        "remaining": arr[2],
    }


# -- K-batch scan steps with bit-packed decisions ----------------------------
#
# Shapes: slots i32[K, B]; permits i32[K, B] (or None => all-ones); lids
# either a 0-d i32 (uniform tenant, materialized on device — saves a K*B
# transfer) or i32[K, B]; now i64[K] (non-decreasing batch stamps).
# Returns (new_state, uint8[K, ceil(B/8)]).


def _scan(step, state, table, slots, lids, permits, now):
    uniform_lid = lids.ndim == 0
    unit_permits = permits is None

    def body(st, xs):
        s = xs[0]
        i = 1
        if uniform_lid:
            l = lids  # 0-d: steps take the zero-table-gather scalar path
        else:
            l = xs[i]
            i += 1
        if unit_permits:
            p = jnp.ones(s.shape, dtype=jnp.int64)
        else:
            p = xs[i].astype(jnp.int64)
            i += 1
        t = xs[-1]
        st, out = step(st, table, s, l, p, t)
        return st, jnp.packbits(out.allowed)

    xs = (slots,)
    if not uniform_lid:
        xs += (lids,)
    if not unit_permits:
        xs += (permits,)
    xs += (now,)
    return jax.lax.scan(body, state, xs)


def sw_scan_bits(state, table, slots, lids, permits, now):
    return _scan(sw_step_p, state, table, slots, lids, permits, now)


def tb_scan_bits(state, table, slots, lids, permits, now):
    return _scan(tb_step_p, state, table, slots, lids, permits, now)
