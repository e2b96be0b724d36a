"""Flat mega-batch decision steps — the streaming hot path, rebuilt.

The scan-of-batches path (ops/packed.py) runs K sequential sub-batches per
dispatch for sequential semantics across sub-batches.  But every sub-batch
in one dispatch shares a single timestamp, and at equal timestamps K
sequential sub-batches are decision-identical to ONE flat sorted batch of
K*B requests: a key's requests still form one contiguous segment in arrival
order (stable sort), refill/window-roll at the shared `now` happens once
per slot either way, and a sub-batch that consumed from a slot leaves
exactly the state the flat segment prefix would (tests/test_flat.py drives
both paths on identical streams to prove it).

Flattening unlocks three structural wins over the scan path, each measured
on the v5e reached over the pre-PR-1 remote link (bench/profile_step.py, B=4M, S=1M):

1. **Payload-carrying sorts** (lax.sort multi-operand, ~17 ms) replace
   argsort + separate 1-lane permutation gathers (~21 ms + 40 ms each for
   the forward and inverse permutes).  The unsort of the decision bits is
   itself a 2-operand sort keyed by the forward order.

2. **Closed-form segment solve** for uniform-permit streams (the
   ``permits=None`` default): within a segment every request carries the
   same weight w and threshold u (one slot == one (limiter, key), so
   policy, refilled balance, and permits are segment-constant), which
   collapses the threshold recurrence

       inc[j] = [ sum_{i<j in seg} w*inc[i] <= u ]

   to ``inc[j] = rank_j * w <= u`` — prior passes before a passing rank
   are exactly ``rank_j``.  No sandwich iteration, no segmented cumsums;
   one log-depth cummax (segment head index) plus elementwise math.
   Weighted per-request permits fall back to the sandwich solver.

3. **One gather / one scatter** of K*B rows instead of K each (same index
   count, but the scatter — 179 ms per 4M rows vs 29 ms for the gather —
   is then replaceable wholesale by the Pallas block-scatter).

Decision math references: semantics/oracle.py (the executable spec);
reference behaviors SlidingWindowRateLimiter.java:86-131 (weighted
two-window estimate, Q1/Q2 quirks) and TokenBucketRateLimiter.java:38-68
(Lua refill/consume, write-only-on-allow).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ratelimiter_tpu.core.config import TOKEN_FP_ONE
from ratelimiter_tpu.ops.segments import (
    first_occurrence,
    last_occurrence,
    segment_totals,
    segmented_cumsum_exclusive,
)
from ratelimiter_tpu.ops.pallas.solver import solve_threshold_recurrence_auto
from ratelimiter_tpu.ops.sliding_window import _rolled, _sw_decode, _sw_encode
from ratelimiter_tpu.ops.token_bucket import _refilled, _tb_decode, _tb_encode
from ratelimiter_tpu.ops.scatter import scatter_rows_sorted


def _sort_by_slot(slots, *payloads):
    """Stable multi-operand sort by slot id; payloads ride along (no
    separate permutation gathers).  Returns (sorted_slots, order, sorted
    payloads...); ``order`` is the forward permutation for unsorting."""
    iota = jnp.arange(slots.shape[0], dtype=jnp.int32)
    out = jax.lax.sort((slots, iota) + payloads, num_keys=1, is_stable=True)
    return out[0], out[1], out[2:]


def _unsort_bits(order, allowed):
    """Arrival-order decision bitmask from sorted-order decisions: one
    2-operand sort keyed by the forward order (a permutation), then
    packbits.  Cheaper than a 1-lane inverse-permutation gather."""
    _, back = jax.lax.sort((order, allowed.astype(jnp.uint8)), num_keys=1)
    return jnp.packbits(back)


def _seg_rank(s, first):
    """Rank of each request within its segment (0-based arrival order)."""
    idx = jnp.arange(s.shape[0], dtype=jnp.int32)
    head = jax.lax.associative_scan(jnp.maximum, jnp.where(first, idx, 0))
    return (idx - head).astype(jnp.int64)


def _solve_uniform(u, w, rank, first, permits_none: bool):
    """inc for the recurrence; closed form when weights are segment-uniform
    (permits is None), sandwich solver otherwise.  Returns i64 0/1."""
    if permits_none:
        return (rank * w <= u).astype(jnp.int64)
    return solve_threshold_recurrence_auto(u, w, first)


# ---------------------------------------------------------------------------
# Token bucket
# ---------------------------------------------------------------------------

def tb_flat_bits(packed, table, slots, lids, permits, now):
    """One flat sorted mega-batch of token-bucket decisions.

    slots i32[B] (< 0 = padding/force-deny); lids 0-d i32 or i32[B];
    permits None (unit) or i32[B]; now i64 scalar.  Returns
    (new_packed, uint8[B/8] arrival-order allow bits).  Decisions are
    identical to tb_step_p over the same batch (and to K sequential
    sub-batches at the same `now` — module docstring).
    """
    scalar_lid = jnp.ndim(lids) == 0
    payloads = ()
    if not scalar_lid:
        payloads += (lids,)
    if permits is not None:
        payloads += (permits,)
    s, order, payloads = _sort_by_slot(slots, *payloads)
    payloads = list(payloads)
    lid = lids if scalar_lid else payloads.pop(0)
    p = None if permits is None else payloads.pop(0).astype(jnp.int64)

    valid = s >= 0
    sc = jnp.clip(s, 0, packed.shape[0] - 1)
    lidc = lid if scalar_lid else jnp.clip(lid, 0, table.cap_fp.shape[0] - 1)
    cap = table.cap_fp[lidc]
    rate = table.rate_fp[lidc]
    maxp = table.max_permits[lidc]
    ttl2 = table.ttl2_ms[lidc]

    rows = _tb_decode(packed[sc])
    v1 = _refilled(rows, cap, rate, ttl2, now)

    req = TOKEN_FP_ONE if permits is None else p * TOKEN_FP_ONE
    pre_ok = valid & ((1 if permits is None else p) <= maxp)
    u = jnp.where(pre_ok, v1 - req, jnp.int64(-1))
    first = first_occurrence(s)
    rank = _seg_rank(s, first)
    inc = _solve_uniform(u, req if permits is not None else
                         jnp.int64(TOKEN_FP_ONE), rank, first,
                         permits is None)
    allowed = (inc == 1) & valid

    lastm = last_occurrence(s) & valid
    if permits is None:
        # Segment totals in closed form: the first max(0, u//w + 1) ranks
        # pass, clamped to the segment length (= rank+1 at its last row).
        n_alw = jnp.where(u >= 0,
                          jnp.minimum(rank + 1, u // TOKEN_FP_ONE + 1),
                          jnp.int64(0))
        tot_w = n_alw * TOKEN_FP_ONE
        any_inc = n_alw > 0
    else:
        tot_w = segment_totals(req * inc, first)
        any_inc = segment_totals(inc, first) > 0
    tokens_new = jnp.where(any_inc, v1 - tot_w, rows[0])
    last_new = jnp.where(any_inc, jnp.maximum(now, 1), rows[1])

    packed_new = scatter_rows_sorted(
        packed, s, lastm, _tb_encode(tokens_new, last_new))
    return packed_new, _unsort_bits(order, allowed)


# ---------------------------------------------------------------------------
# Sliding window
# ---------------------------------------------------------------------------

def sw_flat_bits(packed, table, slots, lids, permits, now):
    """Flat sliding-window counterpart of :func:`tb_flat_bits` (same
    contract; decision math mirrors ops/sliding_window.py:sw_step_p
    including the Q1/Q2 increment-by-1 and post-increment-check quirks)."""
    scalar_lid = jnp.ndim(lids) == 0
    payloads = ()
    if not scalar_lid:
        payloads += (lids,)
    if permits is not None:
        payloads += (permits,)
    s, order, payloads = _sort_by_slot(slots, *payloads)
    payloads = list(payloads)
    lid = lids if scalar_lid else payloads.pop(0)
    p = (jnp.int64(1) if permits is None
         else payloads.pop(0).astype(jnp.int64))

    valid = s >= 0
    sc = jnp.clip(s, 0, packed.shape[0] - 1)
    lidc = lid if scalar_lid else jnp.clip(
        lid, 0, table.max_permits.shape[0] - 1)
    maxp = table.max_permits[lidc]
    win = table.window_ms[lidc]

    rows = _sw_decode(packed[sc])
    curr_ws, curr_e, prev_e, prev_dl_e = _rolled(rows, win, now)
    rem = now % win
    base = (prev_e * (win - rem)) // win

    u = jnp.where(valid, maxp - base - curr_e - p, jnp.int64(-1))
    first = first_occurrence(s)
    rank = _seg_rank(s, first)
    inc = _solve_uniform(u, jnp.ones_like(u), rank, first, permits is None)

    if permits is None:
        n_pass = jnp.maximum(u + 1, 0)          # segment-uniform
        S = jnp.minimum(rank, n_pass)           # prior incs at this rank
        tot = jnp.minimum(rank + 1, n_pass)     # segment total at its last
    else:
        S = segmented_cumsum_exclusive(inc, first)
        tot = segment_totals(inc, first)
    c_j = curr_e + S
    allowed = (inc == 1) & (c_j + 1 <= maxp) & valid

    lastm = last_occurrence(s) & valid
    any_inc = tot > 0
    curr_new = curr_e + tot
    samew = rows[0] == curr_ws
    cdl_new = jnp.where(any_inc, now + win, jnp.where(samew, rows[2], 0))
    curr_ws_b = jnp.broadcast_to(curr_ws, sc.shape).astype(jnp.int64)
    new_rows = _sw_encode(curr_ws_b, curr_new, cdl_new, prev_e, prev_dl_e)

    packed_new = scatter_rows_sorted(packed, s, lastm, new_rows)
    return packed_new, _unsort_bits(order, allowed)
