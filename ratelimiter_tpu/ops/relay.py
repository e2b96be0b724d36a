"""Relay decision steps — the unit-permit streaming hot path.

The host slot index already walks every request of a batch in arrival
order to assign slots, so it can ALSO hand the device each request's
within-batch duplicate rank and each unique slot's segment count for
free (native/slot_index.cpp:assign_batch_uniques — O(1) extra work per
request, epoch-tagged per-slot scratch; :func:`rebuild_words` turns
that digest output into the per-request word stream when needed).  With unit permits the whole threshold
recurrence of the sorted step (ops/flat.py) has a closed form in that
rank: within a segment every request carries the same weight and
threshold, so request j passes iff ``rank_j < avail`` and the slot's
single write needs only the segment length (= rank + 1 at the last
occurrence).  That deletes the device-side sort, segment scans, and
unsort entirely:

    decode word -> gather row -> elementwise math -> masked scatter
                                                  -> packbits

which is the entire step.  On XLA:TPU this matters twice over: the
sort/associative-scan ops the sorted step leans on compile
super-linearly in lane count (minutes at 2M lanes) and run far above the
bandwidth floor, while gather/scatter/elementwise compile in ~1 s at any
size and run near memory speed (bench/profile_compile.py,
bench/profile_ops.py).

Everything about a request travels in ONE uint32 word:

    bit 0                   last-occurrence flag
    bits 1 .. rank_bits     duplicate rank, clamped to 2^rank_bits - 1
                            (the clamp value is a sentinel: the layout
                            guarantees 2^rank_bits - 2 >= every
                            registered limiter's max_permits, and no
                            request with rank above max_permits can ever
                            be allowed, so "clamped" decides as deny)
    bits rank_bits+1 .. 31  slot id; the all-ones padding word decodes
                            to a slot field >= num_slots => invalid lane

so the host->device traffic is 4 bytes/request — the same as the sorted
step's bare slot lane, with the rank riding in bits the slot never uses.

Rank clamping is exact, not approximate: ``avail <= max_permits``
always (token bucket: refilled tokens <= capacity; sliding window:
remaining budget <= max_permits), so any rank at or past the clamp
ceiling compares >= avail and is denied either way, and the write's
``n_allowed = min(seg_len, avail)`` saturates identically.

Decision math references: semantics/oracle.py (the executable spec);
ops/flat.py (the sorted step these decisions are bit-identical to —
tests/test_relay.py drives both on identical streams); reference
behaviors SlidingWindowRateLimiter.java:86-131 and
TokenBucketRateLimiter.java:38-68.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ratelimiter_tpu.core.config import TOKEN_FP_ONE
from ratelimiter_tpu.ops.sliding_window import _rolled, _sw_decode, _sw_encode
from ratelimiter_tpu.ops.token_bucket import _refilled, _tb_decode, _tb_encode


def relay_usable(rank_bits: int, max_permits_registered: int) -> bool:
    """Whether the word layout can carry the engine's traffic: the rank
    clamp ceiling (2^rank_bits - 1, a deny sentinel) must exceed every
    registered limiter's max_permits.  Shared by the single-device and
    sharded engines so the invariant lives in one place."""
    return (rank_bits >= 1
            and (1 << rank_bits) - 2 >= max_permits_registered)


def counts_dtype(max_permits_registered: int):
    """Smallest numpy dtype that can carry per-unique allowed counts
    (None if none fits — the per-request relay path has no such bound)."""
    import numpy as np

    if max_permits_registered <= 255:
        return np.uint8
    if max_permits_registered <= 65535:
        return np.uint16
    return None


def wire_costs(multi_lid: bool, resident_lids: bool = False):
    """(bytes per unique in digest mode, bytes per request in words mode)
    — the constants both stream loops use to elect a mode and to grow
    chunks toward the wire budget.  Digest: 4B uword + 1-2B count back,
    plus a 4B per-unique lid lane for multi-tenant callers that don't
    keep lids device-resident (the single-device loop does — its deltas
    are charged separately; the sharded loop ships the lane).  Words
    mode: 4B word + bits back (+4B lid lane when multi)."""
    digest = 6.0 if (not multi_lid or resident_lids) else 10.0
    return digest, (8.125 if multi_lid else 4.125)


def rebuild_words(uwords, uidx, rank, rank_bits: int):
    """Per-request (slot | clamped rank | last) words from the digest
    output — the words-mode wire format, built host-side in numpy.  For
    an over-clamp segment the flagged lane is the one at rank clamp-1
    rather than the true last; the device write saturates to the same
    value either way (n_allowed = min(avail, seg_len) with avail below
    the clamp)."""
    import numpy as np

    rank_mask = np.uint32((1 << rank_bits) - 1)
    slotf = uwords >> np.uint32(rank_bits + 1)
    cnt_cl = (uwords >> np.uint32(1)) & rank_mask
    return ((slotf[uidx] << np.uint32(rank_bits + 1))
            | (np.minimum(rank.astype(np.uint32), rank_mask)
               << np.uint32(1))
            | (rank.astype(np.uint32) + 1 == cnt_cl[uidx]))


def decode_words(words, rank_bits: int, num_slots: int):
    """uint32[B] -> (slot i32[B], rank i64[B], last bool[B], valid bool[B]).

    Padding lanes (0xFFFFFFFF) decode to slot >= num_slots => invalid.
    """
    w = words.astype(jnp.uint32)
    slot = (w >> (rank_bits + 1)).astype(jnp.int32)
    rank = ((w >> 1) & jnp.uint32((1 << rank_bits) - 1)).astype(jnp.int64)
    last = (w & 1) == 1
    valid = slot < num_slots
    return slot, rank, last, valid


def tb_relay_bits(packed, table, words, lids, now, *, rank_bits: int):
    """One relay batch of unit-permit token-bucket decisions.

    words uint32[B]; lids 0-d i32 (single tenant) or i32[B] lane; now i64
    scalar.  Returns (new_packed, uint8[B/8] arrival-order allow bits).
    Decisions are identical to tb_flat_bits(permits=None) on the same
    batch (tests/test_relay.py).
    """
    num_slots = packed.shape[0]
    slot, rank, last, valid = decode_words(words, rank_bits, num_slots)
    sc = jnp.where(valid, slot, 0)
    scalar_lid = jnp.ndim(lids) == 0
    lidc = lids if scalar_lid else jnp.clip(
        lids, 0, table.cap_fp.shape[0] - 1)
    cap = table.cap_fp[lidc]
    rate = table.rate_fp[lidc]
    maxp = table.max_permits[lidc]
    ttl2 = table.ttl2_ms[lidc]

    rows = _tb_decode(packed[sc])
    v1 = _refilled(rows, cap, rate, ttl2, now)

    # Segment-uniform closed form (ops/flat.py:tb_flat_bits, permits=None):
    # u = v1 - FP_ONE; request passes iff rank * FP_ONE <= u, i.e.
    # rank < avail with avail = u // FP_ONE + 1 (0 when u < 0).
    pre_ok = valid & (1 <= maxp)
    u = jnp.where(pre_ok, v1 - TOKEN_FP_ONE, jnp.int64(-1))
    avail = jnp.where(u >= 0, u // TOKEN_FP_ONE + 1, jnp.int64(0))
    allowed = valid & (rank < avail)

    # Single write per touched slot, at its last occurrence: seg_len is
    # rank + 1 there (the clamp saturates seg_len and avail coherently).
    seg_len = rank + 1
    n_alw = jnp.minimum(avail, seg_len)
    any_inc = n_alw > 0
    tokens_new = jnp.where(any_inc, v1 - n_alw * TOKEN_FP_ONE, rows[0])
    last_new = jnp.where(any_inc, jnp.maximum(now, 1), rows[1])

    mask = valid & last
    widx = jnp.where(mask, slot, jnp.int32(num_slots))  # out-of-range drops
    packed_new = packed.at[widx].set(
        _tb_encode(tokens_new, last_new), mode="drop")
    return packed_new, jnp.packbits(allowed)


def tb_relay_counts(packed, table, uwords, lids, now, *, rank_bits: int,
                    out_dtype=jnp.uint8, slots_sorted: bool = False):
    """Segment-digest token-bucket step: one lane per UNIQUE slot.

    uwords uint32[U] carries (slot | clamped segment count); the step
    returns how many of each segment's requests are allowed (`n_allowed`,
    clipped into out_dtype — the caller guarantees every limiter's
    max_permits fits), and the host reconstructs per-request booleans as
    ``rank < n_allowed[uidx]``.  State writes are identical to
    tb_relay_bits on the expanded batch: every valid lane is its own
    last occurrence.  Decision/state math lives in _tb_counts_core.
    """
    num_slots = packed.shape[0]
    slot, count, _, valid = decode_words(uwords, rank_bits, num_slots)
    packed_new, n_alw = _tb_counts_core(packed, table, slot, count, valid,
                                        lids, now,
                                        slots_sorted=slots_sorted)
    lim = jnp.int64(jnp.iinfo(out_dtype).max)
    return packed_new, jnp.clip(n_alw, 0, lim).astype(out_dtype)


def sw_relay_counts(packed, table, uwords, lids, now, *, rank_bits: int,
                    out_dtype=jnp.uint8, slots_sorted: bool = False):
    """Segment-digest sliding-window step (see tb_relay_counts).

    The per-request decision ``rank < n_allowed`` is exact: with unit
    permits the Q2 post-increment re-check is implied — n_pass =
    maxp - base - curr_e (when positive) and base >= 0, so any rank
    below n_pass also satisfies curr_e + rank + 1 <= maxp.  The core
    returns tot = min(count, n_pass), which reconstructs identically
    (rank < count always, so rank < tot <=> rank < n_pass).
    """
    num_slots = packed.shape[0]
    slot, count, _, valid = decode_words(uwords, rank_bits, num_slots)
    packed_new, tot = _sw_counts_core(packed, table, slot, count, valid,
                                      lids, now, slots_sorted=slots_sorted)
    lim = jnp.int64(jnp.iinfo(out_dtype).max)
    return packed_new, jnp.clip(tot, 0, lim).astype(out_dtype)


def _scatter_rows(packed, slot, valid, new_rows, slots_sorted):
    """Unique-row state write: the tile sweep when the host sorted the
    uniques by slot (padding decodes to slot >= num_slots, at the
    tail) and the shapes allow it, else XLA's per-index scatter."""
    if slots_sorted:
        from ratelimiter_tpu.ops.scatter import scatter_rows_presorted

        return scatter_rows_presorted(packed, slot, valid, new_rows)
    widx = jnp.where(valid, slot, jnp.int32(packed.shape[0]))
    return packed.at[widx].set(new_rows, mode="drop")


def _tb_counts_core(packed, table, slot, count, valid, lids, now,
                    slots_sorted: bool = False):
    """(new_packed, n_allowed per lane) — THE token-bucket digest body
    of tb_relay_counts."""
    sc = jnp.where(valid, slot, 0)
    scalar_lid = jnp.ndim(lids) == 0
    lidc = lids if scalar_lid else jnp.clip(
        lids, 0, table.cap_fp.shape[0] - 1)
    cap = table.cap_fp[lidc]
    rate = table.rate_fp[lidc]
    maxp = table.max_permits[lidc]
    ttl2 = table.ttl2_ms[lidc]
    rows = _tb_decode(packed[sc])
    v1 = _refilled(rows, cap, rate, ttl2, now)
    pre_ok = valid & (1 <= maxp)
    u = jnp.where(pre_ok, v1 - TOKEN_FP_ONE, jnp.int64(-1))
    avail = jnp.where(u >= 0, u // TOKEN_FP_ONE + 1, jnp.int64(0))
    n_alw = jnp.minimum(avail, count)
    any_inc = n_alw > 0
    tokens_new = jnp.where(any_inc, v1 - n_alw * TOKEN_FP_ONE, rows[0])
    last_new = jnp.where(any_inc, jnp.maximum(now, 1), rows[1])
    packed_new = _scatter_rows(packed, slot, valid,
                               _tb_encode(tokens_new, last_new),
                               slots_sorted)
    return packed_new, n_alw


def _sw_counts_core(packed, table, slot, count, valid, lids, now,
                    slots_sorted: bool = False):
    """Sliding-window counterpart of :func:`_tb_counts_core` (see
    sw_relay_counts for the derivation, incl. the implied Q2 check).

    Returns tot = min(count, n_pass) per lane: equivalent to n_pass for
    both the bit (tot > 0 <=> n_pass >= 1 for count >= 1) and the count
    reconstruction (rank < min(count, n_pass) <=> rank < n_pass, since
    rank < count by construction)."""
    sc = jnp.where(valid, slot, 0)
    scalar_lid = jnp.ndim(lids) == 0
    lidc = lids if scalar_lid else jnp.clip(
        lids, 0, table.max_permits.shape[0] - 1)
    maxp = table.max_permits[lidc]
    win = table.window_ms[lidc]
    rows = _sw_decode(packed[sc])
    curr_ws, curr_e, prev_e, prev_dl_e = _rolled(rows, win, now)
    rem = now % win
    base = (prev_e * (win - rem)) // win
    u = jnp.where(valid, maxp - base - curr_e - 1, jnp.int64(-1))
    n_pass = jnp.maximum(u + 1, 0)
    tot = jnp.minimum(count, n_pass)
    any_inc = tot > 0
    curr_new = curr_e + tot
    samew = rows[0] == curr_ws
    cdl_new = jnp.where(any_inc, now + win, jnp.where(samew, rows[2], 0))
    curr_ws_b = jnp.broadcast_to(curr_ws, sc.shape).astype(jnp.int64)
    new_rows = _sw_encode(curr_ws_b, curr_new, cdl_new, prev_e, prev_dl_e)
    packed_new = _scatter_rows(packed, slot, valid, new_rows, slots_sorted)
    return packed_new, tot


def tb_relay_counts_resident(packed, lid_map, table, uwords, delta_slots,
                             delta_lids, now, *, rank_bits: int,
                             out_dtype=jnp.uint8, slots_sorted: bool = False):
    """Digest step with the tenant ids RESIDENT on device.

    One slot is one (limiter, key) pair, so a slot's lid is immutable
    while assigned — the host uploads (slot, lid) pairs only for slots
    whose lid the device doesn't know yet (fresh assignments and
    post-eviction reuse), and the step folds that delta into ``lid_map``
    before deciding.  Steady-state multi-tenant wire cost drops from
    10 B/unique to ~5 (no per-unique lid lane).
    """
    lid_map = lid_map.at[jnp.where(delta_slots >= 0, delta_slots,
                                   lid_map.shape[0])].set(
        delta_lids, mode="drop")
    num_slots = packed.shape[0]
    slot, _, _, valid = decode_words(uwords, rank_bits, num_slots)
    lids = lid_map[jnp.where(valid, slot, 0)]
    packed_new, counts = tb_relay_counts(
        packed, table, uwords, lids, now, rank_bits=rank_bits,
        out_dtype=out_dtype, slots_sorted=slots_sorted)
    return packed_new, lid_map, counts


def sw_relay_counts_resident(packed, lid_map, table, uwords, delta_slots,
                             delta_lids, now, *, rank_bits: int,
                             out_dtype=jnp.uint8, slots_sorted: bool = False):
    """Sliding-window counterpart of :func:`tb_relay_counts_resident`."""
    lid_map = lid_map.at[jnp.where(delta_slots >= 0, delta_slots,
                                   lid_map.shape[0])].set(
        delta_lids, mode="drop")
    num_slots = packed.shape[0]
    slot, _, _, valid = decode_words(uwords, rank_bits, num_slots)
    lids = lid_map[jnp.where(valid, slot, 0)]
    packed_new, counts = sw_relay_counts(
        packed, table, uwords, lids, now, rank_bits=rank_bits,
        out_dtype=out_dtype, slots_sorted=slots_sorted)
    return packed_new, lid_map, counts


def _weighted_step_w(perms_rank, roff, r, count, u_b):
    """Permits of the r-th request of every segment (0 where r >= count).

    The host sorts a chunk's segments by occurrence count DESCENDING and
    ships permits rank-major compacted: all rank-0 permits (in segment
    order), then all rank-1 permits, ...  With that ordering the
    segments active at rank r are a PREFIX of the lane, so each step's
    permits are one contiguous ``dynamic_slice`` at ``roff[r]`` — no
    gathers, no rank-matrix padding, exactly 1 B/request on the wire.
    """
    w = jax.lax.dynamic_slice(perms_rank, (roff[r],),
                              (u_b,)).astype(jnp.int64)
    return jnp.where(r < count, w, jnp.int64(0))


def tb_relay_weighted(packed, table, uwords, perms_rank, roff, lid, now, *,
                      rank_bits: int, r_steps: int):
    """Weighted-permit relay token-bucket step — no sort, no solver.

    uwords uint32[U] carries (slot | segment count) per unique exactly as
    the digest path (padding 0xFFFFFFFF), in COUNT-DESCENDING segment
    order; ``perms_rank`` uint8[N+U] is the chunk's permits rank-major
    compacted (see :func:`_weighted_step_w`); ``roff`` i32[R] the
    per-rank offsets.  A ``lax.scan`` over the ``r_steps`` rank steps
    runs the exact skip recurrence of ops/flat.py:tb_flat_bits (denied
    requests consume nothing) with a U-wide elementwise body — nothing
    here has the super-linear XLA:TPU compile cost of
    sort/associative_scan, so chunks grow to the wire budget like the
    unit-permit relay.

    ``lid`` is a 0-d i32 (single-tenant streams; multi-lid weighted
    streams take the flat path).  Returns (new_packed, packed decision
    bits in the same compact rank-major layout as perms_rank — bit
    [roff[r] + j] decides the r-th request of the j-th count-sorted
    segment, ~1 bit/request); the host reconstructs arrival order via
    its (uidx, rank) scratch and the sort permutation.  Decisions are
    bit-identical to tb_flat_bits on the same chunking
    (tests/test_relay.py).
    """
    num_slots = packed.shape[0]
    u_b = uwords.shape[0]
    slot, count, _, valid = decode_words(uwords, rank_bits, num_slots)
    sc = jnp.where(valid, slot, 0)
    cap = table.cap_fp[lid]
    rate = table.rate_fp[lid]
    maxp = table.max_permits[lid]
    ttl2 = table.ttl2_ms[lid]

    rows = _tb_decode(packed[sc])
    v1 = _refilled(rows, cap, rate, ttl2, now)

    def body(carry, r):
        consumed, buf = carry
        w = _weighted_step_w(perms_rank, roff, r, count, u_b)
        w_fp = w * TOKEN_FP_ONE
        ok = (valid & (w >= 1) & (w <= maxp)
              & (consumed + w_fp <= v1))
        # Decisions go back in the SAME compact rank-major layout the
        # permits came in: ascending-r block writes, each fixing the
        # previous write's padding tail (see _weighted_step_w).
        buf = jax.lax.dynamic_update_slice(
            buf, ok.astype(jnp.uint8), (roff[r],))
        return (consumed + jnp.where(ok, w_fp, 0), buf), None

    (consumed, buf), _ = jax.lax.scan(
        body,
        (jnp.zeros_like(v1),
         jnp.zeros(perms_rank.shape[0], dtype=jnp.uint8)),
        jnp.arange(r_steps, dtype=jnp.int64))
    any_inc = consumed > 0
    tokens_new = jnp.where(any_inc, v1 - consumed, rows[0])
    last_new = jnp.where(any_inc, jnp.maximum(now, 1), rows[1])
    widx = jnp.where(valid & any_inc, slot, jnp.int32(num_slots))
    packed_new = packed.at[widx].set(
        _tb_encode(tokens_new, last_new), mode="drop")
    return packed_new, jnp.packbits(buf)


def sw_relay_weighted(packed, table, uwords, perms_rank, roff, lid, now, *,
                      rank_bits: int, r_steps: int):
    """Weighted-permit relay sliding-window step (see tb_relay_weighted).

    The recurrence state is the count of prior INCREMENTS m (quirk Q1:
    weighted requests check count+permits but increment by 1); the
    emitted decision additionally re-checks the post-increment count
    (quirk Q2), exactly as ops/flat.py:sw_flat_bits.
    """
    num_slots = packed.shape[0]
    u_b = uwords.shape[0]
    slot, count, _, valid = decode_words(uwords, rank_bits, num_slots)
    sc = jnp.where(valid, slot, 0)
    maxp = table.max_permits[lid]
    win = table.window_ms[lid]
    rem = now % win

    rows = _sw_decode(packed[sc])
    curr_ws, curr_e, prev_e, prev_dl_e = _rolled(rows, win, now)
    base = (prev_e * (win - rem)) // win

    def body(carry, r):
        m, buf = carry
        w = _weighted_step_w(perms_rank, roff, r, count, u_b)
        t = maxp - base - curr_e - w
        inc = valid & (w >= 1) & (m <= t)
        allowed = inc & (curr_e + m + 1 <= maxp)
        buf = jax.lax.dynamic_update_slice(
            buf, allowed.astype(jnp.uint8), (roff[r],))
        return (m + inc, buf), None

    (m_fin, buf), _ = jax.lax.scan(
        body,
        (jnp.zeros_like(curr_e),
         jnp.zeros(perms_rank.shape[0], dtype=jnp.uint8)),
        jnp.arange(r_steps, dtype=jnp.int64))
    any_inc = m_fin > 0
    curr_new = curr_e + m_fin
    samew = rows[0] == curr_ws
    cdl_new = jnp.where(any_inc, now + win, jnp.where(samew, rows[2], 0))
    curr_ws_b = jnp.broadcast_to(curr_ws, sc.shape).astype(jnp.int64)
    widx = jnp.where(valid, slot, jnp.int32(num_slots))
    packed_new = packed.at[widx].set(
        _sw_encode(curr_ws_b, curr_new, cdl_new, prev_e, prev_dl_e),
        mode="drop")
    return packed_new, jnp.packbits(buf)


def tb_relay_weighted_counts(packed, table, uwords, wlane, lid, now, *,
                             rank_bits: int, out_dtype=jnp.uint8):
    """Coalesced weighted token-bucket step: one lane per unique, no scan.

    When every repeat of a key inside a chunk carries the SAME permit
    weight w (the overwhelmingly common shape — clients rarely vary a
    key's weight within one flush), the weighted scan recurrence of
    :func:`tb_relay_weighted` has a closed form per segment: denied
    requests consume nothing, so the allowed requests are a PREFIX of
    the segment and ``n_allowed = min(count, v1 // (w * FP_ONE))``
    (0 unless 1 <= w <= max_permits), consuming exactly
    ``n_allowed * w * FP_ONE``.  The host reconstructs per-request
    booleans as ``rank < n_allowed[uidx]`` — bit-identical to the scan
    and to sequential per-request replay (tests/test_coalesce.py drives
    all three).  uwords carries (slot | clamped count) exactly as the
    digest path; the clamp stays exact because n_allowed <= max_permits
    < clamp.  wlane uint8[U] is the per-unique weight (padding lanes
    don't care — they decode invalid).  Device work and wire traffic
    scale with UNIQUES (4B word + 1B weight up, 1-2B count down), not
    requests: the Zipf-coalescing win.
    """
    num_slots = packed.shape[0]
    slot, count, _, valid = decode_words(uwords, rank_bits, num_slots)
    sc = jnp.where(valid, slot, 0)
    cap = table.cap_fp[lid]
    rate = table.rate_fp[lid]
    maxp = table.max_permits[lid]
    ttl2 = table.ttl2_ms[lid]

    rows = _tb_decode(packed[sc])
    v1 = _refilled(rows, cap, rate, ttl2, now)
    w = wlane.astype(jnp.int64)
    ok = valid & (w >= 1) & (w <= maxp)
    w_fp = jnp.where(ok, w, 1) * TOKEN_FP_ONE
    n_alw = jnp.where(ok, jnp.clip(v1 // w_fp, 0, count), jnp.int64(0))
    consumed = n_alw * w_fp
    any_inc = n_alw > 0
    tokens_new = jnp.where(any_inc, v1 - consumed, rows[0])
    last_new = jnp.where(any_inc, jnp.maximum(now, 1), rows[1])
    widx = jnp.where(valid & any_inc, slot, jnp.int32(num_slots))
    packed_new = packed.at[widx].set(
        _tb_encode(tokens_new, last_new), mode="drop")
    lim = jnp.int64(jnp.iinfo(out_dtype).max)
    return packed_new, jnp.clip(n_alw, 0, lim).astype(out_dtype)


def sw_relay_weighted_counts(packed, table, uwords, wlane, lid, now, *,
                             rank_bits: int, out_dtype=jnp.uint8):
    """Coalesced weighted sliding-window step (see
    tb_relay_weighted_counts).

    Closed form of the :func:`sw_relay_weighted` scan under a uniform
    segment weight: the increment test ``m <= maxp - base - curr_e - w``
    admits a prefix of ``n_inc = clip(maxp - base - curr_e - w + 1, 0,
    count)`` requests (0 unless w >= 1; quirk Q1 — weighted requests
    check count+permits but increment by 1), and the emitted decision
    re-checks the post-increment count (quirk Q2): request r is allowed
    iff ``r < min(n_inc, maxp - curr_e)``.  STATE advances by n_inc —
    the Q2-denied prefix tail still increments, exactly as the scan —
    while the returned count is the Q2-checked n_allowed the host
    reconstructs with.
    """
    num_slots = packed.shape[0]
    slot, count, _, valid = decode_words(uwords, rank_bits, num_slots)
    sc = jnp.where(valid, slot, 0)
    maxp = table.max_permits[lid]
    win = table.window_ms[lid]
    rem = now % win

    rows = _sw_decode(packed[sc])
    curr_ws, curr_e, prev_e, prev_dl_e = _rolled(rows, win, now)
    base = (prev_e * (win - rem)) // win
    w = wlane.astype(jnp.int64)
    ok = valid & (w >= 1)
    t = maxp - base - curr_e - w
    n_inc = jnp.where(ok, jnp.clip(t + 1, 0, count), jnp.int64(0))
    n_alw = jnp.minimum(n_inc, jnp.maximum(maxp - curr_e, 0))
    any_inc = n_inc > 0
    curr_new = curr_e + n_inc
    samew = rows[0] == curr_ws
    cdl_new = jnp.where(any_inc, now + win, jnp.where(samew, rows[2], 0))
    curr_ws_b = jnp.broadcast_to(curr_ws, sc.shape).astype(jnp.int64)
    widx = jnp.where(valid, slot, jnp.int32(num_slots))
    packed_new = packed.at[widx].set(
        _sw_encode(curr_ws_b, curr_new, cdl_new, prev_e, prev_dl_e),
        mode="drop")
    lim = jnp.int64(jnp.iinfo(out_dtype).max)
    return packed_new, jnp.clip(n_alw, 0, lim).astype(out_dtype)


def sw_relay_bits(packed, table, words, lids, now, *, rank_bits: int):
    """Relay sliding-window counterpart of :func:`tb_relay_bits` (same
    contract; decision math mirrors ops/flat.py:sw_flat_bits with
    permits=None, including the Q1/Q2 increment-by-1 and
    post-increment-check quirks)."""
    num_slots = packed.shape[0]
    slot, rank, last, valid = decode_words(words, rank_bits, num_slots)
    sc = jnp.where(valid, slot, 0)
    scalar_lid = jnp.ndim(lids) == 0
    lidc = lids if scalar_lid else jnp.clip(
        lids, 0, table.max_permits.shape[0] - 1)
    maxp = table.max_permits[lidc]
    win = table.window_ms[lidc]

    rows = _sw_decode(packed[sc])
    curr_ws, curr_e, prev_e, prev_dl_e = _rolled(rows, win, now)
    rem = now % win
    base = (prev_e * (win - rem)) // win

    # ops/flat.py:sw_flat_bits, permits=None: u = maxp - base - curr_e - 1;
    # inc_j = rank_j <= u; prior increments at rank j are min(rank, n_pass);
    # allowed additionally re-checks the post-increment count (quirk Q2).
    u = jnp.where(valid, maxp - base - curr_e - 1, jnp.int64(-1))
    n_pass = jnp.maximum(u + 1, 0)
    inc = rank < n_pass
    s_prior = jnp.minimum(rank, n_pass)
    c_j = curr_e + s_prior
    allowed = inc & (c_j + 1 <= maxp) & valid

    seg_len = rank + 1
    tot = jnp.minimum(seg_len, n_pass)
    any_inc = tot > 0
    curr_new = curr_e + tot
    samew = rows[0] == curr_ws
    cdl_new = jnp.where(any_inc, now + win, jnp.where(samew, rows[2], 0))
    curr_ws_b = jnp.broadcast_to(curr_ws, sc.shape).astype(jnp.int64)
    new_rows = _sw_encode(curr_ws_b, curr_new, cdl_new, prev_e, prev_dl_e)

    mask = valid & last
    widx = jnp.where(mask, slot, jnp.int32(num_slots))
    packed_new = packed.at[widx].set(new_rows, mode="drop")
    return packed_new, jnp.packbits(allowed)
