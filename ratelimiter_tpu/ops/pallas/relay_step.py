"""Fused Pallas TPU relay-step kernel: gather -> update -> scatter in ONE pass.

The digest relay step (ops/relay.py:tb_relay_counts / sw_relay_counts)
is the streaming hot path's dominant device dispatch, and as composed
XLA it crosses HBM three times per chunk: a row gather of the touched
slots, the elementwise decision math, and a scatter of the new rows
(the dense presorted sweep of ops/pallas/block_scatter.py at best).
This kernel does the whole step in one memory-resident pass over the
state array:

    for each aligned block of T consecutive state rows (one grid step):
        the updates touching it sit in a contiguous window of the
        slot-SORTED unique lane, at most T long (slots are unique)
        -> load block + two T-wide windows into VMEM
        -> decode words, match rows to lanes ((T, T) compare)
        -> select each row's segment count by one exact f32 matmul
        -> run the decision math on the rows IN REGISTER
        -> write the block back in place; matmul-select the per-lane
           allowed counts into the window-shaped count outputs

HBM traffic: read S rows + 2 windows, write S rows + counts — the
gather and the scatter are the same pass, so the step's floor is one
read + one write of the state instead of gather + sweep-read + write.

Window map: identical to block_scatter.py — ``searchsorted`` of the
T-aligned block bounds over the sorted uword lane gives a scalar
sigma[i] per state block such that update windows [sigma[i],
sigma[i]+1] cover every lane whose slot lands in block i.  sigma is
non-decreasing, so the two count outputs (window-a hits and window-b
hits) revisit their blocks only consecutively — a first-visit select
accumulates multi-step hits and an XLA-side visited mask zeroes blocks
no grid step wrote.  Every lane matches in exactly one (step, window)
role, so the two outputs sum to the per-unique allowed counts.

64-bit arithmetic: Mosaic has no i64, so the fixed-point token-bucket
refill and the sliding-window bucket math (the EXACT semantics of
semantics/oracle.py, via ops/token_bucket.py / ops/sliding_window.py)
run as two-lane i32 pairs: add/sub with manual carries, 16-bit-limb
multiplies, and two division strategies — ``u // TOKEN_FP_ONE``
reduces to a constant shift plus an i32 divide-by-1000 (done as an f32
reciprocal estimate with exact integer correction, valid because the
quotient only matters when it is below the segment count < 2^21), and
the sliding window's ``(prev * (win - rem)) // win`` runs a 31-step
vectorized binary search on the quotient (exact by construction; the
VPU cost is noise next to the HBM sweep).  Preconditions the engine
already maintains: counters non-negative, max_permits <= 2^31 - 1
(config validation), rank_bits <= 21 (num_slots >= 2T implies it).

Scope (the "geometry allows" gate): the classic counts wire format,
slot-sorted uniques, scalar tenant id — exactly the headline digest
dispatch.  Multi-tenant lanes (the ``_resident`` variant) would need a
per-row policy gather the window structure cannot express without
per-lid limb matmuls, and the split format's two lane sets are sorted
per set, not merged — both fall back to the composed-XLA step, elected
per path like everything else (ops/pallas/election.py).

Mosaic survival rules (see block_scatter.py, learned on v5e): rank-2
everything, no 1-D slices/gathers/concats, explicit 32-bit literals,
trace under enable_x64(False).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

T = 256          # state rows per block; num_slots must divide by this

_FLAG = os.environ.get("RATELIMITER_RELAY_FUSED", "1") == "1"
_INTERPRET = os.environ.get(
    "RATELIMITER_RELAY_FUSED_INTERPRET", "0") == "1"
_probe_ok: bool | None = None
# Fallback observability (PR 4 silent-degrade fix): a probe failure on
# real hardware means the fused kernel silently stops serving — record
# why, warn ONCE, and surface it via fallback_info() so /actuator/health
# and the ratelimiter.pallas.fused_fallback gauge can report it.
_fallback_reason: str | None = None
_warned = False


def _note_fallback(reason: str) -> None:
    global _fallback_reason, _warned
    _fallback_reason = reason
    try:
        from ratelimiter_tpu.observability import flight_recorder

        flight_recorder().record("pallas.fused_fallback", reason=reason)
    except Exception:  # noqa: BLE001 — observability must not break serving
        pass
    if not _warned:
        _warned = True
        from ratelimiter_tpu.utils.logging import get_logger

        get_logger("pallas.relay_step").warning(
            "fused Pallas relay step not serving (%s); decisions fall "
            "back to the composed XLA step — see "
            "ratelimiter.pallas.fused_fallback and "
            "pallas.relay_fused_live in /actuator/health", reason)


def fallback_info() -> dict:
    """Live/fallback status of the fused relay step for health payloads
    and metrics (reads only already-settled state — never triggers a
    probe or compile).

    ``relay_fused_live`` — the kernel will serve eligible dispatches;
    ``probe_failed`` — the differential probe failed on this hardware
    (the silent-degrade trap: supported platform, losing kernel);
    ``reason`` — why the kernel is not live, when it is not.
    """
    import jax

    platform_ok = _INTERPRET or jax.default_backend() == "tpu"
    elected = None
    if _probe_ok:
        from ratelimiter_tpu.ops.pallas import election

        verdict = election.report().get("relay_fused")
        elected = None if verdict is None else bool(verdict["elected"])
    live = bool(_FLAG and platform_ok and _probe_ok and elected)
    reason = None
    if not live:
        if not _FLAG:
            reason = "disabled (RATELIMITER_RELAY_FUSED=0)"
        elif _probe_ok is False:
            # The trap this exists for: supported platform, losing
            # kernel — outranks every other explanation.
            reason = _fallback_reason or "probe failed"
        elif not platform_ok:
            reason = f"platform {jax.default_backend()} (TPU-only kernel)"
        elif _probe_ok is None:
            reason = "not probed yet"
        elif elected is None:
            reason = "not elected yet"
        else:
            reason = "election lost (XLA measured faster)"
    return {"relay_fused_live": live,
            "probe_failed": _probe_ok is False,
            "reason": reason}

_SIGN = -2147483648   # 0x80000000 as i32
_M16 = 0xFFFF
_FP_ONE_I32 = 1048576000    # 1000 << 20 == core.config.TOKEN_FP_ONE


# ---------------------------------------------------------------------------
# i64-as-i32-pair arithmetic (hi, lo), lo unsigned.  All helpers are
# elementwise over rank-2 arrays and broadcast scalars freely.
# ---------------------------------------------------------------------------

def _i32(v):
    return jnp.int32(v)


def _lshr(x, k: int):
    """Logical right shift by a static k in [1, 31]."""
    return (x >> _i32(k)) & _i32((1 << (32 - k)) - 1)


def _ult(a, b):
    """Unsigned a < b on i32 bit patterns."""
    return (a ^ _i32(_SIGN)) < (b ^ _i32(_SIGN))


def _add64(ah, al, bh, bl):
    lo = al + bl
    hi = ah + bh + _ult(lo, bl).astype(jnp.int32)
    return hi, lo


def _sub64(ah, al, bh, bl):
    lo = al - bl
    hi = ah - bh - _ult(al, bl).astype(jnp.int32)
    return hi, lo


def _lt64(ah, al, bh, bl):
    return (ah < bh) | ((ah == bh) & _ult(al, bl))


def _ge64(ah, al, bh, bl):
    return ~_lt64(ah, al, bh, bl)


def _eq64(ah, al, bh, bl):
    return (ah == bh) & (al == bl)


def _sel64(cond, a, b):
    return jnp.where(cond, a[0], b[0]), jnp.where(cond, a[1], b[1])


def _min64(a, b):
    return _sel64(_lt64(a[0], a[1], b[0], b[1]), a, b)


def _mulu32(a, b):
    """Unsigned 32x32 -> 64 as (hi, lo), via 16-bit limbs (i32 products
    of 16-bit limbs are exact; wraps only discard bits above 2^32)."""
    m16 = _i32(_M16)
    a0, a1 = a & m16, _lshr(a, 16)
    b0, b1 = b & m16, _lshr(b, 16)
    p00 = a0 * b0
    p01 = a0 * b1
    p10 = a1 * b0
    p11 = a1 * b1
    mid = _lshr(p00, 16) + (p01 & m16) + (p10 & m16)   # < 3 * 2^16
    lo = (mid << _i32(16)) | (p00 & m16)
    hi = p11 + _lshr(p01, 16) + _lshr(p10, 16) + _lshr(mid, 16)
    return hi, lo


def _mul64(ah, al, bh, bl):
    """Low 64 bits of the 64x64 product (exact mod 2^64 — callers bound
    true products below 2^63)."""
    hi, lo = _mulu32(al, bl)
    return hi + al * bh + ah * bl, lo


def _shr64(ah, al, k: int):
    """Arithmetic 64-bit right shift by static k in [1, 31]."""
    return ah >> _i32(k), _lshr(al, k) | (ah << _i32(32 - k))


def _shl64_of_u32(x, k: int):
    """(0, x) << k for non-negative x, static k in [1, 31]."""
    return _lshr(x, 32 - k), x << _i32(k)


def _sx(x):
    """Sign-extend i32 -> pair (matches XLA's .astype(int64) on lanes)."""
    return x >> _i32(31), x


def _div1000(n):
    """Exact n // 1000 for i32 0 <= n < 2^31: f32 reciprocal estimate
    (abs error < 0.5), then integer correction by +-1."""
    q = jnp.floor(n.astype(jnp.float32)
                  * jnp.float32(0.001)).astype(jnp.int32)
    q = jnp.where((q + _i32(1)) * _i32(1000) <= n, q + _i32(1), q)
    q = jnp.where(q * _i32(1000) > n, q - _i32(1), q)
    return q


def _div64_by_u32(ph, pl, d):
    """floor(p / d) for a non-negative 64-bit pair p whose quotient fits
    31 bits, d a positive i32 scalar: binary search on the quotient —
    exact with no magic-number proof obligations; 31 static rounds of
    limb-multiply + compare on the VPU."""
    q = jnp.zeros_like(pl)
    for k in range(30, -1, -1):
        cand = q | _i32(1 << k)
        ch, cl = _mulu32(cand, d)
        ok = _ge64(ph, pl, ch, cl)
        q = jnp.where(ok, cand, q)
    return q


# ---------------------------------------------------------------------------
# Kernel
# ---------------------------------------------------------------------------

def _f32_dot(a, b, contract_a: int, contract_b: int):
    """Exact f32 matmul: with 0/1 selectors that match at most once,
    every output element has at most one nonzero term, so values below
    2^24 come out exact."""
    dn = (((contract_a,), (contract_b,)), ((), ()))
    return jax.lax.dot_general(a, b, dn,
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _decode_window(uw, rank_bits: int):
    """(1, T) i32 uword bit patterns -> (slot, count) i32 (1, T).
    Padding (0xFFFFFFFF) decodes to the max slot-field value, which is
    >= num_slots for every legal layout — it can never match a row."""
    slot = _lshr(uw, rank_bits + 1)
    count = _lshr(uw, 1) & _i32((1 << rank_bits) - 1)
    return slot, count


def _par64(params_ref, j: int):
    """j-th logical i64 param as an (hi, lo) scalar pair."""
    return params_ref[2 * j + 1], params_ref[2 * j]


def _tb_row_update(block, cnt_row, params_ref):
    """Token-bucket decision math on T state rows at once (exact i64
    semantics of ops/relay.py:_tb_counts_core via pair arithmetic).
    Returns (new column list [tok_lo, tok_hi, last_lo, last_hi],
    n_allowed i32 (T, 1))."""
    tok = (block[:, 1:2], block[:, 0:1])    # (hi, lo)
    last = (block[:, 3:4], block[:, 2:3])
    pre_ok = params_ref[0] != _i32(0)
    now = _par64(params_ref, 1)
    now1 = _par64(params_ref, 2)
    cap = _par64(params_ref, 3)
    rate = _par64(params_ref, 4)
    ecap = _par64(params_ref, 5)
    ttl2 = _par64(params_ref, 6)

    dl = _add64(last[0], last[1], ttl2[0], ttl2[1])
    expired = (((last[0] == _i32(0)) & (last[1] == _i32(0)))
               | _ge64(now[0], now[1], dl[0], dl[1]))
    v0 = _sel64(expired, cap, tok)
    last_e = _sel64(expired, now, last)
    el = _sub64(now[0], now[1], last_e[0], last_e[1])
    el = _sel64(_lt64(el[0], el[1], _i32(0), _i32(0)),
                (_i32(0), _i32(0)), el)
    el = _sel64(_lt64(ecap[0], ecap[1], el[0], el[1]), ecap, el)
    refill = _mul64(el[0], el[1], rate[0], rate[1])
    v1 = _min64(cap, _add64(v0[0], v0[1], refill[0], refill[1]))

    u = _sub64(v1[0], v1[1], _i32(0), _i32(_FP_ONE_I32))
    u_ok = _ge64(u[0], u[1], _i32(0), _i32(0)) & pre_ok
    u2h, u2l = _shr64(u[0], u[1], 20)         # u // 2^20 (u >= 0 branch)
    c1000 = (cnt_row - _i32(1)) * _i32(1000)  # < 2^31 (rank_bits <= 21)
    # avail >= count  <=>  u2 >= (count-1)*1000; below that u2 fits i32.
    avail_ge = _ge64(u2h, u2l, c1000 >> _i32(31), c1000)
    avail_small = _div1000(u2l) + _i32(1)
    avail = jnp.where(u_ok,
                      jnp.where(avail_ge, cnt_row, avail_small), _i32(0))
    n_alw = jnp.minimum(avail, cnt_row)
    any_inc = n_alw > _i32(0)
    cons = _shl64_of_u32(n_alw * _i32(1000), 20)
    tok_new = _sel64(any_inc,
                     _sub64(v1[0], v1[1], cons[0], cons[1]), tok)
    last_new = _sel64(any_inc, now1, last)
    return [tok_new[1], tok_new[0], last_new[1], last_new[0]], n_alw


def _sw_row_update(block, cnt_row, params_ref):
    """Sliding-window decision math on T rows (exact semantics of
    ops/relay.py:_sw_counts_core).  Returns (new column list [ws_lo,
    ws_hi, curr, prev, cdl_off, pdl_off], tot i32 (T, 1))."""
    win = params_ref[0]          # i32 scalars (validated <= 2^30)
    maxp = params_ref[2]
    wmr = params_ref[4]          # win - now % win
    now = _par64(params_ref, 3)
    cws = _par64(params_ref, 4)
    cwsmw = _par64(params_ref, 5)   # curr_ws - win
    npw = _par64(params_ref, 6)     # now + win
    ws = (block[:, 1:2], block[:, 0:1])
    curr = block[:, 2:3]
    prev = block[:, 3:4]
    cdl = _add64(ws[0], ws[1], _i32(0), block[:, 4:5])
    pdl = _add64(ws[0], ws[1], _i32(0), block[:, 5:6])

    same = _eq64(ws[0], ws[1], cws[0], cws[1])
    next1 = _eq64(ws[0], ws[1], cwsmw[0], cwsmw[1])
    curr_alive = _lt64(now[0], now[1], cdl[0], cdl[1])
    prev_alive = _lt64(now[0], now[1], pdl[0], pdl[1])
    curr_e = jnp.where(same, curr, _i32(0))
    prev_e = jnp.where(same, jnp.where(prev_alive, prev, _i32(0)),
                       jnp.where(next1 & curr_alive, curr, _i32(0)))
    pdle = _sel64(same, pdl, _sel64(next1, cdl, (_i32(0), _i32(0))))

    bp = _mulu32(prev_e, wmr)
    base = _div64_by_u32(bp[0], bp[1], win)
    npass = _sub64(*_sub64(_i32(0), maxp, *_sx(base)), *_sx(curr_e))
    npass_pos = ~_lt64(npass[0], npass[1], _i32(0), _i32(0))
    n_pass = jnp.where(npass_pos, npass[1], _i32(0))  # <= maxp: lo exact
    tot = jnp.minimum(cnt_row, n_pass)
    any_inc = tot > _i32(0)
    curr_new = curr_e + tot
    cdl_new = _sel64(any_inc, npw, _sel64(same, cdl, (_i32(0), _i32(0))))

    def off_of(dl):
        d = _sub64(dl[0], dl[1], cws[0], cws[1])
        return jnp.where(_lt64(d[0], d[1], _i32(0), _i32(0)),
                         _i32(0), d[1])   # alive offsets < 2^31: lo exact

    return [jnp.broadcast_to(cws[1], curr.shape),
            jnp.broadcast_to(cws[0], curr.shape),
            curr_new, prev_e, off_of(cdl_new), off_of(pdle)], tot


def _kernel(sigma_ref, params_ref, state_ref, uwa_ref, uwb_ref,
            out_state_ref, cnt_a_ref, cnt_b_ref, *, algo: str, lanes: int,
            rank_bits: int):
    from jax.experimental import pallas as pl

    i = pl.program_id(0)
    block = state_ref[...]                            # (T, lanes)
    t_slot = (_i32(T) * i
              + jax.lax.broadcasted_iota(jnp.int32, (T, 1), 0))
    slot_a, count_a = _decode_window(uwa_ref[...], rank_bits)
    slot_b, count_b = _decode_window(uwb_ref[...], rank_bits)
    eq_a = (slot_a == t_slot).astype(jnp.float32)     # (T, T): [row, lane]
    eq_b = (slot_b == t_slot).astype(jnp.float32)
    # Per-row segment count + matched flag: one exact f32 select each
    # (slots unique => at most one matching lane per row across BOTH
    # windows, and counts < 2^21 are f32-exact).
    cnt_row = (_f32_dot(eq_a, count_a.astype(jnp.float32), 1, 1)
               + _f32_dot(eq_b, count_b.astype(jnp.float32), 1, 1)
               ).astype(jnp.int32)                    # (T, 1)
    ones = jnp.ones((T, 1), jnp.float32)
    ma = _f32_dot(eq_a, ones, 1, 0)   # ma[t] = lanes of window a at row t
    mb = _f32_dot(eq_b, ones, 1, 0)
    matched = (ma + mb) > jnp.float32(0.0)            # (T, 1)

    if algo == "tb":
        cols, n_alw = _tb_row_update(block, cnt_row, params_ref)
    else:
        cols, n_alw = _sw_row_update(block, cnt_row, params_ref)

    lane_idx = jax.lax.broadcasted_iota(jnp.int32, (T, lanes), 1)
    new_block = block
    for j, col in enumerate(cols):
        new_block = jnp.where(lane_idx == _i32(j), col, new_block)
    out_state_ref[...] = jnp.where(matched, new_block, block)

    # Per-lane counts back in window space: n_alw[t] selected into each
    # window's matching lane ((T,)x(T,1) contraction over rows -> (T,1)
    # per window block).  Consecutive revisits of the same output block
    # accumulate via a first-visit select; blocks never visited are
    # zeroed by the caller's visited mask.
    n_alw_f = jnp.where(matched, n_alw, _i32(0)).astype(jnp.float32)
    out_a = _f32_dot(eq_a, n_alw_f, 0, 0).astype(jnp.int32)   # (T, 1)
    out_b = _f32_dot(eq_b, n_alw_f, 0, 0).astype(jnp.int32)
    mw_a = _f32_dot(eq_a, ones, 0, 0)                         # (T, 1)
    mw_b = _f32_dot(eq_b, ones, 0, 0)
    first = jnp.logical_or(
        i == _i32(0),
        sigma_ref[i] != sigma_ref[jnp.maximum(i - _i32(1), _i32(0))])
    prev_a = jnp.where(first, _i32(0), cnt_a_ref[...])
    prev_b = jnp.where(first, _i32(0), cnt_b_ref[...])
    cnt_a_ref[...] = jnp.where(mw_a > jnp.float32(0.0), out_a, prev_a)
    cnt_b_ref[...] = jnp.where(mw_b > jnp.float32(0.0), out_b, prev_b)


def _call_kernel(algo, state, uwords_i32, sigma, params, rank_bits: int,
                 interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s_rows, lanes = state.shape
    u = uwords_i32.shape[1]
    kernel = functools.partial(_kernel, algo=algo, lanes=lanes,
                               rank_bits=rank_bits)
    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s_rows // T,),
        in_specs=[
            pl.BlockSpec((T, lanes), lambda i, sig, par: (i, 0)),
            pl.BlockSpec((1, T), lambda i, sig, par: (0, sig[i])),
            pl.BlockSpec((1, T), lambda i, sig, par: (0, sig[i] + 1)),
        ],
        out_specs=[
            pl.BlockSpec((T, lanes), lambda i, sig, par: (i, 0)),
            pl.BlockSpec((T, 1), lambda i, sig, par: (sig[i], 0)),
            pl.BlockSpec((T, 1), lambda i, sig, par: (sig[i] + 1, 0)),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((u, 1), jnp.int32),
                   jax.ShapeDtypeStruct((u, 1), jnp.int32)],
        input_output_aliases={2: 0},   # state updated in place in HBM
        interpret=interpret,
    )(sigma, params, state, uwords_i32, uwords_i32)


# ---------------------------------------------------------------------------
# Traced entry points (the engine jits these with donate_argnums=0)
# ---------------------------------------------------------------------------

def _pairs_i32(vec64):
    """i64[k] -> i32[2k] as [lo0, hi0, lo1, hi1, ...] (little-endian
    bitcast — computed BEFORE the x64-off scope so the i64 math is
    real)."""
    return jax.lax.bitcast_convert_type(vec64, jnp.int32).reshape(-1)


def _tb_params(table, lid, now):
    cap = table.cap_fp[lid]
    rate = table.rate_fp[lid]
    maxp = table.max_permits[lid]
    ttl2 = table.ttl2_ms[lid]
    vec = jnp.stack([
        (maxp >= 1).astype(jnp.int64),       # 0: pre_ok
        now.astype(jnp.int64),               # 1
        jnp.maximum(now, 1).astype(jnp.int64),   # 2: last_refill write
        cap, rate,                           # 3, 4
        cap // jnp.maximum(rate, 1) + 1,     # 5: elapsed clamp
        ttl2,                                # 6
    ])
    return _pairs_i32(vec)


def _sw_params(table, lid, now):
    maxp = table.max_permits[lid]
    win = table.window_ms[lid]
    now64 = now.astype(jnp.int64)
    rem = now64 % win
    cws = now64 - rem
    vec = jnp.stack([
        win,                                 # 0 (lo slot: i32 scalar)
        maxp,                                # 1? -> see _sw_row_update
        win - rem,                           # 2: wmr
        now64,                               # 3
        cws,                                 # 4
        cws - win,                           # 5
        now64 + win,                         # 6
    ])
    return _pairs_i32(vec)


def _fused_counts(algo, packed, table, uwords, lid, now, *, rank_bits: int,
                  out_dtype=jnp.uint8, interpret: bool = False):
    """Fused replacement for relay.tb_relay_counts / sw_relay_counts with
    ``slots_sorted=True`` and a scalar ``lid`` — bit-identical decisions
    and state (tests/test_pallas_relay.py drives both).  uwords uint32[U]
    slot-ascending with 0xFFFFFFFF padding at the tail; U and the state
    rows must satisfy :func:`supported`."""
    params = (_tb_params if algo == "tb" else _sw_params)(
        table, lid, jnp.asarray(now))
    s_rows, _ = packed.shape
    u = uwords.shape[0]
    with jax.enable_x64(False):
        # Every scalar below is explicitly 32-bit: a weak python-int
        # literal traced in this scope can still materialize as i64 at
        # lowering time (the same trap block_scatter.py documents).
        uw = uwords.reshape(1, u)
        bounds = (jnp.arange(s_rows // T, dtype=jnp.uint32)
                  * jnp.uint32(T << (rank_bits + 1)))
        starts = jnp.searchsorted(uwords, bounds).astype(jnp.int32)
        sigma = jnp.clip(starts // jnp.int32(T), jnp.int32(0),
                         jnp.int32(u // T - 2))
        new_state, cnt_a, cnt_b = _call_kernel(
            algo, packed, jax.lax.bitcast_convert_type(uw, jnp.int32),
            sigma, params, rank_bits, interpret)
        n_w = u // T
        va = jnp.zeros((n_w,), jnp.int32).at[sigma].set(jnp.int32(1))
        vb = jnp.zeros((n_w,), jnp.int32).at[sigma + jnp.int32(1)].set(
            jnp.int32(1))
        cnt = (cnt_a.reshape(n_w, T) * va[:, None]
               + cnt_b.reshape(n_w, T) * vb[:, None]).reshape(u)
        lim = int(jnp.iinfo(out_dtype).max)
        counts = jnp.clip(cnt, jnp.int32(0),
                          jnp.int32(lim)).astype(out_dtype)
    return new_state, counts


def tb_relay_counts_fused(packed, table, uwords, lid, now, *,
                          rank_bits: int, out_dtype=jnp.uint8,
                          interpret: bool = False):
    return _fused_counts("tb", packed, table, uwords, lid, now,
                         rank_bits=rank_bits, out_dtype=out_dtype,
                         interpret=interpret)


def sw_relay_counts_fused(packed, table, uwords, lid, now, *,
                          rank_bits: int, out_dtype=jnp.uint8,
                          interpret: bool = False):
    return _fused_counts("sw", packed, table, uwords, lid, now,
                         rank_bits=rank_bits, out_dtype=out_dtype,
                         interpret=interpret)


# ---------------------------------------------------------------------------
# Gating: static geometry + one-time correctness probe + measured election
# ---------------------------------------------------------------------------

def supported(state_shape, batch: int, rank_bits: int) -> bool:
    """Static geometry gate: T-aligned table, window-coverable sorted
    lane, counts that stay f32/i32-exact (rank_bits <= 21 — implied by
    the >= 2T slot floor for every engine-derived layout, checked anyway
    for hand-built callers)."""
    try:
        from jax.experimental import pallas as pl  # noqa: F401
    except Exception:  # noqa: BLE001
        return False
    s_rows = state_shape[0]
    return (s_rows % T == 0 and s_rows // T >= 1
            and batch >= 2 * T and batch % T == 0
            and 1 <= rank_bits <= 21)


def interpret_mode() -> bool:
    return _INTERPRET


def _probe() -> bool:
    """One-time differential self-check on this platform: a couple of
    populated steps, fused vs composed XLA, both algorithms, exact."""
    global _probe_ok
    if _probe_ok is not None:
        return _probe_ok
    from ratelimiter_tpu.ops.pallas import (
        probe_failed,
        refuse_interpret_on_tpu,
    )

    refuse_interpret_on_tpu("relay_fused", _INTERPRET,
                            "RATELIMITER_RELAY_FUSED_INTERPRET")
    try:
        from ratelimiter_tpu.core.config import RateLimitConfig
        from ratelimiter_tpu.engine.state import LimiterTable
        from ratelimiter_tpu.ops import relay
        from ratelimiter_tpu.ops.sliding_window import make_sw_packed
        from ratelimiter_tpu.ops.token_bucket import make_tb_packed

        rng = np.random.default_rng(13)
        s_rows, u = 2 * T, 2 * T
        rb = 31 - int(s_rows).bit_length()
        table = LimiterTable()
        lid = jnp.int32(table.register(RateLimitConfig(
            max_permits=9, window_ms=1000, refill_rate=4.0)))
        tarr = table.device_arrays
        slots = np.sort(rng.choice(s_rows, size=u - 17,
                                   replace=False)).astype(np.uint32)
        counts = rng.integers(1, 6, u - 17).astype(np.uint32)
        uw = np.full(u, 0xFFFFFFFF, dtype=np.uint32)
        uw[:u - 17] = (slots << np.uint32(rb + 1)) | (counts << np.uint32(1))
        uw_j = jnp.asarray(uw)
        for algo, make in (("tb", make_tb_packed), ("sw", make_sw_packed)):
            ref_fn = (relay.tb_relay_counts if algo == "tb"
                      else relay.sw_relay_counts)
            fused_fn = (tb_relay_counts_fused if algo == "tb"
                        else sw_relay_counts_fused)
            st_ref = make(s_rows)
            # Populate with two composed steps so the probe sees live
            # windows/refills, then compare the third step exactly.
            for now in (1_000_003, 1_000_400):
                st_ref, _ = ref_fn(st_ref, tarr, uw_j, lid,
                                   jnp.int64(now), rank_bits=rb,
                                   slots_sorted=False)
            st_fused = jnp.array(st_ref)  # independent buffer
            now = jnp.int64(1_001_251)
            want_st, want_c = ref_fn(st_ref, tarr, uw_j, lid, now,
                                     rank_bits=rb, slots_sorted=False)
            got_st, got_c = jax.jit(functools.partial(
                fused_fn, rank_bits=rb, interpret=_INTERPRET))(
                    st_fused, tarr, uw_j, lid, now)
            if not (np.array_equal(np.asarray(want_st), np.asarray(got_st))
                    and np.array_equal(np.asarray(want_c),
                                       np.asarray(got_c))):
                reason = (f"probe mismatch ({algo}): fused output "
                          "diverged from the composed XLA step")
                break
        else:
            reason = None
    except Exception as exc:  # noqa: BLE001 — verdict below
        reason = f"probe error: {type(exc).__name__}: {str(exc)[:160]}"
    if reason is None:
        _probe_ok = True
    else:
        _note_fallback(reason)
        _probe_ok = probe_failed("relay_fused", reason)
    return _probe_ok


def _measure_ab() -> dict:
    """Chained-step A/B at a representative digest shape: K steps
    chained in one jit, one checksum fetched."""
    import time

    from ratelimiter_tpu.core.config import RateLimitConfig
    from ratelimiter_tpu.engine.state import LimiterTable
    from ratelimiter_tpu.ops import relay
    from ratelimiter_tpu.ops.pallas import block_scatter
    from ratelimiter_tpu.ops.token_bucket import make_tb_packed

    s_rows, lanes_u, k_steps = 1 << 18, 1 << 16, 8
    rb = 31 - int(s_rows).bit_length()
    table = LimiterTable()
    lid = jnp.int32(table.register(RateLimitConfig(
        max_permits=100, window_ms=60_000, refill_rate=50.0)))
    tarr = table.device_arrays
    base = np.arange(lanes_u, dtype=np.uint32) * (s_rows // lanes_u)
    uw = jnp.asarray((base << np.uint32(rb + 1)) | np.uint32(1 << 1))
    srt_ok = block_scatter.enabled((s_rows, 4), lanes_u)

    def chain(step):
        @functools.partial(jax.jit, donate_argnums=0)
        def run(packed, now0):
            def body(i, carry):
                packed, acc = carry
                packed, c = step(packed, now0 + i)
                return packed, acc + jnp.sum(c.astype(jnp.int64))

            return jax.lax.fori_loop(0, k_steps, body,
                                     (packed, jnp.int64(0)))

        return run

    def xla_step(packed, now):
        return relay.tb_relay_counts(packed, tarr, uw, lid, now,
                                     rank_bits=rb, slots_sorted=srt_ok)

    def fused_step(packed, now):
        return tb_relay_counts_fused(packed, tarr, uw, lid, now,
                                     rank_bits=rb, interpret=_INTERPRET)

    def best_of(step):
        fn = chain(step)
        packed, acc = fn(make_tb_packed(s_rows), jnp.int64(1_000_000))
        int(np.asarray(acc))  # compile + settle
        best = float("inf")
        for rep in range(3):
            t0 = time.perf_counter()
            packed, acc = fn(packed, jnp.int64(2_000_000 + rep))
            int(np.asarray(acc))
            best = min(best, time.perf_counter() - t0)
        return best / (k_steps * lanes_u)

    return {"pallas_s": best_of(fused_step), "xla_s": best_of(xla_step),
            "uniques": lanes_u, "state_rows": s_rows,
            "xla_sorted_sweep": bool(srt_ok)}


def _elected() -> bool:
    from ratelimiter_tpu.ops.pallas import election

    return election.measured_election("relay_fused", _measure_ab,
                                      interpret=_INTERPRET)


def settle() -> bool:
    """Resolve the support probe + election eagerly (engine init calls
    this before any step kernel compiles).  Respects the
    RATELIMITER_RELAY_FUSED kill switch: disabled means no Pallas
    compile at all.  Returns whether the fused step will actually SERVE
    (supported AND elected)."""
    if not _FLAG:
        return False
    if not (_INTERPRET or jax.default_backend() == "tpu"):
        return False
    if not _probe():
        return False
    return _elected()


def enabled(state_shape, batch: int, rank_bits: int) -> bool:
    """Full per-dispatch gate: flag, platform, geometry, probe, election."""
    if not _FLAG or not supported(state_shape, batch, rank_bits):
        return False
    if not (_INTERPRET or jax.default_backend() == "tpu"):
        return False
    return _probe() and _elected()
