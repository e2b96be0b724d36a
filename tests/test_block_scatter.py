"""Differential tests of the tile sweep (ops/pallas/block_scatter.py)
against XLA's drop-mode scatter, ``state.at[slot].set(rows,
mode="drop")``, in interpret mode on the CPU.

The sweep's blocks are narrowed here (``_TB``) so that a table of a few
thousand slots spans several blocks, a partial last block included, and
one window can fill a whole block.
"""

import zlib

import numpy as np
import pytest

import jax.numpy as jnp

S = 1792        # 3.5 blocks of 512 slots: the last block is partial
N = 1024        # updates per batch: blocks (and windows) of 512
WB = 512


@pytest.fixture()
def bs(monkeypatch):
    from ratelimiter_tpu.ops.pallas import block_scatter

    monkeypatch.setattr(block_scatter, "_TB", WB)
    return block_scatter


def _live(case, rng):
    """Sorted unique live slots of one case."""
    if case == "random":
        return np.sort(rng.choice(S, size=700, replace=False))
    if case == "empty_blocks":     # blocks 0 and 3 get no update
        return np.sort(rng.choice(np.arange(WB, 3 * WB), size=300,
                                  replace=False))
    if case == "full_block":       # every slot of block 1: WB updates
        rest = rng.choice(np.r_[0:WB, 2 * WB:S], size=N - WB - 40,
                          replace=False)
        return np.sort(np.r_[np.arange(WB, 2 * WB), rest])
    if case == "table_edges":      # first and last slots of the table
        return np.r_[0, 1, 2, 127, 128, S - 129, S - 128, S - 2, S - 1]
    if case == "none":
        return np.zeros(0, np.int64)
    raise AssertionError(case)


@pytest.mark.parametrize("lanes", [4, 6])
@pytest.mark.parametrize("case", ["random", "empty_blocks", "full_block",
                                  "table_edges", "none"])
def test_sweep_matches_xla_scatter(bs, case, lanes):
    rng = np.random.default_rng(zlib.crc32(f"{case}/{lanes}".encode()))
    live = _live(case, rng).astype(np.int32)
    u = len(live)
    # Digest layout: live slots first, then a padding tail whose slots
    # decode past the table.
    slots = np.r_[live, np.full(N - u, S + 7, np.int32)].astype(np.int32)
    mask = np.arange(N) < u
    state = rng.integers(-(1 << 31), (1 << 31) - 1, (S, lanes),
                         dtype=np.int64).astype(np.int32)
    rows = rng.integers(-(1 << 31), (1 << 31) - 1, (N, lanes),
                        dtype=np.int64).astype(np.int32)
    want = np.asarray(jnp.asarray(state).at[
        jnp.where(jnp.asarray(mask), jnp.asarray(slots), S)].set(
            jnp.asarray(rows), mode="drop"))
    got = np.asarray(bs.scatter_rows_presorted(
        jnp.asarray(state), jnp.asarray(slots), jnp.asarray(mask),
        jnp.asarray(rows), interpret=True))
    np.testing.assert_array_equal(got, want)


def test_sweep_sorting_entry_matches_xla_scatter(bs):
    """The entry that sorts its own batch: duplicate runs with the
    segment-last mask, padding below zero first."""
    rng = np.random.default_rng(5)
    slots = np.sort(rng.integers(0, S, N - 9)).astype(np.int32)
    slots = np.r_[np.full(9, -1, np.int32), slots]
    mask = (slots >= 0) & np.r_[slots[:-1] != slots[1:], True]
    state = rng.integers(-1000, 1000, (S, 6)).astype(np.int32)
    rows = rng.integers(-1000, 1000, (N, 6)).astype(np.int32)
    want = state.copy()
    want[slots[mask]] = rows[mask]
    got = np.asarray(bs.scatter_rows(
        jnp.asarray(state), jnp.asarray(slots), jnp.asarray(mask),
        jnp.asarray(rows), interpret=True))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("rows,batch,ok", [
    (12_500_224, 1 << 19, True),    # uniform cell's digest chunk
    (1_250_048, 1 << 17, True),     # Zipf cell's first chunk
    (12_500_224, 8192, False),      # micro step on the 0.5 GB table
    (1_250_048, 2048, False),       # below one update per 512 rows
    (1_250_048, 4096, True),
    (1000, 1024, False),            # table not whole tiles
    (65_536, 512, False),           # small batch on a small table
    (65_536, 1024, True),
])
def test_supported_is_a_rule_on_shapes(rows, batch, ok):
    from ratelimiter_tpu.ops.pallas import block_scatter

    assert block_scatter.supported((rows, 6), batch) is ok
