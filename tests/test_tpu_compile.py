"""The main path's Pallas kernels compile for a described TPU v5e.

No chip is needed: the TPU compiler is installed here and compiles for a
described ``v5e:2x2`` topology (section 2 of the on-chip-measurement
guide).  This catches what interpret mode cannot — unaligned slices,
fast-memory overruns, a kernel the compiler refuses — at the real widths
the engine dispatches, at no chip time.

The topology is described only inside the module fixture below (never at
import, in ``skipif`` or in ``parametrize``): only one process at a time
may load the TPU library, and every test worker imports this file.
Everything built from the topology is built in fixtures or tests.  Each
compile runs with the persistent cache off, since an entry written for a
described chip cannot be read back here.
"""

import functools
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

S_ROWS = 1 << 20


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 — any failure means no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def compile_for(one_chip):
    """``compile_for(fn, *shapes)`` -> the compiled program's HLO text,
    with the persistent compilation cache off around the compile
    (``donate`` as jit's ``donate_argnums``)."""
    from jax.experimental.compilation_cache import compilation_cache

    def run(fn, *shapes, x64=True, donate=()):
        specs = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
                 for shape, dtype in shapes]
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            with jax.enable_x64(x64):
                return jax.jit(fn, donate_argnums=donate).lower(
                    *specs).compile().as_text()
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()

    return run


@pytest.mark.parametrize("lanes", [8192, 16384])
def test_pallas_solve_compiles(compile_for, lanes):
    from ratelimiter_tpu.ops.pallas.solver import pallas_solve

    text = compile_for(pallas_solve, *[((lanes,), jnp.int32)] * 3)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("algo,lanes", [("tb", 4), ("sw", 6)])
def test_fused_relay_compiles(compile_for, algo, lanes):
    from ratelimiter_tpu.core.config import RateLimitConfig
    from ratelimiter_tpu.engine.state import LimiterTable
    from ratelimiter_tpu.ops.pallas import relay_step

    table = LimiterTable()
    lid = table.register(RateLimitConfig(max_permits=100, window_ms=60_000,
                                         refill_rate=50.0))
    tarr = table.device_arrays
    fused = (relay_step.tb_relay_counts_fused if algo == "tb"
             else relay_step.sw_relay_counts_fused)
    rank_bits = 31 - S_ROWS.bit_length()
    uniques = 1 << 17

    def step(packed, uwords, now):
        return fused(packed, tarr, uwords, jnp.int32(lid), now,
                     rank_bits=rank_bits)

    text = compile_for(step, ((S_ROWS, lanes), jnp.int32),
                       ((uniques,), jnp.uint32), ((), jnp.int64))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("rows,lanes,updates", [
    (12_500_224, 6, 1 << 19),   # sw-api-10m-uniform: 0.5 GB table
    (1_250_048, 4, 1 << 18),    # tb-burst-1m-zipf
    (312_576, 4, 1 << 16),      # tb-burst-1m-zipf.stream-4chip: a shard
])
def test_block_scatter_compiles(compile_for, rows, lanes, updates):
    """The tile sweep at the stream cells' widths.  The table enters and
    leaves the kernel as a bitcast of its own layout: no copy of it."""
    from ratelimiter_tpu.ops.pallas.block_scatter import (
        scatter_rows_presorted,
    )

    text = compile_for(
        functools.partial(scatter_rows_presorted, interpret=False),
        ((rows, lanes), jnp.int32), ((updates,), jnp.int32),
        ((updates,), jnp.bool_), ((updates, lanes), jnp.int32), x64=False,
        donate=0)
    assert "tpu_custom_call" in text
    table = f"s32[{rows},{lanes}]"
    assert not [ln for ln in text.splitlines()
                if table in ln and " copy(" in ln]
    assert f"s32[{lanes},{rows}]" in text and "bitcast(" in text
