"""Bring-up guards: nothing on the main path hides a missing or failing
TPU (ISSUE 21).  These run on the CPU; where the code branches on the
backend, the test steers ``jax.default_backend`` itself."""

import os
import subprocess
import sys

import pytest

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_fails_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    lines = proc.stdout.strip().splitlines()
    assert not lines or '"ok": true' not in lines[-1]
    assert "no TPU" in proc.stderr


def test_compile_cache_dir_placement(monkeypatch, tmp_path):
    from ratelimiter_tpu.utils import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache.cache_dir() == os.path.join(REPO, ".jax_cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.cache_dir() == str(tmp_path)


def test_enable_compile_cache_honours_env(monkeypatch, tmp_path):
    from jax.experimental.compilation_cache import compilation_cache

    from ratelimiter_tpu.utils.compile_cache import enable_compile_cache

    old = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    try:
        enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    finally:
        jax.config.update("jax_compilation_cache_dir", old)
        compilation_cache.reset_cache()


def _fail(*_a, **_k):
    raise RuntimeError("lowering refused")


@pytest.mark.parametrize("kernel", ["block_scatter", "relay_step", "solver"])
def test_pallas_probe_failure_raises_on_tpu(monkeypatch, kernel):
    from ratelimiter_tpu.ops.pallas import (
        PallasProbeError,
        block_scatter,
        relay_step,
        solver,
    )

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if kernel == "block_scatter":
        monkeypatch.setattr(block_scatter, "_probe_ok", None)
        monkeypatch.setattr(block_scatter, "scatter_rows", _fail)
        probe = block_scatter._probe
    elif kernel == "relay_step":
        for name in ("_probe_ok", "_fallback_reason"):
            monkeypatch.setattr(relay_step, name, None)
        monkeypatch.setattr(relay_step, "_warned", relay_step._warned)
        monkeypatch.setattr(relay_step, "tb_relay_counts_fused", _fail)
        probe = relay_step._probe
    else:
        monkeypatch.setattr(solver, "_pallas_ok", None)
        monkeypatch.setattr(solver, "pallas_solve", _fail)
        probe = solver._pallas_supported
    with pytest.raises(PallasProbeError, match="lowering refused"):
        probe()


def test_pallas_probe_failure_falls_back_off_tpu(monkeypatch):
    """Interpret mode on the CPU keeps the fallback: it exists to
    exercise kernels, and a failing one there is not a device fault."""
    from ratelimiter_tpu.ops.pallas import block_scatter

    monkeypatch.setattr(block_scatter, "_probe_ok", None)
    monkeypatch.setattr(block_scatter, "_INTERPRET", True)
    monkeypatch.setattr(block_scatter, "scatter_rows", _fail)
    assert block_scatter._probe() is False


@pytest.mark.parametrize("kernel", ["block_scatter", "relay_step", "solver"])
def test_interpret_mode_refused_on_tpu(monkeypatch, kernel):
    from ratelimiter_tpu.ops.pallas import (
        PallasProbeError,
        block_scatter,
        relay_step,
        solver,
    )

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if kernel == "block_scatter":
        monkeypatch.setattr(block_scatter, "_probe_ok", None)
        monkeypatch.setattr(block_scatter, "_INTERPRET", True)
        probe = block_scatter._probe
    elif kernel == "relay_step":
        monkeypatch.setattr(relay_step, "_probe_ok", None)
        monkeypatch.setattr(relay_step, "_INTERPRET", True)
        probe = relay_step._probe
    else:
        monkeypatch.setattr(solver, "_pallas_ok", None)
        monkeypatch.setattr(solver, "_PALLAS_INTERPRET", True)
        probe = solver._pallas_supported
    with pytest.raises(PallasProbeError, match="interpret mode"):
        probe()


def test_warmup_failure_propagates_on_tpu(monkeypatch):
    from ratelimiter_tpu.service.wiring import warmup_shapes

    class Broken:
        def __getattr__(self, name):
            return _fail

    storage = type("S", (), {"engine": Broken()})()
    warmup_shapes(storage)  # off the TPU: best-effort
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="lowering refused"):
        warmup_shapes(storage)


def test_dryrun_multichip_requires_its_devices():
    sys.path.insert(0, REPO)
    import __graft_entry__

    with pytest.raises(RuntimeError, match="dryrun needs"):
        __graft_entry__.dryrun_multichip(jax.device_count() + 1)
    assert jax.default_backend() == "cpu"


def test_native_library_rebuilt_on_build_key_mismatch():
    from ratelimiter_tpu.engine import native_index as ni

    if not ni.native_available():
        pytest.skip("native slot index unavailable (no g++?)")
    stamp = ni._LIB_PATH + ".buildkey"
    key = ni.build_key("slot_index.cpp")
    with open(stamp, encoding="ascii") as fh:
        assert fh.read() == key
    before = os.stat(ni._LIB_PATH).st_mtime_ns
    with open(stamp, "w", encoding="ascii") as fh:
        fh.write("built-from-something-else")
    ni._ensure_built(ni._LIB_PATH, "slot_index.cpp")
    with open(stamp, encoding="ascii") as fh:
        assert fh.read() == key
    assert os.stat(ni._LIB_PATH).st_mtime_ns != before


def test_native_build_key_tracks_flags(monkeypatch):
    from ratelimiter_tpu.engine import native_index as ni

    base = ni.build_key("slot_index.cpp")
    monkeypatch.setenv("ARCH", "x86-64-v2")
    assert ni.build_key("slot_index.cpp") != base
