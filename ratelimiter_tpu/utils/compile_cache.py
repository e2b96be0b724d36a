"""Persistent XLA compilation cache: one placement rule for every entry
point (service wiring, hostproc, bench scripts, chip_smoke.py).

The cache lives where ``JAX_COMPILATION_CACHE_DIR`` says when that is
set, and otherwise at the fixed ``<checkout>/.jax_cache`` (listed in
.gitignore).  The directory is never derived from a temp name, a pid or
the time: a directory that moves never hits.  Election verdicts and
probed device rates are kept next to the compiled programs.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def cache_dir() -> str:
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(_CHECKOUT, ".jax_cache"))


def enable_compile_cache() -> None:
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_compilation_cache_dir", cache_dir())
    # 0.1 s: the staged micro steps compile in ~0.3-0.8 s on CPU — under
    # JAX's 1 s default they would recompile at every process boot.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
    # The cache latches "disabled" the first time a compile consults it
    # with no directory configured; a caller that built a storage before
    # wiring would otherwise lose the cache for the whole process.
    compilation_cache.reset_cache()
