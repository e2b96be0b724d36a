"""Test harness config.

Tests run on CPU with 8 virtual devices so the sharded (multi-chip) engine
paths are exercised without TPU hardware — the key-space sharding is
device-count agnostic (SURVEY.md §4 "multi-device tests runnable on CPU").

jax may already be imported by the time this conftest runs (pytest's import
graph pulls it in), so the platform override must go through jax.config —
the JAX_PLATFORMS env var is latched at import.  XLA_FLAGS is read at first
backend initialization, which has not happened yet, so the env var works for
the virtual device count.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax

jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running soak/drill tests (excluded from tier-1 "
        "'-m \"not slow\"' runs; verify.sh runs them with RUN_SLOW=1)")
