"""Measured device step rates for the stream loops' cost models.

The chunk-plan election and the words-vs-digest mode election charge
the device step explicitly (storage/tpu.py).  Through r4 those charges
were constants measured once on a v5e dev chip and frozen into source —
wrong on any other TPU generation, and badly wrong on the CPU devices
the test suite and the local-latency bench run on (VERDICT r4 #5).

This module measures them at runtime: a short chained-step probe (the
same chain-K-steps-in-one-jit, fetch-one-checksum, subtract-RTT method
as bench/device_only.py, shrunk to ~0.1-0.3 s of device time) run once
per (platform, device kind) and cached

- in-process (module dict), and
- on disk next to the compile cache (device_rates_<platform>_<kind>.json)
  so later processes skip the probe entirely.

``RATELIMITER_RATE_PROBE=0`` disables probing (the v5e fallback
constants below are used; tests/conftest.py sets it).  Only that switch
serves them: a probe that fails raises, since an assumed rate for a
device nobody measured would steer every election.
Rates are returned as a dict
``{"s_per_lane", "s_per_unique_sorted", "s_per_unique_unsorted"}``.
The probed artifact additionally carries ``probed_at_ms`` and the
device kind so BENCH_DETAIL can record exactly what the elections ran
on (VERDICT r4 #5 "Done" criterion).
"""

from __future__ import annotations

import json
import os
import time
from typing import Dict, Optional

# v5e dev-chip measurements (ROUND_NOTES r4, bench/device_only.py):
# relay words step 58 ns/lane; digest counts step 24.6 ns/unique through
# the dense presorted sweep, 52.2 ns through XLA's per-index scatter.
FALLBACK_RATES: Dict[str, float] = {
    "s_per_lane": 60e-9,
    "s_per_unique_sorted": 25e-9,
    "s_per_unique_unsorted": 52e-9,
}

_mem_cache: Dict[str, Dict] = {}


def _cache_path(platform: str, kind: str) -> Optional[str]:
    from ratelimiter_tpu.utils.compile_cache import cache_dir

    base = cache_dir()
    safe_kind = "".join(ch if ch.isalnum() else "_" for ch in kind)[:40]
    return os.path.join(base, f"device_rates_{platform}_{safe_kind}.json")


def _probe() -> Dict[str, float]:
    """Measure the three step rates on the default device (~0.1-0.3 s
    of device time + one compile per step shape, disk-cached)."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ratelimiter_tpu import RateLimitConfig
    from ratelimiter_tpu.engine.state import LimiterTable
    from ratelimiter_tpu.ops import relay
    from ratelimiter_tpu.ops.token_bucket import make_tb_packed

    num_slots = 1 << 19
    lanes = 1 << 17
    k_steps = 16
    table = LimiterTable()
    lid = table.register(RateLimitConfig(
        max_permits=100, window_ms=60_000, refill_rate=50.0))
    tarr = table.device_arrays
    lid_dev = jnp.int32(lid)
    rb = 8

    tiny = jax.jit(lambda v: v.sum())
    np.asarray(tiny(jnp.zeros(8, jnp.int32)))
    t0 = time.perf_counter()
    for _ in range(2):
        np.asarray(tiny(jnp.zeros(8, jnp.int32)))
    rtt_s = (time.perf_counter() - t0) / 2

    base = np.arange(lanes, dtype=np.uint32) * (num_slots // lanes)
    shuf = np.random.default_rng(9).permutation(base).astype(np.uint32)

    def chain(step_fn):
        @functools.partial(jax.jit, donate_argnums=0)
        def run(packed, now0):
            def body(i, carry):
                packed, acc = carry
                packed, out = step_fn(packed, now0 + i)
                return packed, acc + jnp.sum(out.astype(jnp.int64))

            packed, acc = jax.lax.fori_loop(0, k_steps, body,
                                            (packed, jnp.int64(0)))
            return packed, acc

        return run

    words = jnp.asarray((base << np.uint32(rb + 1)) | np.uint32(1))
    uw_sorted = jnp.asarray((base << np.uint32(rb + 1))
                            | np.uint32(1 << 1))
    uw_shuf = jnp.asarray((shuf << np.uint32(rb + 1)) | np.uint32(1 << 1))

    def relay_step(packed, now):
        return relay.tb_relay_bits(packed, tarr, words, lid_dev, now,
                                   rank_bits=rb)

    def digest_step(uw, sorted_flag):
        def step(packed, now):
            return relay.tb_relay_counts(
                packed, tarr, uw, lid_dev, now, rank_bits=rb,
                out_dtype=jnp.uint8, slots_sorted=sorted_flag)

        return step

    def measure(step_fn) -> float:
        fn = chain(step_fn)
        packed, acc = fn(make_tb_packed(num_slots), jnp.int64(1_000_000))
        int(np.asarray(acc))  # compile + settle
        t0 = time.perf_counter()
        packed, acc = fn(packed, jnp.int64(2_000_000))
        int(np.asarray(acc))
        dt = time.perf_counter() - t0
        return max(dt - rtt_s, 1e-6) / (k_steps * lanes)

    from ratelimiter_tpu.ops.pallas import block_scatter
    from ratelimiter_tpu.ops.pallas import relay_step as fused_relay

    rates = {
        "s_per_lane": measure(relay_step),
        "s_per_unique_unsorted": measure(digest_step(uw_shuf, False)),
    }
    if block_scatter.enabled((num_slots, 2), lanes):
        rates["s_per_unique_sorted"] = measure(digest_step(uw_sorted, True))
    else:  # sorted sweep can't engage on this backend: same cost
        rates["s_per_unique_sorted"] = rates["s_per_unique_unsorted"]
    # Fused Pallas relay step (per-path election; ops/pallas/relay_step):
    # when it is elected on this device the engine's sorted digest
    # dispatch actually RUNS it, so the sorted rate the stream elections
    # charge must be the better of the two — both raw rates stay
    # recorded so BENCH_DETAIL shows what the election saw.
    if fused_relay.enabled((num_slots, 4), lanes, rb):
        def fused_step(packed, now):
            return fused_relay.tb_relay_counts_fused(
                packed, tarr, uw_sorted, lid_dev, now, rank_bits=rb,
                interpret=fused_relay.interpret_mode())

        rates["s_per_unique_fused"] = measure(fused_step)
        rates["s_per_unique_sorted"] = min(rates["s_per_unique_sorted"],
                                           rates["s_per_unique_fused"])
    return rates


def get_device_rates() -> Dict:
    """Rates for the default jax backend, probing + caching as
    documented in the module docstring."""
    import jax

    dev = jax.devices()[0]
    platform = dev.platform
    kind = getattr(dev, "device_kind", platform)
    key = f"{platform}/{kind}"
    hit = _mem_cache.get(key)
    if hit is not None:
        return hit
    # The opt-out must beat the disk cache: tests (and any run pinning
    # deterministic election inputs) set RATELIMITER_RATE_PROBE=0 and
    # must get the fallback constants even when an earlier bench run
    # left a probe artifact on this host.
    if os.environ.get("RATELIMITER_RATE_PROBE", "1") == "0":
        rates = dict(FALLBACK_RATES, source="fallback", device=key)
        _mem_cache[key] = rates
        return rates
    path = _cache_path(platform, kind)
    if path and os.path.exists(path):
        try:
            with open(path, "r", encoding="utf-8") as fh:
                rates = json.load(fh)
            if all(k in rates for k in FALLBACK_RATES):
                _mem_cache[key] = rates
                return rates
        except Exception:  # noqa: BLE001 — corrupt cache: re-probe
            pass
    rates = dict(_probe(), source="probe", device=key,
                 probed_at_ms=int(time.time() * 1000))
    _mem_cache[key] = rates
    if path:
        try:
            os.makedirs(os.path.dirname(path), exist_ok=True)
            tmp = path + ".tmp"
            with open(tmp, "w", encoding="utf-8") as fh:
                json.dump(rates, fh)
            os.replace(tmp, path)
        except Exception:  # noqa: BLE001 — disk cache is best-effort
            pass
    return rates
