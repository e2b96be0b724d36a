"""Bring-up smoke: the limiter's main path on an attached TPU, every
decision checked against the executable oracle (semantics/oracle.py).

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the sharded path on four chips

One process, which never starts a child: a chip belongs to one process
at a time.  Phases, in order, each printing one JSON line with its wall
seconds, compile seconds and decision count:

1. device   — requires TPU devices (no CPU fallback), the native slot
              index built from the committed source, the Pallas probes
              (they raise on a TPU backend) and the device-rate probe.
2. tb_stream — BASELINE config 2: token bucket over 1M keys, Zipf(1.1),
              through ``try_acquire_stream_ids`` plus one string-key pass
              through ``try_acquire_many``; == ``TokenBucketOracle``.
3. sw_stream — BASELINE config 3: sliding window over 10M keys, uniform,
              slot table sized for 10M keys; == ``SlidingWindowOracle``.
4. served   — ``build_app`` + ``make_server`` on the TPU backend; the
              reference's 200/429 sequences, then zero fail-open,
              breaker and degraded decisions in /actuator/metrics.

With ``--chips 4`` the streams run on ``ShardedDeviceEngine`` over a
4-device mesh, each shard's state and tables are checked to live on
their own device, and the service runs with ``parallel.shard=auto``.

The last stdout line is exactly ``{"ok": true, "device": {...}}``; any
mismatch or exception exits non-zero before it.  Rates printed on the
earlier lines are for orientation only: they are not benchmark numbers.
The compile cache follows utils/compile_cache.py
(``JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 20261015
T0_MS = 1_760_000_020_000  # fixed injected clock (20 s into a minute)

# Deployment scale of BASELINE configs 2 and 3 (bench.py's full scale).
SIZES = {
    "tb_keys": 1_000_000,
    "tb_pass": 1 << 21,     # two passes: >= 4M stream decisions
    "str_keys": 1 << 18,    # one string-key pass
    "sw_keys": 10_000_000,
    "sw_pass": 1 << 20,     # two passes: >= 2M stream decisions
    "batch": 1 << 18,
    "subbatches": 8,
}


class SmokeError(AssertionError):
    pass


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


class Compiles:
    """Backend compile seconds and count as JAX reports them (a
    persistent-cache hit counts too, at its retrieval time), and the
    persistent cache's hits."""

    def __init__(self):
        import jax

        self.events: list = []
        self.names: list = []
        self.cache_hits = 0

        def on_duration(name, secs, fun_name="?", **_kw):
            if name == "/jax/core/compile/backend_compile_duration":
                self.events.append(secs)
                self.names.append(fun_name)

        def on_event(name, **_kw):
            if name == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def total(self) -> float:
        return float(sum(self.events))


def run_phase(name: str, compiles: Compiles, fn) -> dict:
    n0, hits0 = len(compiles.events), compiles.cache_hits
    t0 = time.perf_counter()
    info = fn()
    wall = time.perf_counter() - t0
    evs = compiles.events[n0:]
    slowest = sorted(zip(evs, compiles.names[n0:]), reverse=True)[:3]
    rec = {"phase": name, "wall_s": wall, "compile_s": float(sum(evs)),
           "n_compiles": len(evs),
           "cache_hits": compiles.cache_hits - hits0,
           "slowest_compiles": [[n, s] for s, n in slowest], **info}
    emit(rec)
    return rec


def require_devices(n_chips: int) -> list:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SmokeError(f"no TPU: JAX found {devs[0].platform} devices "
                         f"({len(devs)}); this smoke never runs on the CPU")
    if len(devs) < n_chips:
        raise SmokeError(f"--chips {n_chips} needs {n_chips} TPU devices, "
                         f"JAX found {len(devs)}")
    return devs[:n_chips]


def check_oracle(phase: str, got, want) -> None:
    got = np.asarray(got, dtype=bool)
    bad = np.flatnonzero(got != want)
    if len(bad):
        raise SmokeError(f"{phase}: {len(bad)} of {len(want)} decisions "
                         f"differ from the oracle (first at {bad[0]})")


def oracle_decisions(oracle, keys, now_ms: int) -> np.ndarray:
    acquire = oracle.try_acquire
    return np.fromiter((acquire(k, 1, now_ms).allowed for k in keys),
                       dtype=bool, count=len(keys))


def make_storage(devices, num_keys: int, clock):
    """A TpuBatchedStorage sized for ``num_keys``: single-device engine on
    one chip, the program's sharded storage over ``devices`` otherwise."""
    from ratelimiter_tpu.ops.pallas.block_scatter import align_slots
    from ratelimiter_tpu.storage import TpuBatchedStorage, sharded_storage

    num_slots = align_slots(int(num_keys * 1.25))
    if len(devices) == 1:
        return TpuBatchedStorage(num_slots=num_slots, clock_ms=clock)
    return sharded_storage(num_slots, devices, clock_ms=clock)


def shard_placement(engine, devices, tables: bool = True) -> dict:
    """Each shard's state parts (and, once per-shard dispatch has placed
    them, its limiter-table copy) on its own device."""
    import jax

    def device_of(tree):
        ids = {d.id for leaf in jax.tree.leaves(tree) for d in leaf.devices()}
        return ids.pop() if len(ids) == 1 else sorted(ids)

    want = [d.id for d in devices]
    placed = {algo: [device_of(p) for p in parts]
              for algo, parts in engine._parts.items()}
    if tables:
        parts = engine._table_parts[1]
        placed["table"] = [device_of(parts[s]) for s in sorted(parts)]
    for what, ids in placed.items():
        if ids != want:
            raise SmokeError(f"{what} shards on devices {ids}, "
                             f"want one per device {want}")
    return placed


def phase_device(devices) -> dict:
    import jax

    from ratelimiter_tpu.engine.native_index import native_available
    from ratelimiter_tpu.ops import pallas

    if not native_available():
        raise SmokeError("native slot index did not load (make -C native)")
    pallas.settle_all()  # probes raise on a TPU backend
    return {"decisions": 0, "platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices),
            "all_devices": len(jax.devices()), "native_index": True}


def phase_tb_stream(devices, rng) -> dict:
    from ratelimiter_tpu import RateLimitConfig
    from ratelimiter_tpu.algorithms import TokenBucketRateLimiter
    from ratelimiter_tpu.bench.harness import zipf_stream
    from ratelimiter_tpu.metrics import MeterRegistry
    from ratelimiter_tpu.semantics import TokenBucketOracle

    s = SIZES
    cfg = RateLimitConfig(max_permits=100, window_ms=60_000,
                          refill_rate=50.0)
    clock = [T0_MS]
    storage = make_storage(devices, s["tb_keys"], lambda: clock[0])
    registry = MeterRegistry()
    limiter = TokenBucketRateLimiter(storage, cfg, registry)
    oracle = TokenBucketOracle(cfg)
    passes = []
    # Two passes, one second apart: the second sees 50 tokens of refill
    # on every key the first drained.
    for step in range(2):
        ids = zipf_stream(rng, s["tb_keys"], s["tb_pass"])
        t0 = time.perf_counter()
        got = limiter.try_acquire_stream_ids(
            ids, batch=s["batch"], subbatches=s["subbatches"])
        wall = time.perf_counter() - t0
        check_oracle(f"tb_stream pass {step}", got,
                     oracle_decisions(oracle, ids.tolist(), clock[0]))
        passes.append({"decisions": len(ids), "wall_s": wall,
                       "allowed": int(np.sum(got))})
        clock[0] += 1000
    # One string-key pass on its own limiter (its own lid and oracle).
    str_limiter = TokenBucketRateLimiter(storage, cfg, registry)
    str_oracle = TokenBucketOracle(cfg)
    keys = [f"user-{k}" for k in
            zipf_stream(rng, s["tb_keys"], s["str_keys"]).tolist()]
    t0 = time.perf_counter()
    got = str_limiter.try_acquire_many(keys)
    wall = time.perf_counter() - t0
    check_oracle("tb_stream strings", got,
                 oracle_decisions(str_oracle, keys, clock[0]))
    passes.append({"decisions": len(keys), "wall_s": wall,
                   "allowed": int(np.sum(got)), "keys": "str"})
    info = {"decisions": sum(p["decisions"] for p in passes),
            "oracle_mismatches": 0, "keys": s["tb_keys"],
            "num_slots": storage.engine.num_slots, "passes": passes}
    if len(devices) > 1:
        info["shard_devices"] = shard_placement(storage.engine, devices)
    storage.close()
    return info


def phase_sw_stream(devices, rng) -> dict:
    from ratelimiter_tpu import RateLimitConfig
    from ratelimiter_tpu.algorithms import SlidingWindowRateLimiter
    from ratelimiter_tpu.bench.harness import uniform_stream
    from ratelimiter_tpu.metrics import MeterRegistry
    from ratelimiter_tpu.semantics import SlidingWindowOracle

    s = SIZES
    cfg = RateLimitConfig(max_permits=100, window_ms=60_000)
    clock = [T0_MS]
    storage = make_storage(devices, s["sw_keys"], lambda: clock[0])
    limiter = SlidingWindowRateLimiter(storage, cfg, MeterRegistry())
    oracle = SlidingWindowOracle(cfg)
    passes = []
    # The second pass lands in the next window, so keys the first pass
    # touched are read through the weighted previous-window estimate.
    for step in range(2):
        ids = uniform_stream(rng, s["sw_keys"], s["sw_pass"])
        t0 = time.perf_counter()
        got = limiter.try_acquire_stream_ids(
            ids, batch=s["batch"], subbatches=s["subbatches"])
        wall = time.perf_counter() - t0
        check_oracle(f"sw_stream pass {step}", got,
                     oracle_decisions(oracle, ids.tolist(), clock[0]))
        passes.append({"decisions": len(ids), "wall_s": wall,
                       "allowed": int(np.sum(got))})
        clock[0] += 50_000
    info = {"decisions": sum(p["decisions"] for p in passes),
            "oracle_mismatches": 0, "keys": s["sw_keys"],
            "num_slots": storage.engine.num_slots, "passes": passes}
    if len(devices) > 1:
        info["shard_devices"] = shard_placement(storage.engine, devices)
    storage.close()
    return info


def _request(port: int, method: str, path: str, headers=None, body=None):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=data, method=method,
        headers={"Content-Type": "application/json", **(headers or {})})
    try:
        with urllib.request.urlopen(req, timeout=120) as resp:
            return resp.status, json.loads(resp.read() or b"{}")
    except urllib.error.HTTPError as err:
        return err.code, json.loads(err.read() or b"{}")


def _away_from_minute_edge(margin_ms: int = 15_000) -> None:
    """The served limiters run on the wall clock with 60 s windows: keep
    each 200/429 sequence inside one window."""
    into = int(time.time() * 1000) % 60_000
    if into > 60_000 - margin_ms:
        time.sleep((60_000 - into) / 1000 + 0.5)


def _served_engine(storage):
    """The device engine under the service's wrapper chain (retry,
    breaker, ...), which must be the one the phase asked for."""
    seen = set()
    while storage is not None and id(storage) not in seen:
        seen.add(id(storage))
        engine = getattr(storage, "engine", None)
        if engine is not None:
            return engine
        storage = getattr(storage, "_inner", None)
    raise SmokeError("the service built no device engine")


def phase_served(devices) -> dict:
    from ratelimiter_tpu import RateLimitConfig
    from ratelimiter_tpu.semantics import TokenBucketOracle
    from ratelimiter_tpu.service.app import make_server
    from ratelimiter_tpu.service.props import AppProperties
    from ratelimiter_tpu.service.wiring import build_app

    props = AppProperties({
        "storage.backend": "tpu",
        "parallel.shard": "auto" if len(devices) > 1 else "off",
        "warmup.enabled": "true",
        "server.port": "0",
    })
    ctx = build_app(props)
    engine = _served_engine(ctx.storage)
    srv = make_server(ctx, port=0)
    thread = threading.Thread(target=srv.serve_forever, daemon=True)
    thread.start()
    port = srv.server_address[1]
    n = 0
    try:
        _away_from_minute_edge()
        codes = [_request(port, "GET", "/api/data",
                          {"X-User-ID": "smoke-user"})[0]
                 for _ in range(101)]
        n += len(codes)
        if codes != [200] * 100 + [429]:
            raise SmokeError(f"/api/data gave {codes}, want 100 x 200 "
                             "then 429")
        codes = [_request(port, "POST", "/api/login",
                          body={"username": "smoke-login"})[0]
                 for _ in range(11)]
        n += len(codes)
        if codes != [200] * 10 + [429]:
            raise SmokeError(f"/api/login gave {codes}, want 10 x 200 "
                             "then 429")
        # Burst limiter: cap 50, 10/s refill.  Sizes sit a second or
        # more of refill away from their threshold, so the oracle at
        # the client's send time decides them.
        oracle = TokenBucketOracle(RateLimitConfig(
            max_permits=50, window_ms=60_000, refill_rate=10.0))
        sizes = [30, 30, 20, 25, 51]
        got, want = [], []
        for size in sizes:
            now = int(time.time() * 1000)
            want.append(oracle.try_acquire("smoke-batch", size, now).allowed)
            code, _ = _request(port, "POST", "/api/batch",
                               {"X-User-ID": "smoke-batch"}, {"size": size})
            got.append(code == 200)
            if code not in (200, 429):
                raise SmokeError(f"/api/batch size {size} gave {code}")
        n += len(sizes)
        if got != want:
            raise SmokeError(f"/api/batch sizes {sizes} allowed {got}, "
                             f"oracle {want}")
        status, metrics = _request(port, "GET", "/actuator/metrics")
        meters = metrics["meters"]
        hidden = {name: meters.get(name, 0) for name in (
            "ratelimiter.failopen.allowed", "ratelimiter.breaker.opened",
            "ratelimiter.breaker.short_circuited",
            "ratelimiter.degraded.decisions")}
        if status != 200 or any(hidden.values()):
            raise SmokeError(f"device failures hidden by the service: "
                             f"{hidden}")
        info = {"decisions": n, "requests": n, "batch_sizes": sizes,
                "batch_allowed": got, **hidden,
                "engine": type(engine).__name__}
        if len(devices) > 1:
            # The micro-batch path steps the whole mesh with a replicated
            # table; only the state is sharded.
            info["shard_devices"] = shard_placement(engine, devices,
                                                    tables=False)
    finally:
        srv.shutdown()
        thread.join(timeout=10)
        ctx.close()
    return info


def pallas_verdicts() -> dict:
    from ratelimiter_tpu.ops.pallas import election_report, relay_step

    return {"pallas_elections": election_report(),
            "relay_fused": relay_step.fallback_info()}


def run(n_chips: int) -> dict:
    devices = require_devices(n_chips)
    import ratelimiter_tpu
    from ratelimiter_tpu.utils.compile_cache import cache_dir, \
        enable_compile_cache

    pkg = os.path.dirname(os.path.abspath(ratelimiter_tpu.__file__))
    if pkg != os.path.join(HERE, "ratelimiter_tpu"):
        raise SmokeError(f"ratelimiter_tpu imported from {pkg}, not from "
                         f"this checkout ({HERE})")
    enable_compile_cache()
    compiles = Compiles()
    rng = np.random.default_rng(SEED)
    t0 = time.perf_counter()
    run_phase("device", compiles, lambda: phase_device(devices))
    run_phase("tb_stream", compiles, lambda: phase_tb_stream(devices, rng))
    run_phase("sw_stream", compiles, lambda: phase_sw_stream(devices, rng))
    emit(pallas_verdicts())
    run_phase("served", compiles, lambda: phase_served(devices))
    emit({"total_wall_s": time.perf_counter() - t0,
          "total_compile_s": compiles.total(),
          "n_compiles": len(compiles.events),
          "cache_hits": compiles.cache_hits,
          "compile_cache_dir": cache_dir()})
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4 runs the sharded path on a 4-chip mesh")
    args = parser.parse_args(argv)
    device = run(args.chips)
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
