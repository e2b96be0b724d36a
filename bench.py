"""Driver benchmark: prints ONE JSON line with the headline metric.

Headline: end-to-end rate-limit decisions/sec on a 1M-key token-bucket
Zipf(1.1) stream (BASELINE.json config #2) — integer keys in, allow/deny
out, through the native slot index + the pipelined relay/digest device path
on one chip.  vs_baseline compares against the reference's published 80,192
req/s (README single-key sliding-window, local cache on, M1 + Redis —
BASELINE.md).

Robustness discipline (VERDICT r2 #1 — the driver's recorded number must
match the code's ability):

- Every stream scenario runs a FULL untimed warmup pass first.  The relay
  chunk-growth schedule is deterministic in the key stream, so the warmup
  visits every chunk shape the timed passes will visit — no mid-timing
  XLA compiles (r2's prime suspect for the 5x driver/builder swing).
- Timed passes record a per-pass phase breakdown (assign_s / host_s /
  fetch_s / wire_bytes / chunks) from the storage's stream instrumentation
  plus the number and seconds of backend compiles that fired inside the
  timed region — so BENCH_DETAIL explains where the seconds went.
- If the pass walls spread wider than 1.6x, ONE extra pass runs; every
  pass is recorded.

Detailed results for all scenarios land in BENCH_DETAIL.json:
  1. single-key sliding window, 10 threads, through the micro-batcher
     (a CPU-device run of the same code in a child process is recorded
     as sw_single_key_threaded_local — the RTT<<TTL regime the
     reference actually operates in)
  2. 1M-key token bucket, Zipf(1.1)      [headline, streaming path]
  3. 10M-key sliding window, uniform     (streaming path)
  4. 100K-tenant multi-config mix        (churn pass and resident-lid
     steady-state passes, reported separately)
  5. burst batch-acquire tryAcquire(key, n in [1,100]) over 1M keys
  plus: a latency-SLO section (per-request percentiles + RTT
  decomposition against the <=1 ms target) and the in-process Pallas
  election verdicts.

Every child process this bench starts is pinned to the CPU at spawn:
this process holds the chip.

Scale knobs: BENCH_SCALE=small|full (default full on TPU, small elsewhere).
The persistent XLA compilation cache (utils/compile_cache.py) makes
repeat runs cheap.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

_REPO = os.path.dirname(os.path.abspath(__file__))
# Every child this bench starts runs on the CPU, pinned at spawn: the
# parent holds the chip, and a chip belongs to one process at a time.
_CPU_CHILD_ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main() -> None:
    import jax

    from ratelimiter_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    platform = jax.devices()[0].platform
    scale = os.environ.get("BENCH_SCALE") or ("full" if platform == "tpu" else "small")
    small = scale == "small"
    log(f"bench: platform={platform} scale={scale}")

    # -- compile accounting: every backend compile that fires inside a timed
    # region is a measurement hazard; count them so the detail can prove a
    # pass was (or was not) compile-contaminated.
    compile_events: list = []

    def _on_event(name, secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            compile_events.append(secs)

    jax.monitoring.register_event_duration_secs_listener(_on_event)

    class _compiles:
        def __enter__(self):
            self._n0 = len(compile_events)
            return self

        def __exit__(self, *a):
            evs = compile_events[self._n0:]
            self.n = len(evs)
            self.secs = round(float(sum(evs)), 3)

    from ratelimiter_tpu import RateLimitConfig
    from ratelimiter_tpu.algorithms import (
        SlidingWindowRateLimiter,
        TokenBucketRateLimiter,
    )
    from ratelimiter_tpu.bench.harness import (
        bench_end_to_end_stream,
        bench_threaded,
        uniform_stream,
        zipf_stream,
    )
    from ratelimiter_tpu.engine.engine import DeviceEngine
    from ratelimiter_tpu.engine.state import LimiterTable
    from ratelimiter_tpu.metrics import MeterRegistry
    from ratelimiter_tpu.storage import TpuBatchedStorage

    from ratelimiter_tpu.utils.tracing import device_profile

    profile_dir = os.environ.get("BENCH_PROFILE")
    rng = np.random.default_rng(42)
    detail = {"platform": platform, "scale": scale}
    t_start = time.time()

    # Which Pallas kernels are LIVE vs silently fallen back (VERDICT r2 #6:
    # the axis must be falsifiable from the artifacts).  settle() is the
    # same cached probe the engines consult, so this records exactly what
    # the scenario dispatches will use.
    from ratelimiter_tpu.ops.pallas import (
        block_scatter,
        election_report,
        relay_step,
        solver,
    )

    detail["pallas"] = {
        "flag": os.environ.get("RATELIMITER_PALLAS", "1"),
        "solver_live": bool(solver.settle()),
        "block_scatter_live": bool(block_scatter.settle()),
        "relay_fused_live": bool(relay_step.settle()),
        # Per-path measured elections (ops/pallas/election.py): which
        # backend serves each Pallas-capable path on THIS device, with
        # the A/B timings the verdicts came from — so a path can never
        # silently run a measured-slower kernel (perf_smoke.py asserts
        # record/verdict consistency in CI).
        "elections": election_report(),
    }
    log(f"pallas: solver_live={detail['pallas']['solver_live']} "
        f"block_scatter_live={detail['pallas']['block_scatter_live']} "
        f"relay_fused_live={detail['pallas']['relay_fused_live']}")

    # Streaming shape: K sub-batches of B per device dispatch.
    B = (1 << 12) if small else (1 << 19)
    K = 4 if small else 8
    super_n = B * K

    def _agg_stats(stats):
        """Collapse per-chunk records into one phase breakdown."""
        if not stats:
            return None
        agg = {
            "chunks": len(stats),
            "assign_s": round(sum(r.get("assign_s", 0) for r in stats), 4),
            # walk_s records are cumulative within a pass: take the max.
            # assign_s is the walk time EXPOSED on the main thread (a
            # prefetched walk that hid under a fetch shows ~0); walk_s is
            # the true walk seconds wherever they ran.
            "walk_s": round(max((r.get("walk_s", 0) for r in stats),
                                default=0.0), 4),
            "host_s": round(sum(r.get("host_s", 0) for r in stats), 4),
            "fetch_s": round(sum(r.get("fetch_s", 0) for r in stats), 4),
            "max_fetch_s": round(max((r.get("fetch_s", 0) for r in stats),
                                     default=0.0), 4),
            "wire_bytes": int(sum(r.get("wire_bytes", 0) for r in stats)),
        }
        for extra in ("dispatch_s", "pack_s"):
            tot = sum(r.get(extra, 0) for r in stats)
            if tot:
                agg[extra] = round(tot, 4)
        modes: dict = {}
        for r in stats:
            m = r.get("mode", "?")
            modes[m] = modes.get(m, 0) + 1
        agg["modes"] = modes
        return agg

    def run_stream(go, key_ids, permits, reps, storage, warmed=False):
        """Full untimed warmup pass (visits every chunk shape the growth
        schedule reaches), then ``reps`` timed passes with per-pass phase
        breakdowns; retries once if the pass walls spread wider than
        1.6x."""
        n = len(key_ids)
        res = {"mode": "stream_ids", "batch": B, "subbatches": K,
               "decisions_per_pass": n}
        if not warmed:
            with _compiles() as cw:
                go(key_ids, permits)
            res["warmup"] = {"n_compiles": cw.n, "compile_s": cw.secs}
        passes = []

        def timed_pass():
            storage.stream_stats = stats = []
            with _compiles() as c:
                t0 = time.perf_counter()
                allowed = go(key_ids, permits)
                wall = time.perf_counter() - t0
            storage.stream_stats = None
            rec = {"wall_s": round(wall, 4),
                   "decisions_per_sec": round(n / wall, 1),
                   "n_compiles": c.n, "compile_s": c.secs,
                   "phase": _agg_stats(stats)}
            passes.append(rec)
            return allowed

        for _ in range(reps):
            allowed = timed_pass()
        walls = [p["wall_s"] for p in passes]
        if platform == "tpu" and max(walls) > 1.6 * min(walls):
            # A pass was degraded by something outside the code (a
            # noisy neighbor): one retry, recorded.
            res["retried"] = True
            allowed = timed_pass()
        total_wall = sum(p["wall_s"] for p in passes)
        rates = sorted(p["decisions_per_sec"] for p in passes)
        res.update({
            "decisions": n * len(passes), "wall_s": round(total_wall, 4),
            "decisions_per_sec": n * len(passes) / total_wall,
            # The median pass is robust to single multi-second link
            # stalls (observed: a 65 s zero-compile fetch on an
            # otherwise-normal run); the aggregate and every pass stay
            # recorded alongside.
            "median_pass_decisions_per_sec": rates[len(rates) // 2],
            "best_pass_decisions_per_sec": rates[-1],
            "passes": passes,
            "allowed_last_pass": int(allowed.sum()),
        })
        return res

    # -- scenario 2 (headline): 1M-key token bucket, Zipf(1.1) ---------------
    num_keys = 20_000 if small else 1_000_000
    n_requests = super_n * (2 if small else 4)
    log(f"scenario 2: TB Zipf over {num_keys} keys, {n_requests} reqs/pass...")

    tb_cfg = RateLimitConfig(max_permits=100, window_ms=60_000, refill_rate=50.0)
    from ratelimiter_tpu.ops.pallas.block_scatter import align_slots

    storage = TpuBatchedStorage(num_slots=align_slots(
        max(num_keys * 2, 1 << 16)))
    # Auto-elected host-parallel partitioned index (r7): the storage
    # constructions pick it up by default; record what the headline ran
    # with so the walk-term split in the phase lanes is attributable.
    detail["host_parallel"] = {
        "elected": storage._host_parallel,
        "note": ("0 = single-LRU native index; T>1 = T-way partitioned "
                 "walk (engine/partitioned.py), auto-elected from cores "
                 "and table size, explicit kwarg wins")}
    log(f"host_parallel: {storage._host_parallel}")
    tb_limiter = TokenBucketRateLimiter(storage, tb_cfg, MeterRegistry())

    key_ids = zipf_stream(rng, num_keys, n_requests)
    with device_profile(profile_dir):
        res = run_stream(
            lambda ids, p: tb_limiter.try_acquire_stream_ids(
                ids, p, batch=B, subbatches=K),
            key_ids, None, 2 if small else 3, storage)
    detail["tb_1m_zipf_stream_ids"] = res
    # Median pass: robust to single link stalls; every pass + the
    # aggregate are in BENCH_DETAIL with their phase breakdowns.
    headline = res["median_pass_decisions_per_sec"]
    log(f"  stream (int keys): {headline:,.0f} decisions/s median pass "
        f"(aggregate {res['decisions_per_sec']:,.0f}, best "
        f"{res['best_pass_decisions_per_sec']:,.0f})")

    # String-key end-to-end (Python key handling included; streamed).
    # 8M requests (r5, was 2M): the string walk runs ~70 ns/request
    # (pack + hash + probe), so short streams were dominated by the
    # fixed final-fetch round trip and measured the link, not the
    # path.  Per-batch round-trip latency is reported separately
    # (batch_latency) — this figure is sustained throughput.
    n_str = min(n_requests, 50_000 if small else 8_000_000)
    keys = [f"k{i}" for i in key_ids[:n_str]]
    res = bench_end_to_end_stream(tb_limiter, keys, None, storage=storage)
    for p in res["passes"]:  # collapse raw chunk records to phase lanes
        p["phase"] = _agg_stats(p.pop("stats"))
    detail["tb_1m_zipf_end_to_end_strs"] = res
    log(f"  end-to-end (str keys): {res['decisions_per_sec']:,.0f} decisions/s"
        f" (median pass {res['median_pass_decisions_per_sec']:,.0f})")
    storage.close()

    # -- scenario 1: single-key SW, 10 threads through the batcher -----------
    log("scenario 1: single-key sliding window, 10 threads...")
    sw_cfg = RateLimitConfig(max_permits=100, window_ms=60_000,
                             enable_local_cache=True, local_cache_ttl_ms=100)
    storage = TpuBatchedStorage(num_slots=1 << 12, max_delay_ms=0.3)
    sw_limiter = SlidingWindowRateLimiter(storage, sw_cfg, MeterRegistry())
    res = bench_threaded(
        sw_limiter,
        keys_per_thread=lambda t: ["hot-key"],
        n_threads=10,
        requests_per_thread=200 if small else 2000,
    )
    # Context figure: one synchronous decision round trip on this link.
    # When it exceeds the 100 ms local-cache TTL (always true on the dev
    # link, never true on a local-attached TPU), every cache expiry
    # chains a full round trip and the scenario measures the LINK, not
    # the engine — the reference's regime (0.8 ms Redis RTT << TTL)
    # reproduces only with local attachment (see
    # sw_single_key_threaded_local for that regime measured in-process).
    t0 = time.perf_counter()
    for _ in range(3):
        sw_limiter.try_acquire("rtt-probe-key")
    res["device_round_trip_ms"] = round(
        (time.perf_counter() - t0) / 3 * 1000, 1)
    res["note"] = ("per-request latency includes the host<->device link "
                   "RTT of this environment on cache misses; see "
                   "device_round_trip_ms and sw_single_key_threaded_local")
    detail["sw_single_key_threaded"] = res
    log(f"  {res['decisions_per_sec']:,.0f} req/s; "
        f"p99 {res['request_latency']['p99_us']:.0f} us")

    # -- latency-SLO section: per-request percentiles + decomposition --------
    # The <=1 ms p99 target (BASELINE.md) is a LOCAL-attachment claim; this
    # section records the remote link numbers alongside the pieces that compose
    # them (batcher flush delay, device RTT) so the production claim is
    # checkable: p99_local ~= max_delay_ms + device step + PCIe RTT.
    log("latency SLO: 16 threads, distinct keys, percentiles + decomposition...")
    res = bench_threaded(
        sw_limiter,
        keys_per_thread=lambda t: [f"slo-user-{t}-{i}" for i in range(64)],
        n_threads=16,
        requests_per_thread=100 if small else 400,
    )
    res["decomposition"] = {
        "batcher_max_delay_ms": 0.3,
        "device_round_trip_ms": detail["sw_single_key_threaded"][
            "device_round_trip_ms"],
        "target_p99_ms_local": 1.0,
        "note": ("link RTT dominates every percentile here; on local "
                 "attachment the same path's bound is max_delay + one "
                 "device step + PCIe round trip — see "
                 "sw_single_key_threaded_local for the measured "
                 "zero-RTT regime"),
    }
    detail["latency_slo_threaded"] = res
    log(f"  p50 {res['request_latency']['p50_us']:.0f} us, "
        f"p99 {res['request_latency']['p99_us']:.0f} us over "
        f"{res['request_latency']['n_samples']} requests")
    storage.close()

    # -- scenario 1-local: same code, CPU device in-process (RTT ~ 0) --------
    # The reference's operating regime is RTT << cache TTL; a remote link
    # inverts that.  A subprocess pins jax to the in-process CPU device and
    # reruns scenario 1 — same limiter, same batcher, no link.
    log("scenario 1-local: single-key SW, CPU device in-process...")
    try:
        proc = subprocess.run(
            [sys.executable, os.path.join(_REPO, "bench",
                                          "local_single_key.py")],
            capture_output=True, timeout=600, text=True, cwd=_REPO,
            env=_CPU_CHILD_ENV)
        if proc.returncode != 0 or not proc.stdout.strip():
            raise RuntimeError(
                f"rc={proc.returncode} stderr={proc.stderr[-500:]!r}")
        detail["sw_single_key_threaded_local"] = json.loads(
            proc.stdout.strip().splitlines()[-1])
        r = detail["sw_single_key_threaded_local"]
        log(f"  local: {r['decisions_per_sec']:,.0f} req/s; "
            f"p99 {r['request_latency']['p99_us']:.0f} us")
    except Exception as exc:  # noqa: BLE001 — aux section must not kill bench
        detail["sw_single_key_threaded_local"] = {"error": str(exc)}
        log(f"  local single-key failed: {exc}")

    # -- latency SLO, local attachment, realistic load (VERDICT r3 #6) -------
    # 16 threads x 4096 distinct keys, cache OFF: every request crosses
    # the device boundary through the micro-batcher, against the <=1 ms
    # p99 target — with a measured decomposition (flush deadline, single
    # device step) when the backend's floor makes the target unreachable.
    log("latency SLO local: 16 threads, multi-key, cache off (subprocess)...")
    try:
        proc = subprocess.run(
            [sys.executable, os.path.join(_REPO, "bench",
                                          "local_latency_slo.py")],
            capture_output=True, timeout=900, text=True, cwd=_REPO,
            env=_CPU_CHILD_ENV)
        if proc.returncode != 0 or not proc.stdout.strip():
            raise RuntimeError(
                f"rc={proc.returncode} stderr={proc.stderr[-500:]!r}")
        detail["latency_slo_local"] = json.loads(
            proc.stdout.strip().splitlines()[-1])
        r = detail["latency_slo_local"]
        log(f"  local SLO: p50 {r['request_latency']['p50_us']:.0f} us, "
            f"p99 {r['request_latency']['p99_us']:.0f} us "
            f"(target 1000 us, meets={r['meets_target']}; device step "
            f"{r['decomposition']['device_step_16_lanes_ms']} ms)")
    except Exception as exc:  # noqa: BLE001 — aux section must not kill bench
        detail["latency_slo_local"] = {"error": str(exc)}
        log(f"  local SLO failed: {exc}")

    # -- sidecar loopback: production ingress under pipelining load ----------
    # N pipelining clients -> TCP sidecar -> shared micro-batcher
    # (VERDICT #6: the ingress had correctness tests only).  CPU device
    # in its own subprocess — it measures the ingress machinery, and
    # this process owns the TPU.
    log("sidecar loopback: 8 pipelining clients (subprocess)...")
    try:
        proc = subprocess.run(
            [sys.executable, os.path.join(_REPO, "bench",
                                          "sidecar_loopback.py")],
            capture_output=True, timeout=600, text=True, cwd=_REPO,
            env=_CPU_CHILD_ENV)
        if proc.returncode != 0 or not proc.stdout.strip():
            raise RuntimeError(
                f"rc={proc.returncode} stderr={proc.stderr[-500:]!r}")
        detail["sidecar_loopback"] = json.loads(
            proc.stdout.strip().splitlines()[-1])
        r = detail["sidecar_loopback"]
        log(f"  sidecar: {r['decisions_per_sec']:,.0f} decisions/s; "
            f"batch p99 {r['batch_latency']['p99_us']:.0f} us")
    except Exception as exc:  # noqa: BLE001 — aux section must not kill bench
        detail["sidecar_loopback"] = {"error": str(exc)}
        log(f"  sidecar loopback failed: {exc}")

    # -- coalesce smoke: Zipf key coalescing A/B (v5 ingest digest) ----------
    # The wire-speed ingestion claim: repeat-heavy Zipf traffic coalesces
    # to one weighted decision per unique key, bit-identical to the
    # sequential oracle.  Subprocess (CPU in-process device).
    log("coalesce smoke: Zipf digest vs rank-major scan (subprocess)...")
    try:
        proc = subprocess.run(
            [sys.executable, os.path.join(_REPO, "bench",
                                          "coalesce_smoke.py")],
            capture_output=True, timeout=600, text=True, cwd=_REPO,
            env=_CPU_CHILD_ENV)
        if proc.returncode != 0 or not proc.stdout.strip():
            raise RuntimeError(
                f"rc={proc.returncode} stderr={proc.stderr[-500:]!r}")
        detail["coalesce_smoke"] = json.loads(
            proc.stdout.strip().splitlines()[-1])
        r = detail["coalesce_smoke"]
        log(f"  coalesce: {r['coalesce_ratio']}x vs uncoalesced scan "
            f"({r['coalesced_decisions_per_sec']:,.0f}/s; "
            f"{r['oracle_mismatches']} oracle mismatches)")
    except Exception as exc:  # noqa: BLE001 — aux section must not kill bench
        detail["coalesce_smoke"] = {"error": str(exc)}
        log(f"  coalesce smoke failed: {exc}")

    # -- scenario 3: 10M-key sliding window, uniform (streaming) -------------
    num_keys3 = 50_000 if small else 10_000_000
    n3 = super_n * (2 if small else 4)
    log(f"scenario 3: SW uniform over {num_keys3} keys (stream)...")
    storage3 = TpuBatchedStorage(
        num_slots=align_slots(max(int(num_keys3 * 1.25), 1 << 16)))
    sw3 = SlidingWindowRateLimiter(
        storage3,
        RateLimitConfig(max_permits=100, window_ms=60_000,
                        enable_local_cache=False),
        MeterRegistry())
    res = run_stream(
        lambda ids, p: sw3.try_acquire_stream_ids(ids, p, batch=B,
                                                  subbatches=K),
        uniform_stream(rng, num_keys3, n3), None, 2 if small else 3,
        storage3)
    detail["sw_10m_uniform_stream"] = res
    log(f"  stream: {res['decisions_per_sec']:,.0f} decisions/s")
    storage3.close()

    # -- scenario 4: 100K-tenant multi-config mix (multi-lid stream) ---------
    # Measured in TWO phases (VERDICT r2 #4): a CHURN pass where every lid
    # is a first touch (the warmup fills the slot space with a disjoint
    # key population, so the timed churn pass pays full eviction + lid
    # delta-upload cost at warm compile shapes), then STEADY-STATE passes
    # where the lids are device-resident and the digest wire cost drops to
    # ~5-6 B/unique.
    n_tenants = 1000 if small else 100_000
    n4 = super_n * (2 if small else 3)
    log(f"scenario 4: {n_tenants}-tenant mix (churn + steady stream)...")
    table = LimiterTable(capacity=n_tenants + 2)
    lids = np.asarray(
        [table.register(RateLimitConfig(
            max_permits=50 + (i % 100), window_ms=60_000,
            refill_rate=float(5 + i % 20)))
         for i in range(n_tenants)], dtype=np.int64)
    storage4 = TpuBatchedStorage(
        engine=DeviceEngine(num_slots=align_slots(max(n_tenants * 8, 1 << 16)),
                            table=table))
    tenant_of_req = rng.integers(0, n_tenants, size=n4)
    # ~8 user keys per tenant, per-request tenant policy.
    keys4 = (tenant_of_req * 8 + rng.integers(0, 8, size=n4)).astype(np.int64)
    lids4 = lids[tenant_of_req]
    # Warmup on a DISJOINT key population: compiles every chunk shape and
    # fills the slot space so the churn pass below is 100% first-touch.
    with _compiles() as cw:
        storage4.acquire_stream_ids(
            "tb", lids4, keys4 + n_tenants * 8, batch=B, subbatches=K)
    storage4.stream_stats = churn_stats = []
    with _compiles() as cc:
        t0 = time.perf_counter()
        allowed_churn = storage4.acquire_stream_ids("tb", lids4, keys4,
                                                    batch=B, subbatches=K)
        churn_wall = time.perf_counter() - t0
    storage4.stream_stats = None
    detail["multi_tenant_100k_churn"] = {
        "mode": "stream_ids_multi_first_touch", "decisions": n4,
        "wall_s": round(churn_wall, 4),
        "decisions_per_sec": round(n4 / churn_wall, 1),
        "tenants": n_tenants, "allowed": int(allowed_churn.sum()),
        "n_compiles": cc.n, "compile_s": cc.secs,
        "warmup": {"n_compiles": cw.n, "compile_s": cw.secs},
        "phase": _agg_stats(churn_stats),
    }
    log(f"  churn (first touch): {n4 / churn_wall:,.0f} decisions/s")
    # run_stream's own untimed warmup doubles as the first steady pass:
    # the zero-delta resident-lid dispatch is a NEW compile shape after a
    # churn pass (delta lanes shrink to the floor bucket), and it must
    # settle before the timed steady passes.
    res = run_stream(
        lambda ids, p: storage4.acquire_stream_ids("tb", lids4, ids,
                                                   batch=B, subbatches=K),
        keys4, None, 2 if small else 3, storage4)
    res["mode"] = "stream_ids_multi_steady"
    res["tenants"] = n_tenants
    detail["multi_tenant_100k_stream"] = res
    log(f"  steady state: {res['decisions_per_sec']:,.0f} decisions/s")
    storage4.close()

    # -- scenario 5: burst batch-acquire over 1M keys (streaming) ------------
    num_keys5 = 20_000 if small else 1_000_000
    n5 = super_n * (2 if small else 3)
    log(f"scenario 5: burst batch-acquire over {num_keys5} keys...")
    storage5 = TpuBatchedStorage(num_slots=align_slots(
        max(num_keys5 * 2, 1 << 16)))
    tb5 = TokenBucketRateLimiter(
        storage5,
        RateLimitConfig(max_permits=100, window_ms=60_000, refill_rate=100.0),
        MeterRegistry())
    key5 = uniform_stream(rng, num_keys5, n5)
    perms5 = rng.integers(1, 101, size=n5).astype(np.int64)
    res = run_stream(
        lambda ids, p: tb5.try_acquire_stream_ids(ids, p, batch=B,
                                                  subbatches=K),
        key5, perms5, 2, storage5)
    detail["tb_burst_batch_stream"] = res
    log(f"  stream: {res['decisions_per_sec']:,.0f} decisions/s")
    storage5.close()

    # -- sharded scaling (virtual CPU mesh, subprocess) ----------------------
    # The multi-chip sharding machinery measured 1 -> 8 shards; a separate
    # process because the CPU backend must be selected before any device
    # work (this process owns the TPU).
    log("sharded scaling (8-device virtual CPU mesh, subprocess)...")
    try:
        proc = subprocess.run(
            [sys.executable, os.path.join(_REPO, "bench",
                                          "sharded_scaling.py")],
            capture_output=True, timeout=600, text=True, cwd=_REPO,
            env=_CPU_CHILD_ENV)
        if proc.returncode != 0 or not proc.stdout.strip():
            raise RuntimeError(
                f"rc={proc.returncode} stderr={proc.stderr[-500:]!r}")
        detail["sharded_scaling"] = json.loads(
            proc.stdout.strip().splitlines()[-1])
        for p in detail["sharded_scaling"]["points"]:
            s = p.get("str_end_to_end")
            extra = (f"; strs {s['decisions_per_sec']:,.0f}/s"
                     if s else "")
            log(f"  {p['n_shards']} shard(s): "
                f"{p['decisions_per_sec']:,.0f} decisions/s{extra}")
    except Exception as exc:  # noqa: BLE001 — aux section must not kill bench
        detail["sharded_scaling"] = {"error": str(exc)}
        log(f"  sharded scaling failed: {exc}")

    # Elections resolved lazily during the run (engine dispatches) land
    # in the final record too.
    detail["pallas"]["elections"] = election_report()
    detail["total_bench_seconds"] = time.time() - t_start

    with open(os.path.join(_REPO, "BENCH_DETAIL.json"), "w") as fh:
        json.dump(detail, fh, indent=2)

    baseline = 80_192.0  # reference README throughput (BASELINE.md)
    # Honest labeling: the headline is the MEDIAN timed pass of the
    # int-key stream (robust to single link stalls; aggregate + every
    # pass recorded in BENCH_DETAIL); the string-key end-to-end number
    # lives under tb_1m_zipf_end_to_end_strs.
    print(json.dumps({
        "metric": "tb_1m_keys_zipf_stream_decisions_per_sec_median_pass",
        "value": round(float(headline), 1),
        "unit": "decisions/s",
        "vs_baseline": round(float(headline) / baseline, 2),
    }))


if __name__ == "__main__":
    main()
