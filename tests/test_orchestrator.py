"""Self-healing failover orchestrator (PR 9).

Layers under test, bottom-up:

- the fencing epoch on TpuBatchedStorage: monotonic install, typed
  FencedError on every decision surface, shard-scoped fences that let
  survivor traffic through, lift_fence restoration;
- the orchestrator state machine driven tick-by-tick on a simulated
  clock: SUSPECT needs consecutive failures, a heal inside the
  hysteresis window is a counted false alarm (flap damping), promotion
  falls back to a spare standby, exhausted candidates fail the shard
  closed;
- the full drills: orchestrated_failover_drill (kill one shard of N
  mid-Zipf-stream, ZERO manual actuator calls, oracle-bit-identical,
  re-seeded back to N+1) and orchestrator_flap_drill (transient fault
  never promotes; fenced zombie dispatch refused);
- wiring: ratelimiter.orchestrator.* props build the in-process N+1
  topology, /actuator/orchestrator and the health payload expose it.
"""

import numpy as np
import pytest

from ratelimiter_tpu import RateLimitConfig
from ratelimiter_tpu.engine.state import LimiterTable
from ratelimiter_tpu.metrics import MeterRegistry
from ratelimiter_tpu.parallel import ShardedDeviceEngine, make_mesh
from ratelimiter_tpu.replication import (
    FailoverOrchestrator,
    OrchestratorConfig,
    ShardFailoverRouter,
    ShardStandbySet,
    ShardedReplicationLog,
    ShardedReplicator,
)
from ratelimiter_tpu.storage import TpuBatchedStorage
from ratelimiter_tpu.storage.errors import FencedError

T0 = 1_753_000_000_000


# ---------------------------------------------------------------------------
# Fencing epoch (storage layer)
# ---------------------------------------------------------------------------

def test_fence_is_monotonic_and_refuses_all_surfaces():
    clock = {"t": T0}
    storage = TpuBatchedStorage(num_slots=128, clock_ms=lambda: clock["t"])
    lid = storage.register_limiter("tb", RateLimitConfig(
        max_permits=10, window_ms=1000, refill_rate=5.0))
    storage.acquire("tb", lid, "a", 1)
    storage.fence(3)
    for call in (
        lambda: storage.acquire("tb", lid, "a", 1),
        lambda: storage.acquire_many("tb", [lid], ["a"], [1]),
        lambda: storage.acquire_many_ids("tb", lid, np.array([1]),
                                         np.array([1])),
        lambda: storage.acquire_stream_ids("tb", lid, np.array([1])),
        lambda: storage.acquire_stream_strs("tb", lid, ["a"]),
    ):
        with pytest.raises(FencedError):
            call()
    assert storage.fence_rejected == 5
    assert storage.fence_info()["epoch"] == 3
    # Monotonic: a stale orchestrator replaying an old epoch is refused.
    with pytest.raises(ValueError, match="monotonic"):
        storage.fence(3)
    with pytest.raises(ValueError, match="monotonic"):
        storage.fence(2)
    # A stale lift is refused too; a current one restores service.
    with pytest.raises(ValueError, match="behind"):
        storage.lift_fence(2)
    storage.lift_fence(3)
    out = storage.acquire_many("tb", [lid], ["a"], [1])
    assert len(out["allowed"]) == 1
    storage.close()


def test_shard_scoped_fence_lets_survivors_through():
    from ratelimiter_tpu.parallel.sharded import shard_of_int_keys

    n_sh = 4
    engine = ShardedDeviceEngine(
        slots_per_shard=128, table=LimiterTable(),
        mesh=make_mesh(n_devices=n_sh))
    clock = {"t": T0}
    storage = TpuBatchedStorage(engine=engine, clock_ms=lambda: clock["t"])
    lid = storage.register_limiter("tb", RateLimitConfig(
        max_permits=10, window_ms=1000, refill_rate=5.0))
    keys = np.arange(64, dtype=np.int64)
    shard = shard_of_int_keys(keys, n_sh)
    victim = int(np.bincount(shard, minlength=n_sh).argmax())
    victim_keys = keys[shard == victim]
    other_keys = keys[shard != victim]
    storage.fence(1, shards=(victim,))
    with pytest.raises(FencedError):
        storage.acquire_stream_ids("tb", lid, victim_keys)
    with pytest.raises(FencedError):
        storage.acquire_many_ids("tb", lid, victim_keys[:2],
                                 np.array([1, 1]))
    # Survivor-only dispatches pass the fence.
    got = storage.acquire_stream_ids("tb", lid, other_keys)
    assert len(got) == len(other_keys)
    # A MIXED dispatch touching the fenced shard is refused whole.
    with pytest.raises(FencedError):
        storage.acquire_stream_ids("tb", lid, keys)
    storage.lift_fence(1, shards=(victim,))
    got = storage.acquire_stream_ids("tb", lid, victim_keys)
    assert len(got) == len(victim_keys)
    storage.close()


# ---------------------------------------------------------------------------
# State machine (tick-driven, simulated clock)
# ---------------------------------------------------------------------------

def make_topology(n_shards=2, slots_per_shard=128, probe=None, spares=None,
                  registry=None, reseed=True, **cfg_kw):
    clock = {"t": T0}
    engine = ShardedDeviceEngine(
        slots_per_shard=slots_per_shard, table=LimiterTable(),
        mesh=make_mesh(n_devices=n_shards))
    primary = TpuBatchedStorage(engine=engine, clock_ms=lambda: clock["t"])
    router = ShardFailoverRouter(primary)

    def factory():
        return TpuBatchedStorage(num_slots=slots_per_shard,
                                 clock_ms=lambda: clock["t"])

    mesh_set = ShardStandbySet(n_shards, factory, registry=registry)
    repl = ShardedReplicator(ShardedReplicationLog(primary),
                             mesh_set.in_process_sinks())
    sim = {"s": 0.0}
    cfg = OrchestratorConfig(probe_interval_ms=50.0, suspect_threshold=2,
                             hysteresis_ms=150.0, promote_backoff_ms=1.0,
                             reseed=reseed, **cfg_kw)
    orch = FailoverOrchestrator(
        router, mesh_set, repl, standby_factory=factory, config=cfg,
        probe=probe, spares=spares, registry=registry,
        clock=lambda: sim["s"], sleep=lambda s: None)

    def tick(n=1):
        for _ in range(n):
            sim["s"] += cfg.probe_interval_ms / 1000.0
            orch.tick()

    return clock, primary, router, mesh_set, repl, orch, tick


def test_transient_fault_is_flap_damped():
    """Fail for exactly the suspect threshold, heal inside the
    hysteresis window: one false alarm, no fence, no promotion."""
    bad = {"on": False}
    clock, primary, router, mesh_set, repl, orch, tick = make_topology(
        probe=lambda q: not (bad["on"] and q == 0))
    try:
        tick(3)
        assert orch.status()["shards"][0]["state"] == "MONITORING"
        bad["on"] = True
        tick(2)  # consecutive threshold reached
        assert orch.status()["shards"][0]["state"] == "SUSPECT"
        bad["on"] = False
        tick()
        st = orch.status()
        assert st["shards"][0]["state"] == "MONITORING"
        assert st["false_alarms"] == 1
        assert st["promotions"] == 0
        assert orch.fence_epoch == 0
        assert primary.fence_info()["epoch"] == 0
    finally:
        orch.close()
        router.close()
        mesh_set.close()


def test_single_blip_never_reaches_suspect():
    """One failed probe (below the consecutive threshold) is absorbed in
    MONITORING — not even a SUSPECT transition, no false alarm."""
    bad = {"on": False}
    clock, primary, router, mesh_set, repl, orch, tick = make_topology(
        probe=lambda q: not (bad["on"] and q == 0))
    try:
        bad["on"] = True
        tick()          # one failure: threshold is 2
        bad["on"] = False
        tick(3)
        st = orch.status()
        assert st["shards"][0]["state"] == "MONITORING"
        assert st["false_alarms"] == 0
    finally:
        orch.close()
        router.close()
        mesh_set.close()


def test_promotion_falls_back_to_spare_standby():
    """The primary standby's promote fails (stale stream) — the spare
    candidate wins instead of the shard failing closed."""
    from ratelimiter_tpu.replication import InProcessSink, StandbyReceiver
    from ratelimiter_tpu.replication.log import ReplicationLog

    bad = {"on": False}
    registry = MeterRegistry()
    clock, primary, router, mesh_set, repl, orch, tick = make_topology(
        probe=lambda q: not (bad["on"] and q == victim
                             and orch.promotions == 0),
        registry=registry)
    lid = primary.register_limiter("tb", RateLimitConfig(
        max_permits=10, window_ms=1000, refill_rate=5.0))
    try:
        from ratelimiter_tpu.parallel.sharded import shard_of_int_keys

        keys = np.arange(32, dtype=np.int64)
        shard = shard_of_int_keys(keys, 2)
        victim = int(np.bincount(shard, minlength=2).argmax())
        clock["t"] += 5
        primary.acquire_stream_ids("tb", lid, keys)
        repl.ship_now()
        # A consistent SPARE standby fed by its own full stream.
        spare_storage = TpuBatchedStorage(num_slots=128,
                                          clock_ms=lambda: clock["t"])
        spare_rx = StandbyReceiver(spare_storage)
        # The spare receives the victim shard's stream (an ordinary flat
        # stream) via a second sink teed for this test.
        frames = repl.log.cut_shard(victim)
        from ratelimiter_tpu.replication.wire import encode_frame

        for f in frames:
            spare_rx.apply_bytes(encode_frame(f))
        if not spare_rx.consistent:
            repl.log.request_full(victim)
            for f in repl.log.cut_shard(victim):
                spare_rx.apply_bytes(encode_frame(f))
        assert spare_rx.consistent
        orch._spares = {victim: [spare_rx]}
        # Poison the primary standby: mark its stream inconsistent so
        # standby_ok refuses it (stale replica must not be promoted).
        mesh_set.receivers[victim].consistent = False
        bad["on"] = True
        tick(8)
        st = orch.status()["shards"][victim]
        assert st["state"] in ("RESTORED", "MONITORING"), st
        assert router.shard_health()[victim] == "promoted"
        assert router.replacements[victim] is spare_storage
        assert orch.promotions == 1
        spare_storage.flush()
    finally:
        orch.close()
        router.close()
        mesh_set.close()


def test_exhausted_candidates_fail_the_shard_closed():
    bad = {"on": False}
    registry = MeterRegistry()
    clock, primary, router, mesh_set, repl, orch, tick = make_topology(
        probe=lambda q: not (bad["on"] and q == 0), registry=registry)
    try:
        # No traffic ever replicated: the standby is unbootstrapped, so
        # standby_ok refuses it and there are no spares.
        bad["on"] = True
        tick(12)
        st = orch.status()
        assert st["shards"][0]["state"] == "FAILED"
        assert st["promotions"] == 0
        assert router.shard_health()[0] == "failed"
        # Fail-closed: the router denies the dead shard's keys.
        assert registry.scrape()[
            "ratelimiter.orchestrator.state"] == 5.0
        # The terminal state sticks (no auto-unfence flapping).
        tick(3)
        assert orch.status()["shards"][0]["state"] == "FAILED"
    finally:
        orch.close()
        router.close()
        mesh_set.close()


def test_unfence_recovers_a_terminal_failed_shard():
    """Operator exit from terminal FAILED: fence lifted, router repaired
    back to the primary, fresh standby re-seeded — shard serves again."""
    bad = {"on": False}
    registry = MeterRegistry()
    clock, primary, router, mesh_set, repl, orch, tick = make_topology(
        probe=lambda q: not (bad["on"] and q == 0), registry=registry)
    lid = primary.register_limiter("tb", RateLimitConfig(
        max_permits=10, window_ms=1000, refill_rate=5.0))
    try:
        bad["on"] = True
        tick(12)
        assert orch.status()["shards"][0]["state"] == "FAILED"
        assert primary.fence_info()["shards"] == [0]
        # unfence is the FAILED-only exit: live shards are refused.
        with pytest.raises(ValueError, match="not FAILED"):
            orch.unfence(1)
        bad["on"] = False  # the operator repaired/verified the shard
        out = orch.unfence(0)
        assert out["state"] == "MONITORING"
        assert orch.status()["shards"][0]["state"] == "MONITORING"
        assert primary.fence_info()["shards"] == []
        assert router.shard_health()[0] == "active"
        # Shard-0 keys serve through the router again (fence lifted,
        # routing back on the primary).
        clock["t"] += 5
        got = router.acquire_stream_ids(
            "tb", lid, np.arange(64, dtype=np.int64))
        assert len(got) == 64
        # Standby coverage resumed: the replaced standby re-baselines
        # from a FULL frame on the next cut.
        repl.ship_now()
        assert mesh_set.receivers[0].consistent
        assert not mesh_set.receivers[0].promoted
        tick(3)
        assert orch.status()["shards"][0]["state"] == "MONITORING"
    finally:
        orch.close()
        router.close()
        mesh_set.close()


def test_unfence_actuator_endpoint():
    """POST /actuator/orchestrator/unfence: plumbing + typed refusals
    (the full unfence path is covered by the direct test above)."""
    import http.client
    import json
    import threading

    from ratelimiter_tpu.service.app import make_server
    from ratelimiter_tpu.service.props import AppProperties
    from ratelimiter_tpu.service.wiring import build_app

    props = AppProperties({
        "storage.backend": "tpu",
        "storage.num_slots": "4096",
        "parallel.shard": "auto",
        "warmup.enabled": "false",
        "ratelimiter.orchestrator.enabled": "true",
        "ratelimiter.orchestrator.probe_interval_ms": "60000",
        "replication.interval_ms": "60000",
    })
    ctx = build_app(props)
    if ctx.orchestrator is None:
        ctx.close()
        pytest.skip("container exposes a single device; no shards")
    srv = make_server(ctx, port=0)
    thread = threading.Thread(target=srv.serve_forever, daemon=True)
    thread.start()
    try:
        conn = http.client.HTTPConnection(
            "127.0.0.1", srv.server_address[1], timeout=10)

        def post(body):
            conn.request("POST", "/actuator/orchestrator/unfence",
                         body=json.dumps(body),
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            return resp.status, json.loads(resp.read() or b"{}")

        status, payload = post({})
        assert status == 400 and "shard" in payload["error"]
        status, payload = post({"shard": 0})  # MONITORING, not FAILED
        assert status == 409 and "not FAILED" in payload["error"]
        conn.close()
    finally:
        srv.shutdown()
        ctx.close()


def test_router_shard_status_reports_time_in_state():
    clock, primary, router, mesh_set, repl, orch, tick = make_topology()
    try:
        st = router.shard_status()
        assert st[0]["state"] == "active"
        assert st[0]["in_state_ms"] >= 0
        router.fail_shard(1)
        st = router.shard_status()
        assert st[1]["state"] == "failed"
        assert st[1]["since_ms"] >= T0 // 2  # a real wall timestamp
        import time as time_mod

        time_mod.sleep(0.02)
        assert router.shard_status()[1]["in_state_ms"] >= 15
    finally:
        orch.close()
        router.close()
        mesh_set.close()


# ---------------------------------------------------------------------------
# The drills (fast variants; verify.sh runs these)
# ---------------------------------------------------------------------------

def test_orchestrated_failover_drill_fast():
    from ratelimiter_tpu.storage.chaos import orchestrated_failover_drill

    registry = MeterRegistry()
    report = orchestrated_failover_drill(
        n_shards=4, slots_per_shard=256, n_keys=64, waves=2,
        stream_n=512, batch=16, registry=registry)
    assert report["mismatches"] == 0
    assert report["decisions"] > 1000
    assert report["promotions"] == 1
    assert report["reseeds"] == 1           # back to N+1
    assert report["false_alarms"] == 0
    assert report["fence_rejected"] >= 1    # the zombie was refused
    assert report["cycles"][0]["detection_ms"] <= 450.0
    meters = registry.scrape()
    assert meters["ratelimiter.orchestrator.promotions"] == 1.0
    assert meters["ratelimiter.orchestrator.false_alarms"] == 0.0
    assert meters["ratelimiter.orchestrator.state"] == 0.0  # settled
    assert meters["ratelimiter.replication.failovers"] == 1.0


def test_orchestrator_flap_drill_fast():
    from ratelimiter_tpu.storage.chaos import orchestrator_flap_drill

    registry = MeterRegistry()
    report = orchestrator_flap_drill(registry=registry)
    assert report["mismatches"] == 0
    assert report["false_alarms"] == 3
    assert report["fence_rejected"] >= 1
    meters = registry.scrape()
    assert meters["ratelimiter.orchestrator.promotions"] == 0.0
    assert meters["ratelimiter.orchestrator.false_alarms"] == 3.0


@pytest.mark.slow
def test_orchestrator_soak_slow():
    """Multi-cycle kill -> promote -> re-seed -> kill-again: the
    re-seeded standby must carry the SECOND failover."""
    from ratelimiter_tpu.storage.chaos import orchestrated_failover_drill

    registry = MeterRegistry()
    report = orchestrated_failover_drill(
        n_shards=4, slots_per_shard=512, n_keys=96, waves=3,
        stream_n=1536, batch=32, cycles=3, registry=registry)
    assert report["mismatches"] == 0
    assert report["promotions"] == 3
    assert report["reseeds"] == 3
    assert len({c["fence_epoch"] for c in report["cycles"]}) == 3


# ---------------------------------------------------------------------------
# Wiring + actuator surface
# ---------------------------------------------------------------------------

def test_wiring_orchestrator_disabled_by_default():
    from ratelimiter_tpu.service.props import AppProperties
    from ratelimiter_tpu.service.wiring import _maybe_orchestrator

    clock = {"t": T0}
    storage = TpuBatchedStorage(num_slots=256, clock_ms=lambda: clock["t"])
    handle, serving = _maybe_orchestrator(storage, AppProperties({}),
                                          MeterRegistry())
    assert handle is None and serving is storage
    storage.close()


def test_wiring_orchestrator_requires_sharded_engine():
    from ratelimiter_tpu.service.props import AppProperties
    from ratelimiter_tpu.service.wiring import _maybe_orchestrator

    clock = {"t": T0}
    storage = TpuBatchedStorage(num_slots=256, clock_ms=lambda: clock["t"])
    handle, serving = _maybe_orchestrator(
        storage, AppProperties({"ratelimiter.orchestrator.enabled": "true"}),
        MeterRegistry())
    assert handle is None and serving is storage  # warned, disabled
    storage.close()


def test_wiring_orchestrator_builds_n_plus_one_topology():
    from ratelimiter_tpu.service.app import health_payload
    from ratelimiter_tpu.service.props import AppProperties
    from ratelimiter_tpu.service.wiring import AppContext, _maybe_orchestrator

    engine = ShardedDeviceEngine(
        slots_per_shard=128, table=LimiterTable(),
        mesh=make_mesh(n_devices=2))
    clock = {"t": T0}
    storage = TpuBatchedStorage(engine=engine, clock_ms=lambda: clock["t"])
    registry = MeterRegistry()
    props = AppProperties({
        "ratelimiter.orchestrator.enabled": "true",
        "ratelimiter.orchestrator.probe_interval_ms": "60000",
        "replication.interval_ms": "60000",
    })
    handle, serving = _maybe_orchestrator(storage, props, registry)
    assert handle is not None
    try:
        assert serving is handle.router
        assert handle.standby_set.n_shards == 2
        status = handle.status()
        assert status["enabled"] is True
        assert status["shards"][0]["state"] == "MONITORING"
        assert status["config"]["suspect_threshold"] == 3
        # Health payload folds the orchestrator + per-shard detail in.
        ctx = AppContext(props=props, storage=serving, registry=registry,
                         limiters={}, fail_open=True, orchestrator=handle)
        payload = health_payload(ctx)
        assert payload["status"] == "UP"
        assert payload["orchestrator"]["promotions"] == 0
        assert payload["shards_detail"]["0"]["state"] == "active"
        assert "in_state_ms" in payload["shards_detail"]["0"]
    finally:
        handle.close()
        serving.close()


def test_build_app_serves_through_router(monkeypatch):
    """Full wiring with the orchestrator on: the limiter trio serves
    through retry(breaker(router)) and the actuator surface answers."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    from ratelimiter_tpu.service.app import health_payload
    from ratelimiter_tpu.service.props import AppProperties
    from ratelimiter_tpu.service.wiring import build_app

    props = AppProperties({
        "storage.backend": "tpu",
        "storage.num_slots": "4096",
        "parallel.shard": "auto",
        "warmup.enabled": "false",
        "ratelimiter.orchestrator.enabled": "true",
        # Park the cadences: this test drives nothing periodic.
        "ratelimiter.orchestrator.probe_interval_ms": "60000",
        "replication.interval_ms": "60000",
    })
    ctx = build_app(props)
    try:
        if ctx.orchestrator is None:
            pytest.skip("container exposes a single device; no shards")
        assert ctx.limiters["api"].try_acquire("user-1") is True
        assert ctx.limiters["burst"].try_acquire("user-1", 2) is True
        payload = health_payload(ctx)
        assert payload["status"] == "UP"
        assert payload["orchestrator"]["promotions"] == 0
        assert all(v == "active" for v in payload["shards"].values())
        status = ctx.orchestrator.status()
        assert status["enabled"] is True
        assert all(s["state"] == "MONITORING"
                   for s in status["shards"].values())
    finally:
        ctx.close()
