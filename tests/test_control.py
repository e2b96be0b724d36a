"""Adaptive policy control plane (control/, ARCHITECTURE §15).

- Live set_policy actuation: generation metadata, bit-identity across
  an update boundary on the micro / stream / lease paths vs an oracle
  fed the same generation schedule, hybrid-tier invalidation.
- AIMD convergence on a simulated clock: storm -> multiplicative cut ->
  additive recovery; pinned-lid immunity; hierarchical global cap.
- Concurrency slots: lease budgets bounded by max_concurrent.
- The LimiterTable._grow hazard regression: a capacity grow under
  concurrent dispatch stays decision-safe (and warns).
- Policy replication: a mid-stream update crosses a PR 9 failover —
  the promoted standby serves the post-update generation.
"""

import threading

import numpy as np
import pytest

from ratelimiter_tpu.control import AdaptivePolicyController, ControlConfig
from ratelimiter_tpu.core.config import RateLimitConfig
from ratelimiter_tpu.engine.state import LimiterTable
from ratelimiter_tpu.metrics import MeterRegistry
from ratelimiter_tpu.observability.flightrecorder import FlightRecorder
from ratelimiter_tpu.semantics.oracle import (
    SlidingWindowOracle,
    TokenBucketOracle,
)
from ratelimiter_tpu.storage.tpu import TpuBatchedStorage

T0 = 1_700_000_000_000


def make_storage(clock, **kw):
    kw.setdefault("num_slots", 512)
    kw.setdefault("max_delay_ms", 0.2)
    return TpuBatchedStorage(clock_ms=lambda: clock["t"], **kw)


# ---------------------------------------------------------------------------
# Actuation path: set_policy + generations
# ---------------------------------------------------------------------------

def test_set_policy_generation_metadata():
    clock = {"t": T0}
    st = make_storage(clock)
    lid = st.register_limiter("sw", RateLimitConfig(max_permits=10,
                                                    window_ms=1000))
    assert st.policy_info()["generation"] == 0
    assert st.policy_info()["lids"][lid]["generation"] == 0
    gen = st.set_policy(lid, RateLimitConfig(max_permits=5,
                                             window_ms=1000))
    info = st.policy_info()
    assert gen == 1 and info["generation"] == 1
    assert info["lids"][lid] == {
        "algo": "sw", "generation": 1, "max_permits": 5,
        "window_ms": 1000, "refill_rate": 0.0}
    # Window is shape: immutable.
    with pytest.raises(ValueError):
        st.set_policy(lid, RateLimitConfig(max_permits=5, window_ms=2000))
    with pytest.raises(KeyError):
        st.set_policy(99, RateLimitConfig(max_permits=5, window_ms=1000))
    st.close()


def test_bit_identity_across_policy_boundary_micro_and_stream():
    """Micro batches and the string-stream path must stay bit-identical
    to an oracle fed the SAME generation schedule (raise AND cut, both
    algos), with per-key state carried across the boundary."""
    clock = {"t": T0}
    st = make_storage(clock)
    sw0 = RateLimitConfig(max_permits=8, window_ms=1000)
    tb0 = RateLimitConfig(max_permits=20, window_ms=1000, refill_rate=10.0)
    lid_sw = st.register_limiter("sw", sw0)
    lid_tb = st.register_limiter("tb", tb0)
    osw, otb = SlidingWindowOracle(sw0), TokenBucketOracle(tb0)
    st.add_policy_listener(
        lambda lid, algo, cfg, gen:
            (osw if lid == lid_sw else otb).reconfigure(cfg))

    rng = np.random.default_rng(42)
    schedule = [None, (3, 5.0), None, (30, 2.0), (8, 10.0), None]
    keys = [f"u{i}" for i in range(6)]
    for step, update in enumerate(schedule):
        if update is not None:
            mp, rate = update
            st.set_policy(lid_sw, RateLimitConfig(max_permits=mp,
                                                  window_ms=1000))
            st.set_policy(lid_tb, RateLimitConfig(
                max_permits=mp, window_ms=1000, refill_rate=rate))
        clock["t"] += int(rng.choice([1, 250, 400, 999, 1500]))
        now = clock["t"]
        ks = [keys[i] for i in rng.integers(0, len(keys), 24)]
        out = st.acquire_many("sw", [lid_sw] * 24, ks, [1] * 24)
        expect = [osw.try_acquire(k, 1, now) for k in ks]
        assert out["allowed"].tolist() == [d.allowed for d in expect], step
        assert out["observed"].tolist() == [d.observed for d in expect]
        out = st.acquire_many("tb", [lid_tb] * 24, ks, [1] * 24)
        expect = [otb.try_acquire(k, 1, now) for k in ks]
        assert out["allowed"].tolist() == [d.allowed for d in expect], step
        # String-stream path (relay/digest machinery) across the same
        # generation schedule.
        sk = [keys[i] for i in rng.integers(0, len(keys), 64)]
        allowed = st.acquire_stream_strs("sw", lid_sw, sk)
        expect = [osw.try_acquire(k, 1, now).allowed for k in sk]
        assert np.asarray(allowed).tolist() == expect, step
    st.close()


def test_bit_identity_across_policy_boundary_lease_path():
    """lease_reserve / lease_credit against the oracle reserve/credit
    spec across a rate cut: a renewal at an older generation
    re-reserves under the NEW rate."""
    clock = {"t": T0 + 100}
    st = make_storage(clock)
    cfg0 = RateLimitConfig(max_permits=20, window_ms=1000)
    lid = st.register_limiter("sw", cfg0)
    oracle = SlidingWindowOracle(cfg0)
    st.add_policy_listener(
        lambda l, algo, cfg, gen: oracle.reconfigure(cfg))

    out = st.lease_reserve("sw", lid, "k", 16)
    got, ws = oracle.reserve("k", 16, clock["t"])
    assert (out["granted"], out["ws"]) == (got, ws) == (16, out["ws"])

    st.set_policy(lid, RateLimitConfig(max_permits=6, window_ms=1000))
    # Credit back 10 unused, re-reserve: the new rate clamps the grant.
    cr = st.lease_credit("sw", lid, "k", 10, out["ws"])
    assert cr["credited"] == oracle.credit("k", 10, ws, clock["t"])
    out2 = st.lease_reserve("sw", lid, "k", 16)
    got2, _ = oracle.reserve("k", 16, clock["t"])
    assert out2["granted"] == got2
    assert out2["granted"] == 0  # 6 charged > new max 6: nothing left
    st.close()


def test_lease_manager_rebases_budget_after_policy_cut():
    from ratelimiter_tpu.leases import LeaseManager

    clock = {"t": T0}
    st = make_storage(clock)
    lid = st.register_limiter("sw", RateLimitConfig(max_permits=100,
                                                    window_ms=1000))
    mgr = LeaseManager(st, default_budget=64, ttl_ms=10_000.0)
    g = mgr.grant(lid, "k")
    assert g.granted == 64
    st.set_policy(lid, RateLimitConfig(max_permits=10, window_ms=1000))
    # Renewal at the older generation: unused budget credited, fresh
    # budget clamped by the NEW rate.
    g2 = mgr.renew(lid, "k", used=4)
    assert g2 is not None and 0 < g2.granted <= 10
    assert mgr.policy_rebased_total == 1
    st.close()


def test_set_policy_invalidates_hybrid_serving_entries():
    clock = {"t": T0}
    st = make_storage(clock, serving_cache=True,
                      serving_cache_ttl_ms=10_000.0)
    lid = st.register_limiter("tb", RateLimitConfig(
        max_permits=10, window_ms=60_000, refill_rate=5.0))
    # Adopt: an allowed decision from a full bucket.
    st.acquire("tb", lid, "h", 1)
    st.flush()
    assert len(st._serving) == 1
    st.set_policy(lid, RateLimitConfig(max_permits=4, window_ms=60_000,
                                       refill_rate=5.0))
    assert len(st._serving) == 0  # entry dropped with the old policy
    # Decisions after the update still match the oracle under the new
    # config with the pre-update consumption intact.
    oracle = TokenBucketOracle(RateLimitConfig(
        max_permits=10, window_ms=60_000, refill_rate=5.0))
    oracle.try_acquire("h", 1, T0)
    oracle.reconfigure(RateLimitConfig(max_permits=4, window_ms=60_000,
                                       refill_rate=5.0))
    clock["t"] += 10
    out = st.acquire("tb", lid, "h", 1)
    d = oracle.try_acquire("h", 1, clock["t"])
    assert bool(out["allowed"]) == d.allowed
    assert int(out["observed"]) == d.observed
    st.close()


# ---------------------------------------------------------------------------
# AIMD controller
# ---------------------------------------------------------------------------

def _drive(st, lid, key, demand, now):
    out = st.acquire_many("sw", [lid] * demand, [key] * demand,
                          [1] * demand)
    return int(out["allowed"].sum())


def make_controller(st, clock, registry=None, recorder=None, **cfg):
    cfg.setdefault("interval_ms", 1000.0)
    cfg.setdefault("window_ms", 2000)
    cfg.setdefault("min_load_per_s", 1.0)
    return AdaptivePolicyController(
        st, ControlConfig(**cfg), registry=registry, recorder=recorder,
        clock_ms=lambda: clock["t"])


def test_aimd_storm_cut_and_recovery_simulated_clock():
    """Storm -> multiplicative cut toward the floor -> post-storm
    additive recovery back to the ceiling, all on a simulated clock."""
    clock = {"t": T0}
    st = make_storage(clock)
    registry = MeterRegistry()
    recorder = FlightRecorder(256)
    lid = st.register_limiter("sw", RateLimitConfig(max_permits=100,
                                                    window_ms=1000))
    ctl = AdaptivePolicyController(
        st, ControlConfig(interval_ms=1000.0, window_ms=2000,
                          floor_fraction=0.1, decrease_factor=0.5,
                          increase_fraction=0.1, min_load_per_s=1.0),
        registry=registry, recorder=recorder,
        clock_ms=lambda: clock["t"])

    fractions = []
    for sec in range(24):
        clock["t"] += 1000
        demand = 1000 if sec < 8 else 20   # storm, then normal load
        _drive(st, lid, "t", demand, clock["t"])
        ctl.tick()
        fractions.append(ctl.status()["lids"][str(lid)]["fraction"])
    # Cut phase: reaches the floor within a few ticks.
    assert min(fractions[:8]) == pytest.approx(0.1)
    # Recovery: additive raise back to the ceiling.
    assert fractions[-1] == pytest.approx(1.0)
    assert fractions[10] < fractions[14] < fractions[-1]
    status = ctl.status()
    assert status["adjustments"] > 0
    assert status["generation"] == st.policy_info()["generation"] > 0
    # Effective policy is back at the registered ceiling.
    assert status["lids"][str(lid)]["effective_max_permits"] == 100
    # Coalesced flight events: the whole convergence is a handful of
    # tallied policy.adjusted entries, not one per tick.
    kinds = [e["kind"] for e in recorder.snapshot(last=256)["events"]]
    n_adjust_events = kinds.count("policy.adjusted")
    assert 0 < n_adjust_events < status["adjustments"]
    meters = registry.scrape()
    assert meters["ratelimiter.control.adjustments"] == \
        status["adjustments"]
    assert meters["ratelimiter.control.generation"] == \
        status["generation"]
    ctl.close()
    st.close()


def test_pinned_lid_is_immune_to_the_loop():
    clock = {"t": T0}
    st = make_storage(clock)
    lid_a = st.register_limiter("sw", RateLimitConfig(max_permits=50,
                                                      window_ms=1000))
    lid_b = st.register_limiter("sw", RateLimitConfig(max_permits=50,
                                                      window_ms=1000))
    ctl = make_controller(st, clock)
    ctl.pin(lid_b)
    for _ in range(4):
        clock["t"] += 1000
        _drive(st, lid_a, "a", 500, clock["t"])   # both storm equally
        _drive(st, lid_b, "b", 500, clock["t"])
        ctl.tick()
    s = ctl.status()
    assert s["lids"][str(lid_a)]["fraction"] < 1.0
    assert s["lids"][str(lid_b)]["fraction"] == 1.0
    assert s["lids"][str(lid_b)]["state"] == "PINNED"
    assert s["pinned"] == [lid_b]
    assert st.policy_info()["lids"][lid_b]["generation"] == 0
    assert st.policy_info()["lids"][lid_b]["max_permits"] == 50
    # Unpin: the lid rejoins the loop and gets cut like its peer.
    ctl.pin(lid_b, pinned=False)
    clock["t"] += 1000
    _drive(st, lid_b, "b", 500, clock["t"])
    ctl.tick()
    assert ctl.status()["lids"][str(lid_b)]["fraction"] < 1.0
    ctl.close()
    st.close()


def test_global_cap_scales_every_tenant():
    """Fleet admitted over the hierarchical cap: every unpinned
    tenant's effective rate scales by cap/admitted (floor-protected),
    and the engagement is a flight event + gauge."""
    clock = {"t": T0}
    st = make_storage(clock)
    registry = MeterRegistry()
    recorder = FlightRecorder(64)
    lids = [st.register_limiter("sw", RateLimitConfig(
        max_permits=100, window_ms=1000)) for _ in range(3)]
    ctl = make_controller(st, clock, registry=registry,
                          recorder=recorder, global_cap_per_s=120.0,
                          target_excess=0.99)
    for _ in range(3):
        clock["t"] += 1000
        for i, lid in enumerate(lids):
            _drive(st, lid, f"k{i}", 80, clock["t"])  # 240/s aggregate
        ctl.tick()
    s = ctl.status()
    assert s["global_scale"] < 1.0
    assert s["global_cap_engagements"] > 0
    for lid in lids:
        eff = s["lids"][str(lid)]["effective_max_permits"]
        assert eff < 100
    assert registry.scrape()["ratelimiter.control.global_scale"] < 1.0
    kinds = [e["kind"] for e in recorder.snapshot(last=64)["events"]]
    assert "control.global_cap_engaged" in kinds
    # Load back under the cap: the scale releases to 1.0.
    for _ in range(6):
        clock["t"] += 1000
        _drive(st, lids[0], "k0", 30, clock["t"])
        ctl.tick()
    assert ctl.status()["global_scale"] == 1.0
    ctl.close()
    st.close()


def test_global_cap_engages_on_raw_observed_load_not_admitted():
    """The shed-heavy storm regression: per-tenant limits deny most of
    the storm, so the ADMITTED rate stays far under the cap while raw
    arrivals are far above it.  Admitted-rate scaling would never
    engage here; the cap must trigger and size on OBSERVED load."""
    clock = {"t": T0}
    st = make_storage(clock)
    recorder = FlightRecorder(64)
    lid = st.register_limiter("sw", RateLimitConfig(max_permits=30,
                                                    window_ms=1000))
    ctl = make_controller(st, clock, recorder=recorder,
                          global_cap_per_s=120.0, target_excess=0.99)
    admitted = 0
    for _ in range(3):
        clock["t"] += 1000
        admitted = _drive(st, lid, "hot", 200, clock["t"])  # 200/s raw
        ctl.tick()
    assert admitted <= 30  # the per-tenant limit sheds the storm...
    s = ctl.status()
    assert s["global_cap_engagements"] > 0
    assert s["global_scale"] == pytest.approx(120.0 / 200.0, rel=0.2)
    events = [e for e in recorder.snapshot(last=64)["events"]
              if e["kind"] == "control.global_cap_engaged"]
    assert events and events[-1]["observed_per_s"] > 120.0
    assert events[-1]["admitted_per_s"] < 120.0  # the old rule's blind spot
    ctl.close()
    st.close()


# ---------------------------------------------------------------------------
# Concurrency slots (leases as slots)
# ---------------------------------------------------------------------------

def test_concurrency_slots_bound_outstanding_lease_budget():
    from ratelimiter_tpu.leases import LeaseManager

    clock = {"t": T0}
    st = make_storage(clock)
    lid = st.register_limiter("tb", RateLimitConfig(
        max_permits=1000, window_ms=60_000, refill_rate=100.0))
    mgr = LeaseManager(st, default_budget=8, max_budget=64,
                       ttl_ms=60_000.0)
    mgr.set_concurrency_cap(lid, 16)
    g1 = mgr.grant(lid, "worker-a", requested=8)
    g2 = mgr.grant(lid, "worker-b", requested=8)
    assert g1.granted == 8 and g2.granted == 8
    # Slots exhausted: a third worker is refused (stays per-decision).
    g3 = mgr.grant(lid, "worker-c", requested=8)
    assert g3.granted == 0
    assert mgr.concurrency_refused_total == 1
    assert mgr.table.outstanding_budget_for("tb", lid) == 16
    # Release frees slots.
    mgr.release(lid, "worker-a", used=8)
    g4 = mgr.grant(lid, "worker-c", requested=8)
    assert g4.granted == 8
    # A renewal only competes with OTHER leases, not its own budget.
    g5 = mgr.renew(lid, "worker-b", used=8, requested=8)
    assert g5 is not None and g5.granted == 8
    # Cap cut below outstanding: the next renewal revokes to the
    # per-decision path (lazy convergence) and credits the remainder.
    mgr.set_concurrency_cap(lid, 8)
    g6 = mgr.renew(lid, "worker-c", used=0, requested=8)
    assert g6 is not None and g6.granted == 0
    assert mgr.table.get("tb", lid, "worker-c") is None
    assert mgr.status()["concurrency_caps"] == {lid: 8}
    st.close()


# ---------------------------------------------------------------------------
# LimiterTable._grow hazard regression
# ---------------------------------------------------------------------------

def test_grow_under_concurrent_dispatch_is_decision_safe():
    """Registering past the table capacity under live traffic must warn
    (the recompile stall is real) but never corrupt decisions."""
    import logging

    clock = {"t": T0}
    st = make_storage(clock, table_capacity=4)
    cfg = RateLimitConfig(max_permits=50, window_ms=1000)
    lid = st.register_limiter("sw", cfg)
    oracle = SlidingWindowOracle(cfg)

    stop = threading.Event()
    errors = []

    def traffic():
        i = 0
        while not stop.is_set():
            try:
                st.acquire_many("sw", [lid] * 8,
                                [f"g{i % 4}"] * 8, [1] * 8)
            except Exception as exc:  # noqa: BLE001
                errors.append(exc)
                return
            i += 1

    thread = threading.Thread(target=traffic)
    thread.start()
    grew = []
    # Capture the grow warning directly off the module logger (the
    # ratelimiter_tpu hierarchy does not propagate to root once
    # setup_logging has run in-session, so caplog would miss it).
    records = []
    handler = logging.Handler()
    handler.emit = records.append
    grow_log = logging.getLogger("ratelimiter_tpu.engine.state")
    grow_log.addHandler(handler)
    try:
        for _ in range(12):  # capacity 4 -> forces >= 2 implicit grows
            grew.append(st.register_limiter("sw", cfg))
    finally:
        grow_log.removeHandler(handler)
    stop.set()
    thread.join()
    assert not errors
    assert st.table.implicit_grows >= 1
    assert any("recompiles" in r.getMessage() for r in records)
    # Decisions on the ORIGINAL lid remained well-formed through the
    # grows; replay a deterministic wave now and require bit-identity.
    st.flush()
    clock["t"] += 5000   # fresh windows: oracle state re-synchronizes
    for lid_new in grew:
        out = st.acquire_many("sw", [lid_new] * 4, ["x"] * 4, [1] * 4)
        assert out["allowed"].tolist() == [True] * 4
    out = st.acquire_many("sw", [lid] * 60, ["fresh"] * 60, [1] * 60)
    expect = [oracle.try_acquire("fresh", 1, clock["t"]).allowed
              for _ in range(60)]
    assert out["allowed"].tolist() == expect
    # Pre-sizing avoids the hazard entirely.
    st2 = make_storage({"t": T0}, table_capacity=64)
    for _ in range(40):
        st2.register_limiter("sw", cfg)
    assert st2.table.implicit_grows == 0
    st2.close()
    st.close()


# ---------------------------------------------------------------------------
# Policy replication across failover (the chaos drill)
# ---------------------------------------------------------------------------

def test_policy_update_replicates_across_failover():
    """A mid-stream set_policy crosses the PR 9 replication stream: the
    promoted standby serves the POST-update generation, decisions
    bit-identical to the generation-aware oracle."""
    from ratelimiter_tpu.replication import (
        InProcessSink,
        ReplicationLog,
        Replicator,
        StandbyReceiver,
    )

    clock = {"t": T0}
    primary = make_storage(clock, num_slots=512)
    standby = make_storage(clock, num_slots=512)
    cfg0 = RateLimitConfig(max_permits=12, window_ms=1000)
    lid = primary.register_limiter("sw", cfg0)
    oracle = SlidingWindowOracle(cfg0)
    log = ReplicationLog(primary)
    receiver = StandbyReceiver(standby)
    repl = Replicator(log, InProcessSink(receiver))

    def wave(storage, n=24):
        keys = [f"w{i % 8}" for i in range(n)]
        out = storage.acquire_many("sw", [lid] * n, keys, [1] * n)
        expect = [oracle.try_acquire(k, 1, clock["t"]).allowed
                  for k in keys]
        assert out["allowed"].tolist() == expect

    wave(primary)
    repl.ship_now()
    # Mid-stream policy update, then more traffic under the new rate.
    new_cfg = RateLimitConfig(max_permits=4, window_ms=1000)
    gen = primary.set_policy(lid, new_cfg)
    oracle.reconfigure(new_cfg)
    clock["t"] += 400
    wave(primary)
    repl.ship_now()

    # Failover: the promoted standby must carry the post-update
    # generation and decide under the NEW policy.
    promoted = receiver.promote()
    assert promoted.policy_info()["generation"] == gen == 1
    assert promoted.policy_info()["lids"][lid]["max_permits"] == 4
    clock["t"] += 2000   # fresh window: continuation is exact
    wave(promoted)
    repl.close()
    primary.close()
    standby.close()


def test_policy_update_after_bootstrap_frame_applies_on_standby():
    """A standby that registered the ORIGINAL config from an early
    frame must apply a later frame's rate change (newer generation)
    instead of refusing it as drift — while true drift still raises."""
    from ratelimiter_tpu.engine.checkpoint import apply_limiter_policies

    clock = {"t": T0}
    st = make_storage(clock)
    lid = st.register_limiter("sw", RateLimitConfig(max_permits=12,
                                                    window_ms=1000))
    # Newer generation: applied.
    apply_limiter_policies(st, {str(lid): {
        "algo": "sw", "max_permits": 5, "window_ms": 1000,
        "refill_rate": 0.0, "gen": 3}})
    assert st.policy_info()["lids"][lid]["max_permits"] == 5
    assert st.policy_info()["lids"][lid]["generation"] == 3
    # Same values, same gen: idempotent no-op.
    apply_limiter_policies(st, {str(lid): {
        "algo": "sw", "max_permits": 5, "window_ms": 1000,
        "refill_rate": 0.0, "gen": 3}})
    # Rate drift with NO newer generation: refused.
    with pytest.raises(ValueError, match="no newer policy generation"):
        apply_limiter_policies(st, {str(lid): {
            "algo": "sw", "max_permits": 7, "window_ms": 1000,
            "refill_rate": 0.0, "gen": 3}})
    # Window drift: always refused.
    with pytest.raises(ValueError, match="algo/window shape"):
        apply_limiter_policies(st, {str(lid): {
            "algo": "sw", "max_permits": 5, "window_ms": 2000,
            "refill_rate": 0.0, "gen": 9}})
    st.close()


# ---------------------------------------------------------------------------
# Operator surface: /actuator/policies + pin + health mirror
# ---------------------------------------------------------------------------

def test_actuator_policies_endpoint_and_pin():
    import http.client
    import json

    from ratelimiter_tpu.service.app import make_server
    from ratelimiter_tpu.service.props import AppProperties
    from ratelimiter_tpu.service.wiring import build_app

    props = AppProperties({
        "storage.backend": "tpu",
        "storage.num_slots": "4096",
        "parallel.shard": "off",
        "warmup.enabled": "false",
        "ratelimiter.control.enabled": "true",
        "ratelimiter.control.interval_ms": "60000",  # tick manually
    })
    ctx = build_app(props)
    srv = make_server(ctx, port=0)
    thread = threading.Thread(target=srv.serve_forever, daemon=True)
    thread.start()
    try:
        conn = http.client.HTTPConnection(
            "127.0.0.1", srv.server_address[1], timeout=10)

        def req(method, path, body=None):
            conn.request(method, path,
                         body=json.dumps(body) if body else None)
            resp = conn.getresponse()
            return resp.status, json.loads(resp.read())

        # Drive one request so lids exist + the controller adopts them.
        conn.request("GET", "/api/data", headers={"X-User-ID": "ctl"})
        conn.getresponse().read()
        ctx.controller.tick()

        status, payload = req("GET", "/actuator/policies")
        assert status == 200 and payload["enabled"]
        assert payload["generation"] == 0
        lid = next(iter(payload["controller"]["lids"]))
        row = payload["controller"]["lids"][lid]
        assert row["state"] in ("IDLE", "STEADY")
        assert not row["pinned"]

        status, out = req("POST", f"/actuator/policies/{lid}/pin")
        assert status == 200 and out["pinned"]
        status, payload = req("GET", "/actuator/policies")
        assert payload["controller"]["lids"][lid]["pinned"]
        assert int(lid) in payload["controller"]["pinned"]

        # Health payload mirrors the control plane.
        status, health = req("GET", "/actuator/health")
        assert health["control"]["pinned"] == [int(lid)]
        assert health["control"]["generation"] == 0

        status, out = req("POST", f"/actuator/policies/{lid}/pin",
                          {"pinned": False})
        assert status == 200 and not out["pinned"]
        status, _ = req("POST", "/actuator/policies/12345/pin")
        assert status == 404
        conn.close()
    finally:
        srv.shutdown()
        ctx.close()
