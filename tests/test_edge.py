"""Edge aggregator tier (edge/ + leases/sublease.py + protocol v6,
ARCHITECTURE §14b).

Layers under test, bottom-up:

- BulkPool sublease accounting: the conservation invariant
  ``remaining + sliced_out + used_pending == budget + deficit`` over
  randomized slice/burn/return/lost/renewal schedules, so the
  aggregator can never admit more than its bulk budgets between
  flushes;
- the nested over-admission bound: burns folded on revoked bulk
  leases reconcile EXACTLY between the aggregator's fold counter and
  the core's ``lease.over_admission``, and stay within the revoked
  bulk budgets;
- the v6 wire surface: bulk grants straddling the old u16 budget
  ceiling, the OP_BULK_RENEW epochs column, and stale lease-instance
  reports landing in over_admission instead of a successor's books;
- scoped fence epochs: ``lease_scope_epoch`` on the unsharded engine;
- the edgeproc standalone process: ready line, front-door serving,
  EOF shutdown;
- the chaos drill (the fast variant verify.sh runs).
"""

import json
import os
import random
import subprocess
import sys
import threading

import pytest

from ratelimiter_tpu import RateLimitConfig
from ratelimiter_tpu.edge import EdgeAggregator
from ratelimiter_tpu.leases import DirectTransport, LeaseClient, LeaseManager
from ratelimiter_tpu.leases.sublease import BulkPool
from ratelimiter_tpu.metrics import MeterRegistry
from ratelimiter_tpu.storage import TpuBatchedStorage

T0 = 1_753_000_000_000


def make_storage(clock, **kw):
    return TpuBatchedStorage(num_slots=256, clock_ms=lambda: clock["t"],
                             **kw)


def make_stack(clock, *, bulk_budget=96, slice_budget=12, flush_ms=50.0,
               max_permits=100_000, registry=None):
    """Storage + manager + one aggregator over a DirectTransport."""
    st = make_storage(clock)
    cfg = RateLimitConfig(max_permits=max_permits, window_ms=60_000,
                          refill_rate=float(max_permits) / 10.0)
    lid = st.register_limiter("tb", cfg)
    mgr = LeaseManager(st, default_budget=slice_budget,
                       max_budget=slice_budget,
                       max_bulk_budget=bulk_budget, ttl_ms=10_000.0,
                       clock_ms=lambda: clock["t"], registry=registry)
    agg = EdgeAggregator(DirectTransport(mgr), bulk_budget=bulk_budget,
                         slice_budget=slice_budget, flush_ms=flush_ms,
                         clock_ms=lambda: clock["t"], registry=registry)
    return st, cfg, lid, mgr, agg


# ---------------------------------------------------------------------------
# BulkPool conservation (the nesting invariant, property-tested)
# ---------------------------------------------------------------------------

def _fresh_pool(budget):
    return BulkPool(lid=1, key="k", budget=budget, remaining=budget,
                    epoch=0, deadline_ms=10_000, granted_total=budget)


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_bulk_pool_conservation_random_schedule(seed):
    """Any interleaving of slice / burn-report / return / lost-holder /
    over-report / renewal keeps every permit in exactly one bucket, and
    the pool's outstanding admission never exceeds budget + deficit."""
    rng = random.Random(seed)
    budget = 200
    pool = _fresh_pool(budget)
    sessions = list(range(6))
    for step in range(400):
        op = rng.choice(["slice", "burn", "ret", "lost", "over",
                         "renew", "topup"])
        sid = rng.choice(sessions)
        sub = pool.subs.get(sid)
        if op == "slice":
            pool.slice(sid, rng.randrange(1, 40))
        elif op == "burn" and sub is not None:
            # Occasionally over-report past the slice (a client whose
            # local count drifted): folds conservatively.
            pool.fold_used(sub, rng.randrange(0, sub.amount + 3))
        elif op == "ret" and sub is not None:
            pool.return_unused(sub)
        elif op == "lost" and sub is not None:
            pool.fold_lost(sub)
            pool.drop_sub(sid)
        elif op == "over":
            pool.fold_over_report(rng.randrange(0, 10))
        elif op == "topup" and sub is not None and sub.amount == 0:
            # top_up's contract: only a folded/emptied slice refills
            # (the renewal path always folds+returns first).
            pool.top_up(sub, rng.randrange(1, 40))
        elif op == "renew":
            # Renewals may shrink (the core re-granted less than what
            # is sliced out) — the gap becomes deficit, never free
            # permits.
            granted = rng.randrange(0, budget + 1)
            pool.apply_renewal(granted, 1000, pool.epoch,
                               rng.randrange(0, 5000), pool.used_pending)
        pool.check_conservation()
        assert pool.outstanding() <= pool.budget + pool.deficit
        assert pool.remaining >= 0 and pool.sliced_out >= 0
        assert pool.used_pending >= 0 and pool.deficit >= 0
    # Fold every straggler and drain: the pool must still conserve.
    for sid in list(pool.subs):
        pool.fold_lost(pool.subs[sid])
        pool.drop_sub(sid)
    pool.check_conservation()
    assert pool.sliced_out == 0


def test_bulk_pool_shrinking_renewal_builds_then_pays_deficit():
    pool = _fresh_pool(100)
    sub = pool.slice(1, 60)
    assert sub.amount == 60
    # The core re-grants only 20 while 60 are in the client's hands.
    pool.apply_renewal(20, 1000, 0, 0, 0)
    assert pool.deficit == 40 and pool.remaining == 0
    pool.check_conservation()
    # Returns pay the deficit down before anything re-enters remaining.
    pool.return_unused(sub)
    assert pool.deficit == 0 and pool.remaining == 20
    pool.check_conservation()


# ---------------------------------------------------------------------------
# Aggregator semantics over a live core (DirectTransport)
# ---------------------------------------------------------------------------

def test_aggregator_collapses_frames_and_reconciles():
    clock = {"t": T0}
    st, cfg, lid, mgr, agg = make_stack(clock)
    clients = [LeaseClient(agg.session(), lid, budget=12,
                           clock_ms=lambda: clock["t"],
                           direct_fallback=False, telemetry=False)
               for _ in range(4)]
    try:
        decisions = 0
        for i in range(600):
            clock["t"] += 1
            assert clients[i % 4].try_acquire(f"k{i % 3}")
            decisions += 1
        for lc in clients:
            lc.release_all()
        agg.release_all()
        st.flush()
        # Multiplicative collapse: 4 clients x 3 keys through one
        # aggregator spend <= decisions/5 upstream frames.
        assert agg.upstream_frames * 5 <= decisions
        # Everything settled: no outstanding lease, exact availability.
        assert mgr.table.outstanding() == 0
        avail = int(st.available_many("tb", lid, ["k0"])[0])
        assert 0 <= avail <= cfg.max_permits
    finally:
        st.close()


def test_aggregator_nested_over_admission_bound():
    """Randomized revocation schedule: fence-epoch advances revoke the
    bulk pools; every burn clients land on revoked slices must fold
    into over_admission at BOTH tiers, with the aggregator's fold delta
    equal to the core's, bounded by the revoked bulk budgets."""
    clock = {"t": T0}
    st, cfg, lid, mgr, agg = make_stack(clock, bulk_budget=48,
                                        slice_budget=8)
    rng = random.Random(7)
    keys = [f"k{i}" for i in range(4)]
    clients = [LeaseClient(agg.session(), lid, budget=8,
                           clock_ms=lambda: clock["t"],
                           direct_fallback=False, telemetry=False)
               for _ in range(3)]
    try:
        epoch = 0
        revoked_budget_sum = 0
        for _ in range(5):
            # Burn a while through the aggregator.
            for _ in range(150):
                clock["t"] += 1
                assert clients[rng.randrange(3)].try_acquire(
                    rng.choice(keys))
            # Settle the pending burn reports, then advance the fence
            # epoch: EVERY live bulk lease is now stale (unsharded
            # scope covers all keys).
            agg.flush()
            revoked_budget_sum += sum(p.budget
                                      for p in agg._pools.values())
            epoch += 1
            st.fence(epoch)
            st.lift_fence(epoch)
            over_core0 = mgr.over_admission_total
            over_agg0 = agg.over_admission_total
            revoked0 = agg.scoped_revocations_total
            # One flush tells the aggregator its pools were revoked
            # (settled above, so the revocation rows report zero burns
            # and the core folds nothing yet).
            agg.flush()
            assert mgr.over_admission_total == over_core0
            assert agg.scoped_revocations_total > revoked0
            # Clients drain their stranded slices (served locally —
            # this IS the bounded over-admission), then re-grant.
            burned = 0
            for lc in clients:
                for k in list(lc._leases):
                    lease = lc._leases[k]
                    while lease.remaining > 0:
                        clock["t"] += 1
                        assert lc.try_acquire(k)
                        burned += 1
                    clock["t"] += 1
                    assert lc.try_acquire(k)  # re-grant at new epoch
            agg.flush()
            assert agg.over_admission_total - over_agg0 >= burned
            assert mgr.over_admission_total - over_core0 \
                == agg.over_admission_total - over_agg0, (
                "core and aggregator over-admission folds diverged")
        assert mgr.over_admission_total <= revoked_budget_sum, (
            "fleet over-admission escaped the revoked bulk budgets")
        for lc in clients:
            lc.release_all()
        agg.release_all()
        assert mgr.table.outstanding() == 0
    finally:
        st.close()


def test_aggregator_session_isolation_one_slice_each():
    """Two sessions on the same key get independent slices from ONE
    pool; a session re-granting folds only its own slice."""
    clock = {"t": T0}
    st, cfg, lid, mgr, agg = make_stack(clock, bulk_budget=64,
                                        slice_budget=8)
    try:
        s1, s2 = agg.session(), agg.session()
        g1 = s1.grant(lid, "k", 8)
        g2 = s2.grant(lid, "k", 8)
        assert g1.granted == 8 and g2.granted == 8
        assert len(agg._pools) == 1
        pool = next(iter(agg._pools.values()))
        assert len(pool.subs) == 2 and pool.sliced_out == 16
        # The CORE sees one bulk lease, not two client leases.
        assert mgr.table.outstanding() == 1
        s1.release(lid, "k", used=3)
        assert len(pool.subs) == 1 and pool.used_pending == 3
        pool.check_conservation()
        agg.release_all()
        assert mgr.table.outstanding() == 0
    finally:
        st.close()


# ---------------------------------------------------------------------------
# v6 wire surface: wide budgets + the lease-instance epoch column
# ---------------------------------------------------------------------------

def test_v6_bulk_budget_straddles_u16():
    """Bulk budgets past the old u16 wire ceiling survive the LEASE /
    BULK_RENEW round trip full-width (the v6 granted64 trailer)."""
    from ratelimiter_tpu.service.sidecar import SidecarClient, SidecarServer

    clock = {"t": T0}
    st = TpuBatchedStorage(num_slots=1024, clock_ms=lambda: clock["t"])
    big = 200_000
    server = SidecarServer(st, host="127.0.0.1").start()
    try:
        lid = server.register("tb", RateLimitConfig(
            max_permits=1 << 20, window_ms=60_000, refill_rate=1e6))
        server.attach_leases(LeaseManager(
            st, default_budget=64, max_budget=64, max_bulk_budget=big,
            ttl_ms=60_000.0, clock_ms=lambda: clock["t"]))
        cli = SidecarClient("127.0.0.1", server.port)
        try:
            assert cli.server_version >= 6
            granted, ttl, epoch = cli.lease_grant(lid, "wide", big,
                                                  bulk=True)
            assert granted == big > 0xFFFF
            rows = cli.lease_bulk_renew(lid, ["wide"], [70_000], [big],
                                        epochs=[epoch])
            assert len(rows) == 1
            g2, _ttl2, _ep2, revoked = rows[0]
            assert not revoked and g2 == big > 0xFFFF
            cli.lease_release(lid, "wide", 0)
        finally:
            cli.close()
    finally:
        server.stop()
        st.close()


def test_bulk_renew_stale_epoch_row_folds_to_over_admission():
    """A dead bulk lease's burn report must land in over_admission even
    when a successor lease already lives on the same key — the epochs
    column names the lease INSTANCE, so the successor's books stay
    untouched."""
    clock = {"t": T0}
    st = make_storage(clock)
    cfg = RateLimitConfig(max_permits=100_000, window_ms=60_000,
                          refill_rate=10_000.0)
    lid = st.register_limiter("tb", cfg)
    mgr = LeaseManager(st, default_budget=16, max_budget=16,
                       max_bulk_budget=64, ttl_ms=10_000.0,
                       clock_ms=lambda: clock["t"])
    t = DirectTransport(mgr)
    try:
        g = t.lease_grant(lid, "k", 64, bulk=True)
        assert g.granted == 64
        dead_epoch = g.epoch
        # The fence advances (the holder's lease is now a dead
        # instance); a successor re-grants at the NEW epoch.
        st.fence(3)
        st.lift_fence(3)
        g2 = t.lease_grant(lid, "k", 64, bulk=True)
        assert g2.granted == 64 and g2.epoch != dead_epoch
        successor = mgr.table.get("tb", lid, "k")
        used0 = successor.used_total
        over0 = mgr.over_admission_total
        rev0 = mgr.revoked_total
        # The dead instance's burns arrive late, stamped with ITS
        # epoch: over_admission only — not a revocation event, and not
        # the successor's problem.
        rows = t.lease_bulk_renew(lid, ["k"], [40], [0],
                                  epochs=[dead_epoch])
        assert rows[0] == (0, 0, 0, True)
        assert mgr.over_admission_total - over0 == 40
        assert mgr.revoked_total == rev0
        assert successor.used_total == used0, (
            "stale-instance burns leaked into the successor's books")
        # The successor still renews normally with its own epoch.
        g3 = mgr.renew(lid, "k", used=5, requested=64,
                       epoch=successor.epoch)
        assert g3 is not None and g3.granted == 64
    finally:
        st.close()


def test_bulk_renew_wire_epoch_column_matches_direct():
    """The OP_BULK_RENEW epochs column decodes row-for-row: a stale
    epoch in one row folds that row to over_admission while its
    neighbors renew normally."""
    from ratelimiter_tpu.service.sidecar import SidecarClient, SidecarServer

    clock = {"t": T0}
    st = TpuBatchedStorage(num_slots=1024, clock_ms=lambda: clock["t"])
    server = SidecarServer(st, host="127.0.0.1").start()
    try:
        lid = server.register("tb", RateLimitConfig(
            max_permits=1 << 20, window_ms=60_000, refill_rate=1e6))
        mgr = LeaseManager(st, default_budget=64, max_budget=64,
                           max_bulk_budget=256, ttl_ms=60_000.0,
                           clock_ms=lambda: clock["t"])
        server.attach_leases(mgr)
        cli = SidecarClient("127.0.0.1", server.port)
        try:
            eps = {}
            for k in ("a", "b", "c"):
                granted, _ttl, epoch = cli.lease_grant(lid, k, 256,
                                                       bulk=True)
                assert granted == 256
                eps[k] = epoch
            over0 = mgr.over_admission_total
            rows = cli.lease_bulk_renew(
                lid, ["a", "b", "c"], [10, 20, 30], [256, 256, 256],
                epochs=[eps["a"], eps["b"] + 7, eps["c"]])
            # Row b was a stale instance: granted 0 is how the wire
            # spells "fold and go away"; its neighbors renew normally.
            assert rows[0][0] == 256 and rows[2][0] == 256
            assert rows[1][0] == 0
            assert mgr.over_admission_total - over0 == 20
            # a and c still live and renewable; b's lease untouched.
            assert mgr.table.get("tb", lid, "b").used_total == 0
            for k in ("a", "b", "c"):
                cli.lease_release(lid, k, 0)
        finally:
            cli.close()
    finally:
        server.stop()
        st.close()


# ---------------------------------------------------------------------------
# Scoped fence epochs (unsharded surface; the drill covers sharded)
# ---------------------------------------------------------------------------

def test_lease_scope_epoch_unsharded_tracks_full_fence():
    clock = {"t": T0}
    st = make_storage(clock)
    lid = st.register_limiter("tb", RateLimitConfig(
        max_permits=100, window_ms=60_000, refill_rate=50.0))
    try:
        e0 = st.lease_scope_epoch(lid, "k")
        st.fence(5)
        st.lift_fence(5)
        assert st.lease_scope_epoch(lid, "k") >= max(e0, 5)
        # Every key shares the scope on an unsharded engine.
        assert st.lease_scope_epoch(lid, "other") \
            == st.lease_scope_epoch(lid, "k")
    finally:
        st.close()


# ---------------------------------------------------------------------------
# edgeproc: the standalone aggregator process
# ---------------------------------------------------------------------------

def _core_server(clock=None):
    from ratelimiter_tpu.service.sidecar import SidecarServer

    st = TpuBatchedStorage(num_slots=1024)
    server = SidecarServer(st, host="127.0.0.1").start()
    lid = server.register("tb", RateLimitConfig(
        max_permits=1 << 20, window_ms=60_000, refill_rate=1e6))
    server.attach_leases(LeaseManager(
        st, default_budget=64, max_budget=64, max_bulk_budget=8192,
        ttl_ms=60_000.0))
    return st, server, lid


def test_edgeproc_in_process_front_door():
    """build_edge fronts a real core: clients on the edge's OWN wire
    port burn subleases locally; the edge's upstream traffic collapses
    multiplicatively; plain ops proxy through."""
    from ratelimiter_tpu.edge.edgeproc import build_edge
    from ratelimiter_tpu.service.sidecar import SidecarClient

    st, core, lid = _core_server()
    edge_server = agg = upstream = None
    try:
        edge_server, agg, upstream = build_edge(
            "127.0.0.1", core.port, [lid], bulk_budget=2048,
            slice_budget=64)
        wire = SidecarClient("127.0.0.1", edge_server.port)
        try:
            cli = LeaseClient(wire, lid, budget=64, telemetry=False,
                              direct_fallback=False)
            n = 1500
            for i in range(n):
                assert cli.try_acquire(f"hot{i % 2}")
            cli.release_all()
            # The edge spent <= n/5 frames upstream for n decisions.
            assert agg.upstream_frames * 5 <= n
            # Plain per-decision ops proxy to the core unchanged.
            assert wire.try_acquire(lid, "proxy-key") is True
            assert wire.available(lid, "proxy-key") >= 0
        finally:
            wire.close()
        agg.release_all()
        assert core._leases.table.outstanding() == 0
    finally:
        if upstream is not None:
            upstream.close()
        if edge_server is not None:
            edge_server.stop()
        core.stop()
        st.close()


@pytest.mark.slow
def test_edgeproc_subprocess_ready_and_eof_shutdown():
    """The process contract hostproc also honors: one JSON ready line
    on stdout, serve until stdin EOF, exit 0."""
    st, core, lid = _core_server()
    try:
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.Popen(
            [sys.executable, "-m", "ratelimiter_tpu.edge.edgeproc",
             "--upstream-host", "127.0.0.1",
             "--upstream-port", str(core.port),
             "--lids", str(lid)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(
                __file__))))
        try:
            ready = json.loads(proc.stdout.readline())
            assert ready["ready"] and ready["role"] == "edge"
            assert ready["version"] >= 6
            from ratelimiter_tpu.service.sidecar import SidecarClient

            wire = SidecarClient("127.0.0.1", int(ready["port"]))
            try:
                cli = LeaseClient(wire, lid, budget=64, telemetry=False,
                                  direct_fallback=False)
                for _ in range(200):
                    assert cli.try_acquire("sub")
                cli.release_all()
            finally:
                wire.close()
            proc.stdin.close()  # EOF => graceful shutdown
            assert proc.wait(timeout=30) == 0
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait(timeout=10)
    finally:
        core.stop()
        st.close()


# ---------------------------------------------------------------------------
# Service wiring: /actuator/edge + config gating
# ---------------------------------------------------------------------------

def test_wiring_edge_disabled_without_leases():
    from ratelimiter_tpu.service.props import AppProperties
    from ratelimiter_tpu.service.wiring import build_app

    ctx = build_app(AppProperties({
        "storage.backend": "tpu", "storage.num_slots": "1024",
        "parallel.shard": "off", "warmup.enabled": "false",
        "ratelimiter.edge.enabled": "true",  # but leases are off
    }))
    try:
        assert ctx.edge is None
    finally:
        ctx.close()


def test_wiring_edge_sessions_and_actuator():
    import http.client

    from ratelimiter_tpu.service.app import make_server
    from ratelimiter_tpu.service.props import AppProperties
    from ratelimiter_tpu.service.wiring import build_app

    ctx = build_app(AppProperties({
        "storage.backend": "tpu", "storage.num_slots": "1024",
        "parallel.shard": "off", "warmup.enabled": "false",
        "ratelimiter.lease.enabled": "true",
        "ratelimiter.lease.max_bulk_budget": "4096",
        "ratelimiter.edge.enabled": "true",
        "ratelimiter.edge.bulk_budget": "512",
        "ratelimiter.edge.slice_budget": "32",
    }))
    srv = make_server(ctx, port=0)
    thread = threading.Thread(target=srv.serve_forever, daemon=True)
    thread.start()
    try:
        assert ctx.edge is not None
        lid = ctx.limiters["burst"]._lid
        cli = LeaseClient(ctx.edge.session(), lid, budget=32,
                          telemetry=False, direct_fallback=False)
        for _ in range(40):
            cli.try_acquire("edge-wired")
        conn = http.client.HTTPConnection(
            "127.0.0.1", srv.server_address[1], timeout=10)
        conn.request("GET", "/actuator/edge")
        body = json.loads(conn.getresponse().read())
        conn.close()
        assert body["enabled"] is True
        assert body["pools"] >= 1 and body["subleases"] >= 1
        cli.release_all()
    finally:
        srv.shutdown()
        ctx.close()


# ---------------------------------------------------------------------------
# The drill (fast variant; verify.sh runs this)
# ---------------------------------------------------------------------------

def test_aggregator_failover_drill_fast():
    from ratelimiter_tpu.storage.chaos import aggregator_failover_drill

    registry = MeterRegistry()
    report = aggregator_failover_drill(registry=registry)
    assert report["promotions"] == 1
    assert report["decisions"] > 500
    # Multiplicative collapse while healthy.
    assert report["wire_frames_healthy"] * 5 <= report["decisions"]
    # Death bounded by the dropped bulk budgets (nesting invariant).
    assert report["burned_after_death"] \
        <= report["exposure"]["sliced_out"] \
        <= report["exposure"]["bulk_budget"]
    # Scoped revocation: some pools died, but strictly fewer than the
    # key population — only the victim shard's routes were revoked.
    assert 0 < report["scoped_revocations"] < 12
    meters = registry.scrape()
    assert meters["ratelimiter.edge.bulk_renewals"] >= 1.0
    assert meters["ratelimiter.edge.scoped_revocations"] \
        == float(report["scoped_revocations"])
    assert meters["ratelimiter.lease.outstanding"] == 0.0
