"""Fleet telemetry plane (ARCHITECTURE §13e): per-tenant usage ring
exactness, client burn telemetry over the wire (drop-don't-block),
fleet-counter reconciliation, and end-to-end trace lineage with lease
ops interleaved."""

import threading
import time

import pytest

from ratelimiter_tpu.core.config import RateLimitConfig
from ratelimiter_tpu.metrics import MeterRegistry
from ratelimiter_tpu.observability.telemetry import (
    ClientTelemetry,
    TelemetryPlane,
    TraceLineage,
    decode_report,
    default_key_class,
    mint_trace_id,
)
from ratelimiter_tpu.observability.usage import FIELDS, UsageRing

T0 = 1_700_000_000_000


class FakeClock:
    def __init__(self, t=T0):
        self.t = t

    def __call__(self):
        return self.t


# ---------------------------------------------------------------------------
# Usage ring
# ---------------------------------------------------------------------------

def test_usage_ring_window_rotation_exact_vs_brute_force():
    """Window sums must equal a brute-force recount of the raw event
    log across bucket rotations, ring wrap-arounds, and a clock jump
    far past the ring span."""
    import random

    rnd = random.Random(1234)
    clock = FakeClock()
    ring = UsageRing(clock_ms=clock, max_tenants=8,
                     resolutions=((100, 8), (1000, 8)))
    events = []  # (t_ms, tenant, field, n)
    for step in range(3000):
        # Mixed cadence: mostly small steps, occasional jumps including
        # one far past the whole ring span.
        clock.t += rnd.choice([0, 1, 7, 40, 140, 900, 5000]
                              if step != 1500 else [50_000])
        tenant = rnd.randrange(3)
        field = rnd.choice(FIELDS)
        n = rnd.randrange(1, 5)
        ring.record(tenant, **{field: n})
        events.append((clock.t, tenant, field, n))

        if step % 157 == 0:
            for window_ms in (100, 250, 800, 3000, 8000):
                got, covered = ring.window_counts(tenant, window_ms)
                # Brute force with the SAME bucket-epoch definition:
                # pick the resolution the ring picks, count events whose
                # epoch is within the last k epochs incl. current.
                r = ring._pick_res(window_ms)
                bucket_ms, slots = ring._res[r]
                k = min(max(-(-window_ms // bucket_ms), 1), slots)
                e_now = clock.t // bucket_ms
                expect = dict.fromkeys(FIELDS, 0)
                for t_ms, ten, f, m in events:
                    if ten != tenant:
                        continue
                    e = t_ms // bucket_ms
                    # Events older than the ring span were overwritten —
                    # only epochs inside the last `slots` epochs can
                    # still be represented, and the window keeps k.
                    if e_now - k < e <= e_now:
                        expect[f] += m
                assert got == expect, (step, window_ms, got, expect)
                assert covered == k * bucket_ms


def test_usage_ring_tenant_cap_counts_drops():
    ring = UsageRing(clock_ms=FakeClock(), max_tenants=2)
    assert ring.record(1, admitted=1)
    assert ring.record(2, admitted=1)
    assert not ring.record(3, admitted=1)   # over the cap: refused
    assert ring.dropped_tenants == 1
    assert ring.tenants() == [1, 2]


def test_usage_signals_contract():
    clock = FakeClock()
    ring = UsageRing(clock_ms=clock, resolutions=((1000, 64),))
    ring.record(7, admitted=30, denied=10)
    ring.record(7, shed=5, lease_local=20)
    sig = ring.signals(7, window_ms=10_000)
    assert sig.tenant == 7 and sig.window_s == 10.0
    assert (sig.admitted, sig.denied, sig.shed) == (30, 10, 5)
    assert sig.lease_local == 20
    assert sig.goodput == pytest.approx(3.0)
    assert sig.observed_load == pytest.approx(4.5)
    assert sig.lease_local_rate == pytest.approx(2.0)


# ---------------------------------------------------------------------------
# Client telemetry codec + plane folding
# ---------------------------------------------------------------------------

def test_client_telemetry_roundtrip_and_classes():
    telem = ClientTelemetry(client_id=42, max_classes=2)
    telem.record_burn(1, "acme:u1", 2, 3.0)
    telem.record_burn(1, "acme:u2", 1, 900.0)
    telem.record_deny(1, "globex:u9", 10.0)
    telem.record_burn(1, 'evil"class\n:x', 1, 1.0)   # 3rd class: overflow
    blob = telem.encode_and_reset()
    assert not telem.pending()

    report = decode_report(blob)
    assert report.client_id == 42
    assert (report.allowed, report.denied) == (3, 1)
    recs = {cls: (a, d, p) for _lid, cls, a, d, p in report.records}
    assert recs["acme"] == (2, 0, 3)
    assert recs["globex"] == (0, 1, 0)
    assert recs["~other"] == (1, 0, 1)
    assert sum(c for _i, c in report.hist) == 4


def test_client_telemetry_sampled_latency_stamping():
    """stamp_pending: the caller pays the perf_counter pair only for
    the FIRST record of each flush interval — counts always land,
    the histogram gets one sample per interval, and encode_and_reset
    re-arms the stamp."""
    telem = ClientTelemetry(client_id=7)
    assert telem.stamp_pending
    telem.record_burn(1, "t:a", 1, 4.0)
    assert not telem.stamp_pending          # first sample taken
    telem.record_burn(1, "t:a", 1)          # latency-free fast path
    telem.record_deny(1, "t:b")
    report = decode_report(telem.encode_and_reset())
    assert (report.allowed, report.denied) == (2, 1)  # counts complete
    assert sum(c for _i, c in report.hist) == 1       # one sample
    assert telem.stamp_pending               # re-armed by the flush
    # A latency passed while unarmed still lands (the caller decides).
    telem.record_deny(1, "t:b", 9.0)
    assert not telem.stamp_pending
    report = decode_report(telem.encode_and_reset())
    assert sum(c for _i, c in report.hist) == 1


def test_default_key_class_bounds_cardinality():
    assert default_key_class("tenant:user123") == "tenant"
    assert default_key_class("plainkey") == "*"
    assert default_key_class(":leading") == "*"


def test_plane_fold_counters_staleness_and_rejects():
    clock = FakeClock()
    reg = MeterRegistry()
    plane = TelemetryPlane(reg, clock_ms=clock)
    telem = ClientTelemetry(client_id=9)
    telem.record_burn(3, "t:one", 1, 5.0)
    telem.record_burn(3, "t:one", 1, 5.0)
    telem.record_deny(3, "u:two", 5.0)
    assert plane.fold(telem.encode_and_reset()) == 2  # classes t and u
    scrape = reg.scrape()
    assert scrape["ratelimiter.decisions.allowed"] == 2
    assert scrape["ratelimiter.decisions.denied"] == 1
    assert scrape["ratelimiter.decisions.lease_local"] == 3
    assert scrape["ratelimiter.telemetry.reports"] == 1
    assert scrape["ratelimiter.telemetry.local_latency"]["count"] == 3
    counts, _ = plane.usage.window_counts(3, 10_000)
    assert counts["admitted"] == 2 and counts["lease_local"] == 2

    clock.t += 750
    assert plane.staleness_ms() == 750.0
    # Malformed input is counted, never raised.
    assert plane.fold(b"\x01garbage") == -1
    assert plane.reports_rejected == 1
    assert scrape is not None

    # note_server + shed + degraded feed the same fleet counters.
    plane.note_server(3, 10, 7)
    plane.note_shed(3, 2)
    plane.note_degraded(3, True)
    assert plane.allowed_total == 2 + 7 + 1
    assert plane.shed_total == 2
    counts, _ = plane.usage.window_counts(3, 10_000)
    assert counts["shed"] == 2


def test_plane_prometheus_labeled_series_escaped():
    from ratelimiter_tpu.observability import prometheus

    reg = MeterRegistry()
    plane = TelemetryPlane(reg, clock_ms=FakeClock())
    telem = ClientTelemetry(client_id=1,
                            key_class=lambda k: k.split("|")[0])
    telem.record_burn(5, 'bad\\cls"x\n|y', 1, 2.0)
    plane.fold(telem.encode_and_reset())
    text = prometheus.render(reg, collectors=(plane,))
    # Tenant series present...
    assert 'ratelimiter_tenant_admitted_total{tenant="5"} 1' in text
    # ...and the hostile key-class label is escaped per the exposition
    # format (backslash, quote, newline).
    assert ('key_class="bad\\\\cls\\"x\\n"' in text), text
    # Exposition stays line-parseable: no raw newline inside a sample.
    for line in text.splitlines():
        assert line.startswith("#") or " " in line


# ---------------------------------------------------------------------------
# Trace lineage
# ---------------------------------------------------------------------------

def test_lineage_sampling_forced_and_bounds():
    lin = TraceLineage(capacity=4, sample_n=0, max_hops=3)
    tid = mint_trace_id()
    assert not lin.sampled(tid)          # sample_n=0: only forced ids
    assert not lin.record(tid, "sidecar")
    lin.force(tid)
    assert lin.sampled(tid)
    assert lin.record(tid, "sidecar")
    assert lin.record(tid, "batcher")
    assert lin.record(tid, "resolve")
    assert not lin.record(tid, "overflow")   # max_hops bound
    assert lin.hops(tid) == ["sidecar", "batcher", "resolve"]
    assert lin.dropped_hops == 1

    # Capacity LRU: old traces fall off.
    tids = []
    for _ in range(6):
        t = mint_trace_id()
        lin.force(t)
        lin.record(t, "hop")
        tids.append(t)
    assert lin.lineage(tids[-1])
    assert not lin.lineage(tid)


# ---------------------------------------------------------------------------
# End-to-end: leases + telemetry + lineage through sidecar v4
# ---------------------------------------------------------------------------

@pytest.fixture
def lease_stack():
    from ratelimiter_tpu.leases import LeaseManager
    from ratelimiter_tpu.service.sidecar import SidecarServer
    from ratelimiter_tpu.storage import TpuBatchedStorage

    storage = TpuBatchedStorage(num_slots=1 << 10, max_delay_ms=0.2)
    server = SidecarServer(storage, host="127.0.0.1").start()
    lid = server.register("tb", RateLimitConfig(
        max_permits=1 << 18, window_ms=60_000, refill_rate=1e6))
    manager = LeaseManager(storage, default_budget=8, max_budget=8,
                           ttl_ms=60_000.0)
    server.attach_leases(manager)
    yield storage, server, manager, lid
    server.stop()
    storage.close()


def test_trace_propagation_sidecar_with_lease_ops_interleaved(lease_stack):
    """grant -> local burns -> renew must read back under ONE trace
    lineage, and a plain traced TRY_ACQUIRE shows its own
    sidecar -> batcher -> shard -> resolve path."""
    from ratelimiter_tpu.leases import LeaseClient
    from ratelimiter_tpu.service.sidecar import SidecarClient

    storage, server, manager, lid = lease_stack
    wire = SidecarClient("127.0.0.1", server.port)
    assert wire.server_version >= 4
    cli = LeaseClient(wire, lid, budget=8, trace_lineage=True,
                      telemetry_flush_ms=0.0)
    try:
        # Burn through one budget so a renew happens, with ordinary
        # traced decisions interleaved between the lease ops.
        for i in range(12):
            assert cli.try_acquire("trace:leased")
            if i == 5:
                assert wire.try_acquire(lid, f"plain{i}",
                                        trace_id=mint_trace_id())
        tid = cli.trace_of("trace:leased")
        assert tid
        hops = storage.lineage.hops(tid)
        # One lineage spans the lease lifecycle: the grant, then the
        # renew carrying the locally-burned decisions.
        gi = hops.index("lease.grant")
        ci = hops.index("client")
        ri = hops.index("lease.renew")
        assert gi < ci < ri
        assert {"sidecar", "batcher", "shard", "resolve"} <= set(hops)
        burns = [h for h in storage.lineage.lineage(tid)
                 if h["hop"] == "client"]
        assert burns[0]["local_burns"] == 8   # the exhausted budget

        # And the explicitly-traced plain decision got its own path.
        plain_tid = None
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and plain_tid is None:
            snap = storage.lineage.snapshot(last=64)["traces"]
            for th, hop_list in snap.items():
                names = [h["hop"] for h in hop_list]
                if names[:1] == ["sidecar"] and "lease.grant" not in names \
                        and "batcher" in names:
                    plain_tid = th
                    assert {"shard", "resolve"} <= set(names)
            time.sleep(0.01)
        assert plain_tid is not None, "traced TRY_ACQUIRE left no lineage"
    finally:
        cli.release_all()
        wire.close()


def test_fleet_counters_reconcile_over_wire(lease_stack):
    """After release_all's final flush, ratelimiter.decisions.* equals
    the client's ground-truth decision count exactly."""
    from ratelimiter_tpu.leases import LeaseClient
    from ratelimiter_tpu.service.sidecar import SidecarClient

    storage, server, manager, lid = lease_stack
    plane = storage.telemetry
    base = plane.allowed_total + plane.denied_total
    wire = SidecarClient("127.0.0.1", server.port)
    cli = LeaseClient(wire, lid, budget=8)
    try:
        n = 50
        for i in range(n):
            assert cli.try_acquire(f"acct:k{i % 3}")
        cli.release_all()
        # The release frames (request/response) serialize BEHIND the
        # final telemetry frame, so the fold has landed by now.
        assert plane.allowed_total + plane.denied_total - base == n
        assert plane.lease_local_total >= cli.local_decisions
        assert server.telemetry_frames_total > 0
        assert plane.reports_total > 0
    finally:
        wire.close()


def test_v3_client_sees_no_telemetry_and_old_framing(lease_stack):
    """A v3-pinned client is served byte-identically to a v3 server:
    TELEMETRY answers BAD_FRAME/unknown-op, lease ops still work."""
    from ratelimiter_tpu.service import sidecar as sc
    from ratelimiter_tpu.service.sidecar import SidecarClient

    storage, server, manager, lid = lease_stack
    client = SidecarClient("127.0.0.1", server.port, protocol=3)
    assert client.server_version == 3
    assert not client.telemetry_supported()
    assert client.telemetry_report(b"anything") is False
    g = client.lease_grant(lid, "v3:key", 8)
    assert g is not None and g.granted == 8
    client.lease_release(lid, "v3:key", 0)
    # Hand-built TELEMETRY frame on the v3 connection: unknown op.
    client._send(client._frame(sc.OP_TELEMETRY, 0, 0, "x"))
    status, _, errno = client._read_raw()
    assert (status, errno) == (sc.ST_BAD_FRAME, sc.ERR_UNKNOWN_OP)
    assert client.try_acquire(lid, "v3-still-works") is True
    client.close()


def test_telemetry_drop_dont_block_under_partition(lease_stack):
    """FaultInjectingProxy.partition(): reports are lost in flight but
    local lease decisions keep answering at memory speed — the decision
    path is never pinned behind a telemetry send; a fully-dead socket
    then exercises the dropped-flush counter + the telemetry-down
    latch (one bounded failure, never retried inline)."""
    from ratelimiter_tpu.leases import LeaseClient, LeaseManager
    from ratelimiter_tpu.service.sidecar import SidecarClient
    from ratelimiter_tpu.storage.chaos import FaultInjectingProxy

    storage, server, manager, lid = lease_stack
    # A budget big enough that NO renew happens during the partition —
    # the only wire traffic after the grant is telemetry flushes.
    server.attach_leases(LeaseManager(storage, default_budget=1 << 15,
                                      max_budget=1 << 15,
                                      ttl_ms=600_000.0))
    plane = storage.telemetry
    proxy = FaultInjectingProxy(server.port).start()
    try:
        wire = SidecarClient("127.0.0.1", proxy.port, timeout=5.0,
                             telemetry_send_timeout=0.2)
        cli = LeaseClient(wire, lid, budget=1 << 15,
                          telemetry_flush_ms=0.0)
        # Grant once while the link is healthy; the huge budget means
        # no renew (no wire op on the decision path) afterwards.
        assert cli.try_acquire("part:key")
        time.sleep(0.05)
        reports_before = plane.reports_total
        proxy.partition()
        t0 = time.perf_counter()
        for _ in range(4000):
            assert cli.try_acquire("part:key")
        wall = time.perf_counter() - t0
        assert cli.local_decisions >= 4000
        # Drop-don't-block: the partitioned link never stalls the
        # decision path (response-less frames, bounded send timeout).
        assert wall < 3.0, f"decision path stalled {wall:.1f}s"
        # The partitioned proxy swallowed every in-flight report: the
        # server folded nothing new (the staleness gauge is what makes
        # this visible operationally).
        time.sleep(0.05)
        assert plane.reports_total == reports_before

        # Link fully dead: the flush attempt FAILS (not just vanishes),
        # is counted as dropped, latches telemetry down — and the local
        # decision still answers.
        wire._sock.close()
        dropped_before = cli.telemetry_dropped
        assert cli.try_acquire("part:key")
        assert cli.telemetry_dropped == dropped_before + 1
        assert wire._telemetry_down
        # Latched: later flushes fail fast without touching the socket.
        assert cli.try_acquire("part:key")
        assert cli.telemetry_dropped == dropped_before + 2
    finally:
        proxy.stop()


# ---------------------------------------------------------------------------
# Flight recorder: lease lifecycle events + filters
# ---------------------------------------------------------------------------

def test_lease_lifecycle_flight_events_and_revocation_storm():
    from ratelimiter_tpu.leases import LeaseManager
    from ratelimiter_tpu.observability import FlightRecorder
    from ratelimiter_tpu.storage import TpuBatchedStorage

    clock = FakeClock()
    rec = FlightRecorder(capacity=256)
    storage = TpuBatchedStorage(num_slots=1 << 10, clock_ms=clock)
    try:
        lid = storage.register_limiter("tb", RateLimitConfig(
            max_permits=1 << 16, window_ms=60_000, refill_rate=1e6))
        mgr = LeaseManager(storage, default_budget=4, max_budget=4,
                           ttl_ms=1000.0, clock_ms=clock, recorder=rec,
                           storm_threshold=3, storm_window_ms=5000.0)
        keys = [f"storm:k{i}" for i in range(5)]
        for k in keys:
            assert mgr.grant(lid, k, 4).granted == 4
        assert rec.events(kind="lease.granted")

        # Release one (event), expire one (TTL), then bump the fence
        # epoch and renew the rest: a coalesced revocation storm.
        mgr.release(lid, keys[0], 1)
        assert rec.events(kind="lease.released")
        clock.t += 2000   # TTL passed for everyone still outstanding
        assert mgr.renew(lid, keys[1], 1) is None   # expired
        assert rec.events(kind="lease.expired")
        # Re-grant three, then fence: their renewals revoke.
        for k in keys[2:]:
            assert mgr.grant(lid, k, 4).granted == 4
        storage.fence(1)
        storage.lift_fence(1)   # lift so only the epoch delta remains
        for k in keys[2:]:
            assert mgr.renew(lid, k, 2) is None
        assert rec.events(kind="lease.revoked")
        storms = rec.events(kind="lease.revocation_storm")
        assert storms and storms[0]["n_revocations"] >= 3
        assert mgr.revocation_storms >= 1
    finally:
        storage.close()


def test_flightrecorder_kind_and_since_ms_filters():
    from ratelimiter_tpu.observability import FlightRecorder

    rec = FlightRecorder(capacity=64)
    rec.record("lease.granted", key="a")
    rec.record("overload.shed", reason="x")
    cut_ms = time.time_ns() // 1_000_000
    time.sleep(0.002)
    rec.record("lease.revoked", key="b")
    rec.record("lease.granted", key="c")

    snap = rec.snapshot(kind="lease")
    kinds = [e["kind"] for e in snap["events"]]
    assert kinds == ["lease.granted", "lease.revoked", "lease.granted"]
    assert snap["filtered"]["matched"] == 3

    snap = rec.snapshot(since_ms=cut_ms + 1)
    assert [e["kind"] for e in snap["events"]] == [
        "lease.revoked", "lease.granted"]

    snap = rec.snapshot(kind="lease.granted", since_ms=cut_ms + 1)
    assert [e["key"] for e in snap["events"]] == ["c"]
    # Unfiltered snapshots keep their original shape (no filter block).
    assert "filtered" not in rec.snapshot()


def test_flightrecorder_http_filters_and_tenants_endpoint():
    """?kind=/&since_ms= on /actuator/flightrecorder + the new
    /actuator/tenants payload through the full wiring."""
    import http.client
    import json

    from ratelimiter_tpu.service.app import make_server
    from ratelimiter_tpu.service.props import AppProperties
    from ratelimiter_tpu.service.wiring import build_app

    props = AppProperties({
        "storage.backend": "tpu",
        "storage.num_slots": "4096",
        "batcher.max_delay_ms": "0.2",
        "parallel.shard": "off",
        "warmup.enabled": "false",
        "ratelimiter.lease.enabled": "true",
    })
    ctx = build_app(props)
    srv = make_server(ctx, port=0)
    thread = threading.Thread(target=srv.serve_forever, daemon=True)
    thread.start()
    try:
        conn = http.client.HTTPConnection(
            "127.0.0.1", srv.server_address[1], timeout=10)
        conn.request("GET", "/api/data", headers={"X-User-ID": "ten1"})
        conn.getresponse().read()
        conn.request("GET", "/actuator/health")
        conn.getresponse().read()

        conn.request("GET", "/actuator/flightrecorder?kind=health")
        fr = json.loads(conn.getresponse().read())
        assert fr["events"] and all(
            e["kind"] == "health" for e in fr["events"])
        conn.request("GET",
                     "/actuator/flightrecorder?kind=health&since_ms="
                     f"{time.time_ns() // 1_000_000 + 60_000}")
        fr = json.loads(conn.getresponse().read())
        assert fr["events"] == []
        conn.request("GET", "/actuator/flightrecorder?since_ms=oops")
        assert conn.getresponse().status == 400

        conn.request("GET", "/actuator/tenants")
        resp = conn.getresponse()
        assert resp.status == 200
        tenants = json.loads(resp.read())
        assert tenants["enabled"]
        assert tenants["tenants"], "no tenant usage recorded"
        assert "telemetry" in tenants
        assert "leases" in tenants
        some = next(iter(tenants["tenants"].values()))
        assert some["totals"]["admitted"] >= 1
        conn.close()
    finally:
        srv.shutdown()
        ctx.close()
