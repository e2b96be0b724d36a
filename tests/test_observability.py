"""Observability subsystem (ARCHITECTURE §13): Prometheus exposition,
request-lifecycle trace propagation, flight recorder, latency stage
histograms."""

import re
import threading

from ratelimiter_tpu.metrics import MeterRegistry
from ratelimiter_tpu.observability import (
    FlightRecorder,
    render_prometheus,
)


# ---------------------------------------------------------------------------
# Prometheus text exposition
# ---------------------------------------------------------------------------

def test_prometheus_golden():
    """Exact output for two counters, one gauge, one histogram — pins
    the format (name sanitization, HELP escaping + the description-table
    fallback for meters registered without one, bucket ladder,
    sum/count)."""
    reg = MeterRegistry()
    reg.counter("ratelimiter.requests.allowed", "Allowed requests").add(42)
    # Registered WITHOUT a description: HELP comes from the
    # METRIC_HELP description table.
    reg.counter("ratelimiter.cache.hits").add(7)
    reg.gauge("ratelimiter.replication.lag_ms", "Replication lag").set(1.5)
    t = reg.timer("ratelimiter.storage.latency",
                  "Dispatch latency\nsecond line \\ backslash")
    for v in (1.0, 3.0, 100.0):
        t.record_us(v)
    got = render_prometheus(reg)
    expected = "\n".join([
        "# HELP ratelimiter_cache_hits_total Local TTL-cache hits",
        "# TYPE ratelimiter_cache_hits_total counter",
        "ratelimiter_cache_hits_total 7",
        "# HELP ratelimiter_replication_lag_ms Replication lag",
        "# TYPE ratelimiter_replication_lag_ms gauge",
        "ratelimiter_replication_lag_ms 1.5",
        "# HELP ratelimiter_requests_allowed_total Allowed requests",
        "# TYPE ratelimiter_requests_allowed_total counter",
        "ratelimiter_requests_allowed_total 42",
        "# HELP ratelimiter_storage_latency_seconds "
        "Dispatch latency\\nsecond line \\\\ backslash",
        "# TYPE ratelimiter_storage_latency_seconds histogram",
        'ratelimiter_storage_latency_seconds_bucket{le="1e-06"} 1',
        'ratelimiter_storage_latency_seconds_bucket{le="2e-06"} 1',
        'ratelimiter_storage_latency_seconds_bucket{le="4e-06"} 2',
        'ratelimiter_storage_latency_seconds_bucket{le="8e-06"} 2',
        'ratelimiter_storage_latency_seconds_bucket{le="1.6e-05"} 2',
        'ratelimiter_storage_latency_seconds_bucket{le="3.2e-05"} 2',
        'ratelimiter_storage_latency_seconds_bucket{le="6.4e-05"} 2',
        'ratelimiter_storage_latency_seconds_bucket{le="0.000128"} 3',
        'ratelimiter_storage_latency_seconds_bucket{le="+Inf"} 3',
        "ratelimiter_storage_latency_seconds_sum 0.000104",
        "ratelimiter_storage_latency_seconds_count 3",
    ]) + "\n"
    assert got == expected


def _parse_histograms(text):
    """name -> {"buckets": [(le, cum)], "sum": float, "count": int}"""
    hists = {}
    for line in text.splitlines():
        m = re.match(r'^(\w+)_bucket\{le="([^"]+)"\} (\d+)$', line)
        if m:
            le = float("inf") if m.group(2) == "+Inf" else float(m.group(2))
            hists.setdefault(m.group(1), {"buckets": []})[
                "buckets"].append((le, int(m.group(3))))
            continue
        m = re.match(r"^(\w+)_(sum|count) (\S+)$", line)
        if m and m.group(1) in hists:
            hists[m.group(1)][m.group(2)] = float(m.group(3))
    return hists


def test_prometheus_histogram_invariants():
    """Bucket bounds and cumulative counts strictly monotonic; +Inf
    equals _count; _sum consistent with the recorded values."""
    reg = MeterRegistry()
    t = reg.timer("ratelimiter.latency.total", "total")
    import random

    rnd = random.Random(7)
    values = [rnd.uniform(0.1, 1e7) for _ in range(500)]
    for v in values:
        t.record_us(v)
    hists = _parse_histograms(render_prometheus(reg))
    h = hists["ratelimiter_latency_total_seconds"]
    les = [b[0] for b in h["buckets"]]
    cums = [b[1] for b in h["buckets"]]
    assert les == sorted(les) and len(set(les)) == len(les)
    assert cums == sorted(cums), "cumulative counts must be monotonic"
    assert les[-1] == float("inf")
    assert cums[-1] == h["count"] == len(values)
    assert abs(h["sum"] - sum(values) / 1e6) < 1e-6


def test_prometheus_name_sanitization():
    reg = MeterRegistry()
    reg.counter("ratelimiter.weird-name.v2", "d").add(1)
    out = render_prometheus(reg)
    assert "ratelimiter_weird_name_v2_total 1" in out


def test_prometheus_labeled_collector_golden():
    """Collector-provided labeled families render after the registry's
    meters, with label keys sorted and values escaped."""

    class FakeCollector:
        @staticmethod
        def prometheus_samples():
            return [(
                "ratelimiter.tenant.admitted", "counter", "Per-tenant",
                [({"tenant": "3"}, 10),
                 ({"tenant": "7", "key_class": 'a"b\\c\nd'}, 2)],
            )]

    reg = MeterRegistry()
    reg.counter("ratelimiter.requests.allowed", "Allowed").add(1)
    out = render_prometheus(reg, collectors=(FakeCollector(),))
    assert out.endswith("\n".join([
        "# HELP ratelimiter_tenant_admitted_total Per-tenant",
        "# TYPE ratelimiter_tenant_admitted_total counter",
        'ratelimiter_tenant_admitted_total{tenant="3"} 10',
        'ratelimiter_tenant_admitted_total'
        '{key_class="a\\"b\\\\c\\nd",tenant="7"} 2',
    ]) + "\n"), out


# ---------------------------------------------------------------------------
# Flight recorder
# ---------------------------------------------------------------------------

def test_flight_recorder_ring_wrap():
    rec = FlightRecorder(capacity=8)
    for i in range(20):
        rec.record("k", i=i)
    snap = rec.snapshot()
    assert snap["total_events"] == 20
    assert len(snap["events"]) == 8
    assert [e["i"] for e in snap["events"]] == list(range(12, 20))
    assert [e["seq"] for e in snap["events"]] == list(range(12, 20))


def test_flight_recorder_thread_safety():
    rec = FlightRecorder(capacity=64)
    n_threads, per = 8, 500

    def work(t):
        for i in range(per):
            rec.record(f"t{t}", i=i)
            if i % 100 == 0:
                rec.snapshot(last=16)

    threads = [threading.Thread(target=work, args=(t,))
               for t in range(n_threads)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    snap = rec.snapshot()
    assert snap["total_events"] == n_threads * per
    assert len(snap["events"]) == 64
    # Sequence numbers of surviving events are unique and ordered.
    seqs = [e["seq"] for e in snap["events"]]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)


def test_flight_recorder_coalescing():
    rec = FlightRecorder(capacity=16)
    for _ in range(10):
        rec.record("overload.shed", coalesce_ms=60_000.0, reason="x")
    events = rec.events(kind="overload.shed")
    assert len(events) == 1
    assert events[0]["n"] == 10


def test_flight_recorder_transitions_and_anomalies():
    rec = FlightRecorder(capacity=16, slo_ms=1.0, context_events=4)
    assert rec.record_transition("health", "UP")
    assert not rec.record_transition("health", "UP")  # no repeat
    assert rec.record_transition("health", "SHEDDING")
    assert [e["state"] for e in rec.events(kind="health")] == [
        "UP", "SHEDDING"]

    rec.note_dispatch(500.0)          # under the 1 ms SLO: no anomaly
    rec.note_dispatch(2_000.0, {"device": 1_800.0}, algo="tb")
    snap = rec.snapshot()
    assert snap["anomaly_total"] == 1
    anom = snap["anomalies"][0]
    assert anom["total_us"] == 2000.0
    assert anom["stages_us"] == {"device": 1800.0}
    assert anom["algo"] == "tb"
    assert len(anom["context"]) <= 4  # the last ring events ride along


def test_flight_recorder_mark_and_since():
    rec = FlightRecorder(capacity=16)
    rec.record("a")
    mark = rec.mark()
    rec.record("b")
    rec.record("a")
    kinds = [e["kind"] for e in rec.events(since=mark)]
    assert kinds == ["b", "a"]


# ---------------------------------------------------------------------------
# Request-lifecycle tracing (batcher -> histograms + sampled traces)
# ---------------------------------------------------------------------------

def _stage_sum_close_to_total(entry):
    stages = entry["stages_us"]
    assert set(stages) == {"queue_wait", "assembly", "device", "resolve"}
    for v in stages.values():
        assert v >= 0.0
    total = entry["latency_us"]
    assert abs(sum(stages.values()) - total) <= 1.0  # rounding slack


def test_trace_propagation_single_acquire():
    """One tryAcquire through the micro-batcher yields one sampled trace
    whose four stages are non-negative and telescope to ≈ total."""
    from ratelimiter_tpu.core.config import RateLimitConfig
    from ratelimiter_tpu.storage import TpuBatchedStorage

    storage = TpuBatchedStorage(num_slots=256, max_delay_ms=0.1,
                                trace_sample=1,
                                recorder=FlightRecorder())
    try:
        lid = storage.register_limiter("sw", RateLimitConfig.per_minute(10))
        out = storage.acquire("sw", lid, "trace-user", 1)
        assert out["allowed"]
        storage.flush()
        # The sampled trace lands on the drain thread right after the
        # future resolves; give it a moment.
        import time

        entry = None
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and entry is None:
            recent = storage.trace.snapshot()["recent"]
            entry = next((e for e in recent
                          if e.get("path") == "micro"
                          and "stages_us" in e), None)
            if entry is None:
                time.sleep(0.01)
        assert entry is not None, "no sampled micro trace recorded"
        _stage_sum_close_to_total(entry)
        assert entry["batch"] >= 1

        # The stage histograms aggregated the same lifecycle.
        scrape = storage.registry.scrape()
        for stage in ("queue_wait", "assembly", "device", "resolve",
                      "total"):
            snap = scrape[f"ratelimiter.latency.{stage}"]
            assert snap["count"] >= 1, stage
    finally:
        storage.close()


def test_trace_propagation_through_sidecar():
    """The same lifecycle trace survives the TCP front door: one
    pipelined sidecar acquire produces a sampled micro trace."""
    from ratelimiter_tpu.core.config import RateLimitConfig
    from ratelimiter_tpu.service import sidecar as sc
    from ratelimiter_tpu.storage import TpuBatchedStorage

    storage = TpuBatchedStorage(num_slots=256, max_delay_ms=0.1,
                                trace_sample=1,
                                recorder=FlightRecorder())
    server = sc.SidecarServer(storage, host="127.0.0.1").start()
    try:
        lid = server.register("tb", RateLimitConfig(
            max_permits=50, window_ms=60_000, refill_rate=10.0))
        client = sc.SidecarClient("127.0.0.1", server.port)
        assert client.try_acquire(lid, "sidecar-trace-user") is True
        client.close()
        import time

        entry = None
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and entry is None:
            recent = storage.trace.snapshot()["recent"]
            entry = next((e for e in recent
                          if e.get("path") == "micro"
                          and "stages_us" in e), None)
            if entry is None:
                time.sleep(0.01)
        assert entry is not None, "no sampled trace through the sidecar"
        _stage_sum_close_to_total(entry)
    finally:
        server.stop()
        storage.close()


def test_slow_dispatch_anomaly_capture():
    """A dispatch over the SLO threshold snapshots its stage breakdown
    into the flight recorder."""
    from ratelimiter_tpu.core.config import RateLimitConfig
    from ratelimiter_tpu.storage import TpuBatchedStorage

    rec = FlightRecorder(slo_ms=0.000001)  # everything is an anomaly
    storage = TpuBatchedStorage(num_slots=256, max_delay_ms=0.1,
                                recorder=rec)
    try:
        lid = storage.register_limiter("sw", RateLimitConfig.per_minute(10))
        storage.acquire("sw", lid, "slow-user", 1)
        storage.flush()
        import time

        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            if rec.snapshot()["anomaly_total"] > 0:
                break
            time.sleep(0.01)
        snap = rec.snapshot()
        assert snap["anomaly_total"] > 0
        assert snap["anomalies"][0]["kind"] == "slow_dispatch"
    finally:
        storage.close()


def test_stream_dispatch_path_enrichment():
    """Stream dispatches record their dispatch route (relay/flat/...)
    in the decision trace."""
    import numpy as np

    from ratelimiter_tpu.core.config import RateLimitConfig
    from ratelimiter_tpu.storage import TpuBatchedStorage

    storage = TpuBatchedStorage(num_slots=4096,
                                recorder=FlightRecorder())
    try:
        lid = storage.register_limiter("tb", RateLimitConfig(
            max_permits=1000, window_ms=1000, refill_rate=500.0))
        keys = np.arange(5000, dtype=np.int64) % 64
        storage.acquire_stream_ids("tb", lid, keys)
        recent = storage.trace.snapshot()["recent"]
        paths = {e.get("path") for e in recent}
        assert any(p and p != "micro" for p in paths), paths
    finally:
        storage.close()


def test_actuator_prometheus_and_flightrecorder_endpoints():
    """The HTTP tier serves both new actuator surfaces."""
    import http.client
    import json
    import threading

    from ratelimiter_tpu.service.app import make_server
    from ratelimiter_tpu.service.props import AppProperties
    from ratelimiter_tpu.service.wiring import build_app

    props = AppProperties({
        "storage.backend": "tpu",
        "storage.num_slots": "4096",
        "batcher.max_delay_ms": "0.2",
        "parallel.shard": "off",
        "warmup.enabled": "false",
    })
    ctx = build_app(props)
    srv = make_server(ctx, port=0)
    thread = threading.Thread(target=srv.serve_forever, daemon=True)
    thread.start()
    port = srv.server_address[1]
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
        conn.request("GET", "/api/data", headers={"X-User-ID": "u1"})
        assert conn.getresponse().read()

        conn.request("GET", "/actuator/prometheus")
        resp = conn.getresponse()
        assert resp.status == 200
        assert resp.getheader("Content-Type", "").startswith("text/plain")
        text = resp.read().decode()
        assert "ratelimiter_storage_latency_seconds_bucket" in text
        assert "ratelimiter_requests_allowed_total" in text
        hists = _parse_histograms(text)
        for name, h in hists.items():
            cums = [b[1] for b in h["buckets"]]
            assert cums == sorted(cums), name
            assert cums[-1] == h["count"], name

        conn.request("GET", "/actuator/health")
        assert conn.getresponse().read()
        conn.request("GET", "/actuator/flightrecorder")
        resp = conn.getresponse()
        assert resp.status == 200
        fr = json.loads(resp.read())
        # The health poll above recorded the UP transition.
        assert any(e["kind"] == "health" for e in fr["events"])
        conn.close()
    finally:
        srv.shutdown()
        ctx.close()
