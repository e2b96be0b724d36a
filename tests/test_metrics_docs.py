"""Metrics-name drift check: every meter the full wiring registers must
appear in ARCHITECTURE.md's §13 metric catalog — new metrics without
docs fail CI."""

import os
import re
import threading

import pytest

_ARCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "ARCHITECTURE.md")


def _documented_names() -> set:
    with open(_ARCH, encoding="utf-8") as fh:
        text = fh.read()
    names = set(re.findall(r"ratelimiter\.[a-z0-9_.]+", text))
    # Table rows compress families as `ratelimiter.stream.pack` /
    # `.index` / ... — expand the short suffixes against their prefix.
    for prefix, suffixes in re.findall(
            r"`(ratelimiter\.[a-z0-9_.]+)`((?:\s*/\s*`\.[a-z0-9_]+`)+)",
            text):
        base = prefix.rsplit(".", 1)[0]
        for suffix in re.findall(r"`\.([a-z0-9_]+)`", suffixes):
            names.add(f"{base}.{suffix}")
    return names


def test_all_registered_meters_are_documented():
    """Boot the full wiring (tpu backend, breaker, degraded, sidecar),
    drive one request through each surface so lazily-created meters
    exist, then assert every registered name is in the §13 table."""
    from ratelimiter_tpu.service.app import make_server
    from ratelimiter_tpu.service.props import AppProperties
    from ratelimiter_tpu.service.wiring import build_app

    props = AppProperties({
        "storage.backend": "tpu",
        "storage.num_slots": "4096",
        "batcher.max_delay_ms": "0.2",
        "parallel.shard": "off",
        "warmup.enabled": "false",
        "ratelimiter.sidecar.enabled": "true",
        "ratelimiter.sidecar.port": "0",
        "ratelimiter.lease.enabled": "true",
        "ratelimiter.edge.enabled": "true",
        "ratelimiter.control.enabled": "true",
        "ratelimiter.control.interval_ms": "60000",
        "ratelimiter.fleet.enabled": "true",
        "ratelimiter.fleet.probe_interval_ms": "60000",
        "ratelimiter.obs.trace_sample": "4",
    })
    ctx = build_app(props)
    srv = make_server(ctx, port=0)
    thread = threading.Thread(target=srv.serve_forever, daemon=True)
    thread.start()
    try:
        import http.client

        conn = http.client.HTTPConnection(
            "127.0.0.1", srv.server_address[1], timeout=10)
        conn.request("GET", "/api/data", headers={"X-User-ID": "drift"})
        conn.getresponse().read()
        conn.request("GET", "/actuator/health")
        conn.getresponse().read()
        conn.close()

        registered = set(ctx.registry.meters())
        assert registered, "wiring registered no meters?"
        documented = _documented_names()
        undocumented = sorted(registered - documented)
        assert not undocumented, (
            "meters registered but missing from ARCHITECTURE.md §13's "
            f"catalog: {undocumented} — document them or rename")
    finally:
        srv.shutdown()
        ctx.close()


def test_catalog_regex_expands_families():
    """Guard the expansion helper itself: compressed table rows must
    yield their full names."""
    names = _documented_names()
    for expected in ("ratelimiter.stream.pack", "ratelimiter.stream.fetch",
                     "ratelimiter.sidecar.pipeline_shed",
                     "ratelimiter.replication.applied_epoch",
                     "ratelimiter.requests.allowed",
                     "ratelimiter.lease.granted",
                     "ratelimiter.lease.local_decisions",
                     "ratelimiter.lease.over_admission",
                     "ratelimiter.decisions.allowed",
                     "ratelimiter.decisions.denied",
                     "ratelimiter.decisions.shed",
                     "ratelimiter.decisions.lease_local",
                     "ratelimiter.telemetry.reports",
                     "ratelimiter.telemetry.rejected",
                     "ratelimiter.telemetry.staleness_ms",
                     "ratelimiter.telemetry.local_latency",
                     "ratelimiter.tenant.admitted",
                     "ratelimiter.fleet.nodes",
                     "ratelimiter.fleet.respawns",
                     "ratelimiter.fleet.reseeds",
                     "ratelimiter.fleet.upgrade_steps",
                     "ratelimiter.control.leader",
                     "ratelimiter.control.elections",
                     "ratelimiter.control.stale_rejected",
                     "ratelimiter.control.converge_ms"):
        assert expected in names, expected
