"""Sidecar ingress hardening under network fault injection.

The fast drill (`storage/chaos.py:ingress_drill`) is the acceptance
surface: under malformed-frame, slowloris, garbage, and kill-mid-pipeline
faults the server stays up, healthy clients' decisions stay bit-identical
to ``semantics/oracle.py``, shed frames carry the typed retry-after
status, and handler threads / batcher futures / queue depth return to
baseline.  The slow soak drives 8 pipelining clients against sustained
faults for ~30 s (RUN_SLOW=1 via verify.sh).
"""

import threading
import time

import pytest

from ratelimiter_tpu import RateLimitConfig
from ratelimiter_tpu.metrics import MeterRegistry
from ratelimiter_tpu.service import sidecar as sc
from ratelimiter_tpu.storage import FaultInjectingProxy, TpuBatchedStorage
from ratelimiter_tpu.storage.chaos import ingress_drill

T0 = 1_753_000_000_000


def test_ingress_drill_fast():
    registry = MeterRegistry()
    report = ingress_drill(registry=registry)
    assert report["mismatches"] == 0
    assert set(report["faults"]) == {
        "malformed", "malformed_v5_columns", "slowloris", "garbage",
        "kill_mid_pipeline"}
    assert report["shed"] >= 1
    # 5 classic malformed frames + 4 malformed v5 columnar frames, every
    # one answered in-protocol with the stream staying in sync.
    assert report["malformed_answered"] == 9
    scrape = registry.scrape()
    assert scrape["ratelimiter.sidecar.malformed"] >= 9
    assert scrape["ratelimiter.sidecar.idle_closed"] >= 1
    assert scrape["ratelimiter.sidecar.pipeline_shed"] >= 1
    assert scrape["ratelimiter.sidecar.connections"] == 0


def test_fault_proxy_passthrough_is_transparent():
    clock = {"t": T0}
    storage = TpuBatchedStorage(num_slots=256, max_delay_ms=0.2,
                                clock_ms=lambda: clock["t"])
    server = sc.SidecarServer(storage, host="127.0.0.1").start()
    proxy = FaultInjectingProxy(server.port).start()
    try:
        lid = server.register("tb", RateLimitConfig(
            max_permits=50, window_ms=60_000, refill_rate=25.0))
        client = sc.SidecarClient("127.0.0.1", proxy.port)
        assert client.server_version >= 3  # handshake survives the hop
        got = client.acquire_batch(lid, [f"p{i}" for i in range(16)])
        assert all(s == sc.ST_OK and a for s, a, _ in got)
        client.close()
        assert proxy.connections == 1
        assert proxy.faults_injected == 0
    finally:
        proxy.stop()
        server.stop()
        storage.close()


def test_batcher_forget_withdraws_queued_requests():
    """`MicroBatcher.forget` removes still-queued futures (cancelled, out
    of the waiter set, slots unpinned) and leaves dispatched ones alone."""
    from ratelimiter_tpu.engine.batcher import MicroBatcher

    gate = threading.Event()

    def dispatch(slots, lids, permits):
        gate.wait(timeout=5.0)
        return {"allowed": [True] * len(slots)}

    # Huge delay: nothing dispatches until flush is forced.
    batcher = MicroBatcher(dispatch={"sw": dispatch},
                           clear={"sw": lambda s: None},
                           max_batch=1024, max_delay_ms=10_000.0)
    try:
        futs = [batcher.submit("sw", i, 0, 1) for i in range(8)]
        assert batcher.queue_depth() == 8
        dropped = futs[:5]
        assert batcher.forget(dropped) == 5
        assert batcher.abandoned_total == 5
        assert batcher.queue_depth() == 3
        assert batcher.pending_slots("sw") == {5, 6, 7}
        assert all(f.cancelled() for f in dropped)
        gate.set()
        batcher.flush()
        for f in futs[5:]:
            assert f.result(timeout=5.0)["allowed"] is True
        # Nothing left in the stranding-watch set.
        with batcher._cv:
            assert not batcher._waiters
        # Forgetting already-resolved futures is a no-op.
        assert batcher.forget(futs[5:]) == 0
    finally:
        batcher.close()


def test_health_state_machine_includes_sidecar_sheds():
    """The TCP front door participates in the PR 2 health state machine:
    a pipeline shed flips /actuator/health to SHEDDING within the window
    and decays back to UP after it."""
    from ratelimiter_tpu.service.app import health_payload
    from ratelimiter_tpu.service.props import AppProperties
    from ratelimiter_tpu.service.wiring import build_app

    clock = {"t": T0}
    storage = TpuBatchedStorage(num_slots=256, max_delay_ms=0.2,
                                clock_ms=lambda: clock["t"])
    props = AppProperties({
        "ratelimiter.overload.shed_health_window_ms": "400"})
    ctx = build_app(props, storage=storage)
    server = sc.SidecarServer(storage, host="127.0.0.1",
                              max_pipeline=4).start()
    ctx.sidecar = server
    try:
        lid = server.register("tb", RateLimitConfig(
            max_permits=100, window_ms=60_000, refill_rate=50.0))
        assert health_payload(ctx)["status"] == "UP"
        client = sc.SidecarClient("127.0.0.1", server.port)
        got = client.acquire_batch(lid, [f"h{i}" for i in range(16)])
        assert any(s == sc.ST_SHED for s, _, _ in got)
        payload = health_payload(ctx)
        assert payload["status"] == "SHEDDING"
        assert payload["sidecar"]["pipeline_shed_total"] >= 1
        time.sleep(0.6)  # outlive the 400 ms shed window
        assert health_payload(ctx)["status"] == "UP"
        client.close()
    finally:
        server.stop()
        ctx.close()


def test_wiring_starts_sidecar_from_props():
    from ratelimiter_tpu.service.app import health_payload
    from ratelimiter_tpu.service.props import AppProperties
    from ratelimiter_tpu.service.wiring import build_app

    ctx = build_app(AppProperties({
        "storage.num_slots": "256",
        "warmup.enabled": "false",
        "ratelimiter.sidecar.enabled": "true",
        "ratelimiter.sidecar.port": "0",   # ephemeral
    }))
    try:
        assert ctx.sidecar is not None
        client = sc.SidecarClient("127.0.0.1", ctx.sidecar.port)
        assert client.server_version >= 3
        assert client.ping()
        client.close()
        assert "sidecar" in health_payload(ctx)
    finally:
        ctx.close()


@pytest.mark.slow
def test_ingress_soak_slow():
    """30 s soak: 8 pipelining clients sustain decisions while chaos
    clients hammer the proxy with cycling faults.  Healthy traffic never
    sees a non-OK status; everything drains to baseline at the end."""
    duration_s = 30.0
    n_clients = 8
    pipeline = 32
    storage = TpuBatchedStorage(num_slots=1 << 12, max_delay_ms=0.3,
                                max_inflight=1)
    server = sc.SidecarServer(
        storage, host="127.0.0.1",
        max_frame_bytes=512, max_key_bytes=64, max_pipeline=256,
        idle_timeout_ms=5_000.0, read_timeout_ms=500.0).start()
    proxy = FaultInjectingProxy(server.port, seed=3).start()
    stop = threading.Event()
    errors: list = []
    decisions = [0] * n_clients
    try:
        lid = server.register("tb", RateLimitConfig(
            max_permits=1_000_000, window_ms=60_000, refill_rate=1e6))
        lid_atk = server.register("tb", RateLimitConfig(
            max_permits=1000, window_ms=60_000, refill_rate=100.0))

        def healthy_loop(i: int) -> None:
            try:
                client = sc.SidecarClient("127.0.0.1", server.port)
                r = 0
                while not stop.is_set():
                    keys = [f"c{i}-k{(r * pipeline + j) % 512}"
                            for j in range(pipeline)]
                    got = client.acquire_batch(lid, keys)
                    for s, _, _ in got:
                        assert s == sc.ST_OK, f"healthy client saw {s}"
                    decisions[i] += len(got)
                    r += 1
                client.close()
            except Exception as exc:  # noqa: BLE001 — collected below
                errors.append((i, repr(exc)))

        def chaos_loop() -> None:
            faults = ["kill", "garbage", "truncate", None]
            k = 0
            while not stop.is_set():
                mode = faults[k % len(faults)]
                if mode == "kill":
                    proxy.set_fault("kill", after=100 + 40 * (k % 5))
                elif mode == "garbage":
                    proxy.set_fault("garbage", after=13 + 7 * (k % 9),
                                    n=32)
                elif mode == "truncate":
                    proxy.set_fault("truncate", after=9 + 5 * (k % 7))
                else:
                    proxy.set_fault(None)
                k += 1
                try:
                    atk = sc.SidecarClient("127.0.0.1", proxy.port,
                                           timeout=2.0, protocol=1)
                    atk.acquire_batch(lid_atk,
                                      [f"a{j}" for j in range(24)])
                    atk.close()
                except Exception:  # noqa: BLE001 — faults SHOULD break it
                    pass
                time.sleep(0.02)

        threads = [threading.Thread(target=healthy_loop, args=(i,),
                                    daemon=True)
                   for i in range(n_clients)]
        threads += [threading.Thread(target=chaos_loop, daemon=True)
                    for _ in range(2)]
        for t in threads:
            t.start()
        time.sleep(duration_s)
        stop.set()
        for t in threads:
            t.join(timeout=15.0)

        assert not errors, f"healthy clients failed: {errors[:5]}"
        assert sum(decisions) > 0
        # Everything returns to baseline: no wedged handlers, no leaked
        # futures, queue drained, server still answering.
        batcher = storage._batcher
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            with batcher._cv:
                waiters = len(batcher._waiters)
            if waiters == 0 and batcher.queue_depth() == 0 \
                    and server.inflight() == 0:
                break
            time.sleep(0.1)
        with batcher._cv:
            assert not batcher._waiters, "batcher futures leaked"
        assert batcher.queue_depth() == 0
        assert server.inflight() == 0
        probe = sc.SidecarClient("127.0.0.1", server.port)
        assert probe.ping()
        probe.close()
    finally:
        stop.set()
        proxy.stop()
        server.stop()
        storage.close()


# ---------------------------------------------------------------------------
# Partition / flap primitives (the orchestrator drills build on these)
# ---------------------------------------------------------------------------

def _echo_server():
    import socketserver

    class Echo(socketserver.BaseRequestHandler):
        def handle(self):
            while True:
                try:
                    data = self.request.recv(64)
                except OSError:
                    return
                if not data:
                    return
                try:
                    self.request.sendall(data)
                except OSError:
                    return

    class Server(socketserver.ThreadingTCPServer):
        allow_reuse_address = True
        daemon_threads = True

    server = Server(("127.0.0.1", 0), Echo)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    return server


def test_proxy_partition_drops_both_directions_without_rst():
    """partition(): bytes vanish in BOTH directions, but neither socket
    is closed — the peer looks silently gone (recv blocks to timeout,
    send succeeds into the void), exactly the no-RST network-partition
    shape.  heal() restores the SAME connection."""
    import socket

    echo = _echo_server()
    proxy = FaultInjectingProxy(echo.server_address[1]).start()
    try:
        conn = socket.create_connection(("127.0.0.1", proxy.port),
                                        timeout=2.0)
        conn.sendall(b"ping")
        assert conn.recv(16) == b"ping"

        proxy.partition()
        conn.settimeout(0.3)
        conn.sendall(b"lost")            # send succeeds: no RST came back
        with pytest.raises(socket.timeout):
            conn.recv(16)                # ...but nothing ever returns
        assert proxy.faults_injected >= 1

        proxy.heal()                     # same connection, live again
        conn.settimeout(2.0)
        conn.sendall(b"back")
        assert conn.recv(16) == b"back"
        conn.close()
    finally:
        proxy.stop()
        echo.shutdown()
        echo.server_close()


def test_proxy_flap_alternates_partition_and_passthrough():
    """flap(period_s): the link alternates healthy/partitioned every
    half period — the flaky-link shape the orchestrator's hysteresis
    must damp.  Sampled across several periods, both phases must be
    observed on one connection."""
    import socket

    echo = _echo_server()
    proxy = FaultInjectingProxy(echo.server_address[1]).start()
    try:
        period = 0.4
        proxy.flap(period)
        conn = socket.create_connection(("127.0.0.1", proxy.port),
                                        timeout=2.0)
        conn.settimeout(0.15)
        ok = cut = 0
        deadline = time.monotonic() + 4 * period
        while time.monotonic() < deadline and not (ok and cut):
            try:
                conn.sendall(b"x")
                if conn.recv(16):
                    ok += 1
                else:
                    break
            except socket.timeout:
                cut += 1
            time.sleep(period / 8)
        assert ok >= 1, "flap never let a byte through"
        assert cut >= 1, "flap never cut the link"
        proxy.heal()
        conn.close()
    finally:
        proxy.stop()
        echo.shutdown()
        echo.server_close()
