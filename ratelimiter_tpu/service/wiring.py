"""Application wiring (C3 parity).

The reference's Spring ``@Configuration`` builds one storage bean, a meter
registry, and three named limiters (config/RateLimiterConfig.java:31-95):

- ``apiRateLimiter``   — sliding window, 100/min, local cache on (100 ms TTL)
- ``authRateLimiter``  — sliding window, 10/min, cache OFF (strictness)
- ``burstRateLimiter`` — token bucket, capacity 50, refill 10/sec

This module builds the identical trio over this framework's storage
backends, selected by ``storage.backend`` (tpu | memory), plus the shared
registry and the fail-open policy object.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

from ratelimiter_tpu.algorithms import SlidingWindowRateLimiter, TokenBucketRateLimiter
from ratelimiter_tpu.core.config import RateLimitConfig
from ratelimiter_tpu.core.limiter import RateLimiter
from ratelimiter_tpu.metrics import MeterRegistry
from ratelimiter_tpu.service.props import AppProperties
from ratelimiter_tpu.storage import (
    FaultInjectingStorage,
    InMemoryStorage,
    RateLimitStorage,
    TpuBatchedStorage,
    sharded_storage,
)


@dataclasses.dataclass
class ReplicationHandle:
    """What replication wiring hands the app: the primary's replicator
    or the standby's receiver+server, behind one close()."""

    role: str
    replicator: object = None
    receiver: object = None
    server: object = None

    def status(self) -> Dict:
        out = {"role": self.role}
        if self.replicator is not None:
            log = self.replicator.log
            if hasattr(log, "epochs"):  # sharded: per-shard epoch streams
                out.update(epochs=list(log.epochs),
                           shards=self.replicator.shard_status(),
                           journal=log.journal_kind)
            else:
                out.update(epoch=log.epoch,
                           journal=getattr(log, "journal_kind", "host"))
            out.update(lag_ms=self.replicator.lag_ms(),
                       frames_shipped=self.replicator.frames_shipped,
                       bytes_shipped=self.replicator.bytes_shipped,
                       errors=self.replicator.errors)
            if hasattr(self.replicator, "coalesced"):
                out["coalesced"] = self.replicator.coalesced
        if self.receiver is not None:
            out.update(applied_epoch=self.receiver.last_epoch,
                       consistent=self.receiver.consistent,
                       promoted=self.receiver.promoted,
                       frames_applied=self.receiver.frames_applied)
        return out

    def close(self) -> None:
        if self.replicator is not None:
            self.replicator.close()
        if self.server is not None:
            self.server.stop()


@dataclasses.dataclass
class OrchestratorHandle:
    """Self-healing failover wiring (ratelimiter.orchestrator.*): the
    orchestrator, the router the app serves through, the per-shard
    replicator feeding the in-process standby mesh."""

    orchestrator: object
    router: object
    replicator: object
    standby_set: object

    def status(self) -> Dict:
        out = {"enabled": True, **self.orchestrator.status()}
        out["router"] = {str(q): v
                         for q, v in self.router.shard_status().items()}
        out["replication"] = {str(q): v for q, v in
                              self.replicator.shard_status().items()}
        return out

    def close(self) -> None:
        self.orchestrator.close()
        self.replicator.close()
        # A standby whose receiver was PROMOTED is now the serving
        # replacement (closed with the router's chain); re-seeded fresh
        # standbys are ours to close.
        promoted = tuple(
            q for q, rx in enumerate(self.standby_set.receivers)
            if getattr(rx, "promoted", False))
        self.standby_set.close(except_shards=promoted)


@dataclasses.dataclass
class FleetControlHandle:
    """Fleet-true control wiring (ratelimiter.control.fleet.*): the
    epoch-fenced FleetControlPlane the adaptive controller actuates
    through, plus the ControllerElection repairing leader death."""

    plane: object
    election: object

    def lagging_nodes(self) -> list:
        """Members whose last applied policy generation sits behind the
        leader's last broadcast — the generation-convergence invariant's
        health-fold signal (reads the plane's cached view; no RPC)."""
        target = int(self.plane.last_broadcast_generation)
        if target <= 0:
            return []
        return sorted(
            name for name, gen in self.plane.node_generations.items()
            if int(gen) < target)

    def status(self) -> Dict:
        out = {"enabled": True, "fleet": True,
               **self.plane.fleet_status()}
        out["election"] = self.election.status()
        out["lagging_nodes"] = self.lagging_nodes()
        return out

    def close(self) -> None:
        self.election.close()
        self.plane.close()


@dataclasses.dataclass
class AppContext:
    props: AppProperties
    storage: RateLimitStorage
    registry: MeterRegistry
    limiters: Dict[str, RateLimiter]
    fail_open: bool
    replication: ReplicationHandle | None = None
    # The CircuitBreakerStorage layer (None when breaker.enabled=false or
    # the storage was injected) — the health state machine reads it.
    breaker: object = None
    # The TCP decision sidecar (ratelimiter.sidecar.enabled) — the health
    # state machine folds its shed/connection stats in.
    sidecar: object = None
    # The flight recorder behind GET /actuator/flightrecorder (the
    # process-global instance unless a test injected one).
    recorder: object = None
    # Self-healing failover (ratelimiter.orchestrator.enabled) — the
    # autonomous fence/promote/re-seed loop over a sharded primary.
    orchestrator: OrchestratorHandle | None = None
    # Token-lease manager (ratelimiter.lease.enabled) — serves the
    # sidecar's v3 LEASE/RENEW/RELEASE ops and in-process LeaseClients.
    leases: object = None
    # Control-plane RPC listener (ratelimiter.control.port) — this
    # node's remote fence/lease/probe/promote authority surface.
    control: object = None
    # Adaptive policy controller (ratelimiter.control.enabled) — the
    # AIMD loop behind GET /actuator/policies (ARCHITECTURE §15).
    controller: object = None
    # Fleet NodeManager (ratelimiter.fleet.enabled) — node lifecycle +
    # autopilot substrate behind GET /actuator/fleet (ARCHITECTURE §16).
    fleet: object = None
    # Fleet-true control plane (ratelimiter.control.fleet.enabled) —
    # epoch-fenced controller leadership + cross-host policy broadcast
    # behind GET /actuator/controller (ARCHITECTURE §15).
    fleet_control: FleetControlHandle | None = None
    # In-process edge aggregator (ratelimiter.edge.enabled) — bulk
    # leases subleased to in-process clients behind GET /actuator/edge
    # (ARCHITECTURE §14b).
    edge: object = None

    def close(self) -> None:
        if self.edge is not None:
            # Return every outstanding bulk budget before the lease
            # manager (and its storage) goes away.
            try:
                self.edge.release_all()
            except Exception:  # noqa: BLE001 — best-effort drain
                pass
        if self.fleet is not None:
            self.fleet.close()
        if self.controller is not None:
            self.controller.close()
        if self.fleet_control is not None:
            self.fleet_control.close()
        if self.control is not None:
            self.control.stop()
        if self.sidecar is not None:
            self.sidecar.stop()
        if self.replication is not None:
            self.replication.close()
        if self.orchestrator is not None:
            self.orchestrator.close()
        self.storage.close()


def warmup_shapes(storage: RateLimitStorage, max_batch: int = 8192) -> None:
    """Compile the hot dispatch shapes before traffic arrives.

    A cold service otherwise spends its first requests inside 40-90 s jit
    compiles, during which token buckets legitimately refill — confusing
    and latency-hostile.  Padding-only batches (slot -1) compile the exact
    shapes the micro-batcher uses without touching any real slot state.

    Warms the smallest bucket (single requests) and the full-flush bucket;
    intermediate power-of-two buckets compile on demand (or come from the
    persistent cache).  Padding-only batches route as shard-0 padding on
    the sharded engine, so both engine kinds warm their acquire and peek
    shapes.  Off the TPU each call is best-effort; on a TPU backend a
    warmup failure propagates — it is the device failing, and the
    breaker and degraded tiers would otherwise hide it behind 200s.
    """
    import jax

    strict = jax.default_backend() == "tpu"
    engine = getattr(storage, "engine", None)
    if engine is None:
        return
    now = 1  # any positive stamp; padding batches never write state
    calls = [
        lambda: engine.sw_acquire([-1], [0], [1], now),
        lambda: engine.tb_acquire([-1], [0], [1], now),
        lambda: engine.sw_acquire([-1] * max_batch, [0] * max_batch,
                                  [1] * max_batch, now),
        lambda: engine.tb_acquire([-1] * max_batch, [0] * max_batch,
                                  [1] * max_batch, now),
        lambda: engine.sw_available([0], [0], now),
        lambda: engine.tb_available([0], [0], now),
    ]
    for call in calls + [engine.block_until_ready]:
        try:
            call()
        except Exception:  # noqa: BLE001 — best-effort off the TPU
            if strict:
                raise


def build_storage(props: AppProperties, meter_registry=None) -> RateLimitStorage:
    backend = (props.get("storage.backend") or "tpu").lower()
    if backend == "memory":
        return InMemoryStorage()
    if backend == "tpu":
        num_slots = props.get_int("storage.num_slots", 1 << 20)
        shard = (props.get("parallel.shard") or "auto").lower()
        devices = []
        if shard in ("auto", "true", "on"):
            import jax

            devices = jax.devices()
        kw = dict(
            max_batch=props.get_int("batcher.max_batch", 8192),
            max_delay_ms=props.get_float("batcher.max_delay_ms", 0.5),
            max_inflight=props.get_int("batcher.max_inflight", 4),
            # Admission control (engine/batcher.py): bounded pending queue
            # + per-request queue-deadline budgets; sheds raise
            # OverloadedError, which service/app.py maps to 429+Retry-After.
            max_pending=props.get_int("ratelimiter.overload.max_pending",
                                      65536),
            queue_deadline_ms=props.get_float(
                "ratelimiter.overload.deadline_ms", 1000.0),
            meter_registry=meter_registry,
            # Observability (ARCHITECTURE §13): 1-in-N full-trace
            # sampling + the slow-dispatch anomaly threshold.
            trace_sample=props.get_int("ratelimiter.obs.trace_sample", 0),
            obs_slo_ms=props.get_float("ratelimiter.obs.slo_ms", 0.0),
            # Adaptive flush + hybrid serving tier (ARCHITECTURE §6d).
            adaptive_flush=props.get_bool(
                "ratelimiter.microbatch.adaptive_flush", True),
            flush_floor_ms=props.get_float(
                "ratelimiter.microbatch.flush_floor_ms", 0.05),
            serving_cache=props.get_bool(
                "ratelimiter.cache.hybrid.enabled", False),
            serving_cache_ttl_ms=props.get_float(
                "ratelimiter.cache.hybrid.ttl_ms", 50.0),
            serving_cache_max_keys=props.get_int(
                "ratelimiter.cache.hybrid.max_keys", 65536),
            serving_cache_unconfirmed_cap=props.get_int(
                "ratelimiter.cache.hybrid.unconfirmed_cap", 64),
            serving_cache_guard_ms=props.get_float(
                "ratelimiter.cache.hybrid.guard_ms", 5.0),
            # Fleet telemetry plane + trace lineage (ARCHITECTURE §13e).
            usage_max_tenants=props.get_int(
                "ratelimiter.usage.max_tenants", 256),
            telemetry_max_clients=props.get_int(
                "ratelimiter.telemetry.max_clients", 1024),
            lineage_capacity=props.get_int(
                "ratelimiter.obs.lineage_capacity", 256),
        )
        # Pre-sized policy table (an implicit mid-traffic grow
        # recompiles the device step — engine/state.py:_grow).
        capacity = props.get_int("ratelimiter.table.capacity", 64)
        if len(devices) > 1:
            from ratelimiter_tpu.engine.state import LimiterTable

            return sharded_storage(num_slots, devices,
                                   LimiterTable(capacity=capacity), **kw)
        return TpuBatchedStorage(num_slots=num_slots,
                                 table_capacity=capacity, **kw)
    raise ValueError(f"unknown storage.backend: {backend!r}")


def _maybe_chaos(storage: RateLimitStorage, props: AppProperties):
    """Wrap the backend in the fault injector when a chaos drill is on."""
    rate = props.get_float("chaos.failure_rate", 0.0)
    latency = props.get_float("chaos.latency_ms", 0.0)
    if rate <= 0 and latency <= 0:
        return storage
    return FaultInjectingStorage(storage, failure_rate=rate,
                                 latency_ms=latency)


def _maybe_breaker(storage: RateLimitStorage, props: AppProperties,
                   registry: MeterRegistry):
    """Circuit breaker between retry and chaos — ``retry(breaker(chaos(
    storage)))`` — so every retry attempt against a dead backend counts
    toward the threshold, and once open, decisions short-circuit to the
    degraded host limiter instead of paying retry exhaustion per request.
    Returns ``(wrapped_storage, breaker_or_None)``."""
    if not props.get_bool("breaker.enabled", True):
        return storage, None
    from ratelimiter_tpu.storage.breaker import CircuitBreakerStorage

    fallback = None
    if (props.get_bool("ratelimiter.degraded.enabled", True)
            and getattr(storage, "supports_device_batching", False)):
        from ratelimiter_tpu.storage.degraded import DegradedHostLimiter

        # Walk the wrapper chain for the raw storage's telemetry plane
        # so degraded decisions stay in the fleet counters.
        plane, inner, seen = None, storage, set()
        while inner is not None and id(inner) not in seen:
            seen.add(id(inner))
            plane = getattr(inner, "telemetry", None)
            if plane is not None:
                break
            inner = getattr(inner, "_inner", None)
        fallback = DegradedHostLimiter(
            registry=registry,
            max_keys=props.get_int("ratelimiter.degraded.max_keys", 65536),
            telemetry=plane)
    breaker = CircuitBreakerStorage(
        storage,
        failure_threshold=props.get_int("breaker.failure_threshold", 8),
        open_ms=props.get_float("breaker.open_ms", 5000.0),
        half_open_probes=props.get_int("breaker.half_open_probes", 1),
        fallback=fallback,
        registry=registry,
    )
    return breaker, breaker


def _maybe_sidecar(storage: RateLimitStorage, props: AppProperties,
                   registry: MeterRegistry):
    """Config-gated TCP decision sidecar (OFF by default).

    Attaches to the RAW device-batched storage — the sidecar's pipelined
    ``acquire_async`` path needs the micro-batcher, and its per-frame
    admission control composes with (not under) the breaker/retry
    wrappers that serve the HTTP tier."""
    if not props.get_bool("ratelimiter.sidecar.enabled", False):
        return None
    if not getattr(storage, "supports_device_batching", False):
        import logging

        logging.getLogger("ratelimiter").warning(
            "ratelimiter.sidecar.enabled but the %s backend has no "
            "batched decision protocol; sidecar disabled",
            type(storage).__name__)
        return None
    from ratelimiter_tpu.service.sidecar import SidecarServer

    return SidecarServer.from_props(storage, props, registry).start()


def _maybe_leases(storage: RateLimitStorage, sidecar, props: AppProperties,
                  registry: MeterRegistry):
    """Config-gated token-lease tier (OFF by default; ARCHITECTURE §14).

    Builds a ``LeaseManager`` over the SERVING storage (the failover
    router when the orchestrator is on — lease grants must route to a
    promoted replacement exactly like decisions) and attaches it to the
    sidecar's v3 LEASE/RENEW/RELEASE ops when one is running.  Without
    a sidecar the manager still serves in-process ``LeaseClient``s
    through ``DirectTransport``."""
    if not props.get_bool("ratelimiter.lease.enabled", False):
        return None
    if not getattr(storage, "supports_device_batching", False) \
            and not hasattr(storage, "lease_reserve"):
        import logging

        logging.getLogger("ratelimiter").warning(
            "ratelimiter.lease.enabled but the %s backend has no "
            "lease_reserve surface; leases disabled",
            type(storage).__name__)
        return None
    from ratelimiter_tpu.leases import LeaseManager

    manager = LeaseManager(
        storage,
        default_budget=props.get_int("ratelimiter.lease.default_budget",
                                     64),
        max_budget=props.get_int("ratelimiter.lease.max_budget", 1024),
        ttl_ms=props.get_float("ratelimiter.lease.ttl_ms", 2000.0),
        deny_ttl_ms=props.get_float("ratelimiter.lease.deny_ttl_ms", 25.0),
        max_leases=props.get_int("ratelimiter.lease.max_leases", 65536),
        # Concurrency slots (ARCHITECTURE §15): bound every tenant's
        # aggregate outstanding lease budget (0 = unbounded).
        max_concurrent=props.get_int("ratelimiter.control.max_concurrent",
                                     0),
        # Aggregator-tier bulk leases (ARCHITECTURE §14b) may exceed
        # the per-client cap; 0 keeps bulk clamped like ordinary grants.
        max_bulk_budget=props.get_int("ratelimiter.lease.max_bulk_budget",
                                      0),
        registry=registry,
    )
    if sidecar is not None:
        sidecar.attach_leases(manager)
    return manager


def _maybe_edge(leases, props: AppProperties, registry: MeterRegistry):
    """Config-gated in-process edge aggregator (OFF by default;
    ARCHITECTURE §14b).

    Fronts the lease manager with an ``EdgeAggregator`` over a
    ``DirectTransport``: in-process ``LeaseClient``s built on
    ``ctx.edge.session()`` burn memory-speed subleases carved from one
    bulk lease per hot (lid, key), and the aggregator renews its whole
    portfolio in one batch per flush interval.  The standalone-process
    shape of the same tier is ``python -m ratelimiter_tpu.edge.edgeproc``
    pointed at this node's sidecar."""
    if not props.get_bool("ratelimiter.edge.enabled", False):
        return None
    if leases is None:
        import logging

        logging.getLogger("ratelimiter").warning(
            "ratelimiter.edge.enabled requires ratelimiter.lease.enabled; "
            "edge aggregator disabled")
        return None
    from ratelimiter_tpu.edge import EdgeAggregator
    from ratelimiter_tpu.leases import DirectTransport

    return EdgeAggregator(
        DirectTransport(leases),
        bulk_budget=props.get_int("ratelimiter.edge.bulk_budget", 4096),
        slice_budget=props.get_int("ratelimiter.edge.slice_budget", 64),
        flush_ms=props.get_float("ratelimiter.edge.flush_ms", 50.0),
        registry=registry,
    )


def _maybe_controller(serving: RateLimitStorage, props: AppProperties,
                      registry: MeterRegistry, breaker, recorder):
    """Config-gated adaptive policy control plane (OFF by default;
    ARCHITECTURE §15).

    Builds the tick-driven AIMD controller over the SERVING storage
    (the failover router when the orchestrator is on — policy updates
    must broadcast to promoted replacements exactly like decisions),
    observing the fleet telemetry plane's ``UsageSignals`` and the
    breaker's overload state, actuating live ``set_policy`` row updates.
    """
    if not props.get_bool("ratelimiter.control.enabled", False):
        return None
    if not hasattr(serving, "set_policy") \
            or getattr(serving, "telemetry", None) is None:
        import logging

        logging.getLogger("ratelimiter").warning(
            "ratelimiter.control.enabled but the %s backend has no "
            "set_policy/telemetry surface; adaptive control disabled",
            type(serving).__name__)
        return None
    from ratelimiter_tpu.control import (
        AdaptivePolicyController,
        ControlConfig,
    )

    return AdaptivePolicyController(
        serving,
        ControlConfig(
            interval_ms=props.get_float("ratelimiter.control.interval_ms",
                                        1000.0),
            window_ms=props.get_int("ratelimiter.control.window_ms", 2000),
            target_excess=props.get_float(
                "ratelimiter.control.target_excess", 0.5),
            increase_fraction=props.get_float(
                "ratelimiter.control.increase_fraction", 0.1),
            decrease_factor=props.get_float(
                "ratelimiter.control.decrease_factor", 0.5),
            floor_fraction=props.get_float(
                "ratelimiter.control.floor_fraction", 0.1),
            global_cap_per_s=props.get_float(
                "ratelimiter.control.global_cap_per_s", 0.0),
            staleness_bound_ms=props.get_float(
                "ratelimiter.control.staleness_bound_ms", 0.0),
        ),
        breaker=breaker,
        registry=registry,
        recorder=recorder,
    ).start()


def _maybe_fleet_control(serving: RateLimitStorage, props: AppProperties,
                         registry: MeterRegistry, recorder, fleet):
    """Config-gated fleet-true control plane (OFF by default;
    ARCHITECTURE §15).

    When enabled, the adaptive controller runs over a
    :class:`~ratelimiter_tpu.control.FleetControlPlane` instead of the
    local serving storage: fleet-summed UsageSignals in, epoch-fenced
    generation-stamped ``set_policy`` broadcasts out.  The companion
    :class:`~ratelimiter_tpu.control.ControllerElection` rides the
    fleet NodeManager's probe tick when one is running, else its own
    cadence thread.  Returns ``(handle_or_None, controller_storage)``
    — when enabled, the PLANE is what ``_maybe_controller`` builds on.
    """
    if not props.get_bool("ratelimiter.control.fleet.enabled", False):
        return None, serving
    import logging
    import os

    peers = [p.strip() for p in
             (props.get("ratelimiter.control.fleet.peers") or "").split(",")
             if p.strip()]
    if not peers:
        # Single-node cell: this process's own control port is the one
        # member seat (leadership is then trivially held, but the
        # epoch/generation discipline — and the actuator surface — are
        # identical to the multi-host shape).
        port = props.get_int("ratelimiter.control.port", 0)
        if port <= 0:
            logging.getLogger("ratelimiter").warning(
                "ratelimiter.control.fleet.enabled needs peers or an "
                "own ratelimiter.control.port to form a member set; "
                "fleet control disabled")
            return None, serving
        host = props.get("ratelimiter.control.host") or "127.0.0.1"
        peers = [f"{host}:{port}"]
    from ratelimiter_tpu.control import ControllerElection, FleetControlPlane
    from ratelimiter_tpu.replication.control import ControlClient
    from ratelimiter_tpu.replication.remote import RemoteBackend

    members = {}
    for part in peers:
        peer_host, _, peer_port = part.rpartition(":")
        backend = RemoteBackend(
            ControlClient(peer_host or "127.0.0.1", int(peer_port)),
            label=part)
        members[backend.label] = backend
    node = (props.get("ratelimiter.control.fleet.node")
            or f"ctrl-{os.getpid()}")
    plane = FleetControlPlane(
        node, members,
        ttl_ms=props.get_float("ratelimiter.control.fleet.ttl_ms", 3000.0),
        recorder=recorder)
    election = ControllerElection(
        [plane],
        interval_ms=props.get_float(
            "ratelimiter.control.fleet.interval_ms", 500.0),
        registry=registry, recorder=recorder)
    if fleet is not None:
        # Re-election rides the NodeManager's probe tick — leader death
        # is detected and repaired from the same cadence that detects
        # node death, no second thread.
        fleet.attach(election)
    else:
        election.start()
    return FleetControlHandle(plane=plane, election=election), plane


def _maybe_fleet(props: AppProperties, registry: MeterRegistry, recorder):
    """Config-gated fleet NodeManager (OFF by default; ARCHITECTURE
    §16).  Starts the probe cadence with an empty fleet — nodes are
    spawned/adopted by operator tooling (or a FleetAutopilot attached
    at runtime); the service plane contributes the actuator surface,
    the health fold, and the ``ratelimiter.fleet.*`` metrics."""
    if not props.get_bool("ratelimiter.fleet.enabled", False):
        return None
    from ratelimiter_tpu.fleet import LocalExecutor, NodeManager

    return NodeManager(
        executor=LocalExecutor(boot_timeout_s=props.get_float(
            "ratelimiter.fleet.boot_timeout_s", 180.0)),
        probe_interval_ms=props.get_float(
            "ratelimiter.fleet.probe_interval_ms", 500.0),
        probe_fail_threshold=props.get_int(
            "ratelimiter.fleet.probe_fail_threshold", 3),
        registry=registry, recorder=recorder,
    ).start()


def _maybe_retry(storage: RateLimitStorage, props: AppProperties):
    """Per-op retry around the (possibly chaos-wrapped) backend — the
    RedisRateLimitStorage.java:155-178 analog, composed so transient faults
    are absorbed here and only retry exhaustion reaches fail-open."""
    from ratelimiter_tpu.storage.errors import RetryPolicy
    from ratelimiter_tpu.storage.retry import RetryingStorage

    attempts = props.get_int("storage.retry.max_retries", 3)
    if attempts <= 0:
        return storage
    return RetryingStorage(storage, RetryPolicy(
        max_retries=attempts,
        retry_delay_ms=props.get_float("storage.retry.delay_ms", 10.0)))


def _maybe_replication(storage: RateLimitStorage, props: AppProperties,
                       registry: MeterRegistry) -> ReplicationHandle | None:
    """Config-gated replication wiring (OFF by default).

    ``replication.role=primary`` journals this storage and ships epoch
    frames to ``replication.target`` (host:port of a standby's
    listener); ``replication.role=standby`` starts the frame listener
    on ``replication.listen_port`` over this storage — which then idles
    as a shadow until an operator (or orchestrator) promotes it.

    A SHARDED primary (parallel/sharded.py engine) replicates per
    shard: ``replication.targets`` lists one standby ``host:port`` per
    shard (comma-separated, shard order) and each shard ships its own
    epoch stream to an ordinary flat standby of ``slots_per_shard``
    geometry — promotion replaces one shard, never the world.
    """
    if not props.get_bool("replication.enabled", False):
        return None
    import logging

    logger = logging.getLogger("ratelimiter")
    if not getattr(getattr(storage, "engine", None), "supports_replication",
                   False):
        logger.warning("replication.enabled but the %s backend has no "
                       "journaled engine; replication disabled",
                       type(storage).__name__)
        return None
    from ratelimiter_tpu.replication import (
        ReplicationLog,
        ReplicationServer,
        Replicator,
        ShardedReplicationLog,
        ShardedReplicator,
        SocketSink,
        StandbyReceiver,
    )

    role = (props.get("replication.role") or "primary").lower()
    if role == "primary":
        engine = storage.engine
        if hasattr(engine, "n_shards"):
            targets = (props.get("replication.targets")
                       or props.get("replication.target") or "")
            parts = [t.strip() for t in targets.split(",") if t.strip()]
            if len(parts) != engine.n_shards:
                logger.warning(
                    "sharded replication needs one replication.targets "
                    "entry per shard (%d given, %d shards); replication "
                    "disabled", len(parts), engine.n_shards)
                return None
            ack_s = props.get_float("replication.ack_timeout_ms",
                                    5000.0) / 1000.0
            sinks = {}
            for q, part in enumerate(parts):
                host, _, port = part.rpartition(":")
                sinks[q] = SocketSink(host or "127.0.0.1", int(port),
                                      ack_timeout=ack_s)
            repl = ShardedReplicator(
                ShardedReplicationLog(storage), sinks,
                interval_ms=props.get_float("replication.interval_ms",
                                            200.0),
                registry=registry,
            ).start()
            return ReplicationHandle(role="primary", replicator=repl)
        target = props.get("replication.target")
        if not target:
            logger.warning("replication.role=primary without "
                           "replication.target; replication disabled")
            return None
        host, _, port = target.rpartition(":")
        repl = Replicator(
            ReplicationLog(storage),
            SocketSink(host or "127.0.0.1", int(port),
                       ack_timeout=props.get_float(
                           "replication.ack_timeout_ms", 5000.0) / 1000.0),
            interval_ms=props.get_float("replication.interval_ms", 200.0),
            registry=registry,
        ).start()
        return ReplicationHandle(role="primary", replicator=repl)
    if role == "standby":
        receiver = StandbyReceiver(storage, registry=registry)
        server = ReplicationServer(
            receiver, port=props.get_int("replication.listen_port", 7401),
        ).start()
        return ReplicationHandle(role="standby", receiver=receiver,
                                 server=server)
    raise ValueError(f"unknown replication.role: {role!r}")


def _maybe_control(storage: RateLimitStorage, props: AppProperties,
                   replication: ReplicationHandle | None):
    """Config-gated control-plane RPC port (OFF by default).

    Exposes THIS process's fence/lease/probe authority over the small
    length-prefixed-JSON wire (replication/control.py) so a remote
    orchestrator — or an operator with a socket — can PROBE it, FENCE
    it, grant/renew its serving lease, and RESTORE (unfence) it.  A
    standby-role process additionally serves the remote-promotion RPC
    and the lease-relay mailbox (its ``repl_rx_age_ms`` is the witness
    signal).  Always binds the RAW device storage: fencing authority is
    node-local and must not route through failover wrappers."""
    port = props.get_int("ratelimiter.control.port", 0)
    if port <= 0:
        return None
    if not hasattr(storage, "fence"):
        import logging

        logging.getLogger("ratelimiter").warning(
            "ratelimiter.control.port set but the %s backend has no "
            "fence/lease surface; control port disabled",
            type(storage).__name__)
        return None
    from ratelimiter_tpu.replication.control import (
        ControlServer,
        primary_handlers,
        standby_handlers,
    )

    host = props.get("ratelimiter.control.host") or "127.0.0.1"
    if replication is not None and replication.receiver is not None:
        handlers = standby_handlers(storage, replication.receiver,
                                    repl_server=replication.server)
    else:
        handlers = primary_handlers(
            storage,
            replicator=(replication.replicator
                        if replication is not None else None))
    return ControlServer(handlers, host=host, port=port).start()


def _maybe_orchestrator(storage: RateLimitStorage, props: AppProperties,
                        registry: MeterRegistry):
    """Config-gated self-healing failover (OFF by default).

    Requires a SHARDED device engine.  Builds the single-host N+1
    topology: an in-process standby mesh (one flat standby per shard),
    per-shard replication streams, a ``ShardFailoverRouter`` the app
    serves through, and the ``FailoverOrchestrator`` watching it all —
    a dead shard is fenced, its standby promoted, its keys re-routed,
    and a fresh standby re-seeded with zero operator involvement.

    Returns ``(handle_or_None, serving_storage)`` — when enabled, the
    ROUTER becomes the storage the breaker/retry wrappers compose
    around.
    """
    if not props.get_bool("ratelimiter.orchestrator.enabled", False):
        return None, storage
    import logging

    logger = logging.getLogger("ratelimiter")
    engine = getattr(storage, "engine", None)
    if not hasattr(engine, "n_shards"):
        logger.warning(
            "ratelimiter.orchestrator.enabled but the %s backend has no "
            "sharded engine (orchestrated failover promotes one shard of "
            "N); orchestrator disabled", type(storage).__name__)
        return None, storage
    from ratelimiter_tpu.replication import (
        BackendLeaseChannel,
        FailoverOrchestrator,
        OrchestratorConfig,
        ShardedReplicationLog,
        ShardedReplicator,
        ShardFailoverRouter,
        ShardStandbySet,
    )

    sps = int(engine.slots_per_shard)

    def standby_factory():
        return TpuBatchedStorage(num_slots=sps)

    mesh_set = ShardStandbySet(int(engine.n_shards), standby_factory,
                               registry=registry)
    repl = ShardedReplicator(
        ShardedReplicationLog(storage), mesh_set.in_process_sinks(),
        interval_ms=props.get_float("replication.interval_ms", 200.0),
        registry=registry,
    ).start()
    router = ShardFailoverRouter(storage)
    # Distributed fence lease (ARCHITECTURE §10c): with a TTL set, every
    # shard's channel grants the one in-process primary — the lease then
    # guards "the orchestrator loop is alive and talking to us" (a hung
    # or killed orchestrator self-fences the storage within one TTL
    # instead of leaving fencing authority silently dead).  Cross-host
    # deployments build remote channels (replication/remote.py) instead.
    lease_ttl = props.get_float(
        "ratelimiter.orchestrator.fence_lease_ttl_ms", 0.0)
    lease_channels = ({q: BackendLeaseChannel(storage)
                       for q in range(int(engine.n_shards))}
                      if lease_ttl > 0 else None)
    orch = FailoverOrchestrator(
        router, mesh_set, repl, standby_factory=standby_factory,
        config=OrchestratorConfig(
            probe_interval_ms=props.get_float(
                "ratelimiter.orchestrator.probe_interval_ms", 100.0),
            suspect_threshold=props.get_int(
                "ratelimiter.orchestrator.suspect_threshold", 3),
            hysteresis_ms=props.get_float(
                "ratelimiter.orchestrator.hysteresis_ms", 500.0),
            promote_retries=props.get_int(
                "ratelimiter.orchestrator.promote_retries", 3),
            promote_backoff_ms=props.get_float(
                "ratelimiter.orchestrator.promote_backoff_ms", 50.0),
            reseed=props.get_bool("ratelimiter.orchestrator.reseed", True),
            fence_lease_ttl_ms=lease_ttl,
            fence_wait_slack_ms=props.get_float(
                "ratelimiter.orchestrator.fence_wait_slack_ms", 100.0),
        ),
        lease_channels=lease_channels,
        registry=registry,
    ).start()
    handle = OrchestratorHandle(orchestrator=orch, router=router,
                                replicator=repl, standby_set=mesh_set)
    return handle, router


def build_app(props: AppProperties | None = None,
              storage: RateLimitStorage | None = None) -> AppContext:
    props = props or AppProperties.load()
    from ratelimiter_tpu.utils.compile_cache import enable_compile_cache
    from ratelimiter_tpu.utils.logging import setup_logging

    setup_logging(props)
    enable_compile_cache()
    registry = MeterRegistry()
    # Flight recorder (observability/flightrecorder.py): the process-
    # global ring every subsystem appends state transitions to; sized +
    # SLO-armed from config here, served at /actuator/flightrecorder.
    from ratelimiter_tpu.observability import flight_recorder

    recorder = flight_recorder()
    recorder.resize(props.get_int("ratelimiter.obs.flight_capacity", 1024))
    slo_ms = props.get_float("ratelimiter.obs.slo_ms", 0.0)
    if slo_ms > 0:
        recorder.set_slo_ms(slo_ms)
    own_storage = storage is None
    storage = storage or build_storage(props, meter_registry=registry)
    replication = None
    breaker = None
    sidecar = None
    orchestrator = None
    leases = None
    edge = None
    control = None
    controller = None
    fleet = None
    fleet_control = None
    if own_storage:
        # Self-healing failover (the orchestrator owns its OWN per-shard
        # replication into an in-process standby mesh, so it supersedes
        # the replication.* wiring — both would fight over the journal).
        orchestrator, serving = _maybe_orchestrator(storage, props,
                                                    registry)
        if orchestrator is not None and props.get_bool(
                "replication.enabled", False):
            import logging

            logging.getLogger("ratelimiter").warning(
                "ratelimiter.orchestrator.enabled supersedes "
                "replication.* wiring (the orchestrator runs its own "
                "per-shard streams); replication.* ignored")
        elif orchestrator is None:
            # Replication attaches to the RAW TPU storage (the journal
            # hooks the engine), before the chaos/retry wrappers compose
            # around it.
            replication = _maybe_replication(storage, props, registry)
        sidecar = _maybe_sidecar(storage, props, registry)
        # Control port over the RAW storage's fence/lease authority
        # (plus the standby receiver's promote surface when this node
        # runs replication.role=standby).
        control = _maybe_control(storage, props, replication)
        if props.get_bool("warmup.enabled", True):
            warmup_shapes(storage,
                          max_batch=props.get_int("batcher.max_batch", 8192))
        # Fused-kernel fallback gauge at boot (the PR 4 silent-degrade
        # fix): the engine's settle_all() has resolved the probe by now,
        # so a probe failure on real hardware is visible from the first
        # scrape, not only after the first health hit.
        from ratelimiter_tpu.ops.pallas import relay_step

        registry.gauge(
            "ratelimiter.pallas.fused_fallback",
            "1 when the fused relay kernel's differential probe failed "
            "on this hardware (serving composed XLA instead)",
        ).set(1.0 if relay_step.fallback_info()["probe_failed"] else 0.0)
        # The router (when the orchestrator is on) becomes the storage
        # the breaker/retry wrappers compose around — warmup above ran
        # against the raw device storage.
        storage = serving
        # Leases grant against the SERVING storage (router when
        # present) so a promoted replacement receives the charges for
        # its keys exactly like decisions.
        leases = _maybe_leases(serving, sidecar, props, registry)
        edge = _maybe_edge(leases, props, registry)
        wrapped, breaker = _maybe_breaker(_maybe_chaos(storage, props),
                                          props, registry)
        storage = _maybe_retry(wrapped, props)
        # Degraded-mode seeds must follow live policy updates: an outage
        # after a set_policy approximates under the generation that is
        # actually serving, not the boot-time registration.
        if breaker is not None and breaker.fallback is not None \
                and hasattr(serving, "add_policy_listener"):
            serving.add_policy_listener(breaker.fallback.update_policy)
        fleet = _maybe_fleet(props, registry, recorder)
        # The adaptive controller actuates on the SERVING storage
        # (router when present) and reads the breaker's overload state
        # — or, in fleet mode, on the epoch-fenced FleetControlPlane
        # broadcasting to the whole cell.
        fleet_control, control_target = _maybe_fleet_control(
            serving, props, registry, recorder, fleet)
        controller = _maybe_controller(control_target, props, registry,
                                       breaker, recorder)

    limiters: Dict[str, RateLimiter] = {
        # Default API limiter: 100 req/min sliding window with local cache
        # (config/RateLimiterConfig.java:46-59).
        "api": SlidingWindowRateLimiter(
            storage,
            RateLimitConfig(max_permits=100, window_ms=60_000,
                            enable_local_cache=True, local_cache_ttl_ms=100),
            registry,
        ),
        # Strict auth limiter: 10/min, no cache (:65-77).
        "auth": SlidingWindowRateLimiter(
            storage,
            RateLimitConfig(max_permits=10, window_ms=60_000,
                            enable_local_cache=False),
            registry,
        ),
        # Burst-friendly token bucket: 50 capacity, 10/sec refill (:83-95).
        "burst": TokenBucketRateLimiter(
            storage,
            RateLimitConfig(max_permits=50, window_ms=60_000, refill_rate=10.0),
            registry,
        ),
    }
    if sidecar is not None:
        # Expose the HTTP tier's limiters to sidecar clients under their
        # existing lids — both front doors share the same device
        # counters per key (ids are distributed via config, like the
        # reference's named Spring beans; see /actuator/health.sidecar).
        for name, limiter in limiters.items():
            lid = getattr(limiter, "_lid", None)
            if lid is not None:
                algo = "tb" if isinstance(limiter, TokenBucketRateLimiter) \
                    else "sw"
                sidecar.expose(lid, algo, limiter._config)
    return AppContext(
        props=props,
        storage=storage,
        registry=registry,
        limiters=limiters,
        fail_open=props.get_bool("ratelimiter.fail_open", True),
        replication=replication,
        breaker=breaker,
        sidecar=sidecar,
        recorder=recorder,
        orchestrator=orchestrator,
        leases=leases,
        control=control,
        controller=controller,
        fleet=fleet,
        fleet_control=fleet_control,
        edge=edge,
    )
