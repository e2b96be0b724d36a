"""Lease loopback benchmark: the wire-frame collapse, measured.

Token leases (leases/, ARCHITECTURE §14) exist to stop paying one wire
frame per decision.  This bench runs both ingress shapes over the same
storage on loopback TCP and reports the collapse:

- **v2 pass** (baseline): N pipelining clients stream per-decision
  TRY_ACQUIRE frames through the sidecar — exactly one wire frame per
  decision (the PR 5 ingress, i.e. today's production path);
- **lease pass**: the same clients speak protocol v3 through a
  ``LeaseClient``: budgets are charged once, burned locally, renewed
  one frame per budget — wire frames per decision ~ 1/budget.

``--assert-ratio`` gates BOTH claims (run by verify.sh):

- >= 10x fewer wire frames per decision than the v2 pass, and
- equal or better decision throughput (local burns are memory-speed;
  anything less means the lease path added overhead somewhere it must
  not).

Emits one JSON line; bench.py can record it as ``lease_loopback``.
Run with cwd=repo root:  python bench/lease_loopback.py
Env: BENCH_SCALE=small shrinks the request count (CI).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

N_CLIENTS = 4
PIPELINE = 64          # frames per pipelined v2 batch
KEYS_PER_CLIENT = 8    # distinct leased keys per client (one lease each)
BUDGET = 64


def v2_pass(server, lid, reps: int) -> dict:
    """Per-decision baseline: pipelined TRY_ACQUIRE, 1 frame/decision."""
    from ratelimiter_tpu.service.sidecar import SidecarClient

    barrier = threading.Barrier(N_CLIENTS + 1)
    allowed = [0] * N_CLIENTS

    def client_loop(t: int) -> None:
        cli = SidecarClient("127.0.0.1", server.port, protocol=2)
        try:
            keys = [f"v2-c{t}-k{i % KEYS_PER_CLIENT}"
                    for i in range(PIPELINE)]
            cli.acquire_batch(lid, keys)  # warm
            barrier.wait()
            got = 0
            for _ in range(reps):
                res = cli.acquire_batch(lid, keys)
                got += sum(1 for _, a, _ in res if a)
            allowed[t] = got
        finally:
            cli.close()

    threads = [threading.Thread(target=client_loop, args=(t,), daemon=True)
               for t in range(N_CLIENTS)]
    for th in threads:
        th.start()
    barrier.wait()
    t0 = time.perf_counter()
    for th in threads:
        th.join()
    wall = time.perf_counter() - t0
    n = N_CLIENTS * reps * PIPELINE
    return {
        "decisions": n,
        "allowed": sum(allowed),
        "wall_s": round(wall, 4),
        "decisions_per_sec": round(n / wall, 1),
        # The v2 protocol is one frame per decision by definition.
        "wire_frames": n,
        "frames_per_decision": 1.0,
    }


def lease_pass(server, lid, reps: int) -> dict:
    """Leased clients: local burns, one renewal frame per budget (+ the
    piggybacked response-less telemetry frame, counted honestly)."""
    from ratelimiter_tpu.leases import LeaseClient
    from ratelimiter_tpu.service.sidecar import SidecarClient

    barrier = threading.Barrier(N_CLIENTS + 1)
    stats = [None] * N_CLIENTS
    per_client = reps * PIPELINE

    def client_loop(t: int) -> None:
        wire = SidecarClient("127.0.0.1", server.port)
        # Client 0 traces its leases so the bench can assert the full
        # client->sidecar->batcher->shard lineage server-side.
        cli = LeaseClient(wire, lid, budget=BUDGET,
                          trace_lineage=(t == 0))
        try:
            keys = [f"ls-c{t}-k{i}" for i in range(KEYS_PER_CLIENT)]
            assert cli.try_acquire(keys[0])  # warm (compiles the grant)
            barrier.wait()
            got = 0
            for i in range(per_client):
                if cli.try_acquire(keys[i % KEYS_PER_CLIENT]):
                    got += 1
            traces = [cli.trace_of(k) for k in keys]
            cli.release_all()
            stats[t] = {"allowed": got, "wire": cli.wire_ops,
                        "local": cli.local_decisions,
                        "telemetry_frames": cli.telemetry_flushes,
                        "telemetry_dropped": cli.telemetry_dropped,
                        "traces": [x for x in traces if x]}
        finally:
            wire.close()

    threads = [threading.Thread(target=client_loop, args=(t,), daemon=True)
               for t in range(N_CLIENTS)]
    for th in threads:
        th.start()
    barrier.wait()
    t0 = time.perf_counter()
    for th in threads:
        th.join()
    wall = time.perf_counter() - t0
    n = N_CLIENTS * per_client
    # Decision frames (grant/renew/release/fallback) are the collapse
    # the lease design claims; telemetry frames are a SEPARATE,
    # response-less observability stream — folding them into the same
    # ratio diluted the headline (~48x read as ~27x).  Report both.
    wire = sum(s["wire"] for s in stats)
    telem = sum(s["telemetry_frames"] for s in stats)
    return {
        "decisions": n,
        "allowed": sum(s["allowed"] for s in stats),
        "local_decisions": sum(s["local"] for s in stats),
        "wall_s": round(wall, 4),
        "decisions_per_sec": round(n / wall, 1),
        "wire_frames": wire,
        "wire_frames_with_telemetry": wire + telem,
        "telemetry_frames": telem,
        "telemetry_dropped": sum(s["telemetry_dropped"] for s in stats),
        "frames_per_decision": round(wire / n, 5),
        "frames_per_decision_with_telemetry": round((wire + telem) / n, 5),
        "budget": BUDGET,
        "traces": [t for s in stats for t in s.get("traces", ())],
        # Ground truth for the fleet-reconciliation assertion: every
        # decision this pass made (including the warm one per client).
        "ground_truth_decisions": N_CLIENTS * (per_client + 1),
    }


def direct_shared_pass(server, lid, reps: int) -> dict:
    """Direct leases on SHARED hot keys: the lease table grants one
    lease per (lid, key), so with every client hammering the same key
    set only one client burns locally per key — the rest pay a wire
    frame per decision through the fallback.  This is the ingress shape
    the aggregator tier (ARCHITECTURE §14b) exists to collapse."""
    from ratelimiter_tpu.leases import LeaseClient
    from ratelimiter_tpu.service.sidecar import SidecarClient

    barrier = threading.Barrier(N_CLIENTS + 1)
    stats = [None] * N_CLIENTS
    per_client = reps * PIPELINE
    keys = [f"agg-k{i}" for i in range(KEYS_PER_CLIENT)]  # SHARED

    def client_loop(t: int) -> None:
        wire = SidecarClient("127.0.0.1", server.port)
        cli = LeaseClient(wire, lid, budget=BUDGET, telemetry=False,
                          direct_fallback=True)
        try:
            assert cli.try_acquire(keys[t % KEYS_PER_CLIENT])  # warm
            barrier.wait()
            got = 0
            for i in range(per_client):
                if cli.try_acquire(keys[(t + i) % KEYS_PER_CLIENT]):
                    got += 1
            cli.release_all()
            stats[t] = {"allowed": got, "wire": cli.wire_ops,
                        "local": cli.local_decisions}
        finally:
            wire.close()

    threads = [threading.Thread(target=client_loop, args=(t,), daemon=True)
               for t in range(N_CLIENTS)]
    for th in threads:
        th.start()
    barrier.wait()
    t0 = time.perf_counter()
    for th in threads:
        th.join()
    wall = time.perf_counter() - t0
    n = N_CLIENTS * per_client
    wire = sum(s["wire"] for s in stats)
    return {
        "decisions": n,
        "allowed": sum(s["allowed"] for s in stats),
        "local_decisions": sum(s["local"] for s in stats),
        "wall_s": round(wall, 4),
        "decisions_per_sec": round(n / wall, 1),
        "wire_frames": wire,
        "frames_per_decision": round(wire / n, 5),
    }


def aggregator_pass(server, lid, reps: int) -> dict:
    """The same shared hot keys through ONE EdgeAggregator: each client
    burns a sublease locally, the aggregator holds one bulk lease per
    key and renews its whole portfolio in one v6 OP_BULK_RENEW frame
    per flush — every upstream frame rides ONE TCP connection, counted
    at the aggregator (the only place wire traffic exists)."""
    from ratelimiter_tpu.edge import EdgeAggregator
    from ratelimiter_tpu.leases import LeaseClient
    from ratelimiter_tpu.service.sidecar import SidecarClient

    barrier = threading.Barrier(N_CLIENTS + 1)
    stats = [None] * N_CLIENTS
    per_client = reps * PIPELINE
    keys = [f"agg-k{i}" for i in range(KEYS_PER_CLIENT)]  # SHARED
    wire = SidecarClient("127.0.0.1", server.port)
    agg = EdgeAggregator(wire, bulk_budget=N_CLIENTS * BUDGET * 2,
                         slice_budget=BUDGET, flush_ms=50.0)

    def client_loop(t: int) -> None:
        cli = LeaseClient(agg.session(), lid, budget=BUDGET,
                          telemetry=False, direct_fallback=False)
        assert cli.try_acquire(keys[t % KEYS_PER_CLIENT])  # warm
        barrier.wait()
        got = 0
        for i in range(per_client):
            if cli.try_acquire(keys[(t + i) % KEYS_PER_CLIENT]):
                got += 1
        cli.release_all()
        stats[t] = {"allowed": got, "local": cli.local_decisions}

    try:
        threads = [threading.Thread(target=client_loop, args=(t,),
                                    daemon=True)
                   for t in range(N_CLIENTS)]
        for th in threads:
            th.start()
        barrier.wait()
        t0 = time.perf_counter()
        for th in threads:
            th.join()
        wall = time.perf_counter() - t0
        agg.release_all()
        n = N_CLIENTS * per_client
        return {
            "decisions": n,
            "allowed": sum(s["allowed"] for s in stats),
            "local_decisions": sum(s["local"] for s in stats),
            "wall_s": round(wall, 4),
            "decisions_per_sec": round(n / wall, 1),
            "wire_frames": agg.upstream_frames,
            "bulk_renewals": agg.bulk_renewals_total,
            "subleases_granted": agg.slices_granted_total,
            "frames_per_decision": round(agg.upstream_frames / n, 5),
        }
    finally:
        wire.close()


def main() -> None:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    parser = argparse.ArgumentParser()
    parser.add_argument("--assert-ratio", action="store_true",
                        help="gate >=10x wire-frame reduction at equal or "
                             "better decision throughput vs the v2 pass")
    parser.add_argument("--aggregator", action="store_true",
                        help="also run the shared-hot-key arms: direct "
                             "leases (fallback-heavy) vs one edge "
                             "aggregator subleasing bulk budgets; with "
                             "--assert-ratio, gate the >=5x collapse")
    args = parser.parse_args()

    from ratelimiter_tpu.core.config import RateLimitConfig
    from ratelimiter_tpu.leases import LeaseManager
    from ratelimiter_tpu.service.sidecar import SidecarServer
    from ratelimiter_tpu.storage import TpuBatchedStorage
    from ratelimiter_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    small = os.environ.get("BENCH_SCALE", "small") == "small"
    reps = 30 if small else 150

    storage = TpuBatchedStorage(num_slots=1 << 14, max_delay_ms=0.3,
                                max_inflight=4)
    server = SidecarServer(storage, host="127.0.0.1").start()
    try:
        lid = server.register("tb", RateLimitConfig(
            max_permits=1 << 20, window_ms=60_000, refill_rate=1e6))
        server.attach_leases(LeaseManager(
            storage, default_budget=BUDGET, max_budget=BUDGET,
            ttl_ms=60_000.0,
            # Only bulk (aggregator-tier) grants see this cap; the
            # default arms never issue one, so their wire traffic is
            # byte-identical with or without it.
            max_bulk_budget=N_CLIENTS * BUDGET * 4))
        storage.warm_micro_shapes()

        # Best-of-2 each (scheduler noise must not read as a regression).
        v2 = max((v2_pass(server, lid, reps) for _ in range(2)),
                 key=lambda r: r["decisions_per_sec"])
        plane = storage.telemetry
        fleet0 = plane.allowed_total + plane.denied_total
        ls_runs = [lease_pass(server, lid, reps) for _ in range(2)]
        fleet_delta = plane.allowed_total + plane.denied_total - fleet0
        ls = max(ls_runs, key=lambda r: r["decisions_per_sec"])

        # Telemetry round trip: after release_all's final flush, the
        # server-side fleet decision counters must reconcile EXACTLY
        # with the clients' ground-truth decision counts (the staleness
        # bound is one flush interval; at release it is zero).
        expected = sum(r["ground_truth_decisions"] for r in ls_runs)
        telemetry = {
            "fleet_counter_delta": fleet_delta,
            "ground_truth": expected,
            "lease_local_folded": plane.lease_local_total,
            "reports": plane.reports_total,
            "staleness_ms": plane.staleness_ms(),
        }
        assert fleet_delta == expected, (
            f"fleet decision counters ({fleet_delta}) do not reconcile "
            f"with client ground truth ({expected}) after the final "
            "telemetry flush")
        assert plane.reports_total > 0, "no telemetry report was folded"
        # A traced leased key must read back the full distributed
        # lineage: client -> sidecar -> batcher -> shard.
        lineage_ok = False
        for tid in ls_runs[-1]["traces"]:
            hops = set(storage.lineage.hops(tid))
            if {"sidecar", "lease.grant", "client", "batcher",
                    "shard"} <= hops:
                lineage_ok = True
                break
        assert lineage_ok, (
            "no leased trace carried the full client->sidecar->batcher->"
            "shard lineage")

        reduction = (v2["frames_per_decision"]
                     / max(ls["frames_per_decision"], 1e-9))
        reduction_all = (v2["frames_per_decision"]
                         / max(ls["frames_per_decision_with_telemetry"],
                               1e-9))
        speedup = ls["decisions_per_sec"] / max(v2["decisions_per_sec"],
                                                1.0)
        out = {
            "bench": "lease_loopback",
            "note": ("loopback TCP, CPU device in-process: measures the "
                     "wire-frame collapse of token leases vs the "
                     "per-decision v2 ingress over the same storage"),
            "v2": v2,
            "lease": {k: v for k, v in ls.items() if k != "traces"},
            "telemetry": telemetry,
            # Headline = DECISION frames only; the telemetry stream is
            # reported alongside, not folded in (it diluted the ratio).
            "wire_frame_reduction": round(reduction, 1),
            "wire_frame_reduction_with_telemetry": round(reduction_all, 1),
            "throughput_ratio": round(speedup, 2),
        }
        if args.aggregator:
            # Shared-hot-key arms (ARCHITECTURE §14b): direct leases
            # degenerate to per-decision fallback when every client
            # hammers the same keys; one aggregator collapses that
            # ingress multiplicatively.  Same admitted traffic: the
            # generous config admits every burn, so any allowed !=
            # decisions gap is an admission mismatch, not throttling.
            direct = max((direct_shared_pass(server, lid, reps)
                          for _ in range(2)),
                         key=lambda r: r["decisions_per_sec"])
            agg = max((aggregator_pass(server, lid, reps)
                       for _ in range(2)),
                      key=lambda r: r["decisions_per_sec"])
            assert direct["allowed"] == direct["decisions"], (
                f"direct-shared arm admission mismatch: "
                f"{direct['allowed']} != {direct['decisions']}")
            assert agg["allowed"] == agg["decisions"], (
                f"aggregator arm admission mismatch: "
                f"{agg['allowed']} != {agg['decisions']}")
            collapse = (direct["frames_per_decision"]
                        / max(agg["frames_per_decision"], 1e-9))
            out["direct_shared"] = direct
            out["aggregator"] = agg
            out["aggregator_frame_collapse"] = round(collapse, 1)
        print(json.dumps(out))
        if args.assert_ratio:
            assert reduction >= 10.0, (
                f"lease wire-frame reduction {reduction:.1f}x < 10x "
                f"(lease {ls['frames_per_decision']:.4f} frames/decision "
                f"vs v2 {v2['frames_per_decision']:.1f})")
            assert speedup >= 1.0, (
                f"leased decision throughput fell to {speedup:.2f}x of "
                f"the per-decision v2 path ({ls['decisions_per_sec']:.0f}"
                f"/s vs {v2['decisions_per_sec']:.0f}/s)")
            if args.aggregator:
                assert collapse >= 5.0, (
                    f"aggregator frame collapse {collapse:.1f}x < 5x vs "
                    f"the direct-lease arm on the same shared hot keys "
                    f"(agg {agg['frames_per_decision']:.5f} "
                    f"frames/decision vs direct "
                    f"{direct['frames_per_decision']:.5f})")
    finally:
        server.stop()
        storage.close()


if __name__ == "__main__":
    main()
