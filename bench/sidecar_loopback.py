"""Sidecar loopback benchmark (VERDICT #6 — production ingress).

The decision sidecar (service/sidecar.py) is the framework's
many-clients/one-authority ingress: non-Python services stream binary
decision requests over TCP and every connection funnels into the shared
micro-batcher.  This bench runs the production topology in miniature on
loopback TCP:

    N pipelining clients -> sidecar server -> shared micro-batcher
                         -> device engine (CPU in-process here)

Each client pipelines frames in batches (the protocol's intended use —
one syscall per direction per batch, like Redis pipelining), so the
measurement covers frame parse + validation, per-request submit, batcher
coalescing across ALL clients, device dispatch, and response framing.
Emits decisions/s plus per-batch round-trip percentiles (p50/p99) into
ONE JSON line; bench.py records it in BENCH_DETAIL as
``sidecar_loopback``.

Modes:

- default: the hardened v2 server (frame validation, pipeline cap,
  deadlines, v2 handshake) — the production configuration.
- ``--assert-ratio``: ALSO measures an unhardened pass (bounds off, v1
  clients, no handshake) over the same storage and asserts the hardened
  number stays >= 0.9x of it — the ingress-hardening perf gate run by
  verify.sh.  Each configuration is measured twice and the best pass
  counts (CI noise must not read as a hardening regression).
- ``--faults``: runs the hardened pass while chaos clients hammer the
  server through a ``FaultInjectingProxy`` cycling kill / garbage /
  truncate faults — reports healthy-client throughput under fire and
  asserts the server survives.

Run with cwd=repo root:  python bench/sidecar_loopback.py
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

N_CLIENTS = 8
PIPELINE = 64          # frames per pipelined batch (one syscall each way)


def run_pass(storage, reps: int, *, hardened: bool, tag: str,
             chaos: bool = False, block: bool = False,
             protocol: int | None = None,
             server_kwargs: dict | None = None,
             block_rows: int = 16) -> dict:
    """One measured loopback pass over an EXISTING storage (a fresh
    server per pass; the batcher/device state is shared, which is the
    production shape — many ingress generations, one authority)."""
    import numpy as np

    from ratelimiter_tpu.core.config import RateLimitConfig
    from ratelimiter_tpu.service.sidecar import SidecarClient, SidecarServer
    from ratelimiter_tpu.storage.chaos import FaultInjectingProxy

    if hardened:
        server = SidecarServer(storage, host="127.0.0.1",
                               **(server_kwargs or {})).start()
    else:
        # Every bound off: the pre-hardening ingress shape.
        server = SidecarServer(
            storage, host="127.0.0.1", max_frame_bytes=0, max_key_bytes=0,
            max_pipeline=0, max_connections=0, idle_timeout_ms=0,
            read_timeout_ms=0, resolve_timeout_ms=0).start()
    proxy = FaultInjectingProxy(server.port, seed=7).start() if chaos \
        else None
    stop_chaos = threading.Event()
    if protocol is None:
        protocol = 2 if hardened else 1
    try:
        lid = server.register("tb", RateLimitConfig(
            max_permits=1_000_000, window_ms=60_000, refill_rate=1e6))
        storage.warm_micro_shapes()

        lat_lock = threading.Lock()
        batch_lat_us: list = []
        allowed_total = [0]
        barrier = threading.Barrier(N_CLIENTS + 1)

        def client_loop(t: int) -> None:
            cli = SidecarClient("127.0.0.1", server.port,
                                protocol=protocol)

            def submit(keys):
                # block=True: v5 columnar frames (one frame + one bitmask
                # per block_rows chunk) instead of per-request frames.
                if block:
                    return cli.acquire_block(lid, keys,
                                             max_rows=block_rows)
                return [a for _, a, _ in cli.acquire_batch(lid, keys)]

            try:
                keys0 = [f"{tag}-c{t}-w{i}" for i in range(PIPELINE)]
                submit(keys0)  # warm the path
                # Synchronized warm rounds: concurrent clients coalesce
                # into batch shapes a lone client never produces, and
                # their XLA compiles must fire before the timed region.
                barrier.wait()
                for _ in range(3):
                    submit(keys0)
                barrier.wait()
                local_lat, local_allowed = [], 0
                for r in range(reps):
                    keys = [f"{tag}-c{t}-k{(r * PIPELINE + i) % 512}"
                            for i in range(PIPELINE)]
                    t0 = time.perf_counter()
                    res = submit(keys)
                    local_lat.append((time.perf_counter() - t0) * 1e6)
                    local_allowed += sum(1 for a in res if a)
                with lat_lock:
                    batch_lat_us.extend(local_lat)
                    allowed_total[0] += local_allowed
            finally:
                cli.close()

        def chaos_loop() -> None:
            import socket as socket_mod

            lid_atk = server.register("tb", RateLimitConfig(
                max_permits=1000, window_ms=60_000, refill_rate=100.0))
            k = 0
            while not stop_chaos.is_set():
                mode = ("kill", "garbage", "truncate")[k % 3]
                if mode == "kill":
                    proxy.set_fault("kill", after=90 + 30 * (k % 5))
                elif mode == "garbage":
                    proxy.set_fault("garbage", after=11 + 9 * (k % 7),
                                    n=32)
                else:
                    proxy.set_fault("truncate", after=7 + 5 * (k % 6))
                k += 1
                try:
                    atk = SidecarClient("127.0.0.1", proxy.port,
                                        timeout=1.0, protocol=1)
                    atk.acquire_batch(lid_atk,
                                      [f"a{j}" for j in range(16)])
                    atk.close()
                except (OSError, RuntimeError, socket_mod.timeout):
                    pass
                time.sleep(0.01)

        threads = [threading.Thread(target=client_loop, args=(t,),
                                    daemon=True)
                   for t in range(N_CLIENTS)]
        if chaos:
            threads.append(threading.Thread(target=chaos_loop,
                                            daemon=True))
        for th in threads:
            th.start()
        barrier.wait()   # start of the synchronized warm rounds
        barrier.wait()   # warm done: timed region begins
        t_start = time.perf_counter()
        for th in threads[:N_CLIENTS]:
            th.join()
        wall = time.perf_counter() - t_start
        stop_chaos.set()

        n = N_CLIENTS * reps * PIPELINE
        lat = np.asarray(batch_lat_us)
        out = {
            "clients": N_CLIENTS,
            "pipeline_depth": PIPELINE,
            "decisions": n,
            "wall_s": round(wall, 4),
            "decisions_per_sec": round(n / wall, 1),
            "allowed": allowed_total[0],
            "hardened": hardened,
            "columnar": block,
            "batch_latency": {
                "p50_us": round(float(np.percentile(lat, 50)), 1),
                "p99_us": round(float(np.percentile(lat, 99)), 1),
                "max_us": round(float(lat.max()), 1),
                "n_samples": int(len(lat)),
            },
            # Amortized per-request figure: a pipelined batch of
            # PIPELINE frames shares one round trip.
            "per_request_p99_us": round(
                float(np.percentile(lat, 99)) / PIPELINE, 2),
        }
        if chaos:
            out["chaos"] = {
                "proxy_connections": proxy.connections,
                "faults_injected": proxy.faults_injected,
                "sidecar_malformed": server.malformed_total,
                "sidecar_idle_closed": server.idle_closed_total,
            }
            assert storage.is_available(), "storage died under faults"
        return out
    finally:
        stop_chaos.set()
        if proxy is not None:
            proxy.stop()
        server.stop()


def main() -> None:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    parser = argparse.ArgumentParser()
    parser.add_argument("--assert-ratio", action="store_true",
                        help="measure unhardened vs hardened and assert "
                             "hardened >= 0.9x")
    parser.add_argument("--faults", action="store_true",
                        help="run the hardened pass under proxy fault "
                             "injection")
    args = parser.parse_args()

    from ratelimiter_tpu.storage import TpuBatchedStorage
    from ratelimiter_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    small = os.environ.get("BENCH_SCALE", "small") == "small"
    reps = 40 if small else 200

    storage = TpuBatchedStorage(num_slots=1 << 14, max_delay_ms=0.3,
                                max_inflight=4)
    try:
        out = {"bench": "sidecar_loopback",
               "note": ("loopback TCP, CPU device in-process: measures "
                        "the ingress machinery (framing + validation + "
                        "batcher coalescing across clients), not a TPU")}
        if args.assert_ratio:
            # Best-of-2 per configuration: scheduler noise on a loaded
            # box must not read as a hardening regression.
            raw = max((run_pass(storage, reps, hardened=False,
                                tag=f"raw{i}")
                       for i in range(2)),
                      key=lambda r: r["decisions_per_sec"])
            hard = max((run_pass(storage, reps, hardened=True,
                                 tag=f"hard{i}")
                        for i in range(2)),
                       key=lambda r: r["decisions_per_sec"])
            ratio = (hard["decisions_per_sec"]
                     / max(raw["decisions_per_sec"], 1.0))
            out.update(hard)
            out["unhardened_decisions_per_sec"] = raw["decisions_per_sec"]
            out["hardening_ratio"] = round(ratio, 3)
            assert ratio >= 0.9, (
                f"hardened ingress throughput fell to {ratio:.2f}x of the "
                f"unhardened path (hardened "
                f"{hard['decisions_per_sec']:.0f}/s vs raw "
                f"{raw['decisions_per_sec']:.0f}/s) — the 0.9x gate "
                "failed")
            # v5 columnar vs v4 per-request frames, apples to apples:
            # both arms on a hardened server whose pipeline cap admits
            # the whole burst (no differential shedding — shed frames
            # do zero device work and would flatter the v4 arm), so
            # every burst is ONE micro-batch flush of PIPELINE real
            # decisions in both shapes.  v5 ships 1 frame + 1 bitmask
            # where v4 ships PIPELINE frames + PIPELINE responses.
            deep = {"max_pipeline": PIPELINE}
            v4 = max((run_pass(storage, reps, hardened=True,
                               tag=f"v4f{i}", protocol=4,
                               server_kwargs=deep)
                      for i in range(2)),
                     key=lambda r: r["decisions_per_sec"])
            v5 = max((run_pass(storage, reps, hardened=True,
                               tag=f"v5b{i}", block=True,
                               server_kwargs=deep, block_rows=PIPELINE)
                      for i in range(2)),
                     key=lambda r: r["decisions_per_sec"])
            ratio5 = (v5["decisions_per_sec"]
                      / max(v4["decisions_per_sec"], 1.0))
            out["v4_decisions_per_sec"] = v4["decisions_per_sec"]
            out["v5_block_decisions_per_sec"] = v5["decisions_per_sec"]
            out["columnar_ratio"] = round(ratio5, 3)
            # Deterministic wire accounting: frames per burst each way.
            out["v5_frames_per_burst"] = -(-PIPELINE // PIPELINE)
            out["v4_frames_per_burst"] = PIPELINE
            assert ratio5 >= 0.9, (
                f"v5 columnar ingress fell to {ratio5:.2f}x of the v4 "
                f"per-request path ({v5['decisions_per_sec']:.0f}/s vs "
                f"{v4['decisions_per_sec']:.0f}/s) — the 0.9x floor "
                "failed")
        else:
            out.update(run_pass(storage, reps, hardened=True, tag="main",
                                chaos=args.faults))
        print(json.dumps(out))
    finally:
        storage.close()


if __name__ == "__main__":
    main()
