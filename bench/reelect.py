"""Election re-seed sweep (the BENCH round refresher, ISSUE 18 c).

The measured elections — ``pallas.relay_fused_live`` vs the lowered
micro step, block-scatter vs dense one-hot, device-journal placement,
the staged micro-step combine — persist their verdicts on disk so production boots
skip the probe.  Verdicts go stale: a runtime upgrade, a new BLAS, or a
changed kernel can flip a winner, and a stale verdict silently pins the
loser.  This sweep:

1. snapshots then DELETES every persisted verdict (``pallas_elect_*``)
   in the compile cache directory (utils/compile_cache.py), so the next
   dispatch of each path re-measures;
2. re-runs ``bench/sharded_scaling.py``;
3. runs ``bench.py`` for a full round (its in-process dispatches
   re-elect every pallas path) and writes the
   refreshed round to ``BENCH_r06.json`` in the same shape as prior
   rounds (``{n, cmd, rc, tail, parsed}``) plus the refreshed election
   verdicts, the prior (pre-clear) verdicts for diffing, and the
   sharded-scaling points.

Run with cwd=repo root:  python bench/reelect.py
Flags: --skip-bench  (clear + sharded_scaling only; no BENCH_r06.json)
Env: BENCH_SCALE=small keeps the refresh cheap (CI).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import subprocess
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

ROUND = 6


def clear_verdicts() -> dict:
    """Snapshot + delete persisted election files; return the
    snapshot keyed by filename (the pre-clear verdicts, for diffing)."""
    from ratelimiter_tpu.utils.compile_cache import cache_dir

    prior: dict = {}
    removed = []
    for path in sorted(glob.glob(os.path.join(cache_dir(),
                                              "pallas_elect_*.json"))):
        name = os.path.basename(path)
        try:
            with open(path) as fh:
                prior[name] = json.load(fh)
        except Exception as exc:  # noqa: BLE001 — record, still clear
            prior[name] = {"unreadable": str(exc)}
        os.unlink(path)
        removed.append(path)
    return {"prior_verdicts": prior, "removed": removed}


def refresh_elections() -> dict:
    """Force-resolve every election that can measure on this platform.

    bench.py's in-process report only contains paths its own dispatches
    happened to probe — on CPU the pallas kernels are unsupported (no
    probe fires, by design), so the report would be empty there.  This
    resolves each electable path directly against the now-cleared disk
    cache: the pallas settle (micro / relay_fused, and the tile sweep's probe — a
    no-op off-TPU) and the device-journal placement (measures on every
    backend).  Runs in a child (``--refresh``): this parent stays off
    JAX, so each child in turn can hold the chip."""
    import jax

    from ratelimiter_tpu.ops import pallas as pallas_pkg
    from ratelimiter_tpu.ops.pallas import election
    from ratelimiter_tpu.replication import log as rlog
    from ratelimiter_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    election.reset_for_tests()       # drop in-process memos too
    pallas_pkg.settle_all()          # TPU: micro/relay_fused/sweep probe
    rlog.device_journal_elected()    # measures host-vs-device everywhere
    return {"platform": jax.default_backend(),
            "verdicts": election.report()}


def _run(cmd: list, timeout: int, cpu: bool = False) -> dict:
    """Run one child to its end and parse its last JSON line.  Children
    run one at a time and this process never imports JAX, so a child
    may hold the chip; ``cpu`` pins a virtual-mesh child at spawn."""
    env = dict(os.environ, JAX_PLATFORMS="cpu") if cpu else None
    proc = subprocess.run([sys.executable, *cmd], capture_output=True,
                          timeout=timeout, text=True, cwd=_REPO, env=env)
    out = {"rc": proc.returncode,
           "tail": (proc.stdout + proc.stderr)[-2000:]}
    if proc.returncode == 0 and proc.stdout.strip():
        try:
            out["parsed"] = json.loads(
                proc.stdout.strip().splitlines()[-1])
        except ValueError:
            pass
    return out


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--skip-bench", action="store_true",
                        help="clear verdicts + rerun sharded_scaling only "
                             "(no bench.py round, no BENCH_r06.json)")
    parser.add_argument("--bench-timeout", type=int, default=3600)
    parser.add_argument("--refresh", action="store_true",
                        help=argparse.SUPPRESS)  # the child mode
    args = parser.parse_args()
    if args.refresh:
        print(json.dumps(refresh_elections()))
        return

    t0 = time.time()
    cleared = clear_verdicts()
    print(f"cleared {len(cleared['removed'])} persisted verdict/rate "
          "file(s)", file=sys.stderr)
    refresh = _run([os.path.abspath(__file__), "--refresh"], timeout=900)
    if "parsed" not in refresh:
        raise SystemExit(f"election refresh failed: {refresh['tail']}")
    refreshed = refresh["parsed"]
    print(f"re-measured elections on {refreshed['platform']}: "
          f"{sorted(refreshed['verdicts'])}", file=sys.stderr)

    print("re-running sharded_scaling...", file=sys.stderr)
    sharded = _run([os.path.join(_REPO, "bench", "sharded_scaling.py")],
                   timeout=900, cpu=True)
    if args.skip_bench:
        print(json.dumps({"cleared": len(cleared["removed"]),
                          "elections": sorted(refreshed["verdicts"]),
                          "sharded_rc": sharded["rc"]}))
        return

    # Full round: bench.py re-elects every pallas path on first dispatch
    # (the files we just deleted force a fresh measurement) and writes
    # the refreshed verdicts into BENCH_DETAIL.json.
    print("running bench.py (fresh election round)...", file=sys.stderr)
    bench = _run([os.path.join(_REPO, "bench.py")],
                 timeout=args.bench_timeout)

    # Verdicts of record: the force-resolved set, overlaid with
    # anything bench.py's own dispatches probed (on TPU the bench
    # round's in-traffic measurements win over the synthetic probe).
    elections: dict = dict(refreshed["verdicts"])
    try:
        with open(os.path.join(_REPO, "BENCH_DETAIL.json")) as fh:
            bench_elections = json.load(fh).get("pallas", {}).get(
                "elections", {})
        if isinstance(bench_elections, dict):
            elections.update(bench_elections)
    except Exception as exc:  # noqa: BLE001 — round still recorded
        elections["bench_detail_error"] = str(exc)

    record = {
        "n": ROUND,
        "cmd": "python bench/reelect.py  # clears election caches, then "
               "python bench.py",
        "rc": bench["rc"],
        "tail": bench["tail"],
        "parsed": bench.get("parsed"),
        "elections": elections,
        "election_platform": refreshed["platform"],
        "prior_verdicts": cleared["prior_verdicts"],
        "verdict_files_cleared": [os.path.relpath(p, _REPO)
                                  if p.startswith(_REPO) else p
                                  for p in cleared["removed"]],
        "sharded_scaling": sharded.get("parsed",
                                       {"rc": sharded["rc"],
                                        "tail": sharded["tail"][-500:]}),
        "reelect_wall_s": round(time.time() - t0, 1),
    }
    out_path = os.path.join(_REPO, f"BENCH_r{ROUND:02d}.json")
    with open(out_path, "w") as fh:
        json.dump(record, fh, indent=2)
    print(json.dumps({"round": ROUND, "rc": bench["rc"],
                      "elections": list(elections)
                      if isinstance(elections, dict) else [],
                      "cleared": len(cleared["removed"]),
                      "wrote": os.path.basename(out_path)}))
    if bench["rc"] != 0:
        sys.exit(1)


if __name__ == "__main__":
    main()
