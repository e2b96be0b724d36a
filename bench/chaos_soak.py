"""Chaos soak gate: run seeded conductor schedules against the full
in-process fleet and assert the invariant catalog stays clean.

Fast gate (verify.sh):

    python bench/chaos_soak.py --seeds 3 --assert-invariants

Long soak (RUN_SLOW=1 verify.sh):

    python bench/chaos_soak.py --seeds 8 --steps 48 --soak \
        --assert-invariants

On a violation the failing schedule is minimized and written as a
replayable artifact; the gate prints the artifact path so the failure
can be re-run exactly:

    python -m ratelimiter_tpu.chaos.replay --artifact <path>
"""

import argparse
import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from ratelimiter_tpu.chaos.minimize import minimize  # noqa: E402
from ratelimiter_tpu.chaos.plan import FaultPlan  # noqa: E402
from ratelimiter_tpu.chaos.replay import dump_artifact  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seeds", type=int, default=3,
                    help="number of seeded schedules to run")
    ap.add_argument("--base-seed", type=int, default=0,
                    help="first seed (schedules use base..base+seeds-1)")
    ap.add_argument("--steps", type=int, default=24,
                    help="conductor steps per schedule")
    ap.add_argument("--fault-rate", type=float, default=0.5,
                    help="per-step fault probability for the generator")
    ap.add_argument("--edge", choices=["direct", "tcp"], default="direct",
                    help="edge upstream topology (tcp = real proxy wire)")
    ap.add_argument("--soak", action="store_true",
                    help="long-soak shape: larger steps floor, both "
                         "edge topologies alternate across seeds")
    ap.add_argument("--assert-invariants", action="store_true",
                    help="exit non-zero on any invariant violation")
    ap.add_argument("--artifact-dir", default="/tmp",
                    help="where failing schedules are written")
    args = ap.parse_args()

    from ratelimiter_tpu.chaos.harness import run_plan

    steps = max(args.steps, 48) if args.soak else args.steps
    failures = []
    t0 = time.time()
    for i in range(args.seeds):
        seed = args.base_seed + i
        edge = args.edge
        if args.soak and i % 2 == 1:
            edge = "tcp" if edge == "direct" else "direct"
        plan = FaultPlan.generate(seed, steps=steps,
                                  fault_rate=args.fault_rate,
                                  topology={"edge": edge})
        t1 = time.time()
        report = run_plan(plan)
        dt = time.time() - t1
        v = report.get("violation")
        status = (f"VIOLATION [{v['invariant']}] step {v['step']}"
                  if v else "ok")
        print(f"seed {seed:>3} edge={edge:<6} "
              f"actions={len(plan.actions):>3} "
              f"decisions={report['decisions']:>5} "
              f"promotions={sum(report['promotions'])} "
              f"zombies_fenced={report['zombies_fenced']} "
              f"{dt:6.1f}s  {status}")
        if v is None:
            continue
        res = minimize(plan, max_runs=24)
        art = os.path.join(args.artifact_dir,
                           f"chaos_failure_seed{seed}.json")
        dump_artifact(art, res["plan"], res["violation"] or v,
                      minimized=res["reproduced"],
                      original_actions=res["reduced_from"])
        print(f"  minimized {res['reduced_from']} -> "
              f"{len(res['plan'].actions)} action(s) in {res['runs']} "
              f"runs; artifact: {art}")
        print(f"  replay: python -m ratelimiter_tpu.chaos.replay "
              f"--artifact {art}")
        failures.append({"seed": seed, "violation": v, "artifact": art})

    total = time.time() - t0
    print(f"\n{args.seeds} schedule(s), {len(failures)} violation(s), "
          f"{total:.1f}s total")
    print(json.dumps({"schedules": args.seeds, "steps": steps,
                      "violations": failures}, default=str))
    if failures and args.assert_invariants:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
