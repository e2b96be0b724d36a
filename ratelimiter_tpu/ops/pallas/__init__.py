"""Pallas TPU kernels (probe-gated, XLA fallbacks, decisions identical).

Every kernel here is gated twice: a one-time correctness PROBE (tiny
differential against the XLA truth — on a TPU backend a lowering
failure or mismatch raises ``PallasProbeError``; in interpret mode on
the CPU it means fallback) and a one-time measured ELECTION
(ops/pallas/election.py — a supported kernel that measures slower than
the XLA path it replaces does not serve).  ``settle_all()`` resolves
both eagerly at engine init; ``election_report()`` exposes the verdicts
for bench.py, chip_smoke.py and the perf-smoke consistency gate.
"""


class PallasProbeError(RuntimeError):
    """A Pallas kernel failed its probe, or was asked to run in
    interpret mode, on a TPU backend."""


def refuse_interpret_on_tpu(kernel: str, interpret: bool,
                            switch: str) -> None:
    """Interpret mode exists to exercise kernels on the CPU in tests; on
    a TPU it would serve every decision from the Pallas interpreter."""
    import jax

    if interpret and jax.default_backend() == "tpu":
        raise PallasProbeError(
            f"{switch}=1 would run {kernel} in interpret mode on a TPU "
            "backend; unset it")


def probe_failed(kernel: str, reason: str) -> bool:
    """Verdict of a failed probe.  On a TPU backend the failure raises:
    a kernel that does not lower or disagrees with the XLA path is a
    fault to fix, not a fallback to hide.  Elsewhere (interpret mode on
    the CPU) the kernel falls back and this returns False."""
    import jax

    if jax.default_backend() == "tpu":
        raise PallasProbeError(f"{kernel} probe failed on tpu: {reason}")
    return False


def settle_all() -> None:
    """Resolve every kernel's support probe (and election) eagerly.

    Engines call this at init, before any step kernel compiles, so no
    probe compiles nested inside another program's lowering and a probe
    failure on a TPU raises at init rather than mid-traffic.  Each
    module's settle() honors its own kill switch, and all no-op off-TPU
    (the interpret overrides still probe lazily by design — interpret
    lowering nests fine).
    """
    import jax

    if jax.default_backend() != "tpu":
        return
    from ratelimiter_tpu.ops.pallas import block_scatter
    from ratelimiter_tpu.ops.pallas import relay_step
    from ratelimiter_tpu.ops.pallas import solver

    block_scatter.settle()
    solver.settle()
    relay_step.settle()


def election_report() -> dict:
    """Per-path election verdicts + measurements resolved so far (see
    ops/pallas/election.py).  Paths that never probed (e.g. CPU runs)
    are simply absent."""
    from ratelimiter_tpu.ops.pallas import election

    return election.report()
