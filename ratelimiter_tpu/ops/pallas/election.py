"""Measured per-path Pallas elections (VERDICT r5 #7, generalized r6->r7).

A supported kernel is not necessarily a WINNING kernel: r05's A/B (a remote-link run before PR 1)
put the Pallas micro-batch solver at x0.91 of the XLA path on the very
traffic it exists to serve, and which backend wins varies by device
generation and toolchain.  PR 3 gave the solver a one-time timed A/B
(`solver.py:_micro_election`); this module is that machinery extracted
so Pallas-capable paths elect the same way:

- ``micro``        — the micro-batch sandwich solver (ops/pallas/solver.py)
- ``relay_fused``  — the fused relay-step kernel (relay_step.py)

The tile sweep (block_scatter.py) has no election: it serves wherever
its rule on the shapes (``supported``) holds.

Each path registers a measure function returning ``{"pallas_s",
"xla_s", ...shape keys...}``; the verdict (Pallas serves iff
``pallas_s <= margin * xla_s``) is cached in-process and on disk per
(platform, device kind, path) next to the compile cache — so one
process pays the A/B and every later process reads the verdict.  ``report()`` returns every resolved
verdict with its measurements, which bench.py and bench/device_only.py
record into BENCH_DETAIL so no path can silently run a measured-slower
backend (bench/perf_smoke.py asserts record/verdict consistency in CI).

Overrides: ``RATELIMITER_PALLAS_ELECT=auto|on|off`` applies to every
path; ``RATELIMITER_PALLAS_ELECT_<PATH>`` (upper-cased path name) wins
over the global for that path.  ``on`` = always use Pallas when the
support probe passes (the pre-r6 behavior); ``off`` = never; ``auto`` =
measure.  Interpret mode skips the election (it exists to exercise the
kernels on CPU, not to win) — callers pass ``interpret=True`` and get
an elected-True verdict tagged ``source: interpret``.
"""

from __future__ import annotations

import json
import os
import time
from typing import Callable, Dict, Optional

_ELECT_ENV = "RATELIMITER_PALLAS_ELECT"
# Pallas keeps a path unless XLA clearly wins: the margin absorbs timer
# noise so a dead-even A/B doesn't flap between processes.
DEFAULT_MARGIN = 1.05

# path -> {"elected": bool, "source": str, ...measurements...}
_verdicts: Dict[str, Dict] = {}


def _cache_path(path_name: str) -> Optional[str]:
    from ratelimiter_tpu.utils.compile_cache import cache_dir

    base = cache_dir()
    try:
        import jax

        dev = jax.devices()[0]
        kind = getattr(dev, "device_kind", dev.platform)
    except Exception:  # noqa: BLE001
        return None
    safe = "".join(ch if ch.isalnum() else "_" for ch in kind)[:40]
    return os.path.join(
        base, f"pallas_elect_{dev.platform}_{safe}_{path_name}.json")


def _policy(path_name: str) -> str:
    per_path = os.environ.get(
        f"{_ELECT_ENV}_{path_name.upper()}", "").lower()
    if per_path:
        return per_path
    return os.environ.get(_ELECT_ENV, "auto").lower()


def measured_election(
    path_name: str,
    measure: Callable[[], Dict],
    *,
    margin: float = DEFAULT_MARGIN,
    interpret: bool = False,
) -> bool:
    """True when the Pallas implementation of ``path_name`` should serve
    on this device.  ``measure`` runs at most once per (device, path)
    across processes; off the TPU a measurement failure keeps Pallas
    (the support probe already proved it computes correctly — refusing
    to elect on a timing error would silently discard a working kernel).
    On a TPU backend it raises: a measurement that cannot run there is a
    device fault."""
    hit = _verdicts.get(path_name)
    if hit is not None:
        return bool(hit["elected"])
    try:
        return _resolve_verdict(path_name, measure, margin, interpret)
    finally:
        _note_verdict(path_name)


def _note_verdict(path_name: str) -> None:
    """Every freshly-resolved election verdict lands in the flight
    recorder — a losing kernel silently reverting to XLA is exactly the
    kind of transition an operator reconstructs timelines from."""
    v = _verdicts.get(path_name)
    if v is None:
        return
    try:
        from ratelimiter_tpu.observability import flight_recorder

        flight_recorder().record(
            "pallas.election", path=path_name,
            elected=bool(v.get("elected")), source=str(v.get("source")))
    except Exception:  # noqa: BLE001 — observability must not break elections
        pass


def _resolve_verdict(
    path_name: str,
    measure: Callable[[], Dict],
    margin: float,
    interpret: bool,
) -> bool:
    policy = _policy(path_name)
    if policy in ("on", "always", "1"):
        _verdicts[path_name] = {"elected": True, "source": "env_on"}
        return True
    if policy in ("off", "never", "0"):
        _verdicts[path_name] = {"elected": False, "source": "env_off"}
        return False
    if interpret:
        # Interpret mode exists to exercise the kernel; timing it against
        # compiled XLA on CPU would always reject it.
        _verdicts[path_name] = {"elected": True, "source": "interpret"}
        return True
    disk = _cache_path(path_name)
    if disk and os.path.exists(disk):
        try:
            with open(disk, "r", encoding="utf-8") as fh:
                data = json.load(fh)
            _verdicts[path_name] = dict(data, source="disk_cache")
            return bool(data["elected"])
        except Exception:  # noqa: BLE001 — corrupt cache: re-measure
            pass
    try:
        ab = dict(measure())
        elected = ab["pallas_s"] <= margin * ab["xla_s"]
    except Exception as exc:  # noqa: BLE001 — measurement failed: keep Pallas
        import jax

        if jax.default_backend() == "tpu":
            raise  # a device fault on the chip, not a timing error
        _verdicts[path_name] = {"elected": True, "source": "measure_error",
                                "error": str(exc)[:200]}
        return True
    rec = dict(ab, elected=bool(elected), margin=margin,
               measured_at_ms=int(time.time() * 1000))
    _verdicts[path_name] = dict(rec, source="measured")
    if disk:
        try:
            os.makedirs(os.path.dirname(disk), exist_ok=True)
            tmp = disk + ".tmp"
            with open(tmp, "w", encoding="utf-8") as fh:
                json.dump(rec, fh)
            os.replace(tmp, disk)
        except Exception:  # noqa: BLE001 — disk cache is best-effort
            pass
    return bool(elected)


def report() -> Dict[str, Dict]:
    """Every verdict this process has resolved (for BENCH_DETAIL and the
    perf-smoke consistency gate).  Copies, so callers can't poison the
    cache."""
    return {k: dict(v) for k, v in _verdicts.items()}


def reset_for_tests() -> None:
    """Drop the in-process verdict cache (tests flip env overrides)."""
    _verdicts.clear()
