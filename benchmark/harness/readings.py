"""What the per-layer readers read: the window's decisions, the
program's stage timers as differences across the window, the trace's
reduction, the bytes the work needs.

Timers are the program's log2-bucket histograms (``metrics/registry.py``:
bucket ``i`` counts samples in ``(2^(i-1), 2^i]`` us), read here by
their count, total and buckets.
"""

from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass
class TimerDelta:
    count: int
    total_us: float
    buckets: list


def snapshot_timers(registry) -> dict:
    """``name -> (count, total_us, buckets)`` of every timer the
    program registered (duck-typed: anything with ``bucket_counts``)."""
    out = {}
    for name, meter in registry.meters().items():
        if hasattr(meter, "bucket_counts") and hasattr(meter, "total_us"):
            out[name] = (meter.count(), meter.total_us(),
                         list(meter.bucket_counts()))
    return out


def timer_deltas(before: dict, after: dict) -> dict:
    out = {}
    for name, (n1, t1, b1) in after.items():
        n0, t0, b0 = before.get(name, (0, 0.0, [0] * len(b1)))
        out[name] = TimerDelta(n1 - n0, t1 - t0,
                               [x - y for x, y in zip(b1, b0)])
    return out


@dataclasses.dataclass
class Readings:
    driver: str                 # the traffic's driver: "stream"
    algorithm: str
    decisions: int              # decisions completed in the window
    timers: dict                # name -> TimerDelta over the window
    peaks: dict
    trace: object = None        # harness.trace.Reduction (traced runs)
    work_bytes: Optional[float] = None  # bytes the traced calls need

    def timer(self, name: str) -> Optional[TimerDelta]:
        d = self.timers.get(name)
        return d if d is not None and d.count > 0 else None

    def per_decision_ns(self, *names: str) -> Optional[float]:
        """Summed timer seconds across the window per decision, in ns."""
        deltas = [self.timer(n) for n in names]
        if self.decisions <= 0 or all(d is None for d in deltas):
            return None
        total_us = sum(d.total_us for d in deltas if d is not None)
        return total_us * 1e3 / self.decisions
