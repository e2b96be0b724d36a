"""What a driver hands back to ``run.py``."""

from __future__ import annotations

import dataclasses
from typing import Optional

from harness.readings import Readings


@dataclasses.dataclass
class Outcome:
    correct: bool
    attempted: int
    failed: int
    end_to_end: dict            # metric name -> value (the driver's own)
    readings: Readings
    device: dict                # harness.device.device_record(...)
    checks: dict                # name -> {"value": v, "limit": l}
    info: dict                  # diagnostics printed beside the result
    breakdown: Optional[dict] = None
