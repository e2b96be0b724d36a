"""The control (the reference in int32) fails every cell's check, at a
size a test run holds; on the chip the same code runs at the cells'
own size (``control.py``)."""

import numpy as np
import pytest

from control import stream_control
from tiny import run_tiny, tiny_cell


@pytest.mark.parametrize("name", ["tb-burst-1m-zipf.stream",
                                  "sw-api-10m-uniform.stream"])
@pytest.mark.parametrize("seed", [11, 3_000_000_123])
def test_stream_control_is_not_correct(name, seed):
    checks = stream_control(tiny_cell(name), seed, window_calls=6)
    assert checks["mismatches"]["value"] > 0
    assert checks["key_count_errors"]["value"] > 0


def test_reference_passes_what_control_fails():
    """The same tiny stream run checks out against the i64 reference."""
    out = run_tiny(tiny_cell("sw-api-10m-uniform.stream"))
    assert out.correct and np.all([c["value"] == 0
                                   for c in out.checks.values()])
