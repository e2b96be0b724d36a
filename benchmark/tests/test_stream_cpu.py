"""The stream driver end to end on the CPU at a tiny size: the window's
decisions check out against the reference."""

import pytest

from tiny import run_tiny, tiny_cell


@pytest.mark.parametrize("name", ["tb-burst-1m-zipf.stream",
                                  "sw-api-10m-uniform.stream"])
def test_stream_cell_is_correct(name):
    out = run_tiny(tiny_cell(name))
    assert out.correct, out.checks
    assert out.info["decisions_compared"] > 0
    assert out.end_to_end["stream_decisions_per_s"] > 0
    assert out.readings.per_decision_ns("ratelimiter.stream.index") or \
        out.readings.per_decision_ns("ratelimiter.stream.fetch")


def test_stream_traced_run_reads_its_layers(monkeypatch):
    """The traced path on the CPU: XLA's CPU worker threads stand in for
    the chip's op line, so the reduction and every stream reader run."""
    import re

    from harness import trace
    from run import result_line

    monkeypatch.setattr(trace, "DEVICE_PLANE", re.compile(r"^/host:CPU$"))
    monkeypatch.setattr(trace, "OPS_LINE", re.compile(r"^tf_XLA"))
    cell = tiny_cell("tb-burst-1m-zipf.stream")
    out = run_tiny(cell, seconds=4.0, trace=True)
    assert out.correct, out.checks
    line = result_line(cell, out, True, {"hbm_bytes_per_s": 819e9})
    assert set(line["metrics"]) == {m["name"] for m in cell.per_layer}
    assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
    assert line["breakdown"]["device_ops"]
    assert list(line)[-1] == "checks"
