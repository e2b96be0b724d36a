"""The benchmark's own arithmetic: timer differences per decision, the
roofline byte count, the peaks table."""

import json
import os

import pytest

from harness import spec
from harness.readings import Readings, timer_deltas
from harness.work import roofline_share, stream_call_bytes


def _readings(timers, decisions=1000):
    return Readings(driver="stream", algorithm="token_bucket",
                    decisions=decisions, timers=timers,
                    peaks={})


def test_timer_difference_per_decision():
    before = {"ratelimiter.stream.index": (4, 100.0, [0, 4] + [0] * 62),
              "ratelimiter.stream.layout": (4, 50.0, [0] * 64)}
    after = {"ratelimiter.stream.index": (10, 2100.0, [0, 4, 6] + [0] * 61),
             "ratelimiter.stream.layout": (10, 150.0, [0] * 64),
             "ratelimiter.stream.fetch": (3, 30.0, [3] + [0] * 63)}
    d = timer_deltas(before, after)
    assert d["ratelimiter.stream.index"].count == 6
    assert d["ratelimiter.stream.index"].total_us == 2000.0
    assert d["ratelimiter.stream.index"].buckets[:3] == [0, 0, 6]
    r = _readings(d, decisions=500)
    # 2000 us over 500 decisions = 4 us = 4000 ns per decision.
    assert r.per_decision_ns("ratelimiter.stream.index") == 4000.0
    assert r.per_decision_ns("ratelimiter.stream.index",
                             "ratelimiter.stream.layout") == 4200.0
    assert r.per_decision_ns("ratelimiter.stream.route") is None
    assert _readings(d, decisions=0).per_decision_ns(
        "ratelimiter.stream.index") is None


def test_roofline_bytes_count_the_work():
    # 1000 lanes, 100 distinct keys of token-bucket state (2 x i64):
    # 100 * 2 * 16 + 1000 * (4 + 1/8).
    assert stream_call_bytes("token_bucket", 1000, 100) == 3200 + 4125
    assert stream_call_bytes("sliding_window", 8, 8) == 8 * 80 + 8 * 4.125
    # 819 bytes in 1 ns of busy time at 819 GB/s: the whole roofline.
    assert roofline_share(819.0, 1e-9, 819e9) == pytest.approx(100.0)


def test_peaks_lookup():
    assert spec.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(spec.SpecError, match="no peaks"):
        spec.peaks_for("TPU v9 imaginary")


def test_every_metric_has_a_reader_and_every_cell_its_files():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for m in bench["per_layer"]:
        assert callable(spec.load_reader(m["name"]))
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.per_layer and cell.end_to_end
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
