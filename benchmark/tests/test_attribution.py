"""Idle attribution and per-step device time on small synthetic traces:
the innermost program span on the caller's thread takes each idle
nanosecond, other threads' spans are ignored, the groups add up to the
idle share, and op time is grouped by the module that contains it."""

import pytest

from harness.attribution import device_steps, idle_by_span, leaf_timeline
from harness.trace import Line, reduce_lines

MS = 1e6  # ns
P = "ratelimiter.stream."


def _trace():
    # Caller's thread: window 0-100, one call 0-90 with stages inside.
    caller = Line("/host:CPU", "python", [
        ("bench.trace_window", 0.0, 100 * MS),
        ("bench.stream_call", 0.0, 90 * MS),
        (P + "call", 2 * MS, 86 * MS),          # 2-88
        (P + "plan", 2 * MS, 3 * MS),           # 2-5
        (P + "assign", 5 * MS, 25 * MS),        # 5-30
        (P + "index", 6 * MS, 20 * MS),         # 6-26, inline walk
        (P + "elect", 30 * MS, 5 * MS),         # 30-35
        (P + "layout", 35 * MS, 5 * MS),        # 35-40
        (P + "enqueue", 40 * MS, 2 * MS),       # 40-42
        (P + "drain_wait", 50 * MS, 30 * MS),   # 50-80
    ])
    # A worker walks the whole time: never charged.
    worker = Line("/host:CPU", "python", [(P + "index", 0.0, 100 * MS)])
    dev = Line("/device:TPU:0", "XLA Ops", [
        ("%fusion.1 = s32[8]{0} fusion()", 42 * MS, 8 * MS),   # 42-50
        ("%fusion.2 = s32[8]{0} fusion()", 60 * MS, 10 * MS),  # 60-70
    ])
    mods = Line("/device:TPU:0", "XLA Modules", [
        ("jit_tb_relay_counts(12)", 41 * MS, 10 * MS),         # 41-51
        ("jit_tb_reset_p(3)", 59 * MS, 12 * MS),               # 59-71
    ])
    return [worker, caller, dev, mods]


def test_innermost_program_span_on_the_caller_takes_the_idle():
    a = idle_by_span(_trace())
    assert a.window_s == pytest.approx(0.1)
    assert a.idle_s == pytest.approx(0.082)   # busy 42-50 and 60-70
    expect = {
        "bench.stream_call": 0.002 + 0.002,   # 0-2, 88-90
        P + "plan": 0.003,
        P + "assign": 0.001 + 0.004,          # 5-6, 26-30
        P + "index": 0.020,                   # nested inside assign
        P + "elect": 0.005,
        P + "layout": 0.005,
        P + "enqueue": 0.002,
        P + "drain_wait": 0.010 + 0.010,      # 50-60, 70-80
        P + "call": 0.008,                    # 80-88; 42-50 is busy
        "bench.trace_window": 0.010,          # 90-100
    }
    assert a.idle_by_span == {n: pytest.approx(v) for n, v in expect.items()}
    assert a.program_spans == 8


def test_shares_add_up_to_the_idle_share():
    lines = _trace()
    a = idle_by_span(lines)
    sh = a.shares()
    assert sh["index"] == pytest.approx(100 * (0.005 + 0.020) / 0.1)
    assert sh["dispatch"] == pytest.approx(100 * 0.015 / 0.1)
    assert sh["drain"] == pytest.approx(100 * 0.020 / 0.1)
    assert sh["unexplained"] == pytest.approx(100 * 0.022 / 0.1)
    assert sum(sh.values()) == pytest.approx(
        100 * reduce_lines(lines).idle_share)


def test_a_trace_without_program_spans_charges_the_bench_spans():
    lines = [Line(ln.plane, ln.name,
                  [e for e in ln.events if not e[0].startswith(P)])
             for ln in _trace()]
    a = idle_by_span(lines)
    assert a.program_spans == 0
    assert set(a.idle_by_span) == {"bench.stream_call", "bench.trace_window"}
    assert a.shares()["unexplained"] == pytest.approx(82.0)


def test_leaf_timeline_covers_the_window_in_order():
    leaves = leaf_timeline(_trace()[1].events, 0.0, 100 * MS)
    assert leaves[0][0] == 0.0 and leaves[-1][1] == 100 * MS
    assert all(a[1] == b[0] for a, b in zip(leaves, leaves[1:]))
    assert [n for _, _, n in leaves[:4]] == [
        "bench.stream_call", P + "plan", P + "assign", P + "index"]


def test_device_time_is_grouped_by_the_containing_module():
    lines = _trace()
    assert device_steps(lines) == [["jit_tb_reset_p", pytest.approx(0.010)],
                                   ["jit_tb_relay_counts",
                                    pytest.approx(0.008)]]
    # An op outside every module is "none"; a second chip halves the
    # average.
    lines.append(Line("/device:TPU:1", "XLA Ops",
                      [("%copy = s32[8]{0} copy()", 10 * MS, 4 * MS)]))
    assert device_steps(lines) == [["jit_tb_reset_p", pytest.approx(0.005)],
                                   ["jit_tb_relay_counts",
                                    pytest.approx(0.004)],
                                   ["none", pytest.approx(0.002)]]


def test_a_trace_without_the_caller_or_ops_is_refused():
    lines = _trace()
    with pytest.raises(ValueError, match="bench.stream_call"):
        idle_by_span([Line(ln.plane, ln.name,
                           [e for e in ln.events
                            if e[0] != "bench.stream_call"])
                      for ln in lines])
    with pytest.raises(ValueError, match="no device op"):
        device_steps([ln for ln in lines if ln.name != "XLA Ops"])
