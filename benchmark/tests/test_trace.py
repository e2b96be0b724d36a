"""Trace reduction on a small synthetic trace: busy union, idle share,
the top device ops and idle gaps named by host span."""

import pytest

from harness.trace import Line, reduce_lines, union

MS = 1e6  # ns


def _trace(extra_device=False):
    host = Line("/host:CPU", "python3", [
        ("bench.trace_window", 0.0, 100 * MS),
        ("bench.stream_call", 0.0, 60 * MS),
        ("bench.check", 70 * MS, 30 * MS),
        ("not_ours", 0.0, 100 * MS),
    ])
    dev = Line("/device:TPU:0", "XLA Ops", [
        ("relay", 10 * MS, 20 * MS),       # 10-30
        ("relay", 25 * MS, 10 * MS),       # 25-35, overlaps: union 10-35
        ("digest", 50 * MS, 5 * MS),       # 50-55
        ("outside", 120 * MS, 10 * MS),    # after the window: clipped away
    ])
    steps = Line("/device:TPU:0", "Steps", [("step", 0.0, 100 * MS)])
    lines = [host, dev, steps]
    if extra_device:
        lines.append(Line("/device:TPU:1", "XLA Ops",
                          [("relay", 0.0, 50 * MS)]))
    return lines


def test_op_names_drop_the_hlo_body():
    from harness.trace import op_name

    assert op_name("%fusion.1 = s32[1250048,4]{0,1:T(4,128)S(1)} fusion("
                   "s32[1250048,4]{0,1:T(4,128)S(1)} %custom-call.12), "
                   "kind=kCustom") == "fusion.1 s32[1250048,4]"
    assert op_name("%copy-done = (s32[8]{0}, u32[]) copy-done(%x)") == \
        "copy-done (s32[8]"
    assert op_name("relay") == "relay"


def test_union_merges_overlaps():
    assert union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]


def test_busy_idle_and_breakdown():
    red = reduce_lines(_trace())
    assert red.window_s == pytest.approx(0.1)
    assert red.busy_s == pytest.approx(0.030)        # 10-35 and 50-55
    assert red.idle_share == pytest.approx(0.7)
    assert red.n_devices == 1
    assert red.device_ops == [["relay", pytest.approx(0.030)],
                              ["digest", pytest.approx(0.005)]]
    # Gaps: 55-100 (45 ms; check 30 ms > call 5 ms), 35-50, 0-10.
    assert red.idle_gaps == [["bench.check", pytest.approx(0.045)],
                             ["bench.stream_call", pytest.approx(0.015)],
                             ["bench.stream_call", pytest.approx(0.010)]]


def test_busy_is_averaged_over_the_chips_that_ran():
    red = reduce_lines(_trace(extra_device=True))
    assert red.n_devices == 2
    assert red.busy_s == pytest.approx((0.030 + 0.050) / 2)


def test_a_trace_without_window_or_ops_is_refused():
    lines = _trace()
    with pytest.raises(ValueError, match="bench.trace_window"):
        reduce_lines([ln for ln in lines if ln.plane != "/host:CPU"])
    with pytest.raises(ValueError, match="no device op"):
        reduce_lines([ln for ln in lines if ln.name != "XLA Ops"])
