"""``bench/trace.py``: the reduction from a profiler trace to busy time,
device time per operation and idle gaps, checked by hand."""
import pytest

from bench import trace
from bench.tests.conftest import ROOT


def test_union_and_clip():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]
    assert trace.clip([(0, 4), (6, 9), (10, 12)], 2, 8) == [(2, 4), (6, 8)]


def test_reduce_by_hand():
    # window 0..100 ns.  Device 0 runs a 10..30 and b 20..40 (overlapping),
    # a loop 58..72 that holds a 60..70, and c 95..120 (cut at 100): busy
    # 30 + 14 + 5 = 49 ns.  Device 1 runs a 0..15: 15 ns.  Mean 32 ns.
    spans = [("bench.window", 0, 100), ("bench.call", 0, 50),
             ("bench.pull", 38, 50), ("bench.between", 50, 90)]
    devices = [[("a", 10, 30), ("b", 20, 40), ("loop", 58, 72),
                ("a", 60, 70), ("c", 95, 120)],
               [("a", 0, 15)]]
    s = trace.reduce(spans, devices)
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_s == pytest.approx(32e-9)
    assert s.devices == 2
    # leaves only (the loop holds a): a (20 + 10 + 15) / 2, b 20 / 2, c 5 / 2
    assert s.device_ops == [["a", pytest.approx(22.5e-9)],
                            ["b", pytest.approx(10e-9)],
                            ["c", pytest.approx(2.5e-9)]]
    # device 0's gaps: 72..95 (mid 83.5, between), 40..58 (mid 49: call
    # and pull hold it, pull is shorter), 0..10 (mid 5, call)
    assert s.idle_gaps == [["bench.between", pytest.approx(23e-9)],
                           ["bench.pull", pytest.approx(18e-9)],
                           ["bench.call", pytest.approx(10e-9)]]
    assert s.breakdown() == {"device_ops": s.device_ops,
                             "idle_gaps": s.idle_gaps}


def test_short_name():
    assert trace.short_name("%fusion.5 = s32[4194304]{0:T(1024)} fusion("
                            "s32[4194304]{0:T(1024)} %p), kind=kLoop") \
        == "%fusion.5 s32[4194304]"
    assert trace.short_name("%while.37 = (pred[8]{0}, s32[]) while(...)") \
        == "%while.37"


def test_recorded_chip_trace():
    """``data/small.xplane.pb``, recorded on one v5e by ``record_trace.py``:
    three calls of one fusion over 4 MiB.  By hand from its events (ns):
    bench.window 47155908..114419584; the fusion ran 46348621..46376718
    (before the window: the device clock leads the host's by about
    0.8 ms), 68774680..68802783 and 90522986..90551086."""
    s = trace.summarize(str(ROOT / "bench" / "tests" / "data"
                            / "small.xplane.pb"))
    assert s.devices == 1
    assert s.window_s == pytest.approx((114419584 - 47155908) * 1e-9)
    busy = (68802783 - 68774680) + (90551086 - 90522986)
    assert s.busy_s == pytest.approx(busy * 1e-9)
    assert s.device_ops == [["%multiply_add_fusion f32[1024,1024]",
                             pytest.approx(busy * 1e-9)]]
    # the gaps, longest first: op 3..window end, op 2..op 3 and window
    # start..op 2, each with its midpoint in a bench.between span
    assert [g[0] for g in s.idle_gaps] == ["bench.between"] * 3
    assert [g[1] for g in s.idle_gaps] == pytest.approx(
        [(114419584 - 90551086) * 1e-9, (90522986 - 68802783) * 1e-9,
         (68774680 - 47155908) * 1e-9])


def test_reduce_refuses_a_trace_without_device_or_window():
    with pytest.raises(ValueError, match="no device operations"):
        trace.reduce([("bench.window", 0, 1)], [])
    with pytest.raises(ValueError, match="one bench.window"):
        trace.reduce([], [[("a", 0, 1)]])
