"""The reduction from device-operation intervals and host spans to busy
time, idle share and named gaps; and the reader on a small recorded trace."""

import os

import pytest

from benchmark.lib import trace as tr

RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "tiny_v5e.xplane.pb")


def test_busy_is_the_union_not_the_sum():
    ops = [(0.0, 1.0), (0.5, 1.5), (3.0, 4.0)]
    assert tr.merge(ops) == [(0.0, 1.5), (3.0, 4.0)]
    assert tr.busy(ops, 0.0, 5.0) == pytest.approx(2.5)
    assert tr.busy(ops, 1.0, 3.5) == pytest.approx(1.0)  # clipped
    assert tr.gaps(ops, 0.0, 5.0) == [(1.5, 3.0), (4.0, 5.0)]


def test_gap_goes_to_the_innermost_span_that_covers_it():
    spans = [("bench.fit", 0.0, 10.0), ("bench.fence", 1.4, 3.1),
             ("bench.callback", 1.0, 3.5)]
    assert tr.attribute((1.5, 3.0), spans) == "bench.fence"
    assert tr.attribute((4.0, 5.0), spans) == "bench.fit"
    assert tr.attribute((11.0, 12.0), spans) == "unattributed"


def test_reduce_gives_idle_share_top_ops_and_collective_time():
    dev0 = [("fusion.1", 0.0, 1.0), ("all-reduce.3", 1.0, 1.5),
            ("fusion.1", 2.0, 3.0)]
    dev1 = [("fusion.1", 0.0, 1.0), ("fusion.1", 2.0, 4.0)]
    spans = [("bench.traced_slice", 0.0, 4.0), ("bench.fit", 1.4, 2.1)]
    r = tr.reduce({0: dev0, 1: dev1}, spans, "bench.traced_slice")
    assert r["window_s"] == pytest.approx(4.0)
    assert r["busy_s_device0"] == pytest.approx(2.5)
    assert r["busy_s"] == pytest.approx((2.5 + 3.0) / 2)
    assert r["idle_share_device0"] == pytest.approx(1 - 2.5 / 4.0)
    assert r["collective_s_device0"] == pytest.approx(0.5)
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(2.0)]
    assert r["idle_gaps"][0] == ["unattributed", pytest.approx(1.0)]
    assert r["idle_gaps"][1] == ["bench.fit", pytest.approx(0.5)]


@pytest.mark.parametrize("spans", [
    [("bench.traced_slice", 0.0, 4.0)],       # another clock altogether
    [("bench.traced_slice", 100.5, 103.0)],   # a quarter of the work outside
    [("bench.fit", 100.0, 103.0)],            # the slice was never marked
])
def test_reduce_refuses_a_span_that_does_not_hold_the_device_operations(spans):
    dev0 = [("fusion.1", 100.0, 101.0), ("fusion.2", 102.0, 103.0)]
    with pytest.raises(tr.ClocksDisagree):
        tr.reduce({0: dev0}, spans, "bench.traced_slice")


def test_reduce_takes_a_clock_offset_of_a_thousandth_of_the_slice():
    dev0 = [("fusion.1", 99.999, 101.0), ("fusion.2", 102.0, 103.0)]
    r = tr.reduce({0: dev0}, [("bench.traced_slice", 100.0, 103.5)],
                  "bench.traced_slice")
    assert r["window_s"] == pytest.approx(3.5)
    assert r["busy_s"] == pytest.approx(2.0)


def test_no_device_operation_is_nothing_to_read():
    assert tr.reduce({}, [], "bench.traced_slice") is None
    assert tr.reduce({0: []}, [], "bench.traced_slice") is None


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace beside the test")
def test_recorded_v5e_trace_reduces():
    ops, spans, layout = tr.load(RECORDED)
    assert 0 in ops and ops[0], layout
    assert any(name == "bench.traced_slice" for name, _, _ in spans)
    # three 2 us programs, each traced 1.2 ms BEFORE the host span that
    # dispatched it: the offset of the two clocks, which is most of so short
    # a slice and a thousandth of a cell's
    with pytest.raises(tr.ClocksDisagree):
        tr.reduce(ops, spans, "bench.traced_slice")
    r = tr.reduce(ops, spans, "bench.traced_slice", covered=0.3)
    assert 0.0 < r["busy_s"] <= r["window_s"]
    assert 0.0 <= r["idle_share_device0"] < 1.0
    assert r["device_ops"] and r["idle_gaps"]
    assert all(g[0].startswith("bench.") or g[0] == "unattributed"
               for g in r["idle_gaps"])
