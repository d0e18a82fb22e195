"""The trace reduction, on a recorded CPU trace of a tiny jitted
sort-and-scatter program (three calls inside a ``bench.window`` span) and
on hand-made intervals."""
import os

import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "cpu_sort_scatter.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    return trace.load(DATA)


def test_recorded_trace_has_ops_window_and_spans(recorded):
    names = {trace.op_class(o.name) for o in recorded.ops}
    assert {"sort", "wrapped_scatter"} <= names
    assert trace.window_of(recorded)[1] > trace.window_of(recorded)[0]
    assert sum(n == "bench.solve" for n, _, _ in recorded.spans) == 3


def test_recorded_summary(recorded):
    s = trace.summarize(recorded)
    assert s.devices == 1
    assert 0 < s.busy_s < s.window_s
    assert 0 < s.idle_share < 1
    # the sort is most of this program's device time
    assert 0.5 < s.class_share("sort") <= 1
    # the host slept in bench.idle between the calls: most idle time
    assert s.gaps[0][0] == "bench.idle"
    assert sum(g for _, g in s.gaps) == pytest.approx(s.window_s - s.busy_s)


def test_breakdown_shape(recorded):
    b = trace.breakdown(trace.summarize(recorded))
    assert set(b) == {"device_ops", "idle_gaps"}
    for rows in b.values():
        assert 0 < len(rows) <= 10
        assert all(isinstance(n, str) and s >= 0 for n, s in rows)
    assert b["device_ops"][0][0] == "sort.0"


@pytest.mark.parametrize("intervals, merged", [
    ([], []),
    ([(0, 2), (1, 3)], [(0, 3)]),
    ([(5, 6), (0, 1), (1, 2)], [(0, 2), (5, 6)]),
    ([(0, 10), (2, 3)], [(0, 10)]),
])
def test_union(intervals, merged):
    assert trace.union(intervals) == merged


@pytest.mark.parametrize("name, cls", [
    ("sort.12", "sort"), ("fusion-3", "fusion"), ("copy", "copy"),
    ("select_bitcast_fusion", "select_bitcast_fusion"),
    ("%sort.5 = (s32[8]{0}, s32[8]{0}) sort(s32[8]{0} %a, s32[8]{0} %b)",
     "sort"),
    ("%fusion.634 = s32[64]{0} fusion(s32[8]{0} %c), kind=kCustom, "
     "calls=%fused_computation.1", "fusion:kCustom"),
    ("%while.3 = (s32[]) while((s32[]) %t), condition=%c, body=%b", "while")])
def test_op_class(name, cls):
    assert trace.op_class(name) == cls


def test_self_time_of_nested_operations():
    # a while holding two body operations, then one alone
    ops = [(0, 10), (1, 3), (4, 8), (12, 13)]
    assert trace.self_times(ops) == [4, 2, 4, 1]


def test_busy_is_union_clipped_to_window_and_averaged_over_devices():
    ns = 1e9
    t = trace.Trace(
        ops=[trace.Op("/device:TPU:0", "%while.1 = () while()", 0.5 * ns,
                      2.5 * ns),
             trace.Op("/device:TPU:0", "%sort.2 = s32[4] sort()", 1.5 * ns,
                      2 * ns),
             trace.Op("/device:TPU:0", "%fusion.2 = f32[4] fusion(), "
                      "kind=kCustom", 2.5 * ns, 3 * ns),
             trace.Op("/device:TPU:1", "%sort.1 = s32[4] sort()", 4 * ns,
                      6 * ns)],
        spans=[(trace.WINDOW, 1 * ns, 5 * ns),
               ("bench.flush", 3 * ns, 3.5 * ns)])
    s = trace.summarize(t)
    assert s.window_s == pytest.approx(4.0)
    assert s.busy_s == pytest.approx((2.0 + 1.0) / 2)
    assert s.by_class["sort"] == pytest.approx(0.5 + 1.0)
    assert s.by_class["while"] == pytest.approx(1.0)
    assert s.by_class["fusion:kCustom"] == pytest.approx(0.5)
    assert s.class_share("sort") == pytest.approx(1.5 / 3.0)
    # device 0 is idle 3..5 s; the middle, 4 s, lies outside bench.flush
    assert s.gaps == [("outside bench spans", pytest.approx(2.0))]


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        trace.window_of(trace.Trace(ops=[], spans=[]))
