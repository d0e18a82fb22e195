"""The trace reduction (``bench/trace.py``): its wire reader on hand-encoded
messages and against ``jax.profiler.ProfileData``, its rules on hand-made
intervals, and its readings of two recorded traces: a CPU trace of a tiny
jitted sort-and-scatter program (three calls inside a ``bench.window``
span), which names no scope, and a v5e trace of Louvain on two R-MAT
scale-11 graphs inside a ``bench.window`` span
(``record_louvain_trace.py``)."""
import math
import os
import types

import pytest

from bench import spec, trace

DATA = os.path.join(os.path.dirname(__file__), "data")
V5E = os.path.join(DATA, "v5e_louvain_rmat11.xplane.pb")
CPU = os.path.join(DATA, "cpu_sort_scatter.xplane.pb")
READERS = ("local_move_share.solve", "aggregate_share.solve",
           "ingest.canonicalize_ms")


# ------------------------------------------------------------ wire format


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _f(field: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(field << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(field << 3 | 2) + _varint(len(value)) + value


def _map(field: int, key: int, value: bytes) -> bytes:
    return _f(field, _f(1, key) + _f(2, value))


def _xspace() -> bytes:
    """A device plane whose two operations carry their ``tf_op`` as a
    string and as a reference to a stat name, a third with none; a host
    plane with the window and two spans."""
    dev = (_f(2, "/device:TPU:0")
           + _map(5, 1, _f(1, 1) + _f(2, "tf_op"))
           + _map(5, 2, _f(1, 2) + _f(2, "jit(stage)/repro.aggregate/x:"))
           + _map(4, 10, _f(1, 10) + _f(2, "%gather.1 = ...")
                  + _f(5, _f(1, 1) + _f(5, "jit(stage)/while/body/"
                                           "repro.local_move/gather:")))
           + _map(4, 11, _f(1, 11) + _f(2, "%scatter.2 = ...")
                  + _f(5, _f(1, 1) + _f(7, 2)))
           + _map(4, 12, _f(1, 12) + _f(2, "%while.3 = ..."))
           + _f(3, _f(2, "Async XLA Ops") + _f(3, 1000)
                + _f(4, _f(1, 10) + _f(2, 0) + _f(3, 999_000)))
           + _f(3, _f(2, "XLA Ops") + _f(3, 1000)
                + _f(4, _f(1, 12) + _f(2, 0) + _f(3, 6_000_000))
                + _f(4, _f(1, 10) + _f(2, 1_000_000) + _f(3, 3_000_000))
                + _f(4, _f(1, 11) + _f(2, 4_000_000) + _f(3, 1_000_000))))
    host = (_f(2, "/host:CPU")
            + _map(4, 1, _f(1, 1) + _f(2, "bench.window"))
            + _map(4, 2, _f(1, 2) + _f(2, "repro.ingest.canonicalize"))
            + _map(4, 3, _f(1, 3) + _f(2, "jax.other"))
            + _f(3, _f(2, "main") + _f(3, 0)
                 + _f(4, _f(1, 1) + _f(2, 0) + _f(3, 10_000_000_000))
                 + _f(4, _f(1, 2) + _f(2, 7_500_000) + _f(3, 9 * 10**9))
                 + _f(4, _f(1, 3) + _f(2, 7_000_000) + _f(3, 1_000_000))))
    return _f(1, dev) + _f(1, host) + _f(4, "a-host")


@pytest.fixture(scope="module")
def encoded(tmp_path_factory):
    path = tmp_path_factory.mktemp("xspace") / "t.xplane.pb"
    path.write_bytes(_xspace())
    return trace.load(str(path))


def test_reader_decodes_operations_and_their_name_stacks(encoded):
    assert [(o.name, o.scope, o.start, o.end) for o in encoded.ops] == [
        ("%while.3 = ...", "", 1000.0, 7000.0),
        ("%gather.1 = ...", "repro.local_move", 2000.0, 5000.0),
        ("%scatter.2 = ...", "repro.aggregate", 5000.0, 6000.0)]
    assert encoded.spans == [("bench.window", 0.0, 1e7),
                             ("repro.ingest.canonicalize", 7500.0, 9007500.0)]


def test_summary_of_encoded_trace(encoded):
    s = trace.summarize(encoded)
    assert s.devices == 1
    assert s.busy_s == pytest.approx(6e-6)
    assert s.by_scope == pytest.approx({"": 2e-6, "repro.local_move": 3e-6,
                                        "repro.aggregate": 1e-6})
    assert s.by_class == pytest.approx({"while": 2e-6, "gather": 3e-6,
                                        "scatter": 1e-6})
    assert s.scope_share("repro.local_move") == pytest.approx(0.5)
    assert s.scope_share("repro.refine") is None
    assert s.span_s == pytest.approx({"repro.ingest.canonicalize": 9e-3})
    # idle 7 us..10 ms has its middle in the program's span, 0..1 us none
    assert s.gaps == [("repro.ingest.canonicalize", pytest.approx(9993e-6)),
                      (trace.OUTSIDE, pytest.approx(1e-6))]


# ------------------------------------------------------------ rules


@pytest.mark.parametrize("tf_op, scope", [
    ("jit(stage)/while/body/while/body/gather:", ""),
    ("jit(stage)/while/body/repro.local_move/while/body/gather:",
     "repro.local_move"),
    ("jit(stage)/repro.finalize/jit(remap_communities)/repro.aggregate/"
     "scatter:", "repro.finalize"),
    ("jit(stage)/while/body/cond/branch_0_fun/repro.refine/"
     "repro.local_move/scatter-add:", "repro.refine"),
    ("", ""),
])
def test_scope_is_the_outermost_program_scope(tf_op, scope):
    assert trace.scope_of(tf_op) == scope


def test_idle_gap_is_named_by_innermost_span_of_either_family():
    spans = [(trace.WINDOW, 0, 100), ("bench.ingest", 10, 60),
             ("repro.ingest", 12, 58), ("repro.ingest.canonicalize", 15, 50),
             ("bench.solve", 60, 100)]
    busy = [(55, 62), (90, 100)]
    # idle 0..55 has its middle, 27.5, in canonicalize (inside
    # repro.ingest, inside bench.ingest); idle 62..90 only in bench.solve
    assert trace.idle_gaps(busy, 0, 100, spans) == [
        ("repro.ingest.canonicalize", pytest.approx(55e-9)),
        ("bench.solve", pytest.approx(28e-9))]

# ------------------------------------------------------------ recorded


@pytest.fixture(scope="module")
def recorded():
    return trace.load(CPU)


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


@pytest.fixture(scope="module")
def v5e():
    return trace.summarize(trace.load(V5E))


def test_v5e_trace_is_small():
    assert os.path.getsize(V5E) < 1 << 20


@pytest.mark.parametrize("path", [V5E, CPU], ids=["v5e", "cpu"])
def test_operations_match_profile_data(path):
    """The wire reader finds the operations ``jax.profiler.ProfileData``
    finds, in the same order, by the same names, at the same times (to
    the nanosecond ProfileData floors them to)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    theirs = [(p.name, ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
              for p in pd.planes for line in p.lines for ev in line.events
              if (line.name == "XLA Ops" if p.name.startswith("/device:TPU:")
                  else any(k == "hlo_op" for k, _ in ev.stats))]
    mine = trace.load(path).ops
    assert len(mine) == len(theirs) > 0
    assert all(a.device == d and a.name == n and abs(a.start - s) < 2
               and abs(a.end - e) < 2 for a, (d, n, s, e) in zip(mine, theirs))


def test_v5e_scope_shares_sum_to_one(v5e):
    total = sum(v5e.by_scope.values()) / (v5e.busy_s * v5e.devices)
    assert total == pytest.approx(1.0, abs=1e-6)
    for name in ("repro.local_move", "repro.aggregate", "repro.modularity",
                 "repro.finalize"):
        assert 0 < v5e.scope_share(name) < 1, name
    assert v5e.by_scope.get("", 0.0) < 0.05 * v5e.busy_s


def test_v5e_idle_gap_under_a_program_span_is_named_by_it(v5e):
    assert any(n.startswith(trace.PREFIX) for n, _ in v5e.gaps)
    assert "repro.ingest.canonicalize" in dict(v5e.gaps)
    # the breakdown names it too: the program's span, not bench.ingest
    assert "repro.ingest.canonicalize" in dict(
        trace.breakdown(v5e)["idle_gaps"])


def _run(summary, solves=2):
    return types.SimpleNamespace(solves=[object()] * solves, summary=summary)


def test_readers_on_v5e_trace(v5e):
    got = {m: spec.reader(m)(_run(v5e)) for m in READERS}
    assert 0.5 < got["local_move_share.solve"] < 1
    assert 0 < got["aggregate_share.solve"] < 0.5
    assert (got["local_move_share.solve"] + got["aggregate_share.solve"]
            < 1)
    # two solves of 2,048-vertex graphs: well under a second of numpy
    assert 0 < got["ingest.canonicalize_ms"] < 1000


# What each reader gave on the recorded v5e trace before the two trace
# readers were folded into one: the scope readers then read the wire
# reader's times, the class and idle readers ProfileData's, which floors
# every start and duration to a whole nanosecond.
PARENT = {"local_move_share.solve": 0.7719155774510938,
          "aggregate_share.solve": 0.21076004327241263,
          "ingest.canonicalize_ms": 1.65145,
          "sort_share.solve": 0.008923872156779863,
          "idle_share.solve": 0.11399433895549427}


def _floored(t):
    """``t`` with ProfileData's times: start and duration floored to ns."""
    def op(o):
        s = math.floor(o.start)
        return trace.Op(o.device, o.name, s,
                        s + round((o.end - o.start) * 1e3) // 1000, o.scope)

    return trace.Trace(ops=[op(o) for o in t.ops], spans=t.spans)


@pytest.mark.parametrize("metric", sorted(PARENT))
def test_readers_read_the_parents_values_on_v5e_trace(metric, v5e):
    got = spec.reader(metric)(_run(v5e))
    floored = spec.reader(metric)(_run(trace.summarize(
        _floored(trace.load(V5E)))))
    if metric in READERS:
        assert got == pytest.approx(PARENT[metric], rel=1e-12, abs=0)
    else:
        # the same on the same times; the nanosecond floor moves it less
        # than a ten-thousandth
        assert floored == pytest.approx(PARENT[metric], rel=1e-12, abs=0)
        assert got == pytest.approx(PARENT[metric], rel=1e-4, abs=0)


@pytest.mark.parametrize("summary", ["cpu trace", "no trace"])
def test_scope_readers_read_none(summary):
    """No scope or span of the program on the CPU backend's trace, and
    nothing to read without a trace: never 0."""
    s = trace.summarize(trace.load(CPU)) if summary == "cpu trace" else None
    assert {m: spec.reader(m)(_run(s)) for m in READERS} == dict.fromkeys(
        READERS)


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
    assert s.gaps == [(trace.OUTSIDE, pytest.approx(2.0))]


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        trace.window_of(trace.Trace(ops=[], spans=[]))
