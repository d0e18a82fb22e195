"""The program's scopes and spans in a trace (``bench/scopes.py``): its wire
reader on hand-encoded messages, its rules on hand-made intervals, and its
readings of a recorded v5e trace (Louvain on two R-MAT scale-11 graphs
inside a ``bench.window`` span, ``record_louvain_trace.py``) and of the
recorded CPU trace, which names no scope."""
import os
import types

import pytest

from bench import scopes, spec, trace

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
    return scopes.load(str(path))


def test_reader_decodes_operations_and_their_name_stacks(encoded):
    assert [(o.tf_op, o.start, o.end) for o in encoded.ops] == [
        ("", 1000.0, 7000.0),
        ("jit(stage)/while/body/repro.local_move/gather:", 2000.0, 5000.0),
        ("jit(stage)/repro.aggregate/x:", 5000.0, 6000.0)]
    assert encoded.spans == [("bench.window", 0.0, 1e7),
                             ("repro.ingest.canonicalize", 7500.0, 9007500.0)]


def test_summary_of_encoded_trace(encoded):
    s = scopes.summarize(encoded)
    assert s.devices == 1
    assert s.busy_s == pytest.approx(6e-6)
    assert s.by_scope == pytest.approx({"": 2e-6, "repro.local_move": 3e-6,
                                        "repro.aggregate": 1e-6})
    assert s.scope_share("repro.local_move") == pytest.approx(0.5)
    assert s.scope_share("repro.refine") is None
    assert s.span_s == pytest.approx({"repro.ingest.canonicalize": 9e-3})
    # idle 7 us..10 ms has its middle in the program's span, 0..1 us none
    assert s.gaps == [("repro.ingest.canonicalize", pytest.approx(9993e-6)),
                      (scopes.OUTSIDE, pytest.approx(1e-6))]


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
    assert scopes.scope_of(tf_op) == scope


def test_idle_gap_is_named_by_innermost_span_of_either_family():
    spans = [(trace.WINDOW, 0, 100), ("bench.ingest", 10, 60),
             ("repro.ingest", 12, 58), ("repro.ingest.canonicalize", 15, 50),
             ("bench.solve", 60, 100)]
    busy = [(55, 62), (90, 100)]
    # idle 0..55 has its middle, 27.5, in canonicalize (inside
    # repro.ingest, inside bench.ingest); idle 62..90 only in bench.solve
    assert scopes.idle_gaps(busy, 0, 100, spans) == [
        ("repro.ingest.canonicalize", pytest.approx(55e-9)),
        ("bench.solve", pytest.approx(28e-9))]


# ------------------------------------------------------------ recorded


@pytest.fixture(scope="module")
def v5e():
    return scopes.summarize(scopes.load(V5E))


def test_v5e_trace_is_small():
    assert os.path.getsize(V5E) < 1 << 20


def test_v5e_operations_match_trace_reader():
    """The wire reader finds the operations ``bench/trace.py`` finds, in the
    same order, at the same times (to the nanosecond ProfileData rounds)."""
    mine, theirs = scopes.load(V5E).ops, trace.load(V5E).ops
    assert len(mine) == len(theirs) > 0
    assert all(a.device == b.device and abs(a.start - b.start) < 2
               and abs(a.end - b.end) < 2 for a, b in zip(mine, theirs))


def test_v5e_scope_shares_sum_to_one(v5e):
    total = sum(v5e.by_scope.values()) / (v5e.busy_s * v5e.devices)
    assert total == pytest.approx(1.0, abs=1e-6)
    for name in ("repro.local_move", "repro.aggregate", "repro.modularity",
                 "repro.finalize"):
        assert 0 < v5e.scope_share(name) < 1, name
    assert v5e.by_scope.get("", 0.0) < 0.05 * v5e.busy_s


def test_v5e_idle_gap_under_a_program_span_is_named_by_it(v5e):
    assert any(n.startswith(scopes.PREFIX) for n, _ in v5e.gaps)
    assert "repro.ingest.canonicalize" in dict(v5e.gaps)


def _run(path, monkeypatch, solves=2):
    monkeypatch.setattr(scopes, "trace_files", lambda root: [path])
    return types.SimpleNamespace(
        solves=[object()] * solves,
        summary=trace.summarize(trace.load(path)))


def test_readers_on_v5e_trace(monkeypatch):
    run = _run(V5E, monkeypatch)
    got = {m: spec.reader(m)(run) for m in READERS}
    assert 0.5 < got["local_move_share.solve"] < 1
    assert 0 < got["aggregate_share.solve"] < 0.5
    assert (got["local_move_share.solve"] + got["aggregate_share.solve"]
            < 1)
    # two solves of 2,048-vertex graphs: well under a second of numpy
    assert 0 < got["ingest.canonicalize_ms"] < 1000


def test_readers_read_none_on_cpu_trace(monkeypatch):
    run = _run(CPU, monkeypatch)
    assert {m: spec.reader(m)(run) for m in READERS} == dict.fromkeys(
        READERS)


def test_readers_read_none_on_another_runs_trace(monkeypatch):
    run = _run(V5E, monkeypatch)
    run.summary.window_s += 1.0
    assert {m: spec.reader(m)(run) for m in READERS} == dict.fromkeys(
        READERS)


def test_readers_read_none_without_a_trace(monkeypatch):
    monkeypatch.setattr(scopes, "trace_files", lambda root: [])
    run = types.SimpleNamespace(solves=[object()], summary=None)
    assert {m: spec.reader(m)(run) for m in READERS} == dict.fromkeys(
        READERS)
    assert {m: spec.reader(m)(None) for m in READERS} == dict.fromkeys(
        READERS)
