#!/usr/bin/env python3
"""Record the small TPU trace that ``test_bench_trace.py`` reads.

    python3 bench/tests/record_louvain_trace.py <out.xplane.pb> [scale]

On the chip it is started on: two R-MAT graphs
(``bench/generators/rmat.rmat_edges``, default scale 11, edge factor 3)
are solved once each to compile, then solved again through the harness's
own loop (``loops.solve_once`` with ``entries/louvain.py``: host edge list,
``from_numpy_edges``, default ``louvain()``, labels on the host), inside a
``bench.window`` span, under the profiler.  The recorded trace is cut to
what ``bench/trace.py`` reads (``trim``), so
the file stays under 1 MB: each ``/device:TPU:<n>`` plane's ``XLA Ops``
line, with its events' times and its operations' names and ``tf_op``, and
the ``/host:CPU`` lines that hold the spans.
"""
from __future__ import annotations

import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
os.environ.setdefault("TPU_LOG_DIR", "disabled")
if sys.path and os.path.abspath(sys.path[0]) == HERE:
    sys.path.pop(0)
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import graphs, loops, spec, trace  # noqa: E402

KEEP = ("/device:TPU:", "/host:CPU")


def _key(field: int, wire: int) -> bytes:
    return _uvarint(field << 3 | wire)


def _uvarint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _raw(b: bytes, lo: int, hi: int):
    """``(field, value, bytes of the whole field)`` of one message."""
    i = lo
    while i < hi:
        start = i
        key, i = trace._varint(b, i)
        f, wire = key >> 3, key & 7
        if wire == 0:
            v, i = trace._varint(b, i)
        elif wire == 2:
            n, i = trace._varint(b, i)
            v, i = (i, i + n), i + n
        else:
            v, i = None, i + (8 if wire == 1 else 4)
        yield f, v, b[start:i]


def _sub(field: int, body: bytes) -> bytes:
    return _key(field, 2) + _uvarint(len(body)) + body


def _device_plane(b: bytes, lo: int, hi: int) -> bytes:
    """A device plane with its ``XLA Ops`` line alone, its events without
    stats, and its event metadata down to id, name and ``tf_op``."""
    stat_names = trace._plane(b, lo, hi)["stats"]
    out = bytearray()
    for f, v, raw in _raw(b, lo, hi):
        if f == 3:
            if trace._line(b, v)[0] != "XLA Ops":
                continue
            line = bytearray()
            for lf, lv, lraw in _raw(b, *v):
                if lf == 4:
                    lraw = _sub(4, b"".join(
                        r for ef, _, r in _raw(b, *lv) if ef != 4))
                line += lraw
            raw = _sub(3, bytes(line))
        elif f == 4:
            entry = bytearray()
            for ef, ev, eraw in _raw(b, *v):
                if ef == 2:
                    eraw = _sub(2, b"".join(
                        r for mf, mv, r in _raw(b, *ev)
                        if mf in (1, 2) or (mf == 5 and trace._stat(
                            b, mv, stat_names)[0] == "tf_op")))
                entry += eraw
            raw = _sub(4, bytes(entry))
        out += raw
    return _sub(1, bytes(out))


def trim(data: bytes) -> bytes:
    """The ``XSpace`` ``data`` cut to what ``bench/trace.py`` reads: the
    planes named in ``KEEP``; of a device plane, what ``_device_plane``
    keeps; of the host plane, the lines that hold a ``bench.*`` or
    ``repro.*`` span."""
    out = bytearray()
    for f, v, raw in _raw(data, 0, len(data)):
        if f == 1:
            plane = trace._plane(data, *v)
            if not plane["name"].startswith(KEEP):
                continue
            if plane["name"].startswith("/device:"):
                raw = _device_plane(data, *v)
            else:
                names = {m: n for m, (n, _) in plane["events"].items()}
                raw = _sub(1, b"".join(
                    r for pf, pv, r in _raw(data, *v)
                    if pf != 3 or any(
                        names.get(trace._event(data, e)[0], "").startswith(
                            trace.HOST_PREFIXES)
                        for e in trace._line(data, pv)[2])))
        out += raw
    return bytes(out)


def main(out: str, scale: int = 11) -> None:
    rmat_edges = spec.module("generators", "rmat").rmat_edges
    gs = []
    for k in range(2):
        lo, hi = rmat_edges(scale, 3, 0.57, 0.19, 0.19,
                            graphs.stream(0, 1, k))
        gs.append((lo, hi, 1 << scale))
    entry = spec.entry({"algorithm": "louvain"})
    for k, g in enumerate(gs):
        loops.solve_once(entry, k, *g)
    tmp = out + ".d"
    with trace.capture(tmp) as found:
        with trace.span(trace.WINDOW):
            for k, g in enumerate(gs):
                loops.solve_once(entry, k, *g)
    with open(found[0], "rb") as f:
        data = trim(f.read())
    with open(out, "wb") as f:
        f.write(data)
    shutil.rmtree(tmp, ignore_errors=True)
    s = trace.summarize(trace.load(out))
    print(f"{out}: {len(data)} bytes; window {s.window_s!r} s, busy "
          f"{s.busy_s!r} s; by scope {s.by_scope}; spans {s.span_s}; "
          f"gaps {s.gaps}")


if __name__ == "__main__":
    main(sys.argv[1], *(int(a) for a in sys.argv[2:3]))
