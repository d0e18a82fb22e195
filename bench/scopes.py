"""The program's own names in a profiler trace: device time by ``repro.*``
scope, host time in ``repro.*`` spans, idle gaps named by either family.

``bench/trace.py`` reads the trace through ``jax.profiler.ProfileData``,
which does not expose an event's metadata.  An XLA operation's name stack
lives there: the ``tf_op`` stat of its event metadata, such as
``jit(stage)/while/body/repro.aggregate/gather``, where each
``jax.named_scope`` the program opened is one component.  So this module
reads the ``.xplane.pb`` itself, with a small reader of the protobuf wire
format (``XSpace`` > ``XPlane`` > ``XLine`` > ``XEvent``, standard library
only).  Its rules follow ``bench/trace.py``:

* device operations are the events of the ``XLA Ops`` line of each
  ``/device:TPU:<n>`` plane, at ``timestamp_ns + offset_ps`` of their line;
* an operation's scope is the OUTERMOST ``repro.*`` component of its
  ``tf_op`` (finalize's remap is ``repro.finalize``, Leiden's inner sweeps
  ``repro.refine``), ``""`` when it has none;
* time by scope is self time inside the ``bench.window`` span
  (``trace.self_times``), over busy time: the union of the operations'
  intervals, averaged over the devices that ran any;
* host spans are the events named ``bench.*`` or ``repro.*`` on any other
  plane; ``span_s`` sums each ``repro.*`` name's seconds inside the window;
* an idle gap is named by the innermost span of either family around its
  middle: ``repro.ingest.canonicalize`` where the program held the host,
  a ``bench.*`` name where only the harness ran.

Scope and span names are matched as literal strings, so a share reads
None, never 0 or another scope's, where the trace holds no operation of
that scope: on the CPU backend (no TPU plane), under a program that opens
no such scope, or after the program renamed it.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import sys
from collections import defaultdict

from bench import trace

PREFIX = "repro."
HOST_PREFIXES = ("bench.", PREFIX)
OUTSIDE = "outside spans"


# ------------------------------------------------------------ wire format


def _varint(b: bytes, i: int):
    r = s = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << s
        if c < 0x80:
            return r, i
        s += 7


def _fields(b: bytes, i: int, end: int):
    """``(field number, value)`` of one message in ``b[i:end]``: an int for
    a varint, ``(start, end)`` for a length-delimited field."""
    while i < end:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            n, i = _varint(b, i)
            v, i = (i, i + n), i + n
        elif wire == 1:
            v, i = None, i + 8
        elif wire == 5:
            v, i = None, i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _text(b: bytes, span) -> str:
    return b[span[0]:span[1]].decode("utf-8", "replace")


def _plane(b: bytes, lo: int, hi: int) -> dict:
    """Name, lines (undecoded), event metadata ``{id: (name, stats)}`` and
    stat names ``{id: name}`` of one ``XPlane``."""
    out = {"name": "", "lines": [], "events": {}, "stats": {}}
    for f, v in _fields(b, lo, hi):
        if f == 2:
            out["name"] = _text(b, v)
        elif f == 3:
            out["lines"].append(v)
        elif f in (4, 5):          # map entry: key 1, value 2
            mid, val = 0, None
            for ef, ev in _fields(b, *v):
                if ef == 1:
                    mid = ev
                elif ef == 2:
                    val = ev
            if val is None:
                continue
            name, stats = "", []
            for mf, mv in _fields(b, *val):
                if mf == 2:
                    name = _text(b, mv)
                elif mf == 5 and f == 4:
                    stats.append(mv)
            if f == 4:
                out["events"][mid] = (name, stats)
            else:
                out["stats"][mid] = name
    return out


def _stat(b: bytes, span, stat_names: dict):
    """``(name, str value or None)`` of one ``XStat``."""
    mid, value = 0, None
    for f, v in _fields(b, *span):
        if f == 1:
            mid = v
        elif f == 5:
            value = _text(b, v)
        elif f == 7:               # a string kept once, as a stat's name
            value = stat_names.get(v)
    return stat_names.get(mid, ""), value


def _line(b: bytes, span):
    """Name, ``timestamp_ns`` and undecoded events of one ``XLine``."""
    name, ts, events = "", 0, []
    for f, v in _fields(b, *span):
        if f == 2:
            name = _text(b, v)
        elif f == 3:
            ts = _signed(v)
        elif f == 4:
            events.append(v)
    return name, ts, events


def _event(b: bytes, span):
    """``(metadata id, offset_ps, duration_ps)`` of one ``XEvent``."""
    mid = off = dur = 0
    for f, v in _fields(b, *span):
        if f == 1:
            mid = v
        elif f == 2:
            off = _signed(v)
        elif f == 3:
            dur = v
    return mid, off, dur


# ------------------------------------------------------------ the trace


@dataclasses.dataclass
class Op:
    device: str
    tf_op: str    # the operation's name stack; "" when the trace has none
    start: float  # ns
    end: float    # ns


@dataclasses.dataclass
class Trace:
    ops: list     # [Op] of the XLA Ops lines of /device:TPU:<n> planes
    spans: list   # [(name, start_ns, end_ns)] of bench.* and repro.* spans


def load(path: str) -> Trace:
    with open(path, "rb") as f:
        b = f.read()
    ops, spans = [], []
    for f, v in _fields(b, 0, len(b)):
        if f != 1:                 # XSpace.planes
            continue
        plane = _plane(b, *v)
        on_device = plane["name"].startswith("/device:TPU:")
        tf_op_of: dict = {}
        for mid, (_, stats) in plane["events"].items() if on_device else ():
            for st in stats:
                sname, value = _stat(b, st, plane["stats"])
                if sname == "tf_op" and value:
                    tf_op_of[mid] = value
        for lspan in plane["lines"]:
            lname, ts, events = _line(b, lspan)
            if on_device and lname != "XLA Ops":
                continue
            for ev in events:
                mid, off, dur = _event(b, ev)
                s = ts + off / 1e3
                e = s + dur / 1e3
                if on_device:
                    ops.append(Op(plane["name"], tf_op_of.get(mid, ""), s, e))
                else:
                    name = plane["events"].get(mid, ("", []))[0]
                    if name.startswith(HOST_PREFIXES):
                        spans.append((name, s, e))
    return Trace(ops=ops, spans=spans)


def scope_of(tf_op: str) -> str:
    """The outermost ``repro.*`` component of a name stack, or ``""``."""
    for part in tf_op.split("/"):
        if part.startswith(PREFIX):
            return part
    return ""


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float      # mean over devices with any operation
    devices: int
    by_scope: dict     # outermost repro.* scope ("" unscoped) -> self s
    span_s: dict       # repro.* span name -> seconds inside the window
    gaps: list         # [(innermost span, seconds)] of idle gaps

    def scope_share(self, name: str) -> float | None:
        """Self time of ``name``'s operations over busy time; None when the
        trace names no operation ``name``, or the device was idle."""
        if name not in self.by_scope or self.busy_s <= 0:
            return None
        return self.by_scope[name] / (self.busy_s * self.devices)


def summarize(t: Trace) -> Summary:
    lo, hi = trace.window_of(t)
    per_dev = defaultdict(list)
    stacks = defaultdict(list)
    for o in t.ops:
        if o.end > lo and o.start < hi:
            per_dev[o.device].append((max(o.start, lo), min(o.end, hi)))
            stacks[o.device].append(o.tf_op)
    by_scope = defaultdict(float)
    for d, iv in per_dev.items():
        for tf_op, own in zip(stacks[d], trace.self_times(iv)):
            by_scope[scope_of(tf_op)] += own * 1e-9
    busy = {d: sum(e - s for s, e in trace.union(iv))
            for d, iv in per_dev.items()}
    n_dev = max(1, len(busy))
    span_s = defaultdict(float)
    for name, s, e in t.spans:
        if name.startswith(PREFIX) and e > lo and s < hi:
            span_s[name] += (min(e, hi) - max(s, lo)) * 1e-9
    first = sorted(per_dev)[0] if per_dev else None
    gaps = idle_gaps(trace.union(per_dev[first]) if first else [], lo, hi,
                     t.spans)
    return Summary(window_s=(hi - lo) * 1e-9,
                   busy_s=sum(busy.values()) * 1e-9 / n_dev, devices=n_dev,
                   by_scope=dict(by_scope), span_s=dict(span_s), gaps=gaps)


def idle_gaps(busy, lo, hi, spans) -> list:
    """Idle time of one device inside the window, summed by the innermost
    span of either family (other than the window) around each gap's
    middle."""
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    inner = [(n, s, e) for n, s, e in spans if n != trace.WINDOW]
    out = defaultdict(float)
    for s, e in zip(edges[0::2], edges[1::2]):
        if e <= s:
            continue
        mid = 0.5 * (s + e)
        around = [(se - ss, n) for n, ss, se in inner if ss <= mid <= se]
        out[min(around)[1] if around else OUTSIDE] += (e - s) * 1e-9
    return sorted(out.items(), key=lambda kv: -kv[1])


# ------------------------------------------------------------ a run's trace

_CACHE: dict = {}


def trace_files(root: str) -> list:
    """The ``.xplane.pb`` files a traced run of the checkout at ``root``
    leaves (``run.py`` records into ``.bench_cache/trace``, emptied first)."""
    return sorted(glob.glob(os.path.join(
        root, ".bench_cache", "trace", "plugins", "profile", "*",
        "*.xplane.pb")))


def of_run(run, root: str) -> Summary | None:
    """The scope summary of ``run``'s traced window, or None when the run
    has no trace, or the trace found is not the run's (its window differs
    from the one ``bench/trace.py`` read).  Read once per trace file; the
    first read logs device time by scope and idle time by span to standard
    error."""
    if run is None or run.summary is None:
        return None
    files = trace_files(root)
    if len(files) != 1:
        return None
    key = (files[0], os.path.getmtime(files[0]))
    if key not in _CACHE:
        _CACHE.clear()
        s = summarize(load(files[0]))
        _CACHE[key] = s
        print(f"device time by scope (s): "
              f"{sorted(s.by_scope.items(), key=lambda kv: -kv[1])}; "
              f"repro.* spans in the window (s): "
              f"{sorted(s.span_s.items(), key=lambda kv: -kv[1])}; "
              f"idle time by span (s): {s.gaps[:10]}",
              file=sys.stderr, flush=True)
    s = _CACHE[key]
    if abs(s.window_s - run.summary.window_s) > 1e-6 * max(1.0, s.window_s):
        return None
    return s


def checkout_of(reader_file: str) -> str:
    """The checkout a reader ``bench/metrics/<name>.py`` belongs to."""
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(reader_file))))
