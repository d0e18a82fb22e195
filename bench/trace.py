"""Profiler traces: capture, and the reduction from trace to metrics.

A run with ``--trace 1`` records the whole measured window with
``jax.profiler`` and wraps it, and each step of the harness inside it, in
host spans named ``bench.*`` (``span``); the program opens its own
``repro.*`` host spans and device scopes.  ``load`` reads the
``.xplane.pb`` itself, with a small reader of the protobuf wire format
(``XSpace`` > ``XPlane`` > ``XLine`` > ``XEvent``, standard library only):
``jax.profiler.ProfileData`` does not expose an event's metadata, where an
XLA operation's name stack (its ``tf_op`` stat, such as
``jit(stage)/while/body/repro.aggregate/gather``) lives.

* device operations are the events of the ``XLA Ops`` line of each
  ``/device:TPU:<n>`` plane, at ``timestamp_ns + offset_ps`` of their
  line; a trace with no such plane (the CPU backend) has its operations
  on host threads, as events that carry an ``hlo_op`` stat, and those are
  taken instead;
* the window is the ``bench.window`` span;
* busy time is the union of the operations' intervals inside the window,
  averaged over the devices that ran any; idle share is one minus busy
  over the window;
* on a TPU an operation's event is named by its HLO instruction
  (``%sort.12 = s32[...] sort(...)``); its name is ``sort.12``, and its
  class that name without a trailing ``.<n>`` or ``-<n>`` (``sort``), a
  fusion's with its kind (``fusion:kCustom``, the gathers and scatters);
* an operation's scope is the OUTERMOST ``repro.*`` component of its
  ``tf_op`` (finalize's remap is ``repro.finalize``, Leiden's inner sweeps
  ``repro.refine``), ``""`` when it has none;
* operations nest (a ``while`` holds its body's operations), so time by
  class, by name and by scope is self time: an operation's duration less
  that of the operations directly inside it;
* host spans are the events named ``bench.*`` or ``repro.*`` on any other
  plane; ``span_s`` sums each ``repro.*`` name's seconds inside the window;
* an idle gap is named by the innermost span of either family around its
  middle: ``repro.ingest.canonicalize`` where the program held the host,
  a ``bench.*`` name where only the harness ran.

Scope and span names are matched as literal strings, so a share reads
None, never 0 or another scope's, where the trace holds no operation of
that scope: on the CPU backend, under a program that opens no such scope,
or after the program renamed it.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
import re
import shutil
from collections import defaultdict

WINDOW = "bench.window"
PREFIX = "repro."
HOST_PREFIXES = ("bench.", PREFIX)
OUTSIDE = "outside spans"
_LABEL = 120   # characters of an operation's HLO text kept as its label
_SUFFIX = re.compile(r"[.\-]\d+$")
_HLO = re.compile(r"%(\S+) = ")
_KIND = re.compile(r"kind=(k\w+)")


def span(name: str):
    """A host span in the profiler's trace (free when no trace runs)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


@contextlib.contextmanager
def capture(directory: str):
    """Trace everything inside the block into ``directory`` (emptied
    first); yields a list that holds the ``.xplane.pb`` path afterwards."""
    import jax

    shutil.rmtree(directory, ignore_errors=True)
    os.makedirs(directory)
    found: list = []
    jax.profiler.start_trace(directory)
    try:
        yield found
    finally:
        jax.profiler.stop_trace()
        found += sorted(glob.glob(os.path.join(
            directory, "plugins", "profile", "*", "*.xplane.pb")))


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
    """``(metadata id, offset_ps, duration_ps, undecoded stats)`` of one
    ``XEvent``."""
    mid = off = dur = 0
    stats = []
    for f, v in _fields(b, *span):
        if f == 1:
            mid = v
        elif f == 2:
            off = _signed(v)
        elif f == 3:
            dur = v
        elif f == 4:
            stats.append(v)
    return mid, off, dur, stats


# ------------------------------------------------------------ the trace


@dataclasses.dataclass
class Op:
    device: str
    name: str     # the operation's HLO text on a TPU
    start: float  # ns
    end: float    # ns
    scope: str = ""   # outermost repro.* component of its tf_op


@dataclasses.dataclass
class Trace:
    ops: list     # [Op]
    spans: list   # [(name, start_ns, end_ns)] of bench.* and repro.* spans


def scope_of(tf_op: str) -> str:
    """The outermost ``repro.*`` component of a name stack, or ``""``."""
    for part in tf_op.split("/"):
        if part.startswith(PREFIX):
            return part
    return ""


def _events(b: bytes, plane: dict, lines=None):
    """``(metadata id, start_ns, end_ns, undecoded stats)`` of the events
    of ``plane``'s lines (those named in ``lines``, where given)."""
    for lspan in plane["lines"]:
        lname, ts, events = _line(b, lspan)
        if lines is not None and lname not in lines:
            continue
        for ev in events:
            mid, off, dur, stats = _event(b, ev)
            s = ts + off / 1e3
            yield mid, s, s + dur / 1e3, stats


def _host_ops(b: bytes, plane: dict) -> list:
    """The operations the CPU backend ran on ``plane``'s threads: events
    that carry an ``hlo_op`` stat."""
    return [Op(plane["name"], plane["events"].get(mid, ("",))[0], s, e)
            for mid, s, e, stats in _events(b, plane)
            if any(_stat(b, st, plane["stats"])[0] == "hlo_op"
                   for st in stats)]


def load(path: str) -> Trace:
    """Device operations and ``bench.*``/``repro.*`` host spans of one
    trace file."""
    with open(path, "rb") as f:
        b = f.read()
    ops, spans, host = [], [], []
    for f, v in _fields(b, 0, len(b)):
        if f != 1:                 # XSpace.planes
            continue
        plane = _plane(b, *v)
        if not plane["name"].startswith("/device:TPU:"):
            host.append(plane)
            for mid, s, e, _ in _events(b, plane):
                name = plane["events"].get(mid, ("",))[0]
                if name.startswith(HOST_PREFIXES):
                    spans.append((name, s, e))
            continue
        tf_op_of: dict = {}
        for mid, (_, stats) in plane["events"].items():
            for st in stats:
                sname, value = _stat(b, st, plane["stats"])
                if sname == "tf_op" and value:
                    tf_op_of[mid] = value
        for mid, s, e, _ in _events(b, plane, ("XLA Ops",)):
            ops.append(Op(plane["name"], plane["events"].get(mid, ("",))[0],
                          s, e, scope_of(tf_op_of.get(mid, ""))))
    if not ops:
        ops = [o for plane in host for o in _host_ops(b, plane)]
    return Trace(ops=ops, spans=spans)


# ------------------------------------------------------------ reduction


def union(intervals) -> list:
    """Merged, sorted ``[(start, end)]`` of possibly overlapping
    intervals."""
    merged: list = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return [tuple(m) for m in merged]


def op_name(text: str) -> str:
    """``sort.12`` of ``%sort.12 = s32[8] sort(...)``; other names as
    they are."""
    m = _HLO.match(text)
    return m.group(1) if m else text


def op_class(text: str) -> str:
    cls = _SUFFIX.sub("", op_name(text))
    kind = _KIND.search(text) if cls == "fusion" else None
    return f"fusion:{kind.group(1)}" if kind else cls


def self_times(ops) -> list:
    """Per operation of one device, its duration less the durations of
    the operations directly inside it."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][0], -ops[i][1]))
    own = [e - s for s, e in ops]
    stack: list = []
    for i in order:
        s, e = ops[i]
        while stack and ops[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= min(e, ops[stack[-1]][1]) - s
        stack.append(i)
    return own


def window_of(trace: Trace):
    """``(start_ns, end_ns)`` of the ``bench.window`` span."""
    w = [(s, e) for n, s, e in trace.spans if n == WINDOW]
    if not w:
        raise ValueError(f"no {WINDOW} span in the trace")
    return w[0]


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float           # mean over devices with any operation
    devices: int
    by_class: dict          # class -> self seconds inside the window
    by_name: dict           # HLO text (cut) -> self seconds
    by_scope: dict          # outermost repro.* scope ("" unscoped) -> self s
    span_s: dict            # repro.* span name -> seconds inside the window
    gaps: list              # [(innermost span, seconds)] of idle gaps

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def class_share(self, cls: str) -> float | None:
        """Device time of ``cls`` over busy time; None when idle."""
        if self.busy_s <= 0:
            return None
        return self.by_class.get(cls, 0.0) / (self.busy_s * self.devices)

    def scope_share(self, name: str) -> float | None:
        """Self time of ``name``'s operations over busy time; None when the
        trace names no operation ``name``, or the device was idle."""
        if name not in self.by_scope or self.busy_s <= 0:
            return None
        return self.by_scope[name] / (self.busy_s * self.devices)


def summarize(trace: Trace) -> Summary:
    lo, hi = window_of(trace)
    per_dev = defaultdict(list)
    inside = defaultdict(list)
    for o in trace.ops:
        if o.end > lo and o.start < hi:
            per_dev[o.device].append((max(o.start, lo), min(o.end, hi)))
            inside[o.device].append(o)
    by_class = defaultdict(float)
    by_name = defaultdict(float)
    by_scope = defaultdict(float)
    for d, iv in per_dev.items():
        for o, t in zip(inside[d], self_times(iv)):
            by_class[op_class(o.name)] += t * 1e-9
            by_name[o.name[:_LABEL]] += t * 1e-9
            by_scope[o.scope] += t * 1e-9
    busy = {d: sum(e - s for s, e in union(iv)) for d, iv in per_dev.items()}
    n_dev = max(1, len(busy))
    span_s = defaultdict(float)
    for name, s, e in trace.spans:
        if name.startswith(PREFIX) and e > lo and s < hi:
            span_s[name] += (min(e, hi) - max(s, lo)) * 1e-9
    first = sorted(per_dev)[0] if per_dev else None
    gaps = idle_gaps(union(per_dev[first]) if first else [], lo, hi,
                     trace.spans)
    return Summary(window_s=(hi - lo) * 1e-9,
                   busy_s=sum(busy.values()) * 1e-9 / n_dev,
                   devices=n_dev, by_class=dict(by_class),
                   by_name=dict(by_name), by_scope=dict(by_scope),
                   span_s=dict(span_s), gaps=gaps)


def idle_gaps(busy, lo, hi, spans) -> list:
    """Idle time of one device inside the window, summed by the innermost
    span of either family (other than the window) around each gap's
    middle."""
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    inner = [(n, s, e) for n, s, e in spans if n != WINDOW]
    out = defaultdict(float)
    for s, e in zip(edges[0::2], edges[1::2]):
        if e <= s:
            continue
        mid = 0.5 * (s + e)
        around = [(se - ss, n) for n, ss, se in inner if ss <= mid <= se]
        out[min(around)[1] if around else OUTSIDE] += (e - s) * 1e-9
    return sorted(out.items(), key=lambda kv: -kv[1])


def breakdown(summary: Summary, top: int = 10) -> dict:
    """The result line's ``breakdown``: the device operations that took most
    time, and idle time by what the host was doing."""
    ops = sorted(summary.by_name.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in summary.gaps[:top]]}
