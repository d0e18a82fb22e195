"""Profiler traces: capture, and the reduction from trace to metrics.

A run with ``--trace 1`` records the whole measured window with
``jax.profiler`` and wraps it, and each step of the harness inside it, in
host spans named ``bench.*`` (``span``).  The reduction reads the
``.xplane.pb`` with ``jax.profiler.ProfileData`` alone:

* device operations are the events of the ``XLA Ops`` line of each
  ``/device:TPU:<n>`` plane; a trace with no such plane (the CPU backend)
  has its operations on host threads, as events that carry an ``hlo_op``
  stat, and those are taken instead;
* the window is the ``bench.window`` span;
* busy time is the union of the operations' intervals inside the window,
  averaged over the devices that ran any; idle share is one minus busy
  over the window;
* on a TPU an operation's event is named by its HLO instruction
  (``%sort.12 = s32[...] sort(...)``); its name is ``sort.12``, and its
  class that name without a trailing ``.<n>`` or ``-<n>`` (``sort``), a
  fusion's with its kind (``fusion:kCustom``, the gathers and scatters);
* operations nest (a ``while`` holds its body's operations), so time by
  class and by name is self time: an operation's duration less that of
  the operations directly inside it;
* an idle gap is named by the innermost ``bench.*`` span around its
  middle: what the harness, and so the host, was doing.
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


@dataclasses.dataclass
class Op:
    device: str
    name: str
    start: float  # ns
    end: float    # ns


@dataclasses.dataclass
class Trace:
    ops: list     # [Op]
    spans: list   # [(name, start_ns, end_ns)] of bench.* host spans


def load(path: str) -> Trace:
    """Device operations and ``bench.*`` host spans of one trace file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, host_ops, spans = [], [], []
    for plane in pd.planes:
        on_device = plane.name.startswith("/device:TPU:")
        for line in plane.lines:
            for ev in line.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if on_device:
                    if line.name == "XLA Ops":
                        ops.append(Op(plane.name, ev.name, s, e))
                elif ev.name.startswith("bench."):
                    spans.append((ev.name, s, e))
                elif any(k == "hlo_op" for k, _ in ev.stats):
                    host_ops.append(Op(plane.name, ev.name, s, e))
    return Trace(ops=ops or host_ops, spans=spans)


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


def _clipped(ops, lo, hi):
    return [(max(o.start, lo), min(o.end, hi)) for o in ops
            if o.end > lo and o.start < hi]


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float           # mean over devices with any operation
    devices: int
    by_class: dict          # class -> self seconds inside the window
    by_name: dict           # HLO text (cut) -> self seconds
    gaps: list              # [(host activity, seconds)] of idle gaps

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def class_share(self, cls: str) -> float | None:
        """Device time of ``cls`` over busy time; None when idle."""
        if self.busy_s <= 0:
            return None
        return self.by_class.get(cls, 0.0) / (self.busy_s * self.devices)


def summarize(trace: Trace) -> Summary:
    lo, hi = window_of(trace)
    per_dev = defaultdict(list)
    names = defaultdict(list)
    for o in trace.ops:
        c = _clipped([o], lo, hi)
        if c:
            per_dev[o.device].append(c[0])
            names[o.device].append(o.name)
    by_class = defaultdict(float)
    by_name = defaultdict(float)
    for d, iv in per_dev.items():
        for text, t in zip(names[d], self_times(iv)):
            by_class[op_class(text)] += t * 1e-9
            by_name[text[:_LABEL]] += t * 1e-9
    busy = {d: sum(e - s for s, e in union(iv)) for d, iv in per_dev.items()}
    n_dev = max(1, len(busy))
    first = sorted(per_dev)[0] if per_dev else None
    gaps = _gaps(union(per_dev[first]) if first else [], lo, hi, trace.spans)
    return Summary(window_s=(hi - lo) * 1e-9,
                   busy_s=sum(busy.values()) * 1e-9 / n_dev,
                   devices=n_dev, by_class=dict(by_class),
                   by_name=dict(by_name), gaps=gaps)


def _gaps(busy, lo, hi, spans):
    """Idle time of one device inside the window, summed by the innermost
    ``bench.*`` span (other than the window) around each gap's middle."""
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    inner = [(n, s, e) for n, s, e in spans if n != WINDOW]
    out = defaultdict(float)
    for s, e in zip(edges[0::2], edges[1::2]):
        if e <= s:
            continue
        mid = 0.5 * (s + e)
        around = [(se - ss, n) for n, ss, se in inner if ss <= mid <= se]
        out[min(around)[1] if around else "outside bench spans"] += (e - s) * 1e-9
    return sorted(out.items(), key=lambda kv: -kv[1])


def breakdown(summary: Summary, top: int = 10) -> dict:
    """The result line's ``breakdown``: the device operations that took most
    time, and idle time by what the host was doing."""
    ops = sorted(summary.by_name.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in summary.gaps[:top]]}


def describe(path: str, limit: int = 8) -> str:
    """Planes, lines, event counts and a few events of a trace: what to
    read before writing a rule against it."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  line {line.name!r}: {len(evs)} events")
            names = defaultdict(float)
            for ev in evs:
                names[ev.name] += ev.duration_ns
            for n, d in sorted(names.items(), key=lambda kv: -kv[1])[:limit]:
                out.append(f"    {d * 1e-9:.6f} s  {n}")
            for ev in evs[:2]:
                out.append(f"    e.g. {ev.name} start={ev.start_ns} "
                           f"dur={ev.duration_ns} stats={list(ev.stats)[:8]}")
    return "\n".join(out)
