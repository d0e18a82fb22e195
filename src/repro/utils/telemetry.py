"""Process-local telemetry: counters, host-side samples and trace spans.

Counters are bumped at HOST/trace time (guard activations, fallback
engagements, fault injections) — never inside a compiled program — so they
cost nothing on the device hot path.  A counter bumped during tracing counts
compiled-program constructions, not per-call executions; that is the useful
signal for guards that are resolved statically (e.g. "the packed id scatter
was disabled for this capacity").

>>> from repro.utils import telemetry
>>> telemetry.bump("agg.pack_disabled")
>>> telemetry.get("agg.pack_disabled")
1

``span`` is the one helper for host spans: a ``jax.profiler``
``TraceAnnotation`` that lands in the profiler's own trace beside the device
operations, on the same clock, and costs a no-op when no trace is being
captured.  Device-side phases are named with ``jax.named_scope`` where the
work is traced (``repro.local_move``, ``repro.aggregate``, …); the scope
becomes part of every operation's name stack (its ``tf_op``) in the trace.
"""
from __future__ import annotations

import threading
from typing import Dict

import jax

_lock = threading.Lock()
_counters: Dict[str, int] = {}
_values: Dict[str, Dict[str, float]] = {}


def bump(name: str, k: int = 1) -> None:
    with _lock:
        _counters[name] = _counters.get(name, 0) + k


def get(name: str) -> int:
    with _lock:
        return _counters.get(name, 0)


def observe(name: str, value: float) -> None:
    """Record one sample of a host-side measurement (latency, backoff sleep,
    breaker-open duration, …) into a cheap running aggregate —
    count/sum/min/max/last, no per-sample storage.  Same host-only
    discipline as ``bump``: never called from inside a compiled program."""
    v = float(value)
    with _lock:
        agg = _values.get(name)
        if agg is None:
            _values[name] = {"count": 1, "sum": v, "min": v, "max": v,
                             "last": v}
        else:
            agg["count"] += 1
            agg["sum"] += v
            agg["min"] = min(agg["min"], v)
            agg["max"] = max(agg["max"], v)
            agg["last"] = v


def values() -> Dict[str, Dict[str, float]]:
    with _lock:
        return {k: dict(v) for k, v in _values.items()}


def span(name: str, **args):
    """A host span ``name`` in the profiler's trace, with ``args`` as its
    stats (free when no trace is being captured)."""
    return jax.profiler.TraceAnnotation(name, **args)


def snapshot() -> Dict[str, int]:
    with _lock:
        return dict(_counters)


def reset() -> None:
    with _lock:
        _counters.clear()
        _values.clear()
