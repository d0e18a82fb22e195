"""The chip: presence, identity, memory, compile cache and compile counts."""
from __future__ import annotations

import json
import os


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require_tpu(chips: int) -> dict:
    """The device as JAX reports it; raises ``NoAccelerator`` unless JAX
    finds at least ``chips`` TPU devices.  Never falls back to the CPU."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != "tpu":
        raise NoAccelerator(f"JAX finds no TPU: {info}")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX finds {info}")
    return info


def peaks(kind: str, path: str) -> dict:
    """Published peaks of ``kind`` from ``peaks.json``; an unknown kind is
    an error, not a default."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in {path}")
    return table[kind]


def use_compile_cache(directory: str) -> None:
    """JAX's persistent compilation cache at ``directory`` (a fixed path in
    the checkout, whatever the environment says), caching every program,
    however quickly it compiled."""
    import jax

    os.makedirs(directory, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", directory)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def cache_entries(directory: str) -> int:
    return len(os.listdir(directory)) if os.path.isdir(directory) else 0


def peak_bytes(n_devices: int) -> int | None:
    """``peak_bytes_in_use`` of the fullest of the first ``n_devices``."""
    import jax

    peaks_seen = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                  for d in jax.devices()[:n_devices]]
    peaks_seen = [p for p in peaks_seen if p is not None]
    return max(peaks_seen) if peaks_seen else None


class CompileCounter:
    """Counts, from JAX's monitoring events, the jaxprs traced, the
    executables built (compiled, or loaded from the persistent cache) and
    of those the ones loaded from the cache.  ``snapshot()`` gives the
    totals so far."""

    TRACE = "/jax/core/compile/jaxpr_trace_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax

        self.counts = {"traces": 0, "executables": 0, "cache_loads": 0}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == self.TRACE:
            self.counts["traces"] += 1
        elif event == self.COMPILE:
            self.counts["executables"] += 1

    def _event(self, event, **_):
        if event == self.HIT:
            self.counts["cache_loads"] += 1

    def snapshot(self) -> dict:
        return dict(self.counts)

    def close(self) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)

    @staticmethod
    def since(before: dict, after: dict) -> dict:
        return {k: after[k] - before[k] for k in after}
