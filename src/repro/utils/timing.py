"""Wall-clock timing helpers used by benchmarks and the phase breakdown.

The paper reports per-phase runtimes (Fig. 4 breaks Louvain into local-moving
and aggregation).  ``Timer`` accumulates named phases so the benchmark harness
can reproduce that breakdown.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Dict, Iterator


@dataclass
class Timer:
    """Accumulating phase timer.

    >>> t = Timer()
    >>> with t.phase("local_moving"):
    ...     pass
    >>> "local_moving" in t.totals
    True
    """

    totals: Dict[str, float] = field(default_factory=dict)
    counts: Dict[str, int] = field(default_factory=dict)

    @contextlib.contextmanager
    def phase(self, name: str) -> Iterator[None]:
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            self.totals[name] = self.totals.get(name, 0.0) + dt
            self.counts[name] = self.counts.get(name, 0) + 1

    @property
    def total(self) -> float:
        return sum(self.totals.values())
