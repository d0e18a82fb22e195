"""The loop that drives the system: whole solves by one caller, back to
back, in whole rounds of the cell's graphs.

It calls the program only through the cell's entry (``entries/<call>.py``:
``ingest``, such as ``from_numpy_edges``, then ``solve``, such as
``louvain()``) and times them on the host clock; each step sits in a
``bench.*`` span for the trace.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from bench.trace import span


@dataclasses.dataclass
class Answer:
    """One partition the system returned, with what it reported."""
    graph: int                 # index into the cell's graphs
    labels: np.ndarray
    modularity: float | None   # reported; None where the entry reports none
    n_communities: int | None
    result: object = None      # the entry's own result object


def answer_of(graph: int, result) -> Answer:
    return Answer(graph=graph, labels=np.asarray(result.labels),
                  modularity=getattr(result, "modularity", None),
                  n_communities=getattr(result, "n_communities", None),
                  result=result)


@dataclasses.dataclass
class Solve:
    end: float        # seconds from the window's start
    ingest_s: float
    answer: Answer


def solve_once(entry, graph: int, u, v, n: int):
    """One whole solve: host edge list → ``entry.ingest`` → ``entry.solve``
    → labels on the host.  Returns ``(ingest_s, Answer)``."""
    t0 = time.perf_counter()
    with span("bench.ingest"):
        g = entry.ingest(u, v, n)
    t1 = time.perf_counter()
    with span("bench.solve"):
        ans = answer_of(graph, entry.solve(g))
    return t1 - t0, ans


def closed(graphs, entry, seconds: float) -> list:
    """Solve ``graphs`` in turn, back to back, in whole rounds (each graph
    once), until a round ends at or after ``seconds``: at any speed every
    graph is solved equally often, so every seed does the same work."""
    out = []
    t0 = time.perf_counter()
    while True:
        for gi, g in enumerate(graphs):
            ingest_s, ans = solve_once(entry, gi, *g)
            out.append(Solve(time.perf_counter() - t0, ingest_s, ans))
        if out[-1].end >= seconds:
            return out
