"""The comparison that decides ``correct``.

Each answer is a partition of one input graph, with the modularity the
system reported for it.  The plain reference (``reference/<algorithm>.py``)
judges it on the host, in float64, by two numbers:

* ``bad_partitions``: answers that are not a partition as stated (one
  label per vertex, labels ``0..k-1`` all used, ``k`` the reported
  community count); exact, limit 0;
* ``q_gap``: the widest gap between the reported modularity and the
  modularity of the returned labels;

and ``unanswered``, the answers due that never came or came back as an
error (exact, limit 0).  Each limit is set in ``limits/<cell>.json`` from
the readings recorded in PERF.md.  (The shortfall of the labels'
modularity below the reference algorithm's own is not compared: on graphs
drawn from the seed the control's reading is under three times the
program's, so no limit between them holds; PERF.md gives the readings.)
"""
from __future__ import annotations

import numpy as np


def symmetric(u, v):
    """Directed-symmetric edge list of unit-weight undirected edges without
    loops: ``(src, dst, w)``."""
    u = np.asarray(u, np.int64)
    v = np.asarray(v, np.int64)
    return (np.concatenate([u, v]), np.concatenate([v, u]),
            np.ones(2 * u.size))


def is_partition(labels, n: int, n_communities) -> bool:
    lab = np.asarray(labels)
    if lab.shape != (n,) or not np.issubdtype(lab.dtype, np.integer):
        return False
    k = int(lab.max()) + 1 if n else 0
    if n and (lab.min() < 0 or np.unique(lab).size != k):
        return False
    return n_communities is None or int(n_communities) == k


class Judge:
    """Judges answers against the reference."""

    def __init__(self, ref, graphs):
        self.ref = ref
        self.graphs = graphs          # index -> (u, v, n)
        self.readings = {"bad_partitions": 0, "q_gap": 0.0, "answers": 0}

    def judge(self, gi: int, labels, reported_q, n_communities) -> None:
        u, v, n = self.graphs[gi]
        r = self.readings
        r["answers"] += 1
        if not is_partition(labels, n, n_communities):
            r["bad_partitions"] += 1
            return
        src, dst, w = symmetric(u, v)
        q = self.ref.modularity(src, dst, w, np.asarray(labels, np.int64))
        if reported_q is not None:
            r["q_gap"] = max(r["q_gap"], abs(float(reported_q) - q))


def verdict(readings: dict, limits: dict) -> tuple[bool, dict]:
    """``(correct, compared)``: each number beside its limit."""
    compared = {k: {"value": readings[k], "limit": limits[k]}
                for k in ("unanswered", "bad_partitions", "q_gap")}
    ok = all(c["value"] is not None and c["value"] <= c["limit"]
             for c in compared.values())
    return ok, compared
