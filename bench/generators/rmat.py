"""Graph500 R-MAT (``kind: "rmat"``): skewed degrees and weak communities,
the stand-in for SNAP graphs such as com-youtube.

``graph(cfg, rng)``: one graph of exactly ``cfg["vertices"]`` vertices and
``cfg["undirected_edges"]`` edges, R-MAT parameters ``cfg["rmat"]``
(``a``, ``b``, ``c``) and ``cfg["edge_factor"]``.
"""
from __future__ import annotations

import math

import numpy as np


def rmat_edges(scale: int, edge_factor: int, a: float, b: float, c: float,
               rng: np.random.Generator):
    """Graph500 R-MAT: undirected edges ``(lo, hi)``, loops dropped,
    duplicates merged, ``n = 2**scale``.  The sampling is
    ``repro.graph.generators.rmat`` as of its bring-up on the chip."""
    n = 1 << scale
    m = n * edge_factor
    u = np.zeros(m, dtype=np.int64)
    v = np.zeros(m, dtype=np.int64)
    ab = a + b
    for bit in range(scale):
        r = rng.random(m)
        right = r >= ab
        r2 = rng.random(m)
        v_right_top = r2 >= (a / ab)
        v_right_bottom = r2 >= (c / (1.0 - ab))
        u |= right.astype(np.int64) << bit
        v |= np.where(right, v_right_bottom, v_right_top).astype(np.int64) << bit
    lo = np.minimum(u, v)
    hi = np.maximum(u, v)
    ok = lo != hi
    lo, hi = lo[ok], hi[ok]
    _, idx = np.unique(lo * n + hi, return_index=True)
    return lo[idx], hi[idx]


def rmat_graph(cfg: dict, rng: np.random.Generator):
    """One R-MAT stand-in: ``(u, v, n)`` with exactly
    ``cfg["undirected_edges"]`` edges, a uniform sample of the deduplicated
    R-MAT edges, so every graph has the same shape."""
    p = cfg["rmat"]
    n = int(cfg["vertices"])
    scale = int(math.log2(n))
    if 1 << scale != n:
        raise ValueError(f"R-MAT vertices must be a power of two, got {n}")
    lo, hi = rmat_edges(scale, int(cfg["edge_factor"]), p["a"], p["b"],
                        p["c"], rng)
    e = int(cfg["undirected_edges"])
    if lo.size < e:
        raise ValueError(f"R-MAT gave {lo.size} edges, fewer than {e}")
    keep = np.sort(rng.choice(lo.size, size=e, replace=False))
    return lo[keep], hi[keep], n


graph = rmat_graph
