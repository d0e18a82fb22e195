"""Graph generators of the benchmark, driven by a configuration file.

Copies, not imports, of the program's generators, so that a change to the
program cannot move the yardstick.  Every generator takes its randomness
from ``stream(seed, tag)`` and nothing else, so one seed gives one input.
A configuration's ``kind`` names the generator; ``rmat`` is the one
there is.
"""
from __future__ import annotations

import math

import numpy as np


def stream(seed: int, *tag: int) -> np.random.Generator:
    """Independent generator for ``(seed, *tag)``; any non-negative seed,
    however large."""
    return np.random.default_rng([int(seed), *[int(t) for t in tag]])


# ---------------------------------------------------------------- R-MAT


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


def closed_order(cfg: dict, traffic: dict, seed: int):
    """The closed loop's timed graphs: the configuration's ``graph_seed``
    fixes ``traffic["graphs"]`` R-MAT graphs, the same for every run; the
    run's seed orders them.  (With timed graphs drawn from the run's seed,
    the number of sweeps, and so the work, moved with the seed: runs of
    different seeds spread 3.3% where two runs of one seed differed by
    0.05%.)"""
    k = int(traffic["graphs"])
    gs = [rmat_graph(cfg, stream(cfg["graph_seed"], 1, i)) for i in range(k)]
    return [gs[i] for i in stream(seed, 7).permutation(k)]


def check_graph(cfg: dict, seed: int):
    """A graph of the same shape drawn from the run's seed, solved after
    the window through the same entry and programs and judged with the
    timed answers, so that every run meets data it was not written on."""
    return rmat_graph(cfg, stream(seed, 8))
