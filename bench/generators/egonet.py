"""Ego-networks (``kind: "egonet"``): the stand-in for SNAP ego-Twitter,
where each user's ego-net (the alters, without the ego, as SNAP's
``.edges`` files give them) is clustered into circles.

An ego-net of ``n`` alters holds ``k`` planted circles (``k`` uniform in
``cfg["circles"]``, each alter in one circle drawn uniformly); an alter
pair is an undirected edge with probability ``p_in`` inside a circle and
``p_out`` across, ``p_in = cfg["circle_affinity"] * p_out``, both scaled so
that the ego-net's expected density is ``cfg["density"]`` whatever its
circles: every ego-net has one density.  Sizes are lognormal with median
``cfg["median_vertices"]`` and ``sigma`` ``cfg["sigma"]``, rounded and
clipped to ``cfg["vertices"]``.  Weights are unit.

``graph(cfg, rng)`` is one ego-net of a size drawn from that law;
``pool(cfg, rng)`` is the cell's pool of ``cfg["egonets"]`` ego-nets, whose
sizes are the law's quantiles at ``(i + 1/2) / egonets`` in an order drawn
from ``rng``, so that the pool holds the law's mean, not a sample's.
Everything is drawn from the ``rng`` given and nothing else.
"""
from __future__ import annotations

from statistics import NormalDist

import numpy as np


def _size(cfg: dict, z) -> np.ndarray:
    lo, hi = cfg["vertices"]
    n = np.rint(cfg["median_vertices"] * np.exp(cfg["sigma"] * np.asarray(z)))
    return np.clip(n, lo, hi).astype(np.int64)


def egonet(cfg: dict, n: int, rng: np.random.Generator):
    """One ego-net of ``n`` alters: undirected edges ``(u, v)``, ``u < v``."""
    k_lo, k_hi = cfg["circles"]
    k = int(rng.integers(k_lo, k_hi + 1))
    circle = rng.integers(0, k, size=n)
    a, b = np.triu_indices(n, 1)
    inside = circle[a] == circle[b]
    # share of pairs inside a circle, and p_out so the density is the
    # configuration's whatever the circles' sizes
    f = inside.mean() if a.size else 0.0
    p_out = cfg["density"] / (1.0 + f * (cfg["circle_affinity"] - 1.0))
    p = np.where(inside, cfg["circle_affinity"] * p_out, p_out)
    keep = rng.random(a.size) < p
    return a[keep].astype(np.int64), b[keep].astype(np.int64), int(n)


def graph(cfg: dict, rng: np.random.Generator):
    """One ego-net, its size drawn from the lognormal law."""
    return egonet(cfg, int(_size(cfg, rng.standard_normal())), rng)


def pool(cfg: dict, rng: np.random.Generator) -> list:
    """The pool: ``cfg["egonets"]`` ego-nets at the law's quantiles, in an
    order drawn from ``rng``."""
    k = int(cfg["egonets"])
    z = [NormalDist().inv_cdf((i + 0.5) / k) for i in range(k)]
    sizes = _size(cfg, z)[rng.permutation(k)]
    return [egonet(cfg, int(n), rng) for n in sizes]
