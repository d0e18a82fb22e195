"""Plain reference Louvain (Blondel et al. 2008), in numpy, on the host.

Written from the paper's description, independent of the program: local
moving by modularity gain, then aggregation of each community into one
vertex, level after level until no vertex moves.  Moves are made for all
vertices at once (as the parallel algorithms the program follows do),
each candidate mover kept with probability 1/2 so that neighbours do not
swap back and forth, and a singleton moves to another singleton only if
that one has the lower id.  It is a quality anchor, not a replica: its
labels differ from the program's, its modularity is close.

``dtype`` is the precision of every stored value and every sum: each sum
is a scatter-add into an array of that dtype, so with ``bfloat16`` it
rounds at every addition, as a segment sum moved to bfloat16 would.
"""
from __future__ import annotations

import numpy as np


def _seg(idx, vals, size, dtype):
    out = np.zeros(size, dtype)
    np.add.at(out, idx, vals.astype(dtype))
    return out


def _local_move(src, dst, w, n, dtype, rng, max_sweeps):
    """One level's local moving; returns labels in ``[0, n)``."""
    k = _seg(src, w, n, dtype)
    two_m = _seg(np.zeros(src.size, np.int64), w, 1, dtype)[0]
    off = src != dst
    s, d, ww = src[off], dst[off], w[off]
    lab = np.arange(n)
    for _ in range(max_sweeps):
        tot = _seg(lab, k, n, dtype)
        size = np.bincount(lab, minlength=n)
        key = s * n + lab[d]
        uk, inv = np.unique(key, return_inverse=True)
        kvc = _seg(inv, ww, uk.size, dtype)
        gv, gc = uk // n, uk % n
        own = gc == lab[gv]
        kva = np.zeros(n, dtype)
        kva[gv[own]] = kvc[own]
        gv, gc, kvc = gv[~own], gc[~own], kvc[~own]
        if not gv.size:
            break
        a = lab[gv]
        gain = (kvc - kva[gv]) - k[gv] * (tot[gc] - tot[a] + k[gv]) / two_m
        # rows are sorted by (vertex, community): the best is the first
        # row of its vertex that reaches the vertex's largest gain
        gain = gain.astype(np.float64)
        start = np.flatnonzero(np.concatenate([[True], gv[1:] != gv[:-1]]))
        best = np.maximum.reduceat(gain, start)
        hit = np.flatnonzero(gain == np.repeat(best, np.diff(
            np.append(start, gv.size))))
        first = hit[np.concatenate([[True], gv[hit][1:] != gv[hit][:-1]])]
        gv, gc, gain, a = gv[first], gc[first], gain[first], a[first]
        want = gain > 0
        swap = (size[a] == 1) & (size[gc] == 1) & (gc > a)
        want &= ~swap
        if not want.any():
            break
        move = want & (rng.random(gv.size) < 0.5)
        lab[gv[move]] = gc[move]
    return lab


def _aggregate(src, dst, w, lab, dtype):
    ids, lab = np.unique(lab, return_inverse=True)
    nc = ids.size
    key = lab[src] * nc + lab[dst]
    uk, inv = np.unique(key, return_inverse=True)
    return uk // nc, uk % nc, _seg(inv, w, uk.size, dtype), lab, nc


def modularity(src, dst, w, labels, dtype=np.float64) -> float:
    """Newman–Girvan Q of ``labels`` on the directed-symmetric edge list
    (a loop stored once with its weight doubled)."""
    n = labels.size
    w = w.astype(dtype)
    zero = np.zeros(src.size, np.int64)
    vol = _seg(zero, w, 1, dtype)[0]
    if float(vol) == 0.0:
        return 0.0
    intra = labels[src] == labels[dst]
    w_in = _seg(zero[intra], w[intra], 1, dtype)[0]
    deg = _seg(src, w, n, dtype)
    vol_c = _seg(labels, deg, n, dtype) / vol
    sq = _seg(np.zeros(n, np.int64), vol_c * vol_c, 1, dtype)[0]
    return float(w_in / vol - sq)


def solve(src, dst, w, n: int, *, max_levels: int, max_sweeps: int,
          dtype=np.float64, seed: int = 0):
    """Louvain on ``n`` vertices; returns ``(labels, modularity)``, the
    modularity computed in ``dtype`` as the answer would report it."""
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    w0 = np.asarray(w, np.float64).astype(dtype)
    rng = np.random.default_rng(seed)
    assign = np.arange(n)
    s, d, ww, nv = src, dst, w0, n
    for _ in range(max_levels):
        lab = _local_move(s, d, ww, nv, dtype, rng, max_sweeps)
        if np.array_equal(lab, np.arange(nv)):
            break
        s, d, ww, lab, nv = _aggregate(s, d, ww, lab, dtype)
        assign = lab[assign]
    _, assign = np.unique(assign, return_inverse=True)
    return assign, modularity(src, dst, w0, assign, dtype)
