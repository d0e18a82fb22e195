"""Modularity (§II-C) and the move gain Δ𝑄 (Eq. 1) on the directed-symmetric form.

With the Graph convention (self-loops stored once with doubled weight):

    Q(C) = Σ_c  w_in(c)/vol(V)  −  (vol_w(c)/vol(V))²

where ``w_in(c)`` counts directed intra-community weight (loops enter once but
carry doubled weight — i.e. exactly twice the undirected intra weight), and
``vol(V) = Σ_v deg_w(v) = 2W``.  On loop-free graphs this equals NetworkX's
``community.modularity`` definition exactly (tested).

Move gain: for v moving A → B (paper Eq. 1; note the paper's ``deg_w(V)`` is a
typo for ``deg_w(v)``):

    ΔQ_{v→B} = 2·[ (cut_w(v,B⁻) − cut_w(v,A⁻))/vol(V)
                   − deg_w(v)·(vol_w(B⁻) − vol_w(A⁻))/vol(V)² ]

We maximize the equivalent integer-friendly score

    score(B) = vol(V)·(cut_w(v,B⁻) − cut_w(v,A⁻)) − deg_w(v)·(vol_w(B⁻) − vol_w(A⁻))

with ΔQ = 2·score/vol(V)².  ``score(A) = 0`` by construction, so "move iff
score > 0" is exactly "move iff ΔQ > 0".
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.graph.structure import Graph


def community_volumes(g: Graph, com: jax.Array) -> jax.Array:
    """vol_w(c) = Σ_{v∈c} deg_w(v), indexed by community id (capacity n_max)."""
    deg = g.weighted_degrees()
    return jax.ops.segment_sum(deg, com, num_segments=g.n_max)


def community_sizes(g: Graph, com: jax.Array) -> jax.Array:
    ones = jnp.where(g.vertex_mask(), 1, 0)
    return jax.ops.segment_sum(ones, com, num_segments=g.n_max)


def intra_weight(g: Graph, com: jax.Array) -> jax.Array:
    """Σ_c w_in(c): directed weight of edges with both endpoints co-clustered."""
    same = com[g.src] == com[g.dst]
    return jnp.sum(jnp.where(g.edge_mask & same, g.w, 0.0))


def modularity(g: Graph, com: jax.Array, *, promote: bool = False) -> jax.Array:
    """Newman–Girvan modularity of the partition ``com`` (f32 scalar).

    Guard rails (DESIGN.md §Robustness):
    * an edgeless graph (vol = 0) returns Q = 0 instead of 0/0 = NaN; for
      vol > 0 the guarded expression is bitwise identical to the unguarded
      one (same divisions, selected verbatim);
    * ``promote=True`` (the drivers set it via ``accum_needs_promotion``
      when m·max-weight approaches float32 precision loss) accumulates the
      volume/intra sums in float64 when x64 is enabled — otherwise it stays
      f32 and ``accum_dtype`` records the risk for the RunReport.
    """
    from repro.kernels.common import accum_dtype

    acc = accum_dtype(promote)
    with jax.named_scope("repro.modularity"):
        if acc == jnp.float32:
            vol_v = g.total_volume()
            w_in = intra_weight(g, com)
            vol_c = community_volumes(g, com)
        else:
            wm = jnp.where(g.edge_mask, g.w, 0.0).astype(acc)
            vol_v = jnp.sum(wm)
            same = com[g.src] == com[g.dst]
            w_in = jnp.sum(jnp.where(same, wm, jnp.zeros((), acc)))
            deg = jax.ops.segment_sum(wm, g.src, num_segments=g.n_max)
            vol_c = jax.ops.segment_sum(deg, com, num_segments=g.n_max)
        safe = jnp.where(vol_v > 0, vol_v, jnp.ones((), vol_v.dtype))
        q = w_in / safe - jnp.sum((vol_c / safe) ** 2)
        return jnp.where(vol_v > 0, q,
                         jnp.zeros((), q.dtype)).astype(jnp.float32)


def delta_q_from_score(score: jax.Array, vol_v: jax.Array) -> jax.Array:
    return 2.0 * score / (vol_v * vol_v)


def move_score(
    cut_vB: jax.Array,
    cut_vA: jax.Array,
    deg_v: jax.Array,
    vol_B_minus: jax.Array,
    vol_A_minus: jax.Array,
    vol_v: jax.Array,
) -> jax.Array:
    """score = vol(V)·(cut(v,B⁻) − cut(v,A⁻)) − deg_w(v)·(vol(B⁻) − vol(A⁻))."""
    return vol_v * (cut_vB - cut_vA) - deg_v * (vol_B_minus - vol_A_minus)


def modularity_dense_reference(adj, com) -> float:
    """O(n²) dense oracle for tests: adj is a symmetric numpy matrix with
    doubled diagonal (matching the Graph convention)."""
    import numpy as np

    adj = np.asarray(adj, dtype=np.float64)
    com = np.asarray(com)
    vol_v = adj.sum()
    deg = adj.sum(axis=1)
    q = 0.0
    for c in np.unique(com):
        idx = com == c
        w_in = adj[np.ix_(idx, idx)].sum()
        vol_c = deg[idx].sum()
        q += w_in / vol_v - (vol_c / vol_v) ** 2
    return float(q)
