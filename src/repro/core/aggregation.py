"""Louvain aggregation phase (paper Alg. 3 l.13-17, §III-B2) — jit-native.

Steps, exactly as the paper describes, re-expressed for XLA:
  1. *Remap* community IDs to a contiguous [0, n_comm) range
     (sort + run-detect + scatter — Arkouda ``GroupBy`` keys);
  2. *Rewrite* edge endpoints through the remap;
  3. *Merge* parallel edges with weight summation
     (``GroupBy((src,dst)).sum(w)`` + ``Broadcast`` ≙ ``groupby_sum``).

Intra-community edges collapse onto self-loops whose (single, doubled) weight
equals the directed intra weight — preserving vol/deg/modularity invariants
(see tests/test_louvain.py::test_coarsen_preserves_modularity).

Outputs keep static capacities with masks, so every coarsening level runs
under one compiled program per capacity.  Three coarsening paths exist:

* ``remap_and_coarsen_binned`` (default in both louvain drivers, via the
  ``remap_and_coarsen_by`` dispatch): NO sort anywhere — the sort-free
  invariant of DESIGN.md §Pipeline.  The remap is a presence bitmap +
  ``cumsum`` (``graph/segment.py contiguize_ids``) and the parallel-edge
  merge scatter-accumulates weights into dense per-src-community bin rows
  (``kernels/aggregation``), with a ``lax.cond``-gated fallback onto the
  one-sort path for rows over the static bin width.
* ``remap_and_coarsen`` (``LouvainConfig.aggregation="sort"``): steps 1-3
  fused into ONE ``lax.sort`` over the combined (m edges + n vertices)
  entry list — the retired default, kept as the binned path's parity
  ORACLE.  Vertex entries (sorted ahead of their community's edges via a
  -1 dst key) enumerate the contiguous ids; edge runs are grouped, summed
  and scatter-compacted off the SAME sorted order.
* ``remap_communities_sorted`` + ``coarsen_graph``: the two-step reference
  path (one n-sort + one m-sort), the original oracle.

All three produce bit-for-bit identical coarse graphs, including the
unspecified-slot conventions (tests/test_aggregation.py), so
``shrink_graph`` and the cascade boundary sync are agnostic to the path.

``shrink_graph`` compacts a coarsened graph into smaller static capacities
for the capacity-scheduled cascade (DESIGN.md §Pipeline): coarsening output
is front-compacted and src-sorted by construction, so the capacity change is
a static slice + sentinel rewrite, entirely on device.
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.graph import segment as seg
from repro.graph.structure import Graph
from repro.kernels.aggregation import binned_coarsen

AGGREGATION_METHODS = ("binned", "sort")


@jax.jit
def remap_communities(com: jax.Array, vertex_mask: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Contiguize community ids — sort-free.

    Presence bitmap + ``cumsum`` (``graph/segment.py contiguize_ids``); the
    historical sorted version survives as ``remap_communities_sorted`` and
    the two agree bitwise (tests/test_aggregation.py).

    Returns (new_com, n_comm): ``new_com[v] ∈ [0, n_comm)`` for valid v,
    ``n_max`` sentinel for invalid v.  Ordering is by old community id
    (deterministic).
    """
    n = com.shape[0]
    sentinel = jnp.int32(n)
    with jax.named_scope("repro.aggregate"):
        table, n_comm = seg.contiguize_ids(com, vertex_mask, n)
        new_com = jnp.where(vertex_mask, table[jnp.clip(com, 0, n - 1)],
                            sentinel)
    return new_com, n_comm


@jax.jit
def remap_communities_sorted(com: jax.Array, vertex_mask: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Sorted contiguize oracle (the pre-sort-free ``remap_communities``):
    one n-sort + run-detect + scatter, Arkouda ``GroupBy`` keys."""
    n = com.shape[0]
    sentinel = jnp.int32(n)
    key = jnp.where(vertex_mask, com, sentinel)
    (sk,), (pidx,) = seg.sort_by_keys((key,), (jnp.arange(n, dtype=jnp.int32),))
    starts_all = seg.run_starts(sk)
    rid = seg.run_ids(starts_all)
    n_comm = jnp.sum((starts_all & (sk < sentinel)).astype(jnp.int32))
    new_com = jnp.zeros((n,), jnp.int32).at[pidx].set(rid)
    new_com = jnp.where(vertex_mask, new_com, sentinel)
    return new_com, n_comm


@jax.jit
def remap_and_coarsen(
    g: Graph, com: jax.Array
) -> Tuple[jax.Array, jax.Array, Graph]:
    """Fused remap + coarsen: ONE ``lax.sort`` per aggregation.

    Equivalent to ``remap_communities`` followed by ``coarsen_graph`` —
    bit-for-bit, including unspecified-slot conventions — but the standalone
    vertex-side sort is folded into the edge-grouping sort: the combined
    (m + n)-entry list carries one entry per edge keyed by its RAW
    (com[src], com[dst]) pair and one entry per vertex keyed by
    (com[v], -1), so within each source community the vertex entries sort
    first.  Runs of the first key enumerate communities in ascending raw-id
    order (every valid community owns at least one vertex entry), which is
    exactly ``remap_communities``'s ordering; because the raw→contiguous map
    is monotone, edge runs also appear in the two-step path's group order,
    so group sums accumulate in the same element order (bitwise-equal
    floats) and the scatter compaction lands them in the same slots.

    Returns ``(new_com, n_comm, coarse_graph)``.
    """
    n, m = g.n_max, g.m_max
    sentinel = jnp.int32(n)
    vmask = g.vertex_mask()
    com_c = jnp.clip(com, 0, n - 1)

    # combined entry list: m edge entries then n vertex entries
    flag = jnp.concatenate([
        jnp.where(g.edge_mask, 0, 1),
        jnp.where(vmask, 0, 1),
    ]).astype(jnp.int32)
    a = jnp.concatenate([
        jnp.where(g.edge_mask, com_c[jnp.clip(g.src, 0, n - 1)], sentinel),
        jnp.where(vmask, com, sentinel),
    ]).astype(jnp.int32)
    b = jnp.concatenate([
        jnp.where(g.edge_mask, com_c[jnp.clip(g.dst, 0, n - 1)], sentinel),
        jnp.full((n,), -1, jnp.int32),          # vertices ahead of edges
    ])
    wv = jnp.concatenate([
        jnp.where(g.edge_mask, g.w, 0.0),
        jnp.zeros((n,), g.w.dtype),
    ])
    payload = jnp.concatenate([
        jnp.full((m,), n, jnp.int32),           # edge entries: sink id
        jnp.arange(n, dtype=jnp.int32),         # vertex entries: vertex id
    ])
    (sflag, sa, sb), (sw, spay) = seg.sort_by_keys((flag, a, b), (wv, payload))
    svalid = sflag == 0
    is_vtx = sb == jnp.int32(-1)
    total = m + n

    # community enumeration: runs of (flag, a); the j-th valid run is the
    # j-th distinct live community in ascending raw-id order
    a_starts = seg.run_starts(sflag, sa)
    a_rid = seg.run_ids(a_starts)
    n_comm = jnp.sum((a_starts & svalid).astype(jnp.int32))

    # new_com per vertex: scatter each vertex entry's community run id back
    # to its vertex slot (slot n is the sink for non-vertex entries)
    vpos = jnp.where(svalid & is_vtx, spay, n)
    new_com = (jnp.full((n + 1,), sentinel, jnp.int32)
               .at[vpos].set(a_rid)[:n])
    new_com = jnp.where(vmask, new_com, sentinel)
    # raw community id -> contiguous id table (for the dst rewrite); every
    # valid raw id is written (identically) by each of its vertex entries
    vkey = jnp.where(svalid & is_vtx, sa, n)
    raw2new = (jnp.full((n + 1,), sentinel, jnp.int32)
               .at[vkey].set(a_rid))

    # edge grouping: runs of (flag, a, b) restricted to valid edge entries
    starts_all = seg.run_starts(sflag, sa, sb)
    rid = seg.run_ids(starts_all)
    sums = jax.ops.segment_sum(
        jnp.where(svalid & ~is_vtx, sw, 0.0), rid, num_segments=total)
    e_starts = starts_all & svalid & (~is_vtx)
    e_rid = jnp.cumsum(e_starts.astype(jnp.int32)) - 1
    n_groups = jnp.sum(e_starts.astype(jnp.int32))

    # scatter-compact group representatives to the front (graph/segment.py's
    # run-detect/scatter machinery, no second sort); slots >= n_groups are
    # masked, matching coarsen_graph's contract
    pos = jnp.where(e_starts, e_rid, total)
    idx = (jnp.zeros((total + 1,), jnp.int32)
           .at[pos].set(jnp.arange(total, dtype=jnp.int32))[:m])
    grp_ok = jnp.arange(m, dtype=jnp.int32) < n_groups
    gsrc = jnp.where(grp_ok, a_rid[idx], sentinel)
    gdst = jnp.where(grp_ok, raw2new[jnp.clip(sb[idx], 0, n)], sentinel)
    gw = jnp.where(grp_ok, sums[rid[idx]], 0.0)
    cg = Graph(
        src=gsrc,
        dst=gdst,
        w=gw,
        edge_mask=grp_ok,
        n_valid=n_comm.astype(jnp.int32),
        m_valid=n_groups,
        n_max=n,
        m_max=m,
        sorted_by="src",
    )
    return new_com, n_comm, cg


@partial(jax.jit, static_argnames=("width", "impl", "force_overflow"))
def remap_and_coarsen_binned(
    g: Graph, com: jax.Array, *, width: int | None = None, impl: str = "auto",
    force_overflow: bool = False
) -> Tuple[jax.Array, jax.Array, Graph]:
    """Sort-free remap + coarsen (DESIGN.md §Aggregation kernel).

    Bitmap-``cumsum`` remap followed by the binned scatter merge
    (``kernels/aggregation.binned_coarsen``); bit-for-bit identical to the
    one-sort ``remap_and_coarsen`` oracle, including unspecified-slot
    conventions, so downstream ``shrink_graph`` / cascade boundary sync run
    unchanged.  ``width`` defaults to the capacity-derived
    ``kernels.common.pick_bin_width`` menu pick (static at trace time).

    Returns ``(new_com, n_comm, coarse_graph)``.

    ``force_overflow`` (static, part of the jit cache key) is the
    ``binned_overflow`` fault-injection point — see
    ``kernels.aggregation.binned_coarsen``.
    """
    new_com, n_comm = remap_communities(com, g.vertex_mask())
    cg = binned_coarsen(g, new_com, n_comm, width=width, impl=impl,
                        force_overflow=force_overflow)
    return new_com, n_comm, cg


def remap_and_coarsen_by(
    method: str, g: Graph, com: jax.Array, faults=()
) -> Tuple[jax.Array, jax.Array, Graph]:
    """Dispatch one aggregation step by method name.

    ``"binned"`` (the default everywhere) runs the sort-free path;
    ``"sort"`` keeps the one-sort fused path selectable as the documented
    oracle (``LouvainConfig.aggregation``).

    ``faults`` is the armed fault-point collection threaded down from the
    driver (``utils.faultinject``): passing it explicitly (instead of
    reading the global registry here, possibly mid-trace) keeps every
    enclosing jit/lru_cache program keyed on the fault state, so a
    clean-cached trace is never reused under faults or vice versa.
    """
    if method not in AGGREGATION_METHODS:
        raise ValueError(
            f"unknown aggregation {method!r}, want one of {AGGREGATION_METHODS}")
    with jax.named_scope("repro.aggregate"):
        if method == "sort":
            return remap_and_coarsen(g, com)
        return remap_and_coarsen_binned(
            g, com, force_overflow="binned_overflow" in faults)


def shrink_graph(g: Graph, n_max: int, m_max: int) -> Graph:
    """Compact a coarsened graph into smaller static capacities (on device).

    Requires ``n_valid <= n_max``, ``m_valid <= m_max`` and valid edges
    front-compacted (both hold for ``remap_and_coarsen``/``coarsen_graph``
    output — the capacity-scheduled cascade checks the counts host-side
    before descending).  Pure slice + sentinel rewrite: vertex ids are
    already contiguous in [0, n_valid), so only the padding sentinel value
    changes with the capacity.
    """
    sent = jnp.int32(n_max)
    with jax.named_scope("repro.aggregate"):
        em = g.edge_mask[:m_max]
        return Graph(
            src=jnp.where(em, g.src[:m_max], sent),
            dst=jnp.where(em, g.dst[:m_max], sent),
            w=jnp.where(em, g.w[:m_max], 0.0),
            edge_mask=em,
            n_valid=g.n_valid,
            m_valid=g.m_valid,
            n_max=int(n_max),
            m_max=int(m_max),
            sorted_by=g.sorted_by,
        )


@jax.jit
def coarsen_graph(g: Graph, new_com: jax.Array, n_comm: jax.Array) -> Graph:
    """Build the super-vertex graph for contiguous community ids ``new_com``.

    Two-step reference path (with ``remap_communities``): kept as the
    documented oracle for ``remap_and_coarsen``, which fuses the remap sort
    into this GroupBy's sort."""
    n, m = g.n_max, g.m_max
    sentinel = jnp.int32(n)
    csrc = jnp.where(g.edge_mask, new_com[jnp.clip(g.src, 0, n - 1)], sentinel)
    cdst = jnp.where(g.edge_mask, new_com[jnp.clip(g.dst, 0, n - 1)], sentinel)
    w = jnp.where(g.edge_mask, g.w, 0.0)
    (gk, gs, gvalid, n_groups) = seg.groupby_sum((csrc, cdst), w, valid=g.edge_mask)
    gsrc, gdst = gk
    grp_ok = gvalid & (gsrc < sentinel)
    return Graph(
        src=jnp.where(grp_ok, gsrc, sentinel),
        dst=jnp.where(grp_ok, gdst, sentinel),
        w=jnp.where(grp_ok, gs, 0.0),
        edge_mask=grp_ok,
        n_valid=n_comm.astype(jnp.int32),
        m_valid=jnp.sum(grp_ok.astype(jnp.int32)),
        n_max=n,
        m_max=m,
        sorted_by="src",
    )
