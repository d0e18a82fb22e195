"""Capacity padding + leading-batch-axis packing (DESIGN.md §Serving).

The batched many-graph engine (``core.batch``) relies on two facts about the
``Graph`` representation:

  * arrays are capacity-padded with validity masks, so re-padding a graph to
    a LARGER static capacity changes only the padding (the sentinel value
    tracks the new ``n_max``) — by the same capacity-portability contract
    the cascade's ``shrink_graph`` descends on, results for valid vertices
    are bit-identical at any capacity that holds the graph;
  * ``Graph`` is a registered pytree whose data fields (src/dst/w/edge_mask/
    n_valid/m_valid) are leaves and whose capacities are STATIC meta, so
    same-capacity graphs stack along a new leading batch axis for free and
    the stacked object is a valid ``jax.vmap`` operand (each vmap lane sees
    an ordinary single ``Graph``).

``pad_graph`` is the exact inverse direction of ``aggregation.shrink_graph``
(grow instead of shrink); ``stack_graphs`` produces the batched container.
All three work on the host, in numpy: a batch is padded and stacked there
and moved to the device in one ``device_put``, so no program is traced or
compiled per graph size or slot count.
"""
from __future__ import annotations

from typing import Sequence

import jax
import numpy as np

from repro.graph.structure import Graph


def pad_graph(g: Graph, n_cap: int, m_cap: int) -> Graph:
    """Re-pad ``g`` into LARGER static capacities (pure pad + sentinel
    rewrite, on the host: numpy leaves).

    Vertex ids are untouched (valid ids live in [0, n_valid) at any
    capacity); invalid src/dst entries are rewritten from the old ``n_max``
    sentinel to ``n_cap`` and new edge slots are appended fully masked, so
    every invariant (``sorted_by``, front-compaction, mask counts) survives.
    A graph already at ``(n_cap, m_cap)`` — the serve engine ingests to its
    bucket — is returned as it is.
    """
    n_cap, m_cap = int(n_cap), int(m_cap)
    if n_cap < g.n_max or m_cap < g.m_max:
        raise ValueError(
            f"pad_graph only grows capacities: have ({g.n_max}, {g.m_max}), "
            f"asked ({n_cap}, {m_cap})")
    if n_cap == g.n_max and m_cap == g.m_max:
        return g
    g = jax.device_get(g)      # numpy leaves; a host graph stays as it is
    pad = m_cap - g.m_max
    sent = np.full((pad,), n_cap, np.int32)
    return Graph(
        src=np.concatenate([np.where(g.edge_mask, g.src, n_cap), sent]
                           ).astype(np.int32),
        dst=np.concatenate([np.where(g.edge_mask, g.dst, n_cap), sent]
                           ).astype(np.int32),
        w=np.concatenate([np.where(g.edge_mask, g.w, 0.0),
                          np.zeros((pad,))]).astype(np.float32),
        edge_mask=np.concatenate([g.edge_mask, np.zeros((pad,), bool)]),
        n_valid=g.n_valid,
        m_valid=g.m_valid,
        n_max=n_cap,
        m_max=m_cap,
        sorted_by=g.sorted_by,
    )


def empty_slot(n_cap: int, m_cap: int, sorted_by: str = "src") -> Graph:
    """A fully-masked (0 vertices, 0 edges) graph at the given capacities,
    on the host — the batch-axis filler (DESIGN.md §Serving).  Runs through
    every evaluator as a no-op lane: no valid vertex is ever active, every
    level converges immediately (0 communities == 0 valid vertices), and
    the modularity guard returns 0 for the zero-volume graph."""
    return Graph(
        src=np.full((m_cap,), n_cap, np.int32),
        dst=np.full((m_cap,), n_cap, np.int32),
        w=np.zeros((m_cap,), np.float32),
        edge_mask=np.zeros((m_cap,), bool),
        n_valid=np.int32(0),
        m_valid=np.int32(0),
        n_max=int(n_cap),
        m_max=int(m_cap),
        sorted_by=sorted_by,
    )


def stack_graphs(graphs: Sequence[Graph]) -> Graph:
    """Stack same-capacity Graphs along a new leading batch axis, on the
    host (numpy), for ONE transfer of the batch.

    Returns a ``Graph`` whose DATA leaves carry a leading batch dimension
    (src/dst/w/edge_mask become ``(B, m_max)``, the valid counts ``(B,)``)
    while the static meta stays scalar — NOT a semantically valid single
    graph, but exactly the pytree ``jax.vmap(..., in_axes=0)`` maps over.
    ``sorted_by`` must agree across the batch (it is static meta and the
    traced-ELL path keys on it); all capacities must already match — pad
    with ``pad_graph`` first.
    """
    if not graphs:
        raise ValueError("stack_graphs needs at least one graph")
    g0 = graphs[0]
    for g in graphs:
        if (g.n_max, g.m_max) != (g0.n_max, g0.m_max):
            raise ValueError(
                f"capacity mismatch in batch: ({g.n_max}, {g.m_max}) vs "
                f"({g0.n_max}, {g0.m_max}) — pad_graph to a common bucket "
                "capacity first")
        if g.sorted_by != g0.sorted_by:
            raise ValueError(
                f"sorted_by mismatch in batch: {g.sorted_by!r} vs "
                f"{g0.sorted_by!r}")
    return Graph(
        src=np.stack([np.asarray(g.src) for g in graphs]),
        dst=np.stack([np.asarray(g.dst) for g in graphs]),
        w=np.stack([np.asarray(g.w) for g in graphs]),
        edge_mask=np.stack([np.asarray(g.edge_mask) for g in graphs]),
        n_valid=np.stack([np.asarray(g.n_valid) for g in graphs]),
        m_valid=np.stack([np.asarray(g.m_valid) for g in graphs]),
        n_max=g0.n_max,
        m_max=g0.m_max,
        sorted_by=g0.sorted_by,
    )
