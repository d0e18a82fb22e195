"""Core graph structure.

Representation (DESIGN.md §2): an undirected weighted graph is stored as a
**directed-symmetric** edge list —

  * every undirected edge {u, v}, u != v, appears as BOTH (u, v, w) and (v, u, w);
  * a self-loop on v appears ONCE as (v, v, w_loop) where ``w_loop`` is the
    *doubled* loop weight ("loops are counted twice", paper §II-A).  Louvain
    aggregation produces exactly this form: the self-edge of a super-vertex
    carries the full directed intra-community weight.

With that convention everything is a plain segment reduction over ``src``:

  deg_w(v)  = segment_sum(w, src)[v]                      (loops counted twice)
  vol_w(V)  = sum(w)                                      ("2W")
  cut_w(v,S)= sum of w over out-edges into S, loops excluded

All arrays have **static capacity** (``n_max`` vertices / ``m_max`` directed
edges) with validity masks, so multi-level coarsening reuses the same buffers
under jit — the TPU answer to Arkouda's dynamically-sized GroupBy outputs.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["src", "dst", "w", "edge_mask", "n_valid", "m_valid"],
    meta_fields=["n_max", "m_max", "sorted_by"],
)
@dataclasses.dataclass(frozen=True)
class Graph:
    """Directed-symmetric weighted graph with static capacity.

    Attributes:
      src, dst:  int32[m_max] endpoints (invalid entries hold ``n_max`` sentinels)
      w:         float32[m_max] edge weights (0 for invalid entries)
      edge_mask: bool[m_max] validity
      n_valid:   int32 scalar — number of live vertices (vertices are [0, n_valid))
      m_valid:   int32 scalar — number of live directed edges
      n_max, m_max: static capacities
      sorted_by: "src" | "dst" | None — current sort invariant (static metadata)
    """

    src: jax.Array
    dst: jax.Array
    w: jax.Array
    edge_mask: jax.Array
    n_valid: jax.Array
    m_valid: jax.Array
    n_max: int
    m_max: int
    sorted_by: Optional[str]

    # ---- derived quantities (all jit-safe) ----

    def vertex_mask(self) -> jax.Array:
        return jnp.arange(self.n_max, dtype=jnp.int32) < self.n_valid

    def weighted_degrees(self) -> jax.Array:
        """deg_w(v): sum of out-edge weights (self-loops stored doubled)."""
        return jax.ops.segment_sum(
            jnp.where(self.edge_mask, self.w, 0.0), self.src, num_segments=self.n_max
        )

    def unweighted_degrees(self) -> jax.Array:
        ones = jnp.where(self.edge_mask, 1, 0)
        return jax.ops.segment_sum(ones, self.src, num_segments=self.n_max)

    def total_volume(self) -> jax.Array:
        """vol_w(V) = 2W (sum of all directed weights incl. doubled loops)."""
        return jnp.sum(jnp.where(self.edge_mask, self.w, 0.0))

    def is_loop(self) -> jax.Array:
        return self.edge_mask & (self.src == self.dst)

    def loop_weights(self) -> jax.Array:
        """Per-vertex (doubled) self-loop weight."""
        lw = jnp.where(self.is_loop(), self.w, 0.0)
        return jax.ops.segment_sum(lw, self.src, num_segments=self.n_max)

    def row_ptr(self) -> jax.Array:
        """CSR row pointers — requires ``sorted_by == 'src'``."""
        if self.sorted_by != "src":
            raise ValueError("row_ptr requires the graph sorted by src")
        return jnp.searchsorted(
            self.src, jnp.arange(self.n_max + 1, dtype=self.src.dtype), side="left"
        ).astype(jnp.int32)

    # ---- host-side views ----

    def to_numpy_edges(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(src, dst, w) of valid directed edges, as host numpy."""
        mask = np.asarray(self.edge_mask)
        return (
            np.asarray(self.src)[mask],
            np.asarray(self.dst)[mask],
            np.asarray(self.w)[mask],
        )

    def n(self) -> int:
        return int(self.n_valid)

    def m_directed(self) -> int:
        return int(self.m_valid)

    def __repr__(self) -> str:  # concise; avoids materializing arrays in logs
        return (
            f"Graph(n_max={self.n_max}, m_max={self.m_max}, sorted_by={self.sorted_by!r})"
        )


def host_graph(
    src,
    dst,
    w,
    *,
    n_max: int,
    m_max: Optional[int] = None,
    n_valid: Optional[int] = None,
    sorted_by: Optional[str] = None,
) -> Graph:
    """Pad already-symmetrized directed edge arrays to capacity ON THE HOST:
    a ``Graph`` whose leaves are numpy arrays (invalid entries hold the
    ``n_max`` sentinel, zero weight and a clear ``edge_mask``).

    Nothing runs on the device, so no program is traced or compiled for
    the raw edge count; ``jax.device_put`` of the result moves each array
    once (``graph_from_arrays``), and graphs of one capacity stack on the
    host before a single transfer (``graph.packing``).
    """
    src, dst, w = np.asarray(src), np.asarray(dst), np.asarray(w)
    m = src.shape[0]
    # floor the edge capacity at 1: an edgeless graph keeps one fully-masked
    # padding slot so every kernel's static-shape assumption (m_max >= 1,
    # e.g. the GroupBy's (m-1,) run-start buffer) holds on degenerate input
    m_max = m_max or max(m, 1)
    if m_max < m:
        raise ValueError(f"m_max={m_max} < m={m}")
    if n_valid is not None and n_valid > n_max:
        raise ValueError(f"n_valid={n_valid} > n_max={n_max}")
    pad = m_max - m
    sentinel = np.full((pad,), n_max, np.int32)
    return Graph(
        src=np.concatenate([src.astype(np.int32), sentinel]),
        dst=np.concatenate([dst.astype(np.int32), sentinel]),
        w=np.concatenate([w.astype(np.float32), np.zeros((pad,), np.float32)]),
        edge_mask=np.arange(m_max) < m,
        n_valid=np.int32(n_max if n_valid is None else n_valid),
        m_valid=np.int32(m),
        n_max=int(n_max),
        m_max=int(m_max),
        sorted_by=sorted_by,
    )


def graph_from_arrays(
    src: jax.Array,
    dst: jax.Array,
    w: jax.Array,
    *,
    n_max: int,
    m_max: Optional[int] = None,
    n_valid: Optional[int] = None,
    sorted_by: Optional[str] = None,
    validate: Optional[bool] = None,
) -> Graph:
    """Wrap already-symmetrized directed edge arrays, padded to capacity on
    the host (``host_graph``) and moved to the device one array each.

    ``validate`` runs the STRUCTURAL half of ``builders.validate_graph``
    (mask counts, id ranges, weight finiteness, sort invariant) on the
    result; symmetry is deliberately not enforced here because callers hand
    this function deliberately one-sided intermediates.  None defers to
    ``builders.DEFAULT_VALIDATE`` (flipped on by the test conftest).
    """
    g = host_graph(src, dst, w, n_max=n_max, m_max=m_max, n_valid=n_valid,
                   sorted_by=sorted_by)
    from repro.graph import builders  # late: builders imports this module
    if builders.DEFAULT_VALIDATE if validate is None else validate:
        builders.validate_graph(g, symmetry=False)
    return jax.device_put(g)
