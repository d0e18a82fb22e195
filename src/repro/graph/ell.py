"""Degree-bucketed ELL (padded neighbor-list) layout for the Pallas kernel path.

TPU adaptation of the per-vertex neighborhood loops (DESIGN.md §2): vertices
are grouped by degree into buckets of fixed width W ∈ BUCKET_WIDTHS; within a
bucket, neighbor ids/weights are dense (rows, W) tiles — ideal for VMEM
BlockSpecs.  Vertices with deg > max(W) fall back to the sort+segment path
(the "tail"), mirroring how high-degree hubs get special-cased in parallel
community detection codes.

The full multi-width bucketing is a HOST-side build: row capacities are
data-dependent (a jit-native rebuild would need n_max-row buckets per
width), so it applies to the finest (level-0) graph only.  Coarse levels
inside the capacity-scheduled cascade use ``traced_ell_tile`` instead — a
jit-traceable single-bucket rebuild at a STATIC per-stage width over the
src-sorted coarse edge list, with above-width vertices flagged for the
edge-list tail fallback (DESIGN.md §Pipeline).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.graph.structure import Graph

BUCKET_WIDTHS = (16, 64, 256, 1024)
# On a TPU the pallas backend's scoring kernel holds an (R_blk, W, W)
# pairwise tensor that outgrows VMEM past W=256 even at one sublane of rows
# (8·1024² f32 = 32 MiB), so its layout stops there and wider rows join the
# hub tail.  Off the chip the interpret-mode kernels take every width.
TPU_PALLAS_BUCKET_WIDTHS = (16, 64, 256)
ROW_PAD = 8  # sublane alignment for (rows, W) tiles
CHUNK_ELEMS = 1 << 15  # target neighbor slots per stacked chunk (DESIGN.md §Kernels)


@dataclasses.dataclass(frozen=True)
class EllBucket:
    width: int
    rows: np.ndarray      # int32[R] vertex id per row (sentinel n_max for padding rows)
    nbr: np.ndarray       # int32[R, W] neighbor vertex ids (sentinel n_max pad)
    w: np.ndarray         # float32[R, W] edge weights (0 pad)
    n_rows_valid: int


@dataclasses.dataclass(frozen=True)
class EllGraph:
    n_max: int
    buckets: Tuple[EllBucket, ...]
    tail_vertices: np.ndarray     # int32[T] vertices handled by the sort path
    tail_edge_idx: np.ndarray     # int64[K] indices into the dst-sorted edge list
    loop_w: np.ndarray            # float32[n_max] doubled self-loop weight per vertex
    deg_w: np.ndarray             # float32[n_max]

    @property
    def has_tail(self) -> bool:
        return self.tail_vertices.size > 0


def build_ell(
    g: Graph,
    widths: Tuple[int, ...] = BUCKET_WIDTHS,
    include_loops: bool = False,
) -> EllGraph:
    """Host-side ELL build.  Rows are IN-neighborhoods (edges grouped by dst);
    by symmetry these equal out-neighborhoods.  Self-loops are excluded from
    neighbor tiles by default (they are never move candidates) and reported
    separately via ``loop_w``.
    """
    src, dst, w = g.to_numpy_edges()
    n = g.n_max

    loop_w = np.zeros(n, dtype=np.float32)
    np.add.at(loop_w, src[src == dst], w[src == dst])
    deg_w = np.zeros(n, dtype=np.float32)
    np.add.at(deg_w, src, w)

    # Sort the FULL list by (dst, src) first: tail_edge_idx must index the
    # same dst-sorted view that to_device reconstructs when it materializes
    # the tail edge arrays.
    order = np.lexsort((src, dst))
    src, dst, w = src[order], dst[order], w[order]
    deg_full = np.zeros(n, dtype=np.int64)
    np.add.at(deg_full, dst, 1)
    row_ptr_full = np.concatenate([[0], np.cumsum(deg_full)])

    if not include_loops:
        keep = src != dst
        src_b, dst_b, w_b = src[keep], dst[keep], w[keep]
    else:
        src_b, dst_b, w_b = src, dst, w
    deg = np.zeros(n, dtype=np.int64)
    np.add.at(deg, dst_b, 1)
    row_ptr = np.concatenate([[0], np.cumsum(deg)])
    src, dst, w = src_b, dst_b, w_b

    max_w = widths[-1]
    buckets: List[EllBucket] = []
    prev = 0
    # prefix sums of the (dst, src)-sorted neighbor ids: per-row neighbor
    # min is the first entry of the run, the mean comes from the cumsum
    src_cum = np.concatenate([[0.0], np.cumsum(src, dtype=np.float64)])
    for W in widths:
        vids = np.where((deg > prev) & (deg <= W))[0]
        prev = W
        if len(vids):
            # neighbor-ID locality order (Sahu, arXiv:2301.12390): rows whose
            # neighborhoods touch nearby vertex ids become adjacent, so each
            # row-block of the streamed kernel reads a narrow table window
            lo_n = src[row_ptr[vids]]          # in-row neighbors are sorted
            mean_n = ((src_cum[row_ptr[vids + 1]] - src_cum[row_ptr[vids]])
                      / deg[vids])
            vids = vids[np.lexsort((vids, mean_n, lo_n))]
        R = int(np.ceil(max(1, len(vids)) / ROW_PAD) * ROW_PAD)
        rows = np.full(R, n, dtype=np.int32)
        nbr = np.full((R, W), n, dtype=np.int32)
        ww = np.zeros((R, W), dtype=np.float32)
        for r, v in enumerate(vids):
            lo, hi = row_ptr[v], row_ptr[v + 1]
            rows[r] = v
            nbr[r, : hi - lo] = src[lo:hi]
            ww[r, : hi - lo] = w[lo:hi]
        buckets.append(EllBucket(W, rows, nbr, ww, len(vids)))

    tail_vertices = np.where(deg > max_w)[0].astype(np.int32)
    tail_edges = []
    for v in tail_vertices:  # index into the FULL dst-sorted list (loops incl.)
        tail_edges.append(np.arange(row_ptr_full[v], row_ptr_full[v + 1], dtype=np.int64))
    tail_edge_idx = (
        np.concatenate(tail_edges) if tail_edges else np.zeros(0, dtype=np.int64)
    )
    return EllGraph(
        n_max=n,
        buckets=tuple(buckets),
        tail_vertices=tail_vertices,
        tail_edge_idx=tail_edge_idx,
        loop_w=loop_w,
        deg_w=deg_w.astype(np.float32),
    )


# ------------------------------------------------------------ traced rebucketing


def traced_ell_tile(
    g: Graph, width: int
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Jit-traceable single-bucket ELL view of a src-sorted coarse graph.

    The ``build_ell`` equivalent for graphs built INSIDE a compiled program
    (the cascade's coarse levels, DESIGN.md §Pipeline): one (n_max, width)
    neighbor tile with row v holding vertex v's non-loop out-edges — by the
    directed-symmetric convention these equal the in-neighborhoods
    ``build_ell`` buckets — rebuilt per level from CSR row pointers in O(n·W)
    gathers, no data-dependent shapes.  Vertices whose degree exceeds the
    static ``width`` are flagged ``is_tail`` and their row is masked to pure
    padding; the engine evaluates them through the tables tail evaluator
    over the full edge list (gated out when no tail exists at runtime).

    Returns ``(rows[n], nbr[n, W], w[n, W], is_tail[n])`` with the same
    sentinel conventions as ``EllBucket`` (row id / neighbor id ``n_max``
    and weight 0 mark padding).
    """
    n, m = g.n_max, g.m_max
    if g.sorted_by != "src":
        raise ValueError("traced_ell_tile requires a src-sorted graph")
    rp = g.row_ptr()
    deg = rp[1:] - rp[:-1]
    vmask = g.vertex_mask()
    is_tail = vmask & (deg > width)
    arange_n = jnp.arange(n, dtype=jnp.int32)
    rows = jnp.where(vmask & ~is_tail, arange_n, n)
    j = jnp.arange(width, dtype=jnp.int32)
    idx = jnp.clip(rp[:-1, None] + j[None, :], 0, max(m - 1, 0))
    take = (j[None, :] < deg[:, None]) & (rows < n)[:, None]
    nbr = jnp.where(take, g.dst[idx], n)
    wt = jnp.where(take, g.w[idx], 0.0)
    # self-loops are never move candidates (Graph convention): mask to sink
    loop = nbr == arange_n[:, None]
    return rows, jnp.where(loop, n, nbr), jnp.where(loop, 0.0, wt), is_tail


# ------------------------------------------------------------ device layout
#
# The sweep engine (core/engine.py) runs the whole local-moving phase inside
# one jitted lax.while_loop, so bucket tiles must be device-resident pytree
# leaves (host numpy would force a transfer per sweep).  Each bucket is
# stacked into one (n_chunks, rows_per_chunk, W) array; the fused local_move
# kernel (DESIGN.md §Kernels) consumes it through ``grid_view`` as a single
# (n_chunks·rows_per_chunk, W) tile, so chunks become independent grid steps
# of one dispatch — the chunk dim is kept for layout/debug tooling, not for
# a scan chain.


def _rows_per_chunk(width: int, target_elems: int = CHUNK_ELEMS) -> int:
    return max(ROW_PAD, (target_elems // max(1, width)) // ROW_PAD * ROW_PAD)


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["win_blk"],
    meta_fields=["slot", "block_rows", "n_slots"],
)
@dataclasses.dataclass(frozen=True)
class TableWindows:
    """Per-row-block table-window metadata for the streamed local_move path.

    Block b of ``block_rows`` consecutive (locality-ordered) rows touches
    vertex ids within [win_blk[b]·slot, win_blk[b]·slot + 2·slot): the
    streamed kernel DMAs exactly that slice of each per-vertex table per
    grid step (DESIGN.md §Kernels).  ``slot`` (the window offset stride,
    a multiple of the 128-entry lane) and ``n_slots`` (rows of the
    overlapped (n_slots, 2·slot) table view) are STATIC; ``win_blk`` is the
    int32[n_blocks] slot index per block, consumed as a scalar-prefetch
    operand.  Padding/sentinel ids are masked in the kernel and need no
    window coverage.
    """

    win_blk: jax.Array
    slot: int
    block_rows: int
    n_slots: int


def compute_windows(rows: np.ndarray, nbr: np.ndarray, n_max: int,
                    block_rows: int) -> TableWindows:
    """Host-side window build over a bucket's flattened (R,)/(R, W) tiles.

    Per block of ``block_rows`` rows: [lo, hi) spans every REAL id the block
    touches (row ids and neighbor ids; sentinel padding excluded).  The slot
    stride is the max block span rounded up to the lane width, so every
    block's span fits one 2-slot overlapped window regardless of alignment.
    """
    from repro.kernels.common import TABLE_LANE, cdiv

    R = rows.shape[0]
    nb = max(1, cdiv(R, block_rows))
    pad = nb * block_rows - R
    rows_p = np.concatenate([rows, np.full(pad, n_max, rows.dtype)])
    nbr_p = np.concatenate(
        [nbr, np.full((pad, nbr.shape[1]), n_max, nbr.dtype)])
    rows2 = rows_p.reshape(nb, block_rows)
    nbr2 = nbr_p.reshape(nb, block_rows, -1)

    lo = np.minimum(
        np.where(rows2 < n_max, rows2, n_max).min(axis=1),
        np.where(nbr2 < n_max, nbr2, n_max).min(axis=(1, 2)),
    ).astype(np.int64)
    hi = np.maximum(
        np.where(rows2 < n_max, rows2, -1).max(axis=1),
        np.where(nbr2 < n_max, nbr2, -1).max(axis=(1, 2)),
    ).astype(np.int64) + 1
    empty = hi <= lo          # all-padding block: any window works
    lo[empty], hi[empty] = 0, 1

    span = int((hi - lo).max())
    slot = int(np.ceil(max(span, 1) / TABLE_LANE) * TABLE_LANE)
    return TableWindows(
        win_blk=jnp.asarray((lo // slot).astype(np.int32)),
        slot=slot,
        block_rows=int(block_rows),
        n_slots=max(1, cdiv(n_max + 1, slot)),
    )


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["rows", "nbr", "w", "windows"],
    meta_fields=["width", "n_rows_valid"],
)
@dataclasses.dataclass(frozen=True)
class DeviceBucket:
    """One degree bucket, chunk-stacked for the local_move Pallas grid.

    rows: int32[C, Rc]      vertex id per row (sentinel n_max for padding)
    nbr:  int32[C, Rc, W]   neighbor ids (sentinel n_max padding)
    w:    float32[C, Rc, W] edge weights (0 padding)

    ``n_rows_valid`` is STATIC (a pytree meta field): the host-side bucketing
    knows how many rows are real, so the sweep engine can skip all-padding
    buckets at trace time instead of evaluating pure-sentinel tiles.
    ``windows`` is the per-row-block table-window metadata enabling the
    streamed (beyond-VMEM) kernel path; None for hand-built buckets, which
    then support the resident path only.
    """

    rows: jax.Array
    nbr: jax.Array
    w: jax.Array
    width: int
    n_rows_valid: int = -1  # -1 = unknown (treated as non-empty)
    windows: Optional[TableWindows] = None


def grid_view(b: DeviceBucket) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Collapse the chunk dim: ``(rows[C·Rc], nbr[C·Rc, W], w[C·Rc, W])``.

    This is the layout the fused local_move kernel grids over — one 1-D grid
    of row-blocks spanning ALL chunks of the bucket (grid length =
    n_chunks × row_blocks_per_chunk), replacing the old per-bucket lax.scan
    chain.  The stack is chunk-major contiguous, so the reshape is free.
    """
    W = b.width
    return b.rows.reshape(-1), b.nbr.reshape(-1, W), b.w.reshape(-1, W)


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["buckets", "tail_src", "tail_dst", "tail_w", "is_tail"],
    meta_fields=["n_max", "has_tail"],
)
@dataclasses.dataclass(frozen=True)
class DeviceEll:
    """Device-resident ELL layout consumed inside the fused sweep loop.

    Tail edges are pre-extracted (src, dst, w) arrays so the per-sweep
    ``lexsort`` of the legacy path is hoisted out of the loop entirely.
    """

    buckets: Tuple[DeviceBucket, ...]
    tail_src: jax.Array   # int32[K]
    tail_dst: jax.Array   # int32[K]
    tail_w: jax.Array     # float32[K]
    is_tail: jax.Array    # bool[n_max]
    n_max: int
    has_tail: bool


def to_device(g: Graph, e: EllGraph, rows_per_chunk: Optional[int] = None,
              block_rows: Optional[int] = None) -> DeviceEll:
    """Stack an EllGraph into the device-resident scan layout (one-time cost).

    ``block_rows`` overrides the streamed-path row-block granularity (and
    thereby the window size).  The default is ``pick_row_block_fused(W)``
    with no table charge — the UPPER BOUND of the resident row block, which
    the resident path shrinks further by its table-scratch bytes — so the
    streamed grid is at least as coarse as the resident one.
    """
    from repro.kernels.common import pick_row_block_fused

    n = e.n_max
    buckets: List[DeviceBucket] = []
    for b in e.buckets:
        W = b.width
        rc = rows_per_chunk or _rows_per_chunk(W)
        r = b.rows.shape[0]
        r_pad = int(np.ceil(max(1, r) / rc) * rc)
        rows = np.full(r_pad, n, dtype=np.int32)
        nbr = np.full((r_pad, W), n, dtype=np.int32)
        ww = np.zeros((r_pad, W), dtype=np.float32)
        rows[:r], nbr[:r], ww[:r] = b.rows, b.nbr, b.w
        c = r_pad // rc
        br = min(block_rows or pick_row_block_fused(W), r_pad)
        buckets.append(
            DeviceBucket(
                rows=jnp.asarray(rows.reshape(c, rc)),
                nbr=jnp.asarray(nbr.reshape(c, rc, W)),
                w=jnp.asarray(ww.reshape(c, rc, W)),
                width=W,
                n_rows_valid=b.n_rows_valid,
                windows=compute_windows(rows, nbr, n, br),
            )
        )

    # materialize tail edges from the same dst-sorted view build_ell indexed
    src, dst, w = g.to_numpy_edges()
    order = np.lexsort((src, dst))
    src, dst, w = src[order], dst[order], w[order]
    idx = e.tail_edge_idx
    is_tail = np.zeros(n, dtype=bool)
    is_tail[e.tail_vertices] = True
    return DeviceEll(
        buckets=tuple(buckets),
        tail_src=jnp.asarray(src[idx].astype(np.int32)),
        tail_dst=jnp.asarray(dst[idx].astype(np.int32)),
        tail_w=jnp.asarray(w[idx].astype(np.float32)),
        is_tail=jnp.asarray(is_tail),
        n_max=n,
        has_tail=bool(e.tail_vertices.size),
    )


def bucket_widths(backend: str) -> Tuple[int, ...]:
    """Level-0 bucket widths for the ``ell`` or ``pallas`` backend."""
    from repro.kernels.common import on_tpu

    if backend == "pallas" and on_tpu():
        return TPU_PALLAS_BUCKET_WIDTHS
    return BUCKET_WIDTHS


def build_device_ell(
    g: Graph,
    widths: Tuple[int, ...] = BUCKET_WIDTHS,
    rows_per_chunk: Optional[int] = None,
) -> DeviceEll:
    """build_ell + to_device in one call (the engine's default path)."""
    return to_device(g, build_ell(g, widths), rows_per_chunk)


def ell_stats(e: EllGraph) -> dict:
    out = {"n": e.n_max, "tail_vertices": int(e.tail_vertices.size)}
    total_slots = 0
    used_slots = 0
    for b in e.buckets:
        total_slots += b.nbr.size
        used_slots += int((b.nbr < e.n_max).sum())
        out[f"bucket_w{b.width}_rows"] = b.n_rows_valid
    out["slot_utilization"] = used_slots / max(1, total_slots)
    return out
