"""Pallas TPU kernels: fused gather + local-move scoring (DESIGN.md §Kernels).

The legacy ELL path materialized four gathered (rows, W) tiles in HBM before
every ``label_argmax`` / ``delta_q_argmax`` launch and serialized chunks
through a per-bucket ``lax.scan``.  Here the per-vertex tables never leave
the kernel family: every per-neighbor gather happens in-kernel, and the only
HBM traffic per row-block is the neighbor tile plus two (R_blk, 1) outputs.
Two table layouts exist, selected by the VMEM-byte budget in
``kernels/common.py`` (``resolve_table_mode``):

* **resident** (fast path, tables fit VMEM): whole (n+1,) tables ride along
  in the ANY memory space and are DMA'd once into VMEM scratch on the first
  grid step; scratch persists, later row-blocks reuse the copies.
  INVARIANT: the grid keeps the default sequential ("arbitrary") semantics —
  a parallel dimension would hand later steps never-DMA'd scratch.

* **streamed** (beyond-VMEM): each grid step reads only its row-block's
  TABLE WINDOW.  Host-side locality ordering (graph/ell.py) makes each
  block's ids span a narrow range [lo, hi); ``TableWindows`` publishes the
  per-block slot index ``win_blk[b] = lo // slot`` as a scalar-prefetch
  operand and the table is presented as an OVERLAPPED (n_slots, 2·slot)
  view (row k covers flat[k·slot : k·slot + 2·slot)), so the BlockSpec
  index map ``(win_blk[b], 0)`` lands the window at slot granularity.  The
  window is a regular blocked input: Pallas's pipeline double-buffers it,
  prefetching block b+1's windows while block b scores, and — because no
  scratch state crosses grid steps — the grid dimension is declared
  PARALLEL (megacore-able).  In-kernel gathers are rebased to window-local
  indices via ``win_lo = win_blk[b]·slot``.

Grid scheme: one pallas_call per degree bucket with a 1-D grid over
row-blocks spanning ALL chunks of the bucket (the (n_chunks, rows, W) stack
of ``graph/ell.to_device`` collapses to (n_chunks·rows, W) for free).
``pick_row_block_fused`` sizes the resident R_blk, charging the table
scratch against the VMEM budget; the streamed block size is pinned by the
window metadata (``TableWindows.block_rows``).

The scoring math lives in ref.py (which itself delegates to the
label_argmax / delta_q oracles): each kernel body is table-DMA/window-load +
the SAME traced gather+score code as the oracle path (sentinel ids are
masked to sink values, never read), so kernel ≡ ref bit-compatibility holds
by construction for both layouts.  Louvain runs on the per-VERTEX composed
tables of ``ref.compose_louvain_tables`` so every in-kernel gather is
vertex-indexed and therefore window-friendly.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import TABLE_LANE, cdiv, pick_row_block_fused
from repro.kernels.local_move.ref import (
    local_move_louvain_tables_ref,
    local_move_plp_ref,
)


def _pad_table(tab: jax.Array, fill) -> jax.Array:
    """Pad a (n+1,) table to a lane multiple for the ANY→VMEM copy."""
    m = tab.shape[0]
    pad = (-m) % TABLE_LANE
    return jnp.pad(tab, (0, pad), constant_values=fill) if pad else tab


def _copy_tables_once(table_refs, scratch_refs, sem):
    """DMA every table into VMEM scratch on the first grid step only;
    scratch persists across grid steps, so later blocks reuse the copies.
    Relies on the sequential ("arbitrary") grid execution order — see the
    module-docstring INVARIANT (the STREAMED kernels have no such state and
    run under a parallel grid)."""

    @pl.when(pl.program_id(0) == 0)
    def _():
        for src, dst in zip(table_refs, scratch_refs):
            cp = pltpu.make_async_copy(src, dst, sem)
            cp.start()
            cp.wait()


def window_flat(tab: jax.Array, slot: int, n_slots: int, fill) -> jax.Array:
    """Flat table padded to (n_slots+1)·slot so every 2-slot window slice
    [k·slot, k·slot + 2·slot) is in range for k < n_slots.  Shared by the
    overlapped BlockSpec view below and the pure-jnp windowed oracle's
    ``dynamic_slice`` (ops.py) — ONE copy of the padding invariant.
    Padding beyond id n is never read (sentinel ids are masked in
    ref._gather), ``fill`` just keeps it typed."""
    pad = (n_slots + 1) * slot - tab.shape[0]
    return jnp.pad(tab, (0, pad), constant_values=fill) if pad else tab


def _window_view(tab: jax.Array, slot: int, n_slots: int, fill) -> jax.Array:
    """Overlapped (n_slots, 1, 2·slot) window view of a flat (n+1,) table.

    Row k covers flat[k·slot : k·slot + 2·slot): window offsets get slot
    granularity from a plain BlockSpec index map even though block indices
    are multiplied by the block shape.  The unit middle axis makes the
    block's last two dims equal the array's, the form Mosaic accepts for a
    one-row block (a (1, 2·slot) block of a 2-D view is refused: the
    second-to-last block dim must be a multiple of 8).  Built per sweep
    from live tables by pad + reshape + concat — XLA fuses it; the 2× copy
    lives in HBM, which is the point of streaming.
    """
    t2 = window_flat(tab, slot, n_slots, fill).reshape(n_slots + 1, slot)
    return jnp.concatenate([t2[:-1], t2[1:]], axis=1)[:, None, :]


def _window_spec(slot: int) -> pl.BlockSpec:
    """This grid step's (1, 2·slot) window of an overlapped view."""
    return pl.BlockSpec((pl.squeezed, 1, 2 * slot),
                        lambda i, wb: (wb[i], 0, 0))


def _pad_tiles(rows, nbr, w, r_blk: int, sentinel: int):
    R = rows.shape[0]
    pad = (-R) % r_blk
    if pad:
        rows = jnp.pad(rows, (0, pad), constant_values=sentinel)
        nbr = jnp.pad(nbr, ((0, pad), (0, 0)), constant_values=sentinel)
        w = jnp.pad(w, ((0, pad), (0, 0)))
    return rows, nbr, w, R + pad


def _check_windows(windows, R: int):
    nb = windows.win_blk.shape[0]
    if cdiv(R, windows.block_rows) != nb:
        raise ValueError(
            f"window metadata mismatch: {nb} blocks of "
            f"{windows.block_rows} rows vs {R} tile rows — windows must be "
            f"computed over the same (padded) bucket layout they score")
    return nb


# ----------------------------------------------------------------- PLP


def _local_move_plp_kernel(
    lab_tab_ref,   # (n_pad,) int32 in ANY — whole labels_ext table
    rows_ref,      # (R_blk, 1) int32
    nbr_ref,       # (R_blk, W) int32
    w_ref,         # (R_blk, W) float32
    seed_ref,      # (1, 1) int32
    out_lab_ref,   # (R_blk, 1) int32
    out_prop_ref,  # (R_blk, 1) int32 (0/1)
    lab_vmem,      # (n_pad,) int32 VMEM scratch
    sem,
    *,
    sentinel: int,
    tie_eps: float,
):
    _copy_tables_once((lab_tab_ref,), (lab_vmem,), sem)
    # gathers + scoring run in-kernel on the VMEM-resident table, through the
    # SAME code as the oracle path (ref.py); sentinel ids are masked to the
    # sink value, real ids index inside [0, n], so the lane padding of the
    # (n_pad,) scratch is never read
    best_lab, prop = local_move_plp_ref(
        rows_ref[...][:, 0],
        nbr_ref[...],
        w_ref[...],
        lab_vmem[...],
        seed_ref[0, 0].astype(jnp.uint32),
        tie_eps=tie_eps,
        sentinel=sentinel,
    )
    out_lab_ref[...] = best_lab[:, None]
    out_prop_ref[...] = prop.astype(jnp.int32)[:, None]


def _local_move_plp_streamed_kernel(
    win_ref,       # (n_blocks,) int32 scalar-prefetch — slot index per block
    rows_ref,      # (R_blk, 1) int32
    nbr_ref,       # (R_blk, W) int32
    w_ref,         # (R_blk, W) float32
    seed_ref,      # (1, 1) int32
    lab_win_ref,   # (1, 2·slot) int32 — this block's window of labels_ext
    out_lab_ref,   # (R_blk, 1) int32
    out_prop_ref,  # (R_blk, 1) int32 (0/1)
    *,
    sentinel: int,
    tie_eps: float,
    slot: int,
):
    base = win_ref[pl.program_id(0)] * slot
    best_lab, prop = local_move_plp_ref(
        rows_ref[...][:, 0],
        nbr_ref[...],
        w_ref[...],
        lab_win_ref[...].reshape(-1),
        seed_ref[0, 0].astype(jnp.uint32),
        tie_eps=tie_eps,
        sentinel=sentinel,
        win_lo=base,
    )
    out_lab_ref[...] = best_lab[:, None]
    out_prop_ref[...] = prop.astype(jnp.int32)[:, None]


def local_move_plp_pallas(
    rows: jax.Array,        # (R,) int32
    nbr: jax.Array,         # (R, W) int32
    w: jax.Array,           # (R, W) float32
    labels_ext: jax.Array,  # (n+1,) int32
    seed: jax.Array,        # scalar int/uint32
    *,
    tie_eps: float,
    sentinel: int,
    interpret: bool,
    row_block: int | None = None,
    vmem_budget: int | None = None,
) -> Tuple[jax.Array, jax.Array]:
    R, W = nbr.shape
    tab = _pad_table(labels_ext, sentinel)
    n_pad = tab.shape[0]
    r_blk = row_block or min(
        pick_row_block_fused(W, vmem_budget, table_bytes=4 * n_pad), R)
    rows, nbr, w, Rp = _pad_tiles(rows, nbr, w, r_blk, sentinel)

    kern = functools.partial(
        _local_move_plp_kernel, sentinel=sentinel, tie_eps=tie_eps
    )
    wide = lambda: pl.BlockSpec((r_blk, W), lambda i: (i, 0))
    col = lambda: pl.BlockSpec((r_blk, 1), lambda i: (i, 0))
    out_lab, out_prop = pl.pallas_call(
        kern,
        grid=(Rp // r_blk,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            col(), wide(), wide(),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=[col(), col()],
        out_shape=[
            jax.ShapeDtypeStruct((Rp, 1), jnp.int32),
            jax.ShapeDtypeStruct((Rp, 1), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((n_pad,), jnp.int32),
            pltpu.SemaphoreType.DMA,
        ],
        interpret=interpret,
    )(
        tab,
        rows[:, None],
        nbr,
        w,
        jnp.asarray(seed, jnp.int32).reshape(1, 1),
    )
    return out_lab[:R, 0], out_prop[:R, 0]


def local_move_plp_pallas_streamed(
    rows: jax.Array,        # (R,) int32
    nbr: jax.Array,         # (R, W) int32
    w: jax.Array,           # (R, W) float32
    labels_ext: jax.Array,  # (n+1,) int32
    seed: jax.Array,        # scalar int/uint32
    *,
    tie_eps: float,
    sentinel: int,
    interpret: bool,
    windows,                # graph.ell.TableWindows
) -> Tuple[jax.Array, jax.Array]:
    R, W = nbr.shape
    nb = _check_windows(windows, R)
    r_blk, S = windows.block_rows, windows.slot
    rows, nbr, w, Rp = _pad_tiles(rows, nbr, w, r_blk, sentinel)
    ov = _window_view(labels_ext, S, windows.n_slots, sentinel)

    kern = functools.partial(
        _local_move_plp_streamed_kernel,
        sentinel=sentinel, tie_eps=tie_eps, slot=S,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((r_blk, 1), lambda i, wb: (i, 0)),
            pl.BlockSpec((r_blk, W), lambda i, wb: (i, 0)),
            pl.BlockSpec((r_blk, W), lambda i, wb: (i, 0)),
            pl.BlockSpec((1, 1), lambda i, wb: (0, 0)),
            _window_spec(S),
        ],
        out_specs=[
            pl.BlockSpec((r_blk, 1), lambda i, wb: (i, 0)),
            pl.BlockSpec((r_blk, 1), lambda i, wb: (i, 0)),
        ],
    )
    out_lab, out_prop = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((Rp, 1), jnp.int32),
            jax.ShapeDtypeStruct((Rp, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(
        windows.win_blk,
        rows[:, None],
        nbr,
        w,
        jnp.asarray(seed, jnp.int32).reshape(1, 1),
        ov,
    )
    return out_lab[:R, 0], out_prop[:R, 0]


# ----------------------------------------------------------------- Louvain


def _local_move_louvain_kernel(
    com_tab_ref,   # (n_pad,) int32 in ANY — com_v (per-vertex community)
    vol_tab_ref,   # (n_pad,) float32 in ANY — volcom_v
    size_tab_ref,  # (n_pad,) int32 in ANY — sizecom_v
    deg_tab_ref,   # (n_pad,) float32 in ANY — deg_v
    rows_ref,      # (R_blk, 1) int32
    nbr_ref,       # (R_blk, W) int32
    w_ref,         # (R_blk, W) float32
    invvol_ref,    # (1, 1) float32
    out_cand_ref,  # (R_blk, 1) int32
    out_prop_ref,  # (R_blk, 1) int32 (0/1)
    com_vmem,
    vol_vmem,
    size_vmem,
    deg_vmem,
    sem,
    *,
    sentinel: int,
    singleton_rule: bool,
):
    _copy_tables_once(
        (com_tab_ref, vol_tab_ref, size_tab_ref, deg_tab_ref),
        (com_vmem, vol_vmem, size_vmem, deg_vmem),
        sem,
    )
    # gathers (candidate community + the Eq. 1 volume/size/degree terms, all
    # vertex-indexed thanks to compose_louvain_tables — five tiles that never
    # touch HBM) + scoring run in-kernel on the VMEM-resident tables, through
    # the SAME code as the oracle path (ref.py)
    best_cand, prop = local_move_louvain_tables_ref(
        rows_ref[...][:, 0],
        nbr_ref[...],
        w_ref[...],
        com_vmem[...],
        vol_vmem[...],
        size_vmem[...],
        deg_vmem[...],
        invvol_ref[0, 0],
        sentinel=sentinel,
        singleton_rule=singleton_rule,
    )
    out_cand_ref[...] = best_cand[:, None]
    out_prop_ref[...] = prop.astype(jnp.int32)[:, None]


def _local_move_louvain_streamed_kernel(
    win_ref,        # (n_blocks,) int32 scalar-prefetch — slot index per block
    rows_ref,       # (R_blk, 1) int32
    nbr_ref,        # (R_blk, W) int32
    w_ref,          # (R_blk, W) float32
    invvol_ref,     # (1, 1) float32
    com_win_ref,    # (1, 2·slot) int32 — window of com_v
    vol_win_ref,    # (1, 2·slot) float32 — window of volcom_v
    size_win_ref,   # (1, 2·slot) int32 — window of sizecom_v
    deg_win_ref,    # (1, 2·slot) float32 — window of deg_v
    out_cand_ref,   # (R_blk, 1) int32
    out_prop_ref,   # (R_blk, 1) int32 (0/1)
    *,
    sentinel: int,
    singleton_rule: bool,
    slot: int,
):
    base = win_ref[pl.program_id(0)] * slot
    best_cand, prop = local_move_louvain_tables_ref(
        rows_ref[...][:, 0],
        nbr_ref[...],
        w_ref[...],
        com_win_ref[...].reshape(-1),
        vol_win_ref[...].reshape(-1),
        size_win_ref[...].reshape(-1),
        deg_win_ref[...].reshape(-1),
        invvol_ref[0, 0],
        sentinel=sentinel,
        singleton_rule=singleton_rule,
        win_lo=base,
    )
    out_cand_ref[...] = best_cand[:, None]
    out_prop_ref[...] = prop.astype(jnp.int32)[:, None]


def local_move_louvain_pallas(
    rows: jax.Array,       # (R,) int32
    nbr: jax.Array,        # (R, W) int32
    w: jax.Array,          # (R, W) float32
    com_v: jax.Array,      # (n+1,) int32 — COMPOSED per-vertex tables
    volcom_v: jax.Array,   # (n+1,) float32  (ref.compose_louvain_tables,
    sizecom_v: jax.Array,  # (n+1,) int32     built once per sweep by the
    deg_v: jax.Array,      # (n+1,) float32   caller and shared by buckets)
    inv_vol: jax.Array,    # f32 scalar
    *,
    sentinel: int,
    singleton_rule: bool,
    interpret: bool,
    row_block: int | None = None,
    vmem_budget: int | None = None,
) -> Tuple[jax.Array, jax.Array]:
    R, W = nbr.shape
    com_t = _pad_table(com_v, sentinel)
    vol_t = _pad_table(volcom_v, 0)
    size_t = _pad_table(sizecom_v, 0)
    deg_t = _pad_table(deg_v, 0)
    n_pad = com_t.shape[0]
    r_blk = row_block or min(
        pick_row_block_fused(W, vmem_budget, table_bytes=4 * 4 * n_pad), R)
    rows, nbr, w, Rp = _pad_tiles(rows, nbr, w, r_blk, sentinel)

    kern = functools.partial(
        _local_move_louvain_kernel,
        sentinel=sentinel,
        singleton_rule=singleton_rule,
    )
    any_spec = lambda: pl.BlockSpec(memory_space=pl.ANY)
    wide = lambda: pl.BlockSpec((r_blk, W), lambda i: (i, 0))
    col = lambda: pl.BlockSpec((r_blk, 1), lambda i: (i, 0))
    out_cand, out_prop = pl.pallas_call(
        kern,
        grid=(Rp // r_blk,),
        in_specs=[
            any_spec(), any_spec(), any_spec(), any_spec(),
            col(), wide(), wide(),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=[col(), col()],
        out_shape=[
            jax.ShapeDtypeStruct((Rp, 1), jnp.int32),
            jax.ShapeDtypeStruct((Rp, 1), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((n_pad,), jnp.int32),
            pltpu.VMEM((n_pad,), jnp.float32),
            pltpu.VMEM((n_pad,), jnp.int32),
            pltpu.VMEM((n_pad,), jnp.float32),
            pltpu.SemaphoreType.DMA,
        ],
        interpret=interpret,
    )(
        com_t, vol_t, size_t, deg_t,
        rows[:, None],
        nbr,
        w,
        jnp.asarray(inv_vol, jnp.float32).reshape(1, 1),
    )
    return out_cand[:R, 0], out_prop[:R, 0]


def local_move_louvain_pallas_streamed(
    rows: jax.Array,       # (R,) int32
    nbr: jax.Array,        # (R, W) int32
    w: jax.Array,          # (R, W) float32
    com_v: jax.Array,      # (n+1,) int32 — COMPOSED per-vertex tables
    volcom_v: jax.Array,   # (n+1,) float32  (see local_move_louvain_pallas)
    sizecom_v: jax.Array,  # (n+1,) int32
    deg_v: jax.Array,      # (n+1,) float32
    inv_vol: jax.Array,    # f32 scalar
    *,
    sentinel: int,
    singleton_rule: bool,
    interpret: bool,
    windows,              # graph.ell.TableWindows
) -> Tuple[jax.Array, jax.Array]:
    R, W = nbr.shape
    nb = _check_windows(windows, R)
    r_blk, S = windows.block_rows, windows.slot
    rows, nbr, w, Rp = _pad_tiles(rows, nbr, w, r_blk, sentinel)
    ov_com = _window_view(com_v, S, windows.n_slots, sentinel)
    ov_vol = _window_view(volcom_v, S, windows.n_slots, 0)
    ov_size = _window_view(sizecom_v, S, windows.n_slots, 0)
    ov_deg = _window_view(deg_v, S, windows.n_slots, 0)

    kern = functools.partial(
        _local_move_louvain_streamed_kernel,
        sentinel=sentinel, singleton_rule=singleton_rule, slot=S,
    )
    win = lambda: _window_spec(S)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((r_blk, 1), lambda i, wb: (i, 0)),
            pl.BlockSpec((r_blk, W), lambda i, wb: (i, 0)),
            pl.BlockSpec((r_blk, W), lambda i, wb: (i, 0)),
            pl.BlockSpec((1, 1), lambda i, wb: (0, 0)),
            win(), win(), win(), win(),
        ],
        out_specs=[
            pl.BlockSpec((r_blk, 1), lambda i, wb: (i, 0)),
            pl.BlockSpec((r_blk, 1), lambda i, wb: (i, 0)),
        ],
    )
    out_cand, out_prop = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((Rp, 1), jnp.int32),
            jax.ShapeDtypeStruct((Rp, 1), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(
        windows.win_blk,
        rows[:, None],
        nbr,
        w,
        jnp.asarray(inv_vol, jnp.float32).reshape(1, 1),
        ov_com, ov_vol, ov_size, ov_deg,
    )
    return out_cand[:R, 0], out_prop[:R, 0]

