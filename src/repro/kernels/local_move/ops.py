"""Dispatch wrappers for the fused local_move kernels (pallas/oracle).

Plain jit-safe functions, deliberately NOT wrapped in ``jax.jit``: they are
only ever called inside the already-jitted sweep loop, where a nested jit
would add trace/dispatch overhead and block fusion with the surrounding
scatter (same rationale as the label_argmax / delta_q wrappers).

Inputs accept any leading shape — ``rows`` may be the chunk-stacked
(n_chunks, rows) layout of ``graph/ell.DeviceEll`` or already flat; the
wrapper collapses leading dims so the Pallas grid spans all chunks of the
bucket, and reshapes the outputs back.

Table layout selection (DESIGN.md §Kernels): ``table_mode`` picks between
the VMEM-RESIDENT fast path and the WINDOWED STREAMED path; ``auto``
resolves from the VMEM byte budget (``kernels.common.resolve_table_mode``)
at trace time.  On a TPU the Pallas path is always GATHERED: XLA gathers
the (rows, W) tiles and the label_argmax / delta_q kernels score them
(``_resolve_mode``).  Streaming needs the per-row-block window metadata
(``graph.ell.TableWindows``, passed duck-typed so the kernel layer stays
free of graph-layer imports); without it the resident path is used.  Both
pallas layouts and both pure-jnp oracles are bit-identical — the windowed
oracle slices the same windows with ``lax.dynamic_slice`` and runs the SAME
per-block ref the streamed kernel body runs.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.common import (
    TABLE_LANE,
    default_interpret,
    on_tpu,
    pick_row_block,
    require_lowerable,
    resolve_table_mode,
    vmem_budget_bytes,
)
from repro.kernels.delta_q.kernel import delta_q_pallas
from repro.kernels.label_argmax.kernel import label_argmax_pallas
from repro.kernels.local_move.kernel import (
    _check_windows,
    _pad_tiles,
    local_move_louvain_pallas,
    local_move_louvain_pallas_streamed,
    local_move_plp_pallas,
    local_move_plp_pallas_streamed,
    window_flat,
)
from repro.kernels.local_move.ref import (
    compose_louvain_tables,
    local_move_louvain_tables_ref,
    local_move_plp_ref,
    louvain_gather,
    louvain_score,
    plp_gather,
    plp_score,
)


def _flatten(rows, nbr, w):
    W = nbr.shape[-1]
    return (
        rows.reshape(-1).astype(jnp.int32),
        nbr.reshape(-1, W).astype(jnp.int32),
        w.reshape(-1, W).astype(jnp.float32),
    )


def gathered_row_block(width: int, rows: int,
                       vmem_budget: Optional[int] = None) -> int:
    """Rows per grid step of the gathered layout's scoring kernel: the
    (R_blk, W, W) pairwise tensor and its temporaries within the VMEM
    budget (a 1/32 share per pairwise f32 element leaves room for the
    double-buffered (R_blk, W) tiles)."""
    return min(pick_row_block(width, vmem_budget_bytes(vmem_budget) // 32),
               rows)


def _resolve_mode(table_mode: str, windows, n_tables: int, sentinel: int,
                  vmem_budget: Optional[int], use_pallas: bool = False) -> str:
    """Static table-layout decision for one dispatch.

    On a TPU the Pallas path always takes the ``gathered`` layout (XLA
    gathers the tiles, the label_argmax / delta_q kernels score them): the
    resident and streamed
    kernels gather in-kernel, which Mosaic refuses
    (``kernels.common.TPU_UNLOWERABLE``), so asking for them there raises.
    Elsewhere:

    ``auto`` additionally requires the STREAMED footprint to earn its keep:

    * the window must be narrower than the table — with poor id-locality
      one outlier row inflates the per-bucket slot stride to the whole id
      range (``TableWindows`` docstring), and a 2-slot window ≥ the table
      would re-read the full table per grid step, strictly worse than the
      resident one-shot DMA;
    * the double-buffered windows (2 live buffers of 2·slot entries per
      table) must fit the same half-budget bound the resident tables were
      tested against — mediocre locality past the resident budget would
      otherwise stream windows that bust VMEM just the same.

    Failing either check falls back to resident.  Explicit ``table_mode='streamed'`` is honored unchecked (the
    degenerate-window parity tests rely on it).
    """
    if use_pallas and on_tpu():
        if table_mode in ("resident", "streamed"):
            require_lowerable(f"local_move.{table_mode}")
        return "gathered"
    if windows is None:
        if table_mode == "streamed":
            raise ValueError(
                "table_mode='streamed' requires window metadata "
                "(graph.ell.TableWindows); build buckets via to_device()")
        return "resident"
    n_pad = -(-(sentinel + 1) // TABLE_LANE) * TABLE_LANE
    mode = resolve_table_mode(table_mode, 4 * n_tables * n_pad, vmem_budget)
    if mode == "streamed" and table_mode == "auto":
        win_bytes = 4 * n_tables * (2 * windows.slot) * 2  # 2 = live buffers
        if (2 * windows.slot >= n_pad
                or resolve_table_mode("auto", win_bytes, vmem_budget)
                != "resident"):
            return "resident"
    return mode


def _blocked(windows, rows, nbr, w, sentinel: int):
    """Reshape flat tiles into the (n_blocks, block_rows, ·) window layout
    (same metadata validation as the Pallas streamed path)."""
    R, W = nbr.shape
    nb = _check_windows(windows, R)
    r_blk = windows.block_rows
    rows, nbr, w, _ = _pad_tiles(rows, nbr, w, r_blk, sentinel)
    return (rows.reshape(nb, r_blk), nbr.reshape(nb, r_blk, W),
            w.reshape(nb, r_blk, W), R)


def _window_flat(tab, windows, fill):
    """Flat table padded so every 2-slot window slice is in range — the
    SAME padding step (kernel.window_flat) the overlapped BlockSpec view is
    built from."""
    return window_flat(tab, windows.slot, windows.n_slots, fill)


def local_move_plp(
    rows: jax.Array,        # (..., ) int32 vertex id per row
    nbr: jax.Array,         # (..., W) int32 neighbor ids
    w: jax.Array,           # (..., W) float32 edge weights
    labels_ext: jax.Array,  # (n+1,) labels table, labels_ext[n] = n
    seed: jax.Array,        # scalar tie-noise seed
    *,
    tie_eps: float,
    sentinel: int,
    use_pallas: bool = False,
    interpret: bool | None = None,
    windows=None,                       # graph.ell.TableWindows | None
    table_mode: str = "auto",           # auto | resident | streamed
    vmem_budget: int | None = None,
) -> Tuple[jax.Array, jax.Array]:
    """(best_label, propose) per row, gathers fused into the evaluator."""
    lead = rows.shape
    rows_f, nbr_f, w_f = _flatten(rows, nbr, w)
    labels_ext = labels_ext.astype(jnp.int32)
    mode = _resolve_mode(table_mode, windows, 1, sentinel, vmem_budget,
                         use_pallas)
    if use_pallas:
        interp = default_interpret() if interpret is None else interpret
        if mode == "gathered":
            nbr_lab, cur_lab, rows_n = plp_gather(
                rows_f, nbr_f, labels_ext, sentinel=sentinel)
            r_blk = gathered_row_block(nbr_f.shape[1], nbr_f.shape[0],
                                       vmem_budget)
            best, prop = plp_score(
                nbr_lab, w_f, cur_lab, rows_n, seed, tie_eps=tie_eps,
                sentinel=sentinel, argmax=functools.partial(
                    label_argmax_pallas, interpret=interp, row_block=r_blk),
            )
        elif mode == "streamed":
            best, prop = local_move_plp_pallas_streamed(
                rows_f, nbr_f, w_f, labels_ext, seed,
                tie_eps=tie_eps, sentinel=sentinel, interpret=interp,
                windows=windows,
            )
        else:
            best, prop = local_move_plp_pallas(
                rows_f, nbr_f, w_f, labels_ext, seed,
                tie_eps=tie_eps, sentinel=sentinel, interpret=interp,
                vmem_budget=vmem_budget,
            )
        prop = prop != 0
    elif mode == "streamed":
        # pure-jnp windowed oracle: per block, slice the SAME 2-slot window
        # the streamed kernel's BlockSpec lands and run the SAME ref body
        R = rows_f.shape[0]
        rows_b, nbr_b, w_b, _ = _blocked(windows, rows_f, nbr_f, w_f, sentinel)
        flat = _window_flat(labels_ext, windows, sentinel)
        S = windows.slot

        def one(r_, nb_, w_, k):
            winv = jax.lax.dynamic_slice(flat, (k * S,), (2 * S,))
            return local_move_plp_ref(
                r_, nb_, w_, winv, seed,
                tie_eps=tie_eps, sentinel=sentinel, win_lo=k * S)

        best, prop = jax.vmap(one)(rows_b, nbr_b, w_b, windows.win_blk)
        best, prop = best.reshape(-1)[:R], prop.reshape(-1)[:R]
    else:
        best, prop = local_move_plp_ref(
            rows_f, nbr_f, w_f, labels_ext, seed,
            tie_eps=tie_eps, sentinel=sentinel,
        )
    return best.reshape(lead), prop.reshape(lead)


def local_move_louvain(
    rows: jax.Array,      # (..., ) int32 vertex id per row
    nbr: jax.Array,       # (..., W) int32 neighbor ids
    w: jax.Array,         # (..., W) float32 edge weights
    com_ext: jax.Array,   # (n+1,) community table, com_ext[n] = n
    vol_ext: jax.Array,   # (n+1,) community volumes, vol_ext[n] = 0
    size_ext: jax.Array,  # (n+1,) community sizes, size_ext[n] = 0
    deg_ext: jax.Array,   # (n+1,) weighted degrees, deg_ext[n] = 0
    vol_total: jax.Array,  # scalar vol(V)
    *,
    sentinel: int,
    singleton_rule: bool = True,
    use_pallas: bool = False,
    interpret: bool | None = None,
    windows=None,                       # graph.ell.TableWindows | None
    table_mode: str = "auto",           # auto | resident | streamed
    vmem_budget: int | None = None,
    composed=None,                      # per-vertex composed table 4-tuple
) -> Tuple[jax.Array, jax.Array]:
    """(best_community, propose) per row; gain test is Eq. 1 > 0.

    ``composed`` lets a caller evaluating MANY buckets per sweep (the ELL
    engine) pass the per-vertex composed tables of
    ``ref.compose_louvain_tables`` built ONCE per sweep, instead of this
    wrapper re-composing them per bucket dispatch.
    """
    lead = rows.shape
    rows_f, nbr_f, w_f = _flatten(rows, nbr, w)
    inv_vol = (1.0 / vol_total).astype(jnp.float32)
    if composed is None:
        composed = compose_louvain_tables(
            com_ext.astype(jnp.int32), vol_ext.astype(jnp.float32),
            size_ext.astype(jnp.int32), deg_ext.astype(jnp.float32),
            sentinel)
    com_v, volcom_v, sizecom_v, deg_v = composed
    mode = _resolve_mode(table_mode, windows, 4, sentinel, vmem_budget,
                         use_pallas)
    if use_pallas:
        interp = default_interpret() if interpret is None else interpret
        if mode == "gathered":
            tiles = louvain_gather(rows_f, nbr_f, com_v, volcom_v, sizecom_v,
                                   deg_v, sentinel=sentinel)
            r_blk = gathered_row_block(nbr_f.shape[1], nbr_f.shape[0],
                                       vmem_budget)
            best, prop = louvain_score(
                tiles[0], w_f, *tiles[1:], inv_vol, sentinel=sentinel,
                singleton_rule=singleton_rule, argmax=functools.partial(
                    delta_q_pallas, interpret=interp, row_block=r_blk),
            )
        elif mode == "streamed":
            best, prop = local_move_louvain_pallas_streamed(
                rows_f, nbr_f, w_f, com_v, volcom_v, sizecom_v, deg_v,
                inv_vol, sentinel=sentinel, singleton_rule=singleton_rule,
                interpret=interp, windows=windows,
            )
        else:
            best, prop = local_move_louvain_pallas(
                rows_f, nbr_f, w_f, com_v, volcom_v, sizecom_v, deg_v,
                inv_vol, sentinel=sentinel, singleton_rule=singleton_rule,
                interpret=interp, vmem_budget=vmem_budget,
            )
        prop = prop != 0
    elif mode == "streamed":
        R = rows_f.shape[0]
        rows_b, nbr_b, w_b, _ = _blocked(windows, rows_f, nbr_f, w_f, sentinel)
        flats = (
            _window_flat(com_v, windows, sentinel),
            _window_flat(volcom_v, windows, 0),
            _window_flat(sizecom_v, windows, 0),
            _window_flat(deg_v, windows, 0),
        )
        S = windows.slot

        def one(r_, nb_, w_, k):
            wins = tuple(
                jax.lax.dynamic_slice(f, (k * S,), (2 * S,)) for f in flats)
            return local_move_louvain_tables_ref(
                r_, nb_, w_, *wins, inv_vol,
                sentinel=sentinel, singleton_rule=singleton_rule,
                win_lo=k * S)

        best, prop = jax.vmap(one)(rows_b, nbr_b, w_b, windows.win_blk)
        best, prop = best.reshape(-1)[:R], prop.reshape(-1)[:R]
    else:
        best, prop = local_move_louvain_tables_ref(
            rows_f, nbr_f, w_f, com_v, volcom_v, sizecom_v, deg_v, inv_vol,
            sentinel=sentinel, singleton_rule=singleton_rule,
        )
    return best.reshape(lead), prop.reshape(lead)
