"""Pallas TPU kernel: per-edge bin-rank gather for the sort-free aggregation.

Same resident-table layout as the fused local_move kernels (DESIGN.md
§Kernels): the flat (rows·width,) bin-key table rides along in the ANY
memory space, is DMA'd into VMEM scratch on the first grid step, and every
later row-block of edges gathers its (R_blk, width) key rows in-kernel —
the only HBM traffic per block is the two (R_blk, 1) edge tiles and one
(R_blk, 1) output.  The rank math is ref.py's ``bin_rank_ref`` verbatim, so
kernel ≡ ref bit-compatibility holds by construction.

INVARIANT: the grid keeps the default sequential ("arbitrary") semantics —
a parallel dimension would hand later steps never-DMA'd scratch (the same
invariant as local_move's resident kernels).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.aggregation.ref import bin_rank_ref
from repro.kernels.common import (TABLE_LANE, default_interpret,
                                  pick_row_block_fused)


def _pad_lane(tab: jax.Array, fill) -> jax.Array:
    """Pad a flat table to a lane multiple for the ANY→VMEM copy."""
    pad = (-tab.shape[0]) % TABLE_LANE
    return jnp.pad(tab, (0, pad), constant_values=fill) if pad else tab


def _bin_rank_kernel(
    keys_tab_ref,  # (tab_pad,) int32 in ANY — whole flat bin-key table
    cs_ref,        # (R_blk, 1) int32 — per-edge row (source community)
    cd_ref,        # (R_blk, 1) int32 — per-edge key (destination community)
    out_ref,       # (R_blk, 1) int32 — per-edge within-row rank
    keys_vmem,     # (tab_pad,) int32 VMEM scratch
    sem,
    *,
    width: int,
    empty: int,
):
    @pl.when(pl.program_id(0) == 0)
    def _():
        cp = pltpu.make_async_copy(keys_tab_ref, keys_vmem, sem)
        cp.start()
        cp.wait()

    rank = bin_rank_ref(
        keys_vmem[...],
        cs_ref[...][:, 0],
        cd_ref[...][:, 0],
        width=width,
        empty=empty,
    )
    out_ref[...] = rank[:, None]


def bin_rank_pallas(
    keys_flat: jax.Array,  # (rows·width,) int32 — bin-key table
    cs: jax.Array,         # (R,) int32
    cd: jax.Array,         # (R,) int32
    *,
    width: int,
    empty: int,
    interpret: bool | None = None,
    row_block: int | None = None,
    vmem_budget: int | None = None,
) -> jax.Array:
    """Per-edge bin rank (ref.py contract) with the table VMEM-resident.

    Caller guarantees the table fits the resident budget
    (``kernels.common.resolve_bin_impl``); edges padded to the row block
    must carry the sink row index so their gathers stay in range.
    """
    if interpret is None:
        interpret = default_interpret()
    R = cs.shape[0]
    tab = _pad_lane(keys_flat, empty)
    tab_pad = tab.shape[0]
    r_blk = row_block or min(
        pick_row_block_fused(width, vmem_budget, table_bytes=4 * tab_pad), R)
    pad = (-R) % r_blk
    if pad:
        sink_row = keys_flat.shape[0] // width - 1
        cs = jnp.pad(cs, (0, pad), constant_values=sink_row)
        cd = jnp.pad(cd, (0, pad), constant_values=empty)
    Rp = R + pad

    kern = functools.partial(_bin_rank_kernel, width=width, empty=empty)
    col = lambda: pl.BlockSpec((r_blk, 1), lambda i: (i, 0))
    out = pl.pallas_call(
        kern,
        grid=(Rp // r_blk,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            col(), col(),
        ],
        out_specs=col(),
        out_shape=jax.ShapeDtypeStruct((Rp, 1), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((tab_pad,), jnp.int32),
            pltpu.SemaphoreType.DMA,
        ],
        interpret=interpret,
    )(tab, cs[:, None], cd[:, None])
    return out[:R, 0]
