"""Sort-free binned coarsening — orchestration around the rank kernel.

Replaces the coarsening GroupBy's ``lax.sort`` with direct binned
accumulation over the DENSE contiguous community ids (DESIGN.md
§Aggregation kernel).  Stages, all jit-native with static shapes:

  1. *Gate*: one ``segment_sum`` bounds each source community's edge count;
     any row over the static bin width falls back to the one-sort path via
     ``lax.cond`` BEFORE paying for probing (hash rows cannot hold more
     distinct destinations than the width, and hub rows would otherwise
     probe for many rounds just to discover the overflow).
  2. *Insert*: a ``lax.while_loop`` of scatter-min claim rounds assigns each
     distinct (src-community, dst-community) pair one slot of the
     (n+1, width) bin-key table.  Edges of the SAME pair share the probe
     sequence, so they claim, win and resolve together in one round —
     which keeps every group's weight accumulation in original edge order,
     the bitwise contract below.  Losers (distinct keys contending for one
     slot; the smallest key wins a round) continue linear probing; any
     survivor after the round budget raises the overflow fallback.
  3. *Rank*: per edge, the rank of its destination key within its bin row
     (ref.py by default; kernel.py is the interpret-mode oracle —
     ``resolve_bin_impl``) plus a
     per-row occupancy count and an exclusive ``cumsum`` over rows give the
     canonical front-compacted src-sorted output position with no sort.
  4. *Output*: three m-sized edge scatters — src/dst ids (duplicates write
     identical values) and a ``segment_sum`` of the weights keyed by output
     position.  Because positions ascend with (src, dst) and the adds apply
     in original edge order, the result is bit-for-bit the one-sort
     ``remap_and_coarsen`` coarse graph, including the padding-slot
     conventions (src = dst = sentinel, w = 0, mask False).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.graph import segment as seg
from repro.graph.structure import Graph
from repro.kernels.aggregation.kernel import bin_rank_pallas
from repro.kernels.aggregation.ref import bin_rank_ref
from repro.kernels.common import (cdiv, hash_u32_jnp, pick_bin_width,
                                  resolve_bin_impl)
from repro.utils import telemetry


def community_edge_keys(
    g: Graph, new_com: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """Per-edge (src-community, dst-community) keys; masked edges get the
    ``n_max`` sentinel on both sides (they sort last / route to the sink
    row, in every path)."""
    n = g.n_max
    sentinel = jnp.int32(n)
    cs = jnp.where(g.edge_mask, new_com[jnp.clip(g.src, 0, n - 1)], sentinel)
    cd = jnp.where(g.edge_mask, new_com[jnp.clip(g.dst, 0, n - 1)], sentinel)
    return cs, cd


def insert_bins(
    g: Graph,
    cs: jax.Array,
    cd: jax.Array,
    *,
    width: int,
    max_rounds: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Gate + probing insert.  Returns ``(keys_flat, resolved, overflow,
    rounds)``: the ((n+1)·width + 1,) bin-key table (last element is the
    claim sink), per-edge resolution, the fallback predicate, and the
    number of probe rounds actually run."""
    n, m = g.n_max, g.m_max
    W = width
    empty = jnp.int32(n)
    active = g.edge_mask
    rounds_max = jnp.int32(max_rounds if max_rounds is not None else W)
    sink = (n + 1) * W
    row_base = jnp.where(active, cs, n) * W
    h0 = (hash_u32_jnp(cd) % jnp.uint32(W)).astype(jnp.int32)
    keys0 = jnp.full((sink + 1,), empty, jnp.int32)

    # gate: a row with more than W edges MAY hold more than W distinct
    # destinations; skip probing entirely and let the sort path run
    row_edges = jax.ops.segment_sum(
        jnp.where(active, 1, 0), jnp.clip(cs, 0, n), num_segments=n + 1)
    fits = jnp.max(row_edges[:n]) <= jnp.int32(W)

    def probe(keys):
        def cond(c):
            return (c[2] < rounds_max) & jnp.any(~c[1])

        def body(c):
            keys, resolved, r = c
            slot = (h0 + r) % W
            idx = row_base + slot
            k_cur = keys[idx]
            hit = ~resolved & (k_cur == cd)
            claim = ~resolved & (k_cur == empty)
            keys = keys.at[jnp.where(claim, idx, sink)].min(cd)
            won = claim & (keys[idx] == cd)
            return keys, resolved | hit | won, r + 1

        return jax.lax.while_loop(cond, body, (keys, ~active, jnp.int32(0)))

    def skip(keys):
        return keys, ~active, jnp.int32(0)

    keys, resolved, rounds = jax.lax.cond(fits, probe, skip, keys0)
    overflow = jnp.any(active & ~resolved)
    return keys, resolved, overflow, rounds


# Entries of the (edges, width) key-row gather per rank chunk (32 MiB of
# int32).  Unchunked, that gather is the largest buffer of a level: at
# com-youtube's level 0 (12.4M directed edges, width 64) it takes 12 GiB
# of v5e HBM with its index array and lane padding.
RANK_CHUNK_ELEMS = 1 << 23


def bin_rank_chunked(keys_flat: jax.Array, cs: jax.Array, cd: jax.Array, *,
                     width: int, empty: int) -> jax.Array:
    """``bin_rank_ref`` over edge chunks of ``RANK_CHUNK_ELEMS // width``
    edges (one chunk, i.e. the plain call, when the edges fit).  Ranks are
    integer counts, so chunking cannot change them."""
    m = cs.shape[0]
    chunk = max(8, RANK_CHUNK_ELEMS // width)
    if m <= chunk:
        return bin_rank_ref(keys_flat, cs, cd, width=width, empty=empty)
    nc = cdiv(m, chunk)
    pad = nc * chunk - m
    sink_row = keys_flat.shape[0] // width - 1   # padding ranks to 0
    cs_p = jnp.pad(cs, (0, pad), constant_values=sink_row).reshape(nc, chunk)
    cd_p = jnp.pad(cd, (0, pad), constant_values=empty).reshape(nc, chunk)
    ranks = jax.lax.map(
        lambda a: bin_rank_ref(keys_flat, a[0], a[1], width=width,
                               empty=empty), (cs_p, cd_p))
    return ranks.reshape(-1)[:m]


def binned_coarsen(
    g: Graph,
    new_com: jax.Array,
    n_comm: jax.Array,
    *,
    width: Optional[int] = None,
    impl: str = "auto",
    max_rounds: Optional[int] = None,
    row_block: Optional[int] = None,
    vmem_budget: Optional[int] = None,
    force_overflow: bool = False,
) -> Graph:
    """Sort-free coarse graph for CONTIGUOUS community ids ``new_com``.

    Bit-for-bit identical to ``core.aggregation.coarsen_graph`` /
    ``remap_and_coarsen``'s coarse output (tests/test_aggregation.py); the
    one-sort path remains reachable as the in-graph ``lax.cond`` fallback
    AND as the documented oracle (``LouvainConfig.aggregation="sort"``).

    ``force_overflow`` (static; the ``binned_overflow`` fault-injection
    point) pins the overflow predicate true so every aggregation takes the
    sort fallback — the bit-identity of that descent is what
    tests/test_faults.py asserts.
    """
    n, m = g.n_max, g.m_max
    W = width if width is not None else pick_bin_width(n, m)
    sentinel = jnp.int32(n)
    empty = int(n)
    active = g.edge_mask
    impl_r = resolve_bin_impl(impl)

    cs, cd = community_edge_keys(g, new_com)
    keys, _resolved, overflow, _rounds = insert_bins(
        g, cs, cd, width=W, max_rounds=max_rounds)
    if force_overflow:
        telemetry.bump("fault.binned_overflow.forced")
        overflow = jnp.bool_(True)

    def binned_path(_):
        keys_flat = keys[:-1]
        occ2d = keys_flat.reshape(n + 1, W) != jnp.int32(empty)
        cnt = jnp.sum(occ2d[:n].astype(jnp.int32), axis=1)
        row_start = jnp.cumsum(cnt) - cnt
        n_groups = jnp.sum(cnt)
        cs_c = jnp.clip(cs, 0, n)
        if impl_r == "kernel":
            rank_e = bin_rank_pallas(
                keys_flat, cs_c, cd, width=W, empty=empty,
                row_block=row_block, vmem_budget=vmem_budget)
        else:
            rank_e = bin_rank_chunked(keys_flat, cs_c, cd, width=W,
                                      empty=empty)
        epos = jnp.where(
            active, row_start[jnp.clip(cs, 0, n - 1)] + rank_e, m)
        # duplicate positions write identical values (all edges of a group
        # share (cs, cd)), so the scatter order is immaterial for the ids;
        # the weight adds apply in original edge order — the same order the
        # stable one-sort path accumulates in.  When the (cs, cd) pair packs
        # into one int32 (static trace-time check; true for every stand-in
        # capacity) both ids ride ONE m-scatter instead of two — scatters
        # dominate this path on CPU/TPU alike, and integer pack/unpack is
        # exact so the bitwise contract is untouched.
        if (n + 1) * (n + 1) - 1 <= 2**31 - 1:
            base = jnp.int32(n + 1)
            packed = (jnp.full((m + 1,), sentinel * base + sentinel,
                               jnp.int32).at[epos].set(cs * base + cd)[:m])
            gsrc, gdst = packed // base, packed % base
        else:
            # overflow guard: (n_cap+1)² would not fit int32, so the packed
            # single-scatter id encoding is statically disabled for this
            # capacity; the counter makes the (slower) two-scatter descent
            # observable rather than silent
            telemetry.bump("agg.id_pack_disabled")
            gsrc = (jnp.full((m + 1,), sentinel, jnp.int32)
                    .at[epos].set(cs)[:m])
            gdst = (jnp.full((m + 1,), sentinel, jnp.int32)
                    .at[epos].set(cd)[:m])
        sums = jax.ops.segment_sum(
            jnp.where(active, g.w, 0.0), epos, num_segments=m + 1)[:m]
        gmask = jnp.arange(m, dtype=jnp.int32) < n_groups
        return gsrc, gdst, jnp.where(gmask, sums, 0.0), gmask, n_groups

    def sort_path(_):
        # the one-sort GroupBy (graph/segment.py), exactly coarsen_graph's
        # massaging — the cond-gated overflow fallback
        (gk, gs, gvalid, _ng) = seg.groupby_sum(
            (cs, cd), jnp.where(active, g.w, 0.0), valid=active)
        grp_ok = gvalid & (gk[0] < sentinel)
        return (jnp.where(grp_ok, gk[0], sentinel),
                jnp.where(grp_ok, gk[1], sentinel),
                jnp.where(grp_ok, gs, 0.0),
                grp_ok,
                jnp.sum(grp_ok.astype(jnp.int32)))

    gsrc, gdst, gw, gmask, n_groups = jax.lax.cond(
        overflow, sort_path, binned_path, None)
    return Graph(
        src=gsrc,
        dst=gdst,
        w=gw,
        edge_mask=gmask,
        n_valid=n_comm.astype(jnp.int32),
        m_valid=n_groups,
        n_max=n,
        m_max=m,
        sorted_by="src",
    )
