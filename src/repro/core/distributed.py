"""Multi-device community detection via shard_map (DESIGN.md §6).

Decomposition — the TPU analogue of Chapel multi-locale block distribution:
  * directed edges are sorted by destination and split into contiguous,
    edge-balanced vertex ranges (``graph.partition``); device d OWNS the
    vertices in its range and ALL edges into them, so the per-vertex GroupBy
    (``core.moves``) needs no cross-device reduction;
  * small O(n) state (labels / communities / degrees) is replicated; each
    sweep ends with a psum-merge of the disjoint per-owner updates;
  * O(n) derived state (community volumes/sizes) is recomputed redundantly on
    every device from replicated inputs — compute is cheaper than ICI.

The sweep loop itself is the shared engine's fused phase
(``core.engine.make_distributed_phase``, DESIGN.md §Engine): the
``lax.while_loop`` runs INSIDE the shard_map worker with the convergence
predicate on the replicated ΔN, so one local-moving phase is one jitted call
with zero per-sweep host syncs — the same contract as the single-device
backends.

Matching the paper's own observation (§V-B: "the aggregation phase exhibits
limited scalability due to its global communication requirements"), Louvain
aggregation comes in three flavors:

  * per-level (``pipeline_fused=False``): a global host re-shuffle — gather
    the moved communities, coarsen once (jit), re-partition for the next
    level;
  * fused + SHARD-LOCAL coarsening (``pipeline_fused=True,
    coarsening="shard_local"``, the default): the level loop nests around
    the in-shard_map sweep loop and each device coarsens ONLY its owned
    edge shard with the sort-free binned kernel.  Community ids are
    contiguized by a two-phase scheme (per-device presence-bitmap stripe +
    exclusive prefix over per-shard counts), and the per-shard partial
    coarse lists — bounded by the static ``halo_cap``
    (``kernels.common.pick_halo_cap``) — are exchanged in ONE tiled
    all_gather and merged by a second groupby pass.  The per-level
    collective payload is O(communities + cross-shard community pairs),
    never O(m); a psum'd overflow flag sends the rare cap-busting level to
    the host degradation ladder (retry with replicated coarsening);
  * fused + REPLICATED coarsening (``coarsening="replicated"``): the
    retired gather-then-replicate loop, kept as the selectable parity
    ORACLE — one full-shard all_gather after level 0, then replicated
    groupby recompute on every device.  Shard-local must match it (and the
    single-device fused driver) bit-for-bit on every mesh size
    (tests/test_distributed.py).

Bitwise parity of partial-then-merge coarsening rests on the same
integer-exactness condition as the rest of the repo (DESIGN.md §Numerics):
coarse edge weights are sums of input weights, exact in f32 below
``kernels.common.F32_ACCUM_SAFE``, so per-shard partial sums followed by the
merge groupby reassociate freely; group ORDER is canonical ((cs, cd)
ascending, front-compacted) and therefore shard-count independent.

The same code runs 8 fake CPU devices (tests) or a 512-chip pod mesh
(launch/dryrun.py lowers it for the production mesh).
"""
from __future__ import annotations

import dataclasses
from functools import lru_cache
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import aggregation
from repro.core.engine import (EngineSpec, make_distributed_phase,
                               make_distributed_step, phase_loop)
from repro.core.modularity import modularity
from repro.graph.partition import (EdgePartition, build_halo,
                                   partition_edges_by_dst, partition_quality)
from repro.graph.structure import Graph
from repro.kernels.aggregation import binned_coarsen
from repro.kernels.common import (EDGE_WIRE_BYTES, LABEL_WIRE_BYTES,
                                  accum_dtype, accum_needs_promotion,
                                  dist_comm_bytes_per_level, pick_halo_cap)
from repro.utils import faultinject, telemetry
from repro.utils.errors import RunReport, ShardError
from repro.utils.timing import Timer

COARSENING_MODES = ("shard_local", "replicated")


# ----------------------------------------------------------------- helpers


def _flat_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(mesh.axis_names)


def _engine_faults(faults: frozenset) -> tuple:
    from repro.core.louvain import ENGINE_FAULTS

    return tuple(sorted(f for f in faults if f in ENGINE_FAULTS))


def _prepare_partition(g: Graph, n_devices: int) -> EdgePartition:
    """Partition + the shard-coverage guard (DESIGN.md §Robustness).

    The ``shard_drop`` fault-injection point masks out device 0's entire
    edge shard after partitioning — modelling a lost/corrupted shard.  The
    guard below re-counts the per-device masks against the graph's own
    ``m_valid`` BEFORE any compute is dispatched: losing edges here would
    otherwise just yield a quietly-worse partition (no crash, wrong
    volumes), the canonical silent-corruption outcome.
    """
    part = partition_edges_by_dst(g, n_devices)
    if faultinject.is_active("shard_drop"):
        telemetry.bump("fault.shard_drop.injected")
        emask = np.array(part.edge_mask)
        emask[0, :] = False
        part = dataclasses.replace(part, edge_mask=emask)
    covered = int(np.asarray(part.edge_mask).sum())
    expect = int(g.m_valid)
    if covered != expect:
        raise ShardError(
            f"edge partition covers {covered} directed edges, graph has "
            f"{expect}: a shard was dropped or corrupted")
    return part


def shard_edges(p: EdgePartition, mesh: Mesh):
    """Place partition arrays on the mesh: leading axis over ALL mesh axes."""
    spec = P(_flat_axes(mesh))
    sharding = jax.NamedSharding(mesh, spec)
    dev = lambda x: jax.device_put(jnp.asarray(x), sharding)
    return dev(p.src), dev(p.dst), dev(p.w), dev(p.edge_mask)


# ----------------------------------------------------------------- PLP


def distributed_plp(
    g: Graph,
    mesh: Mesh,
    max_iterations: int = 100,
    threshold: int = 0,
    seed: int = 0,
    tie_eps: float = 0.25,
    move_prob: float = 0.75,
):
    """Driver: partition once, then one fused sharded phase call."""
    n = g.n_max
    part = _prepare_partition(g, mesh.devices.size)
    src, dst, w, emask = shard_edges(part, mesh)
    spec = EngineSpec(
        evaluator="plp",
        backend="distributed",
        max_sweeps=max_iterations,
        threshold=threshold,
        tie_eps=tie_eps,
        move_prob=move_prob,
        # historical behavior of the sharded sweep: tie noise re-drawn per
        # iteration (the closest analogue of Chapel's racy move order)
        reshuffle_ties=True,
        faults=_engine_faults(faultinject.active()),
    )
    phase = make_distributed_phase(mesh, n, spec)

    labels = jnp.arange(n, dtype=jnp.int32)
    active = g.vertex_mask()
    zero = jnp.zeros((n,), jnp.float32)  # deg/vol placeholders (PLP unused)
    labels, active, sweeps, dn_hist, _ = phase(
        src, dst, w, emask, labels, active, jnp.uint32(0), jnp.uint32(seed),
        zero, jnp.float32(1.0), g.n_valid,
    )
    sweeps = int(sweeps)
    history = [int(x) for x in np.asarray(dn_hist)[:sweeps]]
    return np.asarray(labels), history


# ----------------------------------------------------------------- Louvain


@dataclasses.dataclass
class DistLouvainResult:
    labels: np.ndarray
    n_communities: int
    levels: int
    modularity: float
    timer: Timer
    sweeps_per_level: list = dataclasses.field(default_factory=list)
    n_comm_per_level: list = dataclasses.field(default_factory=list)
    modularity_history: list = dataclasses.field(default_factory=list)
    delta_n_per_level: list = dataclasses.field(default_factory=list)
    # which coarsening mode actually produced the answer ("shard_local",
    # "replicated", or "per_level"), after any overflow degradation
    coarsening: str = "replicated"
    # partition health (graph.partition.partition_quality._asdict()) and the
    # per-level collective-payload accounting of the fused pipeline
    partition_stats: dict = dataclasses.field(default_factory=dict)
    comm_stats: dict = dataclasses.field(default_factory=dict)
    # retry/degradation/watchdog accounting (DESIGN.md §Robustness)
    run_report: RunReport = dataclasses.field(default_factory=RunReport)


@lru_cache(maxsize=None)
def make_distributed_pipeline(mesh: Mesh, n: int, m_pad: int,
                              spec: EngineSpec, max_levels: int,
                              agg_method: str = "binned",
                              faults: frozenset = frozenset(),
                              coarsening: str = "shard_local",
                              halo_cap: int = 0,
                              refine_sweeps: int = 0,
                              track_modularity: bool = True,
                              promote: bool = False):
    """Build the jitted whole-run distributed pipeline (DESIGN.md §Pipeline).

    The level loop runs INSIDE the shard_map worker, nested around the
    engine's fused sweep loop, mirroring the single-device pipeline's
    peeled-level-0 structure:

      * LEVEL 0 (the dominant level) sweeps on the device's LOCAL edge
        shard from the host edge-balanced partitioner — per-device compute
        stays ~m/D, exactly like the per-level driver;
      * ``coarsening="shard_local"``: community ids are contiguized by the
        TWO-PHASE scheme (each device scans its ``ceil(n/D)`` stripe of the
        presence bitmap; an all_gather of per-stripe counts provides the
        exclusive prefix that makes local ranks globally dense — bitwise
        equal to ``aggregation.remap_communities``, no sort); each device
        then coarsens ONLY its owned edges with the binned kernel and ships
        the first ``halo_cap`` partial groups through one tiled all_gather;
        a second (identity-map) groupby merges cross-shard duplicates into
        the canonical coarse graph at the REDUCED static capacity
        ``D·halo_cap``.  A psum'd flag records any shard whose partial list
        overflowed the cap — results of an overflowed run are refused by
        the driver, which retries replicated;
      * ``coarsening="replicated"``: the parity oracle — the shard is
        all_gather-ed ONCE into the replicated ``D·m_pad`` list, and
        aggregation is a redundant identical groupby on every device;
      * coarse levels — orders of magnitude smaller — sweep on the (merged,
        replicated) coarse list masked by a static contiguous dst-range
        ownership (``ceil(n/D)`` vertices per device, so the per-sweep psum
        merge stays a disjoint union); shard-local coarsening keeps
        applying per level with the same dst-range ownership;
      * ``refine_sweeps > 0`` enables Leiden refinement: after the macro
        phase, a threshold-0 phase re-runs from singletons restricted to
        macro communities, aggregation groups by the REFINED partition, and
        the next level's local-moving is seeded with each super-vertex's
        macro id — mirroring ``core.louvain`` exactly.  The refine phase
        contains collectives, so it runs UNCONDITIONALLY (uniform across
        devices) and its outputs are simply dead when the level converged —
        bitwise identical to the local driver's cond-gated refinement;
      * per-level modularity (and the final Q) use a psum decomposition of
        ``core.modularity`` over the level-0 shards: per-shard partial
        intra-weight/degree sums are exact in f32 for integer-valued
        weights (F32_ACCUM_SAFE), so the distributed Q is bitwise equal to
        the local oracle's;
      * histories live in sentinel device buffers (``-1`` for counts, NaN
        for modularity — the PR-1 convention), read back once.

    Returns ``pipeline(src, dst, w, edge_mask, seed, n_valid) ->
    (labels, n_final, levels, modularity, sweeps_hist, ncomm_hist,
    mod_hist, dn_hist, pgroups_hist, overflow)`` with ``src..edge_mask``
    the (D, m_pad) partition arrays.  ``pgroups_hist`` counts the gathered
    partial groups per level (-1 where not applicable) — the actual
    shard-local collective payload; ``overflow`` is the psum'd halo-cap
    flag.
    """
    from repro.core.louvain import LEVEL_IT_STRIDE, REFINE_IT_OFFSET

    if coarsening not in COARSENING_MODES:
        raise ValueError(f"coarsening must be one of {COARSENING_MODES}, "
                         f"got {coarsening!r}")
    axes = tuple(mesh.axis_names)
    espec, rspec = P(axes), P()
    D = int(mesh.devices.size)
    stride = -(-n // D)       # static coarse-ownership dst-range width
    n_pad_c = D * stride - n  # stripe padding of the presence bitmap
    if coarsening == "shard_local":
        h_cap = int(halo_cap) if halo_cap else pick_halo_cap(m_pad, D)
        h_cap = min(h_cap, m_pad)
        m_c = D * h_cap       # static capacity of the merged coarse list
    else:
        h_cap = 0
        m_c = D * m_pad       # static capacity of the gathered edge list
    refine = refine_sweeps > 0
    refine_spec = (dataclasses.replace(spec, max_sweeps=refine_sweeps,
                                       threshold=0) if refine else None)
    force_overflow = "binned_overflow" in faults
    max_sweeps = spec.max_sweeps
    acc = accum_dtype(promote)

    def worker(src_l, dst_l, w_l, emask_l, seed, n_valid0):
        src_l, dst_l, w_l, emask_l = (src_l[0], dst_l[0], w_l[0], emask_l[0])
        # linear device index over the (possibly multi-axis) mesh
        d = jnp.int32(0)
        for ax in axes:
            d = d * jax.lax.psum(1, ax) + jax.lax.axis_index(ax)
        lo = d * stride
        hi = jnp.minimum(lo + stride, n)
        arange_n = jnp.arange(n, dtype=jnp.int32)
        n_valid0 = n_valid0.astype(jnp.int32)
        sentinel = jnp.int32(n)
        gather = lambda x: jax.lax.all_gather(x, axes, tiled=True)

        def sweep(sp, src, dst, w, own, vmask, init_com, it0, restrict=None):
            """One fused local-moving phase over the given edge arrays."""
            deg = jax.lax.psum(jax.ops.segment_sum(
                jnp.where(own, w, 0.0), jnp.clip(src, 0, n - 1),
                num_segments=n), axes)
            vol_v = jnp.sum(deg)
            step = make_distributed_step(
                sp, axes, n, src, dst, w, own, deg, vol_v, vmask, restrict)
            com, _, sweeps, dn_h, _act = phase_loop(
                step, init_com, vmask, it0, seed, sp.max_sweeps, sp.threshold)
            return com, sweeps.astype(jnp.int32), dn_h

        def dist_q(com):
            """psum decomposition of ``core.modularity`` over level-0 shards.

            Each partial sum (per-shard intra weight, per-vertex degree) is
            an exact integer in f32 below F32_ACCUM_SAFE, so the psum
            reassociation is bitwise equal to the local single-pass sums;
            the replicated tail (vol_c scatter + Σ(vol_c/vol)² ) runs on
            identical arrays and is deterministic by shape.
            """
            wm = jnp.where(emask_l, w_l, 0.0).astype(acc)
            vol_v = jax.lax.psum(jnp.sum(wm), axes)
            same = com[src_l] == com[dst_l]
            w_in = jax.lax.psum(
                jnp.sum(jnp.where(same, wm, jnp.zeros((), acc))), axes)
            deg = jax.lax.psum(
                jax.ops.segment_sum(wm, src_l, num_segments=n), axes)
            vol_c = jax.ops.segment_sum(deg, com, num_segments=n)
            safe = jnp.where(vol_v > 0, vol_v, jnp.ones((), vol_v.dtype))
            q = w_in / safe - jnp.sum((vol_c / safe) ** 2)
            return jnp.where(vol_v > 0, q,
                             jnp.zeros((), q.dtype)).astype(jnp.float32)

        def contiguize(com, vmask):
            """Two-phase contiguization ≡ ``aggregation.remap_communities``.

            Phase 1: every device scans ITS ``stride``-wide stripe of the
            presence bitmap and ranks its ids locally (one cumsum).
            Phase 2: one all_gather of the D stripe counts gives the
            exclusive prefix; local rank + stripe offset is the globally
            dense id, and a tiled all_gather of the stripe tables yields
            the replicated remap table.  All-int32 arithmetic — bitwise
            equal to the single-pass ``contiguize_ids`` on every mesh.
            """
            idx = jnp.clip(jnp.where(vmask, com, sentinel), 0, n)
            p = jnp.zeros((n + 1,), jnp.int32).at[idx].set(1)[:n]
            if n_pad_c:
                p = jnp.concatenate([p, jnp.zeros((n_pad_c,), jnp.int32)])
            p_d = jax.lax.dynamic_slice(p, (lo,), (stride,))
            counts = jax.lax.all_gather(
                jnp.sum(p_d), axes, tiled=False).reshape(-1)      # (D,)
            off_d = jnp.take(jnp.cumsum(counts) - counts, d)
            t_d = jnp.where(p_d == 1, off_d + jnp.cumsum(p_d) - 1, sentinel)
            table = jax.lax.all_gather(t_d, axes, tiled=True)[:n]
            n_comm = jnp.sum(counts)
            new_com = jnp.where(vmask, table[jnp.clip(com, 0, n - 1)],
                                sentinel)
            return new_com, n_comm

        def coarsen_by(gl, new_com, n_comm):
            if agg_method == "sort":
                return aggregation.coarsen_graph(gl, new_com, n_comm)
            return binned_coarsen(gl, new_com, n_comm,
                                  force_overflow=force_overflow)

        def aggregate_shard_local(a, n_valid, com, vmask, m_cap):
            """Partial per-shard coarsen → halo exchange → collective merge.

            Each device groups ONLY its owned edges (a disjoint cover of the
            level's edge list), ships the first ``h_cap`` partial groups,
            and every device merges the gathered lists with an identity-map
            groupby.  Weight sums are exact integers, and both groupby
            passes emit canonically ordered front-compacted groups, so the
            merged coarse graph is bitwise identical to the replicated
            single-pass oracle.  The collective payload is the contiguize
            table + D·h_cap partial groups — O(communities + cross-shard
            pairs), never O(m).
            """
            a_src, a_dst, a_w, a_own = a
            new_com, n_comm = contiguize(com, vmask)
            gl = Graph(src=a_src, dst=a_dst, w=a_w, edge_mask=a_own,
                       n_valid=n_valid,
                       m_valid=jnp.sum(a_own.astype(jnp.int32)),
                       n_max=n, m_max=m_cap, sorted_by=None)
            part = coarsen_by(gl, new_com, n_comm)
            over = jax.lax.psum(
                (part.m_valid > jnp.int32(h_cap)).astype(jnp.int32),
                axes) > 0
            pgroups = jax.lax.psum(
                jnp.minimum(part.m_valid, jnp.int32(h_cap)), axes)
            gs, gd, gw, gm = (gather(part.src[:h_cap]),
                              gather(part.dst[:h_cap]),
                              gather(part.w[:h_cap]),
                              gather(part.edge_mask[:h_cap]))
            g_part = Graph(src=gs, dst=gd, w=gw, edge_mask=gm,
                           n_valid=n_comm,
                           m_valid=jnp.sum(gm.astype(jnp.int32)),
                           n_max=n, m_max=m_c, sorted_by=None)
            cg = coarsen_by(g_part, arange_n, n_comm)
            return new_com, n_comm, cg, over, pgroups

        def aggregate_replicated(a, n_valid, com):
            """The parity oracle: identical redundant groupby per device."""
            a_src, a_dst, a_w, a_mask = a
            cur = Graph(src=a_src, dst=a_dst, w=a_w, edge_mask=a_mask,
                        n_valid=n_valid,
                        m_valid=jnp.sum(a_mask.astype(jnp.int32)),
                        n_max=n, m_max=m_c, sorted_by=None)
            new_com, n_comm, cg = aggregation.remap_and_coarsen_by(
                agg_method, cur, com, faults)
            n_comm = jax.lax.pmax(n_comm, axes)  # lockstep collective merge
            return new_com, n_comm, cg, jnp.bool_(False), jnp.int32(-1)

        def aggregate(a, n_valid, com, vmask, m_cap):
            if coarsening == "shard_local":
                return aggregate_shard_local(a, n_valid, com, vmask, m_cap)
            return aggregate_replicated(a, n_valid, com)

        def run_level(s, a, n_valid, level_u32, init_com, assign, m_cap):
            """One level: fused local-moving → (refine) → remap+coarsen.

            ``s`` = (src, dst, w, own) sweep arrays (always the local view);
            ``a`` = aggregation arrays at static capacity ``m_cap`` (the
            local shard under shard-local coarsening, the replicated list
            under the oracle).  Mirrors ``core.louvain``'s ``run_level``
            exactly; collectives make every branch run unconditionally,
            with the results dead (never consumed) once the level loop
            exits.
            """
            s_src, s_dst, s_w, s_own = s
            vmask = arange_n < n_valid
            it0 = level_u32 * jnp.uint32(LEVEL_IT_STRIDE)
            com, sweeps, dn_h = sweep(spec, s_src, s_dst, s_w, s_own, vmask,
                                      init_com, it0)
            if not refine:
                new_com, n_comm, cg, over, pgroups = aggregate(
                    a, n_valid, com, vmask, m_cap)
                macro = new_com[jnp.clip(assign, 0, n - 1)]
                assign2, init2, nv2 = macro, arange_n, n_comm
            else:
                # Leiden: macro remap only; aggregation groups by the
                # REFINED partition and the next level's local-moving is
                # seeded with each super-vertex's macro id
                if coarsening == "shard_local":
                    new_com, n_comm = contiguize(com, vmask)
                else:
                    new_com, n_comm = aggregation.remap_communities(
                        com, vmask)
                macro = new_com[jnp.clip(assign, 0, n - 1)]
                ref, _sw_r, _dn_r = sweep(
                    refine_spec, s_src, s_dst, s_w, s_own, vmask, arange_n,
                    it0 + jnp.uint32(REFINE_IT_OFFSET), restrict=com)
                new_ref, n_ref, cg, over, pgroups = aggregate(
                    a, n_valid, ref, vmask, m_cap)
                macro_of_ref = jax.ops.segment_max(
                    jnp.where(vmask, new_com, -1),
                    jnp.clip(new_ref, 0, n - 1), num_segments=n)
                init2 = jnp.clip(macro_of_ref, 0, n - 1).astype(jnp.int32)
                assign2 = new_ref[jnp.clip(assign, 0, n - 1)]
                nv2 = n_ref
            done = n_comm == n_valid             # Alg. 3 l.6 convergence
            q = dist_q(macro) if track_modularity else jnp.float32(0.0)
            nown = cg.edge_mask & (cg.dst >= lo) & (cg.dst < hi)
            return (cg.src, cg.dst, cg.w, cg.edge_mask, nown, nv2, assign2,
                    init2, macro, sweeps, dn_h, n_comm, q, over, pgroups,
                    done)

        # ---- peeled level 0: sweep on the LOCAL edge-balanced shard
        s0 = (src_l, dst_l, w_l, emask_l)
        if coarsening == "replicated":
            # gather the shard ONCE into the replicated full-capacity list
            a0 = (gather(src_l), gather(dst_l), gather(w_l), gather(emask_l))
            m_cap0 = m_c
        else:
            a0, m_cap0 = s0, m_pad
        (csrc, cdst, cw, cmask, own, n_valid, assign, init_com, macro,
         sweeps0, dn0, n_comm0, q0, over, pg0, done) = run_level(
            s0, a0, n_valid0, jnp.uint32(0), arange_n, arange_n, m_cap0)

        mod_hist = jnp.full((max_levels,), jnp.nan, jnp.float32).at[0].set(q0)
        sweeps_hist = jnp.full((max_levels,), -1, jnp.int32).at[0].set(sweeps0)
        ncomm_hist = jnp.full((max_levels,), -1, jnp.int32).at[0].set(n_comm0)
        dn_hist = jnp.full((max_levels, max_sweeps), -1,
                           jnp.int32).at[0].set(dn0)
        pg_hist = jnp.full((max_levels,), -1, jnp.int32).at[0].set(pg0)

        # ---- coarse levels: merged (replicated) list, dst-range ownership
        def cond(c):
            level, done = c[0], c[1]
            return (level < max_levels) & (~done)

        def body(c):
            (level, _done, csrc, cdst, cw, cmask, own_l, n_valid, assign,
             init_com, _macro, mh, sh, nh, dh, ph, ov) = c
            amask = own_l if coarsening == "shard_local" else cmask
            (csrc2, cdst2, cw2, cmask2, own2, nv2, assign2, init2, macro2,
             sweeps, dn_h, n_comm, q, over2, pg, done2) = run_level(
                (csrc, cdst, cw, own_l), (csrc, cdst, cw, amask), n_valid,
                level.astype(jnp.uint32), init_com, assign, m_c)
            mh = mh.at[level].set(q)
            sh = sh.at[level].set(sweeps)
            nh = nh.at[level].set(n_comm)
            dh = dh.at[level].set(dn_h)
            ph = ph.at[level].set(pg)
            return (level + 1, done2, csrc2, cdst2, cw2, cmask2, own2, nv2,
                    assign2, init2, macro2, mh, sh, nh, dh, ph, ov | over2)

        carry = (jnp.int32(1), done, csrc, cdst, cw, cmask, own, n_valid,
                 assign, init_com, macro, mod_hist, sweeps_hist, ncomm_hist,
                 dn_hist, pg_hist, over)
        carry = jax.lax.while_loop(cond, body, carry)
        (levels, _, _, _, _, _, _, _, _, _, macro, mod_hist, sweeps_hist,
         ncomm_hist, dn_hist, pg_hist, overflow) = carry

        final, n_final = aggregation.remap_communities(
            macro, arange_n < n_valid0)
        q = dist_q(final)
        return (final, n_final, levels, q, sweeps_hist, ncomm_hist,
                mod_hist, dn_hist, pg_hist, overflow)

    sharded = jax.shard_map(
        worker, mesh=mesh,
        in_specs=(espec,) * 4 + (rspec,) * 2,
        out_specs=(rspec,) * 10, check_vma=False,
    )
    return jax.jit(sharded)


def _resolve_halo_cap(halo_cap, m_pad: int, n_devices: int) -> int:
    cap = int(halo_cap) if halo_cap else pick_halo_cap(m_pad, n_devices)
    return min(cap, int(m_pad))


def distributed_louvain(
    g: Graph,
    mesh: Mesh,
    max_levels: int = 10,
    max_sweeps: int = 25,
    sweep_threshold: int = 0,
    seed: int = 0,
    move_prob: float = 0.5,
    singleton_rule: bool = True,
    pipeline_fused: bool = True,
    aggregation_method: str = "binned",
    coarsening: str = "shard_local",
    halo_cap: int | None = None,
    refine: bool = False,
    refine_sweeps: int = 8,
    track_modularity: bool = True,
) -> DistLouvainResult:
    """Distributed Louvain/Leiden driver (DESIGN.md §6).

    ``coarsening`` selects the fused pipeline's aggregation layout:
    ``"shard_local"`` (default — per-device partial coarsen + halo-capped
    collective merge) or ``"replicated"`` (the gather-then-replicate parity
    oracle).  Both are bit-identical; a shard whose partial coarse list
    overflows the static ``halo_cap`` flags the run and the driver retries
    replicated, recording the degradation in ``run_report``.  ``refine``
    enables Leiden refinement (fused pipeline only).
    """
    if coarsening not in COARSENING_MODES:
        raise ValueError(f"coarsening must be one of {COARSENING_MODES}, "
                         f"got {coarsening!r}")
    if refine and not pipeline_fused:
        raise ValueError("Leiden refinement (refine=True) requires "
                         "pipeline_fused=True")
    timer = Timer()
    n = g.n_max
    D = int(mesh.devices.size)
    faults = frozenset(faultinject.active())
    report = RunReport(faults=sorted(faults))
    promote = accum_needs_promotion(g.m_max)
    spec = EngineSpec(
        evaluator="louvain",
        backend="distributed",
        max_sweeps=max_sweeps,
        threshold=sweep_threshold,
        move_prob=move_prob,
        singleton_rule=singleton_rule,
        faults=_engine_faults(faults),
    )

    if pipeline_fused:
        with timer.phase("partition"):
            part = _prepare_partition(g, D)
            src, dst, w, emask = shard_edges(part, mesh)
            halo = build_halo(part)
            pq = partition_quality(part, halo)
        h_cap = _resolve_halo_cap(halo_cap, part.m_pad, D)
        used = coarsening
        rs = refine_sweeps if refine else 0
        pipe = make_distributed_pipeline(
            mesh, n, part.m_pad, spec, max_levels, aggregation_method,
            faults, used, h_cap, rs, track_modularity, promote)
        with timer.phase("pipeline"):
            out = pipe(src, dst, w, emask, jnp.uint32(seed), g.n_valid)
            (final, n_final, levels, q, sweeps_hist, ncomm_hist, mod_hist,
             dn_hist, pg_hist, overflow) = jax.device_get(out)  # ONE readback
        if bool(overflow) and used == "shard_local":
            # degradation ladder: a partial coarse list busted the halo cap
            # somewhere in the level loop — the merged graph may have lost
            # groups, so the whole answer is refused and re-run replicated
            telemetry.bump("dist.halo_overflow_retry")
            report.degradations.append({
                "kind": "halo_overflow", "from": "shard_local",
                "to": "replicated",
                "error": f"partial coarse list overflowed halo_cap={h_cap}"})
            used = "replicated"
            pipe = make_distributed_pipeline(
                mesh, n, part.m_pad, spec, max_levels, aggregation_method,
                faults, used, h_cap, rs, track_modularity, promote)
            with timer.phase("pipeline"):
                out = pipe(src, dst, w, emask, jnp.uint32(seed), g.n_valid)
                (final, n_final, levels, q, sweeps_hist, ncomm_hist,
                 mod_hist, dn_hist, pg_hist, overflow) = jax.device_get(out)
        levels = int(levels)
        sweeps_list = [int(x) for x in sweeps_hist[:levels]]
        gathered = [int(x) for x in pg_hist[:levels]]
        model = dist_comm_bytes_per_level(n, part.m_pad, h_cap, D)
        table_bytes = (n + D) * LABEL_WIRE_BYTES
        comm_stats = {
            "mode": used,
            "requested": coarsening,
            "n_devices": D,
            "m_pad": int(part.m_pad),
            "halo_cap": h_cap,
            "bytes_per_level_model": model,
            "gathered_groups_per_level": gathered,
            "actual_bytes_per_level": [
                (table_bytes + gct * EDGE_WIRE_BYTES) if gct >= 0
                else model["replicated"] for gct in gathered],
            "halo_labels": int(pq.total_ghosts),
            # the devices that held an edge shard during this run
            "shard_devices": sorted(s.device.id
                                    for s in src.addressable_shards),
        }
        return DistLouvainResult(
            labels=np.asarray(final),
            n_communities=int(n_final),
            levels=levels,
            modularity=float(q),
            timer=timer,
            sweeps_per_level=sweeps_list,
            n_comm_per_level=[int(x) for x in ncomm_hist[:levels]],
            modularity_history=([float(x) for x in mod_hist[:levels]]
                                if track_modularity else []),
            delta_n_per_level=[[int(x) for x in row[:s]]
                               for row, s in zip(dn_hist[:levels],
                                                 sweeps_list)],
            coarsening=used,
            partition_stats=dict(pq._asdict()),
            comm_stats=comm_stats,
            run_report=report,
        )

    from repro.core.louvain import LEVEL_IT_STRIDE

    g0 = g
    assign = jnp.arange(n, dtype=jnp.int32)
    cur = g
    levels = 0
    sweeps_per_level: list = []
    n_comm_per_level: list = []
    partition_stats: dict = {}

    phase = make_distributed_phase(mesh, n, spec)
    for level in range(max_levels):
        with timer.phase("partition"):
            # the coverage guard applies per level: each re-partition is a
            # fresh opportunity to lose a shard
            part = _prepare_partition(cur, D)
            src, dst, w, emask = shard_edges(part, mesh)
        if level == 0:
            partition_stats = dict(partition_quality(part)._asdict())
        com = jnp.arange(n, dtype=jnp.int32)
        need = cur.vertex_mask()
        with timer.phase("local_moving"):
            # one fused phase per level: while_loop inside the shard_map
            com, need, sweeps, _, _ = phase(
                src, dst, w, emask, com, need,
                jnp.uint32(level * LEVEL_IT_STRIDE), jnp.uint32(seed),
                cur.weighted_degrees(), cur.total_volume(), cur.n_valid,
            )
        sweeps_per_level.append(int(sweeps))
        with timer.phase("aggregation"):
            new_com, n_comm, coarse = aggregation.remap_and_coarsen_by(
                aggregation_method, cur, com, faults)
            n_comm_per_level.append(int(n_comm))
            done = int(n_comm) == int(cur.n_valid)
            if not done:
                assign = new_com[jnp.clip(assign, 0, n - 1)]
                cur = coarse
        levels = level + 1
        if done:
            break

    final_assign, n_final = aggregation.remap_communities(assign, g0.vertex_mask())
    q = float(modularity(g0, final_assign, promote=promote))
    return DistLouvainResult(
        labels=np.asarray(final_assign),
        n_communities=int(n_final),
        levels=levels,
        modularity=q,
        timer=timer,
        sweeps_per_level=sweeps_per_level,
        n_comm_per_level=n_comm_per_level,
        coarsening="per_level",
        partition_stats=partition_stats,
        run_report=report,
    )


def distributed_leiden(g: Graph, mesh: Mesh, **kwargs) -> DistLouvainResult:
    """Leiden = Louvain + the refinement phase between move and aggregate
    (fused distributed pipeline only) — mirrors ``core.louvain.leiden``."""
    kwargs.setdefault("refine", True)
    return distributed_louvain(g, mesh, **kwargs)
