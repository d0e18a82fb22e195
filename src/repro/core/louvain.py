"""Parallel Louvain (paper Alg. 2 + Alg. 3) — TPU-native.

Faithful structure:
  * singleton init with comID = vertexID, volVertex/volCom arrays (Alg. 2 l.3-8)
  * local-moving: per-vertex parallel Δ𝑄 evaluation over neighboring
    communities (Eq. 1), greedy argmax move when Δ𝑄 > 0 (l.9-24)
  * needCheck set: re-evaluate a vertex only if it or a neighbor changed (l.11,
    l.21, l.25)
  * level loop: local-moving then aggregation until |C| == |V| (Alg. 3)

Adaptations (DESIGN.md §2 / §8): atomic volCom updates (l.18-19) become a
segment-sum recompute at each synchronous sweep; the Lu–Halappanavar singleton
tie-break suppresses the classic PLM two-singleton swap oscillation.

The sweep machinery lives in the shared ``core.engine`` (DESIGN.md §Engine).
With ``pipeline_fused=True`` (default) the ENTIRE level loop — fused
local-moving phase → remap → coarsen → modularity accounting, plus the
optional Leiden refinement phase — runs as one jitted ``lax.while_loop`` over
levels with the Alg. 3 ``|C| == |V|`` convergence predicate evaluated on
device: a whole Louvain/Leiden run is ONE dispatch with ONE host readback at
the end (DESIGN.md §Pipeline).  Per-level modularity / sweep-count /
community-count histories are written into fixed-size on-device buffers
(``-1`` / NaN sentinels) and reconstructed from that single transfer.

``pipeline_fused=False`` keeps the per-level Python driver (one fused
local-moving dispatch per level, aggregation and convergence check on host)
with a bit-for-bit parity contract against the fused pipeline, enforced by
``tests/test_pipeline.py``.

``capacity_schedule`` adds the coarse-level CASCADE (DESIGN.md §Pipeline):
once the carried coarse graph fits a smaller static capacity from a bounded
schedule, the fused loop exits, the graph is compacted on device
(``aggregation.shrink_graph``) and the level loop resumes under a program
compiled at the smaller capacity — so deep-hierarchy aggregation sorts and
sweeps stop paying level-0 cost.  Inside a cascade the ``ell``/``pallas``
backends also apply to COARSE levels, through the traced per-stage ELL
re-bucketing (``graph/ell.traced_ell_tile``); ``capacity_schedule="none"``
pins today's single-capacity program — the bit-for-bit parity oracle, with
the segment evaluator on coarse levels, matched by the per-level driver.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import math
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ConfigBase
from repro.core import aggregation
from repro.core.engine import EngineSpec, SweepEngine, device_phase
from repro.core.modularity import modularity
from repro.core.progcache import program_cache
from repro.graph.structure import Graph
from repro.kernels.common import (accum_needs_promotion, on_tpu,
                                  pick_ell_width)
from repro.utils import faultinject, resilience, telemetry
from repro.utils.errors import (CapacityError, CommunityDetectionError,
                                KernelError, NumericError, RunReport)
from repro.utils.timing import Timer

# Fault-injection points that act inside the sweep trace and therefore ride
# the EngineSpec (the jit cache key); the others act at the aggregation /
# driver / ingest layers and are threaded separately (DESIGN.md §Robustness).
ENGINE_FAULTS = ("oscillation", "vmem_starve")

# Kernel-failure degradation ladder: on a non-taxonomy failure the driver
# retries on the next-simpler backend — each step is bit-identical on clean
# input by the kernel≡ell≡segment parity contracts, so descending can only
# trade speed, never results.
BACKEND_DESCENT = {"pallas": "ell", "ell": "segment"}


def backend_descent(backend: str) -> Optional[str]:
    """The ladder's next backend after a failure of ``backend``, or None.

    Never on a TPU: there a backend failure is a fault of the kernels for
    that chip (a Pallas lowering or compile error), and descending would
    hand back an XLA answer in place of the device path that was asked
    for.  The failure surfaces as a ``KernelError``."""
    if on_tpu():
        return None
    return BACKEND_DESCENT.get(backend)

# Sweep-counter stride per level and the refinement phase's offset within a
# level: level L's local-moving phase hashes tie noise / Luby gates from
# it0 = L·LEVEL_IT_STRIDE, Leiden refinement from it0 + REFINE_IT_OFFSET.
# Shared with core.distributed so every driver (local per-level, local fused,
# distributed replicated, distributed shard-local) draws the SAME per-sweep
# randomness — a precondition of the bit-for-bit parity contracts.
LEVEL_IT_STRIDE = 1000
REFINE_IT_OFFSET = 500


# ------------------------------------------------------------ capacity schedule


def auto_capacity_schedule(
    n_max: int,
    m_max: int,
    *,
    max_stages: int = 4,
    shrink: int = 4,
    n_floor: int = 256,
    m_floor: int = 2048,
    min_n: int = 4096,
) -> Tuple[Tuple[int, int], ...]:
    """Bounded static capacity schedule for the coarse-level cascade.

    Quarter steps from the full capacity down to the floors, at most
    ``max_stages`` entries — so at most that many distinct compiled stage
    programs per run regardless of graph size or hierarchy depth (DESIGN.md
    §Pipeline).  Graphs below ``min_n`` vertices stay single-capacity: at
    that scale every level is dispatch-bound and extra compiles cost more
    than the shrink saves.
    """
    caps = [(int(n_max), int(m_max))]
    if n_max < min_n:
        return tuple(caps)
    while len(caps) < max_stages:
        # floors are clamped to the previous capacity: a graph whose own
        # capacity sits below a floor (e.g. a capacity-padded sparse graph
        # with m_max < m_floor) must never be scheduled to GROW
        nc = min(caps[-1][0], max(n_floor, -(-caps[-1][0] // shrink)))
        mc = min(caps[-1][1], max(m_floor, -(-caps[-1][1] // shrink)))
        if (nc, mc) == caps[-1]:
            break
        caps.append((nc, mc))
    return tuple(caps)


def _validate_schedule(sched) -> None:
    if isinstance(sched, str) and sched in ("auto", "none"):
        return
    ok = isinstance(sched, tuple) and len(sched) > 0
    if ok:
        for c in sched:
            if not (isinstance(c, tuple) and len(c) == 2 and all(
                    isinstance(x, int) and not isinstance(x, bool) and x > 0
                    for x in c)):
                ok = False
                break
    if ok:
        for a, b in zip(sched, sched[1:]):
            if not (b[0] <= a[0] and b[1] <= a[1] and b != a):
                ok = False
                break
    if not ok:
        raise ValueError(
            "capacity_schedule must be 'auto' (bounded schedule derived from "
            "the graph capacities), 'none' (single-capacity pipeline, the "
            "parity oracle), or an explicit tuple of descending "
            "(n_cap, m_cap) positive-int pairs such as "
            f"((8192, 131072), (2048, 32768)); got {sched!r}")


@dataclasses.dataclass(frozen=True)
class LouvainConfig(ConfigBase):
    max_levels: int = 10
    max_sweeps: int = 25        # Alg. 2 maxIteration
    sweep_threshold: int = 0    # stop local-moving when ΔN <= this
    backend: str = "segment"    # segment | ell | pallas
    # Coarsening path (DESIGN.md §Aggregation kernel): "binned" is the
    # sort-free scatter-accumulation default; "sort" selects the one-sort
    # fused remap+coarsen, kept as the bit-for-bit parity oracle.
    aggregation: str = "binned"  # binned | sort
    # ell/pallas table layout: VMEM-resident vs windowed streaming; "auto"
    # resolves from the VMEM byte budget (DESIGN.md §Kernels)
    table_mode: str = "auto"    # auto | resident | streamed
    use_need_check: bool = True
    singleton_rule: bool = True # Lu et al. swap suppression
    move_prob: float = 0.5      # Luby-style move gating (1.0 = pure Jacobi)
    seed: int = 0
    track_modularity: bool = True
    fused: bool = True          # one while_loop per level vs per-sweep dispatch
    # Whole-run fusion (DESIGN.md §Pipeline): the level loop itself becomes a
    # lax.while_loop, so louvain()/leiden() is one dispatch + one readback.
    # Requires fused sweeps; with fused=False the per-level driver runs.
    pipeline_fused: bool = True
    # Coarse-level cascade (DESIGN.md §Pipeline): once the carried coarse
    # graph fits a smaller static capacity from the schedule, the fused loop
    # descends to a program compiled at that capacity.  "auto" derives a
    # bounded (≤4-program) schedule from (n_max, m_max); "none" pins the
    # single-capacity pipeline (the bit-for-bit parity oracle); an explicit
    # tuple of descending (n_cap, m_cap) pairs is used as given.
    capacity_schedule: "str | Tuple[Tuple[int, int], ...]" = "auto"
    # Leiden-style refinement (beyond paper; the paper cites Leiden [30] as
    # the natural next algorithm): refine each community into well-connected
    # sub-communities before aggregation, then seed the next level with the
    # macro partition instead of singletons.
    refine: bool = False
    refine_sweeps: int = 8
    # Per-level driver only: record additional L<level>/<phase> timer entries
    # (the paper-style fig4 phase split used by `benchmarks/run.py
    # level_fusion`).
    per_level_timing: bool = False
    # Opt-in stage-boundary checkpoint/resume (DESIGN.md §Resilience): at
    # every cascade stage boundary the carried device state (graph arrays,
    # assignment chain, history buffers, level counter) is persisted via the
    # atomic write-then-rename checkpointer (train/checkpoint.py) into this
    # directory; a killed/preempted run re-invoked with the SAME config and
    # graph resumes from the last committed boundary, bit-identical to the
    # uninterrupted run.  Granularity is the stage boundary — a kill inside
    # a stage replays that stage.  One run per directory; checkpoints are
    # cleared on successful completion.  None (default) = no checkpointing;
    # degenerate (single-stage) schedules cross no boundary and never save.
    checkpoint_dir: Optional[str] = None

    def __post_init__(self):
        if self.max_levels < 1:
            raise ValueError(
                f"max_levels must be >= 1, got {self.max_levels}")
        if not (0.0 < self.move_prob <= 1.0):
            raise ValueError(
                f"move_prob must be in (0, 1], got {self.move_prob}")
        if self.refine_sweeps < 1:
            raise ValueError(
                f"refine_sweeps must be >= 1, got {self.refine_sweeps}")
        if self.aggregation not in aggregation.AGGREGATION_METHODS:
            raise ValueError(
                f"aggregation must be one of "
                f"{aggregation.AGGREGATION_METHODS}, got {self.aggregation!r}")
        _validate_schedule(self.capacity_schedule)


@dataclasses.dataclass
class LouvainResult:
    labels: np.ndarray            # community id per ORIGINAL vertex (contiguous)
    n_communities: int
    levels: int
    modularity: float
    modularity_history: list      # per level
    sweeps_per_level: list
    timer: Timer
    n_comm_per_level: list = dataclasses.field(default_factory=list)
    delta_n_per_level: list = dataclasses.field(default_factory=list)
    # (n_cap, m_cap) of each cascade stage actually entered, in order; a
    # single entry means the schedule degenerated to one program
    cascade_stages: list = dataclasses.field(default_factory=list)
    # what the hardened driver repaired / retried / degraded / flagged on
    # the way here (DESIGN.md §Robustness); clean on the happy path
    run_report: RunReport = dataclasses.field(default_factory=RunReport)


def engine_spec(cfg: LouvainConfig, backend: Optional[str] = None,
                max_sweeps: Optional[int] = None,
                faults: frozenset = frozenset()) -> EngineSpec:
    return EngineSpec(
        evaluator="louvain",
        backend=backend or cfg.backend,
        max_sweeps=cfg.max_sweeps if max_sweeps is None else max_sweeps,
        threshold=cfg.sweep_threshold,
        move_prob=float(cfg.move_prob),
        use_frontier=cfg.use_need_check,
        singleton_rule=cfg.singleton_rule,
        table_mode=cfg.table_mode,
        faults=tuple(sorted(f for f in faults if f in ENGINE_FAULTS)),
    )


def _coarse_backend(backend: str) -> str:
    """DESIGN.md §Pipeline: the host-built ELL layout covers the finest
    graph only; OUTSIDE a cascade every coarse level runs the segment
    evaluator (in both the single-capacity pipeline and the per-level
    driver, so they stay bit-identical).  Cascade stages instead re-bucket
    on the fly — see ``_cascade_coarse_spec``."""
    return "segment" if backend in ("ell", "pallas") else backend


def _resolve_schedule(cfg: LouvainConfig, g: Graph) -> Tuple[Tuple[int, int], ...]:
    """Concrete capacity schedule for this graph: full capacity first, then
    the validated descending entries that actually fit under it."""
    sched = cfg.capacity_schedule
    full = (g.n_max, g.m_max)
    if sched == "none":
        return (full,)
    if sched == "auto":
        return auto_capacity_schedule(g.n_max, g.m_max)
    caps = [full]
    for c in sched:
        c = (int(c[0]), int(c[1]))
        if (c[0] <= full[0] and c[1] <= full[1]
                and (c[0] < caps[-1][0] or c[1] < caps[-1][1])):
            caps.append(c)
    return tuple(caps)


def _cascade_coarse_spec(cfg: LouvainConfig, cascade: bool, width: int,
                         faults: frozenset = frozenset()) -> EngineSpec:
    """Coarse-level engine spec for one stage.

    Inside a cascade the ``ell``/``pallas`` backends keep their fused
    local_move kernels on coarse levels via the traced re-bucketing at the
    stage's static ``width``; outside (the parity oracle) the historical
    segment fallback applies."""
    if cascade and cfg.backend in ("ell", "pallas"):
        return engine_spec(cfg, faults=faults).replace(ell_width=width)
    return engine_spec(cfg, backend=_coarse_backend(cfg.backend),
                       faults=faults)


def _refine_spec(cfg: LouvainConfig,
                 faults: frozenset = frozenset()) -> EngineSpec:
    return engine_spec(cfg, backend="segment", max_sweeps=cfg.refine_sweeps,
                       faults=faults).replace(threshold=0)


# ------------------------------------------------------------ transfer hooks

_transfer_count = 0   # incremented on every pipeline readback (test hook)
_stage_sync_count = 0  # incremented on every cascade stage-boundary sync


def _readback(tree):
    """The ONE bulk device→host transfer of the fused pipeline.

    Every host materialization of results in the ``pipeline_fused`` path
    flows through this function, so tests can count transfers by
    monkeypatching it (or by reading ``_transfer_count``)."""
    global _transfer_count
    _transfer_count += 1
    return jax.device_get(tree)


def _stage_sync(tree):
    """The tiny per-stage-boundary host sync of the cascade: five scalars —
    (done, level, n_valid, m_valid, max_deg) — deciding whether to finalize
    or where to descend, and the next stage's traced-ELL width.  Counted
    separately from the one bulk ``_readback`` so tests can assert the
    cascade's transfer accounting; a degenerate (single-capacity) schedule
    never syncs."""
    global _stage_sync_count
    _stage_sync_count += 1
    done, level, nv, mv, max_deg = jax.device_get(tree)
    return bool(done), int(level), int(nv), int(mv), int(max_deg)


# ------------------------------------------------------------ fused pipeline


def _graph_arrays(g: Graph):
    return (g.src, g.dst, g.w, g.edge_mask, g.n_valid, g.m_valid)


def _build_stage(spec0: Optional[EngineSpec], spec_coarse: EngineSpec,
                 refine_spec: Optional[EngineSpec], max_levels: int,
                 track_modularity: bool, next_caps: Optional[Tuple[int, int]],
                 agg_method: str = "binned",
                 faults: frozenset = frozenset(), promote: bool = False):
    """Build one (un-jitted) cascade stage function (DESIGN.md §Pipeline).

    ``_stage_fn`` wraps this in ``jax.jit`` for the single-graph cascade
    driver; the batched many-graph engine (``core.batch``) instead lifts the
    same pure stage function through ``jax.vmap`` — one builder, two
    dispatch disciplines, so the batched path can never drift from the
    single-graph parity oracle.

    ``spec0 is not None`` marks stage 0: level 0 is peeled out of the loop
    (it may use the host-built ELL backend and always starts from
    singletons); with ``next_caps=None`` as well, this is exactly the
    single-capacity whole-run pipeline — the parity oracle.  Later stages
    resume the level loop from carried state at their own (smaller) static
    capacity.  Levels run inside a ``lax.while_loop`` with the Alg. 3
    ``n_comm == n_valid`` predicate on device; ``next_caps`` adds the
    cascade descent predicate — the loop hands control back to the host
    scheduler (one 5-scalar ``_stage_sync``) as soon as the carried coarse
    graph fits the next capacity.

    Histories are fixed-size on-device buffers threaded THROUGH stages and
    written at absolute level indices — ``modularity[max_levels]`` (NaN
    sentinel), ``sweeps/n_comm[max_levels]`` and
    ``delta_n[max_levels, max_sweeps]`` (``-1`` sentinel, the PR-1
    convention) — so the one bulk readback at the end reconstructs
    ``LouvainResult`` unchanged regardless of how many stages ran.  The
    fifth history element is the scalar non-finite-weight flag (numeric
    guard rail): each level ORs in a finiteness check of its input graph,
    and the driver refuses the answer (``NumericError``) if it comes back
    set — it rides the same bulk readback, costing no extra transfer.
    """

    def stage(g: Graph, ell, g0: Graph, seed, assign, init_com, macro_in,
              level_in, hists):
        n = g.n_max
        arange_n = jnp.arange(n, dtype=jnp.int32)

        def run_level(cur: Graph, assign, init_com, level_u32, spec, ell):
            """One level: fused local-moving → sort-free (or one-sort)
            remap+coarsen → (refine).

            Mirrors one iteration of the per-level driver exactly; returns
            the next level's graph arrays + bookkeeping and this level's
            history entries."""
            if "nan_weight" in faults:
                # fault injection: poison one edge weight at level 1 (a
                # coarse graph mid-pipeline, the hardest place to observe) —
                # the guard below must flag it through the single readback
                cur = dataclasses.replace(cur, w=cur.w.at[0].set(jnp.where(
                    level_u32 == jnp.uint32(1), jnp.float32(jnp.nan),
                    cur.w[0])))
            # numeric guard rail: non-finite weights anywhere in the level
            # loop poison sums silently (NaN gains → no proposals → a
            # "converged" wrong answer), so every level checks its input
            lvl_bad = jnp.any(cur.edge_mask & ~jnp.isfinite(cur.w))
            vmask = cur.vertex_mask()
            it0 = level_u32 * jnp.uint32(LEVEL_IT_STRIDE)
            com, _, sweeps, dn_h, _act_h = device_phase(
                spec, cur, ell, init_com, vmask, it0, seed)
            if refine_spec is None:
                # sort-free binned coarsening by default (DESIGN.md
                # §Pipeline sort-free invariant); "sort" selects the fused
                # one-sort oracle — both bit-for-bit identical
                new_com, n_comm, nxt = aggregation.remap_and_coarsen_by(
                    agg_method, cur, com, faults)
            else:
                # Leiden aggregates by the REFINED partition below; only the
                # macro remap is needed here
                new_com, n_comm = aggregation.remap_communities(com, vmask)
            macro_assign = new_com[jnp.clip(assign, 0, n - 1)]
            done = n_comm == cur.n_valid           # Alg. 3 l.6 convergence
            q = (modularity(g0, macro_assign, promote=promote)
                 if track_modularity else jnp.float32(0.0))

            def advance(_):
                if refine_spec is not None:
                    # Leiden: aggregate by the REFINED partition; seed the
                    # next level's local-moving with each super-vertex's
                    # macro id (paper-order: refinement only when not done)
                    ref, _, _, _, _ = device_phase(
                        refine_spec, cur, None, arange_n, vmask,
                        it0 + jnp.uint32(REFINE_IT_OFFSET), seed, restrict=com)
                    new_ref, n_ref, nxt_r = aggregation.remap_and_coarsen_by(
                        agg_method, cur, ref, faults)
                    # macro seed as the CONTIGUIZED macro id (all members of
                    # a refined group share it): values < n_comm stay valid
                    # under any later stage capacity, and the relabeling is
                    # monotone in the raw id, so every order-based tie-break
                    # downstream is unchanged
                    macro_of_ref = jax.ops.segment_max(
                        jnp.where(vmask, new_com, -1),
                        jnp.clip(new_ref, 0, n - 1), num_segments=n)
                    return (_graph_arrays(nxt_r),
                            new_ref[jnp.clip(assign, 0, n - 1)],
                            jnp.clip(macro_of_ref, 0, n - 1).astype(jnp.int32))
                return _graph_arrays(nxt), macro_assign, arange_n

            def stay(_):
                return _graph_arrays(cur), assign, init_com

            nxt_arrays, assign2, init2 = jax.lax.cond(done, stay, advance,
                                                      None)
            return (nxt_arrays, assign2, init2, macro_assign,
                    sweeps.astype(jnp.int32), dn_h, n_comm, q, done, lvl_bad)

        mod_hist, sweeps_hist, ncomm_hist, dn_hist, bad_w = hists

        if spec0 is not None:
            # peeled level 0: the only level that may use the host-built ELL
            (arrays, assign, init_com, macro, sweeps, dn_h, n_comm, q,
             done, lvl_bad) = run_level(g, assign, init_com, jnp.uint32(0),
                                        spec0, ell)
            mod_hist = mod_hist.at[0].set(q)
            sweeps_hist = sweeps_hist.at[0].set(sweeps)
            ncomm_hist = ncomm_hist.at[0].set(n_comm)
            dn_hist = dn_hist.at[0].set(dn_h)
            bad_w = bad_w | lvl_bad
            level = jnp.int32(1)
        else:
            arrays = _graph_arrays(g)
            macro = macro_in
            done = jnp.bool_(False)
            level = level_in

        def cond(c):
            level, done, arrays = c[0], c[1], c[2]
            keep = (level < max_levels) & (~done)
            if next_caps is not None:
                # cascade descent: exit once the carried graph fits the
                # next (smaller) static capacity
                fits = ((arrays[4] <= next_caps[0])
                        & (arrays[5] <= next_caps[1]))
                keep = keep & (~fits)
            return keep

        def body(c):
            (level, _done, arrays, assign, init_com, _macro,
             mh, sh, nh, dh, bw) = c
            src, dst, w, em, nv, mv = arrays
            # coarsening output is src-sorted and front-compacted — the
            # invariant the traced ELL re-bucketing relies on
            cur = Graph(src=src, dst=dst, w=w, edge_mask=em, n_valid=nv,
                        m_valid=mv, n_max=n, m_max=g.m_max,
                        sorted_by="src")
            (arrays2, assign2, init2, macro2, sweeps, dn_h, n_comm, q,
             done2, lvl_bad) = run_level(cur, assign, init_com,
                                         level.astype(jnp.uint32),
                                         spec_coarse, None)
            mh = mh.at[level].set(q)
            sh = sh.at[level].set(sweeps)
            nh = nh.at[level].set(n_comm)
            dh = dh.at[level].set(dn_h)
            return (level + 1, done2, arrays2, assign2, init2, macro2,
                    mh, sh, nh, dh, bw | lvl_bad)

        carry = (level, done, arrays, assign, init_com, macro,
                 mod_hist, sweeps_hist, ncomm_hist, dn_hist, bad_w)
        carry = jax.lax.while_loop(cond, body, carry)
        (level, done, arrays, assign, init_com, macro,
         mod_hist, sweeps_hist, ncomm_hist, dn_hist, bad_w) = carry

        # stage-boundary stats for the host scheduler: live counts plus the
        # carried graph's max unweighted degree (next stage's width pick) —
        # only a stage that CAN descend pays for the degree reduction
        src, _dst, _w, em, nv, mv = arrays
        if next_caps is None:
            max_deg = jnp.int32(0)
        else:
            deg_cnt = jax.ops.segment_sum(
                jnp.where(em, 1, 0), jnp.clip(src, 0, n - 1), num_segments=n)
            max_deg = jnp.max(jnp.where(arange_n < nv, deg_cnt, 0))

        def finalize(_):
            final_assign, n_final = aggregation.remap_communities(
                macro, g0.vertex_mask())
            return (final_assign, n_final,
                    modularity(g0, final_assign, promote=promote))

        if next_caps is None:
            final_assign, n_final, q_final = finalize(None)
        else:
            # intermediate stages skip the full-capacity final remap +
            # modularity pass: the host only reads these outputs when the
            # run terminates in THIS stage (done or level budget exhausted)
            final_assign, n_final, q_final = jax.lax.cond(
                done | (level >= max_levels), finalize,
                lambda _: (jnp.zeros((g0.n_max,), jnp.int32), jnp.int32(0),
                           jnp.float32(0.0)),
                None)
        return (arrays, assign, init_com, macro,
                (mod_hist, sweeps_hist, ncomm_hist, dn_hist, bad_w),
                level, done, nv, mv, max_deg,
                final_assign, n_final, q_final)

    return stage


@program_cache("louvain.stage", maxsize=64)
def _stage_fn(spec0: Optional[EngineSpec], spec_coarse: EngineSpec,
              refine_spec: Optional[EngineSpec], max_levels: int,
              track_modularity: bool, next_caps: Optional[Tuple[int, int]],
              agg_method: str = "binned",
              faults: frozenset = frozenset(), promote: bool = False):
    """Jitted ``_build_stage``, memoized on the full static key.

    ``faults`` / ``promote`` are part of the cache key ON PURPOSE: a trace
    compiled clean must never be reused under injection (and vice versa).
    Clean runs always pass the defaults, so their cache behavior is
    unchanged.  The cache is bounded (DESIGN.md §Serving): the key ranges
    over the static menus (≤4 cascade capacities, 3 ELL widths, spec
    variants), so 64 entries hold every program a sane workload compiles
    and a long-lived serving process cannot leak programs across config
    churn.
    """
    return jax.jit(_build_stage(spec0, spec_coarse, refine_spec, max_levels,
                                track_modularity, next_caps, agg_method,
                                faults, promote))


@program_cache("louvain.shrink", maxsize=64)
def _shrink_fn(n_in: int, m_in: int, n_out: int, m_out: int):
    """Jitted stage-boundary compaction: slice the front-compacted carried
    graph (and the Leiden macro seed) into the next static capacity —
    ``aggregation.shrink_graph``, entirely on device."""

    def f(arrays, init_com):
        src, dst, w, em, nv, mv = arrays
        gin = Graph(src=src, dst=dst, w=w, edge_mask=em, n_valid=nv,
                    m_valid=mv, n_max=n_in, m_max=m_in, sorted_by="src")
        return aggregation.shrink_graph(gin, n_out, m_out), init_com[:n_out]

    return jax.jit(f)


# ------------------------------------------------- stage checkpoint/resume


def _ckpt_fingerprint(cfg: LouvainConfig, g: Graph) -> dict:
    """Identity of a checkpointable run: the full config (minus the
    checkpoint location itself) + cheap graph identity (capacities, live
    counts, masked weight sum).  A restore whose fingerprint mismatches is
    IGNORED (fresh start + ``louvain.ckpt_mismatch_ignored`` counter) —
    resuming someone else's state would be a silent wrong answer.  The
    json round-trip normalizes tuples to lists so the comparison against
    the manifest-loaded value is exact."""
    d = cfg.to_dict()
    d.pop("checkpoint_dir", None)
    return json.loads(json.dumps({
        "cfg": d,
        "graph": {"n_max": int(g.n_max), "m_max": int(g.m_max),
                  "n_valid": int(g.n_valid), "m_valid": int(g.m_valid),
                  "w_sum": float(jnp.sum(
                      jnp.where(g.edge_mask, g.w, 0.0)))}}))


def _ckpt_save_stage(ckpt_dir: str, fp: dict, k: int, width: int,
                     stage_idxs, g_k: Graph, assign, init_com, macro,
                     level, hists) -> None:
    """Persist the carried device state at a cascade stage boundary —
    the post-shrink graph entering stage ``k`` plus the 5 history buffers,
    the assignment chain and the level counter — via the atomic
    write-then-rename checkpointer, so a crash mid-save never corrupts
    the last committed boundary.  The stage-varying scheduler metadata
    (k, traced-ELL width, stages entered so far) rides the manifest."""
    from repro.train import checkpoint

    tree = {"graph": list(_graph_arrays(g_k)), "assign": assign,
            "init_com": init_com, "macro": macro, "level": level,
            "hists": list(hists)}
    meta = {"fingerprint": fp,
            "stage": {"k": int(k), "width": int(width),
                      "stage_idxs": [int(j) for j in stage_idxs]}}
    checkpoint.save(ckpt_dir, len(stage_idxs), tree,
                    config_json=json.dumps(meta), keep=2)
    telemetry.bump("louvain.ckpt_save")


def _ckpt_try_resume(cfg: LouvainConfig, caps, n0: int, fp: dict):
    """Restore the latest committed stage boundary, or None (no/stale/
    mismatched checkpoint → start fresh)."""
    from repro.train import checkpoint

    ckpt_dir = cfg.checkpoint_dir
    step = checkpoint.latest_step(ckpt_dir)
    if step is None:
        return None
    with open(os.path.join(ckpt_dir, f"step_{step:08d}",
                           "manifest.json")) as f:
        meta = json.load(f)["config"]
    if meta.get("fingerprint") != fp:
        telemetry.bump("louvain.ckpt_mismatch_ignored")
        return None
    stage = meta["stage"]
    k, width = int(stage["k"]), int(stage["width"])
    stage_idxs = [int(j) for j in stage["stage_idxs"]]
    if not 0 < k < len(caps):
        telemetry.bump("louvain.ckpt_mismatch_ignored")
        return None
    n_k, m_k = caps[k]
    sds = jax.ShapeDtypeStruct
    like = {"graph": [sds((m_k,), jnp.int32), sds((m_k,), jnp.int32),
                      sds((m_k,), jnp.float32), sds((m_k,), jnp.bool_),
                      sds((), jnp.int32), sds((), jnp.int32)],
            "assign": sds((n0,), jnp.int32),
            "init_com": sds((n_k,), jnp.int32),
            "macro": sds((n0,), jnp.int32),
            "level": sds((), jnp.int32),
            "hists": [sds((cfg.max_levels,), jnp.float32),
                      sds((cfg.max_levels,), jnp.int32),
                      sds((cfg.max_levels,), jnp.int32),
                      sds((cfg.max_levels, cfg.max_sweeps), jnp.int32),
                      sds((), jnp.bool_)]}
    tree = checkpoint.restore(ckpt_dir, step, like)
    src, dst, w, em, nv, mv = tree["graph"]
    g_k = Graph(src=src, dst=dst, w=w, edge_mask=em, n_valid=nv,
                m_valid=mv, n_max=n_k, m_max=m_k, sorted_by="src")
    return (k, width, stage_idxs, g_k, tree["assign"], tree["init_com"],
            tree["macro"], tree["level"], tuple(tree["hists"]))


def _ckpt_clear(ckpt_dir: str) -> None:
    """Drop committed stage checkpoints after a successful run: the next
    run in this directory starts fresh instead of resuming a finished
    cascade's tail."""
    import shutil

    from repro.train import checkpoint

    for s in checkpoint.all_steps(ckpt_dir):
        shutil.rmtree(os.path.join(ckpt_dir, f"step_{s:08d}"),
                      ignore_errors=True)


def _louvain_pipeline(g: Graph, cfg: LouvainConfig,
                      g_original: Optional[Graph],
                      faults: frozenset = frozenset(),
                      promote: bool = False) -> LouvainResult:
    """Whole-run fused driver: a cascade of at most ``len(schedule)`` stage
    dispatches with ONE bulk readback (``_readback``) at the end and one
    5-scalar ``_stage_sync`` per stage boundary.  A degenerate schedule
    (``"none"``, or ``"auto"`` on a small graph) is exactly the historical
    single-dispatch single-readback pipeline."""
    timer = Timer()
    g0 = g_original if g_original is not None else g
    caps = _resolve_schedule(cfg, g)
    cascade = len(caps) > 1
    spec0 = engine_spec(cfg, faults=faults)
    refine_spec = _refine_spec(cfg, faults) if cfg.refine else None

    n0 = g.n_max
    arange0 = jnp.arange(n0, dtype=jnp.int32)
    hists = (jnp.full((cfg.max_levels,), jnp.nan, jnp.float32),
             jnp.full((cfg.max_levels,), -1, jnp.int32),
             jnp.full((cfg.max_levels,), -1, jnp.int32),
             jnp.full((cfg.max_levels, cfg.max_sweeps), -1, jnp.int32),
             jnp.bool_(False))
    seed_a = jnp.uint32(cfg.seed)

    k = 0
    width = pick_ell_width(None, *caps[0])
    g_k = g
    assign, init_com, macro = arange0, arange0, arange0
    level = jnp.int32(0)
    stage_idxs: list = []

    # Stage-boundary checkpointing only has boundaries to commit when the
    # schedule cascades; a degenerate schedule is a single dispatch.
    ckpt_fp = None
    if cfg.checkpoint_dir and cascade:
        ckpt_fp = _ckpt_fingerprint(cfg, g)
        resumed = _ckpt_try_resume(cfg, caps, n0, ckpt_fp)
        if resumed is not None:
            (k, width, stage_idxs, g_k, assign, init_com, macro, level,
             hists) = resumed
            telemetry.bump("louvain.ckpt_resume")

    ell_k = None
    if k == 0 and cfg.backend in ("ell", "pallas"):
        # resumed stages (k > 0) re-bucket via the traced per-stage ELL
        # path, same as post-shrink stages — no host build needed
        from repro.graph import ell as ell_mod

        with timer.phase("ell_build"):
            ell_k = ell_mod.build_device_ell(
                g, ell_mod.bucket_widths(cfg.backend))

    with timer.phase("pipeline"):
        while True:
            fn = _stage_fn(spec0 if k == 0 else None,
                           _cascade_coarse_spec(cfg, cascade, width, faults),
                           refine_spec, cfg.max_levels, cfg.track_modularity,
                           caps[k + 1] if k + 1 < len(caps) else None,
                           cfg.aggregation, faults, promote)
            (arrays, assign, init_com, macro, hists, level, done, nv, mv,
             max_deg, final_assign, n_final, q_final) = fn(
                g_k, ell_k, g0, seed_a, assign, init_com, macro, level,
                hists)
            stage_idxs.append(k)
            if k + 1 >= len(caps):
                break
            done_h, level_h, nv_h, mv_h, max_deg_h = _stage_sync(
                (done, level, nv, mv, max_deg))
            if done_h or level_h >= cfg.max_levels:
                break
            # descend to the SMALLEST capacity the carried graph fits, so a
            # fast-collapsing hierarchy skips intermediate programs
            k2 = k
            for j in range(k + 1, len(caps)):
                if nv_h <= caps[j][0] and mv_h <= caps[j][1]:
                    k2 = j
            if k2 == k:
                # unreachable by the loop-exit predicate (it only exits on
                # done / budget / fits-next); a silent break here would
                # return the intermediate stage's skipped final outputs.
                # Typed so the degradation ladder can retry the run on the
                # single-capacity (schedule="none") program.
                raise CapacityError(
                    "cascade invariant violated: stage exited without "
                    f"done/budget and ({nv_h}, {mv_h}) fits no capacity in "
                    f"{caps[k + 1:]}")
            g_k, init_com = _shrink_fn(*caps[k], *caps[k2])(arrays, init_com)
            ell_k = None
            k = k2
            width = pick_ell_width(max_deg_h, *caps[k])
            if ckpt_fp is not None:
                _ckpt_save_stage(cfg.checkpoint_dir, ckpt_fp, k, width,
                                 stage_idxs, g_k, assign, init_com, macro,
                                 level, hists)
            if faultinject.consume("preempt_stage"):
                # AFTER the checkpoint committed: models a kill between
                # stages, the worst-case window the resume path must cover
                raise resilience.Preempted(
                    "injected preemption at cascade stage boundary "
                    f"(entering stage k={k})")

        out = _readback((final_assign, n_final, level, q_final) + hists)
    (final_assign, n_final, levels, q, mod_hist, sweeps_hist, ncomm_hist,
     dn_hist, bad_w) = out

    if bool(bad_w):
        # the guard-rail flag from the level loop (rode the one readback):
        # refuse the answer rather than return a silently-poisoned partition
        raise NumericError(
            "non-finite edge weight detected inside the fused level loop")
    if ckpt_fp is not None:
        _ckpt_clear(cfg.checkpoint_dir)
    levels = int(levels)
    sweeps_per_level = [int(s) for s in sweeps_hist[:levels]]
    return LouvainResult(
        labels=np.asarray(final_assign),
        n_communities=int(n_final),
        levels=levels,
        modularity=float(q),
        modularity_history=(
            [float(x) for x in mod_hist[:levels]]
            if cfg.track_modularity else []),
        sweeps_per_level=sweeps_per_level,
        timer=timer,
        n_comm_per_level=[int(x) for x in ncomm_hist[:levels]],
        delta_n_per_level=[
            [int(x) for x in row[:s]]
            for row, s in zip(dn_hist[:levels], sweeps_per_level)],
        cascade_stages=[caps[j] for j in stage_idxs],
    )


# ------------------------------------------------------------ refinement


def _refine_partition(cur: Graph, com_macro: jax.Array, cfg: LouvainConfig,
                      level: int,
                      faults: frozenset = frozenset()) -> jax.Array:
    """Leiden refinement: greedy modularity merges restricted to the macro
    communities, starting from singletons.  Guarantees every aggregated
    super-vertex is contained in (and connected within) a macro community."""
    engine = SweepEngine(cur, _refine_spec(cfg, faults))
    res = engine.run_phase(
        *engine.singleton_state(),
        it0=level * LEVEL_IT_STRIDE + REFINE_IT_OFFSET, seed=cfg.seed,
        restrict=com_macro, fused=cfg.fused,
    )
    return res.labels


# ------------------------------------------------------------ driver (Alg. 3)


def leiden(g: Graph, cfg: LouvainConfig = LouvainConfig(),
           g_original: Optional[Graph] = None) -> LouvainResult:
    """Leiden = Louvain + refinement phase + macro-seeded levels."""
    return louvain(g, cfg.replace(refine=True), g_original)


def _trivial_result(report: RunReport) -> LouvainResult:
    """Degenerate zero-capacity graph: nothing to cluster, nothing to run."""
    return LouvainResult(
        labels=np.zeros((0,), np.int32), n_communities=0, levels=0,
        modularity=0.0, modularity_history=[], sweeps_per_level=[],
        timer=Timer(), run_report=report)


def _finalize_report(res: LouvainResult, cfg: LouvainConfig,
                     report: RunReport) -> LouvainResult:
    """Watchdog accounting + the final numeric gate, after any ladder."""
    for i, s in enumerate(res.sweeps_per_level):
        if s >= cfg.max_sweeps:
            report.warnings.append(f"watchdog:max_sweeps:level{i}")
    if res.levels >= cfg.max_levels:
        report.warnings.append("watchdog:max_levels")
    res.run_report = report
    if not math.isfinite(res.modularity):
        raise NumericError(
            f"non-finite final modularity {res.modularity!r}", report=report)
    return res


def louvain(g: Graph, cfg: LouvainConfig = LouvainConfig(),
            g_original: Optional[Graph] = None) -> LouvainResult:
    """Hardened driver (DESIGN.md §Robustness): runs the fused pipeline or
    the per-level driver under a bounded retry/degradation ladder —

      * capacity bust (``CapacityError``) → ONE retry on the
        single-capacity ``capacity_schedule="none"`` program;
      * non-taxonomy backend failure → descend ``pallas → ell → segment``
        (each step bit-identical on clean input by the parity contracts),
        except on a TPU, where it raises (``backend_descent``);
      * typed taxonomy errors (numeric, validation, …) propagate — they
        mean the ANSWER is unsafe, so no amount of retrying helps;

    everything attempted is recorded in ``result.run_report``.  The clean
    path runs exactly one attempt with default fault/promotion state, so
    its traces, transfer counts and results are unchanged."""
    report = RunReport(faults=sorted(faultinject.active()))
    if g.n_max == 0:
        return _trivial_result(report)
    faults = frozenset(faultinject.active())
    promote = accum_needs_promotion(g.m_max)
    if promote:
        report.warnings.append("precision:f32_accum_risk"
                               if not jax.config.jax_enable_x64
                               else "precision:promoted_f64")
    cfg_try = cfg
    while True:
        try:
            if cfg_try.pipeline_fused and cfg_try.fused:
                res = _louvain_pipeline(g, cfg_try, g_original, faults,
                                        promote)
            else:
                res = _louvain_per_level(g, cfg_try, g_original, faults,
                                         promote)
            break
        except CapacityError as err:
            if cfg_try.capacity_schedule == "none":
                err.report = report
                raise
            telemetry.bump("ladder.capacity_retry")
            report.retries.append({
                "kind": "capacity",
                "from": repr(cfg_try.capacity_schedule), "to": "none",
                "error": str(err)})
            cfg_try = cfg_try.replace(capacity_schedule="none")
        except CommunityDetectionError as err:
            err.report = report
            raise
        except Exception as err:  # noqa: BLE001 — the backend-descent rung
            nxt = backend_descent(cfg_try.backend)
            if nxt is None:
                raise KernelError(
                    f"backend {cfg_try.backend!r} failed with no descent "
                    f"left: {type(err).__name__}: {err}",
                    report=report) from err
            telemetry.bump("ladder.backend_descent")
            report.degradations.append({
                "kind": "backend_descent",
                "from": cfg_try.backend, "to": nxt,
                "error": f"{type(err).__name__}: {err}"})
            cfg_try = cfg_try.replace(backend=nxt)
    return _finalize_report(res, cfg_try, report)


def _tphase(timer: Timer, name: str, level: int, per_level: bool):
    """timer.phase(name), optionally doubled with a level-tagged entry."""
    if not per_level:
        return timer.phase(name)
    stack = contextlib.ExitStack()
    stack.enter_context(timer.phase(name))
    stack.enter_context(timer.phase(f"L{level:02d}/{name}"))
    return stack


def _louvain_per_level(g: Graph, cfg: LouvainConfig,
                       g_original: Optional[Graph],
                       faults: frozenset = frozenset(),
                       promote: bool = False) -> LouvainResult:
    """Per-level Python driver (``pipeline_fused=False``): one fused
    local-moving dispatch per level, aggregation + Alg. 3 convergence on
    host.  Bit-for-bit parity with the fused pipeline is contractual
    (tests/test_pipeline.py) — any change here must be mirrored in
    ``_stage_fn`` and vice versa."""
    timer = Timer()
    g0 = g_original if g_original is not None else g
    n = g.n_max

    assign = jnp.arange(n, dtype=jnp.int32)  # original vertex -> community
    cur = g
    mod_hist: list = []
    sweeps_per_level: list = []
    n_comm_per_level: list = []
    delta_n_per_level: list = []
    levels = 0

    init_com = None   # Leiden: macro partition seeds the next level
    for level in range(cfg.max_levels):
        spec = engine_spec(
            cfg, backend=cfg.backend if level == 0
            else _coarse_backend(cfg.backend), faults=faults)
        if "nan_weight" in faults and level == 1:
            # fault injection: same poison as the fused pipeline's
            cur = dataclasses.replace(
                cur, w=cur.w.at[0].set(jnp.float32(jnp.nan)))
        # numeric guard rail, mirroring the fused pipeline's per-level
        # check (host-side here: this driver already syncs every level)
        if bool(jnp.any(cur.edge_mask & ~jnp.isfinite(cur.w))):
            raise NumericError(
                f"non-finite edge weight detected at level {level}")
        with timer.phase("ell_build") if spec.backend in ("ell", "pallas") \
                else contextlib.nullcontext():
            engine = SweepEngine(cur, spec)
        com = (jnp.arange(n, dtype=jnp.int32)  # singleton init (Alg. 2 l.4)
               if init_com is None else init_com)
        init_com = None
        need = cur.vertex_mask()               # needCheck = true (l.7)

        # ONE fused while_loop call per level (DESIGN.md §Engine): the whole
        # local-moving phase converges on device before anything syncs back
        with _tphase(timer, "local_moving", level, cfg.per_level_timing):
            res = engine.run_phase(
                com, need, it0=level * LEVEL_IT_STRIDE, seed=cfg.seed, fused=cfg.fused)
        com = res.labels
        sweeps_per_level.append(res.sweeps)
        delta_n_per_level.append(res.delta_n_history)

        with _tphase(timer, "aggregation", level, cfg.per_level_timing):
            # sort-free binned coarsening by default; "sort" keeps the fused
            # one-sort oracle — bit-identical either way, and also to the
            # two-step remap_communities_sorted + coarsen_graph reference
            if cfg.refine:
                new_com, n_comm = aggregation.remap_communities(
                    com, cur.vertex_mask())
            else:
                new_com, n_comm, coarse = aggregation.remap_and_coarsen_by(
                    cfg.aggregation, cur, com, faults)
            # macro labels on ORIGINAL vertices (the result partition); under
            # refinement `assign` tracks the finer refined chain instead
            macro_assign = new_com[jnp.clip(assign, 0, n - 1)]
            n_comm_i = int(n_comm)
            n_valid_i = int(cur.n_valid)
            n_comm_per_level.append(n_comm_i)
            done = n_comm_i == n_valid_i          # Alg. 3 l.6 convergence
            if not done and cfg.refine:
                # Leiden: aggregate by the REFINED partition; seed the next
                # level's local-moving with each super-vertex's macro id
                with _tphase(timer, "refinement", level, cfg.per_level_timing):
                    ref = _refine_partition(cur, com, cfg, level, faults)
                new_ref, n_ref, coarse = aggregation.remap_and_coarsen_by(
                    cfg.aggregation, cur, ref, faults)
                # contiguized macro label of each refined group (refined ⊆
                # macro; monotone relabeling — see _stage_fn.run_level)
                macro_of_ref = jax.ops.segment_max(
                    jnp.where(cur.vertex_mask(), new_com, -1),
                    jnp.clip(new_ref, 0, n - 1), num_segments=n)
                assign = new_ref[jnp.clip(assign, 0, n - 1)]
                cur = coarse
                init_com = jnp.clip(macro_of_ref, 0, n - 1).astype(jnp.int32)
            elif not done:
                assign = new_com[jnp.clip(assign, 0, n - 1)]
                cur = coarse
        levels = level + 1
        if cfg.track_modularity:
            mod_hist.append(float(modularity(g0, macro_assign,
                                             promote=promote)))
        if done:
            break

    final_assign, n_final = aggregation.remap_communities(
        macro_assign, g0.vertex_mask())
    q = float(modularity(g0, final_assign, promote=promote))
    return LouvainResult(
        labels=np.asarray(final_assign),
        n_communities=int(n_final),
        levels=levels,
        modularity=q,
        modularity_history=mod_hist,
        sweeps_per_level=sweeps_per_level,
        timer=timer,
        n_comm_per_level=n_comm_per_level,
        delta_n_per_level=delta_n_per_level,
    )
