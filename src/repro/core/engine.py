"""Unified device-resident sweep engine (DESIGN.md §Engine).

One abstraction replaces the six near-identical local-moving sweeps that used
to live in ``core/plp.py``, ``core/louvain.py`` and ``core/distributed.py``:

  evaluator  ×  backend
  ---------     -------
  ``plp``       ``segment``      sort + segment GroupBy over the edge list
  ``louvain``   ``ell``          degree-bucketed dense tiles (jnp oracle)
                ``pallas``       same tiles through the Pallas kernels
                ``distributed``  shard_map over edge-partitioned shards

An evaluator proposes moves — ``(proposal[n], propose[n])`` per vertex — and
the engine owns everything around it: the Luby move-probability coin, the
adopt/changed bookkeeping, ΔN accounting, and active-frontier propagation.

The per-level sweep loop is a ``jax.lax.while_loop`` with on-device
``ΔN ≤ threshold`` convergence, so an entire local-moving phase (all sweeps of
one level) is ONE jitted call: no per-sweep host round-trip, no per-sweep
dispatch.  Per-sweep ΔN / active-count histories are written into fixed-size
on-device buffers and read back once per phase.  Label/frontier buffers are
donated to the fused call on accelerator backends.

``fused=False`` drives the SAME step function from a Python loop (one jitted
call per sweep) — the stepwise reference used by the parity tests and the
``benchmarks`` fused-vs-stepwise comparison.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ConfigBase
from repro.core import moves
from repro.core.common import luby_move_gate, neighbor_or_self_changed
from repro.core.progcache import program_cache
from repro.graph.structure import Graph

# Per-evaluator Luby coin stream constants (kept distinct so PLP and Louvain
# draw decorrelated move coins; values match the original sweep code).
_GATE_CONST = {"plp": (0x85EBCA6B, 313), "louvain": (0x9E3779B1, 101)}

EVALUATORS = ("plp", "louvain")
BACKENDS = ("segment", "ell", "pallas", "distributed")


@dataclasses.dataclass(frozen=True)
class EngineSpec(ConfigBase):
    """Static (hashable) sweep configuration — the jit cache key.

    ``threshold``/``max_sweeps`` define the fused convergence contract: the
    loop runs while ``sweep < max_sweeps and ΔN > threshold``, evaluated
    on device.
    """

    evaluator: str = "plp"       # plp | louvain
    backend: str = "segment"     # segment | ell | pallas | distributed
    max_sweeps: int = 100
    threshold: int = 0           # paper's ΔN threshold θ
    tie_eps: float = 0.25        # PLP tie noise amplitude
    move_prob: float = 1.0       # Luby move gate (1.0 = pure Jacobi)
    use_frontier: bool = True    # paper's active-vertex optimization
    reshuffle_ties: bool = False # PLP: re-draw tie noise each sweep
    singleton_rule: bool = True  # Louvain: Lu et al. swap suppression
    # ell/pallas table layout (DESIGN.md §Kernels): VMEM-resident tables vs
    # per-row-block windowed streaming; "auto" resolves from the VMEM byte
    # budget (kernels.common) at trace time.
    table_mode: str = "auto"     # auto | resident | streamed
    # ell/pallas with NO host-built layout: rebuild a single-bucket ELL tile
    # of this static width per level inside the trace (the cascade's coarse
    # levels, DESIGN.md §Pipeline).  0 = host-built DeviceEll required.
    ell_width: int = 0
    # Armed fault-injection points relevant to the sweep trace (DESIGN.md
    # §Robustness): "oscillation" pins the reported ΔN above the threshold,
    # "vmem_starve" is read by the VMEM budget policy at trace time.  Part
    # of the spec BECAUSE the spec is the jit/lru_cache key — fault state
    # outside the key would let clean traces be reused under faults.
    faults: tuple = ()

    def __post_init__(self):
        from repro.kernels.common import TABLE_MODES
        from repro.utils.faultinject import FAULT_POINTS

        if self.evaluator not in EVALUATORS:
            raise ValueError(f"unknown evaluator {self.evaluator!r}")
        if self.backend not in BACKENDS:
            raise ValueError(f"unknown backend {self.backend!r}")
        if self.table_mode not in TABLE_MODES:
            raise ValueError(f"unknown table_mode {self.table_mode!r}")
        if any(f not in FAULT_POINTS for f in self.faults):
            raise ValueError(f"unknown fault point(s) in {self.faults!r}")
        if self.ell_width < 0:
            raise ValueError(f"ell_width must be >= 0, got {self.ell_width}")
        if self.ell_width > 0 and self.backend not in ("ell", "pallas"):
            raise ValueError(
                "ell_width (traced re-bucketing) requires the ell or pallas "
                f"backend, not {self.backend!r}")


@dataclasses.dataclass
class PhaseResult:
    """Result of one local-moving phase (all sweeps of one level)."""

    labels: jax.Array            # device-resident — no forced host copy
    active: jax.Array
    sweeps: int
    delta_n_history: list
    active_history: list


# ----------------------------------------------------------------- evaluators


def _evaluate_segment(spec: EngineSpec, g: Graph, labels, active, it, seed,
                      restrict):
    """Sort+segment evaluator over the full (single-device) edge list."""
    n = g.n_max
    valid = g.edge_mask & active[jnp.clip(g.dst, 0, n - 1)]
    if spec.evaluator == "plp":
        noise_it = it if spec.reshuffle_ties else jnp.uint32(0)
        best_score, best_lab, cur_score = moves.plp_best_labels(
            g.src, g.dst, g.w, valid, labels, n, noise_it, seed, spec.tie_eps
        )
        propose = active & (best_lab >= 0) & (best_score > cur_score)
        return best_lab, propose

    vmask = g.vertex_mask()
    deg = g.weighted_degrees()              # loop-invariant: hoisted by XLA
    vol_v = g.total_volume()
    vol_com, size_com = moves.community_aux(labels, deg, vmask, n)
    if restrict is not None:
        # Leiden refinement: moves never leave the enclosing macro community
        same_macro = (restrict[jnp.clip(g.src, 0, n - 1)]
                      == restrict[jnp.clip(g.dst, 0, n - 1)])
        valid = valid & same_macro
    best_gain, best_cand = moves.louvain_best_moves(
        g.src, g.dst, g.w, valid, labels, deg, vol_com, size_com, vol_v, n,
        singleton_rule=spec.singleton_rule,
    )
    propose = vmask & active & (best_cand >= 0) & (best_gain > 0.0)
    return best_cand, propose


def _grid_propose(ell, active, n: int, eval_bucket):
    """Shared ELL bucket plumbing: run ``eval_bucket(rows, nbr, w, windows)
    -> (best[R], propose[R])`` once per degree bucket over ALL of its chunks
    at a time (one Pallas grid dispatch on the pallas backend, one vectorized
    jnp call on the ell backend — no lax.scan chain), scattering per-row
    proposals into per-vertex arrays.  Slot n is the write sink for padding /
    non-proposing rows, so real rows (unique across buckets) never collide.
    ``windows`` is the bucket's table-window metadata for the streamed
    (beyond-VMEM) table layout — see DESIGN.md §Kernels."""
    from repro.graph.ell import grid_view

    proposal_ext = jnp.full((n + 1,), -1, jnp.int32)
    propose_ext = jnp.zeros((n + 1,), bool)
    for b in ell.buckets:
        if b.n_rows_valid == 0:
            continue  # statically empty bucket: pure-padding tiles, no work
        rows, nbr, w = grid_view(b)
        best, good = eval_bucket(rows, nbr, w, b.windows)
        row_ok = (rows < n) & active[jnp.clip(rows, 0, n - 1)]
        row_prop = row_ok & good
        idx = jnp.where(row_prop, jnp.clip(rows, 0, n - 1), n)
        proposal_ext = proposal_ext.at[idx].set(jnp.where(row_prop, best, -1))
        propose_ext = propose_ext.at[idx].set(row_prop)
    return proposal_ext[:n], propose_ext[:n]


def _ell_evaluators(spec: EngineSpec, g: Graph, labels, it, seed,
                    use_pallas: bool, table_mode: str):
    """Per-sweep closure pair ``(eval_bucket, eval_tail)`` shared by the
    host-built bucket evaluator and the traced coarse-level evaluator.

    The per-vertex tables (labels for PLP; community/volume/size/degree for
    Louvain) are built ONCE here per sweep; ``eval_bucket(rows, nbr, w,
    windows)`` hands them whole to the ``local_move`` kernel family (gathers
    in-kernel), ``eval_tail(src, dst, w, valid) -> (best[n], good[n])``
    scores an edge list off the SAME extended tables (``moves.*_tables``)."""
    from repro.kernels.local_move import ops as lm_ops

    n = g.n_max

    if spec.evaluator == "plp":
        labels_ext = jnp.concatenate([labels, jnp.int32([n])])
        noise_it = it if spec.reshuffle_ties else jnp.uint32(0)
        noise_seed = seed.astype(jnp.uint32) + noise_it

        def eval_bucket(rows, nbr, w, windows):
            return lm_ops.local_move_plp(
                rows, nbr, w, labels_ext, noise_seed,
                tie_eps=spec.tie_eps, sentinel=n, use_pallas=use_pallas,
                windows=windows, table_mode=table_mode,
            )

        def eval_tail(tail_src, tail_dst, tail_w, valid_t):
            best_score, best_lab, cur_score = moves.plp_best_labels_tables(
                tail_src, tail_dst, tail_w, valid_t, labels_ext,
                n, noise_it, seed, spec.tie_eps,
            )
            return best_lab, (best_lab >= 0) & (best_score > cur_score)

    else:  # louvain
        vmask = g.vertex_mask()
        deg = g.weighted_degrees()
        vol_v = g.total_volume()
        vol_com, size_com = moves.community_aux(labels, deg, vmask, n)
        com_ext = jnp.concatenate([labels, jnp.int32([n])])
        vol_ext = jnp.concatenate([vol_com, jnp.zeros((1,), vol_com.dtype)])
        size_ext = jnp.concatenate([size_com, jnp.zeros((1,), size_com.dtype)])
        deg_ext = jnp.concatenate([deg, jnp.zeros((1,), deg.dtype)])
        # per-VERTEX composed tables, built ONCE per sweep and shared by
        # every bucket dispatch (ref.compose_louvain_tables)
        composed = lm_ops.compose_louvain_tables(
            com_ext, vol_ext.astype(jnp.float32), size_ext,
            deg_ext.astype(jnp.float32), n)

        def eval_bucket(rows, nbr, w, windows):
            return lm_ops.local_move_louvain(
                rows, nbr, w, com_ext, vol_ext, size_ext, deg_ext, vol_v,
                sentinel=n, singleton_rule=spec.singleton_rule,
                use_pallas=use_pallas,
                windows=windows, table_mode=table_mode,
                composed=composed,
            )

        def eval_tail(tail_src, tail_dst, tail_w, valid_t):
            best_gain, best_cand = moves.louvain_best_moves_tables(
                tail_src, tail_dst, tail_w, valid_t,
                com_ext, vol_ext, size_ext, deg_ext, vol_v, n,
                singleton_rule=spec.singleton_rule,
            )
            return best_cand, vmask & (best_cand >= 0) & (best_gain > 0.0)

    return eval_bucket, eval_tail


def _evaluate_ell(spec: EngineSpec, g: Graph, ell, labels, active, it, seed,
                  use_pallas: bool):
    """Degree-bucketed fused-gather evaluator (DESIGN.md §Kernels) over a
    host-built ``DeviceEll``; ``spec.table_mode`` picks VMEM-resident tables
    vs per-row-block windowed streaming.  ``ell`` routes through the
    pure-jnp oracle, ``pallas`` through the fused kernel.  Tail
    (above-widest-bucket) vertices go through the tables tail evaluator on
    the pre-extracted tail edges — the tail's per-sweep lexsort result is
    scored off the one shared per-sweep table build."""
    n = g.n_max
    eval_bucket, eval_tail = _ell_evaluators(
        spec, g, labels, it, seed, use_pallas, spec.table_mode)
    proposal, propose = _grid_propose(ell, active, n, eval_bucket)
    if ell.has_tail:
        valid_t = ((ell.tail_src < n) & (ell.tail_dst < n)
                   & active[jnp.clip(ell.tail_dst, 0, n - 1)])
        best, good = eval_tail(ell.tail_src, ell.tail_dst, ell.tail_w,
                               valid_t)
        tail_prop = ell.is_tail & active & good
        proposal = jnp.where(tail_prop, best, proposal)
        propose = propose | tail_prop
    return proposal, propose


def _evaluate_ell_traced(spec: EngineSpec, g: Graph, tile, labels, active,
                         it, seed):
    """Coarse-level fused-kernel evaluator with NO host-built layout
    (DESIGN.md §Pipeline): the ELL tile is re-bucketed from the src-sorted
    coarse edge list inside the trace (``graph/ell.traced_ell_tile``,
    hoisted to ``make_step`` so one level's sweeps share a single build) at
    the static per-stage width ``spec.ell_width``, then scored through the
    SAME ``local_move`` kernel family as level 0 (``ell`` = jnp oracle,
    ``pallas`` = fused kernel).  Rows are vertex-aligned, so the bucket
    scatter of ``_grid_propose`` reduces to a ``where``.  Vertices wider
    than the tile fall back to the tables tail evaluator over the FULL edge
    list, gated by ``lax.cond`` so hub-free levels skip the per-sweep sort
    entirely.  Tables are never streamed: coarse tables are small by
    construction and streaming needs host-side window metadata (with no
    windows ``auto`` resolves to resident, or to the gathered layout on a
    TPU)."""
    n = g.n_max
    rows, nbr, w_t, is_tail = tile
    eval_bucket, eval_tail = _ell_evaluators(
        spec, g, labels, it, seed, use_pallas=(spec.backend == "pallas"),
        table_mode="auto")
    best, good = eval_bucket(rows, nbr, w_t, None)
    row_prop = (rows < n) & active & good
    proposal = jnp.where(row_prop, best, -1)
    propose = row_prop

    def with_tail(args):
        proposal, propose = args
        dstc = jnp.clip(g.dst, 0, n - 1)
        valid_t = g.edge_mask & is_tail[dstc] & active[dstc]
        best_t, good_t = eval_tail(g.src, g.dst, g.w, valid_t)
        tail_prop = is_tail & active & good_t
        return jnp.where(tail_prop, best_t, proposal), propose | tail_prop

    return jax.lax.cond(jnp.any(is_tail), with_tail, lambda args: args,
                        (proposal, propose))


# ----------------------------------------------------------------- step / loop


def make_step(spec: EngineSpec, g: Graph, ell, restrict):
    """Build the shared sweep step: evaluate → gate → adopt → frontier."""
    n = g.n_max
    mult, salt = _GATE_CONST[spec.evaluator]
    tile = None
    if spec.backend != "segment" and ell is None and spec.ell_width > 0:
        from repro.graph.ell import traced_ell_tile

        # loop-invariant within a level: built once per phase, shared by
        # every sweep of the fused while_loop
        tile = traced_ell_tile(g, spec.ell_width)

    def step(labels, active, it, seed):
        if spec.backend == "segment":
            proposal, propose = _evaluate_segment(
                spec, g, labels, active, it, seed, restrict)
        elif tile is not None:
            proposal, propose = _evaluate_ell_traced(
                spec, g, tile, labels, active, it, seed)
        else:
            proposal, propose = _evaluate_ell(
                spec, g, ell, labels, active, it, seed,
                use_pallas=(spec.backend == "pallas"))
        adopt = propose
        if spec.move_prob < 1.0:
            adopt = adopt & luby_move_gate(n, it, seed, spec.move_prob, mult, salt)
        new_labels = jnp.where(adopt, proposal, labels)
        changed = adopt & (new_labels != labels)
        delta_n = jnp.sum(changed.astype(jnp.int32))
        if "oscillation" in spec.faults:
            # fault injection: the convergence signal never reports a
            # fixpoint (two vertices trading labels forever, Lu &
            # Halappanavar §4).  Labels and frontier are NOT perturbed —
            # only the reported ΔN — so the phase runs to the max_sweeps
            # watchdog bound and, at move_prob=1.0, returns bit-identical
            # labels (a Jacobi fixpoint re-sweeps to itself).
            delta_n = jnp.maximum(delta_n, jnp.int32(spec.threshold) + 1)
        if spec.use_frontier:
            next_active = neighbor_or_self_changed(g, changed)
        else:
            next_active = g.vertex_mask()
        return new_labels, next_active, delta_n

    return step


def phase_loop(step, labels, active, it0, seed, max_sweeps: int, threshold: int):
    """The fused convergence loop: run ``step`` until ΔN ≤ threshold or the
    sweep budget is exhausted, entirely on device.  Returns
    (labels, active, sweeps, dn_hist[max_sweeps], act_hist[max_sweeps])."""

    def cond(carry):
        s, dn, _, _, _, _ = carry
        return (s < jnp.uint32(max_sweeps)) & (dn > jnp.int32(threshold))

    def body(carry):
        s, _, labels, active, dn_hist, act_hist = carry
        labels, active, dn = step(labels, active, it0 + s, seed)
        dn_hist = dn_hist.at[s].set(dn)
        act_hist = act_hist.at[s].set(jnp.sum(active.astype(jnp.int32)))
        return s + jnp.uint32(1), dn, labels, active, dn_hist, act_hist

    init = (
        jnp.uint32(0),
        jnp.int32(threshold) + jnp.int32(1),
        labels,
        active,
        jnp.full((max_sweeps,), -1, jnp.int32),
        jnp.full((max_sweeps,), -1, jnp.int32),
    )
    s, _, labels, active, dn_hist, act_hist = jax.lax.while_loop(cond, body, init)
    return labels, active, s, dn_hist, act_hist


def device_phase(spec: EngineSpec, g: Graph, ell, labels, active, it0, seed,
                 restrict=None):
    """Trace one fused local-moving phase for embedding in a LARGER jitted
    program (e.g. the multi-level pipeline, DESIGN.md §Pipeline).

    Must be called under an enclosing trace/jit; returns the raw loop outputs
    ``(labels, active, sweeps, dn_hist, act_hist)`` with everything device-
    resident.  ``SweepEngine.run_phase`` is the standalone-dispatch wrapper
    around the same loop.  Every operation of the phase sits in the
    ``repro.local_move`` scope, whichever program embeds it.
    """
    with jax.named_scope("repro.local_move"):
        step = make_step(spec, g, ell, restrict)
        return phase_loop(step, labels, active, it0, seed,
                          spec.max_sweeps, spec.threshold)


def _donate_labels() -> bool:
    """Buffer donation for the label/frontier arrays in the fused call.

    Skipped on CPU, where XLA does not implement donation (the warning would
    drown test output); on TPU/GPU the phase reuses the input buffers."""
    return jax.default_backend() != "cpu"


@program_cache("engine.fused_phase", maxsize=128)
def _fused_phase_fn(spec: EngineSpec, donate: bool):
    def phase(g, ell, labels, active, it0, seed, restrict):
        return device_phase(spec, g, ell, labels, active, it0, seed, restrict)

    return jax.jit(phase, donate_argnums=(2, 3) if donate else ())


@program_cache("engine.step", maxsize=128)
def _step_fn(spec: EngineSpec):
    def one_sweep(g, ell, labels, active, it, seed, restrict):
        with jax.named_scope("repro.local_move"):
            return make_step(spec, g, ell, restrict)(labels, active, it, seed)

    return jax.jit(one_sweep)


# ----------------------------------------------------------------- engine


class SweepEngine:
    """Local-moving sweep engine for one graph (one coarsening level).

    >>> eng = SweepEngine(g, EngineSpec(evaluator="plp", max_sweeps=50))
    >>> res = eng.run_phase(*eng.singleton_state(), seed=0)
    """

    def __init__(self, g: Graph, spec: EngineSpec, ell=None):
        if spec.backend == "distributed":
            raise ValueError(
                "use make_distributed_phase() for the distributed backend")
        self.g = g
        self.spec = spec
        self.ell = None
        if spec.backend in ("ell", "pallas") and spec.ell_width == 0:
            from repro.graph import ell as ell_mod

            if ell is None:
                ell = ell_mod.build_device_ell(
                    g, ell_mod.bucket_widths(spec.backend))
            elif isinstance(ell, ell_mod.EllGraph):
                ell = ell_mod.to_device(g, ell)
            self.ell = ell

    def singleton_state(self) -> Tuple[jax.Array, jax.Array]:
        """(labels, active): singleton init + full active set (Alg. 1 l.4-5)."""
        return (jnp.arange(self.g.n_max, dtype=jnp.int32),
                self.g.vertex_mask())

    def run_phase(
        self,
        labels: jax.Array,
        active: jax.Array,
        *,
        it0: int = 0,
        seed: int = 0,
        restrict: Optional[jax.Array] = None,
        fused: bool = True,
    ) -> PhaseResult:
        """Run one local-moving phase to convergence.

        fused=True:  ONE jitted lax.while_loop call; the only host transfer
                     is reading back (sweeps, ΔN history, active history).
        fused=False: stepwise reference — the same step function driven from
                     Python, one jitted call + one ΔN transfer per sweep.
        """
        spec = self.spec
        if restrict is not None and spec.backend != "segment":
            raise ValueError(
                "restrict (Leiden macro confinement) is only implemented for "
                f"the segment backend, not {spec.backend!r}")
        it0_a = jnp.uint32(it0)
        seed_a = jnp.uint32(seed)
        if fused:
            phase = _fused_phase_fn(spec, _donate_labels())
            labels, active, s, dn_hist, act_hist = phase(
                self.g, self.ell, labels, active, it0_a, seed_a, restrict)
            s, dn_hist, act_hist = jax.device_get((s, dn_hist, act_hist))
            s = int(s)
            return PhaseResult(labels, active, s,
                               [int(x) for x in dn_hist[:s]],
                               [int(x) for x in act_hist[:s]])

        step = _step_fn(spec)
        dn_hist, act_hist = [], []
        s = 0
        while s < spec.max_sweeps:
            labels, active, dn = step(
                self.g, self.ell, labels, active, it0_a + jnp.uint32(s),
                seed_a, restrict)
            dn = int(dn)
            dn_hist.append(dn)
            act_hist.append(int(jnp.sum(active.astype(jnp.int32))))
            s += 1
            if dn <= spec.threshold:
                break
        return PhaseResult(labels, active, s, dn_hist, act_hist)


# ----------------------------------------------------------------- distributed


def make_distributed_step(spec: EngineSpec, axes, n: int, src, dst, w, emask,
                          deg, vol_v, vmask, restrict=None):
    """Build one sweep step over a LOCAL edge shard (for use inside a
    shard_map worker): evaluate on local in-edges, psum-merge the disjoint
    per-owner proposals, gate, adopt, frontier.

    ``emask`` is the per-device ownership mask: every vertex's in-edges must
    be owned by exactly one device (dst-disjoint ownership), so the psum
    merge is a pure union.  ``deg``/``vol_v`` are the per-level Louvain
    invariants (ignored by PLP).  ``restrict`` (replicated int32[n] or None)
    confines Louvain moves to vertices sharing its value — the Leiden
    refinement mask, mirroring ``_evaluate_segment``.  Reused by both the
    per-level distributed phase and the fused multi-level pipeline
    (DESIGN.md §Pipeline).
    """
    mult, salt = _GATE_CONST[spec.evaluator]

    def evaluate(labels, active, it, seed):
        valid = emask & active[jnp.clip(dst, 0, n - 1)]
        if spec.evaluator == "plp":
            noise_it = it if spec.reshuffle_ties else jnp.uint32(0)
            best_score, best_lab, cur_score = moves.plp_best_labels(
                src, dst, w, valid, labels, n, noise_it, seed, spec.tie_eps)
            propose_l = active & (best_lab >= 0) & (best_score > cur_score)
            proposal_l = best_lab
        else:
            # replicated O(n) recompute — identical on all devices, no comm
            vol_com, size_com = moves.community_aux(labels, deg, vmask, n)
            if restrict is not None:
                same_macro = (restrict[jnp.clip(src, 0, n - 1)]
                              == restrict[jnp.clip(dst, 0, n - 1)])
                valid = valid & same_macro
            best_gain, best_cand = moves.louvain_best_moves(
                src, dst, w, valid, labels, deg, vol_com, size_com, vol_v,
                n, singleton_rule=spec.singleton_rule)
            propose_l = active & (best_cand >= 0) & (best_gain > 0.0)
            proposal_l = best_cand
        # disjoint-owner merge: every vertex's in-edges live on one device
        merged = jax.lax.psum(
            jnp.where(propose_l, proposal_l, 0).astype(jnp.int32), axes)
        propose = jax.lax.psum(propose_l.astype(jnp.int32), axes) > 0
        return jnp.where(propose, merged, -1), propose

    def frontier(changed):
        contrib = jnp.where(
            emask, changed[jnp.clip(src, 0, n - 1)].astype(jnp.int32), 0)
        nbr_local = jax.ops.segment_sum(
            contrib, jnp.clip(dst, 0, n - 1), num_segments=n)
        return changed | (jax.lax.psum(nbr_local, axes) > 0)

    def step(labels, active, it, seed):
        proposal, propose = evaluate(labels, active, it, seed)
        adopt = propose
        if spec.move_prob < 1.0:
            adopt = adopt & luby_move_gate(
                n, it, seed, spec.move_prob, mult, salt)
        new_labels = jnp.where(adopt, proposal, labels)
        changed = adopt & (new_labels != labels)
        delta_n = jnp.sum(changed.astype(jnp.int32))
        next_active = frontier(changed) if spec.use_frontier else vmask
        return new_labels, next_active, delta_n

    return step


@program_cache("engine.distributed_phase", maxsize=32)
def make_distributed_phase(mesh, n: int, spec: EngineSpec):
    """Build the jitted fused phase for edge-partitioned shards.

    The while_loop runs INSIDE the shard_map worker: small O(n) state is
    replicated, each sweep psum-merges the disjoint per-owner proposals, and
    the convergence predicate is evaluated on the replicated ΔN — identical
    on every device, so the loop exits in lockstep with zero host syncs.

    Returns ``phase(src, dst, w, emask, labels, active, it0, seed, deg,
    vol_v, n_valid) -> (labels, active, sweeps, dn_hist, act_hist)``.
    ``deg``/``vol_v`` are the per-level Louvain invariants (ignored by PLP).
    Cached per (mesh, n, spec) so repeated driver calls reuse the compiled
    phase instead of retracing a fresh closure.
    """
    from jax.sharding import PartitionSpec as P

    axes = tuple(mesh.axis_names)
    espec, rspec = P(axes), P()

    def worker(src, dst, w, emask, labels, active, it0, seed, deg, vol_v,
               n_valid):
        src, dst, w, emask = src[0], dst[0], w[0], emask[0]
        vmask = jnp.arange(n, dtype=jnp.int32) < n_valid
        step = make_distributed_step(
            spec, axes, n, src, dst, w, emask, deg, vol_v, vmask)
        return phase_loop(step, labels, active, it0, seed,
                          spec.max_sweeps, spec.threshold)

    sharded = jax.shard_map(
        worker, mesh=mesh,
        in_specs=(espec,) * 4 + (rspec,) * 7,
        out_specs=(rspec,) * 5, check_vma=False,
    )
    return jax.jit(sharded)
