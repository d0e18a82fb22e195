"""Batched many-graph engine (DESIGN.md §Serving).

PRs 1-7 made ONE graph fast: a whole Louvain run is one dispatch + one
readback.  The serving workload is the opposite shape — millions of small
graphs (ego-nets, session graphs), where per-graph DISPATCH dominates once
the kernels are fast: a 300-vertex graph pays the same Python-driver + jit
launch + readback latency as a 300k-vertex one.  ``louvain_batch`` /
``plp_batch`` amortize it:

  1. **bucket** incoming graphs by ``kernels.common.capacity_signature`` —
     capacities quantize onto a doubling menu with ego-net-scale floors
     (padding waste bounded <2×), so arbitrarily-sized traffic lands on a
     handful of buckets;
  2. **pack** each bucket along a new leading batch axis
     (``graph.packing``), on the host: capacity-padded arrays stack for
     free, every dispatch of a signature has the same slot count
     (``dispatch_width``: ``max_slots``, fewer for wide signatures; short
     chunks are filled with fully-masked empty-slot graphs), and the batch
     moves to the device in one ``device_put`` — so each signature has ONE
     compiled program and no graph size or chunk size traces anything;
  3. **dispatch** the existing fused stage program under ``jax.vmap``: the
     same ``louvain._build_stage`` closure the single-graph cascade jits is
     lifted over the batch axis, so ONE dispatch serves up to ``max_slots``
     graphs of a bucket (the dispatch-width bound caps vmap-lockstep waste
     — see ``MAX_SLOTS``) and per-slot results are bit-identical to the
     unbatched driver by the capacity-portability contract
     (tests/test_batch.py).

Backend notes: the ``segment`` evaluator vmaps directly.  ``ell`` uses the
traced per-level re-bucketing at the signature's static menu width (the
cascade's coarse-level machinery — no host-built layout, pure jnp, vmaps
directly).  ``pallas`` falls back to ``ell`` under vmap — the documented
vmap-of-ref fallback: the kernels' jnp oracle is bit-identical by the
parity contracts, so batching trades the fused-kernel speedup for the
dispatch amortization without touching results; a batch-grid dimension
through the Pallas kernels can lift that later where the kernels permit.
Graphs without the ``sorted_by == "src"`` invariant fall back to the
segment evaluator (also bit-identical).

Compiled programs are memoized in a bounded LRU keyed on the capacity
signature and slot count (``progcache.program_cache``), mirroring the
cascade's ≤4-stage-program discipline: steady-state traffic incurs ZERO
recompiles (tests/test_batch.py).

Host spans name each step of a dispatch in the profiler's trace:
``repro.batch.pack`` (pad, stack, transfer), ``repro.batch.dispatch``
(args ``n_cap``, ``m_cap``, ``slots``, ``graphs``), ``repro.batch.readback``
and ``repro.batch.unpack``; counters ``batch.lanes`` and
``batch.empty_lanes`` count lanes dispatched and filler lanes among them.

Per-graph ``RunReport`` discipline (DESIGN.md §Robustness) is preserved:
empty (zero-capacity) inputs short-circuit to the PR-7 trivial result
without occupying a batch slot, the per-level non-finite-weight guard rides
the batched readback per slot and poisons ONLY the offending graphs
(``NumericError`` names them; clean slots are unaffected), and watchdog /
precision warnings are recorded per slot.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import EngineSpec, device_phase
# NB: ``repro.core``'s package namespace rebinds the names ``louvain``/
# ``plp`` to the driver FUNCTIONS, so the submodules are imported by the
# names we need rather than as module objects.
from repro.core.louvain import (LouvainConfig, LouvainResult, _build_stage,
                                _coarse_backend, _finalize_report, _readback,
                                _refine_spec, _trivial_result)
from repro.core.louvain import engine_spec as louvain_engine_spec
from repro.core.plp import PLPConfig, PLPResult
from repro.core.plp import engine_spec as plp_engine_spec
from repro.core.progcache import program_cache
from repro.graph import packing
from repro.graph.structure import Graph
from repro.kernels.common import (CapacitySignature, accum_needs_promotion,
                                  capacity_signature)
from repro.utils import faultinject, resilience, telemetry
from repro.utils.errors import DeadlineError, KernelError, NumericError, RunReport
from repro.utils.timing import Timer


def _dispatch_guarded(run, deadline: Optional[resilience.Deadline]):
    """Execute one bucket dispatch under the serving resilience contract
    (DESIGN.md §Resilience): the chaos fault sites fire HERE (inside the
    watchdogged callable, so a stalled dispatch is indistinguishable from
    a hung device) and, when a deadline rides the call, the whole thing
    runs under ``resilience.call_with_deadline`` — on overrun the wait is
    cancelled with a typed ``DeadlineError`` and the worker abandoned.
    ``deadline=None`` is the clean path: a plain inline call, no thread."""

    def attempt():
        if faultinject.should_fire("slow_dispatch"):
            # models a hung device / pathological recompile: stall inside
            # the watchdog window
            time.sleep(faultinject.slow_dispatch_seconds())
        if faultinject.should_fire("transient_batch_fail"):
            raise KernelError(
                "injected transient batch dispatch failure "
                "(fault point: transient_batch_fail)")
        return run()

    return resilience.call_with_deadline(
        attempt, deadline.remaining_s() if deadline is not None else None)


def _resolve_batch_backend(backend: str, sorted_ok: bool) -> str:
    """Static backend resolution for the batched path (module docstring):
    ``pallas`` → ``ell`` (vmap-of-ref fallback), and ``ell`` → ``segment``
    when the bucket lacks the src-sorted invariant the traced re-bucketing
    needs.  Every step is bit-identical by the parity contracts."""
    if backend == "pallas":
        telemetry.bump("batch.pallas_vmap_fallback")
        backend = "ell"
    if backend == "ell" and not sorted_ok:
        telemetry.bump("batch.unsorted_segment_fallback")
        backend = "segment"
    return backend


# ------------------------------------------------------------------- louvain


@program_cache("batch.louvain", maxsize=32)
def _louvain_batch_fn(sig: CapacitySignature, slots: int, spec0: EngineSpec,
                      spec_coarse: EngineSpec,
                      refine_spec: Optional[EngineSpec], max_levels: int,
                      track_modularity: bool, agg_method: str,
                      faults: frozenset, promote: bool):
    """One compiled batch program per capacity signature and slot count
    (and spec set): the single-capacity whole-run stage (``_build_stage``
    with ``next_caps=None`` — the cascade's parity oracle) lifted through
    ``jax.vmap`` over the leading batch axis.  ``sig`` and ``slots`` pin
    the static shapes in the cache key, so the jit beneath traces once."""
    stage = _build_stage(
        spec0, spec_coarse, refine_spec, max_levels, track_modularity,
        None, agg_method, faults, promote)
    max_sweeps = spec0.max_sweeps

    def run(g: Graph, seed):
        n = g.n_max
        ar = jnp.arange(n, dtype=jnp.int32)
        hists = (jnp.full((max_levels,), jnp.nan, jnp.float32),
                 jnp.full((max_levels,), -1, jnp.int32),
                 jnp.full((max_levels,), -1, jnp.int32),
                 jnp.full((max_levels, max_sweeps), -1, jnp.int32),
                 jnp.bool_(False))
        (_arrays, _assign, _init, _macro, hists, level, _done, _nv, _mv,
         _max_deg, final_assign, n_final, q_final) = stage(
            g, None, g, seed, ar, ar, ar, jnp.int32(0), hists)
        mod_h, sw_h, nc_h, dn_h, bad_w = hists
        return (final_assign, n_final, level, q_final,
                mod_h, sw_h, nc_h, dn_h, bad_w)

    return jax.jit(jax.vmap(run, in_axes=(0, None)))


def _louvain_specs(cfg: LouvainConfig, sig: CapacitySignature,
                   backend: str, faults: frozenset):
    spec0 = louvain_engine_spec(cfg, backend=backend, faults=faults)
    if backend == "ell":
        # no host-built layout in the batched path: level 0 uses the traced
        # re-bucketing at the signature's static menu width
        spec0 = spec0.replace(ell_width=sig.ell_width)
    # coarse levels mirror the single-capacity parity oracle exactly
    # (schedule="none" semantics): segment evaluator beyond level 0
    spec_coarse = louvain_engine_spec(
        cfg, backend=_coarse_backend(backend), faults=faults)
    refine_spec = (_refine_spec(cfg, faults)
                   if cfg.refine else None)
    return spec0, spec_coarse, refine_spec


def _unpack_labels(final_assign: np.ndarray, g: Graph, n_cap: int) -> np.ndarray:
    """Slot labels at bucket capacity → the graph's own capacity: slice to
    ``n_max`` and rewrite the contiguize sentinel (``n_cap`` → ``n_max``).
    Valid labels are < n_valid <= n_max, so only sentinels can equal
    ``n_cap`` — no device sync needed."""
    lab = np.asarray(final_assign[:g.n_max])
    if n_cap != g.n_max:
        lab = np.where(lab == n_cap, g.n_max, lab).astype(np.int32)
    return lab


#: Most lanes a dispatch runs.  A vmapped while_loop runs every lane until
#: the SLOWEST lane converges, so unbounded batches pay worst-case
#: sweep/level counts for all slots; chunking a bucket into at most this
#: many lanes caps that lockstep waste (and the packed-batch memory
#: footprint).  8 was the measured CPU-serving optimum.
MAX_SLOTS = 8

#: Edge entries one dispatch carries over all its lanes, at most: a
#: signature runs ``dispatch_width`` lanes, one width per signature, so
#: one compiled program per signature.  A lane costs what its entries cost
#: once the arrays are large, live or empty (PERF.md: on the CPU an empty
#: lane of the (256, 32768) signature costs 3/4 of a live one; on the v5e
#: the youtube graph's 345,050-entry scatters and gathers run ~2.2 ms
#: each), so wide signatures take fewer lanes and small ones keep
#: ``MAX_SLOTS``.
LANE_ENTRIES = 65536


def dispatch_width(sig: CapacitySignature, max_slots: int = MAX_SLOTS) -> int:
    """Lanes every dispatch of ``sig`` runs: ``max_slots``, fewer where
    they would carry more than ``LANE_ENTRIES`` edge entries, at least 1.
    Short chunks fill the width with empty lanes, which converge at once."""
    return min(int(max_slots), max(1, LANE_ENTRIES // int(sig.m_cap)))


def _chunks(idxs: List[int], max_slots: int):
    if max_slots < 1:
        raise ValueError(f"max_slots must be >= 1, got {max_slots}")
    for k in range(0, len(idxs), max_slots):
        yield idxs[k:k + max_slots]


def _schedule_lanes(graphs, idxs: List[int]) -> List[int]:
    """Order a bucket's lanes by predicted sweep count before chunking.

    A vmapped while_loop runs every lane of a chunk until its SLOWEST lane
    converges, so mixing one dense graph with seven sparse ones makes the
    sparse lanes idle through the dense lane's extra sweeps/levels.  Sweep
    and level counts grow with edge count (and, secondarily, vertex count),
    so sorting a bucket descending by ``(m_valid, n_valid)`` packs
    similar-cost graphs into the same ``max_slots`` chunk and confines the
    lockstep waste to the one chunk that actually holds the heavy graphs.
    Pure reordering of which chunk a graph lands in: per-graph results are
    positionally realigned by index and bit-identical either way
    (tests/test_batch.py).
    """
    return sorted(idxs, key=lambda i: (-int(graphs[i].m_valid),
                                       -int(graphs[i].n_valid), i))


def louvain_batch(graphs: Sequence[Graph],
                  cfg: LouvainConfig = LouvainConfig(),
                  max_slots: int = MAX_SLOTS,
                  deadline_s: Optional[float] = None,
                  lane_schedule: bool = True) -> List[LouvainResult]:
    """Run Louvain over many graphs with one dispatch per capacity bucket
    (buckets wider than their ``dispatch_width`` are chunked, and every
    dispatch of a signature runs that many lanes — see ``LANE_ENTRIES``;
    ``lane_schedule`` orders lanes by predicted sweep count first — see
    ``_schedule_lanes`` — without affecting per-graph results).

    Results are positionally aligned with ``graphs`` and bit-identical to
    ``louvain(g, cfg)`` per graph (the parity contract the batch tests
    enforce).  Zero-capacity graphs return the trivial result without
    occupying a slot; if the per-level numeric guard flags non-finite
    weights in some slots, ``NumericError`` names those graph indices —
    clean graphs in the same batch are unaffected (their results would be
    returned on a retry without the poisoned inputs).

    ``deadline_s`` bounds the WHOLE call (DESIGN.md §Resilience): each
    bucket dispatch runs under the remaining-budget watchdog and overrun
    raises a typed ``DeadlineError`` — per-request deadline splitting
    (fail only the expired requests, re-run the rest) is the serving
    layer's job, which knows who owns which deadline.  ``None`` is the
    clean path: no watchdog thread, behavior unchanged.
    """
    graphs = list(graphs)
    results: List[Optional[LouvainResult]] = [None] * len(graphs)
    active_faults = sorted(faultinject.active())
    faults = frozenset(active_faults)
    deadline = (resilience.Deadline(deadline_s)
                if deadline_s is not None else None)

    buckets: Dict[Tuple, List[int]] = {}
    for i, g in enumerate(graphs):
        if g.n_max == 0:
            results[i] = _trivial_result(
                RunReport(faults=active_faults))
            continue
        sig = capacity_signature(g.n_max, g.m_max)
        buckets.setdefault((sig, g.sorted_by), []).append(i)

    bad_slots: List[int] = []
    for (sig, sorted_by), idxs in buckets.items():
        width = dispatch_width(sig, max_slots)
        if lane_schedule and len(idxs) > width:
            telemetry.bump("batch.lane_scheduled_buckets")
            idxs = _schedule_lanes(graphs, idxs)
        for chunk in _chunks(idxs, width):
            if deadline is not None and deadline.expired:
                raise DeadlineError(
                    f"batch deadline ({deadline_s:.3f}s) expired with "
                    "bucket dispatches still pending")
            bad_slots += _run_louvain_bucket(
                graphs, chunk, sig, sorted_by, cfg, faults, active_faults,
                results, width, deadline)
    if bad_slots:
        raise NumericError(
            "non-finite edge weight detected inside the fused level loop "
            f"for graph(s) {sorted(bad_slots)}")
    return results  # type: ignore[return-value]


def _pack(graphs, idxs, sig: CapacitySignature, sorted_by, slots: int):
    """The chunk ``idxs`` as one device batch of ``slots`` lanes: padded
    to the signature and stacked on the host, filled with empty lanes, and
    moved to the device in one ``device_put``."""
    with telemetry.span("repro.batch.pack"):
        lanes = [packing.pad_graph(graphs[i], sig.n_cap, sig.m_cap)
                 for i in idxs]
        lanes += [packing.empty_slot(sig.n_cap, sig.m_cap, sorted_by)
                  ] * (slots - len(lanes))
        gb = jax.device_put(packing.stack_graphs(lanes))
    telemetry.bump("batch.lanes", slots)
    telemetry.bump("batch.empty_lanes", slots - len(idxs))
    return gb


def _dispatch(fn, gb, seed: int, n_graphs: int, readback):
    """One batch program call and its one readback, each in its span."""
    with telemetry.span("repro.batch.dispatch", n_cap=gb.n_max,
                        m_cap=gb.m_max, slots=int(gb.n_valid.shape[0]),
                        graphs=n_graphs):
        out = fn(gb, jnp.uint32(seed))
    with telemetry.span("repro.batch.readback"):
        return readback(out)


def _run_louvain_bucket(graphs, idxs, sig: CapacitySignature,
                        sorted_by, cfg: LouvainConfig, faults: frozenset,
                        active_faults, results, slots: int,
                        deadline: Optional[resilience.Deadline] = None,
                        ) -> List[int]:
    timer = Timer()
    backend = _resolve_batch_backend(cfg.backend, sorted_by == "src")
    spec0, spec_coarse, refine_spec = _louvain_specs(cfg, sig, backend,
                                                     faults)
    promote = accum_needs_promotion(sig.m_cap)

    with timer.phase("pack"):
        gb = _pack(graphs, idxs, sig, sorted_by, slots)
    fn = _louvain_batch_fn(sig, slots, spec0, spec_coarse, refine_spec,
                           cfg.max_levels, cfg.track_modularity,
                           cfg.aggregation, faults, promote)
    with timer.phase("pipeline"):
        # ONE bulk transfer per bucket; under a deadline the dispatch +
        # readback run watchdogged (fault sites fire inside the window)
        host = _dispatch_guarded(
            lambda: _dispatch(fn, gb, cfg.seed, len(idxs), _readback),
            deadline)
    (final_assign, n_final, level, q_final,
     mod_h, sw_h, nc_h, dn_h, bad_w) = host
    telemetry.bump("batch.louvain_dispatches")
    telemetry.bump("batch.louvain_graphs", len(idxs))

    bad_slots: List[int] = []
    with telemetry.span("repro.batch.unpack"):
        for b, i in enumerate(idxs):
            if bool(bad_w[b]):
                bad_slots.append(i)
                continue
            report = RunReport(faults=list(active_faults))
            if promote:
                report.warnings.append("precision:f32_accum_risk"
                                       if not jax.config.jax_enable_x64
                                       else "precision:promoted_f64")
            levels = int(level[b])
            sweeps_per_level = [int(s) for s in sw_h[b][:levels]]
            res = LouvainResult(
                labels=_unpack_labels(final_assign[b], graphs[i], sig.n_cap),
                n_communities=int(n_final[b]),
                levels=levels,
                modularity=float(q_final[b]),
                modularity_history=(
                    [float(x) for x in mod_h[b][:levels]]
                    if cfg.track_modularity else []),
                sweeps_per_level=sweeps_per_level,
                timer=timer,
                n_comm_per_level=[int(x) for x in nc_h[b][:levels]],
                delta_n_per_level=[
                    [int(x) for x in row[:s]]
                    for row, s in zip(dn_h[b][:levels], sweeps_per_level)],
                cascade_stages=[(sig.n_cap, sig.m_cap)],
            )
            results[i] = _finalize_report(res, cfg, report)
    return bad_slots


# ----------------------------------------------------------------------- plp


@program_cache("batch.plp", maxsize=32)
def _plp_batch_fn(sig: CapacitySignature, slots: int, spec: EngineSpec):
    """One compiled PLP batch program per capacity signature and slot
    count: the fused phase loop (``engine.device_phase`` — singleton init, on-device
    convergence) lifted through ``jax.vmap``."""

    def run(g: Graph, seed):
        labels = jnp.arange(g.n_max, dtype=jnp.int32)
        active = g.vertex_mask()
        labels, active, s, dn_hist, act_hist = device_phase(
            spec, g, None, labels, active, jnp.uint32(0), seed)
        return labels, s, dn_hist, act_hist

    return jax.jit(jax.vmap(run, in_axes=(0, None)))


def plp_batch(graphs: Sequence[Graph],
              cfg: PLPConfig = PLPConfig(),
              max_slots: int = MAX_SLOTS,
              deadline_s: Optional[float] = None,
              lane_schedule: bool = True) -> List[PLPResult]:
    """Run PLP over many graphs with one dispatch per capacity bucket —
    ``louvain_batch``'s contract (positional results, per-graph bitwise
    parity with ``plp(g, cfg)``, trivial result for zero-capacity inputs,
    per-slot RunReport, ``max_slots`` dispatch-width bound,
    ``deadline_s`` whole-call watchdog, ``lane_schedule`` sweep-count
    ordering) for the label-propagation evaluator."""
    graphs = list(graphs)
    results: List[Optional[PLPResult]] = [None] * len(graphs)
    active_faults = sorted(faultinject.active())
    faults = frozenset(active_faults)
    deadline = (resilience.Deadline(deadline_s)
                if deadline_s is not None else None)

    buckets: Dict[Tuple, List[int]] = {}
    for i, g in enumerate(graphs):
        if g.n_max == 0:
            results[i] = PLPResult(
                labels=np.zeros((0,), np.int32), iterations=0,
                delta_n_history=[], active_history=[], timer=Timer(),
                run_report=RunReport(faults=active_faults))
            continue
        sig = capacity_signature(g.n_max, g.m_max)
        buckets.setdefault((sig, g.sorted_by), []).append(i)

    for (sig, sorted_by), bucket_idxs in buckets.items():
        width = dispatch_width(sig, max_slots)
        if lane_schedule and len(bucket_idxs) > width:
            telemetry.bump("batch.lane_scheduled_buckets")
            bucket_idxs = _schedule_lanes(graphs, bucket_idxs)
        for idxs in _chunks(bucket_idxs, width):
            if deadline is not None and deadline.expired:
                raise DeadlineError(
                    f"batch deadline ({deadline_s:.3f}s) expired with "
                    "bucket dispatches still pending")
            _run_plp_bucket(graphs, idxs, sig, sorted_by, cfg, faults,
                            active_faults, results, width, deadline)
    return results  # type: ignore[return-value]


def _run_plp_bucket(graphs, idxs, sig: CapacitySignature, sorted_by,
                    cfg: PLPConfig, faults: frozenset, active_faults,
                    results, slots: int,
                    deadline: Optional[resilience.Deadline] = None,
                    ) -> None:
    timer = Timer()
    backend = _resolve_batch_backend(cfg.backend, sorted_by == "src")
    spec = plp_engine_spec(cfg, faults).replace(backend=backend)
    if backend == "ell":
        spec = spec.replace(ell_width=sig.ell_width)

    with timer.phase("pack"):
        gb = _pack(graphs, idxs, sig, sorted_by, slots)
    fn = _plp_batch_fn(sig, slots, spec)
    with timer.phase("move"):
        labels, s, dn_hist, act_hist = _dispatch_guarded(
            lambda: _dispatch(fn, gb, cfg.seed, len(idxs), jax.device_get),
            deadline)
    telemetry.bump("batch.plp_dispatches")
    telemetry.bump("batch.plp_graphs", len(idxs))

    with telemetry.span("repro.batch.unpack"):
        for b, i in enumerate(idxs):
            report = RunReport(faults=list(active_faults))
            its = int(s[b])
            if its >= cfg.max_iterations:
                report.warnings.append("watchdog:max_iterations")
            results[i] = PLPResult(
                labels=np.asarray(labels[b][:graphs[i].n_max]),
                iterations=its,
                delta_n_history=[int(x) for x in dn_hist[b][:its]],
                active_history=[int(x) for x in act_hist[b][:its]],
                timer=timer,
                run_report=report)
