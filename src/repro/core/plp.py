"""Parallel Label Propagation (paper Alg. 1) — TPU-native.

Faithful structure:
  * singleton initialization (l.4)
  * active-vertex set with deactivate-on-stable / reactivate-on-neighbor-change
    (l.5, l.19-20, l.25) — realized as a boolean frontier mask
  * per-iteration move: every active vertex adopts
    argmax_c Σ_{u∈N(v): C(u)=c} w(v,u)   (l.18)
  * termination: ΔN ≤ threshold or maxIteration (l.7-11)

Adaptation (DESIGN.md §2): the paper's asynchronous shared-array update with
benign races becomes a synchronous Jacobi sweep; thread-race tie randomization
becomes seeded hash noise (``tie_noise``).  The sweep itself lives in the
shared ``core.engine`` (DESIGN.md §Engine): this module only configures the
``plp`` evaluator and packages results.  With ``fused=True`` (default) the
whole label-propagation run is ONE jitted ``lax.while_loop`` call with
on-device convergence; ``fused=False`` is the stepwise reference.
"""
from __future__ import annotations

import contextlib
import dataclasses

import jax.numpy as jnp
import numpy as np

from repro.config import ConfigBase
from repro.core.engine import EngineSpec, SweepEngine
from repro.graph.structure import Graph
from repro.utils import faultinject, telemetry
from repro.utils.errors import (CommunityDetectionError, KernelError,
                                RunReport)
from repro.utils.timing import Timer


@dataclasses.dataclass(frozen=True)
class PLPConfig(ConfigBase):
    max_iterations: int = 100
    threshold: int = 0          # paper's ΔN threshold θ
    seed: int = 0
    tie_eps: float = 0.25       # < min weight gap on unit-weight graphs
    use_frontier: bool = True   # the paper's active-vertex optimization
    backend: str = "segment"    # segment | ell | pallas
    # Re-draw tie noise each iteration (closest to the paper's thread-race
    # randomization but can stall convergence on tie-rich graphs) vs a fixed
    # random preference per (vertex,label) pair (converges; default).
    reshuffle_ties: bool = False
    move_prob: float = 0.75     # Luby-style move gating (1.0 = pure Jacobi)
    fused: bool = True          # one while_loop call vs per-sweep dispatch
    # ell/pallas table layout: VMEM-resident vs windowed streaming; "auto"
    # resolves from the VMEM byte budget (DESIGN.md §Kernels)
    table_mode: str = "auto"    # auto | resident | streamed


@dataclasses.dataclass
class PLPResult:
    labels: np.ndarray
    iterations: int
    delta_n_history: list
    active_history: list
    timer: Timer
    # retry/degradation/watchdog accounting (DESIGN.md §Robustness)
    run_report: RunReport = dataclasses.field(default_factory=RunReport)


def engine_spec(cfg: PLPConfig,
                faults: frozenset = frozenset()) -> EngineSpec:
    from repro.core.louvain import ENGINE_FAULTS

    return EngineSpec(
        evaluator="plp",
        backend=cfg.backend,
        max_sweeps=cfg.max_iterations,
        threshold=cfg.threshold,
        tie_eps=float(cfg.tie_eps),
        move_prob=float(cfg.move_prob),
        use_frontier=cfg.use_frontier,
        reshuffle_ties=cfg.reshuffle_ties,
        table_mode=cfg.table_mode,
        faults=tuple(sorted(f for f in faults if f in ENGINE_FAULTS)),
    )


def _plp_once(g: Graph, cfg: PLPConfig, ell_graph,
              faults: frozenset) -> PLPResult:
    timer = Timer()
    with timer.phase("ell_build") if cfg.backend in ("ell", "pallas") \
            else contextlib.nullcontext():
        engine = SweepEngine(g, engine_spec(cfg, faults), ell=ell_graph)

    labels, active = engine.singleton_state()
    with timer.phase("move"):
        res = engine.run_phase(labels, active, seed=cfg.seed, fused=cfg.fused)
    return PLPResult(
        labels=np.asarray(res.labels),
        iterations=res.sweeps,
        delta_n_history=res.delta_n_history,
        active_history=res.active_history,
        timer=timer,
    )


def plp(g: Graph, cfg: PLPConfig = PLPConfig(), ell_graph=None) -> PLPResult:
    """Run Parallel Label Propagation; returns final labels + history.

    Hardened like ``core.louvain.louvain``: non-taxonomy backend failures
    descend the ``pallas → ell → segment`` ladder (bit-identical on clean
    input; never on a TPU), iteration-budget exhaustion is flagged as a watchdog warning,
    and everything attempted lands in ``result.run_report``."""
    from repro.core.louvain import backend_descent

    report = RunReport(faults=sorted(faultinject.active()))
    if g.n_max == 0:
        return PLPResult(labels=np.zeros((0,), np.int32), iterations=0,
                         delta_n_history=[], active_history=[], timer=Timer(),
                         run_report=report)
    faults = frozenset(faultinject.active())
    cfg_try = cfg
    while True:
        try:
            res = _plp_once(g, cfg_try, ell_graph, faults)
            break
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
            # a descended run no longer uses the caller's ELL layout
            ell_graph = None
            cfg_try = cfg_try.replace(backend=nxt)
    if res.iterations >= cfg_try.max_iterations:
        report.warnings.append("watchdog:max_iterations")
    res.run_report = report
    return res
