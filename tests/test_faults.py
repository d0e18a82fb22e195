"""Fault-injection suite (DESIGN.md §Robustness).

The contract under test: every armed fault point either lands on a fallback
path whose result is BIT-IDENTICAL to the clean oracle, or raises a typed
``CommunityDetectionError`` with a populated ``RunReport`` — never a silent
wrong answer.  Run in CI under ``REPRO_VMEM_BUDGET_BYTES=1024`` so the
capacity-adaptive policies are additionally exercised in their starved
regime.
"""
import os
import importlib
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core.louvain import LouvainConfig, louvain
from repro.core.plp import PLPConfig, plp
from repro.graph.builders import (canonicalize_edges, from_numpy_edges,
                                  from_numpy_edges_robust)
from repro.graph.generators import sbm
from repro.utils import faultinject, telemetry
from repro.utils.errors import (CommunityDetectionError, InputValidationError,
                                KernelError, NumericError, RunReport,
                                ShardError)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def graph():
    u, v, w, _ = sbm(200, 4, p_in=0.3, p_out=0.02, seed=3)
    return from_numpy_edges(u, v, w)


# ------------------------------------------------------------------ registry


class TestRegistry:
    def test_unknown_point_rejected(self):
        with pytest.raises(ValueError, match="unknown fault"):
            faultinject.is_active("not_a_fault")
        with pytest.raises(ValueError, match="unknown fault"):
            faultinject.arm("not_a_fault")

    def test_arm_disarm_inject(self):
        assert faultinject.active() == frozenset()
        faultinject.arm("oscillation")
        assert faultinject.is_active("oscillation")
        faultinject.disarm("oscillation")
        assert not faultinject.is_active("oscillation")
        with faultinject.inject("nan_weight", "binned_overflow"):
            assert faultinject.active() == {"nan_weight", "binned_overflow"}
        assert faultinject.active() == frozenset()

    def test_inject_restores_on_exception(self):
        with pytest.raises(RuntimeError):
            with faultinject.inject("nan_weight"):
                raise RuntimeError("boom")
        assert faultinject.active() == frozenset()

    def test_engine_spec_rejects_unknown_faults(self):
        from repro.core.engine import EngineSpec

        with pytest.raises(ValueError, match="unknown fault"):
            EngineSpec(evaluator="plp", backend="segment",
                       faults=("not_a_fault",))

    def test_nested_inject_each_level_restores_what_it_saw(self):
        with faultinject.inject("nan_weight"):
            with faultinject.inject("oscillation", "vmem_starve"):
                assert faultinject.active() == {
                    "nan_weight", "oscillation", "vmem_starve"}
                # re-arming an already-armed point nests harmlessly
                with faultinject.inject("nan_weight"):
                    assert "nan_weight" in faultinject.active()
                assert "nan_weight" in faultinject.active()
            assert faultinject.active() == {"nan_weight"}
        assert faultinject.active() == frozenset()

    def test_nested_inject_restores_through_exceptions(self):
        with faultinject.inject("nan_weight"):
            with pytest.raises(RuntimeError):
                with faultinject.inject("oscillation"):
                    raise RuntimeError("boom")
            assert faultinject.active() == {"nan_weight"}
        assert faultinject.active() == frozenset()

    def test_bare_disarm_restores_env_baseline(self, monkeypatch):
        """A test's bare ``disarm()`` must not switch off the faults a CI
        chaos step configured for the whole process via REPRO_FAULTS."""
        monkeypatch.setenv(faultinject.FAULT_ENV, "oscillation,nan_weight")
        faultinject.arm("vmem_starve")
        faultinject.disarm()
        assert faultinject.active() == {"oscillation", "nan_weight"}
        monkeypatch.delenv(faultinject.FAULT_ENV)
        faultinject.disarm()
        assert faultinject.active() == frozenset()

    def test_rate_schedule_is_bresenham_exact(self):
        faultinject.arm("transient_batch_fail")
        faultinject.set_rate("transient_batch_fail", 0.25)
        fires = [faultinject.should_fire("transient_batch_fail")
                 for _ in range(20)]
        assert sum(fires) == 5          # exactly ⌊20 · 0.25⌋, no RNG
        assert fires == fires[:4] * 5   # periodic: every 4th query
        faultinject.disarm()
        assert not faultinject.should_fire("transient_batch_fail")

    def test_burst_turns_one_fire_into_consecutive_fires(self):
        faultinject.arm("transient_batch_fail")
        faultinject.set_rate("transient_batch_fail", 0.2)
        faultinject.set_burst("transient_batch_fail", 3)
        fires = [faultinject.should_fire("transient_batch_fail")
                 for _ in range(10)]
        assert fires == [False] * 4 + [True] * 3 + [False] * 3
        faultinject.disarm()

    def test_fuel_bounds_total_fires(self):
        faultinject.arm("slow_dispatch")
        faultinject.set_fuel("slow_dispatch", 2)
        fires = [faultinject.should_fire("slow_dispatch") for _ in range(5)]
        assert fires == [True, True, False, False, False]
        faultinject.disarm()

    def test_consume_fires_once_then_self_disarms(self):
        faultinject.arm("preempt_stage")
        assert faultinject.consume("preempt_stage")
        assert not faultinject.is_active("preempt_stage")
        assert not faultinject.consume("preempt_stage")


# ------------------------------------------------------- typed-error faults


class TestNanWeight:
    def test_fused_pipeline_raises_numeric(self, graph):
        with faultinject.inject("nan_weight"):
            with pytest.raises(NumericError) as ei:
                louvain(graph, LouvainConfig())
        assert "nan_weight" in ei.value.report.faults

    def test_per_level_driver_raises_numeric(self, graph):
        with faultinject.inject("nan_weight"):
            with pytest.raises(NumericError) as ei:
                louvain(graph, LouvainConfig(pipeline_fused=False))
        assert "nan_weight" in ei.value.report.faults


class TestShardDrop:
    def test_coverage_guard_raises(self):
        """A dropped shard must be refused before any compute dispatches
        (subprocess: needs 8 fake devices)."""
        code = textwrap.dedent("""
            import numpy as np, jax
            from jax.sharding import Mesh
            from repro.graph.generators import sbm
            from repro.graph.builders import from_numpy_edges
            from repro.core.distributed import distributed_louvain
            from repro.utils import faultinject
            from repro.utils.errors import ShardError
            u, v, w, _ = sbm(200, 4, p_in=0.3, p_out=0.02, seed=3)
            g = from_numpy_edges(u, v, w)
            mesh = Mesh(np.array(jax.devices()).reshape(8), ('data',))
            with faultinject.inject("shard_drop"):
                try:
                    distributed_louvain(g, mesh)
                except ShardError as e:
                    print("SHARD_ERROR", e)
                else:
                    raise SystemExit("no ShardError raised")
        """)
        env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
                   XLA_FLAGS="--xla_force_host_platform_device_count=8")
        p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                           text=True, env=env, cwd=REPO, timeout=900)
        assert p.returncode == 0, p.stdout + "\n" + p.stderr
        assert "SHARD_ERROR" in p.stdout


# --------------------------------------------------- bit-identical fallbacks


class TestBitIdenticalFallbacks:
    def test_binned_overflow_forces_sort_fallback(self, graph):
        clean = louvain(graph, LouvainConfig())
        telemetry.reset()
        with faultinject.inject("binned_overflow"):
            faulted = louvain(graph, LouvainConfig())
        assert np.array_equal(clean.labels, faulted.labels)
        assert clean.modularity == faulted.modularity
        assert clean.n_comm_per_level == faulted.n_comm_per_level
        assert faulted.run_report.faults == ["binned_overflow"]
        assert telemetry.get("fault.binned_overflow.forced") > 0

    def test_vmem_starve_lands_on_streamed_regime(self, graph):
        clean = louvain(graph, LouvainConfig(backend="pallas"))
        telemetry.reset()
        with faultinject.inject("vmem_starve"):
            starved = louvain(graph, LouvainConfig(backend="pallas"))
        assert np.array_equal(clean.labels, starved.labels)
        assert clean.modularity == starved.modularity
        assert telemetry.get("fault.vmem_starve.budget_clamped") > 0

    def test_oscillation_bounded_by_sweep_watchdog(self, graph):
        # move_prob=1.0 (pure Jacobi): a converged labeling is a fixpoint,
        # so forcing the loop to re-sweep cannot change labels — only burn
        # the watchdog budget, which the RunReport must record.
        cfg = LouvainConfig(move_prob=1.0, use_need_check=False, max_sweeps=6)
        clean = louvain(graph, cfg)
        with faultinject.inject("oscillation"):
            faulted = louvain(graph, cfg)
        assert np.array_equal(clean.labels, faulted.labels)
        assert clean.modularity == faulted.modularity
        assert all(s == cfg.max_sweeps for s in faulted.sweeps_per_level)
        assert any(w.startswith("watchdog:max_sweeps")
                   for w in faulted.run_report.warnings)
        assert not faulted.run_report.clean

    def test_oscillation_plp_watchdog(self, graph):
        cfg = PLPConfig(move_prob=1.0, use_frontier=False, max_iterations=5)
        clean = plp(graph, cfg)
        with faultinject.inject("oscillation"):
            faulted = plp(graph, cfg)
        assert np.array_equal(clean.labels, faulted.labels)
        assert faulted.iterations == cfg.max_iterations
        assert "watchdog:max_iterations" in faulted.run_report.warnings


# ------------------------------------------------------- degradation ladder


class TestDegradationLadder:
    def test_backend_descent_to_segment(self, graph, monkeypatch):
        """A non-taxonomy failure in the pallas backend descends
        pallas → ell → segment and still returns the segment answer."""
        louvain_mod = importlib.import_module("repro.core.louvain")

        real = louvain_mod._louvain_pipeline

        def flaky(g, cfg, g0, faults=frozenset(), promote=False):
            if cfg.backend in ("pallas", "ell"):
                raise RuntimeError(f"synthetic {cfg.backend} kernel failure")
            return real(g, cfg, g0, faults, promote)

        monkeypatch.setattr(louvain_mod, "_louvain_pipeline", flaky)
        oracle = louvain(graph, LouvainConfig(backend="segment"))
        res = louvain(graph, LouvainConfig(backend="pallas"))
        assert np.array_equal(res.labels, oracle.labels)
        assert [d["from"] for d in res.run_report.degradations] == \
            ["pallas", "ell"]
        assert all(d["kind"] == "backend_descent"
                   for d in res.run_report.degradations)

    def test_ladder_exhaustion_raises_kernel_error(self, graph, monkeypatch):
        louvain_mod = importlib.import_module("repro.core.louvain")

        def broken(g, cfg, g0, faults=frozenset(), promote=False):
            raise RuntimeError("synthetic failure on every backend")

        monkeypatch.setattr(louvain_mod, "_louvain_pipeline", broken)
        with pytest.raises(KernelError) as ei:
            louvain(graph, LouvainConfig(backend="pallas"))
        # the report shows the whole descent was tried before giving up
        assert [d["from"] for d in ei.value.report.degradations] == \
            ["pallas", "ell"]

    @pytest.mark.parametrize("algo", ["louvain", "plp"])
    def test_no_backend_descent_on_tpu(self, graph, monkeypatch, algo):
        """On a TPU a kernel failure (a Pallas lowering or compile error)
        surfaces as KernelError; it never turns into a quiet ell run."""
        louvain_mod = importlib.import_module("repro.core.louvain")
        plp_mod = importlib.import_module("repro.core.plp")

        def broken(*a, **k):
            raise NotImplementedError("synthetic Mosaic lowering failure")

        target = ((louvain_mod, "_louvain_pipeline") if algo == "louvain"
                  else (plp_mod, "_plp_once"))
        monkeypatch.setattr(*target, broken)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert louvain_mod.backend_descent("pallas") is None
        telemetry.reset()
        with pytest.raises(KernelError, match="synthetic Mosaic") as ei:
            if algo == "louvain":
                louvain_mod.louvain(graph, LouvainConfig(backend="pallas"))
            else:
                plp_mod.plp(graph, PLPConfig(backend="pallas"))
        assert ei.value.report.degradations == []
        assert telemetry.get("ladder.backend_descent") == 0

    def test_capacity_retry_on_single_capacity_program(self, graph,
                                                       monkeypatch):
        louvain_mod = importlib.import_module("repro.core.louvain")
        from repro.utils.errors import CapacityError

        real = louvain_mod._louvain_pipeline

        def busted(g, cfg, g0, faults=frozenset(), promote=False):
            if cfg.capacity_schedule != "none":
                raise CapacityError("synthetic cascade capacity bust")
            return real(g, cfg, g0, faults, promote)

        monkeypatch.setattr(louvain_mod, "_louvain_pipeline", busted)
        oracle = louvain(graph, LouvainConfig(capacity_schedule="none"))
        res = louvain(graph, LouvainConfig(capacity_schedule="auto"))
        assert np.array_equal(res.labels, oracle.labels)
        assert res.run_report.retries == [{
            "kind": "capacity", "from": "'auto'", "to": "none",
            "error": "synthetic cascade capacity bust"}]

    def test_typed_errors_do_not_descend(self, graph, monkeypatch):
        """Taxonomy errors mean the ANSWER is unsafe: no backend retry."""
        louvain_mod = importlib.import_module("repro.core.louvain")

        calls = []

        def poisoned(g, cfg, g0, faults=frozenset(), promote=False):
            calls.append(cfg.backend)
            raise NumericError("synthetic numeric refusal")

        monkeypatch.setattr(louvain_mod, "_louvain_pipeline", poisoned)
        with pytest.raises(NumericError):
            louvain(graph, LouvainConfig(backend="pallas"))
        assert calls == ["pallas"]

    def test_clean_run_report_is_clean(self, graph):
        res = louvain(graph, LouvainConfig())
        assert res.run_report.clean
        assert res.run_report.as_dict()["faults"] == []


# ------------------------------------------------------------------- ingest


class TestIngestRepair:
    def test_clean_input_passes_through_bit_identical(self):
        u = np.array([0, 1, 2], np.int64)
        v = np.array([1, 2, 3], np.int64)
        w = np.array([1.0, 2.0, 3.0])
        u2, v2, w2, n, rep = canonicalize_edges(u, v, w, n=4)
        assert rep.clean and rep.actions == ()
        assert u2 is u and v2 is v and w2 is w

    def test_duplicates_coalesce_to_manual_dedup(self):
        u = np.array([0, 1, 0, 2, 1], np.int64)
        v = np.array([1, 0, 1, 3, 2], np.int64)
        w = np.array([1.0, 2.0, 0.5, 1.0, 1.0])
        u2, v2, w2, n, rep = canonicalize_edges(u, v, w, n=4)
        assert rep.duplicates_coalesced == 2
        g = from_numpy_edges(u2, v2, w2, n=n)
        gm = from_numpy_edges(np.array([0, 1, 2]), np.array([1, 2, 3]),
                              np.array([3.5, 1.0, 1.0]), n=4)
        assert np.array_equal(np.asarray(g.src), np.asarray(gm.src))
        assert np.array_equal(np.asarray(g.w), np.asarray(gm.w))

    def test_bad_weight_policies(self):
        u = np.array([0, 1], np.int64)
        v = np.array([1, 2], np.int64)
        w = np.array([1.0, np.nan])
        with pytest.raises(InputValidationError):
            canonicalize_edges(u, v, w, n=3)
        u2, v2, w2, n, rep = canonicalize_edges(u, v, w, n=3,
                                                bad_weights="drop")
        assert rep.nonfinite_weights == 1 and len(w2) == 1

    def test_out_of_range_ids_and_loops(self):
        u = np.array([0, 1, 2, 9], np.int64)
        v = np.array([1, 1, 0, 0], np.int64)
        w = np.ones(4)
        with pytest.raises(InputValidationError):
            canonicalize_edges(u, v, w, n=3)
        u2, v2, w2, n, rep = canonicalize_edges(
            u, v, w, n=3, bad_ids="drop", self_loops="drop")
        assert rep.out_of_range_ids == 1 and rep.self_loops_dropped == 1
        assert len(u2) == 2

    def test_robust_entry_point_reports(self):
        u = np.array([0, 1, 0], np.int64)
        v = np.array([1, 2, 1], np.int64)
        w = np.array([1.0, 1.0, 2.0])
        g, rep = from_numpy_edges_robust(u, v, w, n=3)
        assert rep.duplicates_coalesced == 1
        assert int(g.m_valid) == 4  # 2 undirected edges, symmetrized


# ------------------------------------------------------------ trivial cases


def test_empty_capacity_early_out():
    g = from_numpy_edges(np.zeros(0, np.int64), np.zeros(0, np.int64),
                         np.zeros(0), n=0)
    res = louvain(g)
    assert res.n_communities == 0 and res.levels == 0
    assert isinstance(res.run_report, RunReport)
    p = plp(g)
    assert p.iterations == 0
