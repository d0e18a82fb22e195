"""Capacity-scheduled coarse-level cascade (DESIGN.md §Pipeline).

Contract: for ANY capacity schedule, ``louvain()``/``leiden()`` results are
BIT-FOR-BIT identical to the single-capacity pipeline
(``capacity_schedule="none"``, the parity oracle) — final labels, levels and
every per-level history — while the cascade executes at most
``len(schedule)`` compiled stage programs, descending through strictly
shrinking static capacities, with one bulk readback plus one 5-scalar sync
per stage boundary.  Coarse levels inside a cascade run the ell/pallas
backends through the traced per-stage ELL re-bucketing instead of the
segment fallback, which must not change a single bit either.
"""
import importlib

import numpy as np
import pytest
import jax.numpy as jnp

louvain_mod = importlib.import_module("repro.core.louvain")
from repro.core.louvain import (LouvainConfig, auto_capacity_schedule,
                                leiden, louvain)
from repro.graph.builders import from_numpy_edges
from repro.graph.generators import sbm
from repro.utils import telemetry


def _banded_graph(n=6144, band=40, k=6, seed=5):
    """Deep-hierarchy graph: ~n/band communities after level 0, collapsing
    over many levels — shrinks past >= 2 capacity steps of the auto
    schedule."""
    rng = np.random.default_rng(seed)
    u = np.repeat(np.arange(n), k)
    v = np.clip(u + rng.integers(1, band, size=n * k), 0, n - 1)
    keep = u != v
    u, v = u[keep], v[keep]
    uu, vv = np.concatenate([u, v]), np.concatenate([v, u])
    return from_numpy_edges(uu, vv, np.ones(uu.size, np.float32))


def _planted_graph(n=5000, communities=40, seed=11):
    u, v, w, _ = sbm(n, communities, p_in=0.08, p_out=0.0008, seed=seed)
    return from_numpy_edges(u, v, w)


def _assert_bitwise_equal(r_a, r_b):
    np.testing.assert_array_equal(np.asarray(r_a.labels),
                                  np.asarray(r_b.labels))
    assert r_a.levels == r_b.levels
    assert r_a.n_communities == r_b.n_communities
    assert r_a.modularity == r_b.modularity
    assert r_a.modularity_history == r_b.modularity_history
    assert r_a.sweeps_per_level == r_b.sweeps_per_level
    assert r_a.n_comm_per_level == r_b.n_comm_per_level
    assert r_a.delta_n_per_level == r_b.delta_n_per_level


# ------------------------------------------------------------ schedule policy


def test_auto_schedule_bounded_and_descending():
    caps = auto_capacity_schedule(1 << 20, 1 << 24)
    assert len(caps) <= 4
    assert caps[0] == (1 << 20, 1 << 24)
    for a, b in zip(caps, caps[1:]):
        assert b[0] < a[0] or b[1] < a[1]
        assert b[0] <= a[0] and b[1] <= a[1]
    # floors hold
    assert all(n >= 256 and m >= 2048 for n, m in caps)


def test_auto_schedule_small_graph_degenerates():
    assert auto_capacity_schedule(200, 4000) == ((200, 4000),)
    assert auto_capacity_schedule(4095, 40000) == ((4095, 40000),)


@pytest.mark.parametrize("bad", [
    "bogus",
    (),
    ((0, 10),),
    ((10, -1),),
    ((10,),),
    ((100, 100), (200, 100)),          # not descending
    ((100, 100), (100, 100)),          # stalled
    (("a", 10),),
])
def test_schedule_validation_rejects(bad):
    with pytest.raises(ValueError, match="capacity_schedule"):
        LouvainConfig(capacity_schedule=bad)


def test_schedule_validation_accepts_forms():
    LouvainConfig(capacity_schedule="auto")
    LouvainConfig(capacity_schedule="none")
    LouvainConfig(capacity_schedule=((4096, 65536), (1024, 16384)))


# ------------------------------------------------------------ parity suite


@pytest.mark.parametrize("backend", ["segment", "ell"])
@pytest.mark.parametrize("algo", ["louvain", "leiden"])
def test_cascade_parity_deep_banded(algo, backend):
    """Deep-hierarchy banded graph: the run must actually descend >= 2
    capacity steps and stay bit-identical to the single-capacity oracle."""
    g = _banded_graph()
    run = leiden if algo == "leiden" else louvain
    cfg = LouvainConfig(seed=5, backend=backend)
    r_c = run(g, cfg.replace(capacity_schedule="auto"))
    r_f = run(g, cfg.replace(capacity_schedule="none"))
    _assert_bitwise_equal(r_c, r_f)
    assert len(r_c.cascade_stages) >= 2, r_c.cascade_stages
    assert r_c.cascade_stages[0] == (g.n_max, g.m_max)
    for a, b in zip(r_c.cascade_stages, r_c.cascade_stages[1:]):
        assert b[0] < a[0] and b[1] < a[1]
    assert r_f.cascade_stages == [(g.n_max, g.m_max)]
    # the schedule bound on compiled stage programs
    assert len(r_c.cascade_stages) <= len(
        auto_capacity_schedule(g.n_max, g.m_max))


def test_cascade_parity_planted_partition():
    g = _planted_graph()
    cfg = LouvainConfig(seed=2, backend="segment")
    r_c = louvain(g, cfg.replace(capacity_schedule="auto"))
    r_f = louvain(g, cfg.replace(capacity_schedule="none"))
    _assert_bitwise_equal(r_c, r_f)
    assert len(r_c.cascade_stages) >= 2, r_c.cascade_stages


def test_cascade_parity_pallas_backend():
    """pallas coarse levels run the fused kernel over the traced tile."""
    g = _banded_graph(n=4608, band=32, k=5, seed=9)
    cfg = LouvainConfig(seed=9, backend="pallas", track_modularity=False)
    r_c = louvain(g, cfg.replace(capacity_schedule="auto"))
    r_f = louvain(g, cfg.replace(capacity_schedule="none"))
    _assert_bitwise_equal(r_c, r_f)
    assert len(r_c.cascade_stages) >= 2


def test_cascade_never_shrinking_degenerates():
    """A hierarchy that never fits the next capacity must stay in the one
    full-capacity program (today's pipeline) and still agree.

    A perfect matching collapses to exactly n/2 communities at level 0 and
    converges at level 1 (the coarse graph is pure self-loops), so the live
    counts never drop below the first capacity step n/4."""
    n = 4500
    u = np.arange(0, n, 2)
    v = u + 1
    g = from_numpy_edges(u, v, np.ones(u.size, np.float32))
    assert g.n_max >= 4096  # auto schedule is NOT degenerate
    assert len(auto_capacity_schedule(g.n_max, g.m_max)) > 1
    cfg = LouvainConfig(seed=1, backend="segment")
    r_c = louvain(g, cfg.replace(capacity_schedule="auto"))
    r_f = louvain(g, cfg.replace(capacity_schedule="none"))
    _assert_bitwise_equal(r_c, r_f)
    # ~n/2 communities never fit the n/4 capacity step: one stage, no descent
    assert r_c.n_communities > n // 4
    assert r_c.cascade_stages == [(g.n_max, g.m_max)]


def test_cascade_capacity_padded_sparse_graph():
    """Schedule floors must clamp to the graph's OWN capacities: a
    capacity-padded sparse graph (m_max below the 2048 m-floor) used to be
    scheduled to GROW its edge capacity, crashing the second stage with a
    shape mismatch."""
    from repro.graph.structure import graph_from_arrays

    rng = np.random.default_rng(0)
    u = rng.integers(0, 900, 800)
    v = rng.integers(0, 900, 800)
    keep = u != v
    uu = np.concatenate([u[keep], v[keep]])
    vv = np.concatenate([v[keep], u[keep]])
    order = np.lexsort((vv, uu))
    g = graph_from_arrays(
        jnp.asarray(uu[order], jnp.int32), jnp.asarray(vv[order], jnp.int32),
        jnp.ones((uu.size,), jnp.float32), n_max=5000, m_max=1800,
        n_valid=900, sorted_by="src")
    assert g.m_max < 2048 <= 4096 <= g.n_max
    caps = auto_capacity_schedule(g.n_max, g.m_max)
    assert all(m <= g.m_max for _, m in caps)
    cfg = LouvainConfig(seed=0, backend="segment")
    r_c = louvain(g, cfg.replace(capacity_schedule="auto"))
    r_f = louvain(g, cfg.replace(capacity_schedule="none"))
    _assert_bitwise_equal(r_c, r_f)
    assert len(r_c.cascade_stages) >= 2


def test_explicit_schedule_and_oversized_entries():
    g = _banded_graph(n=4352, band=40, k=6, seed=3)
    sched = ((1 << 20, 1 << 24),        # larger than the graph: dropped
             (1024, 12288), (320, 4096))
    cfg = LouvainConfig(seed=3, backend="segment")
    r_c = louvain(g, cfg.replace(capacity_schedule=sched))
    r_f = louvain(g, cfg.replace(capacity_schedule="none"))
    _assert_bitwise_equal(r_c, r_f)
    assert r_c.cascade_stages[0] == (g.n_max, g.m_max)
    assert all(s in ((g.n_max, g.m_max),) + sched[1:]
               for s in r_c.cascade_stages)
    assert len(r_c.cascade_stages) >= 2


def test_cascade_transfer_accounting():
    """One bulk readback per run; one 5-scalar sync per stage boundary
    crossed (never more than the schedule allows); zero syncs when the
    schedule degenerates."""
    g = _banded_graph(n=4608, band=32, k=5, seed=7)
    cfg = LouvainConfig(seed=7, backend="segment", track_modularity=False)
    louvain(g, cfg)  # warm (compile outside the counted window)

    before_rb = telemetry.get("louvain.readback")
    before_sync = telemetry.get("louvain.stage_sync")
    r = louvain(g, cfg)
    assert telemetry.get("louvain.readback") == before_rb + 1
    syncs = telemetry.get("louvain.stage_sync") - before_sync
    assert 1 <= syncs <= len(auto_capacity_schedule(g.n_max, g.m_max))
    assert len(r.cascade_stages) >= 2

    # degenerate schedule: single program, zero stage syncs
    r0 = louvain(g, cfg.replace(capacity_schedule="none"))
    before_sync = telemetry.get("louvain.stage_sync")
    louvain(g, cfg.replace(capacity_schedule="none"))
    assert telemetry.get("louvain.stage_sync") == before_sync
    _assert_bitwise_equal(r, r0)


def test_stage_program_count_bounded_by_schedule():
    """Distinct compiled stage programs per run <= len(schedule)."""
    g = _banded_graph(n=4864, band=36, k=5, seed=13)
    cfg = LouvainConfig(seed=13, backend="segment", track_modularity=False)
    louvain(g, cfg)  # warm
    before = louvain_mod._stage_fn.cache_info().misses
    r = louvain(g, cfg)
    assert louvain_mod._stage_fn.cache_info().misses == before  # all cached
    assert len(r.cascade_stages) <= len(
        auto_capacity_schedule(g.n_max, g.m_max))


# ------------------------------------------------------------ traced tile


def test_traced_ell_tile_covers_and_flags_tail():
    from repro.core import aggregation
    from repro.graph.ell import traced_ell_tile

    u, v, w, gt = sbm(300, 10, p_in=0.3, p_out=0.02, seed=4)
    g0 = from_numpy_edges(u, v, w)
    com = jnp.asarray(np.concatenate(
        [gt, np.arange(len(gt), g0.n_max)]), jnp.int32)
    _, _, cg = aggregation.remap_and_coarsen(g0, com)

    rows, nbr, wt, is_tail = traced_ell_tile(cg, 16)
    n = cg.n_max
    deg = np.zeros(n, np.int64)
    src, dst, wv = cg.to_numpy_edges()
    np.add.at(deg, src, 1)
    nv = int(cg.n_valid)
    np.testing.assert_array_equal(np.asarray(is_tail)[:nv], deg[:nv] > 16)
    # non-tail rows reproduce the exact non-loop neighbor multiset
    rows_np, nbr_np, wt_np = (np.asarray(rows), np.asarray(nbr),
                              np.asarray(wt))
    for vtx in range(nv):
        if deg[vtx] > 16:
            assert rows_np[vtx] == n  # tail row is pure padding
            continue
        assert rows_np[vtx] == vtx
        want = sorted((d, ww) for s, d, ww in zip(src, dst, wv)
                      if s == vtx and d != vtx)
        got = sorted((d, ww) for d, ww in zip(nbr_np[vtx], wt_np[vtx])
                     if d < n)
        assert got == want, vtx
    # weights of padding slots are zero
    assert float(wt_np[nbr_np == n].sum()) == 0.0


@pytest.mark.parametrize("evaluator", ["louvain", "plp"])
@pytest.mark.parametrize("width", [4, 64])
def test_traced_engine_matches_segment(evaluator, width):
    """Traced ell/pallas coarse evaluator == segment evaluator, bit-for-bit,
    including a width small enough to force the cond-gated tail path."""
    from repro.core import aggregation
    from repro.core.engine import EngineSpec, SweepEngine
    from repro.graph.ell import traced_ell_tile

    u, v, w, gt = sbm(400, 12, p_in=0.35, p_out=0.03, seed=7)
    g0 = from_numpy_edges(u, v, w)
    com = jnp.asarray(np.concatenate(
        [gt, np.arange(len(gt), g0.n_max)]), jnp.int32)
    _, _, cg = aggregation.remap_and_coarsen(g0, com)
    if width == 4:   # sanity: the forced-tail case really has a tail
        *_, it = traced_ell_tile(cg, width)
        assert bool(jnp.any(it))

    res = {}
    for backend, ew in (("segment", 0), ("ell", width), ("pallas", width)):
        spec = EngineSpec(evaluator=evaluator, backend=backend,
                          max_sweeps=12, move_prob=0.5, ell_width=ew)
        eng = SweepEngine(cg, spec)
        res[backend] = eng.run_phase(*eng.singleton_state(), it0=1000, seed=3)
    for backend in ("ell", "pallas"):
        np.testing.assert_array_equal(
            np.asarray(res[backend].labels), np.asarray(res["segment"].labels))
        assert res[backend].sweeps == res["segment"].sweeps
        assert (res[backend].delta_n_history
                == res["segment"].delta_n_history)


def test_ell_width_spec_validation():
    from repro.core.engine import EngineSpec

    with pytest.raises(ValueError, match="ell_width"):
        EngineSpec(backend="segment", ell_width=16)
    with pytest.raises(ValueError, match="ell_width"):
        EngineSpec(backend="ell", ell_width=-1)
    EngineSpec(backend="pallas", ell_width=64)


def test_pick_ell_width_menu():
    from repro.kernels.common import STAGE_WIDTH_MENU, pick_ell_width

    assert pick_ell_width(3, 1024, 8192) == STAGE_WIDTH_MENU[0]
    assert pick_ell_width(64, 1024, 8192) == 64
    assert pick_ell_width(65, 1024, 8192) == 256
    assert pick_ell_width(10_000, 1024, 8192) == STAGE_WIDTH_MENU[-1]
    # static heuristic (stage 0): 4x average degree, floored at the menu min
    assert pick_ell_width(None, 1024, 2048) == STAGE_WIDTH_MENU[0]
    assert pick_ell_width(None, 1024, 32768) == 256
