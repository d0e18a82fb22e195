"""Multi-level pipeline fusion (DESIGN.md §Pipeline).

Contract: ``pipeline_fused=True`` (whole level loop in one jitted
lax.while_loop, one host readback) and ``pipeline_fused=False`` (per-level
Python driver) produce BIT-FOR-BIT identical final labels, levels, and
per-level histories at fixed seed, for louvain and leiden on the ``segment``
and ``ell`` backends — and the fused pipeline performs exactly one
device→host transfer per call after graph build.
"""
import dataclasses

import numpy as np
import pytest
import jax

from repro.core.louvain import LouvainConfig, leiden, louvain

# repro.core.__init__ re-exports the louvain FUNCTION under the module's
# name, so fetch the actual module object for monkeypatching hooks
import importlib
louvain_mod = importlib.import_module("repro.core.louvain")
from repro.graph.builders import from_numpy_edges
from repro.graph.generators import ring_of_cliques, sbm
from repro.utils import telemetry


def _graph(seed=7, n=200, k=5):
    u, v, w, _ = sbm(n, k, p_in=0.3, p_out=0.03, seed=seed)
    return from_numpy_edges(u, v, w)


def _assert_bitwise_equal(r_fused, r_step):
    np.testing.assert_array_equal(
        np.asarray(r_fused.labels), np.asarray(r_step.labels))
    assert r_fused.levels == r_step.levels
    assert r_fused.n_communities == r_step.n_communities
    assert r_fused.modularity == r_step.modularity
    assert r_fused.modularity_history == r_step.modularity_history
    assert r_fused.sweeps_per_level == r_step.sweeps_per_level
    assert r_fused.n_comm_per_level == r_step.n_comm_per_level
    assert r_fused.delta_n_per_level == r_step.delta_n_per_level


@pytest.mark.parametrize("backend", ["segment", "ell"])
@pytest.mark.parametrize("algo", ["louvain", "leiden"])
def test_pipeline_fused_matches_per_level(algo, backend):
    g = _graph()
    run = leiden if algo == "leiden" else louvain
    cfg = LouvainConfig(seed=3, backend=backend)
    r_fused = run(g, cfg.replace(pipeline_fused=True))
    r_step = run(g, cfg.replace(pipeline_fused=False))
    _assert_bitwise_equal(r_fused, r_step)


def test_pipeline_parity_without_modularity_tracking():
    g = _graph(seed=11)
    cfg = LouvainConfig(seed=1, track_modularity=False)
    r_fused = louvain(g, cfg.replace(pipeline_fused=True))
    r_step = louvain(g, cfg.replace(pipeline_fused=False))
    assert r_fused.modularity_history == [] == r_step.modularity_history
    _assert_bitwise_equal(r_fused, r_step)


def test_pipeline_parity_under_level_budget():
    """Budget exhaustion (max_levels smaller than natural depth) must agree."""
    g = _graph(seed=4)
    cfg = LouvainConfig(seed=4, max_levels=2)
    r_fused = louvain(g, cfg.replace(pipeline_fused=True))
    r_step = louvain(g, cfg.replace(pipeline_fused=False))
    assert r_fused.levels <= 2
    _assert_bitwise_equal(r_fused, r_step)


def test_pipeline_single_readback():
    """The fused pipeline makes exactly ONE device→host transfer per call
    (the `_readback` of the history buffers), and no other jax.device_get."""
    g = _graph(seed=5)
    cfg = LouvainConfig(seed=5)
    louvain(g, cfg)  # warm: compile outside the counted window

    calls = {"readback": 0, "device_get": 0}
    orig_readback = louvain_mod._readback
    orig_device_get = jax.device_get

    def counting_readback(tree):
        calls["readback"] += 1
        return orig_readback(tree)

    def counting_device_get(tree):
        calls["device_get"] += 1
        return orig_device_get(tree)

    louvain_mod._readback = counting_readback
    jax.device_get = counting_device_get
    try:
        louvain(g, cfg)
    finally:
        louvain_mod._readback = orig_readback
        jax.device_get = orig_device_get
    assert calls["readback"] == 1
    assert calls["device_get"] == 1   # only the one inside _readback


def test_pipeline_transfer_counter_hook():
    g = _graph(seed=6)
    before = telemetry.get("louvain.readback")
    louvain(g, LouvainConfig(seed=6))
    assert telemetry.get("louvain.readback") == before + 1


def test_max_levels_one_regression():
    """max_levels=1 used to be the smallest legal value; it must run and the
    two drivers must agree (the old driver raised UnboundLocalError for
    max_levels < 1, which is now rejected at config construction)."""
    g = _graph(seed=8)
    cfg = LouvainConfig(seed=8, max_levels=1)
    r_fused = louvain(g, cfg.replace(pipeline_fused=True))
    r_step = louvain(g, cfg.replace(pipeline_fused=False))
    assert r_fused.levels == 1 == r_step.levels
    _assert_bitwise_equal(r_fused, r_step)


@pytest.mark.parametrize("bad", [
    dict(max_levels=0), dict(max_levels=-3),
    dict(move_prob=0.0), dict(move_prob=-0.5), dict(move_prob=1.5),
    dict(refine_sweeps=0),
])
def test_config_validation_rejects(bad):
    with pytest.raises(ValueError):
        LouvainConfig(**bad)


def test_config_validation_survives_replace():
    cfg = LouvainConfig()
    with pytest.raises(ValueError):
        cfg.replace(max_levels=0)
    assert dataclasses.replace(cfg, max_levels=1).max_levels == 1


def test_pipeline_histories_well_formed():
    """Histories must cover exactly `levels` entries with sane values."""
    u, v, w, _ = ring_of_cliques(10, 5)
    g = from_numpy_edges(u, v, w)
    res = louvain(g, LouvainConfig(seed=2))
    assert res.levels >= 2
    assert len(res.sweeps_per_level) == res.levels
    assert len(res.n_comm_per_level) == res.levels
    assert len(res.modularity_history) == res.levels
    assert len(res.delta_n_per_level) == res.levels
    assert all(s >= 1 for s in res.sweeps_per_level)
    # community counts shrink monotonically and end at the final count
    nc = res.n_comm_per_level
    assert all(b <= a for a, b in zip(nc, nc[1:]))
    assert nc[-1] == res.n_communities
    # ΔN histories are the executed prefix (no -1 sentinels leak out)
    for dn, s in zip(res.delta_n_per_level, res.sweeps_per_level):
        assert len(dn) == s
        assert all(x >= 0 for x in dn)


def test_pipeline_stepwise_sweeps_fall_back_to_per_level():
    """fused=False (stepwise sweeps) cannot run inside the fused pipeline;
    the driver must fall back to the per-level path and still agree."""
    g = _graph(seed=9)
    cfg = LouvainConfig(seed=9)
    r = louvain(g, cfg.replace(fused=False, pipeline_fused=True))
    r_ref = louvain(g, cfg.replace(fused=False, pipeline_fused=False))
    _assert_bitwise_equal(r, r_ref)
    # and the stepwise-sweep run matches the fully fused pipeline too
    r_pipe = louvain(g, cfg)
    _assert_bitwise_equal(r_pipe, r)
