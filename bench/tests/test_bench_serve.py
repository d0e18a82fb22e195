"""The served cell, ``egonet-twitter.louvain-serve``: its generator's pool,
its loop, entry and readers at a tiny size on the CPU, and the engine's
answers on ego-nets of the configuration against the single-graph driver
and the reference."""
import hashlib
import json
import os
import shutil
import time

import numpy as np
import pytest

from bench import check, graphs, run, spec

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "egonet-twitter.louvain-serve"
METRICS = {"idle_share.serve", "serve.graphs_per_dispatch",
           "serve.empty_lane_share", "serve.ingest_ms"}


POOL_SHA = "1618132cf0a8d8af"


@pytest.fixture(scope="module")
def egonet():
    return spec.load_json(os.path.join(ROOT, "bench", "configs",
                                       "egonet-twitter.json"))


def _sha(g) -> str:
    u, v, n = g
    h = hashlib.sha256()
    for part in (u.astype(np.int64).tobytes(), v.astype(np.int64).tobytes(),
                 str(n).encode()):
        h.update(part)
    return h.hexdigest()[:16]


def _pool(cfg, seed=None):
    rng = graphs.stream(cfg["graph_seed"] if seed is None else seed, 1)
    return spec.module("generators", "egonet").pool(cfg, rng)


def test_egonet_pool_is_pinned_and_holds_the_sources_means(egonet):
    """The served cell's pool for ``graph_seed`` 0, pinned as one sha256
    over every ego-net's ``u``, ``v`` (int64) and ``n``; its means are the
    source's (81,306 alters and 1,768,149 edges over 1,000 egos)."""
    gs = _pool(egonet)
    assert len(gs) == egonet["egonets"] == 1000
    h = hashlib.sha256()
    for g in gs:
        h.update(_sha(g).encode())
    assert h.hexdigest()[:16] == POOL_SHA
    pub = egonet["published"]
    n = np.array([g[2] for g in gs])
    m = np.array([g[0].size for g in gs])
    assert n.mean() == pytest.approx(pub["vertices"] / pub["egonets"],
                                     rel=0.02)
    assert m.mean() == pytest.approx(pub["directed_edges"]
                                     / pub["egonets"], rel=0.02)
    assert (n.min(), n.max()) == tuple(egonet["vertices"])


def test_egonets_are_simple_graphs_drawn_from_their_stream(egonet):
    rng = graphs.stream(2**31 + 12345, 8)
    draws = [spec.generator("egonet")(egonet, rng) for _ in range(64)]
    lo, hi = egonet["vertices"]
    for u, v, n in draws:
        assert lo <= n <= hi
        assert np.all(u < v) and (not v.size or v.max() < n)
        assert np.unique(u * n + v).size == u.size
    again = spec.generator("egonet")(egonet, graphs.stream(2**31 + 12345, 8))
    assert np.array_equal(again[1], draws[0][1])
    other = _pool(egonet, seed=1)
    assert [g[2] for g in other] != [g[2] for g in _pool(egonet)]
    assert sorted(g[2] for g in other) == sorted(g[2] for g in _pool(egonet))


@pytest.fixture
def jax_config():
    """``run_cell`` points JAX's compilation cache into its checkout."""
    import jax

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def _hashes(base) -> dict:
    return {os.path.relpath(os.path.join(d, f), base): hashlib.sha256(
        open(os.path.join(d, f), "rb").read()).hexdigest()
        for d, _, fs in os.walk(base) for f in fs}


def test_served_cell_runs_to_a_correct_result_at_a_tiny_size(
        tmp_path, jax_config, capfd):
    """The cell as ``BENCHMARK.json`` names it, with its pool cut to 24
    ego-nets of 16-48 alters and ticks of 8 (its files otherwise as they
    are): untraced and traced runs are correct, nothing is traced or
    compiled inside the window, and the traced run reads all four
    per-layer metrics."""
    base = tmp_path / "bench"
    shutil.copytree(os.path.join(ROOT, "bench"), base,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    cfg = spec.load_json(str(base / "configs" / "egonet-twitter.json"))
    cfg.update(egonets=24, vertices=[16, 48], median_vertices=24)
    (base / "configs" / "egonet-twitter.json").write_text(json.dumps(cfg))
    traffic = spec.traffic("louvain-serve", str(base))
    (base / "traffic" / "louvain-serve.json").write_text(
        json.dumps(dict(traffic, tick=8)))
    before = _hashes(base)
    bench = spec.benchmark(ROOT)
    results = [run.run_cell(bench, CELL, 2**31 + 11, 0.0, traced,
                            t_start=time.perf_counter(), device_check=False,
                            root=str(tmp_path)) for traced in (False, True)]
    err = capfd.readouterr().err
    assert "NOTE: programs were traced or compiled" not in err
    assert "in the window: {'traces': 0, 'executables': 0" in err
    for res in results:
        assert res["correct"], res["compared"]
        assert res["attempted"] == 24 and res["failed"] == 0
    assert set(results[0]["metrics"]) == {"setup_s", "solve_s"}
    assert results[0]["metrics"]["solve_s"]["value"] > 0
    got = results[1]["metrics"]
    assert set(got) == METRICS
    assert 1 <= got["serve.graphs_per_dispatch"]["value"] <= 8
    assert 0 <= got["serve.empty_lane_share"]["value"] < 1
    assert got["serve.ingest_ms"]["value"] > 0
    assert _hashes(base) == before


def test_served_answers_equal_the_single_graph_driver_and_the_reference():
    """32 ego-nets of the configuration drawn from a seed, through one
    ``CommunityServeEngine``: every answer is a partition, equals
    ``louvain()`` on the graph it served (at its bucket's capacity, where
    labels are those of the graph alone by capacity portability), and
    reports the modularity the float64 reference gives its labels within
    1e-5."""
    from launch.community_serve import (CommunityRequest,
                                        CommunityServeEngine, ingest_request)
    from repro.core.louvain import louvain
    from repro.graph.builders import from_numpy_edges

    cfg = spec.load_json(os.path.join(ROOT, "bench", "configs",
                                      "egonet-twitter.json"))
    rng = graphs.stream(2**31 + 7, 8)
    gs = [spec.generator("egonet")(cfg, rng) for _ in range(32)]
    reqs = [CommunityRequest(f"g{i}", u, v, n=n)
            for i, (u, v, n) in enumerate(gs)]
    eng = CommunityServeEngine()
    assert all(eng.submit(r) is None for r in reqs)
    responses = eng.flush()
    assert [r.request_id for r in responses] == [r.request_id for r in reqs]
    ref = spec.reference("louvain")
    for (u, v, n), req, resp in zip(gs, reqs, responses):
        assert resp.ok, resp.error
        res = resp.result
        assert check.is_partition(resp.labels, n, res.n_communities)
        g, _ = ingest_request(req)
        single = louvain(from_numpy_edges(u, v, n=n, n_max=g.n_max,
                                          m_max=g.m_max))
        assert np.array_equal(resp.labels, single.labels[:n])
        assert res.modularity == single.modularity
        assert res.n_communities == single.n_communities
        src, dst, w = check.symmetric(u, v)
        q = ref.modularity(src, dst, w, np.asarray(resp.labels, np.int64))
        assert abs(res.modularity - q) <= 1e-5
