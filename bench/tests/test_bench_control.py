"""The comparison that decides ``correct`` fails what it must.

The control (the reference in bfloat16 in the program's place) comes out
not correct against the cell's own limits; and a whole run of the closed
loop, driven past its look for a chip, comes out not correct when the
timed path is broken underneath: an answer altered where it is produced
(on every graph, or only on graphs other than the timed ones), a state
returned unchanged (labels left as singletons).  Sizes are cut so the
suite can hold them; the sound run beside the faults shows that the
faults, not the size, fail the runs.
"""
import importlib
import json
import os
import shutil
import time

import numpy as np
import pytest

from bench import control, run, spec

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CLOSED = "youtube-rmat16.louvain"


def _edit(path, **kw):
    with open(path) as f:
        d = json.load(f)
    d.update(kw)
    with open(path, "w") as f:
        json.dump(d, f)


@pytest.fixture(scope="module")
def mini(tmp_path_factory):
    """A checkout whose cells are cut to test size."""
    import jax

    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    root = tmp_path_factory.mktemp("mini")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    b = root / "bench"
    _edit(b / "configs" / "youtube-rmat16.json", vertices=2048,
          undirected_edges=5000)
    yield str(root), spec.benchmark(str(root))
    for k, v in saved.items():
        jax.config.update(k, v)


def _run(mini, cell, seconds=2.0):
    root, bench = mini
    return run.run_cell(bench, cell, 11, seconds, False,
                        t_start=time.perf_counter(), device_check=False,
                        root=root)


@pytest.mark.parametrize("cell", [CLOSED])
def test_control_is_not_correct_at_the_cells_limits(cell, tmp_path):
    correct, compared, short = control.control_readings(
        spec.benchmark(ROOT), cell, 3, root=_small_root(tmp_path))
    assert not correct
    assert compared["q_gap"]["value"] > compared["q_gap"]["limit"]
    assert short["q_short.seeded"] is not None


def _small_root(tmp_path):
    """The real limits, on inputs small enough for a test."""
    root = str(tmp_path)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    b = os.path.join(root, "bench")
    _edit(os.path.join(b, "configs", "youtube-rmat16.json"), vertices=4096,
          undirected_edges=10000)
    return root


def test_sound_closed_run_is_correct(mini):
    res = _run(mini, CLOSED)
    assert res["correct"], res["compared"]
    assert res["metrics"]["solve_s"]["value"] > 0


def _altered(labels):
    lab = np.array(labels)
    n = lab.size
    idx = np.random.default_rng(0).choice(n, size=max(1, n // 4),
                                          replace=False)
    lab[idx] = lab[np.roll(idx, 1)]
    return lab


def test_closed_answer_altered_is_not_correct(mini, monkeypatch):
    lv = importlib.import_module("repro.core.louvain")

    orig = lv.louvain

    def broken(g, *a, **k):
        res = orig(g, *a, **k)
        res.labels = _altered(res.labels)
        return res

    monkeypatch.setattr(lv, "louvain", broken)
    res = _run(mini, CLOSED)
    assert not res["correct"]
    assert res["compared"]["q_gap"]["value"] > res["compared"]["q_gap"]["limit"]


def test_closed_state_unchanged_is_not_correct(mini, monkeypatch):
    lv = importlib.import_module("repro.core.louvain")

    orig = lv.louvain

    def unchanged(g, *a, **k):
        res = orig(g, *a, **k)
        res.labels = np.arange(res.labels.size)
        res.n_communities = res.labels.size
        return res

    monkeypatch.setattr(lv, "louvain", unchanged)
    res = _run(mini, CLOSED)
    assert not res["correct"]
    assert res["compared"]["q_gap"]["value"] > res["compared"]["q_gap"]["limit"]


def test_closed_answer_altered_off_the_timed_graphs_is_not_correct(
        mini, monkeypatch):
    """A fault that shows only on data other than the timed graphs is
    caught on the graph drawn from the seed."""
    from bench import graphs

    bd = importlib.import_module("repro.graph.builders")
    lv = importlib.import_module("repro.core.louvain")
    root, bench = mini
    cfg = spec.config(bench, "youtube-rmat16", root)
    traffic = spec.traffic("louvain", os.path.join(root, "bench"))
    timed = {v.tobytes() for _, v, _ in
             graphs.closed_order(cfg, traffic, 11)}
    source = {}
    orig_build, orig_louvain = bd.from_numpy_edges, lv.louvain

    def build(u, v, *a, **k):
        g = orig_build(u, v, *a, **k)
        source[id(g)] = np.asarray(v).tobytes()
        return g

    def broken(g, *a, **k):
        res = orig_louvain(g, *a, **k)
        if source.get(id(g)) not in timed:
            res.labels = _altered(res.labels)
        return res

    monkeypatch.setattr(bd, "from_numpy_edges", build)
    monkeypatch.setattr(lv, "louvain", broken)
    res = _run(mini, CLOSED)
    assert not res["correct"]
    assert res["compared"]["q_gap"]["value"] > res["compared"]["q_gap"]["limit"]
