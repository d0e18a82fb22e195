"""Window arithmetic, discovery of files by name, the shape of
BENCHMARK.json, and the refusal to run without a TPU."""
import contextlib
import hashlib
import json
import os
import re
import shutil
import subprocess
import sys
import time

import pytest

from bench import device, graphs, loops, run, spec, stats

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.fixture(scope="module")
def bench():
    return spec.benchmark(ROOT)


# ------------------------------------------------------------ window rules


@pytest.mark.parametrize("ends, seconds, per_round, expect", [
    ([3.0, 6.0, 9.0, 12.0], 10.0, 1, (12.0, 4)),   # rounds of one solve
    ([3.0, 6.0, 10.0, 12.0], 10.0, 1, (10.0, 3)),  # an end exactly at the close
    ([11.0], 10.0, 1, (11.0, 1)),                   # one solve longer than it
    ([3.0, 6.0], 10.0, 1, None),                    # none reached it
    # rounds of two: a solve past the close ends no round, the round does
    ([3.0, 6.0, 9.0, 12.0], 8.0, 2, (12.0, 4)),
    ([3.0, 6.0, 9.0, 12.0], 6.0, 2, (6.0, 2)),
    ([3.0, 6.0, 9.0], 8.0, 2, None),                # the round is not whole
    ([4.0, 8.0, 12.0, 16.0, 20.0, 24.0], 13.0, 3, (24.0, 6)),
])
def test_solve_window(ends, seconds, per_round, expect):
    assert stats.solve_window(ends, seconds, per_round) == expect


def test_solve_s_is_window_over_whole_solves():
    win, n = stats.solve_window([4.0, 8.5, 12.5], 10.0, 1)
    assert win / n == pytest.approx(12.5 / 3)


def _fake_closed(monkeypatch, durations, order):
    """The closed loop (``cells/closed.py``) on ``order``, on a clock that
    advances by each graph's solve time; returns the graphs it solved, in
    order."""
    clock = {"t": 0.0}
    solved = []

    def solve_once(entry, gi, u, v, n):
        clock["t"] += durations[u]
        solved.append(u)
        return 0.0, loops.Answer(gi, None, None, None)

    monkeypatch.setattr(graphs, "closed_order", lambda *a: list(order))
    monkeypatch.setattr(graphs, "check_graph", lambda *a: order[0])
    monkeypatch.setattr(loops, "solve_once", solve_once)
    monkeypatch.setattr(loops.time, "perf_counter", lambda: clock["t"])
    return solved


@pytest.mark.parametrize("speed", [0.5, 0.93, 1.0, 1.07, 1.6, 3.0])
def test_closed_loop_solves_each_graph_equally_often_at_any_speed(
        monkeypatch, speed):
    """Whatever the speed and the seed's order, the window holds whole
    rounds: every graph equally often, so every seed does the same work."""
    base = {"a": 10.28, "b": 10.69, "c": 9.78}
    durations = {k: t / speed for k, t in base.items()}
    graphs_ = [(k, None, 0) for k in base]
    traffic = {"loop": "closed", "algorithm": "louvain", "graphs": 3}
    counts = set()
    for order in (graphs_, graphs_[::-1], graphs_[1:] + graphs_[:1]):
        solved = _fake_closed(monkeypatch, durations, order)
        out = spec.loop("closed")({}, traffic, 1, 20.0,
                                  contextlib.nullcontext, lambda *a: None)
        n = out["attempted"]
        win = out["metrics"]["solve_s"] * n
        # the warm-up solve, the window's whole rounds, the seeded graph
        assert len(solved) == 1 + n + 1 and len(out["answers"]) == n + 1
        assert n == len(out["solves"]) and n % len(order) == 0
        assert win >= 20.0
        window = solved[1:n + 1]
        assert window.count("a") == window.count("b") == window.count("c")
        assert win == pytest.approx(n / 3 * sum(durations.values()))
        counts.add(n)
    assert len(counts) == 1     # the same number of solves in every order


# ------------------------------------------------------------ discovery


def test_every_cell_finds_its_files(bench):
    for w in bench["workloads"]:
        cfg = spec.config(bench, w["config"], ROOT)
        assert cfg["name"] == w["config"]
        assert callable(spec.generator(cfg["kind"]))
        t = spec.traffic(w["traffic"])
        assert callable(spec.loop(t["loop"]))
        e = spec.entry(t)
        assert callable(e.ingest) and callable(e.solve)
        assert callable(spec.reference(t["algorithm"]).modularity)
        lim = spec.limits(w["name"])
        assert set(lim) == {"unanswered", "bad_partitions", "q_gap"}
        for m in spec.metrics_of(bench, w["name"], "per_layer"):
            assert callable(spec.reader(m["name"]))


def test_a_new_config_traffic_and_metric_are_found_by_name(tmp_path, bench):
    """A later cell adds files and entries and edits none."""
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    cfg = spec.load_json(os.path.join(ROOT, "bench", "configs",
                                      "youtube-rmat16.json"))
    cfg.update(name="throwaway", vertices=1024, undirected_edges=2000)
    (tmp_path / "bench" / "configs" / "throwaway.json").write_text(
        json.dumps(cfg))
    (tmp_path / "bench" / "traffic" / "plp.json").write_text(json.dumps(
        {"loop": "closed", "algorithm": "plp", "graphs": 1,
         "reference": {}}))
    (tmp_path / "bench" / "metrics" / "plp.iterations.py").write_text(
        "def read(run):\n    return 7.0\n")
    b = dict(bench)
    b["configs"] = bench["configs"] + [
        {"name": "throwaway", "source": "x",
         "file": "bench/configs/throwaway.json", "reduced": [], "why": "x"}]
    b["workloads"] = bench["workloads"] + [
        {"name": "throwaway.plp", "config": "throwaway", "traffic": "plp",
         "chips": 1, "why": "x"}]
    b["end_to_end"] = [dict(m, workloads=m["workloads"] + ["throwaway.plp"])
                       if m["name"] == "solve_s" else m
                       for m in bench["end_to_end"]]
    b["per_layer"] = bench["per_layer"] + [
        {"name": "plp.iterations", "unit": "iterations", "better": "lower",
         "source": "program_counter", "layer": "x", "moves": "solve_s",
         "workloads": ["throwaway.plp"]}]
    base = str(tmp_path / "bench")
    w = spec.workload(b, "throwaway.plp")
    assert spec.config(b, w["config"], str(tmp_path))["vertices"] == 1024
    assert spec.traffic(w["traffic"], base)["algorithm"] == "plp"
    names = [m["name"] for m in spec.metrics_of(b, "throwaway.plp",
                                                "per_layer")]
    assert names == ["plp.iterations"]
    assert spec.reader("plp.iterations", base)(None) == 7.0
    with pytest.raises(KeyError):
        spec.workload(b, "missing.cell")


# A deployment that only adds files: a generator kind, a loop, a program
# entry, a reader and a reference, each found by the name its data gives.
NEW_FILES = {
    "generators/cliques.py": '''"""A ring of ``cliques`` cliques of ``size`` vertices, each joined to the
next by one edge, numbered in an order drawn from ``rng``."""
import numpy as np


def graph(cfg, rng):
    k, s = int(cfg["cliques"]), int(cfg["size"])
    a, b = np.triu_indices(s, 1)
    u = np.concatenate([a + c * s for c in range(k)] + [np.arange(k) * s])
    v = np.concatenate([b + c * s for c in range(k)]
                       + [(np.arange(k) * s + s + 1) % (k * s)])
    p = rng.permutation(k * s)
    return np.minimum(p[u], p[v]), np.maximum(p[u], p[v]), k * s
''',
    "cells/one_round.py": '''"""One round: each graph solved once, in the seed's order."""
import os

from bench import graphs, loops, spec

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run(cfg, traffic, seed, seconds, window, log):
    entry = spec.entry(traffic, BENCH)
    gs = graphs.closed_order(cfg, traffic, seed, BENCH)
    loops.solve_once(entry, 0, *gs[0])
    with window():
        solves = loops.closed(gs, entry, 0.0)
    log(f"one round of {len(solves)} solves")
    return dict(graphs=gs, answers=[s.answer for s in solves],
                attempted=len(solves), failed=0, unanswered=0,
                metrics={"solve_s": solves[-1].end / len(solves)},
                solves=solves)
''',
    "entries/plp_few.py": '''"""``plp()`` with at most 20 iterations; PLP names a community by a
member's vertex id, so its labels are renumbered 0..k-1."""
import numpy as np


def ingest(u, v, n):
    from repro.graph.builders import from_numpy_edges

    return from_numpy_edges(u, v, n=n)


def solve(g):
    from repro.core.plp import PLPConfig, plp

    res = plp(g, PLPConfig(max_iterations=20))
    res.labels = np.unique(res.labels, return_inverse=True)[1]
    return res
''',
    "metrics/plp.iterations.py": '''def read(run):
    per = [s.answer.result.iterations for s in run.solves or ()]
    return sum(per) / len(per) if per else None
''',
    "reference/plp.py": '''import numpy as np


def modularity(src, dst, w, labels):
    vol = w.sum()
    deg = np.bincount(src, weights=w, minlength=labels.size)
    vol_c = np.bincount(labels, weights=deg, minlength=labels.size) / vol
    return float(w[labels[src] == labels[dst]].sum() / vol
                 - (vol_c ** 2).sum())
''',
    "configs/ring.json": json.dumps(
        {"name": "ring", "kind": "cliques", "cliques": 8, "size": 6,
         "graph_seed": 0}),
    "traffic/plp_few.json": json.dumps(
        {"loop": "one_round", "algorithm": "plp", "call": "plp_few",
         "graphs": 2}),
    "limits/ring.plp_few.json": json.dumps(
        {"limits": {"unanswered": 0, "bad_partitions": 0, "q_gap": 1e-5}}),
}


def _hashes(base) -> dict:
    return {os.path.relpath(os.path.join(d, f), base): hashlib.sha256(
        open(os.path.join(d, f), "rb").read()).hexdigest()
        for d, _, fs in os.walk(base) for f in fs}


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


def test_a_new_deployment_runs_from_new_files_alone(tmp_path, bench,
                                                    jax_config):
    """A generator kind, a loop, an entry, a reader and a reference, added
    as files to a copy of ``bench/``: the cell runs to a correct result and
    no file that was there changes."""
    base = tmp_path / "bench"
    shutil.copytree(os.path.join(ROOT, "bench"), base,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    before = _hashes(base)
    for rel, text in NEW_FILES.items():
        assert rel not in before
        (base / rel).write_text(text)
    b = dict(bench)
    b["configs"] = bench["configs"] + [
        {"name": "ring", "source": "x", "file": "bench/configs/ring.json",
         "reduced": [], "why": "x"}]
    b["workloads"] = bench["workloads"] + [
        {"name": "ring.plp_few", "config": "ring", "traffic": "plp_few",
         "chips": 1, "why": "x"}]
    b["end_to_end"] = [dict(m, workloads=m["workloads"] + ["ring.plp_few"])
                       if m["name"] == "solve_s" else m
                       for m in bench["end_to_end"]]
    b["per_layer"] = bench["per_layer"] + [
        {"name": "plp.iterations", "unit": "iterations", "better": "lower",
         "source": "program_counter", "layer": "x", "moves": "solve_s",
         "workloads": ["ring.plp_few"]}]
    results = [run.run_cell(b, "ring.plp_few", 2**31 + 5, 0.0, traced,
                            t_start=time.perf_counter(), device_check=False,
                            root=str(tmp_path)) for traced in (False, True)]
    for res in results:
        assert res["correct"], res["compared"]
        assert res["attempted"] == 2
    assert results[0]["metrics"]["solve_s"]["value"] > 0
    assert set(results[1]["metrics"]) == {"plp.iterations"}
    assert 1 <= results[1]["metrics"]["plp.iterations"]["value"] <= 20
    after = _hashes(base)
    assert {k: after[k] for k in before} == before


# ------------------------------------------------------------ BENCHMARK.json


def test_benchmark_json_shape(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "bench/run.py"]
    assert bench["paths"] == ["bench"]
    assert 1 <= bench["run_seconds"] <= 51
    configs = {c["name"] for c in bench["configs"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bench[k]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/")
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
    cells = set()
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        assert (w["config"], w["traffic"]) not in cells
        cells.add((w["config"], w["traffic"]))
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert "bound" not in m
        for w in m["workloads"]:
            moved = [x for x in bench["end_to_end"] if x["name"] == m["moves"]]
            assert w in moved[0].get("workloads", [w])
    for w in bench["workloads"]:
        reported = [m["name"] for m in spec.metrics_of(bench, w["name"],
                                                       "end_to_end")]
        assert "setup_s" in reported and len(reported) >= 2
        assert spec.metrics_of(bench, w["name"], "per_layer")


# ------------------------------------------------------------ no TPU, no run


def test_require_tpu_raises_on_the_cpu():
    import jax

    assert jax.devices()[0].platform == "cpu"
    with pytest.raises(device.NoAccelerator):
        device.require_tpu(1)


def test_unknown_device_kind_has_no_peaks():
    path = os.path.join(ROOT, "bench", "peaks.json")
    assert device.peaks("TPU v5 lite", path)["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        device.peaks("TPU v9 imaginary", path)


def test_run_exits_nonzero_with_no_result_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "youtube-rmat16.louvain", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert "JAX finds no TPU" in p.stderr
    assert '"correct"' not in p.stdout
