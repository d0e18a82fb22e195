"""Window arithmetic, discovery of files by name, the shape of
BENCHMARK.json, and the refusal to run without a TPU."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import device, loops, spec, stats

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


def _fake_closed(monkeypatch, durations):
    """``loops.closed`` on a clock that advances by each graph's solve
    time; returns the graphs it solved, in order."""
    clock = {"t": 0.0}
    solved = []

    def solve_once(algorithm, gi, u, v, n):
        clock["t"] += durations[u]
        solved.append(u)
        return 0.0, loops.Answer(gi, None, None, None)

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
    counts = set()
    for order in (graphs_, graphs_[::-1], graphs_[1:] + graphs_[:1]):
        solved = _fake_closed(monkeypatch, durations)
        out = loops.closed(order, "louvain", 20.0)
        win, n = stats.solve_window([s.end for s in out], 20.0, len(order))
        assert n == len(out) and n % len(order) == 0 and win >= 20.0
        assert solved[:n].count("a") == solved[:n].count("b") \
            == solved[:n].count("c")
        assert win == pytest.approx(n / 3 * sum(durations.values()))
        counts.add(n)
    assert len(counts) == 1     # the same number of solves in every order


# ------------------------------------------------------------ discovery


def test_every_cell_finds_its_files(bench):
    for w in bench["workloads"]:
        assert spec.config(bench, w["config"], ROOT)["name"] == w["config"]
        t = spec.traffic(w["traffic"])
        assert t["loop"] == "closed"
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
