"""``BENCHMARK.json`` and the files it names, found by name.

A cell (``workloads`` entry) names a configuration and a traffic mix.  The
configuration's file is given in ``configs``, and its ``kind`` names the
generator ``generators/<kind>.py``; the traffic mix is
``traffic/<traffic>.json``, its ``loop`` names the loop ``cells/<loop>.py``
and its ``call`` (by default its ``algorithm``) the program entry
``entries/<call>.py``; its ``algorithm`` names the plain reference
``reference/<algorithm>.py``; the cell's correctness limits are
``limits/<cell>.json``; a per-layer metric's reader is
``metrics/<metric>.py``.  Adding a cell, a mix, a deployment or a metric
adds files and entries and edits none.
"""
from __future__ import annotations

import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in bench['workloads']]}")


def config(bench: dict, name: str, root: str = ROOT) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return load_json(os.path.join(root, c["file"]))
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def traffic(name: str, base: str = BENCH) -> dict:
    return load_json(os.path.join(base, "traffic", f"{name}.json"))


def limits(cell: str, base: str = BENCH) -> dict:
    return load_json(os.path.join(base, "limits", f"{cell}.json"))["limits"]


def metrics_of(bench: dict, cell: str, kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries the cell reports: those
    whose ``workloads`` list names it and, without a list, every
    end-to-end metric and every per-layer one whose ``moves`` it reports."""
    def listed(m):
        return "workloads" not in m or cell in m["workloads"]

    if kind == "end_to_end":
        return [m for m in bench[kind] if listed(m)]
    e2e = {m["name"] for m in metrics_of(bench, cell, "end_to_end")}
    return [m for m in bench[kind] if m["moves"] in e2e and listed(m)]


def module(directory: str, name: str, base: str = BENCH):
    """``<base>/<directory>/<name>.py``, loaded anew; a name with no file
    is an error that names the file looked for."""
    path = os.path.join(base, directory, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {directory} {name!r}: {path} does not "
                                f"exist")
    spec = importlib.util.spec_from_file_location(
        f"bench_{directory}_" + name.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str, base: str = BENCH):
    """``read(run)`` of ``metrics/<metric>.py``."""
    return module("metrics", metric, base).read


def generator(kind: str, base: str = BENCH):
    """``graph(cfg, rng) -> (u, v, n)`` of ``generators/<kind>.py``."""
    return module("generators", kind, base).graph


def loop(name: str, base: str = BENCH):
    """``run(cfg, traffic, seed, seconds, window, log)`` of
    ``cells/<name>.py``: the dict ``run.run_cell`` takes."""
    return module("cells", name, base).run


def entry(traffic: dict, base: str = BENCH):
    """``entries/<call>.py`` of a traffic mix, ``call`` defaulting to its
    ``algorithm``: ``ingest(u, v, n)`` and ``solve(g)``."""
    return module("entries", traffic.get("call", traffic["algorithm"]), base)


def reference(algorithm: str, base: str = BENCH):
    """The plain reference module ``reference/<algorithm>.py``."""
    return module("reference", algorithm, base)
