#!/usr/bin/env python3
"""Chip smoke test: the main path once, on one TPU chip, with its answers checked.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # distributed_louvain on a 4-chip mesh

One chip: com-youtube's stand-in (Table I; R-MAT, edge factor 3) cut to
1/8 of the paper's scale — R-MAT scale 18: 262,144 vertices, 762,155
undirected edges, the size of Table I's com-amazon and com-dblp — seed 0,
through ``louvain()`` and ``plp()`` with their default configurations and
with ``backend="pallas"``, each run cold then warm.  The cut is forced by
the run's 1200 s limit: at full scale (2,097,152 vertices) one v5e took
436 s for a warm default ``louvain()`` and 1433 s for a cold pallas one.
Every run must come back with no degradation, retry or ``ladder.*``
count, and with a modularity that a float64 numpy recomputation from the
returned labels and the input edges matches within 1e-4.  Pallas labels
are compared with the default (segment) labels; a difference is printed as
a finding.  Then a ``CommunityServeEngine`` serves 32 ego-net stand-ins
(the ``batch_serve`` benchmark's workload); every response must be ok and
equal to an unbatched run of the same graph.

``--four-chips``: only ``distributed_louvain`` (shard-local coarsening,
default halo cap) on a 4-device mesh against the single-device
``louvain()`` in the same process, once each; labels, modularity and level
count must be identical, the run must not degrade to replicated
coarsening, and the edge shards the run used must sit on all four
devices.

Wall times printed here are smoke timings, not benchmark numbers.  Exits
non-zero on any failure, and when JAX finds no TPU.  The last line of
standard output is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import concurrent.futures
import json
import os
import sys
import time
import traceback

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
DATASET, SCALE, SEED = "com-youtube", 0.125, 0
Q_TOL = 1e-4
N_EGONETS = 32


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def numpy_modularity(src, dst, w, labels) -> float:
    """Newman–Girvan Q in float64 on the host, on the directed-symmetric
    edge list (``core/modularity.py``'s convention)."""
    w = np.asarray(w, np.float64)
    lab = np.asarray(labels, np.int64)
    vol = w.sum()
    if vol == 0:
        return 0.0
    w_in = w[lab[src] == lab[dst]].sum()
    deg = np.bincount(src, weights=w, minlength=lab.shape[0])
    vol_c = np.bincount(lab, weights=deg)
    return float(w_in / vol - np.sum((vol_c / vol) ** 2))


def peak_bytes() -> int | None:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _timed(fn):
    t0 = time.perf_counter()
    res = fn()
    return res, time.perf_counter() - t0


def _ladder_counts() -> dict:
    from repro.utils import telemetry

    return {k: v for k, v in telemetry.snapshot().items()
            if k.startswith("ladder.") and v}


def run_algorithm(name: str, fn, edges, modularity_of) -> np.ndarray:
    """Run ``fn`` cold then warm; check both runs; return the labels."""
    from repro.utils import telemetry

    src, dst, w = edges
    labels = None
    for phase in ("cold", "warm"):
        telemetry.reset()
        res, dt = _timed(fn)
        lab = np.asarray(res.labels)
        q_dev = float(modularity_of(res))
        q_np = numpy_modularity(src, dst, w, lab)
        rep = res.run_report
        print(f"{name} {phase}: smoke timing (not a benchmark) "
              f"{dt:.3f} s, peak_bytes_in_use={peak_bytes()}, "
              f"communities={len(np.unique(lab))}, Q={q_dev:.6f}, "
              f"numpy Q={q_np:.6f}, degradations={rep.degradations}, "
              f"retries={rep.retries}, warnings={rep.warnings}, "
              f"ladder={_ladder_counts()}", flush=True)
        check(not rep.degradations, f"{name} {phase}: degraded")
        check(not rep.retries, f"{name} {phase}: retried")
        check(not _ladder_counts(), f"{name} {phase}: ladder counters")
        check(abs(q_dev - q_np) <= Q_TOL,
              f"{name} {phase}: Q {q_dev} vs numpy {q_np}")
        if labels is not None:
            check(np.array_equal(labels, lab),
                  f"{name}: warm labels differ from cold")
        labels = lab
    return labels


def report_parity(name: str, ref: np.ndarray, got: np.ndarray) -> bool:
    same = np.array_equal(ref, got)
    if same:
        print(f"parity {name}: pallas labels identical to segment", flush=True)
    else:
        diff = np.flatnonzero(ref != got)
        print(f"parity {name}: FINDING pallas labels differ from segment at "
              f"{diff.size} vertices (first {diff[:8].tolist()})", flush=True)
    return same


def one_chip_phases() -> list:
    """(name, thunk) for each com-youtube run, then the parity report."""
    import jax.numpy as jnp

    from repro.core.louvain import LouvainConfig, louvain
    from repro.core.modularity import modularity
    from repro.core.plp import PLPConfig, plp
    from repro.graph import datasets

    lg, dt = _timed(lambda: datasets.load(DATASET, seed=SEED, scale=SCALE))
    g = lg.graph
    edges = g.to_numpy_edges()
    print(f"{DATASET} scale={SCALE} seed={SEED}: V={g.n_max} "
          f"directed edges={int(g.m_valid)} (built in {dt:.3f} s)", flush=True)

    def q_plp(res):
        return modularity(g, jnp.asarray(res.labels))

    def q_louvain(res):
        return res.modularity

    labels = {}
    runs = {
        "louvain": (lambda: louvain(g, LouvainConfig()), q_louvain),
        "louvain[pallas]": (lambda: louvain(g, LouvainConfig(backend="pallas")),
                            q_louvain),
        "plp": (lambda: plp(g, PLPConfig()), q_plp),
        "plp[pallas]": (lambda: plp(g, PLPConfig(backend="pallas")), q_plp),
    }

    def run(name):
        fn, q_of = runs[name]
        labels[name] = run_algorithm(name, fn, edges, q_of)

    def parity():
        for algo in ("louvain", "plp"):
            check(algo in labels and f"{algo}[pallas]" in labels,
                  f"parity {algo}: a run failed")
            report_parity(algo, labels[algo], labels[f"{algo}[pallas]"])

    return ([(name, lambda name=name: run(name)) for name in runs]
            + [("parity", parity)])


def serve_phase() -> None:
    from benchmarks.perf_variants import egonet_edges
    from launch.community_serve import CommunityRequest, CommunityServeEngine
    from repro.core.louvain import louvain
    from repro.core.plp import plp
    from repro.graph.builders import from_numpy_edges
    from repro.kernels.common import capacity_signature

    work = egonet_edges(N_EGONETS, SEED)
    eng = CommunityServeEngine()
    algos = {}
    for i, (u, v, n) in enumerate(work):
        algo = "plp" if i % 2 else "louvain"
        algos[f"ego-{i}"] = algo
        shed = eng.submit(CommunityRequest(request_id=f"ego-{i}", u=u, v=v,
                                           n=n, algo=algo))
        check(shed is None, f"ego-{i} shed at submit: {shed}")
    resp, dt = _timed(eng.flush)
    n_ok = sum(r.ok for r in resp)
    print(f"serve: {len(resp)} responses, {n_ok} ok, smoke timing (not a "
          f"benchmark) {dt:.3f} s, dispatches={eng.stats()['dispatches']}",
          flush=True)
    check(len(resp) == len(work), "serve: missing responses")
    for i, ((u, v, n), r) in enumerate(zip(work, resp)):
        check(r.request_id == f"ego-{i}", "serve: responses out of order")
        check(r.ok, f"{r.request_id}: {r.error}")
        single = louvain if algos[r.request_id] == "louvain" else plp
        # edge capacity padded to the serve bucket's, so the references
        # share a few compiled programs; padding never changes labels
        m_cap = capacity_signature(n, from_numpy_edges(u, v, n=n).m_max).m_cap
        ref = single(from_numpy_edges(u, v, n=n, m_max=m_cap)).labels
        check(np.array_equal(np.asarray(r.labels), np.asarray(ref)),
              f"{r.request_id}: served labels differ from unbatched "
              f"{algos[r.request_id]}()")
    print("serve: every response ok and equal to its unbatched run",
          flush=True)


def four_chip_phase() -> None:
    import jax
    from jax.sharding import Mesh

    from repro.core.distributed import distributed_louvain
    from repro.core.louvain import LouvainConfig, louvain
    from repro.graph import datasets

    devs = jax.devices()
    check(len(devs) >= 4, f"--four-chips needs 4 devices, found {len(devs)}")
    mesh = Mesh(np.array(devs[:4]), ("data",))
    lg = datasets.load(DATASET, seed=SEED, scale=SCALE)
    g = lg.graph
    edges = g.to_numpy_edges()
    print(f"{DATASET} scale={SCALE} seed={SEED}: V={g.n_max} "
          f"directed edges={int(g.m_valid)}", flush=True)

    # the single-device reference runs in a second thread, so its
    # compilation overlaps the distributed one (compiling dominates a cold
    # run); the two timings below therefore share the host
    with concurrent.futures.ThreadPoolExecutor(1) as pool:
        ref = pool.submit(_timed, lambda: louvain(g, LouvainConfig()))
        rd, dt = _timed(lambda: distributed_louvain(
            g, mesh, coarsening="shard_local"))
        cs = rd.comm_stats
        print(f"distributed_louvain cold: smoke timing (not a benchmark) "
              f"{dt:.3f} s, levels={rd.levels}, Q={rd.modularity:.6f}, "
              f"coarsening={rd.coarsening}, halo_cap={cs['halo_cap']}, "
              f"m_pad={cs['m_pad']}, gathered_groups_per_level="
              f"{cs['gathered_groups_per_level']}, shard_devices="
              f"{cs['shard_devices']}, degradations="
              f"{rd.run_report.degradations}", flush=True)
        rl, dt_ref = ref.result()
    print(f"louvain (one device, cold): smoke timing (not a benchmark) "
          f"{dt_ref:.3f} s, levels={rl.levels}, Q={rl.modularity:.6f}",
          flush=True)
    q_np = numpy_modularity(*edges, rd.labels)
    print(f"numpy Q={q_np:.6f}", flush=True)

    # every check is reported, so one run shows every fault
    failed = []

    def expect(cond: bool, what: str) -> None:
        if not cond:
            failed.append(what)
            print(f"FAIL: {what}", flush=True)

    expect(cs["shard_devices"] == sorted(d.id for d in devs[:4]),
           f"edge shards on devices {cs['shard_devices']}, not all four")
    expect(not rd.run_report.degradations,
           f"distributed run degraded: {rd.run_report.degradations}")
    expect(rd.coarsening == "shard_local", f"coarsening={rd.coarsening}")
    same = np.array_equal(rd.labels, np.asarray(rl.labels))
    if not same:
        diff = np.flatnonzero(rd.labels != np.asarray(rl.labels))
        print(f"labels differ at {diff.size} vertices "
              f"(first {diff[:8].tolist()})", flush=True)
    expect(same, "distributed labels differ from louvain()")
    expect(rd.modularity == rl.modularity,
           f"Q {rd.modularity} vs {rl.modularity}")
    expect(rd.levels == rl.levels, f"levels {rd.levels} vs {rl.levels}")
    expect(abs(rd.modularity - q_np) <= Q_TOL, f"Q vs numpy Q {q_np}")
    check(not failed, "; ".join(failed))
    print("distributed_louvain == louvain(): labels, Q and levels identical",
          flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only distributed_louvain on a 4-chip mesh "
                         "against louvain()")
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(f"platform={device['platform']} device_kind={device['kind']} "
          f"devices={device['count']}", flush=True)
    if device["platform"] != "tpu":
        print("FAIL: JAX finds no TPU", file=sys.stderr)
        return 2

    sys.path.insert(0, os.path.join(REPO, "src"))
    from repro.utils.compile_cache import use_compile_cache

    print(f"compile cache: {use_compile_cache()}", flush=True)
    if args.four_chips:
        phases = [("four_chips", four_chip_phase)]
    else:
        phases = one_chip_phases() + [("serve", serve_phase)]
    failed = []
    for name, fn in phases:
        # every phase runs, so one call shows every fault; any fails the smoke
        try:
            fn()
        except Exception as err:  # noqa: BLE001 — reported, then fails
            failed.append(name)
            traceback.print_exc()
            print(f"FAIL {name}: {type(err).__name__}: {err}", flush=True)
    if failed:
        print(f"FAIL: phases {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
