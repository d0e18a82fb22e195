#!/usr/bin/env python3
"""Readings that the correctness limits are set from, on the chip.

    python3 bench/control.py --workload <cell> --seconds <s> \
        --program-seeds <n> ... --control-seeds <n> ...

For each program seed, one whole run of the cell (``run.run_cell``, end-
to-end metrics, every answer judged) in this one process, so that set-up
compiles once.  For each control seed, the control: the plain reference
computed in bfloat16 (every stored value and every sum, see
``reference/louvain.py``) put in the program's place on the same inputs a
run would judge (the timed graphs and the graph drawn from the seed), and
judged the same way.  One JSON line per run: each number a run compares,
and under ``readings`` the widest shortfall of the labels' modularity
below the float64 reference Louvain's, on the timed graphs and on the
seeded one (read, not compared: PERF.md says why).  The limits in
``limits/<cell>.json`` lie between the largest program reading and the
smallest control reading; the control must come out not correct.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
os.environ.setdefault("TPU_LOG_DIR", "disabled")
if sys.path and os.path.abspath(sys.path[0]) == HERE:
    sys.path.pop(0)
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

import ml_dtypes  # noqa: E402
import numpy as np  # noqa: E402

from bench import check, device, graphs, spec  # noqa: E402


_REF_Q: dict = {}


def shortfalls(ref, params: dict, gs, answers) -> dict:
    """The widest shortfall of the answers' modularity below the float64
    reference Louvain's, over the timed graphs and on the graph drawn from
    the seed (the last of ``gs``)."""
    out = {"q_short.timed": 0.0, "q_short.seeded": None}
    for gi, labels in answers:
        u, v, n = gs[gi]
        src, dst, w = check.symmetric(u, v)
        key = (n, u.tobytes(), v.tobytes())
        if key not in _REF_Q:
            _REF_Q[key] = ref.solve(src, dst, w, n, **params)[1]
        short = _REF_Q[key] - ref.modularity(src, dst, w,
                                             np.asarray(labels, np.int64))
        if gi == len(gs) - 1:
            out["q_short.seeded"] = short
        else:
            out["q_short.timed"] = max(out["q_short.timed"], short)
    return out


def control_readings(bench: dict, name: str, seed: int,
                     root: str = ROOT) -> tuple[bool, dict, dict]:
    """``(correct, compared, shortfalls)`` of the control on seed
    ``seed``'s inputs."""
    base = os.path.join(root, "bench")
    cell = spec.workload(bench, name)
    cfg = spec.config(bench, cell["config"], root)
    traffic = spec.traffic(cell["traffic"], base)
    gs = graphs.closed_order(cfg, traffic, seed, base)
    gs.append(graphs.check_graph(cfg, seed, base))
    ref = spec.reference(traffic["algorithm"], base)
    judge = check.Judge(ref, gs)
    answers = []
    for gi, (u, v, n) in enumerate(gs):
        labels, q = ref.solve(*check.symmetric(u, v), n,
                              dtype=ml_dtypes.bfloat16,
                              **traffic["reference"])
        judge.judge(gi, labels, q, int(labels.max()) + 1)
        answers.append((gi, labels))
    correct, compared = check.verdict(dict(judge.readings, unanswered=0),
                                      spec.limits(name, base))
    return correct, compared, shortfalls(ref, traffic["reference"], gs,
                                         answers)


def main(argv=None) -> int:
    from bench import run

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--program-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    a = ap.parse_args(argv)
    bench = spec.benchmark()
    try:
        device.require_tpu(spec.workload(bench, a.workload)["chips"])
    except device.NoAccelerator as err:
        print(f"FAIL: {err}", file=sys.stderr)
        return 2
    traffic = spec.traffic(spec.workload(bench, a.workload)["traffic"])
    ref = spec.reference(traffic["algorithm"])
    for seed in a.program_seeds:
        seen = {}

        def keep(gs, answers):
            seen.update(gs=gs, answers=[(x.graph, x.labels)
                                        for x in answers])

        res = run.run_cell(bench, a.workload, seed, a.seconds, False,
                           t_start=time.perf_counter(), on_answers=keep)
        print(json.dumps({"who": "program", "seed": seed,
                          "correct": res["correct"],
                          "metrics": res["metrics"],
                          "compared": res["compared"],
                          "readings": shortfalls(ref, traffic["reference"],
                                                 seen["gs"],
                                                 seen["answers"])}),
              flush=True)
    for seed in a.control_seeds:
        correct, compared, short = control_readings(bench, a.workload, seed)
        print(json.dumps({"who": "control", "seed": seed,
                          "correct": correct, "compared": compared,
                          "readings": short}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
