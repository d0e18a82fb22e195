#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip it is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  The cell (``BENCHMARK.json`` ``workloads``)
names a configuration (a graph deployment) and a traffic mix; inputs are
made on the host, the timed graphs from the configuration and in an order
drawn from ``--seed``.  Set-up (inputs, ingest and compiling or loading
every program the cell's traffic uses) runs first and is ``setup_s``;
then the window measures for ``--seconds`` (to the end of the first whole
round of the cell's graphs at or after it); then a graph drawn from the
seed is solved the same way, and the plain reference judges every
answer.  ``--trace 0`` reports the cell's end-to-end metrics, ``--trace
1`` records the window with the profiler and reports its per-layer
metrics.

Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for.  Standard error ends with each number compared
beside its limit; the last line of standard output is the result object.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no logs under /tmp
if sys.path and os.path.abspath(sys.path[0]) == HERE:
    sys.path.pop(0)   # bench/trace.py must not shadow the standard library
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import check, device, spec  # noqa: E402
from bench import trace as tracing  # noqa: E402

# inside the checkout, at fixed paths: the cache directory is part of
# every cache key
CACHE = os.path.join(".bench_cache", "jax")
TRACE_DIR = os.path.join(".bench_cache", "trace")


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def _top(seconds: dict, n: int | None = None) -> list:
    return sorted(seconds.items(), key=lambda kv: -kv[1])[:n]


@dataclasses.dataclass
class Run:
    """What a per-layer reader (``metrics/<name>.py``) may read."""

    solves: list | None          # [loops.Solve] in the window
    summary: tracing.Summary     # the traced window


# ------------------------------------------------------------ one run


def run_cell(bench: dict, name: str, seed: int, seconds: float,
             trace: bool, *, t_start: float = T_START,
             device_check: bool = True, root: str = ROOT,
             on_answers=None) -> dict:
    """One run of cell ``name`` of the checkout at ``root``; returns the
    result object.  ``on_answers(graphs, answers)``, where given, sees
    what the reference judges (``control.py`` reads it)."""
    base = os.path.join(root, "bench")
    cache = os.path.join(root, CACHE)
    cell = spec.workload(bench, name)
    cfg = spec.config(bench, cell["config"], root)
    traffic = spec.traffic(cell["traffic"], base)
    lim = spec.limits(name, base)

    if device_check:
        dev = device.require_tpu(cell["chips"])
    else:
        import jax

        d = jax.devices()
        dev = {"platform": d[0].platform, "kind": d[0].device_kind,
               "count": len(d)}
    log(f"device: platform={dev['platform']} device_kind={dev['kind']} "
        f"count={dev['count']}")
    device.use_compile_cache(cache)
    counter = device.CompileCounter()
    entries0 = device.cache_entries(cache)

    marks = {}
    trace_file: list = []

    @contextlib.contextmanager
    def window():
        marks["setup"] = counter.snapshot()
        with contextlib.ExitStack() as stack:
            if trace:
                trace_file.append(stack.enter_context(
                    tracing.capture(os.path.join(root, TRACE_DIR))))
            stack.enter_context(tracing.span(tracing.WINDOW))
            marks["t0"] = time.perf_counter()
            yield
        marks["window"] = counter.snapshot()

    try:
        out = spec.loop(traffic["loop"], base)(cfg, traffic, seed, seconds,
                                               window, log)
    finally:
        counter.close()
    setup_s = marks["t0"] - t_start
    in_window = device.CompileCounter.since(marks["setup"], marks["window"])
    peak = device.peak_bytes(cell["chips"])
    log(f"setup_s={setup_s!r}; set-up: {marks['setup']} (jaxprs traced, "
        f"executables built, of those loaded from the persistent cache); "
        f"in the window: {in_window}; cache entries {entries0} -> "
        f"{device.cache_entries(cache)}; memory_peak_bytes={peak}")
    if any(in_window.values()):
        log(f"NOTE: programs were traced or compiled inside the window: "
            f"{in_window}")

    dev_out = dict(dev, memory_peak_bytes=peak)
    result = {}
    if trace:
        run = Run(out.get("solves"),
                  tracing.summarize(tracing.load(trace_file[0][0])))
        dev_out["busy_s"] = run.summary.busy_s
        dev_out["window_s"] = run.summary.window_s
        values = {m["name"]: (spec.reader(m["name"], base)(run), m["unit"])
                  for m in spec.metrics_of(bench, name, "per_layer")}
        result["breakdown"] = tracing.breakdown(run.summary)
        s = run.summary
        log(f"trace: {trace_file[0][0]}; busy_s={s.busy_s!r} "
            f"window_s={s.window_s!r} devices={s.devices}; device time by "
            f"class (s): {_top(s.by_class, 12)}; by scope (s): "
            f"{_top(s.by_scope)}; repro.* spans in the window (s): "
            f"{_top(s.span_s)}; idle time by span (s): {s.gaps[:10]}")
        del run, s
    else:
        values = {"setup_s": (setup_s, "s")}
        for m in spec.metrics_of(bench, name, "end_to_end"):
            if m["name"] in out["metrics"]:
                values[m["name"]] = (out["metrics"][m["name"]], m["unit"])

    # the program's state goes before the reference runs
    out.pop("solves", None)
    gc.collect()

    if on_answers:
        on_answers(out["graphs"], out["answers"])
    judge = check.Judge(spec.reference(traffic["algorithm"], base),
                        out["graphs"])
    for a in out["answers"]:
        judge.judge(a.graph, a.labels, a.modularity, a.n_communities)
    readings = dict(judge.readings, unanswered=out["unanswered"])
    correct, compared = check.verdict(readings, lim)
    log(f"judged {readings['answers']} answers against the reference")
    for k, c in compared.items():
        log(f"check {k}: {c['value']!r} (limit {c['limit']!r})")

    metrics = {k: {"value": v, "unit": u} for k, (v, u) in values.items()
               if v is not None}
    return dict({"correct": correct, "attempted": out["attempted"],
                 "failed": out["failed"], "metrics": metrics,
                 "device": dev_out}, **result, compared=compared)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    try:
        res = run_cell(spec.benchmark(), a.workload, a.seed, a.seconds,
                       bool(a.trace))
    except device.NoAccelerator as err:
        log(f"FAIL: {err}")
        return 2
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.exit(main())
