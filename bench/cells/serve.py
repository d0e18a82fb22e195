"""Serve loop: one caller, closed ticks over a pool of small graphs.

The configuration's generator gives the pool (``pool(cfg, rng)``, fixed by
``graph_seed``), cut in its order into ticks of ``traffic["tick"]``
requests; a run's seed orders the ticks.  Set-up serves one graph of each
capacity signature the pool holds (the entry's ``signature``), one flush
each and its time logged (on a cold cache, the time to compile that
signature's batch program), so every batch program the window uses exists
before it.  In the window the caller
builds each tick's requests (``entry.ingest``), submits them to one
engine (``entry.engine``) and flushes (``entry.solve``), tick after tick,
in whole rounds of the pool, until a round ends at or after ``seconds``.
``solve_s`` is the window over the requests answered in it.  After the
window a tick of graphs drawn from the run's seed is served the same way
and judged with the rest.
"""
import dataclasses
import os
import time

from bench import graphs, loops, spec, stats
from bench.trace import span

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COUNTERS = ("batch.lanes", "batch.empty_lanes", "batch.louvain_dispatches",
            "batch.louvain_graphs")


@dataclasses.dataclass
class Tick:
    """One tick in the window, what the per-layer readers read."""
    end: float          # seconds from the window's start
    requests: int
    answered: int
    counters: dict      # the program's COUNTERS, their growth in the tick


def _counters() -> dict:
    from repro.utils import telemetry

    return {k: telemetry.get(k) for k in COUNTERS}


def _serve(entry, eng, gs, idxs):
    """``idxs`` of ``gs`` as one tick: ``[(graph index, response)]``."""
    reqs = [entry.ingest(*gs[i]) for i in idxs]
    return list(zip(idxs, entry.solve(eng, reqs)))


def _answers(served):
    return [loops.answer_of(i, r.result) for i, r in served if r.ok]


def run(cfg, traffic, seed, seconds, window, log):
    entry = spec.entry(traffic, BENCH)
    gs = spec.module("generators", cfg["kind"], BENCH).pool(
        cfg, graphs.stream(cfg["graph_seed"], 1))
    first = {}
    for i, g in enumerate(gs):
        first.setdefault(entry.signature(*g), i)
    size = int(traffic["tick"])
    ticks = [list(range(a, min(a + size, len(gs))))
             for a in range(0, len(gs), size)]
    order = [ticks[t] for t in graphs.stream(seed, 7).permutation(len(ticks))]
    log(f"pool of {len(gs)} in {len(ticks)} ticks; signatures "
        f"(n_cap, m_cap): {sorted(first)}")
    eng = entry.engine()
    warm = []
    for sig, i in sorted(first.items()):    # warm-up: a flush a signature
        t = time.perf_counter()
        warm += _serve(entry, eng, gs, [i])
        log(f"warm-up flush of {sig}: {time.perf_counter() - t!r} s")
    if not all(r.ok for _, r in warm):
        raise RuntimeError(f"warm-up failed: {[r.error for _, r in warm]}")

    out = []
    with window():
        t0 = time.perf_counter()
        while not out or out[-1][0].end < seconds:
            for idxs in order:
                c0 = _counters()
                with span("bench.tick"):
                    served = _serve(entry, eng, gs, idxs)
                c1 = _counters()
                tick = Tick(time.perf_counter() - t0, len(idxs),
                            sum(r.ok for _, r in served),
                            {k: c1[k] - c0[k] for k in COUNTERS})
                out.append((tick, served))
    win, n = stats.solve_window([t.end for t, _ in out], seconds,
                                len(order))
    out = out[:n]
    answered = sum(t.answered for t, _ in out)
    ends = [0.0] + [t.end for t, _ in out]
    log(f"ticks in window: {n} over {win!r} s, {answered} of "
        f"{sum(t.requests for t, _ in out)} requests answered; each tick "
        f"(s): {[b - a for a, b in zip(ends, ends[1:])]}")

    rng = graphs.stream(seed, 8)
    check = [spec.generator(cfg["kind"], BENCH)(cfg, rng)
             for _ in range(size)]
    base = len(gs)
    gs = gs + check
    seeded = _serve(entry, eng, gs, list(range(base, len(gs))))
    log(f"tick drawn from the seed: {sum(r.ok for _, r in seeded)} of "
        f"{len(seeded)} answered after the window")
    served = [s for _, tick in out for s in tick] + seeded
    return dict(graphs=gs, answers=_answers(served),
                attempted=sum(t.requests for t, _ in out),
                failed=sum(t.requests - t.answered for t, _ in out),
                unanswered=sum(not r.ok for _, r in served),
                metrics={"solve_s": win / answered if answered else None},
                solves=[t for t, _ in out])
