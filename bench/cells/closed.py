"""Closed loop: one caller, whole rounds of whole solves back to back;
then one graph drawn from the seed, solved the same way and judged with
the rest."""
import os

from bench import graphs, loops, spec, stats

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run(cfg, traffic, seed, seconds, window, log):
    entry = spec.entry(traffic, BENCH)
    gs = graphs.closed_order(cfg, traffic, seed, BENCH)
    loops.solve_once(entry, 0, *gs[0])       # warm-up
    with window():
        solves = loops.closed(gs, entry, seconds)
    win, n = stats.solve_window([s.end for s in solves], seconds, len(gs))
    ends = [0.0] + [s.end for s in solves]
    log(f"solves in window: {n} over {win!r} s; each (s): "
        f"{[b - a for a, b in zip(ends, ends[1:])]}")
    gs.append(graphs.check_graph(cfg, seed, BENCH))
    _, seeded = loops.solve_once(entry, len(gs) - 1, *gs[-1])
    log(f"graph drawn from the seed: solved after the window, "
        f"{seeded.n_communities} communities, modularity {seeded.modularity!r}")
    return dict(graphs=gs, answers=[s.answer for s in solves] + [seeded],
                attempted=n, failed=0, unanswered=0,
                metrics={"solve_s": win / n}, solves=solves[:n])
