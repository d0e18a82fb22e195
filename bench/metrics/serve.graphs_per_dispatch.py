"""Graphs answered per batch dispatch in the window: the program's
counters ``batch.louvain_graphs`` over ``batch.louvain_dispatches``."""


def read(run):
    ticks = run.solves or ()
    dispatches = sum(t.counters["batch.louvain_dispatches"] for t in ticks)
    if not dispatches:
        return None
    return sum(t.counters["batch.louvain_graphs"] for t in ticks) / dispatches
