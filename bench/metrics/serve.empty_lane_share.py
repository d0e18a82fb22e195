"""Filler lanes over lanes dispatched in the window: the program's
counters ``batch.empty_lanes`` over ``batch.lanes`` (None where the
program counts no lanes)."""


def read(run):
    ticks = run.solves or ()
    lanes = sum(t.counters["batch.lanes"] for t in ticks)
    if not lanes:
        return None
    return sum(t.counters["batch.empty_lanes"] for t in ticks) / lanes
