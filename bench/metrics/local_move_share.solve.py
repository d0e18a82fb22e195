"""Device time of the local-moving sweeps (operations in the program's
``repro.local_move`` scope) over device busy time in the window; see
``bench/trace.py``."""


def read(run):
    return run.summary.scope_share("repro.local_move") if run.summary else None
