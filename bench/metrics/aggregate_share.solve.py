"""Device time of aggregation (operations in the program's
``repro.aggregate`` scope) over device busy time in the window; see
``bench/trace.py``."""


def read(run):
    return run.summary.scope_share("repro.aggregate") if run.summary else None
