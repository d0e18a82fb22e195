"""Host time in the program's ``repro.ingest.canonicalize`` spans (the
numpy half of ``from_numpy_edges``) inside the window, per solve, ms; see
``bench/trace.py``."""

SPAN = "repro.ingest.canonicalize"


def read(run):
    s = run.summary
    if not s or not run.solves or SPAN not in s.span_s:
        return None
    return 1000.0 * s.span_s[SPAN] / len(run.solves)
