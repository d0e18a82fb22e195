"""Host ingest: mean ``from_numpy_edges`` time per solve in the window, ms."""


def read(run):
    if not run.solves:
        return None
    return 1000.0 * sum(s.ingest_s for s in run.solves) / len(run.solves)
