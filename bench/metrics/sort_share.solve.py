"""Device time of sort operations over device busy time in the window
(operation class ``sort``; see ``bench/trace.py``)."""


def read(run):
    return run.summary.class_share("sort") if run.summary else None
