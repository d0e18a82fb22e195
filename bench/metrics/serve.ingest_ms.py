"""Host time in the program's ``repro.serve.submit`` spans (admission,
canonical ingest and host padding of one request) inside the window, per
request, ms; see ``bench/trace.py``."""

SPAN = "repro.serve.submit"


def read(run):
    s = run.summary
    requests = sum(t.requests for t in run.solves or ())
    if not s or not requests or SPAN not in s.span_s:
        return None
    return 1000.0 * s.span_s[SPAN] / requests
