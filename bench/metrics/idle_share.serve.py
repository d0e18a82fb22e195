"""One minus the device's busy time over the traced window of a served
cell (the device waits on host ingest, packing and dispatch there)."""


def read(run):
    return run.summary.idle_share if run.summary else None
