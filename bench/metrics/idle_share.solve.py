"""One minus the device's busy time over the traced window."""


def read(run):
    return run.summary.idle_share if run.summary else None
