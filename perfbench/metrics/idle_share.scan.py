"""idle_share.scan: share of the traced window in which no device ran an
operation, in %, in the full-store scan cells."""


def read(run):
    if run.trace is None or run.trace.idle_share is None:
        return None
    return 100.0 * run.trace.idle_share
