"""idle_share.query: share of the traced window in which no device ran an
operation, in %, in the ad-hoc query cells."""


def read(run):
    if run.trace is None or run.trace.idle_share is None:
        return None
    return 100.0 * run.trace.idle_share
