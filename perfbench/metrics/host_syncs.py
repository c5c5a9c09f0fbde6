"""host_syncs: reads of a device value on the host per ad-hoc query, from
the program's own counters (``repro.trace``): ``host_sync`` over
``session_run``, both counted from the process's start (set-up runs each of
the queries the window cycles through once).  None where the program keeps
no such counters."""


def read(run):
    if not run.records.get("queries"):
        return None
    try:
        from repro import trace
    except ImportError:
        return None
    counts = trace.counts()
    sessions = counts.get("session_run", 0)
    if not sessions:
        return None
    return counts.get(trace.HOST_SYNC, 0) / sessions
