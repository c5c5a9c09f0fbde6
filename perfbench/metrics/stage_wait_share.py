"""stage_wait_share: share of each stream pass's wall time in which the fold
loop waited for a staged chunk (StreamReport.wait_s over wall_s), in %."""


def read(run):
    ps = run.records.get("passes")
    if not ps:
        return None
    return 100.0 * sum(p["wait_s"] for p in ps) / sum(p["wall_s"] for p in ps)
