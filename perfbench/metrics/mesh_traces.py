"""mesh_traces: times the program traced a sharded fold's body, per query of
the window, from its ``mesh_traces`` counter (``repro.trace``) as each
query's counter difference.  A warm query that builds its sharded folds
anew reads above 0.  None where the program keeps no such counter."""


def read(run):
    qs = run.records.get("queries")
    if not qs or not any("mesh_traces" in q.get("counts", {}) for q in qs):
        return None
    return sum(q["counts"].get("mesh_traces", 0) for q in qs) / len(qs)
