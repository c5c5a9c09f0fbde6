"""psum_bytes: bytes of the per-resample state pytrees the sharded folds of
one query psum over the mesh, per query of the window, from the program's
``mesh_psum_bytes`` counter (``repro.trace``) as each query's counter
difference.  None where the program keeps no such counter."""


def read(run):
    qs = run.records.get("queries")
    if not qs or not any("mesh_psum_bytes" in q.get("counts", {})
                         for q in qs):
        return None
    return sum(q["counts"].get("mesh_psum_bytes", 0) for q in qs) / len(qs)
