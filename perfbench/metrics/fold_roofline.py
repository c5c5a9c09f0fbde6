"""fold_roofline: the least time the chip needs for the folds' work (work.py,
at the peaks of peaks.json) over the device's busy time inside the stream
passes' spans, in %."""
import work


def read(run):
    shape = run.records.get("fold_shape")
    ps = run.records.get("passes")
    if run.trace is None or not shape or not ps or run.peaks is None:
        return None
    busy = run.trace.busy_in.get("pass", 0.0)
    if busy <= 0:
        return None
    ops = work.fold_ops(shape["B"], shape["n"], shape["d"],
                        shape["hist_columns"])
    nbytes = work.fold_bytes(shape["n"], shape["d"])
    least, _ = work.least_seconds(ops, nbytes, run.peaks)
    return 100.0 * least * len(ps) / busy
