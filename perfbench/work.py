"""The work a fold needs, counted from its shapes, whatever implements it.

A fold of n rows of d columns into B Poisson(1) resamples needs, for each
resample and row: one multiply that applies the weight, two multiply-adds
per column for the two moment sums (w*x and w*x^2), and one add into the
histogram bin of each sketched column.  Per row, once: x^2 for each column.
It must read each row once and its 0/1 validity mask.  Neither count holds
lane or row padding, resample padding, or the Poisson draw itself, so a
kernel that pads, re-reads or regenerates shows as the waste it is.
"""
from __future__ import annotations


def fold_ops(B: int, n: int, d: int, hist_columns: int) -> int:
    """Operations of one fold: 2-op multiply-adds count 2."""
    per_resample_row = 1 + 2 * 2 * d + hist_columns
    return B * n * per_resample_row + n * d


def fold_bytes(n: int, d: int, itemsize: int = 4) -> int:
    """Bytes that must be read: the rows and their f32 mask."""
    return n * d * itemsize + n * 4


def least_seconds(ops: int, nbytes: int, peaks: dict):
    """(seconds, bound): the larger of the compute and memory times at the
    chip's published peaks, and which of the two it is."""
    t_ops = ops / peaks["flops_per_s"]
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
