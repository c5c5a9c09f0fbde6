"""The trace reduction, the work counts and the peak table, on the CPU."""
from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import harness  # noqa: E402
import tracing  # noqa: E402
import work  # noqa: E402

MS = 1e6  # ns


def _hand_trace():
    """Window [0, 100] ms; device busy [10, 30] and [20, 40] (overlap) and
    [70, 80], an op outside the window; host spans poll [0, 50] holding a
    take [45, 50], and sampler [50, 100]."""
    return {
        "devices": {"/device:TPU:0": [["fold", 10 * MS, 30 * MS],
                                      ["merge", 20 * MS, 40 * MS],
                                      ["fold", 70 * MS, 80 * MS],
                                      ["late", 120 * MS, 130 * MS]]},
        "spans": [["bench.window", 0.0, 100 * MS],
                  ["bench.poll", 0.0, 50 * MS],
                  ["bench.take", 45 * MS, 50 * MS],
                  ["bench.sampler", 50 * MS, 100 * MS]],
    }


def test_union_merges_and_clips():
    iv = tracing.union([(5, 9), (0, 2), (1, 3), (8, 12)], 1, 10)
    assert iv == [(1, 3), (5, 10)]
    assert tracing.covered(iv) == 7


def test_busy_and_idle_share():
    red = tracing.reduce(_hand_trace())
    assert red.window_s == pytest.approx(0.1)
    assert red.busy_s == pytest.approx(0.04)           # [10,40] + [70,80]
    assert red.idle_share == pytest.approx(0.6)


def test_idle_gaps_labelled_by_innermost_host_span():
    red = tracing.reduce(_hand_trace())
    gaps = dict((n, s) for n, s in red.idle_gaps)
    # idle [0,10], [40,70] and [80,100]; [40,70] passes through the end of
    # poll, the take inside it, and the start of the sampler
    assert gaps["poll"] == pytest.approx(0.015)        # [0,10] + [40,45]
    assert gaps["take"] == pytest.approx(0.005)        # [45,50]
    assert gaps["sampler"] == pytest.approx(0.040)     # [50,70] + [80,100]
    assert sum(gaps.values()) == pytest.approx(0.1 - red.busy_s)


def test_top_ops_and_busy_inside_spans():
    red = tracing.reduce(_hand_trace())
    ops = dict((n, s) for n, s in red.device_ops)
    assert ops == {"fold": pytest.approx(0.030), "merge": pytest.approx(0.020)}
    assert red.busy_in["poll"] == pytest.approx(0.030)
    assert red.busy_in["sampler"] == pytest.approx(0.010)


def test_reduce_needs_a_window_and_a_device():
    t = _hand_trace()
    with pytest.raises(ValueError):
        tracing.reduce({"devices": t["devices"], "spans": t["spans"][1:]})
    with pytest.raises(ValueError):
        tracing.reduce({"devices": {}, "spans": t["spans"]})


RECORDED = Path(__file__).resolve().parent / "data" / "trace_v5e.json"


def test_recorded_trace_reduces():
    """A trace recorded on one v5e chip by ``tools/trace_probe.py``: one
    streaming pass of two chunks, then 50 ms of host sleep inside the
    window."""
    rec = json.loads(RECORDED.read_text())
    red = tracing.reduce(rec["raw"])
    exp = rec["expected"]
    assert red.window_s == pytest.approx(exp["window_s"], rel=1e-9)
    assert red.busy_s == pytest.approx(exp["busy_s"], rel=1e-9)
    assert 0.0 < red.busy_s < red.window_s
    gaps = dict((n, s) for n, s in red.idle_gaps)
    assert gaps["sleep"] >= 0.05
    assert red.busy_in["pass"] == pytest.approx(red.busy_s, rel=1e-9)


@pytest.mark.parametrize("B,n,d,hist,ops,nbytes", [
    # per resample and row: weight 1 + moments 2*2*d + hist adds;
    # per row: x^2 for each column; bytes: rows and a f32 mask
    (256, 1 << 20, 1, 1, 256 * (1 << 20) * 6 + (1 << 20), (1 << 20) * 8),
    (4, 10, 3, 0, 4 * 10 * 13 + 30, 10 * 3 * 4 + 10 * 4),
])
def test_fold_work_hand_counts(B, n, d, hist, ops, nbytes):
    assert work.fold_ops(B, n, d, hist) == ops
    assert work.fold_bytes(n, d) == nbytes


def test_least_seconds_names_its_bound():
    peaks = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.least_seconds(1000, 50, peaks) == (10.0, "compute")
    assert work.least_seconds(100, 50, peaks) == (5.0, "memory")


def test_peak_table_knows_v5e_and_refuses_other_kinds():
    p = harness.peaks_for("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks_for("TPU v4")
    with pytest.raises(KeyError):
        harness.peaks_for("cpu")
