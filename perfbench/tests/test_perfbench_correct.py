"""What decides ``correct``, at sizes a CPU test run holds.

* Each cell's traffic runs through the harness (the look for a chip
  skipped) and comes out correct.
* With the timed path broken underneath — a step that returns its state
  unchanged, half of each batch left out and the mean taken over the rest,
  an answer altered where it is produced — the same run comes out not
  correct.  (No cell spans chips, so there is no exchange to leave out.)
* The control, the reference with its inputs in bfloat16, reads above each
  cell's limit.

On the CPU the kernels run as scan lowerings, whose float32 sums differ
from the chip's, so the runs here hold each number to a CPU limit; the
control is held to the cell's own limit.
"""
from __future__ import annotations

import dataclasses
import math
import sys
import time
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

import harness  # noqa: E402
import run as run_mod  # noqa: E402

_PAPER_CPU = {"answer_gap": 1e-4, "median_bins": 0.01, "se_z": 6.0}
CPU_LIMITS = {"paper-jobs.adhoc": _PAPER_CPU, "paper-jobs.scan": _PAPER_CPU}
SEED = 2**31 + 101


def _tiny(name: str) -> harness.Cell:
    cell = harness.find_cell(name)
    c = cell.config
    if name == "paper-jobs.adhoc":
        c.update(rows=1 << 18, split_rows=1 << 14)
        cell.traffic["queries"] = 1
    else:
        c.update(rows=1 << 15, split_rows=1 << 12, scan_chunk_rows=1 << 12,
                 scan_resamples=16)
    cell.limits = dict(CPU_LIMITS[name])
    return cell


def _measure(name: str, seconds: float = 0.5) -> dict:
    import jax
    return run_mod.measure(_tiny(name), SEED, seconds, False,
                           jax.devices()[:1], None, time.perf_counter())


CELLS = list(CPU_LIMITS)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    out = _measure(name)
    res = out["result"]
    assert res["correct"] is True, out["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    if name == "paper-jobs.adhoc":
        assert out["facts"]["fell_back"] == 0


def _unchanged(name, monkeypatch):
    if name == "paper-jobs.adhoc":
        import repro.core.session as session
        monkeypatch.setattr(
            session, "poisson_delta_extend",
            lambda pd, x: dataclasses.replace(pd, n=pd.n + len(x),
                                              step=pd.step + 1))
    else:
        import repro.core.streaming as streaming
        monkeypatch.setattr(streaming, "_stream_chunk_jit",
                            lambda states, est, *a, **k: (states, est))


def _first_half(mask):
    import jax.numpy as jnp
    n = mask.shape[0]
    return mask * (jnp.arange(n) < n // 2).astype(mask.dtype)


def _half_batch(name, monkeypatch):
    if name == "paper-jobs.adhoc":
        import repro.core.session as session
        orig = session.poisson_delta_extend
        monkeypatch.setattr(session, "poisson_delta_extend",
                            lambda pd, x: orig(pd, x[:len(x) // 2]))
    else:
        import repro.core.streaming as streaming
        orig = streaming._stream_chunk_jit
        monkeypatch.setattr(
            streaming, "_stream_chunk_jit",
            lambda s, e, xi, vi, *a, **k: orig(s, e, xi, _first_half(vi),
                                               *a, **k))


def _altered(name, monkeypatch):
    from repro.core import reduce_api
    orig = reduce_api.Mean.finalize
    monkeypatch.setattr(reduce_api.Mean, "finalize",
                        lambda self, state: orig(self, state) * 1.01 + 0.1)


FAULTS = {"state_unchanged": _unchanged, "half_batch": _half_batch,
          "answer_altered": _altered}


@pytest.mark.parametrize("fault", list(FAULTS))
@pytest.mark.parametrize("name", CELLS)
def test_broken_run_is_not_correct(name, fault, monkeypatch):
    FAULTS[fault](name, monkeypatch)
    res = _measure(name)["result"]
    assert res["correct"] is False
    assert res["failed"] >= 1


def _paper_rows(n):
    cell = harness.find_cell("paper-jobs.scan")
    return cell, cell.config_mod.make_rows(cell.config, SEED, n)


@pytest.mark.parametrize("name", ["paper-jobs.adhoc", "paper-jobs.scan"])
def test_bf16_control_fails_paper_jobs(name):
    cell, rows = _paper_rows(1 << 16)
    mod, cfg = cell.config_mod, cell.config
    ctl = mod.gaps(cfg, mod.control(cfg, rows), mod.answers(cfg, rows))
    limits = harness.find_cell(name).limits
    assert any(ctl[k] > limits[k] for k in ctl), (ctl, limits)



def test_rows_follow_the_configured_distribution():
    cell, rows = _paper_rows(1 << 16)
    dist = cell.config["distribution"]
    assert rows.dtype == np.float32 and rows.shape == (1 << 16, 1)
    assert abs(rows.mean() - dist["mean"]) < 4 * dist["std"] / 2**8
    assert abs(rows.std() / dist["std"] - 1) < 0.02
    assert np.array_equal(rows, _paper_rows(1 << 16)[1])


def test_se_z_reads_resamples_alike_as_infinite():
    assert harness.se_z(0.0, 1.0, 256) == math.inf
    assert harness.se_z(1.0, 1.0, 256) == 0.0
    # a standard error off by its own spread over B resamples reads ~1
    B = 257
    assert harness.se_z(math.exp((2 / (B - 1)) ** 0.5), 1.0, B) == \
        pytest.approx(1.0)


def test_judge_fails_a_nan_and_counts_failed_answers():
    limits = {"answer_gap": 1e-6}
    failed, checks = harness.judge(
        [{"answer_gap": 0.0}, {"answer_gap": float("nan")}], limits)
    assert failed == 1 and not checks[0].ok
    failed, checks = harness.judge([], limits)
    assert failed == 0 and not checks[0].ok
