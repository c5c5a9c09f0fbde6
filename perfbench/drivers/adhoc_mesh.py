"""The ad-hoc closed loop (``drivers/adhoc.py``) with every session over the
cell's chips as one ``("data",)`` mesh (traffic ``adhoc-mesh``).

Traffic key: ``queries``, as ``adhoc``.  The queries and their timing are
``adhoc``'s own, loaded by path; each ``EarlSession`` the query builds gets
``mesh=``, so SSABE's B̂ ladder and every Δ extend fold their shard of the
sample on each chip and psum the per-resample states.  Each query also
records the difference of the program's counters (``repro.trace.counts()``)
across it, outside its timed span, so that the readers see the window's
queries alone.

``correct``: ``adhoc``'s checks, and one of the exchange between chips.
Each session is given ``checkpoint=`` an in-memory keeper of its snapshots,
so the psummed resample states it answered from (the carry after its last
round) come back with the query.  Their total weight over the B resamples
and the n rows read is a sum of n·B independent Poisson(1) weights, so
``weight_z`` = |Σw − n·B| / √(n·B) reads about |N(0, 1)|; a fold that
keeps one shard's states, or counts a shard twice, reads tens to hundreds.
Both of the group's accumulators, the moments' total weight ``w`` and the
median's histogram, fold the same integer weights, so ``mass_gap``, the
largest difference of their totals over the resamples, is exactly 0 in
float32; a fold or psum in lower precision rounds the totals apart.
"""
from __future__ import annotations

import contextlib
import functools
import math
from pathlib import Path

import numpy as np

from harness import Run, exact, judge, load_module, se_z

_adhoc = load_module(Path(__file__).with_name("adhoc.py"))


class Snapshots:
    """A checkpoint manager that keeps the last snapshot in memory: the
    session's (resample states, estimate state) after each round."""

    def __init__(self):
        self.tree = None

    def save(self, step, tree, extra=None):
        self.tree = tree

    def wait(self):
        pass


@contextlib.contextmanager
def _sessions_over(mesh, keeper):
    """Every ``EarlSession`` built from ``repro.core`` inside the block runs
    over ``mesh`` and snapshots its carry into ``keeper``."""
    import repro.core as core
    plain = core.EarlSession
    core.EarlSession = functools.partial(plain, mesh=mesh, checkpoint=keeper)
    try:
        yield
    finally:
        core.EarlSession = plain


def weight_z(states, n: int, B: int) -> float:
    """|Σw − n·B| / √(n·B) over the moment slots' total weights ``w``
    (one per resample) of a ``StatisticGroup``'s resample states."""
    totals = [float(np.sum(np.asarray(s.w, np.float64)))
              for s in states if hasattr(s, "w")]
    return max(abs(t - n * B) / math.sqrt(n * B) for t in totals)


def mass_gap(states) -> float:
    """The largest |Σ histogram counts − w| over the resamples, between
    each moment slot and each histogram slot of the group's states."""
    ws = [np.asarray(s.w, np.float64)[:, None]
          for s in states if hasattr(s, "w")]
    hs = [np.asarray(s.counts, np.float64).sum(axis=-1)
          for s in states if hasattr(s, "counts")]
    return max(float(np.max(np.abs(h - w))) for w in ws for h in hs)


class Driver(_adhoc.Driver):
    def __init__(self, run: Run):
        super().__init__(run)
        from jax.sharding import Mesh
        self.mesh = Mesh(np.array(run.devices[:4]), ("data",))

    def _query(self, i: int) -> dict:
        import jax

        from repro import trace
        keeper = Snapshots()
        before = trace.counts()
        with _sessions_over(self.mesh, keeper):
            q = super()._query(i)
        after = trace.counts()
        q["counts"] = {k: v - before.get(k, 0) for k, v in after.items()}
        q["states"] = (None if keeper.tree is None
                       else jax.device_get(keeper.tree[0]))
        return q

    def check(self):
        """``adhoc``'s checks of each query, with its ``weight_z`` and
        ``mass_gap`` (an exact job after a fallback has no resample
        states)."""
        mod, cfg = self.run.cell.config_mod, self.cfg
        items, rows_off = [], 0
        for q in self.done:
            rows = self._rows_used(q)
            rows_off = max(rows_off, abs(len(rows) - q["n_used"]))
            ref = mod.answers(cfg, rows)
            item = mod.gaps(cfg, q["answers"], ref)
            if not q["fell_back"]:
                item["se_z"] = se_z(q["mean_se"], ref["mean_se"], q["B"])
                item["weight_z"] = weight_z(q["states"], len(rows), q["B"])
                item["mass_gap"] = mass_gap(q["states"])
            items.append(item)
        failed, checks = judge(items, self.run.cell.limits)
        return failed, checks + [exact("rows_off", rows_off)]
