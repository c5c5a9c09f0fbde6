"""Back-to-back full-store bootstrap passes (traffic ``scan``).

Each pass is ``bootstrap_streaming`` of the configuration's group over the
whole store (``scan_resamples`` resamples, ``scan_chunk_rows``-row chunks)
under a fresh key drawn from the seed: the exact job with its CI, the
paper's fallback.  Set-up folds a two-chunk store of the same rows, which
compiles every program a pass runs.  Passes start while the window is open;
the one in flight when it closes runs to its end and counts, and the rate
is all rows folded over the time from the window's start to that end.

``correct``: every pass's answers against the float64 reference over all
rows of the store, and its bootstrap's standard error of the mean against
sd / sqrt(N).
"""
from __future__ import annotations

import time

import numpy as np

from harness import Run, derive, exact, judge, se_z


class Driver:
    def __init__(self, run: Run):
        self.run = run
        self.cfg = run.cell.config
        self.passes = []

    def _group(self):
        from repro.core import Mean, Quantile, StatisticGroup, Std
        cfg = self.cfg
        return StatisticGroup((
            Mean(), Quantile(cfg["median_q"], nbins=cfg["median_bins"],
                             lo=cfg["median_lo"], hi=cfg["median_hi"]),
            Std()))

    def setup(self) -> None:
        from repro.data import ShardedStore
        cfg = self.cfg
        self.data = self.run.cell.config_mod.make_rows(cfg, self.run.seed,
                                                       cfg["rows"])
        self.store = ShardedStore.from_array(self.data, cfg["split_rows"],
                                             interleave=False)
        self.group = self._group()
        warm = ShardedStore.from_array(self.data[:2 * cfg["scan_chunk_rows"]],
                                       cfg["split_rows"], interleave=False)
        self._pass(warm, derive(self.run.seed, 3, 1 << 20))

    def _pass(self, store, key_seed: int) -> dict:
        import jax

        from repro.core import bootstrap_streaming
        cfg = self.cfg
        t0 = time.perf_counter()
        with self.run.rec.span("bench.pass"):
            res = bootstrap_streaming(store, self.group,
                                      B=cfg["scan_resamples"],
                                      key=jax.random.PRNGKey(key_seed),
                                      chunk=cfg["scan_chunk_rows"])
            cv = float(res.report.cv)
            answers = [float(np.ravel(np.asarray(r))[0])
                       for r in res.estimate]
            mean_se = float(res.report.members[0].se)
        span_s = time.perf_counter() - t0
        rep = res.stream
        return {"rows": int(rep.rows), "valid_rows": int(rep.valid_rows),
                "span_s": span_s, "wall_s": rep.wall_s, "wait_s": rep.wait_s,
                "stage_s": rep.stage_s, "cv": cv, "mean_se": mean_se,
                "answers": dict(zip(("mean", "median", "std"), answers))}

    def window(self, seconds: float) -> dict:
        t0 = time.perf_counter()
        t_end = t0 + seconds
        j = 0
        with self.run.rec.span("bench.window"):
            while True:
                self.passes.append(self._pass(self.store,
                                              derive(self.run.seed, 3, j)))
                j += 1
                if time.perf_counter() >= t_end:
                    break
        elapsed = time.perf_counter() - t0
        self.run.records["passes"] = self.passes
        self.run.records["fold_shape"] = {
            "B": self.cfg["scan_resamples"], "n": self.cfg["rows"],
            "d": self.cfg["columns"], "hist_columns": self.cfg["columns"]}
        return {"scan_rows_per_s": sum(p["rows"] for p in self.passes)
                / elapsed}

    def free(self) -> None:
        self.store = None

    def check(self):
        """Each pass's answers against the reference over all rows, and
        its bootstrap's standard error of the mean against sd / sqrt(N)."""
        mod, cfg = self.run.cell.config_mod, self.cfg
        ref = mod.answers(cfg, self.data)
        B = cfg["scan_resamples"]
        items = [dict(mod.gaps(cfg, p["answers"], ref),
                      se_z=se_z(p["mean_se"], ref["mean_se"], B))
                 for p in self.passes]
        failed, checks = judge(items, self.run.cell.limits)
        rows_off = max(abs(p["valid_rows"] - len(self.data))
                       for p in self.passes)
        return failed, checks + [exact("rows_off", rows_off)]

    def control(self) -> dict:
        """The bfloat16 control's reading of each number over the store."""
        mod, cfg = self.run.cell.config_mod, self.cfg
        return mod.gaps(cfg, mod.control(cfg, self.data),
                        mod.answers(cfg, self.data))

    def facts(self) -> dict:
        return {"passes": len(self.passes),
                "pass_span_seconds": [p["span_s"] for p in self.passes],
                "pass_seconds": [p["wall_s"] for p in self.passes],
                "wait_seconds": [p["wait_s"] for p in self.passes],
                "stage_seconds": [p["stage_s"] for p in self.passes]}
