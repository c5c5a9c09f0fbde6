"""Closed loop of ad-hoc EARL queries from one analyst (traffic ``adhoc``).

Traffic key: ``queries``, the distinct queries drawn from the seed.
Query i builds a fresh ``PreMapSampler`` under seed
f(seed, i) and runs ``EarlSession.run`` under key g(seed, i) to the
configuration's sigma.  Set-up runs each query once, which compiles every
shape the window uses; the window then runs them back to back, cyclically,
and the query in flight when it closes runs to its end and counts.
A query's time runs from the sampler's construction until its answers and
its c_v are on the host.

``correct``: every query of the window, its answers against the float64
reference over exactly the rows the session was handed (recorded by a
proxy at the sampler boundary), and its bootstrap's standard error of the
mean against sd / sqrt(n).
"""
from __future__ import annotations

import time

import numpy as np

from harness import Run, derive, exact, judge, se_z, worst


class TimedSampler:
    """Proxy at the data layer's boundary: times every ``take`` and keeps
    what it returned, so the reference sees the rows the session folded."""

    def __init__(self, inner, rec):
        self._inner = inner
        self._rec = rec
        self.N = inner.N
        self.taken = []
        self.seconds = 0.0

    def take(self, start, stop):
        t0 = time.perf_counter()
        with self._rec.span("bench.take"):
            out = self._inner.take(start, stop)
        self.seconds += time.perf_counter() - t0
        self.taken.append(out)
        return out

    def __getattr__(self, name):
        return getattr(self._inner, name)


class Driver:
    def __init__(self, run: Run):
        self.run = run
        self.cfg = run.cell.config
        self.traffic = run.cell.traffic
        self.done = []

    def setup(self) -> None:
        from repro.core import Mean, Quantile, StatisticGroup, Std
        from repro.data import ShardedStore
        cfg, seed = self.cfg, self.run.seed
        self.data = self.run.cell.config_mod.make_rows(cfg, seed, cfg["rows"])
        self.store = ShardedStore.from_array(self.data, cfg["split_rows"],
                                             interleave=False)
        self.group = StatisticGroup((
            Mean(), Quantile(cfg["median_q"], nbins=cfg["median_bins"],
                             lo=cfg["median_lo"], hi=cfg["median_hi"]),
            Std()))
        self.queries = [(derive(seed, 1, i), derive(seed, 2, i))
                        for i in range(int(self.traffic["queries"]))]
        for i in range(len(self.queries)):
            self._query(i)

    def _query(self, i: int) -> dict:
        import jax

        from repro.core import EarlSession
        from repro.data import PreMapSampler
        cfg, rec = self.cfg, self.run.rec
        sampler_seed, key_seed = self.queries[i]
        t0 = time.perf_counter()
        with rec.span("bench.query"):
            with rec.span("bench.sampler"):
                sampler = TimedSampler(PreMapSampler(self.store,
                                                     seed=sampler_seed), rec)
            t1 = time.perf_counter()
            with rec.span("bench.session"):
                out = EarlSession(
                    sampler, self.group, sigma=cfg["sigma"], tau=cfg["tau"],
                    p_pilot=cfg["p_pilot"], l=cfg["l"],
                    backend=cfg["session_backend"],
                ).run(jax.random.PRNGKey(key_seed))
                float(out.cv)
                result = [float(np.ravel(np.asarray(r))[0])
                          for r in out.result]
                mean_se = float(out.reports[0].se)
        t2 = time.perf_counter()
        return {"query": i, "seconds": t2 - t0,
                "sampler_s": t1 - t0 + sampler.seconds,
                "rounds": int(out.iterations), "n_used": int(out.n_used),
                "B": int(out.B), "fell_back": bool(out.fell_back),
                "answers": dict(zip(("mean", "median", "std"), result)),
                "mean_se": mean_se, "taken": sampler.taken}

    def window(self, seconds: float) -> dict:
        t_end = time.perf_counter() + seconds
        k = 0
        with self.run.rec.span("bench.window"):
            while True:
                self.done.append(self._query(k % len(self.queries)))
                k += 1
                if time.perf_counter() >= t_end:
                    break
        self.run.records["queries"] = self.done
        return {"query_s": sum(q["seconds"] for q in self.done)
                / len(self.done)}

    def free(self) -> None:
        self.store = self.data = None

    def _rows_used(self, q: dict) -> np.ndarray:
        """The rows the session's answer covers: the exact job's full read
        after a fallback, else every take after the pilot's."""
        taken = q["taken"]
        parts = [taken[-1]] if q["fell_back"] else taken[1:]
        return np.concatenate([np.asarray(p, np.float32).reshape(-1)
                               for p in parts])

    def check(self):
        """Each query's answers against the reference over its rows, and
        its bootstrap's standard error of the mean against sd / sqrt(n)
        (an exact job after a fallback has no bootstrap to compare)."""
        mod, cfg = self.run.cell.config_mod, self.cfg
        items, rows_off = [], 0
        for q in self.done:
            rows = self._rows_used(q)
            rows_off = max(rows_off, abs(len(rows) - q["n_used"]))
            ref = mod.answers(cfg, rows)
            item = mod.gaps(cfg, q["answers"], ref)
            if not q["fell_back"]:
                item["se_z"] = se_z(q["mean_se"], ref["mean_se"], q["B"])
            items.append(item)
        failed, checks = judge(items, self.run.cell.limits)
        return failed, checks + [exact("rows_off", rows_off)]

    def control(self) -> dict:
        """The bfloat16 control's worst reading of each answer's numbers
        over the same queries' rows."""
        mod, cfg = self.run.cell.config_mod, self.cfg
        items = []
        for q in self.done:
            rows = self._rows_used(q)
            items.append(mod.gaps(cfg, mod.control(cfg, rows),
                                  mod.answers(cfg, rows)))
        return {k: worst(i[k] for i in items) for k in items[0]}

    def facts(self) -> dict:
        qs = self.done
        return {"queries": len(qs),
                "rounds": [q["rounds"] for q in qs],
                "rows_used": [q["n_used"] for q in qs],
                "resamples": [q["B"] for q in qs],
                "fell_back": sum(q["fell_back"] for q in qs),
                "query_seconds": [q["seconds"] for q in qs]}
