"""EarlSession — the end-to-end early-accurate-result driver (paper Fig. 1).

Pipeline: pilot sample → SSABE (B̂, n̂) → main job on n̂ with B̂ resamples →
AES check c_v ≤ σ → if not, expand the sample (Δs, delta-maintained) and
repeat → correct() the final result with p = n/N.

Fallback (paper §3.1): if SSABE predicts B·n ≥ N, early estimation cannot
beat the exact job — run the statistic over the full data set instead.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import trace
from repro.checkpoint.manager import CheckpointManager, cursor
from repro.core import ssabe as ssabe_mod
from repro.core.bootstrap import BootstrapResult, seed_from_key
from repro.core.delta import (PoissonDelta, poisson_delta_extend,
                              poisson_delta_init, poisson_delta_result)
from repro.core.reduce_api import Statistic, _as_2d, split_params
from repro.core.streaming import run_fingerprint


@dataclasses.dataclass
class EarlyResult:
    result: Any                 # corrected estimate (tuple for groups)
    cv: float                   # achieved error (worst member for groups)
    ci_lo: Any
    ci_hi: Any
    n_used: int
    N: int
    fraction: float             # p = n/N
    B: int
    iterations: int
    fell_back: bool             # True => exact full-data computation
    history: List[dict]
    wall_time_s: float
    ssabe: Optional[ssabe_mod.SSABEResult]
    #: StatisticGroup runs: one AccuracyReport per member, all derived from
    #: the SAME shared resamples (joint CIs); None otherwise / on fallback.
    reports: Optional[tuple] = None


class EarlSession:
    """Drives early approximation of ``stat`` over a Sampler.

    ``sampler`` must provide:
      - ``N``: total population size
      - ``take(start, stop) -> array``: rows [start, stop) of a fixed uniform
        random permutation of the population (so prefixes are uniform
        without-replacement samples and expansion is a prefix-extend).
    """

    def __init__(self, sampler, stat: Statistic, sigma: float = 0.05,
                 tau: float = 0.01, p_pilot: float = 0.01,
                 growth: float = 2.0, max_fraction: float = 1.0,
                 min_pilot: int = 64, max_pilot: int = 8192, l: int = 5,
                 backend: Optional[str] = None, mesh=None,
                 data_axis: str = "data", checkpoint=None,
                 checkpoint_every: int = 1):
        self.sampler = sampler
        self.stat = stat
        self.sigma = float(sigma)
        self.tau = float(tau)
        self.p_pilot = float(p_pilot)
        self.growth = float(growth)
        self.max_fraction = float(max_fraction)
        self.min_pilot = int(min_pilot)
        #: None = materialized jnp weights; "fused_rng" = matrix-free
        #: in-kernel RNG for SSABE and the delta-maintained main loop.
        #: ``mesh`` (fused backend only) shards SSABE and every delta
        #: extension over ``data_axis``: per-shard in-kernel weight streams,
        #: psum'd states, no weight traffic (paper's distributed resampling).
        self.backend = backend
        self.mesh = mesh
        self.data_axis = data_axis
        if mesh is not None and backend != "fused_rng":
            raise ValueError("mesh= requires backend='fused_rng'")
        # the pilot only needs to be large enough for a stable c_v(n) fit
        # (paper §3.2: "the initial n is picked to be small ... estimation
        # can be performed on a single machine"); capping it keeps the
        # local-mode phase O(1) as N grows.
        self.max_pilot = int(max_pilot)
        self.l = int(l)
        #: ``checkpoint`` (a CheckpointManager or a root path) snapshots
        #: the delta-maintained carry after every ``checkpoint_every``-th
        #: expansion round; ``run(key, resume=True)`` restores the latest
        #: snapshot and continues — since the loop's only RNG lives in the
        #: PoissonDelta (base key + per-extend step counter) and
        #: ``sampler.take`` is a fixed permutation, the resumed run is
        #: bitwise equal to the uninterrupted one.
        self.checkpoint = checkpoint
        self.checkpoint_every = int(checkpoint_every)
        if self.checkpoint_every < 1:
            raise ValueError(
                f"checkpoint_every must be >= 1, got {checkpoint_every}")

    # ------------------------------------------------------------------ #
    def _p_keys(self, n_have: int) -> Optional[np.ndarray]:
        """Per-key sampled fractions when the sampler stratifies a keyed
        statistic; None otherwise (scalar whole-table p applies).

        A ``StratifiedSampler`` prefix is uniform WITHIN each key but
        deliberately non-uniform ACROSS keys, so the whole-table p = n/N
        describes no single key — every correction must use that key's own
        ``stratum_counts(n) / stratum_sizes``."""
        if getattr(self.stat, "num_groups", None) is None:
            return None
        counts = getattr(self.sampler, "stratum_counts", None)
        sizes = getattr(self.sampler, "stratum_sizes", None)
        if counts is None or sizes is None:
            return None
        have = np.asarray(counts(n_have), dtype=np.float64)
        total = np.asarray(sizes, dtype=np.float64)
        return have / np.maximum(total, 1.0)

    # ------------------------------------------------------------------ #
    def _full_job(self, t0: float, history) -> EarlyResult:
        with trace.span("session.full_job"):
            N = self.sampler.N
            values = self.sampler.take(0, N)
            res = self.stat(values)
            # groups always get per-member reports, even on the exact-job
            # fallback (degenerate: cv 0, CI collapsed onto the exact
            # answer), so consumers can iterate EarlyResult.reports
            # unconditionally.
            reports = None
            if isinstance(res, tuple):
                from repro.core.accuracy import AccuracyReport
                reports = tuple(
                    AccuracyReport(cv=0.0, se=0.0, rel_halfwidth=0.0,
                                   ci_lo=r, ci_hi=r, boot_mean=r)
                    for r in res)
            elif getattr(self.stat, "num_groups", None) is not None:
                # keyed runs get the same guarantee per KEY: a
                # GroupedStatistic result is a (G, ...) array, one
                # degenerate report per key.
                from repro.core.accuracy import AccuracyReport
                reports = tuple(
                    AccuracyReport(cv=0.0, se=0.0, rel_halfwidth=0.0,
                                   ci_lo=res[g], ci_hi=res[g],
                                   boot_mean=res[g])
                    for g in range(int(self.stat.num_groups)))
            return EarlyResult(
                result=res, cv=0.0, ci_lo=res, ci_hi=res, n_used=N, N=N,
                fraction=1.0, B=1, iterations=len(history), fell_back=True,
                history=history, wall_time_s=time.perf_counter() - t0,
                ssabe=None, reports=reports)

    def run(self, key: jax.Array, resume: bool = False) -> EarlyResult:
        trace.count("session_run")
        with trace.span("session.run"):
            return self._run(key, resume)

    def _run(self, key: jax.Array, resume: bool) -> EarlyResult:
        t0 = time.perf_counter()
        N = self.sampler.N
        history: List[dict] = []

        mgr = CheckpointManager.open(self.checkpoint, resume=resume)

        # ---- pilot + SSABE (local mode) --------------------------------
        n_pilot = min(N, self.max_pilot,
                      max(self.min_pilot, int(self.p_pilot * N)))
        with trace.span("session.pilot"):
            pilot = self.sampler.take(0, n_pilot)
        est = ssabe_mod.ssabe(pilot, self.stat, self.sigma, self.tau,
                              jax.random.fold_in(key, 1), l=self.l, N=N,
                              backend=self.backend, mesh=self.mesh,
                              data_axis=self.data_axis)
        B, n_target = est.B, max(est.n, n_pilot)

        # ---- fallback check (paper §3.1) -------------------------------
        if B * n_target >= N or n_target >= self.max_fraction * N:
            return self._full_job(t0, history)

        # ---- main loop with delta-maintained resamples ------------------
        dim = _as_2d(pilot).shape[1]
        pd = poisson_delta_init(self.stat, B, dim,
                                jax.random.fold_in(key, 2),
                                backend=self.backend, mesh=self.mesh,
                                data_axis=self.data_axis)
        spec, params = split_params(self.stat)
        fp = run_fingerprint(spec, params, int(B),
                             trace.to_host(seed_from_key(pd.key), int), N,
                             dim)
        n_have = 0
        iterations = 0

        def early(res):
            return EarlyResult(
                result=res.estimate, cv=res.cv, ci_lo=res.report.ci_lo,
                ci_hi=res.report.ci_hi, n_used=n_have, N=N,
                fraction=n_have / N, B=B, iterations=iterations,
                fell_back=False, history=history,
                wall_time_s=time.perf_counter() - t0, ssabe=est,
                reports=getattr(res.report, "members", None))

        if resume:
            # pilot + SSABE were just recomputed deterministically from the
            # same key, so B/n_target/est match the original run; only the
            # delta-maintained carry and the cursor come from disk.
            (states, est_state), cur = mgr.restore_cursor(
                "session", fp, (pd.states, pd.est_state), "EarlSession",
                "(statistic, B, key, sampler)")
            pd = dataclasses.replace(pd, states=states, est_state=est_state,
                                     n=int(cur["n_have"]),
                                     step=int(cur["step"]))
            n_have = int(cur["n_have"])
            iterations = int(cur["iterations"])
            n_target = int(cur["n_target_next"])
            history = [dict(e, member_cvs=tuple(e["member_cvs"]))
                       if "member_cvs" in e else dict(e)
                       for e in cur["history"]]
            # the snapshot may already satisfy the gate (the run was killed
            # between the save and the return): re-derive the result from
            # the restored carry and re-check before extending further.
            p = n_have / N
            with trace.span("session.result"):
                res = poisson_delta_result(pd, p=p,
                                           p_keys=self._p_keys(n_have))
            if res.cv <= self.sigma or n_have >= self.max_fraction * N:
                return early(res)
        while True:
            iterations += 1
            with trace.span("session.round", round=iterations):
                n_goal = min(int(n_target), N)
                delta = self.sampler.take(n_have, n_goal)
                with trace.span("delta.extend"):
                    pd = poisson_delta_extend(pd, delta)
                n_have = n_goal
                p = n_have / N
                # the point estimate is delta-maintained in pd.est_state
                # (each extend folds Δs in, O(Δn)); recomputing
                # stat(take(0, n_have)) here would re-read the whole prefix
                # every round, O(n).
                with trace.span("session.result"):
                    res: BootstrapResult = poisson_delta_result(
                        pd, p=p, p_keys=self._p_keys(n_have))
                # for a StatisticGroup, res.cv is the WORST member's c_v
                # (GroupAccuracyReport), so the sigma gate below stops
                # only when ALL members meet the target; the per-member
                # trace is recorded so sessions can see who the straggler
                # was.
                entry = dict(iteration=iterations, n=n_have, B=int(B),
                             cv=float(res.cv), t=time.perf_counter() - t0)
                member_reports = getattr(res.report, "members", None)
                if member_reports is not None:
                    entry["member_cvs"] = tuple(float(r.cv)
                                                for r in member_reports)
                history.append(entry)
                if (mgr is not None
                        and iterations % self.checkpoint_every == 0):
                    # the cursor rides meta.json, so history must be
                    # JSON-plain
                    mgr.save(iterations, (pd.states, pd.est_state),
                             extra=cursor(
                                 "session", fp, n_have=int(n_have),
                                 step=int(pd.step),
                                 iterations=int(iterations),
                                 n_target_next=int(min(
                                     N, int(n_have * self.growth))),
                                 history=[
                                     {**e,
                                      "member_cvs": list(e["member_cvs"])}
                                     if "member_cvs" in e else e
                                     for e in history]))
            if res.cv <= self.sigma or n_have >= self.max_fraction * N:
                if mgr is not None:
                    mgr.wait()          # durable before we report success
                return early(res)
            if n_have >= N:
                if mgr is not None:
                    mgr.wait()
                return self._full_job(t0, history)
            n_target = min(N, int(n_have * self.growth))
