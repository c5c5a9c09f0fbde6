"""SSABE — Sample Size And Bootstrap Estimation (paper §3.2).

Two phases, run on a *pilot* sample (p·N, p ≈ 0.01) in "local mode"
(single device, no mesh — the analogue of the paper's single-JVM pilot):

  Phase A: grow B over candidate values {2, ..., ceil(1/τ)} until the error
           estimate stabilizes: |c_v(B_i) − c_v(B_{i−1})| < τ.
  Phase B: split the pilot into l nested subsamples n_i = n/2^{l−i},
           compute c_v(n_i) with B̂ resamples (delta-maintained across the
           nested growth), least-squares fit the c_v(n) curve, invert for
           the n* that achieves the target σ.

The fitted family is c_v(n) = a·n^(−1/2) + c — the CLT decay the paper's
"best fitting curve" tracks; fit is linear least squares in 1/sqrt(n).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import trace
from repro.core import accuracy
from repro.core.bootstrap import (_resample_states_shape, bootstrap_thetas,
                                  fused_resample_states, mesh_fold,
                                  seed_from_key, sharded_fused_states,
                                  weights_for)
from repro.core.delta import (poisson_delta_extend, poisson_delta_init,
                              poisson_delta_report)
from repro.core.reduce_api import (Statistic, _as_2d, bind_params,
                                   split_params)


@dataclasses.dataclass
class SSABEResult:
    B: int                      # estimated number of bootstraps
    n: int                      # estimated sample size for target sigma
    cv_history_B: List[Tuple[int, float]]   # phase A trace (B_i, cv_i)
    cv_history_n: List[Tuple[int, float]]   # phase B trace (n_i, cv_i)
    fit_a: float
    fit_c: float
    B_theory: int               # 0.5·eps0^-2 (paper §3)
    n_theory: int               # CLT prediction (for fig8)


def _candidates(B_min: int, B_max: int) -> Tuple[int, ...]:
    """The geometric ladder of B candidates: consecutive integers differ by
    O(1/B) by construction (nested prefixes), which would stop at B≈3 for
    any tau; doubling candidates make the |Δc_v| < τ test measure real
    convergence of the bootstrap variance estimate (paper Fig 2a flattens
    near B≈30)."""
    candidates = []
    b = max(2, B_min)
    while b < B_max:
        candidates.append(b)
        b *= 2
    candidates.append(B_max)
    return tuple(candidates)


@partial(jax.jit, static_argnames=("stat", "B_max", "candidates", "engine",
                                   "backend", "mesh", "data_axis"))
def _ladder_jit(x, key, params, stat, B_max, candidates, engine, backend,
                mesh=None, data_axis="data"):
    """Phase A's ladder as one program: thetas for all B_max resamples and
    the worst-member (or worst-key) c_v of every candidate prefix."""
    trace.count(trace.RESULT_TRACES)           # runs only while JAX traces
    stat = bind_params(stat, params)
    if backend == "fused_rng":
        # matrix-free: thetas for all B_max resamples without the (B_max, n)
        # weight matrix (every built-in statistic has a
        # fused_poisson_states path — moments, KMeansStep, Quantile; custom
        # ones materialize the same implicit weights).  With a mesh the
        # pilot shards over the data axis and only the states psum.
        if mesh is not None:
            states = sharded_fused_states(stat, seed_from_key(key), x, B_max,
                                          mesh=mesh, data_axis=data_axis)
        else:
            states = fused_resample_states(stat, seed_from_key(key), x,
                                           B_max)
        thetas = jax.vmap(stat.finalize)(states)
    else:
        # draw the maximal weight matrix once; prefixes give nested B
        thetas = bootstrap_thetas(x, stat,
                                  weights_for(engine, key, B_max, x.shape[0]))
    num_groups = getattr(stat, "num_groups", None)
    return jnp.stack([
        accuracy.worst_cv(jax.tree_util.tree_map(lambda t: t[:B], thetas),
                          num_groups)
        for B in candidates])


def estimate_B(values: jax.Array, stat: Statistic, tau: float,
               key: jax.Array, engine: str = "poisson",
               B_min: int = 2, B_max: int | None = None,
               backend: str | None = None, mesh=None,
               data_axis: str = "data"
               ) -> Tuple[int, List[Tuple[int, float]]]:
    """Phase A.  Common random numbers: resample b is keyed by fold_in(key,b),
    so growing B reuses earlier resamples — c_v(B) is a stable nested
    sequence and the |Δc_v| < τ stop is meaningful (not MC noise).

    With ``backend="fused_rng"`` the nested-prefix property is even
    structural: implicit weights are keyed per (resample-tile, item-tile),
    so row b's weights are independent of B_max entirely.

    The c_v of a StatisticGroup is its WORST member's, so the ladder stops
    only once every member is stable (the group's AES contract); of a
    GroupedStatistic its WORST KEY's.  One compiled step (``_ladder_jit``)
    computes every candidate's c_v, and the host reads them in one
    ``host_sync`` and applies the stop rule."""
    if backend == "fused_rng" and engine != "poisson":
        raise ValueError("backend='fused_rng' requires the poisson engine "
                         "(in-kernel RNG draws iid Poisson(1) weights)")
    if mesh is not None and backend != "fused_rng":
        raise ValueError("mesh= requires backend='fused_rng' (same rule as "
                         "bootstrap/bootstrap_chunked/poisson_delta_init)")
    if B_max is None:
        B_max = max(B_min + 1, int(math.ceil(1.0 / tau)))
    x = _as_2d(values)
    n, dim = x.shape
    candidates = _candidates(B_min, B_max)
    spec, params = split_params(stat)
    trace.count(trace.RESULT_STEPS)
    with mesh_fold(mesh, data_axis, n) as psummed:
        cvs = _ladder_jit(x, key, params, spec, int(B_max), candidates,
                          engine, backend, mesh, data_axis)
        if mesh is not None:        # the states psummed inside the program
            psummed(_resample_states_shape(spec, int(B_max), dim))

    history: List[Tuple[int, float]] = []
    prev_cv = None
    chosen = B_max
    for B, cv in zip(candidates, trace.to_host(cvs).tolist()):
        history.append((B, cv))
        if prev_cv is not None and abs(cv - prev_cv) < tau:
            chosen = B
            break
        prev_cv = cv
    return chosen, history


def fit_cv_curve(ns: np.ndarray, cvs: np.ndarray) -> Tuple[float, float]:
    """Least-squares fit  cv = a·n^(-1/2) + c ;  returns (a, c)."""
    A = np.stack([1.0 / np.sqrt(ns.astype(np.float64)),
                  np.ones_like(ns, dtype=np.float64)], axis=1)
    coef, *_ = np.linalg.lstsq(A, cvs.astype(np.float64), rcond=None)
    return float(coef[0]), float(coef[1])


def invert_cv_curve(a: float, c: float, sigma: float, n_cap: int) -> int:
    """Smallest n with a/sqrt(n) + c <= sigma (capped; paper falls back to
    the full data set when no n achieves sigma)."""
    if a <= 0:
        return 1 if c <= sigma else n_cap
    if c >= sigma:
        return n_cap
    n = (a / (sigma - c)) ** 2
    return int(min(max(1, math.ceil(n)), n_cap))


def estimate_n(values: jax.Array, stat: Statistic, sigma: float, B: int,
               key: jax.Array, l: int = 5, n_cap: int | None = None,
               backend: str | None = None, mesh=None,
               data_axis: str = "data"
               ) -> Tuple[int, List[Tuple[int, float]], float, float]:
    """Phase B with delta maintenance: the nested subsamples n_i = n/2^{l-i}
    are prefixes, so each step extends the Poisson-bootstrap states with the
    new half instead of recomputing (paper: "we perform delta maintenance")."""
    x = _as_2d(values)
    n, dim = x.shape
    if n_cap is None:
        n_cap = 1 << 62

    pd = poisson_delta_init(stat, B, dim, key, backend=backend, mesh=mesh,
                            data_axis=data_axis)
    history: List[Tuple[int, float]] = []
    prev = 0
    for i in range(1, l + 1):
        ni = max(2, n // (2 ** (l - i)))
        pd = poisson_delta_extend(pd, x[prev:ni])
        prev = ni
        history.append((ni, poisson_delta_report(pd).cv))

    ns = np.array([h[0] for h in history])
    cvs = np.array([h[1] for h in history])
    a, c = fit_cv_curve(ns, cvs)
    n_star = invert_cv_curve(a, c, sigma, n_cap)
    return n_star, history, a, c


def ssabe(pilot_values: jax.Array, stat: Statistic, sigma: float, tau: float,
          key: jax.Array, l: int = 5, N: int | None = None,
          engine: str = "poisson",
          backend: str | None = None, mesh=None,
          data_axis: str = "data") -> SSABEResult:
    """The full two-phase SSABE algorithm on a pilot sample.

    ``backend="fused_rng"`` routes both phases matrix-free (in-kernel
    Poisson weights) for every built-in statistic; ``mesh=`` additionally
    shards both phases over the data axis (states psum, weights never
    move)."""
    acc = accuracy
    kb, kn = jax.random.split(jax.random.fold_in(key, 0xEA))
    with trace.span("ssabe"):
        with trace.span("ssabe.B"):
            B_hat, hist_B = estimate_B(pilot_values, stat, tau, kb,
                                       engine=engine, backend=backend,
                                       mesh=mesh, data_axis=data_axis)
        n_cap = N if N is not None else int(1e12)
        with trace.span("ssabe.n"):
            n_hat, hist_n, a, c = estimate_n(
                pilot_values, stat, sigma, B_hat, kn, l=l, n_cap=n_cap,
                backend=backend, mesh=mesh, data_axis=data_axis)

        x = trace.to_host(_as_2d(pilot_values))
        n_theory = acc.theoretical_sample_size(
            sigma, float(x.std()), float(x.mean()))
    return SSABEResult(
        B=B_hat, n=n_hat,
        cv_history_B=hist_B, cv_history_n=hist_n,
        fit_a=a, fit_c=c,
        B_theory=acc.theoretical_num_bootstraps(tau),
        n_theory=n_theory,
    )
