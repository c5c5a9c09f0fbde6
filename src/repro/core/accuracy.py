"""Accuracy measures for early-result estimation (paper §3).

The paper's headline error measure is the coefficient of variation
``c_v = std / |mean|`` computed over the bootstrap result distribution.
The machinery is measure-agnostic (paper: "Our approach is independent of
the error measure"), so we also expose variance, standard error, relative
CI half-width and percentile CIs over the same distribution.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

from repro import trace

_EPS = 1e-12


def _flatten_thetas(thetas: jax.Array) -> jax.Array:
    """(B, ...) -> (B, K) flat view of the bootstrap result distribution."""
    thetas = jnp.asarray(thetas)
    if thetas.ndim == 1:
        return thetas[:, None]
    return thetas.reshape(thetas.shape[0], -1)


def coefficient_of_variation(thetas: jax.Array) -> jax.Array:
    """c_v of a bootstrap result distribution ``thetas`` with leading axis B.

    Scalar statistics: classic std/|mean|.  Vector statistics (e.g. k-means
    centroids): scale-invariant aggregate  sqrt(mean_k var_k) / rms_k(mean_k),
    which reduces to the scalar definition for K=1.
    """
    t = _flatten_thetas(thetas)
    mean = jnp.mean(t, axis=0)
    var = jnp.var(t, axis=0, ddof=1) if t.shape[0] > 1 else jnp.zeros_like(mean)
    num = jnp.sqrt(jnp.mean(var))
    den = jnp.sqrt(jnp.mean(mean * mean))
    return num / (den + _EPS)


def standard_error(thetas: jax.Array) -> jax.Array:
    t = _flatten_thetas(thetas)
    if t.shape[0] <= 1:
        return jnp.zeros(())
    return jnp.sqrt(jnp.mean(jnp.var(t, axis=0, ddof=1)))


def relative_halfwidth(thetas: jax.Array, z: float = 1.96) -> jax.Array:
    """z·SE / |mean| — the relative CI half-width at confidence z."""
    t = _flatten_thetas(thetas)
    mean = jnp.sqrt(jnp.mean(jnp.mean(t, axis=0) ** 2))
    return z * standard_error(thetas) / (mean + _EPS)


def percentile_ci(thetas: jax.Array, alpha: float = 0.05
                  ) -> Tuple[jax.Array, jax.Array]:
    """Efron percentile bootstrap CI (per flattened component)."""
    t = _flatten_thetas(thetas)
    lo = jnp.percentile(t, 100.0 * (alpha / 2.0), axis=0)
    hi = jnp.percentile(t, 100.0 * (1.0 - alpha / 2.0), axis=0)
    return lo, hi


@dataclasses.dataclass(frozen=True)
class AccuracyReport:
    """Everything the AES stage (paper §3.1) derives from one bootstrap run."""
    cv: float
    se: float
    rel_halfwidth: float
    ci_lo: jax.Array
    ci_hi: jax.Array
    boot_mean: jax.Array

    @staticmethod
    def from_thetas(thetas: jax.Array, alpha: float = 0.05) -> "AccuracyReport":
        return report_for(thetas, alpha)


@dataclasses.dataclass(frozen=True)
class GroupAccuracyReport:
    """Per-member AccuracyReports for a ``StatisticGroup`` bootstrap run.

    The scalar gates (cv / se / rel_halfwidth) expose the WORST member, so
    every driver's existing ``report.cv <= sigma`` stop condition reads
    "stop when ALL members meet the target" without changing a line; the
    per-member reports stay available on ``members``.  Because the group
    shares one Poisson weight stream, the member CIs here are JOINT —
    computed from the same resamples, so comparisons across members are
    consistent rather than independently randomized."""
    members: Tuple["AccuracyReport", ...]

    @property
    def cv(self) -> float:
        return max(m.cv for m in self.members)

    @property
    def se(self) -> float:
        return max(m.se for m in self.members)

    @property
    def rel_halfwidth(self) -> float:
        return max(m.rel_halfwidth for m in self.members)

    @property
    def ci_lo(self):
        return tuple(m.ci_lo for m in self.members)

    @property
    def ci_hi(self):
        return tuple(m.ci_hi for m in self.members)

    @property
    def boot_mean(self):
        return tuple(m.boot_mean for m in self.members)

    @property
    def cvs(self) -> Tuple[float, ...]:
        return tuple(m.cv for m in self.members)


@dataclasses.dataclass(frozen=True)
class KeyedAccuracyReport(GroupAccuracyReport):
    """Per-KEY AccuracyReports for a ``GroupedStatistic`` bootstrap run
    (one entry per group key, in key order 0..G-1).

    Inherits the worst-member scalar gates from ``GroupAccuracyReport`` —
    here worst-KEY: ``report.cv <= sigma`` reads "stop when EVERY key
    meets the target", which is the BlinkDB-style per-key guarantee (a
    rare key's wide CI cannot hide behind a heavy hitter's tight one).
    All keys share one Poisson weight stream (common random numbers), so
    cross-key comparisons of these CIs are consistent.

    ``p_keys`` (when the driver ran under stratified sampling) records the
    PER-KEY sampled fractions the thetas were corrected with — key g's
    reports reflect ``inner.correct(·, p_keys[g])`` rather than one
    whole-table p, so a rare stratum's Sum/Count is scaled by its own
    inclusion probability (see ``GroupedStatistic.correct_per_key``)."""
    p_keys: "Tuple[float, ...] | None" = None

    @property
    def worst_key(self) -> int:
        """The key whose cv gates the stop — where more rows are needed."""
        cvs = self.cvs
        return max(range(len(cvs)), key=lambda g: cvs[g])


#: the scalar gates of a report, in the order ``report_scalars`` stacks them
SCALARS = ("cv", "se", "rel_halfwidth")


def _numbers(thetas, alpha: float) -> dict:
    lo, hi = percentile_ci(thetas, alpha)
    return dict(cv=coefficient_of_variation(thetas),
                se=standard_error(thetas),
                rel_halfwidth=relative_halfwidth(thetas),
                ci_lo=lo, ci_hi=hi,
                boot_mean=jnp.mean(_flatten_thetas(thetas), axis=0))


def _parts(thetas, num_groups=None) -> tuple:
    """The theta arrays a report has a member for: a StatisticGroup's
    per-member thetas, with ``num_groups`` the (B, G, ...) thetas of a
    keyed statistic split per key along axis 1, else the one array."""
    if isinstance(thetas, (tuple, list)):
        return tuple(thetas)
    if num_groups is not None:
        thetas = jnp.asarray(thetas)
        return tuple(thetas[:, g] for g in range(int(num_groups)))
    return (thetas,)


def report_numbers(thetas, alpha: float = 0.05, num_groups=None):
    """Everything a report holds, as device arrays and jittable: a dict of
    ``cv``, ``se``, ``rel_halfwidth``, ``ci_lo``, ``ci_hi`` and
    ``boot_mean`` for a (B, ...) theta array, or a tuple of such dicts, one
    per member of a StatisticGroup's tuple of thetas or, with
    ``num_groups``, one per key along axis 1 of (B, G, ...) thetas."""
    numbers = tuple(_numbers(t, alpha) for t in _parts(thetas, num_groups))
    if isinstance(thetas, (tuple, list)) or num_groups is not None:
        return numbers
    return numbers[0]


def _members(numbers) -> tuple:
    return (numbers,) if isinstance(numbers, dict) else tuple(numbers)


def report_scalars(numbers) -> jax.Array:
    """(members, 3): each member's (or key's) ``SCALARS`` in one array, so
    that the host reads them all in one transfer.  Jittable."""
    return jnp.stack([jnp.stack([m[k] for k in SCALARS])
                      for m in _members(numbers)])


def report_from_numbers(numbers, scalars, num_groups=None, p_keys=None):
    """The report of ``report_numbers``' output, its scalar gates taken
    from ``scalars`` (host numbers, one row of ``SCALARS`` per member)."""
    reports = tuple(
        AccuracyReport(*(float(v) for v in row), ci_lo=m["ci_lo"],
                       ci_hi=m["ci_hi"], boot_mean=m["boot_mean"])
        for m, row in zip(_members(numbers), scalars))
    if isinstance(numbers, dict):
        return reports[0]
    if num_groups is not None:
        return KeyedAccuracyReport(
            reports, p_keys=None if p_keys is None
            else tuple(float(p) for p in p_keys))
    return GroupAccuracyReport(reports)


def report_for(thetas, alpha: float = 0.05, num_groups=None, p_keys=None):
    """AccuracyReport for a (B, ...) theta array, a GroupAccuracyReport
    for the tuple of per-member thetas a StatisticGroup produces, or — when
    ``num_groups`` is set (drivers read it off ``stat.num_groups`` for a
    GroupedStatistic) — a KeyedAccuracyReport splitting the (B, G, ...)
    thetas into per-key reports along axis 1.  ``p_keys`` is carried onto
    the keyed report for introspection (the thetas must already be
    per-key corrected).  Eager: each scalar gate is its own read on the
    host; the session's compiled result step reads them all at once
    (``report_scalars``)."""
    numbers = report_numbers(thetas, alpha, num_groups)
    scalars = [[trace.to_host(m[k], float) for k in SCALARS]
               for m in _members(numbers)]
    return report_from_numbers(numbers, scalars, num_groups, p_keys)


def worst_cv(thetas, num_groups=None) -> jax.Array:
    """c_v of a theta distribution — for a StatisticGroup's tuple of
    per-member thetas the WORST member, and with ``num_groups`` (a
    GroupedStatistic's (B, G, ...) thetas) the WORST KEY.  Jittable."""
    return jnp.max(jnp.stack([coefficient_of_variation(t)
                              for t in _parts(thetas, num_groups)]))


def theoretical_num_bootstraps(eps0: float) -> int:
    """Paper §3: theory suggests B = 0.5 * eps0^-2 [Efron '87]."""
    return int(round(0.5 * eps0 ** (-2)))


def theoretical_sample_size(sigma: float, pilot_std: float, pilot_mean: float
                            ) -> int:
    """CLT-based n for the *mean*: c_v(mean over n) = (s/|mu|)/sqrt(n) <= sigma.

    Used as the 'theoretical prediction' line in benchmarks/fig8 — the paper
    shows SSABE's empirical estimate beats this in both directions.
    """
    rel = pilot_std / (abs(pilot_mean) + _EPS)
    return max(1, trace.to_host(jnp.ceil((rel / sigma) ** 2), int))
