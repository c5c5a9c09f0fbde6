"""paper-jobs: the rows, the plain float64 reference and its bfloat16 control.

Imports numpy (and jax only to round on the device) and nothing of the
program.  The job's answers are the mean, the population standard
deviation and the median as a 2048-bin sketch over [lo, hi] defines it:
the first bin whose cumulative share of rows reaches q, reported as the
bin's centre.  The exact counterpart is the q-quantile row value
``sorted(x)[ceil(q n) - 1]``; a sound sketch puts it inside the bin it
reports.
"""
from __future__ import annotations

import math

import numpy as np


def make_rows(cfg: dict, seed: int, n: int) -> np.ndarray:
    """(n, 1) float32 rows of N(mean, std), the same for the same seed."""
    dist = cfg["distribution"]
    rng = np.random.default_rng([int(seed) & ((1 << 64) - 1), 0x0DA7A])
    x = rng.standard_normal(n, dtype=np.float32)
    x *= np.float32(dist["std"])
    x += np.float32(dist["mean"])
    return x.reshape(n, 1)


def answers(cfg: dict, rows) -> dict:
    """Float64 mean, standard deviation and exact q-quantile of ``rows``,
    and the mean's standard error, sd / sqrt(n), which a bootstrap of the
    mean estimates."""
    x = np.asarray(rows, np.float64).reshape(-1)
    n = len(x)
    mean = float(x.mean())
    sd = float(np.sqrt(np.mean((x - mean) ** 2)))
    k = max(1, math.ceil(cfg["median_q"] * n)) - 1
    q_value = float(np.partition(x, k)[k])
    return {"mean": mean, "std": sd, "quantile": q_value,
            "mean_se": sd / math.sqrt(n)}


def gaps(cfg: dict, program: dict, ref: dict) -> dict:
    """How far the program's answers lie from the reference's: the worst
    relative gap of the mean and the standard deviation, and how many bin
    widths the exact quantile lies outside the bin whose centre the
    program reports."""
    lo, hi, nb = cfg["median_lo"], cfg["median_hi"], cfg["median_bins"]
    width = (hi - lo) / nb
    v = min(max(ref["quantile"], lo), hi)
    return {"answer_gap": max(
                abs(program["mean"] - ref["mean"]) / abs(ref["mean"]),
                abs(program["std"] - ref["std"]) / ref["std"]),
            "median_bins": max(0.0, abs(v - program["median"]) / width - 0.5)}


def bf16_rows(rows) -> np.ndarray:
    """``rows`` rounded to bfloat16 on the default device, as float64."""
    import jax.numpy as jnp
    r = jnp.asarray(np.asarray(rows, np.float32)).astype(jnp.bfloat16)
    return np.asarray(r.astype(jnp.float32), np.float64)


def control(cfg: dict, rows) -> dict:
    """The reference with its rows in bfloat16, one precision below the
    float32 the configuration states, and sums kept exact: what a fold
    that reads bfloat16 rows and accumulates in float32 computes.
    Reported as the program's answers are: the median as the centre of the
    bin holding the quantile."""
    a = answers(cfg, bf16_rows(rows))
    lo, hi, nb = cfg["median_lo"], cfg["median_hi"], cfg["median_bins"]
    bw = (hi - lo) / nb
    b = min(max(int((a["quantile"] - lo) // bw), 0), nb - 1)
    return {"mean": a["mean"], "std": a["std"], "median": lo + (b + 0.5) * bw}
