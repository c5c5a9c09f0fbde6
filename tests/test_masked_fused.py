"""Masked-weight fused kernels: interior validity holes on the matrix-free
backend.

``valid_mask`` multiplies the implicit Poisson(1) weight tiles by an exact
0.0/1.0 validity vector, which must

* match the materialized-weights oracle (``implicit_weights * mask``) for
  EVERY built-in statistic and for a StatisticGroup,
* be bitwise identical between the Pallas kernels and the scan lowerings
  (the two lowerings share ``implicit_weight_tile``/``_poisson_tile``),
* reproduce the historical ``n_valid`` prefix masking bit for bit when the
  mask is prefix-shaped (f32 multiply by exactly 1.0/0.0 is exact, so
  ``w * mask`` ≡ ``where(col < n_valid, w, 0)``).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.bootstrap import fused_resample_states
from repro.core.reduce_api import (Count, KMeansStep, Mean, Median, Quantile,
                                   Statistic, StatisticGroup, Std, Sum, Var)
from repro.kernels.weighted_stats.ops import implicit_weights

N, D, B, SEED = 700, 3, 32, 1234


@pytest.fixture(scope="module")
def x2():
    rng = np.random.default_rng(0)
    return jnp.asarray(rng.normal(size=(N, D)).astype(np.float32))


@pytest.fixture(scope="module")
def interior_mask():
    rng = np.random.default_rng(1)
    m = (rng.random(N) > 0.3).astype(np.float32)
    m[0] = 0.0          # hole at the very first row
    m[-1] = 0.0         # and past any prefix interpretation
    return jnp.asarray(m)


def _stats():
    cent = jnp.asarray(np.random.default_rng(2)
                       .normal(size=(4, D)).astype(np.float32))
    return [
        Mean(), Sum(), Count(), Var(), Std(),
        Quantile(0.5, lo=-4.0, hi=4.0, nbins=64),
        Median(lo=-4.0, hi=4.0, nbins=64),
        KMeansStep(cent),
        StatisticGroup([Mean(), Var(),
                        Quantile(0.25, lo=-4.0, hi=4.0, nbins=32)]),
    ]


def _oracle_thetas(stat, x2, mask):
    """Materialized implicit weights × mask, per-row update — the oracle
    every fused masked path must reproduce."""
    w = np.asarray(implicit_weights(SEED, B, N)) * np.asarray(mask)[None, :]

    def one(wr):
        return stat.finalize(stat.update(stat.init_state(D), x2, wr))

    return jax.vmap(one)(jnp.asarray(w))


def _tree_allclose(a, b, **kw):
    jax.tree_util.tree_map(
        lambda u, v: np.testing.assert_allclose(np.asarray(u),
                                                np.asarray(v), **kw), a, b)


def _tree_bitwise(a, b):
    ok = jax.tree_util.tree_map(
        lambda u, v: bool(np.array_equal(np.asarray(u), np.asarray(v))),
        a, b)
    assert all(jax.tree_util.tree_leaves(ok)), ok


class TestInteriorMaskVsOracle:
    @pytest.mark.parametrize("stat", _stats(),
                             ids=lambda s: type(s).__name__)
    def test_fused_matches_materialized_oracle(self, stat, x2,
                                               interior_mask):
        states = fused_resample_states(stat, SEED, x2, B,
                                       valid_mask=interior_mask)
        thetas = jax.vmap(stat.finalize)(states)
        _tree_allclose(thetas, _oracle_thetas(stat, x2, interior_mask),
                       rtol=2e-4, atol=2e-4)

    def test_mask_actually_changes_the_result(self, x2, interior_mask):
        masked = jax.vmap(Mean().finalize)(
            fused_resample_states(Mean(), SEED, x2, B,
                                  valid_mask=interior_mask))
        unmasked = jax.vmap(Mean().finalize)(
            fused_resample_states(Mean(), SEED, x2, B))
        assert not np.allclose(np.asarray(masked), np.asarray(unmasked))


class TestPrefixMaskBitwiseEquivalence:
    """A prefix mask must reproduce n_valid masking BIT FOR BIT — this is
    what lets distributed.py switch to valid_mask without changing any
    pre-existing (prefix-masked) output."""

    @pytest.mark.parametrize("stat", _stats(),
                             ids=lambda s: type(s).__name__)
    def test_prefix_equals_n_valid(self, stat, x2):
        k = 500
        prefix = (jnp.arange(N) < k).astype(jnp.float32)
        a = fused_resample_states(stat, SEED, x2, B, n_valid=k)
        b = fused_resample_states(stat, SEED, x2, B, valid_mask=prefix)
        _tree_bitwise(a, b)


class TestKernelScanParity:
    """Masked Pallas kernels ≡ masked scan lowerings, bitwise (same
    shared tile math on both sides).  Where the kernel contracts a dot, the
    scan is fed the kernel's lane-padded x (``lane_padded``) so both sum
    in the same order."""

    def test_moments(self, x2, interior_mask, lane_padded):
        from repro.kernels.weighted_stats.ops import fused_poisson_moments
        s = fused_poisson_moments(SEED, lane_padded(x2), B,
                                  valid_mask=interior_mask, backend="scan")
        k = fused_poisson_moments(SEED, x2, B, valid_mask=interior_mask,
                                  backend="pallas_interpret")
        _tree_bitwise((s[0], s[1][:, :D], s[2][:, :D]), k)

    def test_moments_stream_kernel(self, x2, interior_mask):
        """The DMA double-buffered n-loop kernel produces the same bits as
        the grid kernel — masked and unmasked."""
        from repro.kernels.weighted_stats.ops import fused_poisson_moments
        for m in (None, interior_mask):
            grid = fused_poisson_moments(SEED, x2, B, valid_mask=m,
                                         backend="pallas_interpret")
            stream = fused_poisson_moments(SEED, x2, B, valid_mask=m,
                                           backend="pallas_interpret",
                                           stream=True)
            _tree_bitwise(grid, stream)

    def test_hist(self, x2, interior_mask):
        from repro.kernels.weighted_hist.ops import fused_poisson_hist
        args = (SEED, x2, -4.0, 4.0, 33, B)
        s = fused_poisson_hist(*args, backend="scan",
                               valid_mask=interior_mask)
        k = fused_poisson_hist(*args, backend="pallas_interpret",
                               valid_mask=interior_mask)
        bb = fused_poisson_hist(*args, backend="pallas_interpret",
                                valid_mask=interior_mask, block_bins=128)
        _tree_bitwise(s, k)
        _tree_bitwise(s, bb)

    def test_kmeans(self, x2, interior_mask, lane_padded):
        from repro.kernels.kmeans_assign.ops import fused_poisson_kmeans
        cent = jnp.asarray(np.random.default_rng(3)
                           .normal(size=(5, D)).astype(np.float32))
        s = fused_poisson_kmeans(SEED, lane_padded(x2), lane_padded(cent),
                                 B, backend="scan", valid_mask=interior_mask)
        k = fused_poisson_kmeans(SEED, x2, cent, B,
                                 backend="pallas_interpret",
                                 valid_mask=interior_mask)
        # sums/counts are bitwise (integer-weighted dot sums); inertia's
        # matvec-vs-dot reduction differs by ulps between the lowerings
        # (pre-existing, mask-independent) — allclose there.
        _tree_bitwise((s[0][..., :D], s[1]), k[:2])
        _tree_allclose(s[2], k[2], rtol=1e-5)

    def test_multi(self, x2, interior_mask, lane_padded):
        from repro.kernels.fused_multi.ops import fused_poisson_multi
        g = StatisticGroup([Mean(),
                            Quantile(0.5, lo=-4.0, hi=4.0, nbins=33)])
        s = fused_poisson_multi(g, SEED, lane_padded(x2), B, backend="scan",
                                valid_mask=interior_mask)
        k = fused_poisson_multi(g, SEED, x2, B, backend="pallas_interpret",
                                valid_mask=interior_mask)
        sm, sh = s
        _tree_bitwise((sm.w, sm.s1[:, :D], sm.s2[:, :D], sh.counts[:, :D],
                       sh.lo[:, :D], sh.hi[:, :D]), tuple(jax.tree.leaves(k)))


class _NoFusedPath(Statistic):
    """Custom statistic predating both the fused hook and valid_mask."""
    moment_powers = None

    def init_state(self, dim):
        return (jnp.zeros(()), jnp.zeros((dim,)))

    def update(self, state, x, w):
        wt, s1 = state
        return wt + jnp.sum(w), s1 + w @ jnp.asarray(x, jnp.float32)

    def merge(self, a, b):
        return a[0] + b[0], a[1] + b[1]

    def finalize(self, state):
        return state[1] / jnp.maximum(state[0], 1.0)


class TestCustomStatisticFallback:
    def test_masked_fallback_matches_oracle(self, x2, interior_mask):
        stat = _NoFusedPath()
        states = fused_resample_states(stat, SEED, x2, B,
                                       valid_mask=interior_mask)
        thetas = jax.vmap(stat.finalize)(states)
        _tree_allclose(thetas, _oracle_thetas(stat, x2, interior_mask),
                       rtol=1e-5, atol=1e-5)


class TestDistributedInteriorHoles:
    """ft/ failed-shard interior holes now run on the fused backend and
    match the default-backend oracle (beyond the 1-device regression in
    test_distributed.py: multi-shard, hole confined to one shard)."""

    def test_fused_matches_default_backend(self):
        from jax.sharding import Mesh

        from repro.core import DistributedEarl, Mean
        mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
        rng = np.random.default_rng(4)
        x = jnp.asarray(rng.normal(size=(64,)).astype(np.float32))
        mask = np.ones(64, np.float32)
        mask[10:20] = 0.0                     # interior block hole
        mask = jnp.asarray(mask)
        key = jax.random.PRNGKey(3)
        fused = DistributedEarl(mesh, Mean(), B=16, backend="fused_rng") \
            .estimate_with_loss_mask(x, mask, key)
        oracle = DistributedEarl(mesh, Mean(), B=16, backend=None) \
            .estimate_with_loss_mask(x, mask, key)
        np.testing.assert_allclose(np.ravel(fused.estimate),
                                   np.ravel(oracle.estimate), rtol=1e-6)
        assert fused.n == oracle.n == 54

    def test_ft_recovery_runs_on_fused_backend(self):
        """The ft/ entry point itself: an interior lost shard (not the
        trailing one, so the mask is NOT a prefix) on the fused backend,
        matching the default backend."""
        from jax.sharding import Mesh

        from repro.core import DistributedEarl, Mean
        from repro.ft.recovery import estimate_with_failures
        mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
        rng = np.random.default_rng(5)
        x = jnp.asarray(rng.normal(size=(80,)).astype(np.float32) + 2.0)
        key = jax.random.PRNGKey(7)
        rep_f = estimate_with_failures(
            DistributedEarl(mesh, Mean(), B=16, backend="fused_rng"),
            x, lost_shards=[1], n_shards=4, sigma=0.5, key=key)
        rep_o = estimate_with_failures(
            DistributedEarl(mesh, Mean(), B=16, backend=None),
            x, lost_shards=[1], n_shards=4, sigma=0.5, key=key)
        np.testing.assert_allclose(np.ravel(rep_f.result),
                                   np.ravel(rep_o.result), rtol=1e-6)
        assert rep_f.p_surviving == rep_o.p_surviving == 0.75
        assert np.isfinite(rep_f.cv)
