"""Matrix-free bootstrap: in-kernel RNG fused moments + histogram sketch.

Covers the ISSUE-1 acceptance criteria:
  * fused moments == materialized implicit-weights oracle (all backends)
  * in-kernel Poisson(1) weights are statistically sound (mean/var, and the
    fused bootstrap matches the jax.random.poisson oracle distributionally)
  * poisson_delta_extend stays exact under backend="fused_rng"
  * shape-capture harness: the fused pipeline at n=2^20, B=256 contains NO
    (B, n)-sized intermediate anywhere in its jaxpr (and the harness itself
    is validated against the legacy path, which does contain one)
  * Quantile scatter-add path == one_hot+einsum oracle == Pallas sketch
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (Mean, Quantile, Std, Var, bootstrap,
                        bootstrap_chunked, multinomial_counts)
from repro.core.bootstrap import seed_from_key
from repro.core.delta import (poisson_delta_extend, poisson_delta_init,
                              poisson_delta_result)
from repro.core.reduce_api import _as_2d
from repro.core.ssabe import ssabe
from repro.kernels.weighted_hist import ops as wh_ops
from repro.kernels.weighted_hist.ref import (weighted_hist_onehot_ref,
                                             weighted_hist_scatter_ref)
from repro.kernels.weighted_stats import ops as ws_ops
from repro.kernels.weighted_stats.ref import weighted_moments_ref


# ----------------------------------------------------------------------------
# jaxpr shape-capture harness
# ----------------------------------------------------------------------------
def _walk_shapes(jaxpr, out):
    """Collect every intermediate aval shape, recursing into sub-jaxprs
    (pjit/scan/pallas_call bodies)."""
    for eqn in jaxpr.eqns:
        for v in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(v, "aval", None)
            if aval is not None and hasattr(aval, "shape"):
                out.append(tuple(aval.shape))
        for p in eqn.params.values():
            for q in (p if isinstance(p, (tuple, list)) else (p,)):
                if hasattr(q, "jaxpr") and hasattr(q.jaxpr, "eqns"):
                    _walk_shapes(q.jaxpr, out)       # ClosedJaxpr
                elif hasattr(q, "eqns"):
                    _walk_shapes(q, out)             # raw Jaxpr
    return out


def _max_intermediate_size(fn, *args):
    jaxpr = jax.make_jaxpr(fn)(*args)
    shapes = _walk_shapes(jaxpr.jaxpr, [])
    return max((int(np.prod(s)) for s in shapes if s), default=0)


class TestNoWeightMatrix:
    B, N = 256, 1 << 20

    def test_fused_pipeline_never_builds_Bn(self, key):
        """n=2^20, B=256: every intermediate in the traced fused pipeline is
        far smaller than the (B, n) weight matrix (268M elements)."""
        from repro.core.bootstrap import _fused_thetas
        x = jnp.zeros((self.N,), jnp.float32)
        biggest = _max_intermediate_size(
            lambda v, k: _fused_thetas(v, Mean(), self.B, k), x, key)
        assert biggest < self.B * self.N / 100, (
            f"largest intermediate has {biggest} elements — "
            f"(B, n) would be {self.B * self.N}")

    def test_harness_detects_legacy_weight_matrix(self, key):
        """Sanity: the same harness DOES flag the materialized-W path."""
        from repro.core.bootstrap import weights_for
        x = jnp.zeros((self.N,), jnp.float32)
        biggest = _max_intermediate_size(
            lambda v, k: weights_for("poisson", k, self.B, v.shape[0]),
            x, key)
        assert biggest >= self.B * self.N

    def test_quantile_scatter_never_builds_onehot(self, key):
        n, d, nbins = 1 << 15, 2, 2048
        q = Quantile(0.5, nbins=nbins)
        x = jnp.zeros((n, d), jnp.float32)
        biggest = _max_intermediate_size(
            lambda v: q.update(q.init_state(d), v).counts, x)
        assert biggest < n * d * nbins / 100, (
            f"largest intermediate has {biggest} elements — "
            f"one_hot would be {n * d * nbins}")

    def test_quantile_fused_pipeline_never_builds_Bn_or_onehot(self, key):
        """ISSUE-3 acceptance: the fused Quantile bootstrap at n=2^20,
        B=256 allocates neither the (B, n) weight matrix (268M elements)
        nor any (n, d, nbins) one-hot (2.1G elements) — the largest
        intermediate is the per-tile one-hot plus the (B, d, nbins)
        sketch."""
        from repro.core.bootstrap import _fused_thetas
        nbins = 2048
        q = Quantile(0.5, nbins=nbins, lo=-8.0, hi=8.0)
        x = jnp.zeros((self.N,), jnp.float32)
        biggest = _max_intermediate_size(
            lambda v, k: _fused_thetas(v, q, self.B, k), x, key)
        assert biggest < self.B * self.N / 100, (
            f"largest intermediate has {biggest} elements — "
            f"(B, n) would be {self.B * self.N}")
        assert biggest < self.N * nbins / 100, (
            f"largest intermediate has {biggest} elements — "
            f"(n, d, nbins) would be {self.N * nbins}")


# ----------------------------------------------------------------------------
# fused moments vs oracles
# ----------------------------------------------------------------------------
class TestFusedMoments:
    @pytest.mark.parametrize("B,n,d", [
        (1, 8, 1), (7, 130, 5), (32, 1000, 1), (64, 2048, 3), (129, 700, 2),
    ])
    def test_matches_implicit_weights_oracle(self, key, B, n, d):
        """Fused output == contracting the materialized implicit weights."""
        x = jax.random.normal(key, (n, d))
        W = ws_ops.implicit_weights(42, B, n)
        wt_r, s1_r, s2_r = weighted_moments_ref(W, x)
        for backend in ("scan", "pallas_interpret"):
            wt, s1, s2 = ws_ops.fused_poisson_moments(42, x, B,
                                                      backend=backend)
            np.testing.assert_allclose(wt, wt_r[:, 0], rtol=1e-6)
            # tile-sequential accumulation != one big dot, so f32 tolerance
            np.testing.assert_allclose(s1, s1_r, rtol=5e-4, atol=1e-4)
            np.testing.assert_allclose(s2, s2_r, rtol=5e-4, atol=1e-4)

    def test_implicit_weights_bit_identical_to_poisson_counts(self):
        """The fast jnp materializer must reproduce the kernel tile
        discipline exactly (same threefry folds, same ladder)."""
        from repro.kernels.poisson_counts import ops as pc_ops
        for B, n in [(5, 100), (129, 1000), (64, 512)]:
            a = ws_ops.implicit_weights(13, B, n)
            b = pc_ops.poisson_counts(13, B, n, backend="pallas_interpret")
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_scan_equals_interpret(self, key, lane_padded):
        x = jax.random.normal(key, (900, 4))
        a = ws_ops.fused_poisson_moments(9, lane_padded(x), 48,
                                         backend="scan")
        b = ws_ops.fused_poisson_moments(9, x, 48,
                                         backend="pallas_interpret")
        for u, v in zip((a[0], a[1][:, :4], a[2][:, :4]), b):
            np.testing.assert_array_equal(np.asarray(u), np.asarray(v))

    def test_deterministic_and_seed_sensitive(self, key):
        x = jax.random.normal(key, (512,))
        a = ws_ops.fused_poisson_moments(5, x, 32)
        b = ws_ops.fused_poisson_moments(5, x, 32)
        c = ws_ops.fused_poisson_moments(6, x, 32)
        np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))
        assert not np.array_equal(np.asarray(a[1]), np.asarray(c[1]))

    def test_n_valid_masks_padding(self, key):
        """Zero-padded tail + n_valid == the unpadded computation."""
        n, pad = 700, 1024 - 700
        x = jax.random.normal(key, (n, 2))
        xp = jnp.pad(x, ((0, pad), (0, 0)))
        a = ws_ops.fused_poisson_moments(3, x, 16)
        b = ws_ops.fused_poisson_moments(3, xp, 16, n_valid=n)
        np.testing.assert_allclose(np.asarray(a[0]), np.asarray(b[0]),
                                   rtol=1e-6)
        np.testing.assert_allclose(np.asarray(a[1]), np.asarray(b[1]),
                                   rtol=1e-6)


class TestInKernelWeightStatistics:
    def test_moments_match_poisson1(self):
        """mean/var of in-kernel weights vs jax.random.poisson."""
        W = ws_ops.implicit_weights(7, 256, 4096)
        ref = jax.random.poisson(jax.random.PRNGKey(7), 1.0,
                                 (256, 4096)).astype(jnp.float32)
        assert abs(float(W.mean()) - float(ref.mean())) < 0.02
        assert abs(float(W.var()) - float(ref.var())) < 0.03
        assert abs(float(W.mean()) - 1.0) < 0.01
        assert abs(float(W.var()) - 1.0) < 0.02

    def test_bootstrap_fused_matches_oracle_distributionally(self, key):
        """bootstrap(..., backend="fused_rng") thetas match the
        jax.random.poisson oracle: same SE scale, same CLT prediction."""
        n = 4000
        x = jax.random.normal(key, (n,)) * 3.0 + 50.0
        r_oracle = bootstrap(x, Mean(), B=256, key=key, engine="poisson")
        r_fused = bootstrap(x, Mean(), B=256, key=key, backend="fused_rng")
        clt = float(jnp.std(x) / jnp.sqrt(n))
        assert abs(r_fused.report.se - clt) / clt < 0.25
        assert abs(r_fused.cv - r_oracle.cv) / r_oracle.cv < 0.5
        np.testing.assert_allclose(np.ravel(r_fused.estimate),
                                   np.ravel(r_oracle.estimate), rtol=1e-5)

    @pytest.mark.parametrize("stat_cls", [Mean, Var, Std])
    def test_fused_stats_agree_with_legacy(self, key, stat_cls):
        x = jax.random.normal(key, (2048,)) * 1.5 + 4
        r_jnp = bootstrap(x, stat_cls(), B=64, key=key)
        r_fus = bootstrap(x, stat_cls(), B=64, key=key, backend="fused_rng")
        assert abs(r_fus.cv - r_jnp.cv) / (abs(r_jnp.cv) + 1e-12) < 0.6
        np.testing.assert_allclose(np.ravel(r_fus.estimate),
                                   np.ravel(r_jnp.estimate), rtol=1e-5)

    def test_fused_requires_poisson_engine(self, key):
        with pytest.raises(ValueError):
            bootstrap(jnp.ones(32), Mean(), B=4, key=key,
                      engine="multinomial", backend="fused_rng")

    def test_custom_stat_falls_back(self, key):
        """A statistic WITHOUT a fused path (every built-in now has one)
        still works under fused_rng via the implicit-weights fallback."""
        from repro.core.reduce_api import Mean

        class NoFusedMean(Mean):
            def fused_poisson_states(self, seed, values, B, n_valid=None):
                return None

        x = jax.random.normal(key, (1000,)) + 5
        r_fb = bootstrap(x, NoFusedMean(), B=16, key=key,
                         backend="fused_rng")
        r_fu = bootstrap(x, Mean(), B=16, key=key, backend="fused_rng")
        # fallback materializes the SAME implicit weights → same thetas
        np.testing.assert_allclose(np.asarray(r_fb.thetas),
                                   np.asarray(r_fu.thetas), rtol=1e-5)


# ----------------------------------------------------------------------------
# delta maintenance + chunked + ssabe under the fused backend
# ----------------------------------------------------------------------------
class TestFusedDelta:
    def test_extend_exact_vs_explicit_weights(self, key):
        """poisson_delta_extend under fused_rng == updating with the
        materialized implicit weights of each step (bit-level key
        discipline: seed_i = offset_seed(seed_from_key(key), i), distinct
        per step by construction and int32-overflow-safe)."""
        from repro.core.bootstrap import offset_seed
        B = 32
        x = jax.random.normal(key, (900, 2))
        pieces = (x[:400], x[400:])

        pd = poisson_delta_init(Mean(), B, 2, key, backend="fused_rng")
        for piece in pieces:
            pd = poisson_delta_extend(pd, piece)
        thetas = poisson_delta_result(pd, Mean()(x)).thetas

        stat = Mean()
        states = jax.vmap(lambda _: stat.init_state(2))(jnp.arange(B))
        for step, piece in enumerate(pieces):
            w = ws_ops.implicit_weights(
                offset_seed(seed_from_key(key), step), B, piece.shape[0])
            states = jax.vmap(lambda s, wr: stat.update(s, piece, wr),
                              in_axes=(0, 0))(states, w)
        ref = jax.vmap(stat.finalize)(states)
        np.testing.assert_allclose(np.asarray(thetas), np.asarray(ref),
                                   rtol=1e-5)

    def test_cv_comparable_to_jnp_backend(self, key):
        x = jax.random.normal(key, (3000,)) * 2 + 9
        res = {}
        for backend in (None, "fused_rng"):
            pd = poisson_delta_init(Mean(), 128, 1, key, backend=backend)
            for piece in (x[:1000], x[1000:]):
                pd = poisson_delta_extend(pd, piece)
            res[backend] = poisson_delta_result(pd, Mean()(x)).cv
        assert abs(res["fused_rng"] - res[None]) / res[None] < 0.5


class TestFusedChunked:
    def test_matches_unchunked_distribution(self, key):
        x = jax.random.normal(key, (3000,)) * 2 + 5
        r_plain = bootstrap(x, Mean(), B=128, key=key, backend="fused_rng")
        r_chunk = bootstrap_chunked(x, Mean(), B=128, key=key, chunk=512,
                                    backend="fused_rng")
        assert abs(r_plain.cv - r_chunk.cv) / r_plain.cv < 0.5
        np.testing.assert_allclose(np.ravel(r_plain.estimate),
                                   np.ravel(r_chunk.estimate), rtol=1e-5)

    def test_ragged_tail_masked(self, key):
        """w_tot must ignore the zero-padded tail of the last chunk."""
        x = jax.random.normal(key, (1001,)) + 3.0
        r = bootstrap_chunked(x, Mean(), B=32, key=key, chunk=256,
                              backend="fused_rng")
        assert r.n == 1001
        assert np.isfinite(r.cv)
        assert abs(float(np.ravel(r.estimate)[0]) - 3.0) < 0.3


class TestFusedSSABE:
    def test_ssabe_fused_close_to_jnp(self, key):
        x = jax.random.normal(key, (1000,)) + 5
        r_jnp = ssabe(x, Mean(), sigma=0.05, tau=0.01, key=key)
        r_fus = ssabe(x, Mean(), sigma=0.05, tau=0.01, key=key,
                      backend="fused_rng")
        assert len(r_fus.cv_history_n) == 5
        # same stopping structure, comparable estimates
        assert r_fus.B <= r_jnp.B * 4 and r_jnp.B <= r_fus.B * 4


# ----------------------------------------------------------------------------
# histogram sketch / Quantile
# ----------------------------------------------------------------------------
class TestWeightedHist:
    @pytest.mark.parametrize("n,d,nbins", [
        (100, 1, 128), (515, 3, 256), (1000, 5, 2048),
        (300, 2, 2000),   # nbins not a 128 multiple: lane padding must
                          # not shift bin edges or drop top-bin mass
    ])
    def test_kernel_and_scatter_match_onehot_oracle(self, key, n, d, nbins):
        x = jax.random.uniform(key, (n, d))
        w = jnp.abs(jax.random.normal(jax.random.fold_in(key, 1), (n,)))
        lo, hi = jnp.zeros((d,)), jnp.ones((d,))
        ref = weighted_hist_onehot_ref(x, w, lo, hi, nbins)
        np.testing.assert_allclose(
            np.asarray(weighted_hist_scatter_ref(x, w, lo, hi, nbins)),
            np.asarray(ref), rtol=1e-5, atol=1e-5)
        for backend in ("jnp", "pallas_interpret"):
            out = wh_ops.weighted_histogram(x, w, lo, hi, nbins,
                                            backend=backend)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       rtol=1e-5, atol=1e-4)

    def test_quantile_update_matches_onehot_oracle(self, key):
        q = Quantile(0.5, nbins=256, lo=-4.0, hi=4.0)
        x = jax.random.normal(key, (777, 2))
        w = jnp.abs(jax.random.normal(jax.random.fold_in(key, 2), (777,)))
        st = q.update(q.init_state(2), x, w)
        ref = weighted_hist_onehot_ref(
            jnp.clip(x, -4.0, 4.0), w, jnp.full((2,), -4.0),
            jnp.full((2,), 4.0), 256)
        np.testing.assert_allclose(np.asarray(st.counts), np.asarray(ref),
                                   rtol=1e-5, atol=1e-4)

    def test_quantile_kernel_backend_matches_default(self, key):
        x = jax.random.normal(key, (513,)) * 2
        for backend in ("pallas_interpret",):
            q0 = Quantile(0.25, nbins=512, lo=-8.0, hi=8.0)
            qk = Quantile(0.25, nbins=512, lo=-8.0, hi=8.0, backend=backend)
            s0 = q0.update(q0.init_state(1), x)
            sk = qk.update(qk.init_state(1), x)
            np.testing.assert_allclose(np.asarray(sk.counts),
                                       np.asarray(s0.counts),
                                       rtol=1e-5, atol=1e-4)
            np.testing.assert_allclose(float(q0.finalize(s0)),
                                       float(qk.finalize(sk)), rtol=1e-6)

    def test_quantile_vmaps_over_bootstrap_axis(self, key):
        """The scatter path must batch over the B resample axis."""
        x = jax.random.normal(key, (800,)) + 7
        q = Quantile(0.5, nbins=512, lo=0.0, hi=14.0)
        r = bootstrap(x, q, B=24, key=key)
        assert r.thetas.shape[0] == 24
        assert abs(float(np.ravel(r.estimate)[0]) - 7.0) < 0.2


class TestFusedQuantile:
    """Quantile's fused_poisson_states: the last materialized fallback in
    fused_resample_states is gone — the histogram sketch accumulates under
    in-kernel Poisson(1) weights."""

    @pytest.mark.parametrize("B,n,d,nbins", [
        (1, 8, 1, 128), (7, 300, 2, 256), (32, 1000, 1, 2048),
        (129, 700, 3, 200),   # nbins not a 128 multiple: lane padding
    ])
    def test_matches_implicit_weights_oracle(self, key, B, n, d, nbins):
        """Fused sketch == scatter-adding the materialized implicit
        weights, on both lowerings."""
        x = jax.random.uniform(key, (n, d))
        lo, hi = jnp.zeros((d,)), jnp.ones((d,))
        W = ws_ops.implicit_weights(42, B, n)
        ref = jnp.stack([weighted_hist_scatter_ref(x, W[b], lo, hi, nbins)
                         for b in range(B)])
        for backend in ("scan", "pallas_interpret"):
            out = wh_ops.fused_poisson_hist(42, x, lo, hi, nbins, B,
                                            backend=backend)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       rtol=1e-5, atol=1e-4)

    def test_bootstrap_fused_matches_materialized_fallback(self, key):
        """bootstrap(Quantile, fused_rng) == vmapped scatter updates under
        the SAME implicit weights (the pre-ISSUE-3 fallback semantics)."""
        x = jax.random.normal(key, (1000,)) + 5
        q = Quantile(0.5, nbins=512, lo=0.0, hi=10.0)
        r = bootstrap(x, q, B=16, key=key, backend="fused_rng")
        assert np.isfinite(r.cv)
        assert abs(float(np.ravel(r.estimate)[0]) - 5.0) < 0.3
        from repro.core.bootstrap import seed_from_key
        W = ws_ops.implicit_weights(seed_from_key(key), 16, 1000)
        x2 = x[:, None]
        ref = jax.vmap(lambda wr: q.finalize(
            q.update(q.init_state(1), x2, wr)))(W)
        np.testing.assert_allclose(np.asarray(r.thetas), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_n_valid_masks_padding(self, key):
        """Without the n_valid column mask the zero-padded tail would land
        spurious mass in bin 0 of every resample."""
        n, pad = 700, 1024 - 700
        x = jax.random.uniform(key, (n, 1)) * 0.9 + 0.05
        xp = jnp.pad(x, ((0, pad), (0, 0)))
        a = wh_ops.fused_poisson_hist(3, x, 0.0, 1.0, 128, 16)
        b = wh_ops.fused_poisson_hist(3, xp, 0.0, 1.0, 128, 16, n_valid=n)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)

    def test_chunked_quantile_streams_through_sketch(self, key):
        x = jax.random.normal(key, (3000,)) * 2 + 5
        q = Quantile(0.5, nbins=1024, lo=-5.0, hi=15.0)
        r_plain = bootstrap(x, q, B=64, key=key, backend="fused_rng")
        r_chunk = bootstrap_chunked(x, q, B=64, key=key, chunk=512,
                                    backend="fused_rng")
        assert abs(float(np.ravel(r_chunk.estimate)[0]) - 5.0) < 0.3
        assert np.isfinite(r_chunk.cv)
        assert abs(r_plain.cv - r_chunk.cv) / (r_plain.cv + 1e-12) < 1.0

    def test_delta_maintenance_fused_quantile(self, key):
        """poisson_delta_extend(Quantile, fused_rng) == scatter updates
        with the per-step materialized implicit weights."""
        from repro.core.bootstrap import offset_seed
        B = 16
        q = Quantile(0.5, nbins=256, lo=-5.0, hi=5.0)
        x = jax.random.normal(key, (900, 1))
        pieces = (x[:400], x[400:])
        pd = poisson_delta_init(q, B, 1, key, backend="fused_rng")
        for piece in pieces:
            pd = poisson_delta_extend(pd, piece)
        thetas = poisson_delta_result(pd, q(x)).thetas

        states = jax.vmap(lambda _: q.init_state(1))(jnp.arange(B))
        for step, piece in enumerate(pieces):
            w = ws_ops.implicit_weights(
                offset_seed(seed_from_key(key), step), B, piece.shape[0])
            states = jax.vmap(lambda s, wr: q.update(s, piece, wr),
                              in_axes=(0, 0))(states, w)
        ref = jax.vmap(q.finalize)(states)
        np.testing.assert_allclose(np.asarray(thetas), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_quantile_backend_routes_fused_kernel(self, key):
        """Quantile(backend="pallas_interpret") routes the fused sketch
        kernel; result matches the default scan lowering."""
        x = jax.random.normal(key, (513,)) * 2
        q0 = Quantile(0.25, nbins=512, lo=-8.0, hi=8.0)
        qk = Quantile(0.25, nbins=512, lo=-8.0, hi=8.0,
                      backend="pallas_interpret")
        s0 = q0.fused_poisson_states(11, x[:, None], 8)
        sk = qk.fused_poisson_states(11, x[:, None], 8)
        np.testing.assert_allclose(np.asarray(sk.counts),
                                   np.asarray(s0.counts),
                                   rtol=1e-5, atol=1e-4)


class TestBinBlockedHist:
    """ROADMAP TPU-tiling knob: ``block_bins`` tiles the d·nbins OUTPUT
    axis of the fused hist kernel so one (block_b, block_bins) window is
    VMEM-resident per grid cell instead of the whole (block_b, d·out_bins)
    block.  Results must be identical to the untiled kernel and the scan
    lowering — the weight tile keying is (seed, b-tile, n-tile) only."""

    @pytest.mark.parametrize("n,d,nbins,block_bins", [
        (700, 2, 256, 128),    # 2 output blocks per dim
        (513, 3, 300, 128),    # nbins not a block multiple: 3 blocks
        (1000, 1, 512, 256),   # d=1 (dim-blocking alone could not tile)
    ])
    def test_interpret_matches_scan_with_multiple_output_blocks(
            self, key, n, d, nbins, block_bins):
        out_bins = nbins + (-nbins) % block_bins
        assert out_bins // block_bins >= 2, "shape must exercise >=2 blocks"
        x = jax.random.uniform(key, (n, d)) * 0.9 + 0.05
        ref = wh_ops.fused_poisson_hist(42, x, 0.0, 1.0, nbins, 16,
                                        backend="scan")
        out = wh_ops.fused_poisson_hist(42, x, 0.0, 1.0, nbins, 16,
                                        backend="pallas_interpret",
                                        block_bins=block_bins)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-4)

    def test_matches_untiled_kernel_and_masks_padding(self, key):
        n, pad = 700, 1024 - 700
        x = jax.random.uniform(key, (n, 1)) * 0.9 + 0.05
        xp = jnp.pad(x, ((0, pad), (0, 0)))
        untiled = wh_ops.fused_poisson_hist(3, x, 0.0, 1.0, 256, 16,
                                            backend="pallas_interpret")
        tiled = wh_ops.fused_poisson_hist(3, xp, 0.0, 1.0, 256, 16,
                                          n_valid=n,
                                          backend="pallas_interpret",
                                          block_bins=128)
        np.testing.assert_allclose(np.asarray(tiled), np.asarray(untiled),
                                   rtol=1e-6)


class TestHistEdgePolicy:
    """Out-of-range/NaN policy (clip into edge bins, drop NaN), identical
    across scatter ref, one-hot oracle, Pallas sketch and fused paths."""

    def _all_paths(self, x, w, lo, hi, nbins):
        d = x.shape[1]
        lo = jnp.full((d,), lo, jnp.float32)
        hi = jnp.full((d,), hi, jnp.float32)
        yield "scatter", weighted_hist_scatter_ref(x, w, lo, hi, nbins)
        yield "onehot", weighted_hist_onehot_ref(x, w, lo, hi, nbins)
        yield "kernel", wh_ops.weighted_histogram(
            x, w, lo, hi, nbins, backend="pallas_interpret")

    def test_upper_edge_lands_in_top_bin(self):
        """x == hi exactly must keep its mass (top bin), not be dropped —
        on every path."""
        x = jnp.array([[0.0], [0.5], [1.0]])
        w = jnp.ones((3,))
        for name, counts in self._all_paths(x, w, 0.0, 1.0, 4):
            counts = np.asarray(counts)
            assert counts[0, -1] == 1.0, name        # x == hi → top bin
            assert counts[0, 0] == 1.0, name         # x == lo → bin 0
            assert counts.sum() == 3.0, name

    def test_out_of_range_clips_including_inf(self):
        x = jnp.array([[-7.0], [2.5], [jnp.inf], [-jnp.inf]])
        w = jnp.ones((4,))
        for name, counts in self._all_paths(x, w, 0.0, 1.0, 8):
            counts = np.asarray(counts)
            assert counts[0, 0] == 2.0, name         # -7, -inf → bin 0
            assert counts[0, -1] == 2.0, name        # 2.5, +inf → top bin
            assert counts.sum() == 4.0, name

    def test_nan_mass_dropped_everywhere(self, key):
        x = jax.random.uniform(key, (64, 2))
        x = x.at[3, 0].set(jnp.nan).at[17, 1].set(jnp.nan)
        w = jnp.abs(jax.random.normal(jax.random.fold_in(key, 1), (64,)))
        outs = dict(self._all_paths(x, w, 0.0, 1.0, 32))
        total = float(jnp.sum(w) * 2 - w[3] - w[17])
        for name, counts in outs.items():
            counts = np.asarray(counts)
            assert np.isfinite(counts).all(), name
            np.testing.assert_allclose(counts.sum(), total, rtol=1e-5,
                                       err_msg=name)
        np.testing.assert_allclose(np.asarray(outs["scatter"]),
                                   np.asarray(outs["onehot"]), rtol=1e-5,
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(outs["kernel"]),
                                   np.asarray(outs["onehot"]), rtol=1e-5,
                                   atol=1e-4)

    def test_nan_dropped_in_fused_sketch(self, key):
        """The fused bootstrap sketch must drop NaN mass identically on
        both lowerings (f32→int32 NaN casts are platform-defined — only
        the mask keeps this deterministic)."""
        x = jax.random.uniform(key, (300, 1))
        x = x.at[5, 0].set(jnp.nan)
        outs = [wh_ops.fused_poisson_hist(9, x, 0.0, 1.0, 64, 8,
                                          backend=b)
                for b in ("scan", "pallas_interpret")]
        W = np.asarray(ws_ops.implicit_weights(9, 8, 300))
        expect = W.sum(axis=1) - W[:, 5]             # row totals minus NaN
        for out in outs:
            assert np.isfinite(np.asarray(out)).all()
            np.testing.assert_allclose(np.asarray(out).sum(axis=(1, 2)),
                                       expect, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(outs[0]),
                                   np.asarray(outs[1]), rtol=1e-6)

    def test_quantile_update_drops_nan(self, key):
        q = Quantile(0.5, nbins=64, lo=0.0, hi=1.0)
        x = jnp.array([0.2, jnp.nan, 0.8])
        st = q.update(q.init_state(1), x)
        assert float(np.asarray(st.counts).sum()) == 2.0
        assert np.isfinite(float(q.finalize(st)))


class TestBf16Moments:
    """ROADMAP bf16 study: x/w enter the dots in bf16, accumulators f32."""

    def test_close_to_f32_and_wtot_exact(self, key):
        x = jax.random.normal(key, (4096, 4)) * 3 + 7
        wt32, s1_32, s2_32 = ws_ops.fused_poisson_moments(5, x, 64)
        wtbf, s1_bf, s2_bf = ws_ops.fused_poisson_moments(
            5, x, 64, dtype=jnp.bfloat16)
        assert all(a.dtype == jnp.float32 for a in (wtbf, s1_bf, s2_bf))
        # weight totals never touch bf16 — bit-exact
        np.testing.assert_array_equal(np.asarray(wt32), np.asarray(wtbf))
        # bf16 has ~3 decimal digits; summed over tiles the relative error
        # stays well under 1% for n=4096
        np.testing.assert_allclose(np.asarray(s1_bf), np.asarray(s1_32),
                                   rtol=1e-2)
        np.testing.assert_allclose(np.asarray(s2_bf), np.asarray(s2_32),
                                   rtol=1e-2)

    def test_scan_equals_interpret_bf16(self, key):
        x = jax.random.normal(key, (900, 2))
        a = ws_ops.fused_poisson_moments(9, x, 32, backend="scan",
                                         dtype=jnp.bfloat16)
        b = ws_ops.fused_poisson_moments(9, x, 32,
                                         backend="pallas_interpret",
                                         dtype=jnp.bfloat16)
        for u, v in zip(a, b):
            np.testing.assert_allclose(np.asarray(u), np.asarray(v),
                                       rtol=2e-3)

    def test_f32_default_unchanged(self, key):
        """dtype defaults to f32 — bit-identical to an explicit f32 ask."""
        x = jax.random.normal(key, (700, 3))
        a = ws_ops.fused_poisson_moments(4, x, 16)
        b = ws_ops.fused_poisson_moments(4, x, 16, dtype=jnp.float32)
        for u, v in zip(a, b):
            np.testing.assert_array_equal(np.asarray(u), np.asarray(v))


class TestShardedOracle:
    """Single-device coverage of sharded_fused_states (the mesh run is
    bit-compared against this oracle in tests/test_sharded_bootstrap.py)."""

    def test_chunk_and_step_mutually_exclusive(self):
        """Stream index (step + c)·nshards + shard aliases across (step,
        chunk) pairs — the combination must raise, not correlate."""
        from repro.core import Mean, sharded_fused_states
        x = jnp.ones((64, 1))
        with pytest.raises(ValueError, match="mutually exclusive"):
            sharded_fused_states(Mean(), 7, x, 8, nshards=2, chunk=16,
                                 step=1)

    def test_nshards1_matches_unsharded(self, key):
        from repro.core import Mean, sharded_fused_states
        from repro.core.bootstrap import fused_resample_states
        x = jax.random.normal(key, (300, 2))
        a = sharded_fused_states(Mean(), 7, x, 16, nshards=1)
        b = fused_resample_states(Mean(), jnp.int32(7), x, 16)
        for u, v in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b)):
            np.testing.assert_array_equal(np.asarray(u), np.asarray(v))


class TestMultinomialScatter:
    def test_single_dispatch_matches_per_row_oracle(self, key):
        """The flattened scatter must equal the old per-row vmap(hist)."""
        B, n = 16, 257
        counts = multinomial_counts(key, B=B, n=n)
        idx = jax.random.randint(key, (B, n), 0, n)

        def hist(row):
            return jnp.zeros((n,), jnp.int32).at[row].add(1)

        ref = jax.vmap(hist)(idx)
        np.testing.assert_array_equal(np.asarray(counts), np.asarray(ref))
