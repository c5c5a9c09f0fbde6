"""GROUP BY for the bootstrap (ISSUE-7): keyed states through every layer.

The load-bearing contract: under ``backend="fused_rng"`` a
``GroupedStatistic``'s key-g thetas are BITWISE equal to running the inner
statistic alone with ``valid_mask = (key == g)`` under the same seed —
one shared implicit Poisson(1) weight stream (common random numbers),
segment-reduced per key by exact 0/1 mask multiplies.  Verified here on
the single-device, chunked, and streaming drivers (the 8-shard mesh lives
in tests/test_sharded_bootstrap.py's subprocess), plus the keyed accuracy
reports, the early-validation satellites, ``Quantile.with_range``
preservation, and a jaxpr capture proving no (B, n) or (n, G)
intermediate exists at n=2^20, B=256, G=64.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import (GroupedStatistic, KeyedAccuracyReport, Mean,
                        Quantile, StatisticGroup, bootstrap,
                        bootstrap_chunked, bootstrap_streaming,
                        sharded_fused_states)
from repro.core.accuracy import report_for
from repro.core.bootstrap import (fused_resample_states, offset_seed,
                                  seed_from_key)
from repro.core.reduce_api import (Count, KMeansStep, Statistic, Sum, Var,
                                   bind_params, split_params)
from repro.data.store import ShardedStore

N, D, G, B, SEED = 700, 2, 4, 32, 1234


@pytest.fixture(scope="module")
def keyed():
    """(values_with_key_column, data_columns, key_column) fixture."""
    rng = np.random.default_rng(0)
    x = rng.normal(size=(N, D)).astype(np.float32)
    gid = rng.integers(0, G, size=N).astype(np.float32)
    vals = jnp.asarray(np.concatenate([x, gid[:, None]], axis=1))
    return vals, vals[:, :D], vals[:, D]


def _tree_bitwise(a, b):
    ok = jax.tree_util.tree_map(
        lambda u, v: bool(np.array_equal(np.asarray(u), np.asarray(v))),
        a, b)
    assert all(jax.tree_util.tree_leaves(ok)), ok


class _CustomInner(Statistic):
    """Mergeable custom statistic with NO fused hook — exercises the
    GroupedStatistic -> fused_poisson_tiled generic tile path."""
    moment_powers = None

    def init_state(self, dim):
        return (jnp.zeros(()), jnp.zeros((dim,)))

    def update(self, state, x, w=None):
        from repro.core.reduce_api import _w
        w = _w(x, w)
        wt, s1 = state
        return wt + jnp.sum(w), s1 + w @ jnp.asarray(x, jnp.float32)

    def merge(self, a, b):
        return a[0] + b[0], a[1] + b[1]

    def finalize(self, state):
        return state[1] / jnp.maximum(state[0], 1.0)


class _NonMergeable(Mean):
    mergeable = False


def _inners():
    cent = jnp.asarray(np.random.default_rng(2)
                       .normal(size=(3, D)).astype(np.float32))
    return [Mean(), Sum(), Count(), Var(),
            Quantile(0.5, lo=-4.0, hi=4.0, nbins=64),
            KMeansStep(cent), _CustomInner()]


# ---------------------------------------------------------------------------
# construction / protocol
# ---------------------------------------------------------------------------
class TestConstruction:
    def test_rejects_nesting(self):
        with pytest.raises(TypeError, match="nest"):
            GroupedStatistic(GroupedStatistic(Mean(), 2), 3)

    def test_rejects_group_inner(self):
        with pytest.raises(TypeError, match="StatisticGroup"):
            GroupedStatistic(StatisticGroup([Mean()]), 2)

    def test_rejects_non_statistic(self):
        with pytest.raises(TypeError):
            GroupedStatistic(lambda x: x, 2)

    def test_rejects_bad_backend(self):
        with pytest.raises(ValueError, match="backend"):
            GroupedStatistic(Mean(), 2, backend="cuda")

    def test_rejects_bad_num_groups(self):
        with pytest.raises(ValueError, match="num_groups"):
            GroupedStatistic(Mean(), 0)

    def test_needs_key_column(self):
        with pytest.raises(ValueError, match="key"):
            GroupedStatistic(Mean(), 2)._split_key(jnp.ones((5,)))

    def test_mergeable_follows_inner(self):
        assert GroupedStatistic(Mean(), 2).mergeable
        assert not GroupedStatistic(_NonMergeable(), 2).mergeable

    def test_split_bind_params_roundtrip(self):
        cent = jnp.asarray(np.random.default_rng(3)
                           .normal(size=(3, D)).astype(np.float32))
        stat = GroupedStatistic(KMeansStep(cent), G)
        spec, params = split_params(stat)
        assert params, "KMeansStep centroids must be threaded as params"
        rebound = bind_params(spec, params)
        assert isinstance(rebound, GroupedStatistic)
        np.testing.assert_array_equal(np.asarray(rebound.inner.centroids),
                                      np.asarray(cent))

    def test_update_matches_per_key_update(self, keyed):
        vals, x, gid = keyed
        stat = GroupedStatistic(Mean(), G)
        st = stat.update(stat.init_state(D + 1), vals)
        for g in range(G):
            ref = Mean().update(Mean().init_state(D), x,
                                (gid == g).astype(jnp.float32))
            _tree_bitwise(jax.tree_util.tree_map(lambda a: a[g], st), ref)


# ---------------------------------------------------------------------------
# the bitwise per-key contract, driver by driver
# ---------------------------------------------------------------------------
class TestSingleDevicePerKeyBitwise:
    @pytest.mark.parametrize("inner", _inners(),
                             ids=lambda s: type(s).__name__)
    def test_fused_thetas_per_key(self, inner, keyed):
        from repro.kernels.fused_multi.ops import fused_poisson_tiled
        vals, x, gid = keyed
        stat = GroupedStatistic(inner, G)
        thetas = jax.vmap(stat.finalize)(
            fused_resample_states(stat, SEED, vals, B))
        for g in range(G):
            mask = (gid == g).astype(jnp.float32)
            if inner.accumulator_key() is None and \
                    not hasattr(inner, "centroids"):
                # custom inner: its per-key-alone fused run is the same
                # generic tile scan (a whole-array update would sum the
                # n axis in one go — a different reduction order)
                ref_states = fused_poisson_tiled(inner, SEED, x, B,
                                                 valid_mask=mask)
            else:
                ref_states = fused_resample_states(inner, SEED, x, B,
                                                   valid_mask=mask)
            ref = jax.vmap(inner.finalize)(ref_states)
            _tree_bitwise(jax.tree_util.tree_map(lambda a: a[:, g], thetas),
                          ref)

    def test_interior_mask_composes(self, keyed):
        """valid_mask holes compose with key masks exactly:
        (w·valid)·keymask ≡ w·(valid·keymask) for 0/1 masks."""
        vals, x, gid = keyed
        rng = np.random.default_rng(1)
        hole = jnp.asarray((rng.random(N) > 0.3).astype(np.float32))
        stat = GroupedStatistic(Mean(), G)
        thetas = jax.vmap(stat.finalize)(
            fused_resample_states(stat, SEED, vals, B, valid_mask=hole))
        for g in range(G):
            ref = jax.vmap(Mean().finalize)(fused_resample_states(
                Mean(), SEED, x, B,
                valid_mask=hole * (gid == g).astype(jnp.float32)))
            _tree_bitwise(thetas[:, g], ref)

    def test_prefix_equals_n_valid(self, keyed):
        vals, _, _ = keyed
        stat = GroupedStatistic(Mean(), G)
        k = 500
        prefix = (jnp.arange(N) < k).astype(jnp.float32)
        a = fused_resample_states(stat, SEED, vals, B, n_valid=k)
        b = fused_resample_states(stat, SEED, vals, B, valid_mask=prefix)
        _tree_bitwise(a, b)

    def test_bootstrap_driver_keyed_report(self, keyed):
        vals, _, _ = keyed
        res = bootstrap(vals, GroupedStatistic(Mean(), G), B=B,
                        key=jax.random.PRNGKey(7), backend="fused_rng")
        assert res.thetas.shape[:2] == (B, G)
        assert isinstance(res.report, KeyedAccuracyReport)
        assert len(res.report.members) == G
        assert res.report.cv == max(res.report.cvs)
        assert res.report.cvs[res.report.worst_key] == res.report.cv


class TestScanPallasParity:
    def test_grouped_moments_scan_vs_pallas(self, keyed, lane_padded):
        from repro.kernels.weighted_stats.ops import fused_poisson_moments
        _, x, gid = keyed
        s = fused_poisson_moments(SEED, lane_padded(x), B, backend="scan",
                                  group_ids=gid, num_groups=G)
        k = fused_poisson_moments(SEED, x, B, backend="pallas_interpret",
                                  group_ids=gid, num_groups=G)
        _tree_bitwise((s[0], s[1][..., :D], s[2][..., :D]), k)

    def test_grouped_moments_masked_parity(self, keyed, lane_padded):
        from repro.kernels.weighted_stats.ops import fused_poisson_moments
        _, x, gid = keyed
        rng = np.random.default_rng(5)
        hole = jnp.asarray((rng.random(N) > 0.4).astype(np.float32))
        s = fused_poisson_moments(SEED, lane_padded(x), B, backend="scan",
                                  valid_mask=hole, group_ids=gid,
                                  num_groups=G)
        k = fused_poisson_moments(SEED, x, B, backend="pallas_interpret",
                                  valid_mask=hole, group_ids=gid,
                                  num_groups=G)
        _tree_bitwise((s[0], s[1][..., :D], s[2][..., :D]), k)

    def test_grouped_hist_pallas_raises(self, keyed):
        from repro.kernels.weighted_hist.ops import fused_poisson_hist
        _, x, gid = keyed
        with pytest.raises(ValueError, match="scan-only"):
            fused_poisson_hist(SEED, x, -4.0, 4.0, 32, B,
                               backend="pallas_interpret",
                               group_ids=gid, num_groups=G)

    def test_grouped_kmeans_pallas_raises(self, keyed):
        from repro.kernels.kmeans_assign.ops import fused_poisson_kmeans
        _, x, gid = keyed
        cent = jnp.zeros((3, D))
        with pytest.raises(ValueError, match="scan"):
            fused_poisson_kmeans(SEED, x, cent, B,
                                 backend="pallas_interpret",
                                 group_ids=gid, num_groups=G)

    def test_grouped_stream_mode_raises(self, keyed):
        from repro.kernels.weighted_stats.ops import fused_poisson_moments
        _, x, gid = keyed
        with pytest.raises(ValueError, match="group"):
            fused_poisson_moments(SEED, x, B, backend="pallas_interpret",
                                  stream=True, group_ids=gid, num_groups=G)


class TestChunkedAndStreamingPerKey:
    CHUNK = 256

    def test_chunked_per_key_oracle(self, keyed):
        """Chunked grouped thetas per key == the per-chunk per-key oracle
        (same offset_seed(base, i) streams, key mask composed with each
        chunk's validity prefix, merged)."""
        vals, _, _ = keyed
        key = jax.random.PRNGKey(11)
        stat = GroupedStatistic(Mean(), G)
        res = bootstrap_chunked(vals, stat, B=B, key=key, chunk=self.CHUNK,
                                backend="fused_rng")
        base = seed_from_key(key)
        pad = (-N) % self.CHUNK
        vp = jnp.pad(vals, ((0, pad), (0, 0)))
        nchunks = vp.shape[0] // self.CHUNK
        for g in range(G):
            acc = None
            for i in range(nchunks):
                ci = vp[i * self.CHUNK:(i + 1) * self.CHUNK]
                nv = min(max(N - i * self.CHUNK, 0), self.CHUNK)
                m = (jnp.arange(self.CHUNK) < nv).astype(jnp.float32) \
                    * (ci[:, D] == g)
                si = fused_resample_states(Mean(), offset_seed(base, i),
                                           ci[:, :D], B, valid_mask=m)
                acc = si if acc is None else \
                    jax.vmap(Mean().merge)(acc, si)
            ref = jax.vmap(Mean().finalize)(acc)
            _tree_bitwise(res.thetas[:, g], ref)

    def test_streaming_bitwise_equals_chunked(self, keyed):
        vals, _, _ = keyed
        key = jax.random.PRNGKey(11)
        store = ShardedStore.from_array(np.asarray(vals), split_size=123)
        sv = jnp.asarray(store.read_all())
        for inner in (Mean(), Quantile(0.5, lo=-4.0, hi=4.0, nbins=64)):
            stat = GroupedStatistic(inner, G)
            rc = bootstrap_chunked(sv, stat, B=B, key=key,
                                   chunk=self.CHUNK, backend="fused_rng")
            rs = bootstrap_streaming(store, stat, B=B, key=key,
                                     chunk=self.CHUNK)
            _tree_bitwise(rc.thetas, rs.thetas)
            _tree_bitwise(rc.estimate, rs.estimate)
            assert isinstance(rs.report, KeyedAccuracyReport)

    def test_sharded_sequential_per_key(self, keyed):
        vals, _, _ = keyed
        stat = GroupedStatistic(Mean(), G)
        st = sharded_fused_states(stat, SEED, vals, B, nshards=4)
        th = jax.vmap(stat.finalize)(st)
        m = -(-N // 4)
        vp = jnp.pad(vals, ((0, 4 * m - N), (0, 0)))
        for g in range(G):
            acc = None
            for i in range(4):
                loc = vp[i * m:(i + 1) * m]
                nv = min(max(N - i * m, 0), m)
                mask = (jnp.arange(m) < nv).astype(jnp.float32) \
                    * (loc[:, D] == g)
                si = fused_resample_states(Mean(), offset_seed(SEED, i),
                                           loc[:, :D], B, valid_mask=mask)
                acc = si if acc is None else \
                    jax.vmap(Mean().merge)(acc, si)
            _tree_bitwise(th[:, g], jax.vmap(Mean().finalize)(acc))


# ---------------------------------------------------------------------------
# keyed accuracy reports
# ---------------------------------------------------------------------------
class TestKeyedAccuracyReport:
    def test_report_for_splits_axis1(self):
        rng = np.random.default_rng(7)
        thetas = jnp.asarray(rng.normal(size=(16, 3, 2)).astype(np.float32)
                             + 5.0)
        rep = report_for(thetas, num_groups=3)
        assert isinstance(rep, KeyedAccuracyReport)
        assert len(rep.members) == 3
        from repro.core.accuracy import AccuracyReport
        for g in range(3):
            solo = AccuracyReport.from_thetas(thetas[:, g])
            assert rep.members[g].cv == solo.cv
        assert rep.cv == max(rep.cvs)
        assert rep.worst_key == int(np.argmax(rep.cvs))

    def test_report_for_without_groups_unchanged(self):
        t = jnp.ones((8, 2)) + jnp.arange(8)[:, None] * 0.01
        from repro.core.accuracy import AccuracyReport
        assert isinstance(report_for(t), AccuracyReport)


# ---------------------------------------------------------------------------
# satellite 1: early validation
# ---------------------------------------------------------------------------
class TestEarlyValidation:
    def _store(self):
        rng = np.random.default_rng(3)
        return ShardedStore.from_array(
            rng.normal(size=(200, 2)).astype(np.float32), split_size=50)

    def test_streaming_rejects_non_mergeable_naming_statistic(self):
        with pytest.raises(ValueError, match="_NonMergeable"):
            bootstrap_streaming(self._store(), _NonMergeable(), B=8,
                                key=jax.random.PRNGKey(0))

    def test_streaming_rejects_grouped_non_mergeable(self):
        with pytest.raises(ValueError, match="GroupedStatistic"):
            bootstrap_streaming(self._store(),
                                GroupedStatistic(_NonMergeable(), 2),
                                B=8, key=jax.random.PRNGKey(0))

    def test_streaming_backend_error_names_supported(self):
        with pytest.raises(ValueError, match="fused_rng") as ei:
            bootstrap_streaming(self._store(), Mean(), B=8,
                                key=jax.random.PRNGKey(0), backend="jnp")
        assert "'jnp'" in str(ei.value)

    def test_sharded_rejects_non_mergeable_naming_statistic(self):
        with pytest.raises(ValueError, match="_NonMergeable"):
            sharded_fused_states(_NonMergeable(), SEED,
                                 jnp.ones((64, 2)), 8, nshards=4)

    def test_grouped_kernel_validates_num_groups(self, keyed):
        from repro.kernels.weighted_stats.ops import fused_poisson_moments
        _, x, gid = keyed
        with pytest.raises(ValueError, match="num_groups"):
            fused_poisson_moments(SEED, x, B, group_ids=gid, num_groups=0)


# ---------------------------------------------------------------------------
# satellite 2: Quantile.with_range preserves every knob
# ---------------------------------------------------------------------------
class TestWithRangePreservesKnobs:
    def test_knobs_survive(self):
        q = Quantile(0.9, nbins=96, lo=0.0, hi=1.0,
                     backend="pallas_interpret", block_bins=32)
        r = q.with_range(-2.0, 2.0)
        assert (r.q, r.nbins, r.backend, r.block_bins) == \
            (0.9, 96, "pallas_interpret", 32)
        # with_range pads the requested range by its 1% pilot margin
        assert r.lo < -2.0 < 2.0 < r.hi

    def test_re_ranged_quantiles_share_slot_in_group(self):
        qa = Quantile(0.25, nbins=64, lo=0.0, hi=1.0).with_range(-4.0, 4.0)
        qb = Quantile(0.75, nbins=64, lo=qa.lo, hi=qa.hi)
        grp = StatisticGroup([qa, qb])
        assert len(grp.slots) == 1, \
            "re-ranged quantile must share the sketch accumulator slot"
        assert qa.accumulator_key() == qb.accumulator_key()


# ---------------------------------------------------------------------------
# jaxpr capture: the acceptance-scale memory contract
# ---------------------------------------------------------------------------
class TestNoMaterializedIntermediates:
    def test_no_Bn_or_nG_aval_at_scale(self):
        n, B_, G_ = 1 << 20, 256, 64
        stat = GroupedStatistic(Mean(), G_)
        big = jax.ShapeDtypeStruct((n, 3), jnp.float32)
        jaxpr = jax.make_jaxpr(
            lambda v: stat.fused_poisson_states(jnp.int32(7), v, B_))(big)
        shapes = []

        def visit(jx):
            for eqn in jx.eqns:
                for v in list(eqn.invars) + list(eqn.outvars):
                    aval = getattr(v, "aval", None)
                    if getattr(aval, "shape", None) is not None:
                        shapes.append(tuple(int(s) for s in aval.shape))
                for p in eqn.params.values():
                    if hasattr(p, "jaxpr"):
                        visit(p.jaxpr)
                    elif isinstance(p, (list, tuple)):
                        for q_ in p:
                            if hasattr(q_, "jaxpr"):
                                visit(q_.jaxpr)

        visit(jaxpr.jaxpr)
        bad = [s for s in shapes
               if (B_ in s and n in s) or (n in s and G_ in s)]
        assert not bad, f"materialized intermediates: {bad[:5]}"
        # nothing bigger than the input itself ever exists
        assert max(int(np.prod(s)) if s else 1 for s in shapes) <= n * 3


# ---------------------------------------------------------------------------
# keyed end-to-end: StratifiedSampler -> SSABE -> EarlSession worst-key stop
# ---------------------------------------------------------------------------
class TestKeyedSession:
    def _keyed_store(self, n=6000, g=3, seed=0):
        rng = np.random.default_rng(seed)
        keys = rng.integers(0, g, size=n)
        data = np.stack([rng.normal(loc=keys * 2.0, scale=0.5),
                         keys], axis=1).astype(np.float32)
        return ShardedStore.from_array(data, 512)

    def test_session_stops_on_worst_key(self):
        from repro.core.session import EarlSession
        from repro.data import StratifiedSampler

        G_ = 3
        store = self._keyed_store(g=G_)
        sampler = StratifiedSampler(store, num_groups=G_, seed=1)
        stat = GroupedStatistic(Mean(), G_)
        sess = EarlSession(sampler, stat, sigma=0.1, backend="fused_rng",
                           max_pilot=512)
        res = sess.run(jax.random.PRNGKey(0))
        assert res.reports is not None and len(res.reports) == G_
        if not res.fell_back:
            # the sigma gate is the WORST key's c_v: every key met it
            assert res.cv == max(r.cv for r in res.reports)
            assert all(r.cv <= sess.sigma for r in res.reports)
            assert res.history[-1]["member_cvs"] == \
                tuple(r.cv for r in res.reports)
        # per-key means of loc = 2*key survive the keyed pipeline
        est = np.asarray(res.result)
        for g in range(G_):
            assert abs(est[g, 0] - 2.0 * g) < 0.25

    def test_ssabe_gates_on_worst_key(self):
        from repro.core.ssabe import ssabe

        G_ = 3
        store = self._keyed_store(g=G_)
        pilot = jnp.asarray(store.read_all()[:1024])
        stat = GroupedStatistic(Mean(), G_)
        est = ssabe(pilot, stat, 0.1, 0.01, jax.random.PRNGKey(3),
                    N=store.N, backend="fused_rng")
        assert est.B >= 1 and est.n >= 1


class TestStratifiedPerKeyCorrection:
    """Per-key ``correct`` under stratified sampling (ISSUE-9 satellite):
    a stratified prefix samples key g at its OWN rate p_g, so keyed
    results must be corrected per key (``correct_per_key``) — a scalar
    whole-table p mis-scales every count-like inner."""

    def _skewed_store(self, n=8000, seed=0):
        """Key frequencies ~[0.75, 0.2, 0.05] — rare key 2 is what
        stratification oversamples relative to its frequency."""
        rng = np.random.default_rng(seed)
        keys = rng.choice(3, size=n, p=[0.75, 0.2, 0.05])
        data = np.stack([rng.normal(loc=1.0 + keys, scale=0.3),
                         keys], axis=1).astype(np.float32)
        return ShardedStore.from_array(data, 512)

    def test_correct_per_key_scales_each_slice_by_its_own_p(self):
        stat = GroupedStatistic(Sum(), 3)
        est = jnp.asarray([[10.0], [20.0], [30.0]])       # (G, ...) axis 0
        out = np.asarray(stat.correct_per_key(est, [0.5, 0.25, 1.0]))
        np.testing.assert_allclose(out[:, 0], [20.0, 80.0, 30.0])
        thetas = jnp.ones((B, 3, 1))                      # (B, G, ...) axis 1
        out = np.asarray(stat.correct_per_key(thetas, [0.5, 0.25, 1.0],
                                              key_axis=1))
        np.testing.assert_allclose(out[0, :, 0], [2.0, 4.0, 1.0])

    def test_correct_per_key_matches_masked_inner_oracle(self, keyed):
        """Key g's per-key-corrected thetas are bitwise equal to the
        masked-inner oracle corrected by p_g alone — correction is
        elementwise, so it preserves the base per-key contract."""
        vals, data, keycol = keyed
        stat = GroupedStatistic(Sum(), G)
        p_keys = [0.5, 0.25, 1.0, 0.8]
        thetas = jax.vmap(stat.finalize)(
            fused_resample_states(stat, SEED, vals, B))
        corrected = stat.correct_per_key(thetas, p_keys, key_axis=1)
        for g in range(G):
            mask = (keycol == g).astype(jnp.float32)
            ref = jax.vmap(Sum().finalize)(fused_resample_states(
                Sum(), SEED, data, B, valid_mask=mask))
            oracle = Sum().correct(ref, p_keys[g])
            _tree_bitwise(np.asarray(corrected)[:, g], oracle)

    def test_correct_per_key_validation(self):
        stat = GroupedStatistic(Sum(), 3)
        with pytest.raises(ValueError, match="p_keys"):
            stat.correct_per_key(jnp.ones((3, 1)), [0.5, 0.5])
        # p_g == 0 (stratum absent from the prefix) passes through
        out = stat.correct_per_key(jnp.ones((3, 1)), [0.5, 0.0, 1.0])
        np.testing.assert_allclose(np.asarray(out)[:, 0], [2.0, 1.0, 1.0])

    def test_poisson_delta_result_p_keys(self, keyed):
        from repro.core.delta import (poisson_delta_extend,
                                      poisson_delta_init,
                                      poisson_delta_result)
        vals, _, keycol = keyed
        stat = GroupedStatistic(Sum(), G)
        pd = poisson_delta_init(stat, B=B, dim=D + 1,
                                key=jax.random.PRNGKey(SEED),
                                backend="fused_rng")
        pd = poisson_delta_extend(pd, vals)
        p_keys = [0.5, 0.25, 1.0, 0.8]
        res = poisson_delta_result(pd, p_keys=p_keys)
        assert res.report.p_keys == tuple(p_keys)
        # key g's estimate is its raw sum scaled by 1/p_g
        raw = np.asarray(poisson_delta_result(pd).estimate)
        out = np.asarray(res.estimate)
        for g in range(G):
            np.testing.assert_allclose(out[g], raw[g] / p_keys[g],
                                       rtol=1e-6)

    def test_p_keys_requires_keyed_statistic(self):
        from repro.core.delta import (poisson_delta_extend,
                                      poisson_delta_init,
                                      poisson_delta_result)
        pd = poisson_delta_init(Sum(), B=8, dim=2,
                                key=jax.random.PRNGKey(0),
                                backend="fused_rng")
        pd = poisson_delta_extend(pd, jnp.ones((16, 2)))
        with pytest.raises(ValueError, match="keyed"):
            poisson_delta_result(pd, p_keys=[0.5])

    def test_stratified_session_corrects_sums_per_key(self):
        """End to end: a keyed SUM session over a StratifiedSampler with
        equal shares (rare keys heavily oversampled vs frequency) must
        recover every key's TRUE total — the whole-table p would inflate
        the rare key's sum by ~frequency/share."""
        from repro.core.session import EarlSession
        from repro.data import StratifiedSampler

        store = self._skewed_store()
        data = store.read_all()
        true = np.array([data[data[:, 1] == g, 0].sum() for g in range(3)])
        sampler = StratifiedSampler(store, num_groups=3, seed=1)
        sess = EarlSession(sampler, GroupedStatistic(Sum(), 3), sigma=0.05,
                           backend="fused_rng", max_pilot=512)
        res = sess.run(jax.random.PRNGKey(2))
        est = np.asarray(res.result)[:, 0]
        np.testing.assert_allclose(est, true, rtol=0.15)
        if res.n_used < store.N:
            # the naive whole-table correction is measurably wrong for
            # the rare key (sampled at ~1/3 share vs 5% frequency)
            n = res.n_used
            counts = sampler.stratum_counts(n)
            raw = est * (counts / np.maximum(sampler.stratum_sizes, 1))
            naive = raw * (store.N / n)
            assert abs(naive[2] - true[2]) > abs(est[2] - true[2])


# The hypothesis property suite for grouped segment-reduction lives in
# tests/test_grouped_properties.py (module-level importorskip, matching
# tests/test_properties.py) so this file runs even without hypothesis.
