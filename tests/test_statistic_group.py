"""StatisticGroup: single-pass multi-statistic bootstrap (ISSUE-5).

Covers the acceptance criteria:
  * jaxpr shape/stream capture at n=2^20, B=256: the group pipeline
    materializes NO (B, n) weight matrix and draws ONE threefry stream per
    tile (same eqn count as a single-statistic run — not one per member);
  * statistical equivalence vs per-member oracles: shared weights make the
    group's member thetas BITWISE equal to each member's dedicated fused
    run under the same key (joint CIs from common random numbers), on both
    the fused and the materialized backends;
  * a 1-member group is bitwise equal to the existing fused path;
  * slot dedup: Mean+Var+Std share one moment accumulator, same-range
    quantiles share one sketch;
  * the Pallas multi-kernel (interpret mode) matches the scan lowering;
  * KMeansStep and custom statistics consume the same cached weight tiles
    via the per-tile callback fallback;
  * group flows end-to-end through chunked / delta / SSABE / EarlSession
    (per-member reports, stop when ALL members meet sigma) and the sharded
    single-device oracle.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (EarlSession, GroupAccuracyReport, KMeansStep, Mean,
                        Quantile, Statistic, StatisticGroup, Std, Var,
                        bootstrap, bootstrap_chunked, sharded_fused_states)
from repro.core.bootstrap import fused_resample_states, seed_from_key
from repro.core.delta import (poisson_delta_extend, poisson_delta_init,
                              poisson_delta_result)
from repro.core.reduce_api import (HistogramState, _ArrayParam, bind_params,
                                   split_params)
from repro.core.ssabe import ssabe
from repro.kernels.fused_multi import ops as fm_ops
from test_matrix_free import _max_intermediate_size, _walk_shapes  # noqa


def _leaves_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(u), np.asarray(v))
        for u, v in zip(la, lb))


def _members():
    return (Mean(), Var(), Quantile(0.5, nbins=512, lo=0.0, hi=16.0))


def _group():
    return StatisticGroup(_members())


# ----------------------------------------------------------------------------
# jaxpr capture: one shared stream, no (B, n) intermediate
# ----------------------------------------------------------------------------
def _count_eqns(fn, *args, name="random_bits"):
    """Count PRNG draw eqns (``random_bits`` is the threefry draw under
    jax's typed-key API — one per weight-tile stream)."""
    jaxpr = jax.make_jaxpr(fn)(*args)
    return _walk_count(jaxpr.jaxpr, name)


def _walk_count(jaxpr, name):
    c = 0
    for eqn in jaxpr.eqns:
        if name in eqn.primitive.name:
            c += 1
        for p in eqn.params.values():
            for q in (p if isinstance(p, (tuple, list)) else (p,)):
                if hasattr(q, "jaxpr") and hasattr(q.jaxpr, "eqns"):
                    c += _walk_count(q.jaxpr, name)
                elif hasattr(q, "eqns"):
                    c += _walk_count(q, name)
    return c


class TestSharedStreamCapture:
    B, N = 256, 1 << 20

    def test_group_pipeline_never_builds_Bn(self, key):
        """n=2^20, B=256: every intermediate of the traced 3-statistic
        group pipeline is far smaller than the (B, n) weight matrix."""
        from repro.core.bootstrap import _fused_thetas
        x = jnp.zeros((self.N,), jnp.float32)
        biggest = _max_intermediate_size(
            lambda v, k: _fused_thetas(v, _group(), self.B, k), x, key)
        assert biggest < self.B * self.N / 100, (
            f"largest intermediate has {biggest} elements — "
            f"(B, n) would be {self.B * self.N}")

    def test_one_threefry_stream_per_tile_not_per_member(self, key):
        """The traced group pipeline contains exactly as many threefry
        eqns as a SINGLE-statistic run — the weight tile is drawn once and
        shared, not regenerated per member."""
        from repro.core.bootstrap import _fused_thetas
        x = jnp.zeros((self.N,), jnp.float32)
        n_group = _count_eqns(
            lambda v, k: _fused_thetas(v, _group(), self.B, k), x, key)
        n_single = _count_eqns(
            lambda v, k: _fused_thetas(v, Mean(), self.B, k), x, key)
        assert n_single > 0          # harness sanity: stream is visible
        assert n_group == n_single, (
            f"group traces {n_group} threefry eqns vs {n_single} for one "
            f"statistic — members are regenerating the stream")

    def test_harness_detects_sequential_duplication(self, key):
        """Sanity: the same counter DOES flag k sequential runs."""
        from repro.core.bootstrap import _fused_thetas

        def seq(v, k):
            return [_fused_thetas(v, m, self.B, k) for m in _members()]

        x = jnp.zeros((self.N,), jnp.float32)
        n_seq = _count_eqns(seq, x, key)
        n_single = _count_eqns(
            lambda v, k: _fused_thetas(v, Mean(), self.B, k), x, key)
        assert n_seq >= 3 * n_single


# ----------------------------------------------------------------------------
# slot dedup + construction
# ----------------------------------------------------------------------------
class TestGroupStructure:
    def test_moment_members_share_one_slot(self):
        g = StatisticGroup((Mean(), Var(), Std(),
                            Quantile(0.5, nbins=64, lo=0.0, hi=1.0)))
        assert len(g.slots) == 2
        assert g.member_slot == (0, 0, 0, 1)

    def test_same_range_quantiles_share_one_sketch(self, key):
        g = StatisticGroup((Quantile(0.25, nbins=128, lo=0.0, hi=10.0),
                            Quantile(0.75, nbins=128, lo=0.0, hi=10.0)))
        assert len(g.slots) == 1
        x = jax.random.uniform(key, (500,)) * 10
        q25, q75 = g(x)
        assert float(q25) < float(q75)

    def test_different_range_quantiles_get_own_slots(self):
        g = StatisticGroup((Quantile(0.5, nbins=128, lo=0.0, hi=10.0),
                            Quantile(0.5, nbins=256, lo=0.0, hi=10.0)))
        assert len(g.slots) == 2

    def test_kmeans_and_custom_never_shared(self):
        cent = jnp.zeros((2, 1))
        g = StatisticGroup((KMeansStep(cent), KMeansStep(cent), Mean()))
        assert len(g.slots) == 3

    def test_constructor_errors(self):
        with pytest.raises(ValueError, match="at least one"):
            StatisticGroup(())
        with pytest.raises(TypeError, match="flatten"):
            StatisticGroup((StatisticGroup((Mean(),)),))
        with pytest.raises(TypeError, match="not a Statistic"):
            StatisticGroup((Mean(), 3.0))
        with pytest.raises(ValueError, match="backend"):
            StatisticGroup((Mean(),), backend="tpu")

    def test_kernel_backend_rejects_kmeans_groups(self, key):
        g = StatisticGroup((Mean(), KMeansStep(jnp.zeros((2, 1)))))
        x = jax.random.normal(key, (256, 1))
        with pytest.raises(ValueError, match="scan"):
            fm_ops.fused_poisson_multi(g, 7, x, 8,
                                       backend="pallas_interpret")

    def test_split_bind_params_thread_member_arrays(self):
        cent = jnp.array([[1.0], [2.0]])
        g = StatisticGroup((Mean(), KMeansStep(cent)))
        spec, params = split_params(g)
        assert isinstance(spec.members[1].centroids, _ArrayParam)
        g2 = bind_params(spec, params)
        np.testing.assert_array_equal(np.asarray(g2.members[1].centroids),
                                      np.asarray(cent))
        # same-shaped fresh group -> SAME spec (one jit cache entry)
        g3 = StatisticGroup((Mean(), KMeansStep(cent + 1.0)))
        assert split_params(g3)[0] == spec


# ----------------------------------------------------------------------------
# equivalence vs per-member oracles (shared weights => bitwise)
# ----------------------------------------------------------------------------
class TestGroupEquivalence:
    def test_fused_member_thetas_bitwise_equal_dedicated_runs(self, key):
        x = jax.random.normal(key, (1000,)) * 2 + 8
        r_g = bootstrap(x, _group(), B=32, key=key, backend="fused_rng")
        for i, m in enumerate(_members()):
            r_m = bootstrap(x, m, B=32, key=key, backend="fused_rng")
            np.testing.assert_array_equal(np.asarray(r_g.thetas[i]),
                                          np.asarray(r_m.thetas))
            np.testing.assert_array_equal(np.ravel(r_g.estimate[i]),
                                          np.ravel(r_m.estimate))

    def test_materialized_backend_shares_weights_too(self, key):
        """backend=None draws ONE (B, n) poisson matrix for the whole
        group — member thetas equal dedicated materialized runs."""
        x = jax.random.normal(key, (700,)) + 5
        r_g = bootstrap(x, _group(), B=16, key=key)
        for i, m in enumerate(_members()):
            r_m = bootstrap(x, m, B=16, key=key)
            np.testing.assert_allclose(np.asarray(r_g.thetas[i]),
                                       np.asarray(r_m.thetas),
                                       rtol=1e-6)

    def test_one_member_group_bitwise_equals_fused_path(self, key):
        x = jax.random.normal(key, (900, 2))
        for m in (Mean(), Quantile(0.5, nbins=256, lo=-8.0, hi=8.0),
                  KMeansStep(jnp.array([[0.0, 0.0], [1.0, 1.0]]))):
            sg = fused_resample_states(StatisticGroup((m,)), jnp.int32(7),
                                       x, 16)
            sm = fused_resample_states(m, jnp.int32(7), x, 16)
            assert _leaves_equal(sg, sm), type(m).__name__

    def test_kernel_matches_scan_lowering(self, key):
        x = jax.random.normal(key, (700, 2)) + 4
        g = StatisticGroup((Mean(), Var(),
                            Quantile(0.5, nbins=200, lo=0.0, hi=8.0),
                            Quantile(0.9, nbins=128, lo=-1.0, hi=9.0)))
        a = fm_ops.fused_poisson_multi(g, 11, x, 24, backend="scan")
        b = fm_ops.fused_poisson_multi(g, 11, x, 24,
                                       backend="pallas_interpret")
        for u, v in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b)):
            np.testing.assert_allclose(np.asarray(u), np.asarray(v),
                                       rtol=1e-5, atol=1e-4)

    def test_kmeans_member_consumes_shared_tiles(self, key):
        x = jax.random.normal(key, (800, 2))
        cent = jnp.array([[-1.0, -1.0], [1.0, 1.0]])
        g = StatisticGroup((Mean(), KMeansStep(cent)))
        s_g = fused_resample_states(g, jnp.int32(5), x, 16)
        s_k = fused_resample_states(KMeansStep(cent), jnp.int32(5), x, 16)
        assert _leaves_equal(s_g[1], s_k)

    def test_custom_statistic_tile_callback_fallback(self, key):
        """A statistic with NO tile_update override rides the same cached
        weight tiles through the default vmapped-update callback."""

        class NoTileMean(Mean):
            def accumulator_key(self):
                return None              # own slot

            def tile_update(self, states, x_tile, w_tile):
                return Statistic.tile_update(self, states, x_tile, w_tile)

        x = jax.random.normal(key, (900,)) + 3
        g = StatisticGroup((Mean(), NoTileMean()))
        r = bootstrap(x, g, B=16, key=key, backend="fused_rng")
        np.testing.assert_allclose(np.asarray(r.thetas[0]),
                                   np.asarray(r.thetas[1]), rtol=1e-5)

    def test_n_valid_masks_padding(self, key):
        n, pad = 700, 1024 - 700
        x = jax.random.uniform(key, (n, 1)) * 10
        xp = jnp.pad(x, ((0, pad), (0, 0)))
        g = _group()
        a = fused_resample_states(g, jnp.int32(3), x, 16)
        b = g.fused_poisson_states(jnp.int32(3), xp, 16, n_valid=n)
        for u, v in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b)):
            np.testing.assert_allclose(np.asarray(u), np.asarray(v),
                                       rtol=1e-6)


# ----------------------------------------------------------------------------
# drivers: chunked / sharded / delta / ssabe / session
# ----------------------------------------------------------------------------
class TestGroupDrivers:
    def test_chunked_matches_unchunked(self, key):
        x = jax.random.normal(key, (3000,)) * 2 + 8
        r_p = bootstrap(x, _group(), B=64, key=key, backend="fused_rng")
        r_c = bootstrap_chunked(x, _group(), B=64, key=key, chunk=512,
                                backend="fused_rng")
        for tp, tc in zip(r_p.thetas, r_c.thetas):
            assert np.isfinite(np.asarray(tc)).all()
        assert abs(r_p.cv - r_c.cv) / (r_p.cv + 1e-12) < 1.0

    def test_sharded_oracle_composes_memberwise(self, key):
        """nshards=1 == unsharded; nshards=4 psums slot-wise (Quantile
        lo/hi untouched)."""
        x = jax.random.normal(key, (1000, 1)) * 2 + 8
        g = _group()
        s1 = sharded_fused_states(g, 7, x, 16, nshards=1)
        s0 = fused_resample_states(g, jnp.int32(7), x, 16)
        assert _leaves_equal(s1, s0)
        s4 = sharded_fused_states(g, 7, x, 16, nshards=4)
        t0 = jax.vmap(g.finalize)(s0)
        t4 = jax.vmap(g.finalize)(s4)
        for a, b in zip(t0, t4):
            assert np.isfinite(np.asarray(b)).all()
        # lo/hi config leaves survive the shard merge un-scaled
        np.testing.assert_array_equal(np.asarray(s4[1].lo),
                                      np.asarray(s0[1].lo))

    def test_delta_extend_matches_per_member_delta(self, key):
        x = jax.random.normal(key, (900, 1)) + 5
        pieces = (x[:400], x[400:])
        pd = poisson_delta_init(_group(), 16, 1, key, backend="fused_rng")
        for piece in pieces:
            pd = poisson_delta_extend(pd, piece)
        res = poisson_delta_result(pd)
        assert isinstance(res.report, GroupAccuracyReport)
        for i, m in enumerate(_members()):
            pm = poisson_delta_init(m, 16, 1, key, backend="fused_rng")
            for piece in pieces:
                pm = poisson_delta_extend(pm, piece)
            np.testing.assert_array_equal(
                np.asarray(res.thetas[i]),
                np.asarray(poisson_delta_result(pm).thetas))

    def test_ssabe_group_stops_on_worst_member(self, key):
        x = jax.random.normal(key, (1000,)) * 2 + 10
        r = ssabe(x, _group(), sigma=0.05, tau=0.01, key=key,
                  backend="fused_rng")
        assert r.B >= 2 and r.n >= 1
        assert len(r.cv_history_n) == 5

    def test_session_end_to_end_per_member_reports(self, key):
        class Perm:
            def __init__(self, data):
                self.data = np.asarray(data)
                self.N = len(data)

            def take(self, a, b):
                return jnp.asarray(self.data[a:b])

        data = np.random.default_rng(3).normal(10, 2, 200_000).astype(
            np.float32)
        g = StatisticGroup((Mean(), Quantile(0.5, lo=0.0, hi=25.0), Std()))
        sess = EarlSession(Perm(data), g, sigma=0.03, backend="fused_rng")
        out = sess.run(key)
        assert not out.fell_back
        assert len(out.reports) == 3
        # every member met sigma (the group gate is the WORST member)
        assert all(r.cv <= 0.03 for r in out.reports)
        assert out.cv == max(r.cv for r in out.reports)
        assert "member_cvs" in out.history[-1]
        est = [float(np.ravel(v)[0]) for v in out.result]
        assert abs(est[0] - 10.0) < 0.3        # mean
        assert abs(est[1] - 10.0) < 0.3        # median
        assert abs(est[2] - 2.0) < 0.3         # std


class TestGroupAccuracyReport:
    def test_worst_member_gates(self, key):
        x = jax.random.normal(key, (500,)) + 6
        r = bootstrap(x, StatisticGroup((Mean(), Var())), B=32, key=key,
                      backend="fused_rng")
        rep = r.report
        assert isinstance(rep, GroupAccuracyReport)
        assert rep.cv == max(m.cv for m in rep.members)
        assert rep.se == max(m.se for m in rep.members)
        assert len(rep.ci_lo) == 2 and len(rep.cvs) == 2


# ---------------------------------------------------------------------------
# The point estimate's histogram out of the fused kernel (core/fold.py)
# ---------------------------------------------------------------------------
def _plus_two(tree):
    """Every moment and count of ``tree`` plus 2; the sketch ranges kept."""
    def add(a):
        if isinstance(a, HistogramState):
            return HistogramState(a.counts + 2.0, a.lo, a.hi)
        return a + 2.0
    return jax.tree_util.tree_map(
        add, tree, is_leaf=lambda a: isinstance(a, HistogramState))


def _estimate_group(two_hists: bool, backend="pallas_interpret"):
    members = [Mean(), Quantile(0.5, nbins=256, lo=6.0, hi=14.0), Std()]
    if two_hists:
        members.append(Quantile(0.9, nbins=200, lo=0.0, hi=20.0))
    return StatisticGroup(members, backend=backend)


def _rough_rows(n, d, seed=0):
    """N(10, 2) rows with NaN, ±inf and values outside both sketch
    ranges."""
    x = np.random.default_rng(seed).normal(10, 2, (n, d)).astype(np.float32)
    x[3, 0], x[8, d - 1], x[13, 0] = np.nan, np.inf, -np.inf
    x[21, d - 1], x[34, 0] = 100.0, -50.0
    return jnp.asarray(x)


@pytest.mark.parametrize("two_hists", [False, True], ids=["1hist", "2hists"])
@pytest.mark.parametrize("B", [20, 300], ids=["B<block_b", "B%block_b"])
@pytest.mark.parametrize("d", [1, 3])
@pytest.mark.parametrize("mask", ["holes", "ragged"])
def test_fold_estimate_in_kernel_is_bit_equal_to_the_scatter(mask, d, B,
                                                             two_hists):
    """fold_rows takes the estimate's histogram counts from the group
    kernel's dots: they, the estimate's moments and the resample states are
    bit-equal to the estimate's ``update`` (the scatter-add) beside the
    kernel without the estimate."""
    from repro.core import fold
    from repro.core.bootstrap import offset_seed
    n = 700
    g = _estimate_group(two_hists)
    x = _rough_rows(n, d)
    rng = np.random.default_rng(1)
    if mask == "holes":
        vm = jnp.asarray((rng.random(n) > 0.3).astype(np.float32))
        kw, est_mask = dict(valid_mask=vm), vm
        # interior holes, some falling inside a row with NaN or inf
        assert 0 < float(vm[:n // 2].sum()) < n // 2
    else:
        kw = dict(n_valid=jnp.int32(611))
        est_mask = (jnp.arange(n) < 611).astype(jnp.float32)
    # running states and estimate that already hold counts and moments
    states0, est0 = _plus_two(fold.empty_carry(g, B, d))

    states, est = jax.jit(lambda s, e, x: fold.fold_rows(
        g, s, e, x, 77, 3, B, **kw))(states0, est0, x)

    want_est = g.update(est0, x, est_mask)
    delta = fused_resample_states(g, offset_seed(77, 3), x, B, **kw)
    want_states = jax.vmap(g.merge)(states0, delta)
    for got, want in zip(jax.tree_util.tree_leaves((est, states)),
                         jax.tree_util.tree_leaves((want_est, want_states))):
        assert got.shape == want.shape
        assert np.array_equal(np.asarray(got), np.asarray(want),
                              equal_nan=True)
    hist_slots = [st for st in est if hasattr(st, "counts")]
    assert len(hist_slots) == (2 if two_hists else 1)
    for st in hist_slots:      # NaN mass dropped, ±inf kept in edge bins
        got = np.asarray(st.counts).sum(axis=1) - 2.0 * st.counts.shape[1]
        nan_rows = np.isnan(np.asarray(x)) * np.asarray(est_mask)[:, None]
        np.testing.assert_array_equal(
            got, float(np.asarray(est_mask).sum()) - nan_rows.sum(axis=0))


@pytest.mark.parametrize("backend,members,want", [
    ("pallas_interpret", None, {"estimate_in_kernel": 1,
                                "estimate_scatter": 0}),
    ("scan", None, {"estimate_in_kernel": 0, "estimate_scatter": 1}),
    ("pallas_interpret", (Mean(), Std()), {"estimate_in_kernel": 0,
                                           "estimate_scatter": 0}),
], ids=["paper-jobs-kernel", "paper-jobs-scan", "moments-only"])
def test_fold_chunk_counts_where_the_estimate_histogram_runs(backend,
                                                             members, want):
    """One trace of the stream step counts the estimate once, by where its
    histogram is built; a warm call counts nothing."""
    from repro import trace
    from repro.core import fold
    g = (_estimate_group(False, backend) if members is None
         else StatisticGroup(members, backend=backend))
    spec, params = split_params(g)
    n, B = 512, 8
    x = _rough_rows(n, 1)
    vi = jnp.asarray((np.arange(n) % 7 != 0).astype(np.float32))

    def step():
        states, est = fold.fresh(fold.empty_carry(g, B, 1))
        before = trace.counts()
        fold.fold_chunk_jit(states, est, x, vi, jnp.int32(5), jnp.int32(0),
                            params, spec, B)
        after = trace.counts()
        return {k: after.get(k, 0) - before.get(k, 0) for k in want}

    assert step() == want
    assert step() == {k: 0 for k in want}


def test_delta_extend_folds_no_estimate():
    """The ad-hoc Δ extends fold with est=None: their estimate is the
    extend's own update, counted by neither counter."""
    from repro import trace
    g = _estimate_group(False)
    pd = poisson_delta_init(g, 8, 1, jax.random.PRNGKey(0),
                            backend="fused_rng")
    before = trace.counts()
    pd = poisson_delta_extend(pd, _rough_rows(300, 1))
    jax.block_until_ready(pd.states)
    after = trace.counts()
    assert {k: after.get(k, 0) - before.get(k, 0)
            for k in ("estimate_in_kernel", "estimate_scatter")} == \
        {"estimate_in_kernel": 0, "estimate_scatter": 0}
