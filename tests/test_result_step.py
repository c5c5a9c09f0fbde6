"""The session's compiled result step and B̂ ladder (``core/delta.py``,
``core/ssabe.py``).

* ``poisson_delta_result`` runs one program — finalize, correct and the
  report's numbers — and reads the report's scalars back in one
  ``host_sync``; it gives the eager composition's answers
  (``vmap(finalize)`` → ``correct`` → ``report_for``): Mean and Quantile
  bit for bit, the rest within float32 rounding; the estimate stays eager
  and bit for bit.
* The B̂ ladder computes every candidate's worst-member c_v in one program
  and one read, and chooses the B̂ the per-candidate eager c_v chooses.
* A session gives the eager composition's B̂, n̂, rounds, rows and
  fallbacks.
* Each program compiles once across rounds of different ``p`` and is not
  traced again on a warm query (``result_traces``).
* ``poisson_delta_init``'s empty states come from one cached program.
"""
import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import trace
from repro.core import (EarlSession, GroupedStatistic, KMeansStep, Mean,
                        Quantile, StatisticGroup, Std, Sum, accuracy)
from repro.core import session as session_mod
from repro.core.bootstrap import (BootstrapResult, bootstrap_thetas,
                                  fused_resample_states, seed_from_key,
                                  weights_for)
from repro.core.delta import (_pd_init_jit, _pd_result_jit,
                              poisson_delta_extend, poisson_delta_init,
                              poisson_delta_result)
from repro.data import PreMapSampler, ShardedStore

ssabe_mod = importlib.import_module("repro.core.ssabe")

KEY = jax.random.PRNGKey(7)


def _keyed_rows(n, rng):
    x = rng.normal(5.0, 2.0, (n, 1)).astype(np.float32)
    g = rng.integers(0, 3, (n, 1)).astype(np.float32)
    return np.concatenate([x, g], axis=1)


def _rows(n, dim=1, seed=0):
    return np.random.default_rng(seed).normal(10.0, 2.0, (n, dim)).astype(
        np.float32)


# name -> (statistic, rows, which members' thetas must be bitwise)
CASES = {
    "mean": (Mean(), _rows(3000), (True,)),
    "quantile": (Quantile(0.5, nbins=256, lo=0.0, hi=20.0), _rows(3000),
                 (True,)),
    "group": (StatisticGroup((Mean(), Quantile(0.5, nbins=256, lo=0.0,
                                               hi=20.0), Std())),
              _rows(3000), (True, True, False)),
    "keyed-sum": (GroupedStatistic(Sum(), 3),
                  _keyed_rows(3000, np.random.default_rng(1)), (False,)),
    "kmeans": (KMeansStep(jnp.asarray([[8.0, 8.0], [10.0, 10.0],
                                       [12.0, 12.0]])),
               _rows(3000, dim=2), (False,)),
}


def _delta(stat, rows, B=32, backend="fused_rng"):
    pd = poisson_delta_init(stat, B, rows.shape[1], KEY, backend=backend)
    for piece in (rows[:1200], rows[1200:]):
        pd = poisson_delta_extend(pd, jnp.asarray(piece))
    return pd


def eager_result(pd, estimate=None, p=1.0, p_keys=None):
    """The eager composition the compiled step replaces: ``vmap(finalize)``
    → ``correct`` → ``report_for``, one read per scalar."""
    thetas = pd.stat.correct(jax.vmap(pd.stat.finalize)(pd.states), p)
    if estimate is None:
        estimate = pd.stat.finalize(pd.est_state)
    return BootstrapResult(
        estimate=pd.stat.correct(estimate, p), thetas=thetas,
        report=accuracy.report_for(
            thetas, num_groups=getattr(pd.stat, "num_groups", None)),
        B=pd.B, n=pd.n)


def eager_cv(thetas, num_groups=None):
    """Worst-member (or worst-key) c_v, one read per member."""
    if isinstance(thetas, (tuple, list)):
        parts = list(thetas)
    elif num_groups is not None:
        parts = [thetas[:, g] for g in range(num_groups)]
    else:
        parts = [thetas]
    return max(float(accuracy.coefficient_of_variation(t)) for t in parts)


def eager_estimate_B(values, stat, tau, key, engine="poisson", B_min=2,
                     B_max=None, backend=None, mesh=None, data_axis="data"):
    """The B̂ ladder as eager steps: thetas, then each candidate's c_v."""
    assert mesh is None
    if B_max is None:
        B_max = max(B_min + 1, int(np.ceil(1.0 / tau)))
    x = jnp.asarray(values).reshape(values.shape[0], -1)
    if backend == "fused_rng":
        thetas = jax.vmap(stat.finalize)(fused_resample_states(
            stat, seed_from_key(key), x, B_max))
    else:
        thetas = bootstrap_thetas(x, stat,
                                  weights_for(engine, key, B_max, x.shape[0]))
    history, prev, chosen = [], None, B_max
    for B in ssabe_mod._candidates(B_min, B_max):
        cv = eager_cv(jax.tree_util.tree_map(lambda t: t[:B], thetas),
                      getattr(stat, "num_groups", None))
        history.append((B, cv))
        if prev is not None and abs(cv - prev) < tau:
            chosen = B
            break
        prev = cv
    return chosen, history


def _parts(tree):
    return tree if isinstance(tree, tuple) else (tree,)


@pytest.mark.parametrize("name", list(CASES))
def test_compiled_step_matches_the_eager_composition(name):
    stat, rows, bitwise = CASES[name]
    pd = _delta(stat, rows)
    p = 0.37
    got = poisson_delta_result(pd, p=p)
    want = eager_result(pd, p=p)
    for exact, a, b in zip(bitwise, _parts(got.thetas), _parts(want.thetas)):
        if exact:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        else:
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6)
    # the estimate is finalized eagerly, as before: bit for bit
    for a, b in zip(_parts(got.estimate), _parts(want.estimate)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert type(got.report) is type(want.report)
    gm = getattr(got.report, "members", (got.report,))
    wm = getattr(want.report, "members", (want.report,))
    assert len(gm) == len(wm)
    for g, w in zip(gm, wm):
        for field in dataclasses.fields(g):
            np.testing.assert_allclose(
                np.asarray(getattr(g, field.name)),
                np.asarray(getattr(w, field.name)), rtol=1e-5,
                err_msg=f"{name}: {field.name}")
        assert isinstance(g.cv, float)
    assert (got.B, got.n) == (want.B, want.n)


@pytest.mark.parametrize("name", list(CASES))
def test_init_is_one_cached_program(name):
    """The empty states come from one program per (spec, B, dim), equal to
    the eager ``vmap(init_state)`` they replace."""
    stat, rows, _ = CASES[name]
    dim = rows.shape[1]
    pd = poisson_delta_init(stat, 19, dim, KEY)
    size = _pd_init_jit._cache_size()
    want_states = jax.vmap(lambda _: stat.init_state(dim))(jnp.arange(19))
    for got, want in ((pd.states, want_states),
                      (pd.est_state, stat.init_state(dim))):
        assert (jax.tree_util.tree_structure(got)
                == jax.tree_util.tree_structure(want))
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    poisson_delta_init(stat, 19, dim, jax.random.PRNGKey(8))
    assert _pd_init_jit._cache_size() == size


def test_a_given_estimate_is_corrected_as_before():
    stat = GroupedStatistic(Sum(), 3)
    rows = CASES["keyed-sum"][1]
    pd = _delta(stat, rows)
    est = stat(jnp.asarray(rows[:2000]))
    got = poisson_delta_result(pd, estimate=est, p=0.25)
    np.testing.assert_array_equal(np.asarray(got.estimate),
                                  np.asarray(stat.correct(est, 0.25)))


def test_one_result_step_is_one_host_sync():
    pd = _delta(*CASES["group"][:2])
    before = trace.counts()
    poisson_delta_result(pd, p=0.5)
    after = trace.counts()
    assert after["host_sync"] - before.get("host_sync", 0) == 1
    assert after["result_steps"] - before.get("result_steps", 0) == 1


def test_step_compiles_once_across_rounds():
    # a B of its own, so that the first call traces the step
    pd = _delta(*CASES["group"][:2], B=23)
    _pd_result_jit._clear_cache()
    before = trace.counts().get("result_traces", 0)
    cvs = [poisson_delta_result(pd, p=p).cv for p in (0.125, 0.25, 0.5, 1.0)]
    assert _pd_result_jit._cache_size() == 1
    assert trace.counts()["result_traces"] - before == 1
    # the group's members are p-invariant: the same c_v every round
    assert len(set(cvs)) == 1


@pytest.mark.parametrize("name,backend", [
    ("mean", None), ("mean", "fused_rng"), ("group", "fused_rng"),
    ("keyed-sum", "fused_rng"), ("kmeans", "fused_rng")])
def test_ladder_chooses_what_the_eager_ladder_chooses(name, backend):
    stat, rows, _ = CASES[name]
    x = jnp.asarray(rows[:2048])
    before = trace.counts()
    got_B, got_hist = ssabe_mod.estimate_B(x, stat, 0.01, KEY,
                                           backend=backend)
    after = trace.counts()
    want_B, want_hist = eager_estimate_B(x, stat, 0.01, KEY,
                                         backend=backend)
    assert got_B == want_B
    assert [b for b, _ in got_hist] == [b for b, _ in want_hist]
    # a two-resample prefix's c_v is a difference of nearly equal float32
    # thetas: one rounding of a theta moves it by ~1e-7, far below tau
    np.testing.assert_allclose([c for _, c in got_hist],
                               [c for _, c in want_hist], rtol=1e-5,
                               atol=1e-6)
    assert all(isinstance(c, float) for _, c in got_hist)
    assert after["host_sync"] - before.get("host_sync", 0) == 1


DATA = np.random.default_rng(2).normal(50, 5, 200_000).astype(np.float32)


def _session(seed, stat, sigma):
    store = ShardedStore.from_array(DATA, 50_000, interleave=False)
    return EarlSession(PreMapSampler(store, seed=seed), stat, sigma=sigma,
                       backend="fused_rng")


#: members whose thetas the compiled step gives bit for bit
EXACT_GROUP = StatisticGroup((Mean(), Quantile(0.5, lo=30.0, hi=70.0,
                                               nbins=64)))
#: and with Std, whose compiled finalize rounds differently
STD_GROUP = StatisticGroup(EXACT_GROUP.members + (Std(),))


def _outcome(out):
    return (out.B, out.ssabe.B if out.ssabe else None,
            out.ssabe.n if out.ssabe else None, out.iterations,
            out.n_used, out.fell_back)


def _both_ways(monkeypatch, stat, sigma, seeds=range(8)):
    """Each seed's session with the compiled steps, then with the eager
    composition in their place."""
    def runs():
        return [_session(s, stat, sigma).run(jax.random.PRNGKey(100 + s))
                for s in seeds]
    compiled = runs()
    with monkeypatch.context() as m:
        m.setattr(ssabe_mod, "poisson_delta_report",
                  lambda pd, p=1.0: eager_result(pd, p=p).report)
        m.setattr(session_mod, "poisson_delta_result", eager_result)
        m.setattr(ssabe_mod, "estimate_B", eager_estimate_B)
        eager = runs()
    return compiled, eager


def test_sessions_match_the_eager_composition(monkeypatch):
    compiled, eager = _both_ways(monkeypatch, EXACT_GROUP, 0.005)
    assert [_outcome(o) for o in compiled] == [_outcome(o) for o in eager]
    for a, b in zip(compiled, eager):
        assert a.cv == b.cv
        for u, v in zip(a.result, b.result):
            np.testing.assert_array_equal(np.asarray(u), np.asarray(v))
    # the seeds cover the exact job, one round and several rounds
    rounds = {o.iterations for o in compiled if not o.fell_back}
    assert any(o.fell_back for o in compiled)
    assert 1 in rounds and max(rounds) > 1


def test_std_sessions_match_within_rounding(monkeypatch):
    """A compiled Std finalize rounds differently from the eager one (an
    ulp of E[x^2] where the variance cancels), so SSABE's c_v(n) moves in
    its fifth significant digit; B̂ and the fallback stay, and n̂, which
    inverts the fitted curve, moves by a few rows in ten thousand."""
    compiled, eager = _both_ways(monkeypatch, STD_GROUP, 0.01)
    for a, b in zip(compiled, eager):
        assert (a.B, a.fell_back) == (b.B, b.fell_back)
        if a.fell_back:
            continue
        assert a.ssabe.B == b.ssabe.B
        np.testing.assert_allclose([c for _, c in a.ssabe.cv_history_n],
                                   [c for _, c in b.ssabe.cv_history_n],
                                   rtol=5e-4)
        assert abs(a.ssabe.n - b.ssabe.n) <= 2e-3 * b.ssabe.n


def test_a_warm_session_traces_no_step():
    _session(1, STD_GROUP, 0.01).run(jax.random.PRNGKey(101))
    before = trace.counts()
    out = _session(1, STD_GROUP, 0.01).run(jax.random.PRNGKey(101))
    after = trace.counts()
    assert not out.fell_back
    assert after.get("result_traces", 0) - before.get("result_traces", 0) \
        == 0
    assert after["result_steps"] - before.get("result_steps", 0) == \
        1 + 5 + out.iterations
