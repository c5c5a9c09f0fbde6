"""Sharded matrix-free bootstrap on 8 forced host devices.

Runs in a subprocess (XLA_FLAGS=--xla_force_host_platform_device_count=8
must be set before jax imports; the main pytest process keeps its single
device — see tests/conftest.py) and asserts the ISSUE-3 acceptance
criteria:

  * sharded fused states are BITWISE equal to the single-device oracle
    (``sharded_fused_states(..., mesh=None, nshards=8)``: same per-shard
    streams, sequential left-fold merge) for all three statistic families
    — Moments, Quantile (histogram psum), KMeansStep;
  * the chunked sharded path (streams keyed (base, shard, chunk)) is
    bitwise equal to its oracle too;
  * per-shard streams are pairwise distinct;
  * an nshards=1 mesh reproduces the single-device unsharded fused path
    bitwise (the seed discipline collapses to the chunk/step counter);
  * delta maintenance, SSABE and EarlSession run end-to-end with mesh=,
    with sane accuracy vs the local path;
  * DistributedEarl(backend="fused_rng") works, including for Quantile
    (whose lo/hi state leaves a raw tree-psum would have scaled 8×).
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core import (DistributedEarl, GroupedStatistic, KMeansStep, Mean,
                        Quantile, StatisticGroup, Var, bootstrap,
                        bootstrap_chunked, sharded_fused_states)
from repro.core.bootstrap import (fused_resample_states, offset_seed,
                                  seed_from_key)
from repro.core.delta import (poisson_delta_extend, poisson_delta_init,
                              poisson_delta_result)
from repro.core.session import EarlSession
from repro.kernels.weighted_stats import ops as ws_ops

out = {}
assert jax.device_count() == 8
mesh = Mesh(np.array(jax.devices()), ("data",))
mesh1 = Mesh(np.array(jax.devices()[:1]), ("data",))
key = jax.random.PRNGKey(0)
x = jax.random.normal(key, (4097, 2)) * 2.0 + 10.0   # ragged: 4097 % 8 != 0

def leaves_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return all(np.array_equal(np.asarray(u), np.asarray(v))
               for u, v in zip(la, lb))

# --- bitwise: mesh vs single-device oracle, all three stat families -----
stats = {
    "moments": Mean(),
    "quantile": Quantile(0.5, nbins=256, lo=0.0, hi=20.0),
    "kmeans": KMeansStep(jnp.array([[9.0, 9.0], [11.0, 11.0]])),
}
for name, stat in stats.items():
    s_mesh = sharded_fused_states(stat, 77, jnp.asarray(x), 32, mesh=mesh)
    s_one = sharded_fused_states(stat, 77, jnp.asarray(x), 32, nshards=8)
    out[f"bitwise_{name}"] = leaves_equal(s_mesh, s_one)

# --- StatisticGroup: bitwise under mesh sharding (ISSUE-5) --------------
grp = StatisticGroup((Mean(), Var(),
                      Quantile(0.5, nbins=256, lo=0.0, hi=20.0),
                      KMeansStep(jnp.array([[9.0, 9.0], [11.0, 11.0]]))))
g_mesh = sharded_fused_states(grp, 77, jnp.asarray(x), 32, mesh=mesh)
g_one = sharded_fused_states(grp, 77, jnp.asarray(x), 32, nshards=8)
out["bitwise_group"] = leaves_equal(g_mesh, g_one)
# and the sharded group's member states equal each member's own sharded
# run (one shared stream -> same resamples, even across the mesh)
g_fin = jax.vmap(grp.finalize)(g_mesh)
for i, m in enumerate(grp.members):
    m_mesh = sharded_fused_states(m, 77, jnp.asarray(x), 32, mesh=mesh)
    out[f"bitwise_group_member{i}"] = leaves_equal(
        jax.vmap(m.finalize)(m_mesh), g_fin[i])

# --- ISSUE-7: GroupedStatistic over the mesh ----------------------------
G = 4
gids = jax.random.randint(jax.random.fold_in(key, 21),
                          (x.shape[0],), 0, G).astype(jnp.float32)
vk = jnp.concatenate([jnp.asarray(x), gids[:, None]], axis=1)
gstat = GroupedStatistic(Mean(), G)
gs_mesh = sharded_fused_states(gstat, 77, vk, 32, mesh=mesh)
gs_one = sharded_fused_states(gstat, 77, vk, 32, nshards=8)
out["bitwise_grouped_mesh"] = leaves_equal(gs_mesh, gs_one)
# per-key thetas == per-key-alone sharded runs: shard the rows the same
# way and run the INNER statistic with the shard's key mask composed
# onto its validity prefix, under the same per-shard streams.
gth = jax.vmap(gstat.finalize)(gs_mesh)
nrows = vk.shape[0]
m = -(-nrows // 8)
xkp = jnp.pad(vk, ((0, 8 * m - nrows), (0, 0)))
ok = True
for g in range(G):
    acc = None
    for i in range(8):
        loc = xkp[i * m:(i + 1) * m]
        nv = min(max(nrows - i * m, 0), m)
        maskg = (jnp.arange(m) < nv).astype(jnp.float32) * (loc[:, 2] == g)
        si = fused_resample_states(Mean(), offset_seed(77, i),
                                   loc[:, :2], 32, valid_mask=maskg)
        acc = si if acc is None else jax.vmap(Mean().merge)(acc, si)
    ok = ok and leaves_equal(jax.vmap(Mean().finalize)(acc), gth[:, g])
out["bitwise_grouped_per_key_mesh"] = ok

# --- bitwise: chunked sharded (streams keyed (base, shard, chunk)) ------
st_m = sharded_fused_states(Mean(), 77, jnp.asarray(x), 32, mesh=mesh,
                            chunk=256)
st_o = sharded_fused_states(Mean(), 77, jnp.asarray(x), 32, nshards=8,
                            chunk=256)
out["bitwise_chunked"] = leaves_equal(st_m, st_o)

# --- nshards=1 mesh == the plain single-device fused path ---------------
s_1mesh = sharded_fused_states(Mean(), 77, jnp.asarray(x), 32, mesh=mesh1)
s_plain = fused_resample_states(Mean(), jnp.int32(77), jnp.asarray(x), 32)
out["bitwise_nshards1"] = leaves_equal(s_1mesh, s_plain)

# --- distinct per-shard streams -----------------------------------------
ws = [np.asarray(ws_ops.implicit_weights(offset_seed(77, i), 16, 512))
      for i in range(8)]
out["streams_distinct"] = all(
    not np.array_equal(ws[i], ws[j])
    for i in range(8) for j in range(i + 1, 8))

# --- bootstrap()/bootstrap_chunked() with mesh: sane accuracy -----------
xb = jax.random.normal(key, (32768,)) * 2.0 + 10.0
r_local = bootstrap(xb, Mean(), B=128, key=key, backend="fused_rng")
r_mesh = bootstrap(xb, Mean(), B=128, key=key, backend="fused_rng",
                   mesh=mesh)
r_ck = bootstrap_chunked(xb, Mean(), B=128, key=key, chunk=1024,
                         backend="fused_rng", mesh=mesh)
out["mesh_est"] = float(np.ravel(r_mesh.estimate)[0])
out["mesh_cv"] = r_mesh.cv
out["chunked_cv"] = r_ck.cv
out["local_cv"] = r_local.cv
out["true"] = float(xb.mean())

# --- sharded quantile composes (per-shard sketches psum) ----------------
q = Quantile(0.5, nbins=512, lo=0.0, hi=20.0)
rq = bootstrap(xb, q, B=64, key=key, backend="fused_rng", mesh=mesh)
out["quantile_est"] = float(np.ravel(rq.estimate)[0])
out["quantile_cv"] = rq.cv

# --- sharded delta maintenance == oracle extend-by-extend ---------------
pd = poisson_delta_init(Mean(), 32, 2, key, backend="fused_rng", mesh=mesh)
pd = poisson_delta_extend(pd, x[:2000])
pd = poisson_delta_extend(pd, x[2000:])
base = seed_from_key(key)
ref = None
for step, piece in enumerate((x[:2000], x[2000:])):
    si = sharded_fused_states(Mean(), base, jnp.asarray(piece), 32,
                              nshards=8, step=step)
    ref = si if ref is None else jax.vmap(Mean().merge)(ref, si)
out["bitwise_delta"] = leaves_equal(pd.states, ref)
out["delta_cv"] = poisson_delta_result(pd).cv

# --- the session's compiled result step and B-hat ladder over the mesh --
# the same answers as on the nshards= oracle's states, on one device
import dataclasses, importlib
from repro.core import accuracy
ssabe_mod = importlib.import_module("repro.core.ssabe")
grp = StatisticGroup((Mean(), Quantile(0.5, nbins=256, lo=0.0, hi=20.0),
                      Var()))
pg = poisson_delta_init(grp, 16, 2, key, backend="fused_rng", mesh=mesh)
pg = poisson_delta_extend(pg, x)
on_mesh = poisson_delta_result(pg, p=0.5)
oracle = poisson_delta_result(dataclasses.replace(
    pg, mesh=None, states=sharded_fused_states(
        grp, seed_from_key(key), jnp.asarray(x), 16, nshards=8)), p=0.5)
out["result_step_mesh_bitwise"] = leaves_equal(on_mesh.thetas,
                                               oracle.thetas)
out["result_step_mesh_cvs"] = [on_mesh.report.cvs, oracle.report.cvs]
B_hat, hist = ssabe_mod.estimate_B(x, grp, 0.01, key, backend="fused_rng",
                                   mesh=mesh)
th = jax.vmap(grp.finalize)(sharded_fused_states(
    grp, seed_from_key(key), jnp.asarray(x), 100, nshards=8))
out["ladder_mesh"] = [B_hat, [list(h) for h in hist]]
oracle_hist, prev, oracle_B = [], None, 100
for b in ssabe_mod._candidates(2, 100):
    cv = max(float(accuracy.coefficient_of_variation(t[:b])) for t in th)
    oracle_hist.append([b, cv])
    if prev is not None and abs(cv - prev) < 0.01:
        oracle_B = b
        break
    prev = cv
out["ladder_oracle"] = [oracle_B, oracle_hist]

# --- EarlSession end-to-end over the mesh -------------------------------
class _Sampler:
    def __init__(self, data):
        self.data = data
        self.N = data.shape[0]
    def take(self, a, b):
        return self.data[a:b]

big = jax.random.normal(jax.random.fold_in(key, 9), (200_000,)) * 5 + 100
sess = EarlSession(_Sampler(big), Mean(), sigma=0.01,
                   backend="fused_rng", mesh=mesh)
er = sess.run(jax.random.PRNGKey(3))
out["session_result"] = float(np.ravel(er.result)[0])
out["session_cv"] = er.cv
out["session_fell_back"] = er.fell_back

# --- DistributedEarl fused backend, incl. Quantile lo/hi psum fix -------
earl = DistributedEarl(mesh, Mean(), B=128, backend="fused_rng")
res = earl.estimate(xb, key)
out["dearl_est"] = float(np.ravel(res.estimate)[0])
out["dearl_cv"] = res.cv
earl_q = DistributedEarl(mesh, Quantile(0.5, nbins=512, lo=0.0, hi=20.0),
                         B=64, backend="fused_rng")
res_q = earl_q.estimate(xb, key)
out["dearl_q_est"] = float(np.ravel(res_q.estimate)[0])

print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def subproc_result():
    env = dict(os.environ)
    env["PYTHONPATH"] = _SRC
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines()
            if l.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


@pytest.mark.parametrize("fam", ["moments", "quantile", "kmeans"])
def test_sharded_states_bitwise_equal_single_device(subproc_result, fam):
    assert subproc_result[f"bitwise_{fam}"]


def test_chunked_sharded_bitwise_equal(subproc_result):
    assert subproc_result["bitwise_chunked"]


def test_grouped_bitwise_under_mesh(subproc_result):
    """ISSUE-7: a GroupedStatistic's sharded states equal the single-device
    oracle bitwise, and each key's thetas equal a per-key-alone sharded
    run of the inner statistic (shard-composed key masks, same streams)."""
    assert subproc_result["bitwise_grouped_mesh"]
    assert subproc_result["bitwise_grouped_per_key_mesh"]


def test_group_bitwise_under_mesh(subproc_result):
    """ISSUE-5: a StatisticGroup's sharded states equal the single-device
    oracle bitwise, and every member's finalized thetas equal the member's
    own sharded run (one shared stream across the mesh)."""
    assert subproc_result["bitwise_group"]
    for i in range(4):
        assert subproc_result[f"bitwise_group_member{i}"], f"member {i}"


def test_single_shard_mesh_matches_unsharded_path(subproc_result):
    assert subproc_result["bitwise_nshards1"]


def test_per_shard_streams_distinct(subproc_result):
    assert subproc_result["streams_distinct"]


def test_sharded_bootstrap_accuracy(subproc_result):
    r = subproc_result
    assert abs(r["mesh_est"] - r["true"]) < 0.1
    assert 0 < r["mesh_cv"] < 0.05
    assert abs(r["mesh_cv"] - r["local_cv"]) / r["local_cv"] < 1.0
    assert 0 < r["chunked_cv"] < 0.05


def test_sharded_quantile_sketch(subproc_result):
    r = subproc_result
    assert abs(r["quantile_est"] - 10.0) < 0.2
    assert 0 < r["quantile_cv"] < 0.05


def test_sharded_delta_bitwise_and_sane(subproc_result):
    assert subproc_result["bitwise_delta"]
    assert 0 < subproc_result["delta_cv"] < 0.1


def test_compiled_result_step_and_ladder_under_mesh(subproc_result):
    """The session's compiled result step on the psummed states, and its
    B̂ ladder folded over the mesh inside one program, give the answers of
    the ``nshards=`` oracle's states."""
    r = subproc_result
    assert r["result_step_mesh_bitwise"]
    np.testing.assert_allclose(*r["result_step_mesh_cvs"], rtol=1e-6)
    (B_hat, hist), (oracle_B, oracle_hist) = (r["ladder_mesh"],
                                              r["ladder_oracle"])
    assert B_hat == oracle_B
    assert [b for b, _ in hist] == [b for b, _ in oracle_hist]
    np.testing.assert_allclose([c for _, c in hist],
                               [c for _, c in oracle_hist],
                               rtol=1e-5, atol=1e-6)


def test_sharded_session_end_to_end(subproc_result):
    r = subproc_result
    assert not r["session_fell_back"]
    assert abs(r["session_result"] - 100.0) < 1.0
    assert r["session_cv"] <= 0.01 * 1.5


def test_distributed_earl_fused_backend(subproc_result):
    r = subproc_result
    assert abs(r["dearl_est"] - r["true"]) < 0.1
    assert 0 < r["dearl_cv"] < 0.05
    assert abs(r["dearl_q_est"] - 10.0) < 0.2
