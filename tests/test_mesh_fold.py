"""The sharded fold as one cached program, with its span and counters.

Runs in a subprocess on 4 forced host devices (the flag must be set before
jax imports; the main pytest process keeps its single device, see
tests/conftest.py):

* a second mesh session of the same shapes builds no program and traces
  no sharded fold (``mesh_traces`` adds 0);
* ``mesh_folds`` and ``mesh_psum_bytes`` follow from the B̂ ladder, ``l``
  and the rounds, and each ``earl.mesh.fold`` span nests under SSABE's or
  a round's Δ extend;
* the cached fold's states equal the sequential ``nshards=4`` oracle bit
  for bit for a group of Mean, Quantile and Std, at a pilot size that 4
  does not divide (the padded tail).

On one device the session opens no mesh span and counts nothing of the
mesh.
"""
import contextlib
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro import trace
from repro.core import EarlSession, Mean, Quantile, StatisticGroup, Std
from repro.data import PreMapSampler, ShardedStore

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import contextlib, json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh

from repro import trace
from repro.core import (EarlSession, Mean, Quantile, StatisticGroup, Std,
                        sharded_fused_states)
from repro.core.bootstrap import state_nbytes
from repro.data import PreMapSampler, ShardedStore

assert jax.device_count() == 4
mesh = Mesh(np.array(jax.devices()), ("data",))
out = {}
programs = []
jax.monitoring.register_event_duration_secs_listener(
    lambda ev, _d, **_: programs.append(ev)
    if ev == "/jax/core/compile/backend_compile_duration" else None)

spans = []
stack = []
plain_span = trace.span

@contextlib.contextmanager
def logged(name, **meta):
    spans.append({"name": trace.PREFIX + name, "meta": meta,
                  "stack": list(stack)})
    stack.append(trace.PREFIX + name)
    try:
        with plain_span(name, **meta):
            yield
    finally:
        stack.pop()

trace.span = logged

rows = (np.random.default_rng(5).standard_normal(1 << 20, dtype=np.float32)
        * 2 + 10).reshape(-1, 1)
store = ShardedStore.from_array(rows, 1 << 18, interleave=False)
group = StatisticGroup((Mean(), Quantile(0.5, nbins=2048, lo=9.0, hi=11.0),
                        Std()))
L, TAU = 5, 0.01

def session(seed):
    return EarlSession(PreMapSampler(store, seed=seed), group, sigma=0.05,
                       tau=TAU, l=L, backend="fused_rng", mesh=mesh)

def counted(seed, key):
    c0, p0, s0 = trace.counts(), len(programs), len(spans)
    res = session(seed).run(jax.random.PRNGKey(key))
    float(res.cv)
    c1 = trace.counts()
    return res, {k: v - c0.get(k, 0) for k, v in c1.items()}, \
        len(programs) - p0, spans[s0:]

first, d_first, p_first, _ = counted(11, 1)
second, d_second, p_second, sp = counted(12, 2)
out["first"] = {"programs": p_first, "mesh_traces": d_first.get("mesh_traces")}
out["second"] = {"programs": p_second,
                 "mesh_traces": d_second.get("mesh_traces"),
                 "same_shapes": [first.n_used, first.B, first.iterations]
                 == [second.n_used, second.B, second.iterations]}

# the counters' formula: one fold for the B-hat ladder at B_max, then one
# per n step and per round at B-hat
per_resample = state_nbytes(jax.eval_shape(lambda: group.init_state(1)))
B_max = int(np.ceil(1 / TAU))
out["folds"] = d_second.get("mesh_folds")
out["folds_expected"] = 1 + L + second.iterations
out["psum_bytes"] = d_second.get("mesh_psum_bytes")
out["psum_bytes_expected"] = per_resample * (
    B_max + (L + second.iterations) * second.B)
folds = [s for s in sp if s["name"] == "earl.mesh.fold"]
out["fold_spans"] = len(folds)
out["fold_shards"] = sorted({s["meta"].get("shards") for s in folds})
out["fold_n"] = [s["meta"].get("n") for s in folds]
out["fold_parents"] = [s["stack"][-1] for s in folds]
out["fold_under_ssabe"] = ["earl.ssabe" in s["stack"] for s in folds]

# bit for bit against the sequential oracle, padded tail (8190 % 4 != 0)
x = jnp.asarray(rows[:8190])
for B in (100, 4):
    m = sharded_fused_states(group, 12345, x, B, mesh=mesh)
    o = sharded_fused_states(group, 12345, x, B, nshards=4)
    out[f"bitwise_B{B}"] = all(
        np.array_equal(np.asarray(a), np.asarray(b)) for a, b in
        zip(jax.tree_util.tree_leaves(m), jax.tree_util.tree_leaves(o)))
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def mesh_result():
    env = dict(os.environ)
    env["PYTHONPATH"] = _SRC
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def test_a_warm_mesh_session_builds_no_program(mesh_result):
    r = mesh_result
    assert r["second"]["same_shapes"]
    assert r["first"]["mesh_traces"] > 0
    assert r["second"]["programs"] == 0
    assert r["second"]["mesh_traces"] == 0


def test_mesh_counters_follow_ladder_l_and_rounds(mesh_result):
    r = mesh_result
    assert r["folds"] == r["folds_expected"]
    assert r["psum_bytes"] == r["psum_bytes_expected"] > 0


def test_mesh_fold_spans_nest_under_ssabe_and_the_extend(mesh_result):
    r = mesh_result
    assert r["fold_spans"] == r["folds"]
    assert r["fold_shards"] == [4]
    # the B-hat ladder folds the whole 8192-row pilot; the n steps fold
    # the nested halves' new rows; the round folds its delta
    assert r["fold_n"][0] == 8192 and sum(r["fold_n"][1:6]) == 8192
    assert r["fold_parents"][0] == "earl.ssabe.B"
    assert set(r["fold_parents"][1:6]) == {"earl.ssabe.n"}
    assert set(r["fold_parents"][6:]) == {"earl.delta.extend"}
    assert r["fold_under_ssabe"] == [True] * 6 + [False] * (
        r["fold_spans"] - 6)


@pytest.mark.parametrize("B", [100, 4])
def test_cached_fold_equals_the_sequential_oracle(mesh_result, B):
    assert mesh_result[f"bitwise_B{B}"]


def test_one_device_session_leaves_the_mesh_alone(monkeypatch):
    opened = []

    @contextlib.contextmanager
    def span(name, **meta):
        opened.append(name)
        yield

    monkeypatch.setattr(trace, "span", span)
    data = np.random.default_rng(3).normal(10, 2, 200_000)
    store = ShardedStore.from_array(data.astype(np.float32), 50_000,
                                    interleave=False)
    group = StatisticGroup((Mean(), Quantile(0.5, nbins=256, lo=9.0,
                                             hi=11.0), Std()))
    before = trace.counts()
    EarlSession(PreMapSampler(store, seed=1), group, sigma=0.05,
                backend="fused_rng").run(jax.random.PRNGKey(0))
    after = trace.counts()
    assert "mesh.fold" not in opened and "session.run" in opened
    for k in (trace.MESH_FOLDS, trace.MESH_TRACES, trace.MESH_PSUM_BYTES):
        assert after.get(k, 0) == before.get(k, 0)
