"""The fold and the resume cursor that every entry point shares.

* ``core/fold.py`` is where rows become resample states: the stream pass,
  ``bootstrap_chunked``, a cumulative ``LiveSession`` and the sharded fold
  must give bit-identical states and estimate for the same rows under one
  base seed, because they all fold through it;
* ``checkpoint.manager.cursor`` and ``CheckpointManager.restore_cursor``
  are the one resume protocol: each entry point refuses a checkpoint of
  another by its cursor's kind, and a stream checkpoint written before
  cursors had a kind still resumes bitwise.
"""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.checkpoint.manager import CheckpointManager
from repro.core.bootstrap import (bootstrap_chunked, seed_from_key,
                                  sharded_fused_states)
from repro.core.reduce_api import Mean, Quantile, StatisticGroup, Std
from repro.core.session import EarlSession
from repro.core.streaming import bootstrap_streaming
from repro.data.sampler import PreMapSampler
from repro.data.store import ShardedStore
from repro.live import IngestLog, LiveSession, LogBatch

KEY = jax.random.PRNGKey(23)
SESSION_KEY = jax.random.PRNGKey(11)
B = 8
CHUNK = 256
N_CHUNKS = 4


class _Kill(Exception):
    """The simulated mid-run death."""


class _DyingManager(CheckpointManager):
    """Commits its first ``die_after`` saves, then kills the run."""

    def __init__(self, root, die_after):
        super().__init__(root, async_save=False)
        self.die_after = die_after
        self.saves = 0

    def save(self, *a, **kw):
        super().save(*a, **kw)
        self.saves += 1
        if self.saves >= self.die_after:
            raise _Kill(f"simulated crash after save #{self.saves}")


def _tree_bitwise(a, b):
    ok = jax.tree_util.tree_map(
        lambda u, v: bool(np.array_equal(np.asarray(u), np.asarray(v))),
        a, b)
    assert all(jax.tree_util.tree_leaves(ok)), ok


def _rows(n=N_CHUNKS * CHUNK, seed=0):
    """N(10, 2) rows, one column: the paper's job's data."""
    rng = np.random.default_rng(seed)
    return (10.0 + 2.0 * rng.standard_normal((n, 1))).astype(np.float32)


STATS = [
    Mean(),
    Quantile(0.5, lo=6.0, hi=14.0, nbins=64),
    StatisticGroup((Mean(), Quantile(0.5, nbins=2048, lo=9.0, hi=11.0),
                    Std())),
]


@pytest.mark.parametrize("stat", STATS, ids=["Mean", "Quantile",
                                             "paper-jobs-group"])
def test_every_entry_point_folds_the_same_bits(stat):
    x = _rows()
    store = ShardedStore.from_array(x, 300, interleave=False)

    stream = bootstrap_streaming(store, stat, B, KEY, chunk=CHUNK)
    chunked = bootstrap_chunked(jnp.asarray(x), stat, B, KEY, chunk=CHUNK,
                                backend="fused_rng")
    live = LiveSession(None, stat, B, KEY)
    for s in range(N_CHUNKS):
        reports = live.feed(LogBatch(s, s * CHUNK,
                                     x[s * CHUNK:(s + 1) * CHUNK]))
    (rep,) = reports
    states, est = sharded_fused_states(stat, seed_from_key(KEY),
                                       jnp.asarray(x), B, nshards=1,
                                       chunk=CHUNK, with_estimate=True)
    _tree_bitwise(live._ring[0].states, states)
    _tree_bitwise(live._ring[0].est, est)
    sharded = (stat.correct(jax.vmap(stat.finalize)(states), 1.0),
               stat.correct(stat.finalize(est), 1.0))
    for thetas, estimate in ((chunked.thetas, chunked.estimate),
                             (rep.thetas, rep.estimate), sharded):
        _tree_bitwise(stream.thetas, thetas)
        _tree_bitwise(stream.estimate, estimate)


# -- the resume cursor ------------------------------------------------------
def _store():
    return ShardedStore.from_array(_rows(n=1000, seed=3)[:, 0], 137,
                                   interleave=False)


def _session_store():
    rng = np.random.default_rng(1)
    data = rng.normal(loc=3.0, scale=5.0,
                      size=(200_000, 2)).astype(np.float32)
    return ShardedStore.from_array(data, 8192)


def _session(checkpoint):
    # under SESSION_KEY, sigma 0.02 needs more than one round over this
    # store, so the first round's checkpoint lands before the run ends
    return EarlSession(PreMapSampler(_session_store(), seed=4), Mean(),
                       sigma=0.02, backend="fused_rng",
                       checkpoint=checkpoint)


def _live_log():
    log = IngestLog()
    for s in range(3):
        log.append(_rows(n=32, seed=10 + s))
    return log


def _write(kind, root):
    """A real checkpoint of the ``kind`` entry point under ``root``."""
    mgr = CheckpointManager(root, async_save=False)
    if kind == "session":
        with pytest.raises(_Kill):
            _session(_DyingManager(root, 1)).run(SESSION_KEY)
    elif kind == "stream":
        bootstrap_streaming(_store(), Mean(), B, KEY, chunk=CHUNK,
                            checkpoint=mgr)
    else:
        LiveSession(_live_log(), Mean(), B, KEY, checkpoint=mgr).poll()
    assert mgr.meta()["cursor"]["kind"] == kind


def _resume(kind, root):
    mgr = CheckpointManager(root, async_save=False)
    if kind == "session":
        _session(mgr).run(SESSION_KEY, resume=True)
    elif kind == "stream":
        bootstrap_streaming(_store(), Mean(), B, KEY, chunk=CHUNK,
                            checkpoint=mgr, resume=True)
    else:
        LiveSession(_live_log(), Mean(), B, KEY, checkpoint=mgr,
                    resume=True)


_NAMES = {"session": "EarlSession", "stream": "bootstrap_streaming",
          "live": "LiveSession"}


@pytest.mark.parametrize("reader,writer", [
    ("session", "stream"), ("stream", "live"), ("live", "session")])
def test_each_entry_point_refuses_anothers_checkpoint(reader, writer,
                                                      tmp_path):
    root = str(tmp_path / "ckpt")
    _write(writer, root)
    name = _NAMES[reader]
    with pytest.raises(ValueError,
                       match=f"has no {name} cursor — not a {name} "
                             "checkpoint"):
        _resume(reader, root)


def test_stream_checkpoint_without_kind_resumes_bitwise(tmp_path):
    """Cursors of the stream pass carried no kind until they all did: a
    checkpoint in that format still resumes, to the same bits."""
    store = _store()
    base = bootstrap_streaming(store, Mean(), B, KEY, chunk=CHUNK)
    root = str(tmp_path / "ckpt")
    with pytest.raises(_Kill):
        bootstrap_streaming(store, Mean(), B, KEY, chunk=CHUNK,
                            checkpoint=_DyingManager(root, 2))
    mgr = CheckpointManager(root, async_save=False)
    meta_path = os.path.join(root, f"ckpt_{mgr.latest_step():08d}",
                             "meta.json")
    with open(meta_path) as f:
        meta = json.load(f)
    assert meta["extra"]["cursor"].pop("kind") == "stream"
    with open(meta_path, "w") as f:
        json.dump(meta, f)

    r = bootstrap_streaming(store, Mean(), B, KEY, chunk=CHUNK,
                            checkpoint=mgr, resume=True)
    assert r.stream.resumed_from_chunk == 2
    _tree_bitwise(base.thetas, r.thetas)
    _tree_bitwise(base.estimate, r.estimate)
