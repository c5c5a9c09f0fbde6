"""Kill/resume bitwise contract for the crash-safe streaming bootstrap.

The contract under test: a ``bootstrap_streaming`` (or ``EarlSession``)
run that is KILLED mid-stream and resumed from its last checkpoint
produces a result BITWISE equal to the uninterrupted run.  This works
because chunk i's implicit Poisson weights are keyed
``offset_seed(base_seed, i)`` (position, not history), the fold is a
left-merge in chunk order, and the checkpoint cursor records exactly
(next chunk, rows consumed) — so the resumed suffix re-derives the same
per-chunk streams the dead run would have drawn.

Kills are simulated deterministically: a CheckpointManager subclass
raises AFTER its k-th successful save, which with ``checkpoint_every=1``
dies exactly at chunk boundary k — every boundary is exercised,
including "crash after the final chunk was already committed".
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.checkpoint.manager import CheckpointManager
from repro.core.reduce_api import (GroupedStatistic, KMeansStep, Mean,
                                   Quantile, StatisticGroup, Var)
from repro.core.session import EarlSession
from repro.core.streaming import bootstrap_streaming
from repro.data.sampler import PreMapSampler
from repro.data.store import ShardedStore

KEY = jax.random.PRNGKey(7)
CHUNK = 256                      # n=1000 → chunks [256, 256, 256, 232]
N_CHUNKS = 4


class _Kill(Exception):
    """The simulated mid-run death."""


class _DyingManager(CheckpointManager):
    """Commits its first ``die_after`` saves, then kills the run — the
    deterministic stand-in for SIGKILL at a chunk boundary."""

    def __init__(self, root, die_after, **kw):
        kw.setdefault("async_save", False)   # committed before the "crash"
        super().__init__(root, **kw)
        self.die_after = die_after
        self.saves = 0

    def save(self, *a, **kw):
        super().save(*a, **kw)
        self.saves += 1
        if self.saves >= self.die_after:
            raise _Kill(f"simulated crash after save #{self.saves}")


def _store_for(stat, n=1000, seed=0):
    rng = np.random.default_rng(seed)
    if getattr(stat, "num_groups", None) is not None:
        x = rng.normal(size=(n, 2)).astype(np.float32)
        k = rng.integers(0, stat.num_groups, size=(n, 1)).astype(np.float32)
        data = np.concatenate([x, k], axis=1)
    else:
        data = rng.normal(size=(n, 2)).astype(np.float32)
    return ShardedStore.from_array(data, 137, interleave=False)


def _tree_bitwise(a, b):
    ok = jax.tree_util.tree_map(
        lambda u, v: bool(np.array_equal(np.asarray(u), np.asarray(v))),
        a, b)
    assert all(jax.tree_util.tree_leaves(ok)), ok


STATS = [
    Mean(), Var(),
    Quantile(0.5, lo=-4.0, hi=4.0, nbins=64),
    KMeansStep(jnp.asarray(np.random.default_rng(2)
                           .normal(size=(3, 2)).astype(np.float32))),
    StatisticGroup([Mean(), Quantile(0.25, lo=-4.0, hi=4.0, nbins=32)]),
    GroupedStatistic(Mean(), 4),
]
_IDS = [("Grouped" if getattr(s, "num_groups", None) is not None
         else type(s).__name__) for s in STATS]


class TestStreamingKillResume:
    @pytest.mark.parametrize("die_after", range(1, N_CHUNKS + 1))
    @pytest.mark.parametrize("stat", STATS, ids=_IDS)
    def test_bitwise_at_every_chunk_boundary(self, stat, die_after,
                                             tmp_path):
        store = _store_for(stat)
        base = bootstrap_streaming(store, stat, B=16, key=KEY, chunk=CHUNK)
        assert base.stream.n_chunks == N_CHUNKS

        root = str(tmp_path / "ckpt")
        with pytest.raises(_Kill):
            bootstrap_streaming(store, stat, B=16, key=KEY, chunk=CHUNK,
                                checkpoint=_DyingManager(root, die_after),
                                checkpoint_every=1)
        r = bootstrap_streaming(
            store, stat, B=16, key=KEY, chunk=CHUNK, resume=True,
            checkpoint=CheckpointManager(root, async_save=False))
        assert r.stream.resumed_from_chunk == die_after
        assert r.stream.n_chunks == N_CHUNKS - die_after
        _tree_bitwise(base.thetas, r.thetas)
        _tree_bitwise(base.estimate, r.estimate)
        assert base.n == r.n

    def test_resume_onto_different_queue_depth(self, tmp_path):
        """The cursor pins the math (chunk index, seed); the prefetch
        queue depth is pure mechanics and may differ across the restart."""
        store = _store_for(Mean())
        base = bootstrap_streaming(store, Mean(), B=16, key=KEY,
                                   chunk=CHUNK, queue_depth=2)
        root = str(tmp_path / "ckpt")
        with pytest.raises(_Kill):
            bootstrap_streaming(store, Mean(), B=16, key=KEY, chunk=CHUNK,
                                queue_depth=2,
                                checkpoint=_DyingManager(root, 2))
        r = bootstrap_streaming(
            store, Mean(), B=16, key=KEY, chunk=CHUNK, queue_depth=5,
            resume=True,
            checkpoint=CheckpointManager(root, async_save=False))
        _tree_bitwise(base.thetas, r.thetas)
        _tree_bitwise(base.estimate, r.estimate)

    def test_ragged_tail_boundary(self, tmp_path):
        """Crash right before the ragged final chunk: the resumed run's
        only work is the 232-row tail, and the cursor's start_row lands
        mid-split (splits are 137 rows, chunks 256)."""
        store = _store_for(Mean())
        base = bootstrap_streaming(store, Mean(), B=16, key=KEY,
                                   chunk=CHUNK)
        root = str(tmp_path / "ckpt")
        with pytest.raises(_Kill):
            bootstrap_streaming(store, Mean(), B=16, key=KEY, chunk=CHUNK,
                                checkpoint=_DyingManager(root, 3))
        store.stats.reset()
        r = bootstrap_streaming(
            store, Mean(), B=16, key=KEY, chunk=CHUNK, resume=True,
            checkpoint=CheckpointManager(root, async_save=False))
        _tree_bitwise(base.thetas, r.thetas)
        # the resumed pass must NOT re-read the 768 committed rows
        assert store.stats.rows_read < store.N

    def test_checkpoint_overhead_run_without_resume_matches(self, tmp_path):
        """Checkpointing must be an observer: a checkpointed (uninterrupted)
        run returns the same bits as a plain run."""
        store = _store_for(Var())
        base = bootstrap_streaming(store, Var(), B=16, key=KEY, chunk=CHUNK)
        r = bootstrap_streaming(
            store, Var(), B=16, key=KEY, chunk=CHUNK,
            checkpoint=str(tmp_path / "ckpt"), checkpoint_every=2)
        _tree_bitwise(base.thetas, r.thetas)
        _tree_bitwise(base.estimate, r.estimate)
        assert r.stream.n_checkpoints == 2


class TestResumeValidation:
    def test_resume_needs_checkpoint(self):
        with pytest.raises(ValueError, match="resume"):
            bootstrap_streaming(_store_for(Mean()), Mean(), B=8, key=KEY,
                                chunk=CHUNK, resume=True)

    def test_fingerprint_rejects_different_statistic(self, tmp_path):
        store = _store_for(Mean())
        root = str(tmp_path / "ckpt")
        bootstrap_streaming(store, Mean(), B=8, key=KEY, chunk=CHUNK,
                            checkpoint=CheckpointManager(root,
                                                         async_save=False))
        with pytest.raises(ValueError, match="fingerprint mismatch"):
            bootstrap_streaming(
                store, Var(), B=8, key=KEY, chunk=CHUNK, resume=True,
                checkpoint=CheckpointManager(root, async_save=False))

    @pytest.mark.parametrize("kw", [
        dict(key=jax.random.PRNGKey(8)),      # different weight streams
        dict(chunk=128),                      # different chunk geometry
        dict(B=16),                           # different resample count
    ], ids=["key", "chunk", "B"])
    def test_fingerprint_rejects_different_run_knobs(self, tmp_path, kw):
        store = _store_for(Mean())
        root = str(tmp_path / "ckpt")
        args = dict(B=8, key=KEY, chunk=CHUNK)
        bootstrap_streaming(store, Mean(), checkpoint=CheckpointManager(
            root, async_save=False), **args)
        args.update(kw)
        with pytest.raises(ValueError, match="fingerprint mismatch"):
            bootstrap_streaming(
                store, Mean(), resume=True,
                checkpoint=CheckpointManager(root, async_save=False),
                **args)

    def test_fingerprint_rejects_different_array_params(self, tmp_path):
        """Same spec, different TRACED params (KMeans centroids) — the
        fingerprint hashes param bytes, not just the structural key."""
        rng = np.random.default_rng(3)
        c1 = jnp.asarray(rng.normal(size=(3, 2)).astype(np.float32))
        c2 = jnp.asarray(rng.normal(size=(3, 2)).astype(np.float32))
        store = _store_for(Mean())
        root = str(tmp_path / "ckpt")
        bootstrap_streaming(store, KMeansStep(c1), B=8, key=KEY,
                            chunk=CHUNK, checkpoint=CheckpointManager(
                                root, async_save=False))
        with pytest.raises(ValueError, match="fingerprint mismatch"):
            bootstrap_streaming(
                store, KMeansStep(c2), B=8, key=KEY, chunk=CHUNK,
                resume=True,
                checkpoint=CheckpointManager(root, async_save=False))

    def test_fingerprint_content_rejects_changed_bytes(self, tmp_path):
        """By default the fingerprint binds the run SHAPE (stat, B, key,
        chunk, N, dim) but not the bytes — ``fingerprint_content=True``
        folds the store's split checksums in, so resuming onto a
        same-shape store whose data changed refuses loudly instead of
        silently mixing two datasets."""
        rng = np.random.default_rng(0)
        data = rng.normal(size=(1000, 2)).astype(np.float32)
        store = ShardedStore.from_array(data, 137, interleave=False)
        root = str(tmp_path / "ckpt")
        with pytest.raises(_Kill):
            bootstrap_streaming(store, Mean(), B=8, key=KEY, chunk=CHUNK,
                                fingerprint_content=True,
                                checkpoint=_DyingManager(root, 2))
        changed = np.array(data)
        changed[500, 0] += 1.0                      # one element, same shape
        bad = ShardedStore.from_array(changed, 137, interleave=False)
        with pytest.raises(ValueError, match="fingerprint mismatch"):
            bootstrap_streaming(bad, Mean(), B=8, key=KEY, chunk=CHUNK,
                                resume=True, fingerprint_content=True,
                                checkpoint=CheckpointManager(
                                    root, async_save=False))
        # the SAME bytes resume cleanly, and bitwise so
        base = bootstrap_streaming(store, Mean(), B=8, key=KEY, chunk=CHUNK)
        same = ShardedStore.from_array(data, 137, interleave=False)
        r = bootstrap_streaming(same, Mean(), B=8, key=KEY, chunk=CHUNK,
                                resume=True, fingerprint_content=True,
                                checkpoint=CheckpointManager(
                                    root, async_save=False))
        _tree_bitwise(base.thetas, r.thetas)

    def test_content_digest_sensitivity(self):
        """store_content_digest: stable across calls, identical for
        identical bytes, different for a one-element change."""
        from repro.core.streaming import store_content_digest
        rng = np.random.default_rng(1)
        data = rng.normal(size=(500, 2)).astype(np.float32)
        a = ShardedStore.from_array(data, 64, interleave=False)
        assert store_content_digest(a) == store_content_digest(a)
        b = ShardedStore.from_array(np.array(data), 64, interleave=False)
        assert store_content_digest(a) == store_content_digest(b)
        mut = np.array(data)
        mut[0, 0] = np.float32(mut[0, 0]) + 1.0
        c = ShardedStore.from_array(mut, 64, interleave=False)
        assert store_content_digest(a) != store_content_digest(c)

    def test_default_fingerprint_binds_shape_not_content(self, tmp_path):
        """The documented default: without ``fingerprint_content`` a
        same-shape different-bytes store is accepted on resume (cheap
        fingerprints; callers opt into the checksum pass)."""
        rng = np.random.default_rng(2)
        data = rng.normal(size=(1000, 2)).astype(np.float32)
        store = ShardedStore.from_array(data, 137, interleave=False)
        root = str(tmp_path / "ckpt")
        with pytest.raises(_Kill):
            bootstrap_streaming(store, Mean(), B=8, key=KEY, chunk=CHUNK,
                                checkpoint=_DyingManager(root, 2))
        changed = np.array(data)
        changed[0, 0] += 1.0
        bad = ShardedStore.from_array(changed, 137, interleave=False)
        r = bootstrap_streaming(bad, Mean(), B=8, key=KEY, chunk=CHUNK,
                                resume=True,
                                checkpoint=CheckpointManager(
                                    root, async_save=False))
        assert r.stream.resumed_from_chunk == 2

    def test_foreign_checkpoint_rejected(self, tmp_path):
        """A checkpoint without a streaming cursor (e.g. an EarlSession or
        training snapshot) must be refused, not silently misread."""
        store = _store_for(Mean())
        root = str(tmp_path / "ckpt")
        mgr = CheckpointManager(root, async_save=False)
        stat = Mean()
        states = jax.vmap(lambda _: stat.init_state(2))(jnp.arange(8))
        mgr.save(0, (states, stat.init_state(2)), extra={"note": "foreign"})
        with pytest.raises(ValueError, match="cursor"):
            bootstrap_streaming(store, Mean(), B=8, key=KEY, chunk=CHUNK,
                                resume=True, checkpoint=mgr)


class TestSessionKillResume:
    """Same contract one layer up: an EarlSession killed between expansion
    rounds resumes from its checkpointed delta-maintained carry and ends
    with the identical early result."""

    # at sigma=0.01 the pilot's fitted c_v floor (~0.009) sits at sigma, so
    # SSABE asks for the whole store and the session rightly runs the exact
    # job instead; 0.02 needs two expansion rounds, which the kill needs.
    SIGMA = 0.02

    def _session(self, store, checkpoint=None):
        return EarlSession(PreMapSampler(store, seed=4), Mean(),
                           sigma=self.SIGMA, backend="fused_rng",
                           checkpoint=checkpoint)

    @pytest.fixture(scope="class")
    def store(self):
        rng = np.random.default_rng(1)
        data = rng.normal(loc=3.0, scale=5.0,
                          size=(200_000, 2)).astype(np.float32)
        return ShardedStore.from_array(data, 8192)

    def test_kill_after_first_round_resumes_bitwise(self, store, tmp_path):
        key = jax.random.PRNGKey(11)
        base = self._session(store).run(key)
        assert base.iterations > 1          # the kill point must be mid-run

        root = str(tmp_path / "ckpt")
        with pytest.raises(_Kill):
            self._session(store, _DyingManager(root, 1)).run(key)
        r = self._session(store, CheckpointManager(
            root, async_save=False)).run(key, resume=True)
        assert r.iterations == base.iterations
        assert r.n_used == base.n_used
        assert r.cv == base.cv
        _tree_bitwise(r.result, base.result)
        _tree_bitwise(r.ci_lo, base.ci_lo)
        assert len(r.history) == len(base.history)

    def test_resume_after_completed_run_rederives_result(self, store,
                                                         tmp_path):
        """Killed between the final save and the return: resume re-checks
        the sigma gate on the restored carry and returns without extending
        the sample any further."""
        key = jax.random.PRNGKey(11)
        root = str(tmp_path / "ckpt")
        full = self._session(store, CheckpointManager(
            root, async_save=False)).run(key)
        store.stats.reset()
        again = self._session(store, CheckpointManager(
            root, async_save=False)).run(key, resume=True)
        assert again.iterations == full.iterations
        assert again.n_used == full.n_used
        _tree_bitwise(again.result, full.result)
        # only the (capped) pilot is re-read; the main sample is not
        assert store.stats.rows_read < full.n_used

    def test_session_fingerprint_rejects_different_stat(self, store,
                                                        tmp_path):
        key = jax.random.PRNGKey(11)
        root = str(tmp_path / "ckpt")
        with pytest.raises(_Kill):
            self._session(store, _DyingManager(root, 1)).run(key)
        bad = EarlSession(PreMapSampler(store, seed=4), Var(),
                          sigma=self.SIGMA, backend="fused_rng",
                          checkpoint=CheckpointManager(root,
                                                       async_save=False))
        with pytest.raises(ValueError, match="fingerprint mismatch"):
            bad.run(key, resume=True)

    def test_session_resume_needs_checkpoint(self, store):
        with pytest.raises(ValueError, match="resume"):
            self._session(store).run(jax.random.PRNGKey(0), resume=True)


class TestPinnedResumeOverGrowingStore:
    """``n_rows=`` + ``fingerprint_content=True`` is the durable-ingest
    resume contract: a pinned run checkpointed mid-stream must resume
    BITWISE even after the log grew underneath it — the fingerprint binds
    the pinned prefix (extent + prefix bytes), not the whole store."""

    def test_resume_after_growth_is_bitwise(self, tmp_path):
        rng = np.random.default_rng(9)
        splits = [rng.normal(size=(250, 2)).astype(np.float32)
                  for _ in range(6)]
        n_rows = 250 * 4                        # pin to the first 4 batches

        base_store = ShardedStore([s.copy() for s in splits[:4]])
        base = bootstrap_streaming(base_store, Mean(), B=8, key=KEY,
                                   chunk=CHUNK)

        store = ShardedStore([s.copy() for s in splits[:4]])
        root = str(tmp_path / "ckpt")
        with pytest.raises(_Kill):
            bootstrap_streaming(store, Mean(), B=8, key=KEY, chunk=CHUNK,
                                n_rows=n_rows, fingerprint_content=True,
                                checkpoint=_DyingManager(root, 2))
        for s in splits[4:]:                    # the log grows meanwhile
            store.append_split(s.copy())
        r = bootstrap_streaming(store, Mean(), B=8, key=KEY, chunk=CHUNK,
                                n_rows=n_rows, resume=True,
                                fingerprint_content=True,
                                checkpoint=CheckpointManager(
                                    root, async_save=False))
        assert r.stream.resumed_from_chunk == 2
        _tree_bitwise(base.thetas, r.thetas)
        _tree_bitwise(base.estimate, r.estimate)
        assert base.n == r.n == n_rows

    def test_fingerprint_binds_the_extent(self, tmp_path):
        """Resuming with a DIFFERENT n_rows is a different run and must
        refuse loudly, not silently re-scale the correction."""
        store = _store_for(Mean())
        root = str(tmp_path / "ckpt")
        with pytest.raises(_Kill):
            bootstrap_streaming(store, Mean(), B=8, key=KEY, chunk=CHUNK,
                                n_rows=750,
                                checkpoint=_DyingManager(root, 2))
        with pytest.raises(ValueError, match="fingerprint mismatch"):
            bootstrap_streaming(store, Mean(), B=8, key=KEY, chunk=CHUNK,
                                n_rows=500, resume=True,
                                checkpoint=CheckpointManager(
                                    root, async_save=False))
