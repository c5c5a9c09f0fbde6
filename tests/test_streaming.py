"""Streaming bootstrap driver + counted-store iteration.

The contracts under test:

* ``bootstrap_streaming`` is BITWISE equal to ``bootstrap_chunked`` over
  ``store.read_all()`` under the same (key, chunk) — same per-chunk seeds
  (``offset_seed(base, i)``), same ragged-tail padding, same single-pass
  unweighted estimate.
* The per-chunk jitted update's intermediates are O(B·d + chunk·d) —
  independent of n (the driver's device footprint can't grow with the
  store).
* ``ShardedStore.iter_batches`` yields the store in order as fixed-size
  batches (ragged tail), opens each split exactly once, and
  ``ReadStats`` stays consistent under concurrent mutation (the prefetch
  thread and main thread both touch it).
"""
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.bootstrap import bootstrap_chunked
from repro.core.reduce_api import (KMeansStep, Mean, Quantile,
                                   StatisticGroup, Var)
from repro.core.streaming import bootstrap_streaming
from repro.data.store import ReadStats, ShardedStore


def _store(n=10_000, d=3, split_size=1234, seed=0):
    rng = np.random.default_rng(seed)
    data = rng.normal(size=(n, d)).astype(np.float32)
    return ShardedStore.from_array(data, split_size, interleave=False)


def _tree_bitwise(a, b):
    ok = jax.tree_util.tree_map(
        lambda u, v: bool(np.array_equal(np.asarray(u), np.asarray(v))),
        a, b)
    assert all(jax.tree_util.tree_leaves(ok)), ok


# ----------------------------------------------------------------------------
# store iteration
# ----------------------------------------------------------------------------
class TestIterBatches:
    def test_batches_reassemble_the_store(self):
        store = _store()
        batches = list(store.iter_batches(3000))
        assert [len(b) for b in batches] == [3000, 3000, 3000, 1000]
        np.testing.assert_array_equal(np.concatenate(batches),
                                      np.concatenate(store.splits))

    def test_each_split_opened_exactly_once(self):
        store = _store()
        store.stats.reset()
        list(store.iter_batches(3000))
        assert store.stats.splits_opened == len(store.splits)
        assert store.stats.rows_read == store.N

    def test_chunk_smaller_than_split_and_larger_than_store(self):
        store = _store(n=100, split_size=40)
        assert [len(b) for b in store.iter_batches(7)] == [7] * 14 + [2]
        whole = list(store.iter_batches(10_000))
        assert len(whole) == 1 and len(whole[0]) == 100

    def test_exact_multiple_has_no_ragged_tail(self):
        store = _store(n=120, split_size=40)
        assert [len(b) for b in store.iter_batches(60)] == [60, 60]

    def test_nonpositive_chunk_raises(self):
        store = _store(n=10, split_size=5)
        with pytest.raises(ValueError, match="chunk"):
            next(store.iter_batches(0))

    def test_start_row_resumes_mid_stream(self):
        store = _store()
        full = list(store.iter_batches(3000))
        store.stats.reset()
        tail = list(store.iter_batches(3000, start_row=6000))
        np.testing.assert_array_equal(np.concatenate(tail),
                                      np.concatenate(full[2:]))
        # splits entirely before the cursor are never opened: the resumed
        # pass pays only for the rows it still needs
        assert store.stats.splits_opened < len(store.splits)
        assert store.stats.rows_read < store.N

    def test_start_row_bounds(self):
        store = _store(n=10, split_size=5)
        with pytest.raises(ValueError, match="start_row"):
            next(store.iter_batches(4, start_row=11))
        with pytest.raises(ValueError, match="start_row"):
            next(store.iter_batches(4, start_row=-1))
        assert list(store.iter_batches(4, start_row=10)) == []

    def test_read_all_matches_concatenated_splits(self):
        store = _store()
        np.testing.assert_array_equal(store.read_all(),
                                      np.concatenate(store.splits))

    def test_read_all_counts_one_pass(self):
        store = _store()
        store.stats.reset()
        store.read_all()
        assert store.stats.splits_opened == len(store.splits)
        assert store.stats.rows_read == store.N


class TestReadStatsThreadSafety:
    def test_concurrent_adds_lose_nothing(self):
        stats = ReadStats()
        PER, THREADS = 5000, 8

        def hammer():
            for _ in range(PER):
                stats.add(splits=1, rows=3)

        ts = [threading.Thread(target=hammer) for _ in range(THREADS)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert stats.splits_opened == PER * THREADS
        assert stats.rows_read == 3 * PER * THREADS


# ----------------------------------------------------------------------------
# the streaming driver
# ----------------------------------------------------------------------------
class TestStreamingBitwiseEqualsChunked:
    KEY = jax.random.PRNGKey(5)
    CHUNK = 3000              # store.N = 10000 → ragged 1000-row tail

    def _both(self, stat):
        store = _store()
        vals = jnp.asarray(store.read_all())
        rc = bootstrap_chunked(vals, stat, B=16, key=self.KEY,
                               chunk=self.CHUNK, backend="fused_rng")
        rs = bootstrap_streaming(store, stat, B=16, key=self.KEY,
                                 chunk=self.CHUNK)
        return rc, rs

    @pytest.mark.parametrize("stat", [
        Mean(), Var(),
        Quantile(0.5, lo=-4.0, hi=4.0, nbins=64),
        StatisticGroup([Mean(), Quantile(0.25, lo=-4.0, hi=4.0, nbins=32)]),
        KMeansStep(jnp.asarray(np.random.default_rng(2)
                               .normal(size=(4, 3)).astype(np.float32))),
    ], ids=lambda s: type(s).__name__)
    def test_thetas_and_estimate_bitwise(self, stat):
        rc, rs = self._both(stat)
        _tree_bitwise(rc.thetas, rs.thetas)
        _tree_bitwise(rc.estimate, rs.estimate)
        assert rc.n == rs.n

    def test_1d_values_and_chunk_equal_to_n(self):
        rng = np.random.default_rng(9)
        store = ShardedStore.from_array(
            rng.normal(size=4096).astype(np.float32), 1000,
            interleave=False)
        rc = bootstrap_chunked(jnp.asarray(store.read_all()), Mean(), B=8,
                               key=self.KEY, chunk=4096,
                               backend="fused_rng")
        rs = bootstrap_streaming(store, Mean(), B=8, key=self.KEY,
                                 chunk=4096)
        _tree_bitwise(rc.thetas, rs.thetas)
        _tree_bitwise(rc.estimate, rs.estimate)

    def test_stream_report_populated(self):
        _, rs = self._both(Mean())
        sr = rs.stream
        assert sr.n_chunks == 4 and sr.rows == 10_000
        assert sr.wall_s > 0 and sr.dispatch_s >= 0 and sr.wait_s >= 0

    def test_stream_report_splits_dispatch_from_drain(self):
        """``dispatch_s`` is the enqueue alone and ``drain_s`` the trailing
        wait for the device: disjoint stretches of the main thread, so with
        ``wait_s`` they fit inside the wall time; the answer is unchanged."""
        rc, rs = self._both(StatisticGroup(
            [Mean(), Quantile(0.25, lo=-4.0, hi=4.0, nbins=32)]))
        sr = rs.stream
        assert sr.drain_s >= 0 and sr.dispatch_s > 0
        assert sr.dispatch_s + sr.drain_s + sr.wait_s <= sr.wall_s
        _tree_bitwise(rc.thetas, rs.thetas)
        _tree_bitwise(rc.estimate, rs.estimate)

    def test_reads_store_exactly_once(self):
        store = _store()
        store.stats.reset()
        bootstrap_streaming(store, Mean(), B=8, key=self.KEY,
                            chunk=self.CHUNK)
        assert store.stats.splits_opened == len(store.splits)
        assert store.stats.rows_read == store.N


class TestStreamingValidation:
    def test_rejects_materialized_backend(self):
        with pytest.raises(ValueError, match="fused_rng"):
            bootstrap_streaming(_store(n=100, split_size=50), Mean(), B=8,
                                key=jax.random.PRNGKey(0), chunk=64,
                                backend=None)

    def test_rejects_empty_store(self):
        with pytest.raises(ValueError, match="non-empty"):
            bootstrap_streaming(ShardedStore([]), Mean(), B=8,
                                key=jax.random.PRNGKey(0), chunk=64)

    def test_rejects_bad_queue_depth(self):
        with pytest.raises(ValueError, match="queue_depth"):
            bootstrap_streaming(_store(n=100, split_size=50), Mean(), B=8,
                                key=jax.random.PRNGKey(0), chunk=64,
                                queue_depth=0)

    def test_store_error_propagates_from_prefetch_thread(self):
        store = _store(n=100, split_size=50)

        def boom(i):
            raise OSError("split unreadable")

        store.read_split = boom
        with pytest.raises(OSError, match="split unreadable"):
            bootstrap_streaming(store, Mean(), B=8,
                                key=jax.random.PRNGKey(0), chunk=64)


class TestProducerLifecycle:
    """A consumer-side failure must not strand the prefetch thread: before
    the stop-event fix, a chunk that poisoned the consumer's jitted update
    left the producer blocked forever in ``Queue.put`` on the full hand-off
    queue — a thread (and its staged device buffers) leaked per failure."""

    @staticmethod
    def _prefetch_threads():
        return [t for t in threading.enumerate()
                if t.name == "earl-stream-prefetch" and t.is_alive()]

    def test_poisoned_chunk_does_not_leak_producer_thread(self):
        rng = np.random.default_rng(3)
        splits = [rng.normal(size=(64, 3)).astype(np.float32)
                  for _ in range(8)]
        # batch 1 has the wrong width: the consumer's update raises at
        # trace time while the producer still has 6 batches to stage
        # through a depth-2 queue (i.e. it WOULD block without the fix)
        splits[1] = rng.normal(size=(64, 2)).astype(np.float32)
        store = ShardedStore(splits)
        assert not self._prefetch_threads()
        with pytest.raises(Exception):
            bootstrap_streaming(store, Mean(), B=8,
                                key=jax.random.PRNGKey(0), chunk=64,
                                queue_depth=2)
        # the driver's cleanup (stop + drain + join) already ran: no
        # prefetch thread may survive the call
        assert not self._prefetch_threads()


class TestStreamingDeviceFootprint:
    """The per-chunk update's intermediates are bounded by the chunk and
    state sizes — NOT by n.  The streamed carry never holds anything of
    size n on device: trace the chunk update and cap every aval."""

    def test_chunk_update_intermediates_are_n_independent(self):
        from test_matrix_free import _max_intermediate_size

        from repro.core.reduce_api import split_params
        from repro.core.streaming import _stream_chunk_jit

        B, chunk, d = 64, 4096, 2
        stat = Mean()
        spec, params = split_params(stat)
        states = jax.vmap(lambda _: stat.init_state(d))(jnp.arange(B))
        est = stat.init_state(d)
        xi = jnp.zeros((chunk, d), jnp.float32)
        vi = jnp.ones((chunk,), jnp.float32)

        biggest = _max_intermediate_size(
            lambda st, e, x: _stream_chunk_jit(
                st, e, x, vi, jnp.int32(0), jnp.int32(0),
                params, spec, B),
            states, est, xi)
        # the (B, chunk) per-chunk weight matrix would be 262144 elements;
        # the largest legitimate intermediate is the (B, block_n=512)
        # weight tile — and, the streaming contract, nothing here depends
        # on the store's n at all (n never enters the trace).
        assert biggest <= B * 512, (
            f"largest per-chunk intermediate has {biggest} elements")

    def test_trace_has_no_n_sized_aval(self):
        """Same trace, explicit shape scan: no aval's leading axis exceeds
        the chunk (i.e. nothing scales with the 10^6-row store this chunk
        might be drawn from)."""
        from test_matrix_free import _walk_shapes

        from repro.core.reduce_api import split_params
        from repro.core.streaming import _stream_chunk_jit

        B, chunk, d = 64, 4096, 2
        stat = Mean()
        spec, params = split_params(stat)
        states = jax.vmap(lambda _: stat.init_state(d))(jnp.arange(B))
        est = stat.init_state(d)
        xi = jnp.zeros((chunk, d), jnp.float32)
        vi = jnp.ones((chunk,), jnp.float32)
        jaxpr = jax.make_jaxpr(
            lambda st, e, x: _stream_chunk_jit(
                st, e, x, vi, jnp.int32(0), jnp.int32(0),
                params, spec, B))(states, est, xi)
        shapes = _walk_shapes(jaxpr.jaxpr, [])
        assert max((max(s) for s in shapes if s), default=0) <= chunk


class TestPinnedExtent:
    """``n_rows=`` pins a pass to the store's first n_rows rows — the
    stable-prefix contract a growing ingest log (live.DurableIngestLog)
    needs: the result must be bitwise what a store holding ONLY those
    rows would produce, and the extent must be validated."""

    KEY = jax.random.PRNGKey(11)

    def test_pinned_run_equals_prefix_store_bitwise(self):
        rng = np.random.default_rng(4)
        splits = [rng.normal(size=(64, 3)).astype(np.float32)
                  for _ in range(6)]
        grown = ShardedStore([s.copy() for s in splits])
        n_rows = 64 * 4
        prefix = ShardedStore([s.copy() for s in splits[:4]])
        r_pin = bootstrap_streaming(grown, Mean(), B=16, key=self.KEY,
                                    chunk=100, n_rows=n_rows)
        r_ref = bootstrap_streaming(prefix, Mean(), B=16, key=self.KEY,
                                    chunk=100)
        _tree_bitwise(r_pin.thetas, r_ref.thetas)
        _tree_bitwise(r_pin.estimate, r_ref.estimate)
        assert r_pin.n == r_ref.n == n_rows
        assert r_pin.stream.rows == n_rows

    def test_pin_mid_split_trims_the_straddling_chunk(self):
        rng = np.random.default_rng(5)
        data = rng.normal(size=(300, 2)).astype(np.float32)
        grown = ShardedStore.from_array(data, 128, interleave=False)
        r_pin = bootstrap_streaming(grown, Mean(), B=8, key=self.KEY,
                                    chunk=64, n_rows=200)
        prefix = ShardedStore.from_array(data[:200], 128, interleave=False)
        r_ref = bootstrap_streaming(prefix, Mean(), B=8, key=self.KEY,
                                    chunk=64)
        _tree_bitwise(r_pin.thetas, r_ref.thetas)
        assert r_pin.n == 200

    def test_n_rows_out_of_range_raises(self):
        store = _store(n=100, split_size=40)
        for bad in (0, -1, 101):
            with pytest.raises(ValueError, match="n_rows"):
                bootstrap_streaming(store, Mean(), B=8,
                                    key=self.KEY, chunk=64, n_rows=bad)
