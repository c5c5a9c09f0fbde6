"""Sampling over the sharded store (paper §3.3): uniformity, prefix
semantics, the lazily drawn permutation, read accounting, pre- vs
post-map."""
import itertools

import numpy as np
import pytest
from scipy import stats as sps

from repro import trace
from repro.data import (PostMapSampler, PreMapSampler, ShardedStore,
                        StratifiedSampler, synthetic_numeric)
from repro.data.sampler import PermutationSampler, lazy_cutoff


def _store(n=50_000, nvals=20, interleave=True):
    # clustered layout: values sorted -> worst case for block sampling
    data = np.sort(np.repeat(np.arange(nvals), n // nvals)).astype(
        np.float32)[:, None]
    return ShardedStore.from_array(data, 1024, interleave=interleave)


class TestUniformity:
    def test_chi_square_uniform_sample(self):
        """Prefix samples from an adversarially clustered layout must be
        uniform (the paper's block-sampling hazard, §7)."""
        store = _store()
        sampler = PreMapSampler(store, seed=0)
        sample = np.asarray(sampler.take(0, 5000)).ravel()
        counts = np.bincount(sample.astype(int), minlength=20)
        chi2, p = sps.chisquare(counts)
        assert p > 0.001, f"sample not uniform: chi2={chi2}, p={p}"

    def test_prefixes_are_nested(self):
        store = _store()
        sampler = PreMapSampler(store, seed=1)
        a = np.asarray(sampler.take(0, 100))
        b = np.asarray(sampler.take(0, 500))
        np.testing.assert_array_equal(a, b[:100])

    def test_no_replacement_within_prefix(self):
        store = ShardedStore.from_array(
            np.arange(10_000, dtype=np.float32)[:, None], 512)
        sampler = PreMapSampler(store, seed=2)
        s = np.asarray(sampler.take(0, 10_000)).ravel()
        assert len(np.unique(s)) == 10_000


def _ids(N, split_rows=1024):
    """A store whose one column holds each row's index."""
    return ShardedStore.from_array(np.arange(N, dtype=np.float32)[:, None],
                                   split_rows, interleave=False)


def _rows(sampler, start, stop):
    return np.asarray(sampler.take(start, stop)).ravel().astype(np.int64)


def _split_takes(s, stop):
    return np.concatenate([_rows(s, a, min(a + 257, stop))
                           for a in range(0, stop, 257)])


def _resume_mid_prefix(s, stop):
    # a fresh sampler's first take starts where a checkpoint left off
    return np.concatenate([_rows(PreMapSampler(s.store, seed=9), 0, 1000),
                           _rows(s, 1000, stop)])


def _after_perm(s, stop):
    s.perm
    return _rows(s, 0, stop)


def _perm_after_takes(s, stop):
    _rows(s, 0, stop // 3)
    return s.perm[:stop].copy()


class TestLazyPermutation:
    """The permutation's prefix is drawn only as far as ``take`` reaches;
    ``take(a, b)`` is a function of (seed, N, a, b) alone."""

    N = 50_000

    @pytest.mark.parametrize("pattern", [
        _split_takes, _resume_mid_prefix, _after_perm, _perm_after_takes],
        ids=["split-takes", "fresh-resume", "after-perm",
             "perm-after-takes"])
    @pytest.mark.parametrize("stop", [3000, 9000],
                             ids=["inside-K", "past-K"])
    def test_rows_do_not_depend_on_the_calls_before(self, pattern, stop):
        store = _ids(self.N)
        one_take = _rows(PreMapSampler(store, seed=9), 0, stop)
        assert lazy_cutoff(self.N) == 6250
        np.testing.assert_array_equal(
            pattern(PreMapSampler(store, seed=9), stop), one_take)

    @pytest.mark.parametrize("N", [1, 2, 5, 8, 9, 1000, 4097, 50_000])
    def test_whole_order_is_a_permutation_across_the_cutoff(self, N):
        s = PreMapSampler(_ids(N), seed=N)
        head = _rows(s, 0, lazy_cutoff(N))
        assert len(np.unique(head)) == len(head)
        perm = s.perm
        np.testing.assert_array_equal(perm[:len(head)], head)
        np.testing.assert_array_equal(np.sort(perm), np.arange(N))
        np.testing.assert_array_equal(_rows(s, 0, N), perm)

    def test_other_seeds_give_other_prefixes(self):
        store = _ids(self.N)
        a, b = (_rows(PreMapSampler(store, seed=k), 0, 100) for k in (1, 2))
        assert not np.array_equal(a, b)

    def test_all_orders_of_five_are_equally_likely(self):
        """The whole order (K = 1 lazy position, then the shuffled rest):
        each of the 5! orders over 12,000 seeds."""
        store = _ids(5)
        index = {p: i for i, p in
                 enumerate(itertools.permutations(range(5)))}
        counts = np.zeros(120)
        for seed in range(12_000):
            counts[index[tuple(PermutationSampler(store, seed).perm)]] += 1
        chi2, p = sps.chisquare(counts)
        assert p > 0.001, f"orders not uniform: chi2={chi2}, p={p}"

    def test_every_position_is_uniform_across_the_cutoff(self):
        """N = 16, K = 2: the value at each position of the whole order,
        over 8,000 seeds."""
        store = _ids(16)
        table = np.zeros((16, 16))
        for seed in range(8_000):
            table[np.arange(16), PermutationSampler(store, seed).perm] += 1
        for pos, row in enumerate(table):
            chi2, p = sps.chisquare(row)
            assert p > 0.001, f"position {pos}: chi2={chi2}, p={p}"

    def test_lazy_prefix_is_a_uniform_sample(self):
        """N = 1000, 100 rows (K = 125): the first row, each row's inclusion
        and the co-inclusion of every pair among rows 0-19 (which share
        bitmap bytes), over 10,000 seeds."""
        N, k, S = 1000, 100, 10_000
        store = _ids(N)
        first = np.zeros(N)
        included = np.zeros(N)
        pairs = np.zeros((20, 20))
        for seed in range(S):
            rows = _rows(PreMapSampler(store, seed=seed), 0, k)
            first[rows[0]] += 1
            included[rows] += 1
            low = np.zeros(20)
            low[rows[rows < 20]] = 1
            pairs += np.outer(low, low)
        assert included.sum() == S * k
        for name, counts in (("first", first), ("inclusion", included),
                             ("pairs", pairs[np.triu_indices(20, 1)])):
            chi2, p = sps.chisquare(counts)
            assert p > 0.001, f"{name} not uniform: chi2={chi2}, p={p}"
        expected = S * k * (k - 1) / (N * (N - 1))
        assert abs(pairs[np.triu_indices(20, 1)].mean() / expected - 1) < 0.05

    def test_a_pilot_from_a_large_store_materialises_nothing(self):
        store = ShardedStore.from_array(np.zeros((1 << 20, 1), np.float32),
                                        1 << 16, interleave=False)
        before = trace.counts()
        s = PreMapSampler(store, seed=2147483901)
        s.take(0, 8192)
        s.take(0, 8192)                      # the round reuses the pilot
        after = trace.counts()
        got = {name: after.get(name, 0) - before.get(name, 0) for name in
               ("sampler_rows_drawn", "sampler_redraws",
                "sampler_full_permute")}
        assert got["sampler_full_permute"] == 0
        assert got["sampler_rows_drawn"] == 8192
        # three blocks of 4096 candidates from 2^20 rows: ~72 duplicates
        assert 0 < got["sampler_redraws"] < 150
        assert s._order is None

    def test_counters_do_not_depend_on_how_the_takes_split(self):
        store = _ids(self.N)

        def drawn(takes):
            s = PreMapSampler(store, seed=3)
            before = trace.counts()
            for a, b in takes:
                s.take(a, b)
            after = trace.counts()
            return [after.get(n, 0) - before.get(n, 0) for n in
                    ("sampler_rows_drawn", "sampler_redraws")]

        one = drawn([(0, 6000)])
        assert one == drawn([(0, 10), (10, 4100), (4100, 6000)])
        assert one == drawn([(5000, 6000), (0, 100)])
        assert one[0] == 6000


class TestReadAccounting:
    def test_pre_map_reads_only_sample(self):
        store = _store()
        sampler = PreMapSampler(store, seed=3)
        sampler.take(0, 1000)
        assert store.stats.rows_read == 1000
        assert store.stats.splits_opened <= len(store.splits)

    def test_post_map_reads_everything_once(self):
        store = _store()
        sampler = PostMapSampler(store, seed=3)
        sampler.take(0, 1000)
        assert store.stats.rows_read == store.N
        assert sampler.kv_count == store.N        # exact ⟨k,v⟩ accounting
        before = store.stats.rows_read
        sampler.take(1000, 2000)                  # cached: no re-read
        assert store.stats.rows_read == before

    def test_pre_and_post_same_rows(self):
        data = synthetic_numeric(20_000, 10, 2, seed=5)
        s1 = PreMapSampler(ShardedStore.from_array(data, 1024, seed=7),
                           seed=9)
        s2 = PostMapSampler(ShardedStore.from_array(data, 1024, seed=7),
                            seed=9)
        np.testing.assert_allclose(np.asarray(s1.take(0, 500)),
                                   np.asarray(s2.take(0, 500)))


def _keyed_store(sizes, seed=0, split_rows=1024):
    """One data column + integer key column; stratum g has sizes[g] rows."""
    rng = np.random.default_rng(seed)
    keys = np.repeat(np.arange(len(sizes)), sizes)
    rng.shuffle(keys)
    data = np.stack([rng.normal(size=len(keys)), keys], axis=1).astype(
        np.float32)
    return ShardedStore.from_array(data, split_rows)


class TestStratifiedSampler:
    SIZES = [9000, 600, 300, 100]           # heavy skew: 90:6:3:1

    def test_equal_shares_balance_prefixes(self):
        store = _keyed_store(self.SIZES)
        s = StratifiedSampler(store, num_groups=4, seed=3)
        counts = s.stratum_counts(360)
        # a uniform prefix would hold ~324:22:11:4 — stride scheduling
        # surfaces every key at the same rate instead
        np.testing.assert_array_equal(counts, [90, 90, 90, 90])
        np.testing.assert_array_equal(s.stratum_sizes, self.SIZES)

    def test_custom_shares_hit_proportions(self):
        store = _keyed_store(self.SIZES)
        s = StratifiedSampler(store, num_groups=4, seed=3,
                              shares=[1.0, 1.0, 2.0, 4.0])
        counts = s.stratum_counts(160)
        np.testing.assert_array_equal(counts, [20, 20, 40, 80])

    def test_exhausted_stratum_lets_others_fill(self):
        store = _keyed_store(self.SIZES)
        s = StratifiedSampler(store, num_groups=4, seed=3)
        counts = s.stratum_counts(2000)
        assert counts[3] == 100              # rare key fully drained
        assert counts.sum() == 2000          # prefix length unchanged
        assert s.stratum_counts(store.N).sum() == store.N

    def test_prefixes_nested_and_without_replacement(self):
        store = _keyed_store(self.SIZES)
        s = StratifiedSampler(store, num_groups=4, seed=5)
        a = np.asarray(s.take(0, 100))
        b = np.asarray(s.take(0, 800))
        np.testing.assert_array_equal(a, b[:100])
        assert len(np.unique(s.perm[:800])) == 800

    def test_within_key_order_matches_base_permutation(self):
        """Each stratum's slice of any prefix must be that stratum's rows
        in BASE permutation order — so per-key prefixes stay uniform
        without-replacement samples of that key."""
        store = _keyed_store(self.SIZES, seed=11)
        base = StratifiedSampler(store, num_groups=4, seed=7)
        ref = np.asarray(store.read_all())[:, 1].astype(np.int64)
        plain_perm = PreMapSampler(store, seed=7).perm
        for g in range(4):
            np.testing.assert_array_equal(
                base.perm[ref[base.perm] == g],
                plain_perm[ref[plain_perm] == g])

    def test_within_key_uniformity(self):
        # clustered values inside one key must come out uniform
        n, nvals = 20_000, 20
        vals = np.sort(np.repeat(np.arange(nvals), n // nvals))
        data = np.stack([vals, np.zeros(n)], axis=1).astype(np.float32)
        data = np.concatenate(
            [data, np.stack([np.zeros(n // 4), np.ones(n // 4)],
                            axis=1).astype(np.float32)])
        store = ShardedStore.from_array(data, 1024)
        s = StratifiedSampler(store, num_groups=2, seed=0)
        sample = np.asarray(s.take(0, 4000))
        key0 = sample[sample[:, 1] == 0.0, 0]
        counts = np.bincount(key0.astype(int), minlength=nvals)
        chi2, p = sps.chisquare(counts)
        assert p > 0.001, f"stratum sample not uniform: chi2={chi2}, p={p}"

    def test_validation_errors(self):
        store = _keyed_store([50, 50])
        with pytest.raises(ValueError, match="keyed rows"):
            StratifiedSampler(ShardedStore.from_array(
                np.zeros((64, 1), np.float32), 32), num_groups=2)
        with pytest.raises(ValueError, match=r"\[0, 1\)"):
            StratifiedSampler(store, num_groups=1)
        bad = ShardedStore.from_array(
            np.stack([np.zeros(64), np.full(64, 0.5)], axis=1).astype(
                np.float32), 32)
        with pytest.raises(ValueError, match="integers"):
            StratifiedSampler(bad, num_groups=2)
        with pytest.raises(ValueError, match="positive"):
            StratifiedSampler(store, num_groups=2, shares=[1.0, -1.0])
        with pytest.raises(ValueError, match="one per group"):
            StratifiedSampler(store, num_groups=2, shares=[1.0])


class TestStore:
    def test_locate_roundtrip(self):
        store = ShardedStore.from_array(
            np.arange(5000, dtype=np.float32)[:, None], 512,
            interleave=False)
        rows = np.array([0, 511, 512, 4999])
        split, local = store.locate(rows)
        for r, s, l in zip(rows, split, local):
            assert store.splits[s][l, 0] == float(r)
