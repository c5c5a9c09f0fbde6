"""Samplers over the sharded store (paper §3.3).

* ``PreMapSampler``  — samples row *indices* first and reads only those
  rows (the paper's pre-map sampling: sample line offsets inside splits,
  backtrack to line start, never load the rest).  Low load cost; the
  ⟨k,v⟩-count estimate is the sampled fraction (correct() uses p=n/N).

* ``PostMapSampler`` — reads the full store once, hash-buckets rows, then
  draws the sample (paper's post-map: exact key accounting, full load
  cost).

* ``PermutationSampler`` — the EarlSession-facing wrapper: a fixed pseudo-
  random permutation of [0, N); ``take(a, b)`` returns permutation rows
  [a, b), so growing samples are prefix-extends (uniform without
  replacement — DESIGN.md §7.2) and delta maintenance gets pure Δs rows.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
import numpy as np

from repro import trace
from repro.data.store import ShardedStore


class PermutationSampler:
    """Uniform without-replacement prefixes via a fixed permutation.

    ``mode="pre_map"`` reads row-granular (cheap); ``mode="post_map"``
    materializes the full store on first touch (exact counts, expensive) —
    both expose identical take() semantics so EarlSession is agnostic.
    """

    def __init__(self, store: ShardedStore, seed: int = 0,
                 mode: str = "pre_map"):
        if mode not in ("pre_map", "post_map"):
            raise ValueError(mode)
        self.store = store
        self.mode = mode
        self.N = store.N
        rng = np.random.default_rng(seed)
        with trace.span("sampler.permute"):
            self.perm = rng.permutation(self.N)
        self._cache: Optional[np.ndarray] = None

    def take(self, start: int, stop: int) -> jnp.ndarray:
        with trace.span("sampler.take"):
            stop = min(stop, self.N)
            rows = self.perm[start:stop]
            if self.mode == "post_map":
                if self._cache is None:
                    self._cache = self.store.read_all()
                return jnp.asarray(self._cache[rows])
            # pre-map: group the requested rows by split, read row-granular
            split, local = self.store.locate(rows)
            order = np.argsort(split, kind="stable")
            out = np.empty((len(rows),) + self.store.splits[0].shape[1:],
                           dtype=self.store.splits[0].dtype)
            i = 0
            while i < len(order):
                j = i
                s = split[order[i]]
                while j < len(order) and split[order[j]] == s:
                    j += 1
                sel = order[i:j]
                out[sel] = self.store.read_rows(int(s), local[sel])
                i = j
            return jnp.asarray(out)


class StratifiedSampler(PermutationSampler):
    """Skew-aware prefix sampler for keyed (GROUP BY) sessions.

    A uniform prefix of a skewed table starves rare keys: with key g at
    frequency f_g, an n-row prefix holds ~f_g·n of its rows, so the
    worst key's c_v — what a keyed ``EarlSession`` gates on via
    ``KeyedAccuracyReport`` — is stuck at the rarest key's trickle and
    the session grows the sample far past what the heavy hitters need.
    This sampler reorders the base permutation by stride scheduling:
    within each stratum rows keep the base permutation's order (so each
    stratum's portion of any prefix is a uniform without-replacement
    sample of that key), and across strata row i of stratum g is
    scheduled at virtual time (i+1)/share_g, the global order being the
    stable ascending sort of those times.  Every prefix then holds the
    strata in ~share proportions — ``shares=None`` gives EQUAL shares,
    surfacing rare keys at the same rate as heavy hitters — and a
    stratum's budget being exhausted simply lets the others fill in.

    The stratum is the integer KEY COLUMN (default: last), matching
    ``GroupedStatistic``'s key-is-last-column convention.  Reading the
    keys is one column scan over the store at construction — the exact
    key accounting of the paper's post-map sampling, paid once.

    Caveat (also in ROADMAP "Known modeling limits"): prefixes are
    uniform WITHIN each key but deliberately non-uniform across keys, so
    whole-table ``correct(p)`` fractions no longer describe any single
    key — keyed sessions should correct per key with that key's own
    sampled fraction (``stratum_counts`` / ``stratum_sizes`` expose the
    numbers).
    """

    def __init__(self, store: ShardedStore, num_groups: int, seed: int = 0,
                 shares=None, key_column: int = -1, mode: str = "pre_map"):
        super().__init__(store, seed=seed, mode=mode)
        self.num_groups = int(num_groups)
        cols = []
        for s in store.splits:
            a = np.asarray(s)
            if a.ndim < 2 or a.shape[1] < 2:
                raise ValueError("StratifiedSampler needs keyed rows: data "
                                 "columns plus an integer key column")
            cols.append(a[:, key_column])
        keys = np.concatenate(cols)
        if np.any(keys != np.floor(keys)):
            raise ValueError("key column must hold integers")
        keys = keys.astype(np.int64)
        if keys.min() < 0 or keys.max() >= self.num_groups:
            raise ValueError(f"keys must lie in [0, {self.num_groups}); got "
                             f"range [{keys.min()}, {keys.max()}]")
        if shares is None:
            shares = np.ones(self.num_groups)
        shares = np.asarray(shares, np.float64)
        if shares.shape != (self.num_groups,) or not np.all(shares > 0):
            raise ValueError("shares must be positive, one per group")
        self.shares = shares / shares.sum()
        #: rows of key g in the whole store — the per-key N for correct(p).
        self.stratum_sizes = np.bincount(keys, minlength=self.num_groups)

        # stride-schedule the base permutation (see class docstring)
        kperm = keys[self.perm]
        order = np.argsort(kperm, kind="stable")
        sorted_k = kperm[order]
        starts = np.searchsorted(sorted_k, np.arange(self.num_groups))
        ranks = np.empty(self.N, np.int64)
        ranks[order] = np.arange(self.N) - starts[sorted_k]
        vtime = (ranks + 1) / self.shares[kperm]
        self.perm = self.perm[np.argsort(vtime, kind="stable")]
        self._kperm = keys[self.perm]

    def stratum_counts(self, stop: int) -> np.ndarray:
        """Rows of each key inside the prefix [0, stop) — with
        ``stratum_sizes`` this gives the per-key sampled fraction a keyed
        ``correct`` should use."""
        stop = min(int(stop), self.N)
        return np.bincount(self._kperm[:stop], minlength=self.num_groups)


class PreMapSampler(PermutationSampler):
    def __init__(self, store: ShardedStore, seed: int = 0):
        super().__init__(store, seed=seed, mode="pre_map")


class PostMapSampler(PermutationSampler):
    """Paper's post-map: read-then-select with hash bucketing.

    The hash layer reproduces Algorithm 1: every row is assigned a random
    key bucket on load; draws pop buckets without replacement.  Counting
    is exact: ``kv_count`` is known after load (pre-map only estimates it).
    """

    def __init__(self, store: ShardedStore, seed: int = 0,
                 num_buckets: int = 1024):
        super().__init__(store, seed=seed, mode="post_map")
        self.num_buckets = num_buckets
        self._loaded = False
        self.kv_count: Optional[int] = None

    def _load(self) -> None:
        self._cache = self.store.read_all()
        self.kv_count = len(self._cache)
        rng = np.random.default_rng(0xB0B)
        self.bucket_of = rng.integers(0, self.num_buckets,
                                      size=self.kv_count)
        self._loaded = True

    def take(self, start: int, stop: int) -> jnp.ndarray:
        if not self._loaded:
            self._load()
        return super().take(start, stop)
