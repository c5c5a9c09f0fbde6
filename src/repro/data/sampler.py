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
  The permutation is drawn lazily, only as far as ``take`` reaches: a
  take that stops at or below the cut-off K(N) = ⌈N/8⌉ costs the new
  positions it draws, and the whole order is materialised only when a
  caller reaches past K or reads ``perm``.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
import numpy as np

from repro import trace
from repro.data.store import ShardedStore


#: candidates drawn at a time by the lazy prefix.  A constant, so the
#: candidate stream, and with it every prefix, is a function of the seed
#: alone, however the takes are split.
_BLOCK = 1 << 12


def lazy_cutoff(N: int) -> int:
    """K(N) = ⌈N/8⌉: how far the permutation's prefix is drawn lazily.
    Up to K fewer than 1/8 of the candidates are duplicates."""
    return -(-N // 8)


class PermutationSampler:
    """Uniform without-replacement prefixes via a fixed permutation.

    ``mode="pre_map"`` reads row-granular (cheap); ``mode="post_map"``
    materializes the full store on first touch (exact counts, expensive) —
    both expose identical take() semantics so EarlSession is agnostic.

    The permutation is a uniform random permutation of [0, N), drawn in two
    parts from independent children of ``seed``:

    * the prefix up to K = ``lazy_cutoff(N)``: the first occurrences, in
      stream order, of a stream of candidates drawn uniformly from [0, N)
      with replacement, in blocks of ``_BLOCK``.  A bitmap of N bits tests
      each candidate against the rows already drawn.  Any prefix is then a
      uniform sample without replacement in uniform random order, and it is
      drawn only as far as a take asks: O(new rows) a take.
    * the rest: the complement of the K-prefix in a uniform order of its
      own, drawn once, when a take reaches past K or ``perm`` is read.

    ``take(a, b)`` is a function of (seed, N, a, b) alone, whatever takes
    came before it.  Counters (``repro.trace``): ``sampler_rows_drawn``,
    the prefix positions handed out by the lazy draw; ``sampler_redraws``,
    the candidates it drew and rejected as duplicates;
    ``sampler_full_permute``, each materialisation of the whole order.
    """

    def __init__(self, store: ShardedStore, seed: int = 0,
                 mode: str = "pre_map"):
        if mode not in ("pre_map", "post_map"):
            raise ValueError(mode)
        self.store = store
        self.mode = mode
        self.N = store.N
        with trace.span("sampler.permute"):
            prefix_seq, self._rest_seq = np.random.SeedSequence(
                seed).spawn(2)
            self._rng = np.random.default_rng(prefix_seq)
            self._K = lazy_cutoff(self.N)
            self._seen = np.zeros((self.N + 7) // 8, np.uint8)
            self._buf = np.empty(0, np.int64)   # prefix drawn so far
            self._n_drawn = 0       # prefix positions of all blocks drawn
            self._hi = 0            # prefix positions handed out
            self._order: Optional[np.ndarray] = None
        self._cache: Optional[np.ndarray] = None

    def _draw_block(self) -> None:
        c = self._rng.integers(0, self.N, size=_BLOCK)
        _, first = np.unique(c, return_index=True)
        new = np.zeros(_BLOCK, bool)
        new[first] = True                       # first in the block
        byte = c >> 3
        bit = np.left_shift(1, c & 7).astype(np.uint8)
        new &= (self._seen[byte] & bit) == 0    # not in an earlier block
        pos = np.flatnonzero(new)
        np.bitwise_or.at(self._seen, byte[pos], bit[pos])
        trace.count("sampler_redraws", _BLOCK - len(pos))
        end = self._n_drawn + len(pos)
        if end > len(self._buf):
            grown = np.empty(max(end, 2 * len(self._buf)), np.int64)
            grown[:self._n_drawn] = self._buf[:self._n_drawn]
            self._buf = grown
        self._buf[self._n_drawn:end] = c[pos]
        self._n_drawn = end

    def _draw(self, stop: int) -> None:
        """Hand out the prefix up to ``stop`` ≤ K positions."""
        if stop <= self._hi:
            return
        with trace.span("sampler.draw"):
            while self._n_drawn < stop:
                self._draw_block()
            trace.count("sampler_rows_drawn", stop - self._hi)
            self._hi = stop

    @property
    def perm(self) -> np.ndarray:
        """The whole order, materialised on first use (O(N)); its first k
        entries are ``take(0, k)``'s rows."""
        if self._order is None:
            self._draw(self._K)
            trace.count("sampler_full_permute")
            order = np.empty(self.N, np.int64)
            order[:self._K] = self._buf[:self._K]
            outside = np.ones(self.N, bool)
            outside[order[:self._K]] = False
            rest = order[self._K:]
            rest[:] = np.flatnonzero(outside)
            np.random.default_rng(self._rest_seq).shuffle(rest)
            self._order = order
            self._seen = self._buf = None
        return self._order

    def _rows(self, start: int, stop: int) -> np.ndarray:
        if self._order is None and stop <= self._K:
            self._draw(stop)
            return self._buf[start:stop]
        return self.perm[start:stop]

    def take(self, start: int, stop: int) -> jnp.ndarray:
        with trace.span("sampler.take"):
            stop = min(stop, self.N)
            rows = self._rows(start, stop)
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
        base = self.perm
        kperm = keys[base]
        order = np.argsort(kperm, kind="stable")
        sorted_k = kperm[order]
        starts = np.searchsorted(sorted_k, np.arange(self.num_groups))
        ranks = np.empty(self.N, np.int64)
        ranks[order] = np.arange(self.N) - starts[sorted_k]
        vtime = (ranks + 1) / self.shares[kperm]
        self._order = base[np.argsort(vtime, kind="stable")]
        self._kperm = keys[self._order]

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
