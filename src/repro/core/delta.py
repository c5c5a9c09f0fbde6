"""Delta-maintained resampling (paper §4).

Inter-iteration (§4.1): when the sample grows s -> s' = s ∪ Δs, reuse the
B resamples instead of redrawing them.

* ``PoissonDelta``           — beyond-paper exact path (DESIGN.md §7.1):
  under Poisson(1) weights, old items' weights are independent of n, so
  extension = draw weights for Δs only and ``merge`` the per-resample
  states.  O(B·Δn), exact, jittable, shard-independent.

* ``MultinomialDeltaBootstrap`` — paper-faithful baseline: maintains item-
  level resamples; on extension the old-part size is drawn from
  Binomial(n', n/n') (Gaussian-approximated per Eq. 3 when n is large),
  items are deleted/added through the §4.1 two-layer *sketch* (memory
  layer of c·sqrt(n) random items over a "disk" layer), and we count the
  simulated disk accesses the sketch saves.  Host/NumPy on purpose — it is
  the baseline benchmarks/fig10 compares against.

Intra-iteration (§4.2): resamples share identical fractions; a shared-base
resample's partial state is computed once and merged into every resample
(Eq. 4 gives the work saved).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import trace
from repro.core import accuracy, fold
from repro.core.bootstrap import (BootstrapResult, mesh_fold,
                                  poisson_weights, seed_from_key,
                                  sharded_fused_states)
from repro.core.reduce_api import (Statistic, StatisticGroup, _as_2d,
                                   bind_params, split_params)


# ============================================================================
# Poisson delta maintenance (exact, jittable)
# ============================================================================
@dataclasses.dataclass
class PoissonDelta:
    stat: Statistic
    key: jax.Array
    states: Any          # pytree with leading B axis
    est_state: Any       # unweighted state over the whole sample
    B: int
    n: int
    step: int            # key-folding counter (one per extend)
    backend: Optional[str] = None   # None = jnp weights, "fused_rng" =
    #                                 matrix-free in-kernel RNG (O(B·d) peak)
    mesh: Any = None                # fused backend only: shard each Δs over
    data_axis: str = "data"         # this mesh axis and psum the states


def poisson_delta_init(stat: Statistic, B: int, dim: int, key: jax.Array,
                       backend: Optional[str] = None, mesh=None,
                       data_axis: str = "data") -> PoissonDelta:
    if backend not in (None, "fused_rng"):
        raise ValueError(f"unknown delta backend: {backend!r}")
    if mesh is not None and backend != "fused_rng":
        raise ValueError("mesh= requires backend='fused_rng' (sharded delta "
                         "maintenance psums fused states)")
    spec, params = split_params(stat)
    states, est_state = _pd_init_jit(params, spec, int(B), int(dim))
    return PoissonDelta(stat=stat, key=key, states=states,
                        est_state=est_state, B=B, n=0, step=0,
                        backend=backend, mesh=mesh, data_axis=data_axis)


@partial(jax.jit, static_argnames=("stat", "B", "dim"))
def _pd_init_jit(params, stat, B, dim):
    """B empty resample states and the estimate's, as one cached program
    (built eagerly, each of their arrays would be a dispatch of its own)."""
    return fold.empty_carry(bind_params(stat, params), B, dim)


@partial(jax.jit, static_argnames=("stat", "B", "backend", "mesh",
                                   "data_axis"))
def _pd_extend_jit(states, est_state, key, step, x, params, stat, B,
                   backend, mesh=None, data_axis="data"):
    stat = bind_params(stat, params)   # traced array params (e.g. centroids)
    if backend == "fused_rng":
        # matrix-free: the Δs weight matrix never materializes; delta
        # states from in-kernel-RNG weights merge into the running states.
        # Streams are offset_seed(seed_from_key(key), step) — distinct per
        # extend by construction (see seed_from_key), safe at the int32
        # boundary.  With a mesh, each shard of Δs draws its own stream
        # (keyed (base, shard, step)) and the delta states psum before the
        # merge — extension traffic is O(B·d states), never O(B·Δn).
        if mesh is not None:
            delta_states = sharded_fused_states(
                stat, seed_from_key(key), x, B, mesh=mesh,
                data_axis=data_axis, step=step)
            new_states = jax.vmap(stat.merge)(states, delta_states)
        else:
            new_states, _ = fold.fold_rows(stat, states, None, x,
                                           seed_from_key(key), step, B)
    else:
        w = poisson_weights(jax.random.fold_in(key, step), B, x.shape[0])
        new_states = jax.vmap(lambda s, wr: stat.update(s, x, wr))(states, w)
    # every backend's estimate, after the resample fold: handed to
    # fold_rows, its update would come first in the program
    new_est = stat.update(est_state, x)
    return new_states, new_est


def poisson_delta_extend(pd: PoissonDelta, new_values: jax.Array
                         ) -> PoissonDelta:
    """Exact inter-iteration maintenance: weights drawn for Δs only; the
    point estimate's state is maintained incrementally too (O(Δn))."""
    x = _as_2d(new_values)
    dn = x.shape[0]
    spec, params = split_params(pd.stat)
    with mesh_fold(pd.mesh, pd.data_axis, dn) as psummed:
        states, est_state = _pd_extend_jit(pd.states, pd.est_state, pd.key,
                                           pd.step, x, params, spec, pd.B,
                                           pd.backend, pd.mesh, pd.data_axis)
        psummed(states)
    return dataclasses.replace(pd, states=states, est_state=est_state,
                               n=pd.n + dn, step=pd.step + 1)


@partial(jax.jit, static_argnames=("stat",))
def _pd_result_jit(states, params, p, stat):
    """The session's result step as one program, cached per statistic
    spec and the states' shapes (B among them); ``p`` is traced, so every
    round reuses it.  Finalizes and corrects the resample states and
    computes the report's numbers on the device."""
    trace.count(trace.RESULT_TRACES)           # runs only while JAX traces
    stat = bind_params(stat, params)
    thetas = stat.correct(jax.vmap(stat.finalize)(states), p)
    numbers = accuracy.report_numbers(
        thetas, num_groups=getattr(stat, "num_groups", None))
    return thetas, numbers, accuracy.report_scalars(numbers)


def _one_device(tree):
    """Each leaf replicated over several devices (a mesh fold's psummed
    states) as its copy on one of them, without a transfer: what reads the
    states after the fold then runs as a one-device program, not as one
    program a chip."""
    def one(a):
        if len(a.sharding.device_set) > 1 and a.sharding.is_fully_replicated:
            return a.addressable_data(0)
        return a
    return jax.tree_util.tree_map(one, tree)


def _result_step(pd: PoissonDelta, p: float):
    """Corrected thetas and their report from one compiled step, the
    report's scalar gates read on the host in one ``host_sync``."""
    spec, params = split_params(pd.stat)
    states = pd.states if pd.mesh is None else _one_device(pd.states)
    trace.count(trace.RESULT_STEPS)
    thetas, numbers, scalars = _pd_result_jit(states, params,
                                              np.float32(p), spec)
    return thetas, accuracy.report_from_numbers(
        numbers, trace.to_host(scalars),
        getattr(pd.stat, "num_groups", None))


def poisson_delta_report(pd: PoissonDelta, p: float = 1.0):
    """The accuracy report of a delta run's resamples alone (SSABE's
    n-steps read its c_v and need no estimate)."""
    return _result_step(pd, p)[1]


def poisson_delta_result(pd: PoissonDelta, estimate: Any = None,
                         p: float = 1.0,
                         p_keys: Optional[np.ndarray] = None
                         ) -> BootstrapResult:
    """Finalize a delta run into a ``BootstrapResult``.

    ``p`` is the whole-table sampled fraction for ``correct``.  The thetas
    and the report come from one compiled step (``_result_step``); the
    estimate — the maintained one, or ``estimate`` if given — is finalized
    and corrected eagerly, so that the answer is what ``stat.finalize``
    gives as the code stands, never a program built before.  For a keyed
    statistic under STRATIFIED sampling, pass ``p_keys`` (per-key sampled
    fractions, length ``num_groups``) instead: each key's thetas and
    estimate are corrected eagerly by that key's own inclusion probability
    (``GroupedStatistic.correct_per_key``), and the fractions are surfaced
    on the resulting ``KeyedAccuracyReport.p_keys``."""
    num_groups = getattr(pd.stat, "num_groups", None)
    if p_keys is not None and num_groups is None:
        raise ValueError("p_keys needs a keyed statistic "
                         "(GroupedStatistic)")
    if estimate is None:
        estimate = pd.stat.finalize(pd.est_state if pd.mesh is None
                                    else _one_device(pd.est_state))
    if p_keys is not None:
        thetas = pd.stat.correct_per_key(
            jax.vmap(pd.stat.finalize)(pd.states), p_keys, key_axis=1)
        estimate = pd.stat.correct_per_key(estimate, p_keys, key_axis=0)
        report = accuracy.report_for(thetas, num_groups=num_groups,
                                     p_keys=p_keys)
    else:
        thetas, report = _result_step(pd, p)
        estimate = pd.stat.correct(estimate, p)
    return BootstrapResult(estimate=estimate, thetas=thetas, report=report,
                           B=pd.B, n=pd.n)


# ============================================================================
# Paper-faithful multinomial delta maintenance with sketches (§4.1)
# ============================================================================
class Sketch:
    """Two-layer memory/disk structure of §4.1.

    ``data`` lives on "disk"; ``c·sqrt(len(data))`` random items live in the
    memory layer.  Sequentially consuming memory items avoids disk access;
    exhausting the sketch triggers a (counted) disk refill.
    """

    def __init__(self, data: np.ndarray, c: float, rng: np.random.Generator):
        self.data = data
        self.c = c
        self.rng = rng
        self.disk_accesses = 0
        self._refill()

    def _refill(self) -> None:
        self.disk_accesses += 1           # one bulk disk read (commit+resample)
        k = min(len(self.data), max(1, int(self.c * math.sqrt(len(self.data)))))
        idx = self.rng.choice(len(self.data), size=k, replace=False)
        self.mem = self.data[idx]
        self.pos = 0

    def take(self, k: int) -> np.ndarray:
        out = []
        while k > 0:
            avail = len(self.mem) - self.pos
            if avail == 0:
                self._refill()
                avail = len(self.mem)
            t = min(k, avail)
            out.append(self.mem[self.pos:self.pos + t])
            self.pos += t
            k -= t
        return np.concatenate(out) if out else self.data[:0]


class MultinomialDeltaBootstrap:
    """Item-level faithful implementation of §4.1 (the fig10 baseline).

    Resamples are index arrays into the growing sample.  ``use_sketch``
    toggles the memory-layer optimization; ``use_gaussian`` toggles the
    Eq. 3 Gaussian approximation of the Eq. 2 binomial.
    """

    def __init__(self, stat: Statistic, B: int, seed: int = 0,
                 c: float = 4.0, use_sketch: bool = True,
                 use_gaussian: bool = True):
        if isinstance(stat, StatisticGroup):
            raise TypeError(
                "MultinomialDeltaBootstrap is the host/NumPy fig10 baseline"
                " and stacks scalar thetas — run StatisticGroup through the"
                " Poisson delta path (poisson_delta_init) instead")
        if getattr(stat, "num_groups", None) is not None:
            raise TypeError(
                "MultinomialDeltaBootstrap does not produce per-key reports"
                " — run GroupedStatistic through the Poisson delta path"
                " (poisson_delta_init) instead")
        self.stat = stat
        self.B = B
        self.rng = np.random.default_rng(seed)
        self.c = c
        self.use_sketch = use_sketch
        self.use_gaussian = use_gaussian
        self.sample = None                 # np.ndarray (n, d)
        self.resamples = None              # list of np index arrays
        self.disk_accesses = 0
        self.items_moved = 0               # total delete+add work performed

    @property
    def n(self) -> int:
        return 0 if self.sample is None else len(self.sample)

    def _old_part_size(self, n: int, n_new: int) -> int:
        """|b'_{i,s}| ~ Binomial(n', n/n')  (Eq. 2), Gaussian approx (Eq. 3)."""
        p = n / n_new
        if self.use_gaussian and n_new >= 64:
            k = int(round(self.rng.normal(n, math.sqrt(n * (1.0 - p)))))
        else:
            k = int(self.rng.binomial(n_new, p))
        return int(np.clip(k, 0, n_new))

    def extend(self, delta: np.ndarray) -> None:
        delta = np.asarray(delta)
        if delta.ndim == 1:
            delta = delta[:, None]
        if self.sample is None:
            # first iteration: Δs_1 against the empty set (paper §4.1)
            self.sample = delta
            n = len(delta)
            self.resamples = [self.rng.integers(0, n, size=n)
                              for _ in range(self.B)]
            return

        n = self.n
        n_new = n + len(delta)
        base = len(self.sample)
        self.sample = np.concatenate([self.sample, delta], axis=0)

        s_sketch = (Sketch(np.arange(n), self.c, self.rng)
                    if self.use_sketch else None)
        d_sketch = (Sketch(np.arange(base, n_new), self.c, self.rng)
                    if self.use_sketch else None)

        new_resamples = []
        for b in self.resamples:
            k = self._old_part_size(n, n_new)
            if k < n:                                   # random deletions
                keep = self.rng.permutation(n)[:k]
                b = b[keep]
                self.items_moved += n - k
            elif k > n:                                 # additions from s
                if s_sketch is not None:
                    add = s_sketch.take(k - n)
                else:
                    self.disk_accesses += k - n         # item-wise disk reads
                    add = self.rng.integers(0, n, size=k - n)
                b = np.concatenate([b, add])
                self.items_moved += k - n
            # additions from Δs
            m = n_new - k
            if d_sketch is not None:
                add_d = d_sketch.take(m)
            else:
                self.disk_accesses += m
                add_d = self.rng.integers(base, n_new, size=m)
            self.items_moved += m
            new_resamples.append(np.concatenate([b, add_d]))
        if s_sketch is not None:
            self.disk_accesses += s_sketch.disk_accesses
            self.disk_accesses += d_sketch.disk_accesses
        self.resamples = new_resamples

    def thetas(self) -> jnp.ndarray:
        outs = []
        for b in self.resamples:
            vals = jnp.asarray(self.sample[b])
            outs.append(self.stat(vals))
        return jnp.stack([jnp.asarray(o) for o in outs])

    def result(self, p: float = 1.0) -> BootstrapResult:
        thetas = self.stat.correct(self.thetas(), p)
        est = self.stat.correct(self.stat(jnp.asarray(self.sample)), p)
        return BootstrapResult(
            estimate=est, thetas=thetas,
            report=accuracy.report_for(thetas),
            B=self.B, n=self.n,
        )


# ============================================================================
# Intra-iteration optimization (§4.2)
# ============================================================================
def p_shared(n: int, y: float) -> float:
    """Eq. 4: P(X=y) = n! / ((n - y·n)! · n^{y·n}), in log space."""
    k = int(round(y * n))
    if k <= 0:
        return 1.0
    if k > n:
        return 0.0
    logp = (math.lgamma(n + 1) - math.lgamma(n - k + 1) - k * math.log(n))
    return min(1.0, math.exp(logp))


def work_saved(n: int, y: float) -> float:
    """Expected fraction of resample work saved: P(X=y)·y (paper §4.2)."""
    return p_shared(n, y) * y


def optimal_y(n: int, grid: int = 200) -> Tuple[float, float]:
    """argmax_y work_saved(n, y) by scan (paper: simple binary search)."""
    best_y, best_w = 0.0, 0.0
    for i in range(1, grid + 1):
        y = i / grid
        w = work_saved(n, y)
        if w > best_w:
            best_y, best_w = y, w
    return best_y, best_w


def shared_base_bootstrap(values: jax.Array, stat: Statistic, B: int,
                          key: jax.Array, y: Optional[float] = None,
                          p: float = 1.0) -> BootstrapResult:
    """Intra-iteration optimized bootstrap: a shared y·n sub-resample's state
    is computed once and merged into every resample's remainder state.

    Work: n·y (once) + B·n·(1−y)  vs  B·n  for the standard bootstrap.
    """
    x = _as_2d(values)
    n, dim = x.shape
    if y is None:
        y, _ = optimal_y(n)
    k = int(round(y * n))
    k_base, k_rest = k, n - k

    kb, kr = jax.random.split(key)
    base_idx = jax.random.randint(kb, (k_base,), 0, n)
    shared_state = stat.update(stat.init_state(dim), x[base_idx])

    rest_idx = jax.random.randint(kr, (B, max(k_rest, 1)), 0, n)

    def one(idx_row):
        st = stat.update(stat.init_state(dim), x[idx_row])
        return stat.finalize(stat.merge(shared_state, st)) if k_rest > 0 \
            else stat.finalize(shared_state)

    thetas = jax.vmap(one)(rest_idx)
    thetas = stat.correct(thetas, p)
    est = stat.correct(stat(values), p)
    return BootstrapResult(
        estimate=est, thetas=thetas,
        report=accuracy.report_for(thetas),
        B=B, n=n,
    )
