"""Bootstrap engines (paper §3, DESIGN.md §2).

A resample-with-replacement is represented by a *weight vector* over the
sample, so ``f(resample)`` is a weighted statistic and the B-resample loop
vectorizes over a dense (B, n) weight matrix — MXU work instead of gathers.

Two engines:

* ``multinomial`` — paper-faithful: the B rows are exact multinomial
  counts Multinomial(n; 1/n,...,1/n), i.e. classic Efron bootstrap.
* ``poisson``     — distributed default (beyond-paper, DESIGN.md §7.1):
  iid Poisson(1) weights per (item, resample).  Same first two moments,
  shard-independent, and makes inter-iteration delta maintenance exact.

Both route moment statistics through kernels/weighted_stats when asked.

Backends (``backend=`` on ``bootstrap``/``bootstrap_chunked``):

* ``None``        — materialized weights (jnp oracle); ``use_kernel`` may
  additionally route the contraction through the weighted_stats kernel.
* ``"fused_rng"`` — matrix-free (poisson engine only): weights are
  generated inside the contraction from a counter-based PRNG, so the (B, n)
  weight matrix never exists.  Statistics opt in via
  ``Statistic.fused_poisson_states``: moment statistics (Mean/Sum/Count/
  Var/Std) route through kernels/weighted_stats.fused_poisson_moments
  (peak O(B·d)), ``KMeansStep`` through
  kernels/kmeans_assign.fused_poisson_kmeans (peak O(B·k·d), and no (n, k)
  distance/one-hot intermediate either), ``Quantile``/``Median`` through
  kernels/weighted_hist.fused_poisson_hist (peak O(B·d·nbins), no
  (n, d, nbins) one-hot either) — every built-in statistic is fused;
  custom statistics without a fused path fall back to materializing the
  same implicit weights per chunk.  The PRNG seed derives deterministically
  from ``key``, so the fold-in discipline (delta maintenance, common random
  numbers) carries over unchanged.  A ``StatisticGroup`` routes through
  kernels/fused_multi: ONE shared weight stream and one pass over x feeds
  every member's accumulator, so a k-statistic session costs ~1× (not k×)
  the RNG and memory traffic — and all members see the same resamples.

Multi-device (``mesh=`` + ``data_axis=`` on the fused backend): the n axis
is sharded over the mesh's data axis with shard_map; each shard runs the
in-kernel weight generation on its local rows with a stream keyed by
``(base_seed, shard_index, chunk)`` via ``offset_seed``, and only the small
per-resample states (``Statistic.psum_state``: (B, d) moments /
(B, k, d) k-means / (B, d, nbins) histograms) cross devices — no weight or
sample traffic ever does.  The paper's Hadoop mapping survives intact:
mapper = shard-local fused update, combiner = ``merge``, reducer = psum of
mergeable states.  ``sharded_fused_states(..., mesh=None, nshards=s)`` runs
the identical decomposition sequentially on one device and is bitwise equal
to the mesh run (XLA's all-reduce and the sequential left-fold merge
reduce in the same shard order) — the single-device path stays the oracle.
"""
from __future__ import annotations

import contextlib
import dataclasses
import inspect
from functools import lru_cache, partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro import trace
from repro.core import accuracy
from repro.core import fold          # which imports this module in turn
from repro.core.reduce_api import (Statistic, _as_2d, bind_params,
                                   split_params)


@dataclasses.dataclass
class BootstrapResult:
    estimate: jax.Array        # f on the full sample (unweighted), corrected
    thetas: jax.Array          # (B, ...) bootstrap result distribution
    report: accuracy.AccuracyReport
    B: int
    n: int

    @property
    def cv(self) -> float:
        return self.report.cv


# ----------------------------------------------------------------------------
# weight generation
# ----------------------------------------------------------------------------
def seed_from_key(key: jax.Array) -> jax.Array:
    """Deterministic int32 seed for the counter-based in-kernel PRNG.

    Multi-stream callers (chunked bootstrap, delta maintenance) derive ONE
    base seed per run and offset it by the chunk/step counter via
    ``offset_seed`` — streams within a run are distinct *by construction*
    (no 31-bit birthday bound), while different keys still give independent
    runs."""
    return jax.random.randint(key, (), 0, jnp.iinfo(jnp.int32).max,
                              dtype=jnp.int32)


_SEED_MOD = int(jnp.iinfo(jnp.int32).max)      # 2^31 - 1


def offset_seed(base_seed, i):
    """The i-th derived stream seed: (base + i) mod (2^31 − 1), computed
    without int32 overflow.

    ``base_seed`` comes from ``seed_from_key`` (∈ [0, 2^31−1)); a plain
    ``base + i`` wraps past ``iinfo(int32).max`` for large chunk/step
    counters (or a base drawn near the boundary), silently flipping the
    seed negative.  Both branches stay inside [0, 2^31−1)."""
    base = jnp.asarray(base_seed, jnp.int32)
    off = jnp.asarray(i, jnp.int32) % _SEED_MOD
    room = _SEED_MOD - off
    return jnp.where(base >= room, base - room, base + off)


def fused_resample_states(stat: Statistic, seed, x2: jax.Array, B: int,
                          n_valid=None, valid_mask=None):
    """B-leading pytree of per-resample states for ``x2`` under implicit
    in-kernel Poisson(1) weights (the matrix-free hot path).

    Statistics with a fused path (``Statistic.fused_poisson_states``:
    moment statistics and KMeansStep) never see a (B, n) matrix; other
    statistics fall back to materializing the same implicit weights.  The
    result is a *delta* state: ``merge`` it into running states
    (delta/chunked) or ``finalize`` it directly (one-shot bootstrap).

    ``valid_mask`` (traced (n,) f32 of exact 0.0/1.0) multiplies the
    implicit weights — arbitrary interior validity holes.  Custom
    statistics whose ``fused_poisson_states`` predates the kwarg are
    detected by signature and routed to the materialized fallback (same
    implicit weights, mask applied to the matrix) rather than crashing.
    """
    if valid_mask is None:
        states = stat.fused_poisson_states(seed, x2, B, n_valid=n_valid)
    elif "valid_mask" in inspect.signature(
            stat.fused_poisson_states).parameters:
        states = stat.fused_poisson_states(seed, x2, B, n_valid=n_valid,
                                           valid_mask=valid_mask)
    else:
        states = None
    if states is not None:
        return states
    from repro.kernels.weighted_stats import ops as ws_ops
    w = ws_ops.implicit_weights(seed, B, x2.shape[0])
    if n_valid is not None:
        w = w * (jnp.arange(x2.shape[0]) < n_valid).astype(w.dtype)[None, :]
    if valid_mask is not None:
        w = w * jnp.asarray(valid_mask, w.dtype).reshape(1, -1)
    dim = x2.shape[1]
    return jax.vmap(lambda wr: stat.update(stat.init_state(dim), x2, wr))(w)


# ----------------------------------------------------------------------------
# sharded matrix-free path (psum the states, never the weights)
# ----------------------------------------------------------------------------
def _shard_local_states(stat: Statistic, base_seed, x_local: jax.Array,
                        B: int, shard_idx, nshards: int, n_valid_local,
                        chunk: Optional[int] = None, step=0,
                        with_estimate: bool = False):
    """Fused states for ONE shard's local rows.

    The shard's stream seed for local chunk c is
    ``offset_seed(base_seed, (step + c) * nshards + shard_idx)`` — chunk-
    major interleaving: distinct per (shard, chunk) within a call and per
    (shard, step) across delta extends, and an nshards=1 "mesh" reproduces
    the single-device seeds exactly (stream index collapses to the
    chunk/step counter).  ``chunk`` and a nonzero ``step`` are mutually
    exclusive (enforced by ``sharded_fused_states``): combining them would
    alias step s's chunk c+1 stream with step s+1's chunk c stream.
    ``chunk=None`` processes the local rows in one fused call.

    ``with_estimate=True`` additionally folds the shard's rows into ONE
    unweighted (all-ones within n_valid) estimate state in the same pass,
    returning ``(states, est_state)`` — the chunked/streaming drivers'
    single-read estimate (no second pass over the data).
    """
    n_local, dim = x_local.shape
    est = stat.init_state(dim) if with_estimate else None
    if chunk is None:
        states, est = fold.fold_rows(stat, None, est, x_local, base_seed,
                                     step * nshards + shard_idx, B,
                                     n_valid=n_valid_local)
        return (states, est) if with_estimate else states
    pad = (-n_local) % chunk
    xp = jnp.pad(x_local, ((0, pad), (0, 0)))
    nchunks = xp.shape[0] // chunk
    xc = xp.reshape(nchunks, chunk, dim)

    def body(carry, c):
        nv = jnp.clip(n_valid_local - c * chunk, 0, chunk)
        return fold.fold_rows(stat, *carry, xc[c], base_seed,
                              (step + c) * nshards + shard_idx, B,
                              n_valid=nv), None

    (states, est), _ = jax.lax.scan(body,
                                    (fold.empty_states(stat, B, dim), est),
                                    jnp.arange(nchunks, dtype=jnp.int32))
    return (states, est) if with_estimate else states


def sharded_fused_states(stat: Statistic, base_seed, x2: jax.Array, B: int,
                         mesh=None, data_axis: str = "data",
                         nshards: Optional[int] = None,
                         chunk: Optional[int] = None, step=0,
                         with_estimate: bool = False):
    """B-leading pytree of fused per-resample states for ``x2``, sharded
    over ``mesh``'s ``data_axis`` (the multi-device matrix-free hot path).

    Rows are split into ``nshards`` contiguous blocks (zero-padded tail,
    masked via per-shard n_valid); each shard generates its implicit
    Poisson(1) weights in-kernel from its own stream (see
    ``_shard_local_states`` for the (base_seed, shard, chunk) keying) and
    only the psum of the small per-resample states
    (``Statistic.psum_state``) crosses devices.

    ``mesh=None`` (with ``nshards`` given) evaluates the identical
    decomposition sequentially on one device — the oracle the mesh run is
    bit-equal to.  ``chunk`` streams each shard's local rows through
    fixed-size fused calls (the sharded analogue of ``bootstrap_chunked``);
    ``step`` offsets the stream counter so delta-maintenance extends draw
    fresh streams per extension.  They are mutually exclusive: the stream
    index (step + c)·nshards + shard would alias across (step, chunk)
    pairs, silently correlating resamples between extensions.

    ``with_estimate=True`` returns ``(states, est_state)`` where
    ``est_state`` is the unweighted full-sample estimate state accumulated
    in the SAME pass (shard-wise merge / psum mirrors the resample states,
    so mesh and sequential stay bitwise consistent) — the single-read
    estimate for the chunked and streaming drivers.
    """
    # Fail actionably BEFORE tracing: a non-mergeable statistic would
    # otherwise die deep inside shard_map/scan with a shape error (or,
    # worse, silently mis-combine per-shard states).
    if not getattr(stat, "mergeable", True):
        raise ValueError(
            f"sharded_fused_states requires a mergeable statistic, but "
            f"{type(stat).__name__} sets mergeable=False — its per-shard "
            "states cannot be merge/psum-combined.  Use the single-device "
            "bootstrap (backend='fused_rng' without mesh=/nshards=), or "
            "implement an associative merge and set mergeable=True")
    if mesh is not None:
        nshards = int(mesh.shape[data_axis])
    if nshards is None:
        raise ValueError("sharded_fused_states needs mesh= or nshards=")
    if chunk is not None and not (isinstance(step, int) and step == 0):
        raise ValueError("chunk= and step= are mutually exclusive (their "
                         "stream indices would alias; see docstring)")
    if mesh is None:
        n = x2.shape[0]
        m = -(-n // nshards)             # ceil: local rows per shard
        xp = jnp.pad(x2, ((0, nshards * m - n), (0, 0)))
        states, est = None, None
        for i in range(nshards):
            nv = min(max(n - i * m, 0), m)
            si = _shard_local_states(stat, base_seed, xp[i * m:(i + 1) * m],
                                     B, i, nshards, nv, chunk=chunk,
                                     step=step, with_estimate=with_estimate)
            if with_estimate:
                si, ei = si
                est = ei if est is None else stat.merge(est, ei)
            states = si if states is None else \
                jax.vmap(stat.merge)(states, si)
        return (states, est) if with_estimate else states

    spec, params = split_params(stat)
    return _mesh_fold_jit(x2, jnp.asarray(base_seed, jnp.int32),
                          jnp.asarray(step, jnp.int32), params, spec, int(B),
                          mesh, data_axis, chunk, bool(with_estimate))


@partial(jax.jit, static_argnames=("stat", "B", "mesh", "data_axis", "chunk",
                                   "with_estimate"))
def _mesh_fold_jit(x2, base_seed, step, params, stat, B, mesh, data_axis,
                   chunk, with_estimate):
    """The mesh branch of ``sharded_fused_states`` as one program, cached
    per (statistic spec, B, mesh, axis, chunk, with_estimate) and the
    shapes of x: a warm call traces and builds nothing.  Called inside
    another jit it inlines there."""
    from jax.sharding import PartitionSpec as P
    stat = bind_params(stat, params)
    nshards = int(mesh.shape[data_axis])
    n = x2.shape[0]
    m = -(-n // nshards)
    xp = jnp.pad(x2, ((0, nshards * m - n), (0, 0)))

    def shard_fn(x_local, seed, step_):
        trace.count(trace.MESH_TRACES)        # runs only while JAX traces
        i = jax.lax.axis_index(data_axis)
        nv = jnp.clip(n - i * m, 0, m)
        st = _shard_local_states(stat, seed, x_local, B, i, nshards, nv,
                                 chunk=chunk, step=step_,
                                 with_estimate=with_estimate)
        if with_estimate:
            st, est = st
            return (stat.psum_state(st, data_axis),
                    stat.psum_state(est, data_axis))
        return stat.psum_state(st, data_axis)

    fn = jax.shard_map(shard_fn, mesh=mesh,
                       in_specs=(P(data_axis, None), P(), P()),
                       out_specs=(P(), P()) if with_estimate else P(),
                       check_vma=False)
    return fn(xp, base_seed, step)


def state_nbytes(tree) -> int:
    """Bytes of the leaves of a state pytree (arrays or shape structs)."""
    return sum(leaf.size * leaf.dtype.itemsize
               for leaf in jax.tree_util.tree_leaves(tree))


@contextlib.contextmanager
def mesh_fold(mesh, data_axis: str, n: int):
    """Host side of one fold of ``n`` rows: with a mesh, the
    ``earl.mesh.fold`` span around its dispatch, then one ``mesh_folds``
    and, in ``mesh_psum_bytes``, the bytes of the state trees handed to the
    yielded ``psummed``; without one, nothing."""
    if mesh is None:
        yield lambda tree: None
        return
    trees = []
    with trace.span("mesh.fold", n=n, shards=mesh.shape[data_axis]):
        yield trees.append
    trace.count(trace.MESH_FOLDS)
    trace.count(trace.MESH_PSUM_BYTES, state_nbytes(trees))


@lru_cache(maxsize=None)
def _resample_states_shape(stat: Statistic, B: int, dim: int):
    """Shapes of ``B`` resample states of ``stat`` over ``dim`` columns."""
    return jax.eval_shape(lambda: fold.empty_states(stat, B, dim))


def multinomial_counts(key: jax.Array, B: int, n: int,
                       resample_size: Optional[int] = None) -> jax.Array:
    """Exact multinomial bootstrap counts, shape (B, n) int32.

    Drawn as n' categorical draws per resample, histogrammed as ONE
    flattened (B·m,) scatter-add into the (B, n) zeros buffer — a single
    XLA scatter dispatch instead of B vmapped ones.
    """
    m = n if resample_size is None else int(resample_size)
    idx = jax.random.randint(key, (B, m), 0, n)            # (B, m) draws
    rows = jnp.broadcast_to(jnp.arange(B, dtype=idx.dtype)[:, None],
                            idx.shape)
    # 2-D scatter indices (not a flattened B·n offset, which would overflow
    # int32 once B·n >= 2^31): still one XLA scatter dispatch.
    return jnp.zeros((B, n), jnp.int32).at[rows, idx].add(1)


def poisson_weights(key: jax.Array, B: int, n: int,
                    dtype=jnp.float32) -> jax.Array:
    """Poisson(1) bootstrap weights, shape (B, n)."""
    return jax.random.poisson(key, 1.0, (B, n)).astype(dtype)


def weights_for(engine: str, key: jax.Array, B: int, n: int) -> jax.Array:
    if engine == "multinomial":
        return multinomial_counts(key, B, n).astype(jnp.float32)
    if engine == "poisson":
        return poisson_weights(key, B, n)
    raise ValueError(f"unknown bootstrap engine: {engine!r}")


# ----------------------------------------------------------------------------
# the resample loop
# ----------------------------------------------------------------------------
def bootstrap_thetas(values: jax.Array, stat: Statistic,
                     weights: jax.Array, use_kernel: bool = False
                     ) -> jax.Array:
    """Apply ``stat`` under every weight row.  Returns (B, ...) results."""
    x2 = _as_2d(values)
    dim = x2.shape[1]

    if use_kernel and stat.moment_powers is not None:
        # fused Pallas path: one (B,n)@(n,d) pass for all moments at once.
        from repro.kernels.weighted_stats import ops as ws_ops
        w_tot, s1, s2 = ws_ops.weighted_moments(weights, x2)
        states = jax.vmap(stat.from_moments)(w_tot, s1, s2)
        return jax.vmap(stat.finalize)(states)

    def one(w_row):
        return stat.finalize(stat.update(stat.init_state(dim), values, w_row))

    return jax.vmap(one)(weights)


def _fused_thetas(values: jax.Array, stat: Statistic, B: int,
                  key: jax.Array) -> jax.Array:
    """Matrix-free resample loop: moments via in-kernel RNG, (B, n) never
    built.  Falls back to materializing the same implicit weights for
    statistics without a moment decomposition."""
    states = fused_resample_states(stat, seed_from_key(key), _as_2d(values),
                                   B)
    return jax.vmap(stat.finalize)(states)


@partial(jax.jit,
         static_argnames=("stat", "B", "engine", "use_kernel", "backend",
                          "mesh", "data_axis"))
def _bootstrap_jit(values, key, params, stat, B, engine, use_kernel,
                   backend, mesh=None, data_axis="data"):
    # ``stat`` is the hashable spec; its array parameters (e.g. KMeansStep
    # centroids) arrive traced in ``params`` so Lloyd-style loops that pass
    # a fresh same-shaped Statistic per call hit this cache entry.
    stat = bind_params(stat, params)
    n = values.shape[0]
    if backend == "fused_rng":
        if mesh is not None:
            states = sharded_fused_states(stat, seed_from_key(key),
                                          _as_2d(values), B, mesh=mesh,
                                          data_axis=data_axis)
            thetas = jax.vmap(stat.finalize)(states)
        else:
            thetas = _fused_thetas(values, stat, B, key)
    else:
        w = weights_for(engine, key, B, n)
        thetas = bootstrap_thetas(values, stat, w, use_kernel=use_kernel)
    estimate = stat(values)
    return thetas, estimate


def _check_fused_backend(backend, engine, mesh):
    if backend not in (None, "fused_rng"):
        raise ValueError(f"unknown bootstrap backend: {backend!r}")
    if backend == "fused_rng" and engine != "poisson":
        raise ValueError("backend='fused_rng' requires the poisson engine "
                         "(in-kernel RNG draws iid Poisson(1) weights)")
    if mesh is not None and backend != "fused_rng":
        raise ValueError("mesh= requires backend='fused_rng' (the sharded "
                         "path psums fused states; materialized weights "
                         "would ship a (B, n) matrix across devices)")


def bootstrap(values: jax.Array, stat: Statistic, B: int, key: jax.Array,
              engine: str = "poisson", p: float = 1.0,
              use_kernel: bool = False, alpha: float = 0.05,
              backend: Optional[str] = None, mesh=None,
              data_axis: str = "data") -> BootstrapResult:
    """One full bootstrap pass: B resamples, result distribution, accuracy.

    ``p`` is the fraction of the population the sample represents — passed to
    ``stat.correct`` (paper §2.1) on both the estimate and the thetas.
    ``backend="fused_rng"`` runs the matrix-free pipeline (module
    docstring); adding ``mesh=`` (a jax.sharding.Mesh) shards the n axis
    over ``data_axis`` and psums the per-shard fused states — no weight or
    sample traffic crosses devices.
    """
    if not isinstance(stat, Statistic):
        raise TypeError("stat must be a reduce_api.Statistic")
    _check_fused_backend(backend, engine, mesh)
    spec, params = split_params(stat)
    with mesh_fold(mesh, data_axis, values.shape[0]) as psummed:
        thetas, estimate = _bootstrap_jit(values, key, params, spec, int(B),
                                          engine, bool(use_kernel), backend,
                                          mesh, data_axis)
        if mesh is not None:        # the states psummed inside the program
            psummed(_resample_states_shape(spec, int(B),
                                           _as_2d(values).shape[1]))
    thetas = stat.correct(thetas, p)
    estimate = stat.correct(estimate, p)
    return BootstrapResult(
        estimate=estimate,
        thetas=thetas,
        report=accuracy.report_for(thetas, alpha=alpha,
                                   num_groups=getattr(stat, "num_groups",
                                                      None)),
        B=int(B),
        n=int(values.shape[0]),
    )


# ----------------------------------------------------------------------------
# streaming / chunked variant (large samples that don't fit a (B,n) matrix)
# ----------------------------------------------------------------------------
def bootstrap_chunked(values: jax.Array, stat: Statistic, B: int,
                      key: jax.Array, chunk: int = 65536,
                      engine: str = "poisson", p: float = 1.0,
                      backend: Optional[str] = None, mesh=None,
                      data_axis: str = "data") -> BootstrapResult:
    """Scan over chunks of the sample, merging per-resample states.

    Only valid for mergeable statistics (all built-ins).  Poisson weights are
    drawn per chunk with a folded key, so the full (B, n) matrix never
    materializes — peak memory is (B, chunk), or O(B·d) / O(B·k·d) /
    O(B·d·nbins) with ``backend="fused_rng"`` (every built-in statistic has
    a fused path — see ``Statistic.fused_poisson_states``; the per-chunk
    weight matrix never materializes either).  Chunk seeds derive as
    ``offset_seed(base, i)`` so long streams can't wrap int32.

    With ``mesh=`` (fused backend only) each shard scans its LOCAL rows in
    ``chunk``-sized fused calls — streams keyed (base_seed, shard, chunk) —
    and the per-resample states psum once at the end; no weight or sample
    traffic crosses devices.
    """
    if engine != "poisson":
        raise ValueError("chunked bootstrap requires the poisson engine "
                         "(multinomial couples all chunks; see DESIGN.md §7)")
    _check_fused_backend(backend, engine, mesh)
    x = _as_2d(values)
    n, dim = x.shape

    if mesh is not None:
        with mesh_fold(mesh, data_axis, n) as psummed:
            states, est = sharded_fused_states(
                stat, seed_from_key(key), x, B, mesh=mesh,
                data_axis=data_axis, chunk=chunk, with_estimate=True)
            psummed((states, est))
    else:
        pad = (-n) % chunk
        xp = jnp.pad(x, ((0, pad), (0, 0)))
        nchunks = xp.shape[0] // chunk
        xc = xp.reshape(nchunks, chunk, dim)

        base_seed = seed_from_key(key)  # one base; chunks offset by counter

        def body(carry, inp):
            states, est = carry
            i, xi = inp
            n_valid = jnp.minimum(chunk, n - i * chunk)  # last-chunk suffix
            # unweighted estimate rides the SAME pass over xi (the old
            # ``stat(values)`` was a second full read of the sample).
            if backend == "fused_rng":
                return fold.fold_rows(stat, states, est, xi, base_seed, i, B,
                                      n_valid=n_valid), None
            vi = (jnp.arange(chunk) < n_valid).astype(jnp.float32)
            est = stat.update(est, xi, vi)
            w = poisson_weights(jax.random.fold_in(key, i), B, chunk) \
                * vi[None, :]
            new = jax.vmap(lambda s, wr: stat.update(s, xi, wr))(states, w)
            return (new, est), None

        (states, est), _ = jax.lax.scan(body, fold.empty_carry(stat, B, dim),
                                        (jnp.arange(nchunks), xc))
    thetas = jax.vmap(stat.finalize)(states)
    thetas = stat.correct(thetas, p)
    estimate = stat.correct(stat.finalize(est), p)
    return BootstrapResult(
        estimate=estimate, thetas=thetas,
        report=accuracy.report_for(thetas,
                                   num_groups=getattr(stat, "num_groups",
                                                      None)),
        B=int(B), n=int(n),
    )
