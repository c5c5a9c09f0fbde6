"""Where rows become resample states: the one fold of every fused path.

The session's Δ extends, ``bootstrap_chunked``, the sharded folds, the
stream pass and the live window all fold a block of rows through
``fold_rows``: the point estimate's update under the rows' mask, then the
block's resample states under in-kernel Poisson(1) weights from stream
``offset_seed(base_seed, i)``, merged into the running states.  Their
bitwise contracts with one another rest on sharing it.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro import trace
from repro.core import bootstrap     # which imports this module in turn
from repro.core.reduce_api import (HistogramState, Quantile, Statistic,
                                   StatisticGroup, bind_params)
from repro.kernels.fused_multi import ops as fm_ops


def empty_states(stat: Statistic, B: int, dim: int):
    """``B`` empty resample states of ``stat`` over ``dim`` columns."""
    return jax.vmap(lambda _: stat.init_state(dim))(jnp.arange(B))


def empty_carry(stat: Statistic, B: int, dim: int):
    """A fold's empty carry: ``B`` empty resample states, and the
    estimate's."""
    return empty_states(stat, B, dim), stat.init_state(dim)


def fresh(tree):
    """Each leaf of ``tree`` in a device buffer of its own, read through the
    host.  A donated carry needs them: JAX's constant cache can hand several
    identical zero leaves one buffer, and donating it twice fails."""
    return jax.tree_util.tree_map(
        lambda a: jax.device_put(trace.to_host(a)), tree)


def fold_rows(stat: Statistic, states, est, x: jax.Array, base_seed, i,
              B: int, *, n_valid=None, valid_mask=None):
    """Fold the rows ``x`` (n, d) into ``(states, est)``; returns the pair.

    The estimate's update (``earl.estimate``) masks the rows by
    ``valid_mask`` (exact 0.0/1.0) and the prefix of ``n_valid`` rows,
    where given; ``est=None`` skips it.  The resample fold
    (``earl.resample``) runs ``fused_resample_states`` on stream
    ``offset_seed(base_seed, i)`` with the same mask and merges the result
    into ``states``, or returns it when ``states`` is None.  Pure and
    jittable: callers trace it inside their own programs.

    Where the resample fold is the fused Pallas kernel of a
    ``StatisticGroup`` with a histogram slot (``estimate_rides_kernel``),
    the estimate's histogram counts come out of that kernel's own dots,
    exact and equal to ``update``'s scatter; its moment slots keep
    ``update``.  Each trace of an estimate counts ``estimate_in_kernel``
    then, or ``estimate_scatter`` where the estimate holds a histogram that
    ``update`` builds.
    """
    in_kernel = (est is not None and isinstance(stat, StatisticGroup)
                 and fm_ops.estimate_rides_kernel(stat))
    if est is not None:
        mask = valid_mask
        if n_valid is not None:
            prefix = (jnp.arange(x.shape[0]) < n_valid).astype(jnp.float32)
            mask = prefix if mask is None else prefix * mask
        if in_kernel:
            trace.count(trace.ESTIMATE_IN_KERNEL)
        elif _holds_histogram(est):
            trace.count(trace.ESTIMATE_SCATTER)
        with jax.named_scope("earl.estimate"):
            est = (tuple(st if isinstance(s, Quantile)
                         else s.update(st, x, mask)
                         for s, st in zip(stat.slots, est))
                   if in_kernel else stat.update(est, x, mask))
    with jax.named_scope("earl.resample"):
        seed = bootstrap.offset_seed(base_seed, i)
        if in_kernel:
            delta, counts = fm_ops.fused_poisson_multi(
                stat, seed, x, B, n_valid=n_valid, valid_mask=valid_mask,
                backend=stat.backend, with_estimate=True)
            counts = iter(counts)
            est = tuple(HistogramState(st.counts + next(counts), st.lo, st.hi)
                        if isinstance(s, Quantile) else st
                        for s, st in zip(stat.slots, est))
        else:
            delta = bootstrap.fused_resample_states(
                stat, seed, x, B, n_valid=n_valid, valid_mask=valid_mask)
        states = delta if states is None else \
            jax.vmap(stat.merge)(states, delta)
    return states, est


def _holds_histogram(est) -> bool:
    return any(isinstance(leaf, HistogramState)
               for leaf in jax.tree_util.tree_leaves(
                   est, is_leaf=lambda a: isinstance(a, HistogramState)))


@partial(jax.jit, static_argnames=("spec", "B"), donate_argnums=(0, 1))
def fold_chunk_jit(states, est, xi, vi, base_seed, i, params, spec, B):
    """Fold one block, a stream pass's chunk i or a live batch of sequence
    number i, into a donated ``(states, est)`` carry (buffers from
    ``fresh``), as one program cached per (statistic spec, B) and shapes.
    ``vi`` is the block's exact 0.0/1.0 row mask: a prefix for a ragged
    tail, holes for rows of lost splits, shed rows or rows off a pane."""
    return fold_rows(bind_params(spec, params), states, est, xi, base_seed,
                     i, B, valid_mask=vi)
