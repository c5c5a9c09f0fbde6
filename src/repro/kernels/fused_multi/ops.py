"""Public wrapper for the fused multi-statistic bootstrap pass.

``fused_poisson_multi`` computes, for every slot accumulator of a
``StatisticGroup``, the B per-resample states under ONE shared implicit
Poisson(1) weight stream and ONE pass over x: each (block_b, block_n)
weight tile is generated once — same ``weight_tile_blocks`` clamp and
``(seed, b-tile, n-tile)`` threefry keying as every other fused path, so
the implicit matrix is bit-identical to
``weighted_stats.ops.implicit_weights(seed, B, n)`` — and handed to every
slot's per-tile accumulator in turn.

Lowerings (``backend``):

* ``"scan"`` (CPU default) — a single ``lax.scan`` over n-tiles whose body
  draws the weight tile via the shared ``implicit_weight_tile`` and calls
  each slot's ``Statistic.tile_update``: moment slots run the
  weighted_stats dot math, histogram slots the weighted_hist scatter math,
  KMeansStep the kmeans_assign tile math, and custom statistics fall back
  to a vmapped ``update`` over the SAME cached tile — nothing ever
  regenerates or re-reads.
* ``"pallas"`` / ``"pallas_interpret"`` — kernels/fused_multi/kernel.py,
  available when every slot is a moment or histogram accumulator (the MXU
  shapes); groups with KMeansStep/custom slots use the scan lowering.
* ``None`` — auto: pallas on TPU when kernel-eligible, scan elsewhere.

NOT internally jitted: the callers (``_bootstrap_jit``, ``_pd_extend_jit``,
the chunked/sharded scan bodies) already trace it inside their jits, and a
StatisticGroup carrying traced member parameters (KMeansStep centroids)
must not be captured as a jit-static argument.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels.fused_multi.kernel import fused_poisson_multi_kernel
from repro.kernels.weighted_stats.ops import (_pad_to, implicit_weight_tile,
                                              weight_tile_blocks)


def _kernel_slots(group) -> Tuple[bool, tuple]:
    """(eligible, hist slot list) — the Pallas kernel handles at most one
    moment slot plus histogram slots."""
    from repro.core.reduce_api import Quantile, _MomentStatistic
    hists = tuple(s for s in group.slots if isinstance(s, Quantile))
    ok = all(isinstance(s, (Quantile, _MomentStatistic))
             for s in group.slots)
    return ok, hists


def _backend(backend, eligible: bool) -> str:
    """The lowering a ``backend`` of None resolves to: the kernel on a TPU
    when the group is eligible, else the scan."""
    if backend is not None:
        return backend
    return ("pallas" if jax.default_backend() == "tpu" and eligible
            else "scan")


def estimate_rides_kernel(group) -> bool:
    """Whether ``fused_poisson_multi(group, ..., with_estimate=True)`` can
    hand back the point estimate's histogram counts: a kernel-eligible
    group with a histogram slot, lowered to the Pallas kernel."""
    eligible, hists = _kernel_slots(group)
    return (eligible and bool(hists)
            and _backend(group.backend, eligible) != "scan")


def _multi_scan(slots, seed, n_valid, xp, B: int, block_b: int,
                block_n: int, maskp=None):
    """CPU lowering: one scan, one weight tile per step, every slot fed."""
    n, d = xp.shape
    nt = n // block_n
    xc = xp.reshape(nt, block_n, d)
    maskc = None if maskp is None else maskp.reshape(nt, block_n)
    init = tuple(jax.vmap(lambda _, s=s: s.init_state(d))(jnp.arange(B))
                 for s in slots)

    def body(states, t):
        w = implicit_weight_tile(seed, n_valid, t, B, block_b, block_n,
                                 valid=None if maskc is None else maskc[t])
        xt = xc[t]
        return tuple(s.tile_update(st, xt, w)
                     for s, st in zip(slots, states)), None

    states, _ = jax.lax.scan(body, init, jnp.arange(nt, dtype=jnp.int32))
    return states


def fused_poisson_tiled(stat, seed, values: jax.Array, B: int,
                        n_valid=None, valid_mask=None,
                        block_b: int = 128, block_n: int = 512):
    """Generic matrix-free tile scan for ONE statistic: draw each implicit
    Poisson(1) weight tile once (shared ``weight_tile_blocks`` /
    ``(seed, b-tile, n-tile)`` keying) and feed it to
    ``stat.tile_update``.  This is the ``_multi_scan`` machinery without
    the slot tuple — the fused path for statistics that segment or
    transform the tile themselves, e.g. ``GroupedStatistic`` over a custom
    inner (its ``tile_update`` splits the key column off ``x_tile`` and
    key-masks the shared weight tile), so even custom keyed statistics
    never materialize the (B, n) weight matrix."""
    if values.ndim == 1:
        values = values[:, None]
    n, d = values.shape
    if n_valid is None:
        n_valid = n
    bb, bn = weight_tile_blocks(B, n, block_b, block_n)
    Bp = B + (-B) % bb
    seed = jnp.asarray(seed, jnp.int32)
    n_valid = jnp.asarray(n_valid, jnp.int32)
    xp = _pad_to(values.astype(jnp.float32), bn, 0)
    mp = None
    if valid_mask is not None:
        mp = _pad_to(jnp.asarray(valid_mask, jnp.float32).reshape(n), bn, 0)
    states = _multi_scan((stat,), seed, n_valid, xp, Bp, bb, bn, maskp=mp)[0]
    return jax.tree_util.tree_map(lambda a: a[:B], states)


def fused_poisson_multi(group, seed, values: jax.Array, B: int,
                        n_valid=None, valid_mask=None,
                        backend: str | None = None,
                        block_b: int = 128, block_n: int = 512,
                        with_estimate: bool = False) -> Tuple:
    """Slot-ordered tuple of B-leading per-resample states for ``group``
    under one shared in-kernel Poisson(1) weight stream.

    ``n_valid`` (traced scalar, default n) masks weight columns >= n_valid
    to zero, exactly as in every other fused path.  ``valid_mask`` (traced
    (n,) f32 of exact 0.0/1.0) multiplies the shared weight tiles —
    arbitrary interior validity holes; a prefix-shaped mask reproduces the
    ``n_valid`` result bit for bit (see ``implicit_weight_tile``).  The
    result is what ``StatisticGroup.fused_poisson_states`` returns — its
    state pytree.

    ``with_estimate=True`` (Pallas lowerings only, see
    ``estimate_rides_kernel``) returns ``(states, counts)``: ``counts``
    holds, per histogram slot, the (d, nbins) unit-weight counts of the
    rows under the same masks, which the kernel takes from the dots that
    build the resample sketches.
    """
    from repro.core.reduce_api import HistogramState, _MomentStatistic
    if values.ndim == 1:
        values = values[:, None]
    n, d = values.shape
    eligible, hist_slots = _kernel_slots(group)
    backend = _backend(backend, eligible)
    if backend not in ("scan", "pallas", "pallas_interpret"):
        raise ValueError(f"unknown fused_poisson_multi backend: {backend!r}")
    if backend != "scan" and not eligible:
        raise ValueError(
            "the fused_multi Pallas kernel covers moment/histogram slots "
            "only; groups with KMeansStep or custom statistics use "
            "backend='scan' (same shared weight tiles, via tile_update)")
    if with_estimate and (backend == "scan" or not hist_slots):
        raise ValueError("with_estimate needs the Pallas kernel and a "
                         "histogram slot (estimate_rides_kernel)")
    if n_valid is None:
        n_valid = n

    bb, bn = weight_tile_blocks(B, n, block_b, block_n)
    Bp = B + (-B) % bb
    seed = jnp.asarray(seed, jnp.int32)
    n_valid = jnp.asarray(n_valid, jnp.int32)
    xp = _pad_to(values.astype(jnp.float32), bn, 0)
    mp = None
    if valid_mask is not None:
        mp = _pad_to(jnp.asarray(valid_mask, jnp.float32).reshape(n), bn, 0)

    if backend == "scan":
        states = _multi_scan(group.slots, seed, n_valid, xp, Bp, bb, bn,
                             maskp=mp)
        return jax.tree_util.tree_map(lambda a: a[:B], states)

    # ---- Pallas kernel path: moments + hist slots only ------------------
    kinds = tuple("moments" if isinstance(s, _MomentStatistic) else "hist"
                  for s in group.slots)
    xpp = _pad_to(xp, 128, 1)
    los = tuple(_pad_to(jnp.full((1, d), s.lo, jnp.float32), 128, 1)
                for s in hist_slots)
    his = tuple(_pad_to(jnp.full((1, d), s.hi, jnp.float32), 128, 1,
                        value=1.0)              # nonzero padding span
                for s in hist_slots)
    outs = fused_poisson_multi_kernel(
        seed, n_valid, xpp, los, his, Bp, kinds=kinds,
        hist_nbins=tuple(s.nbins for s in hist_slots), d_valid=d,
        block_b=bb, block_n=bn, interpret=(backend != "pallas"),
        use_tpu_prng=(backend == "pallas"),
        mask=None if mp is None else mp[None, :],
        with_estimate=with_estimate)

    states, oi = [], 0
    for slot, kind in zip(group.slots, kinds):
        if kind == "moments":
            wt, s1, s2 = outs[oi:oi + 3]
            oi += 3
            states.append(jax.vmap(slot.from_moments)(
                wt[:B, 0], s1[:B, :d], s2[:B, :d]))
        else:
            ob = slot.nbins + (-slot.nbins) % 128
            counts = outs[oi].reshape(Bp, d, ob)[:B, :, :slot.nbins]
            oi += 1
            states.append(HistogramState(
                counts=counts,
                lo=jnp.full((B, d), slot.lo, jnp.float32),
                hi=jnp.full((B, d), slot.hi, jnp.float32)))
    if not with_estimate:
        return tuple(states)
    counts = tuple(e[0].reshape(d, -1)[:, :s.nbins]
                   for e, s in zip(outs[oi:], hist_slots))
    return tuple(states), counts
