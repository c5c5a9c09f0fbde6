"""Fused multi-statistic Pallas kernel (the StatisticGroup hot path).

One kernel pass = one shared implicit Poisson(1) weight tile per
(b, n)-block feeding EVERY slot accumulator of a ``StatisticGroup``: the
moment dot-accumulators of kernels/weighted_stats and the histogram
one-hot contractions of kernels/weighted_hist, fused behind a single
``_poisson_tile`` draw and a single VMEM-resident x tile — k statistics
cost ~1× the PRNG work and x traffic of one, and every member sees the
SAME resamples (joint CIs from common random numbers).

Slot layout is static (``kinds``): at most one ``"moments"`` slot
(Mean/Var/Std/… share one accumulator by construction — see
``Statistic.accumulator_key``) and any number of ``"hist"`` slots, each
with its own (nbins, lo, hi).  KMeansStep / custom slots have no kernel
lowering — ops.py routes groups containing them through the scan lowering,
where they consume the same cached weight tile via
``Statistic.tile_update``.

The per-tile weight draw and the per-slot tile math are imported from the
single-statistic kernels (``_poisson_tile``, ``_bin_indices``,
``finite_mass_mask``), so the implicit weight matrix stays bit-identical
to ``implicit_weights(seed, B, n)`` and the fused group is bit-identical
to running each member's dedicated fused kernel with the same seed.

Grid: (B/bB, n/bn) with the contraction axis n LAST, so every output tile
is revisited sequentially and accumulated in place (same discipline as
weighted_stats / weighted_hist / kmeans_assign).  Both axes stay
sequential: ``with_estimate``'s point-estimate counts are one block that
every grid step of B-tile 0 accumulates into.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.weighted_hist.ref import _bin_indices, finite_mass_mask
from repro.kernels.weighted_stats.kernel import _poisson_tile


def _fm_kernel(scal_ref, x_ref, *refs, kinds, hist_nbins, hist_out_bins,
               d: int, block_b: int, block_n: int, use_tpu_prng: bool,
               has_mask: bool = False, with_estimate: bool = False):
    i = pl.program_id(0)        # B-tile index
    t = pl.program_id(1)        # n-tile index (contraction)

    n_hist = sum(1 for k in kinds if k == "hist")
    in_refs = refs[:2 * n_hist]             # (lo, hi) per hist slot
    m_ref = refs[2 * n_hist] if has_mask else None
    out_refs = refs[2 * n_hist + (1 if has_mask else 0):]
    est_refs = out_refs[len(out_refs) - n_hist:] if with_estimate else ()

    # ONE weight tile for every slot below — the whole point of the kernel.
    w = _poisson_tile(scal_ref[0], i, t, pl.num_programs(1),
                      (block_b, block_n), scal_ref[1],
                      block_n, use_tpu_prng,
                      valid=None if m_ref is None else m_ref[...])  # (bB, bn)
    x = x_ref[...].astype(jnp.float32)                        # (bn, dp)
    bn = x.shape[0]
    if with_estimate:
        # The point estimate's unit weights, as 8 more LHS rows of the
        # B-tile-0 histogram dots: row 0 is the mask _poisson_tile applies
        # to w, rows 1-7 are zero (an (8, 128)-aligned block).
        row = jax.lax.broadcasted_iota(jnp.int32, (8, bn), 0)
        col = t * block_n + jax.lax.broadcasted_iota(jnp.int32, (8, bn), 1)
        unit = jnp.where(jnp.logical_and(row == 0, col < scal_ref[1]),
                         1.0, 0.0)
        if m_ref is not None:
            unit = unit * m_ref[...]
        w_unit = jnp.concatenate([w, unit], axis=0)      # (bB + 8, bn)

    oi = 0      # output-ref cursor
    hidx = 0    # hist-slot cursor
    for kind in kinds:
        if kind == "moments":
            wtot_ref, s1_ref, s2_ref = out_refs[oi:oi + 3]
            oi += 3

            @pl.when(t == 0)
            def _init_m(wtot_ref=wtot_ref, s1_ref=s1_ref, s2_ref=s2_ref):
                wtot_ref[...] = jnp.zeros(wtot_ref.shape, wtot_ref.dtype)
                s1_ref[...] = jnp.zeros(s1_ref.shape, s1_ref.dtype)
                s2_ref[...] = jnp.zeros(s2_ref.shape, s2_ref.dtype)

            wtot_ref[...] += jnp.sum(w, axis=1, keepdims=True)
            s1_ref[...] += jax.lax.dot(w, x,
                                       preferred_element_type=jnp.float32)
            s2_ref[...] += jax.lax.dot(w, x * x,
                                       preferred_element_type=jnp.float32)
        else:
            nbins = hist_nbins[hidx]
            out_bins = hist_out_bins[hidx]
            lo_ref, hi_ref = in_refs[2 * hidx:2 * hidx + 2]
            out_ref = out_refs[oi]
            est_ref = est_refs[hidx] if with_estimate else None
            oi += 1
            hidx += 1

            @pl.when(t == 0)
            def _init_h(out_ref=out_ref):
                out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

            if with_estimate:
                @pl.when(jnp.logical_and(i == 0, t == 0))
                def _init_e(est_ref=est_ref):
                    est_ref[...] = jnp.zeros(est_ref.shape, est_ref.dtype)

            idx = _bin_indices(x, lo_ref[...], hi_ref[...], nbins)
            mass = finite_mass_mask(x)
            bins = jax.lax.broadcasted_iota(jnp.int32, (bn, out_bins), 1)
            # d lane-aligned dots reusing the one weight tile (same layout
            # discipline as weighted_hist's fused kernel); only the d REAL
            # columns are contracted — lane padding of x is never read.
            for c in range(d):
                onehot = ((idx[:, c:c + 1] == bins).astype(jnp.float32)
                          * mass[:, c:c + 1])                 # (bn, ob)
                cols = slice(c * out_bins, (c + 1) * out_bins)
                if not with_estimate:
                    out_ref[:, cols] += jax.lax.dot(
                        w, onehot, preferred_element_type=jnp.float32)
                    continue

                # The estimate's counts come out of the dot that already
                # consumes this one-hot, once: on B-tile 0 alone.
                @pl.when(i == 0)
                def _dot_e(out_ref=out_ref, est_ref=est_ref, cols=cols,
                           onehot=onehot):
                    prod = jax.lax.dot(w_unit, onehot,
                                       preferred_element_type=jnp.float32)
                    out_ref[:, cols] += prod[:block_b]
                    est_ref[:, cols] += prod[block_b:]

                @pl.when(i != 0)
                def _dot(out_ref=out_ref, cols=cols, onehot=onehot):
                    out_ref[:, cols] += jax.lax.dot(
                        w, onehot, preferred_element_type=jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("B", "kinds", "hist_nbins", "d_valid",
                                    "block_b", "block_n", "interpret",
                                    "use_tpu_prng", "with_estimate"))
def fused_poisson_multi_kernel(seed: jax.Array, n_valid: jax.Array,
                               values: jax.Array, hist_lo, hist_hi, B: int,
                               kinds, hist_nbins, d_valid: int,
                               block_b: int = 128, block_n: int = 512,
                               interpret: bool = True,
                               use_tpu_prng: bool = False,
                               mask=None, with_estimate: bool = False):
    """Raw kernel entry: shapes must already be padded (ops.py does this).

    values (n, dp) f32 with dp the 128-lane-padded dimension; ``hist_lo``/
    ``hist_hi`` are tuples of (1, dp) f32 arrays, one per ``"hist"`` entry
    of ``kinds`` (padding spans must be nonzero).  ``kinds`` is the static
    slot layout, e.g. ``("moments", "hist", "hist")``; ``hist_nbins`` the
    matching true bin counts.  ``B`` must be a ``block_b`` multiple,
    ``n_valid`` masks weight columns past the unpadded row count.

    Returns the flat output tuple in slot order: a "moments" slot yields
    (w_tot (B, 1), s1 (B, dp), s2 (B, dp)); a "hist" slot yields
    (B, d_valid·out_bins) with out_bins = nbins lane-padded to 128 —
    callers reshape and slice [..., :nbins].  ``with_estimate`` appends,
    per "hist" slot, an (8, d_valid·out_bins) block whose row 0 is the
    unit-weight histogram of the rows under the same mask (the point
    estimate's counts) and whose other rows are zero.
    """
    n, dp = values.shape
    assert B % block_b == 0 and n % block_n == 0, ((B, n), (block_b, block_n))
    assert d_valid <= dp, (d_valid, dp)
    assert sum(1 for k in kinds if k == "hist") == len(hist_nbins) == \
        len(hist_lo) == len(hist_hi), (kinds, hist_nbins)
    assert kinds.count("moments") <= 1, kinds
    hist_out_bins = tuple(nb + (-nb) % 128 for nb in hist_nbins)

    in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((block_n, dp), lambda i, t: (t, 0))]
    operands = [jnp.stack([jnp.asarray(seed, jnp.int32),
                           jnp.asarray(n_valid, jnp.int32)]), values]
    for lo, hi in zip(hist_lo, hist_hi):
        in_specs.append(pl.BlockSpec((1, dp), lambda i, t: (0, 0)))
        in_specs.append(pl.BlockSpec((1, dp), lambda i, t: (0, 0)))
        operands.extend([lo, hi])
    if mask is not None:
        in_specs.append(pl.BlockSpec((1, block_n), lambda i, t: (0, t)))
        operands.append(mask)

    out_specs, out_shape = [], []
    hidx = 0
    for kind in kinds:
        if kind == "moments":
            out_specs += [
                pl.BlockSpec((block_b, 1), lambda i, t: (i, 0)),
                pl.BlockSpec((block_b, dp), lambda i, t: (i, 0)),
                pl.BlockSpec((block_b, dp), lambda i, t: (i, 0)),
            ]
            out_shape += [
                jax.ShapeDtypeStruct((B, 1), jnp.float32),
                jax.ShapeDtypeStruct((B, dp), jnp.float32),
                jax.ShapeDtypeStruct((B, dp), jnp.float32),
            ]
        else:
            ob = hist_out_bins[hidx]
            hidx += 1
            out_specs.append(pl.BlockSpec((block_b, d_valid * ob),
                                          lambda i, t: (i, 0)))
            out_shape.append(
                jax.ShapeDtypeStruct((B, d_valid * ob), jnp.float32))
    if with_estimate:
        # constant index map: resident across the whole sequential grid
        for ob in hist_out_bins:
            out_specs.append(pl.BlockSpec((8, d_valid * ob),
                                          lambda i, t: (0, 0)))
            out_shape.append(
                jax.ShapeDtypeStruct((8, d_valid * ob), jnp.float32))

    kern = functools.partial(_fm_kernel, kinds=tuple(kinds),
                             hist_nbins=tuple(hist_nbins),
                             hist_out_bins=hist_out_bins, d=d_valid,
                             block_b=block_b, block_n=block_n,
                             use_tpu_prng=use_tpu_prng,
                             has_mask=mask is not None,
                             with_estimate=with_estimate)
    outs = pl.pallas_call(
        kern,
        grid=(B // block_b, n // block_n),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*operands)
    return tuple(outs)
