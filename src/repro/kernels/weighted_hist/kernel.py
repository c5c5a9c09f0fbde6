"""Fused weighted-histogram Pallas kernels (mergeable quantile sketch).

``weighted_hist_kernel`` computes, per value dimension c, a fixed-range
weighted histogram

    counts[c, b] = Σ_i  w[i] · 1[ bin(x[i, c]) = b ]

without ever materializing the (n, d, nbins) one-hot tensor the naive
``jax.nn.one_hot`` + einsum path builds in HBM (the §6.2 median/quantile
memory blowup).  Each (bn, bd) value tile is binned in VMEM and the per-bin
mass is accumulated with one (1, bn) × (bn, nbins) MXU contraction per
dimension column — the one-hot exists only tile-at-a-time in VMEM.

``fused_poisson_hist_kernel`` is the matrix-free bootstrap path for
Quantile/Median: the B Poisson(1) resample weight rows are generated
*inside* the kernel from the same counter-based PRNG tile discipline as
kernels/weighted_stats.fused_poisson_moments (keyed by (seed, b-tile,
n-tile), so the implicit weight matrix is bit-identical to
``implicit_weights(seed, B, n)`` under matching blocks) and contracted
against the tile-local one-hot — neither the (B, n) weight matrix nor the
(n, d, nbins) one-hot ever exists in HBM; peak live state is the
O(B·d·nbins) per-resample histogram accumulators.

Binning rule (clip out-of-range into edge bins, drop NaN mass) is imported
from ref.py so kernel, scan lowering and scatter path can never drift.

Grids: ``(d/bd, n/bn)`` for the single-state pass; ``(B/bB, n/bn)`` for the
fused bootstrap pass with the contraction axis n LAST so output tiles are
revisited sequentially and accumulated in place (same discipline as
weighted_stats / kmeans_assign).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.weighted_hist.ref import _bin_indices, finite_mass_mask
from repro.kernels.weighted_stats.kernel import _poisson_tile


def _wh_kernel(x_ref, w_ref, lo_ref, hi_ref, out_ref, *, nbins: int,
               out_bins: int, block_d: int):
    k = pl.program_id(1)        # n-tile index (accumulation axis)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    x = x_ref[...].astype(jnp.float32)           # (bn, bd)
    w = w_ref[...].astype(jnp.float32)           # (bn, 1)
    # bin against the TRUE nbins; out_bins >= nbins is only lane padding,
    # so bins [nbins, out_bins) stay empty and slicing them off is exact.
    idx = _bin_indices(x, lo_ref[...], hi_ref[...], nbins)      # (bn, bd)
    mass = finite_mass_mask(x)                   # (bn, bd); NaN carries none

    bn = x.shape[0]
    bins = jax.lax.broadcasted_iota(jnp.int32, (bn, out_bins), 1)
    wt = w.reshape(1, bn)
    for c in range(block_d):                     # static unroll, bd is small
        onehot = (idx[:, c:c + 1] == bins).astype(jnp.float32)  # (bn, ob)
        out_ref[c:c + 1, :] += jax.lax.dot(
            wt * mass[:, c].reshape(1, bn), onehot,
            preferred_element_type=jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("nbins", "block_n", "block_d",
                                    "interpret"))
def weighted_hist_kernel(values: jax.Array, weights: jax.Array,
                         lo: jax.Array, hi: jax.Array, nbins: int,
                         block_n: int = 256, block_d: int = 8,
                         interpret: bool = True) -> jax.Array:
    """Raw kernel entry: shapes must already be padded to block multiples.

    values (n, d) f32, weights (n, 1) f32 (zero-padded rows contribute
    nothing), lo/hi (1, d) f32.  ``nbins`` is the true bin count; the
    output's last dim is padded up to the 128-lane multiple (extra bins are
    always zero — callers slice [:, :nbins]).  Returns (d, out_bins) f32.
    """
    n, d = values.shape
    assert n % block_n == 0 and d % block_d == 0, ((n, d), (block_n, block_d))
    out_bins = nbins + (-nbins) % 128

    grid = (d // block_d, n // block_n)
    kern = functools.partial(_wh_kernel, nbins=nbins, out_bins=out_bins,
                             block_d=block_d)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, block_d), lambda j, k: (k, j)),
            pl.BlockSpec((block_n, 1), lambda j, k: (k, 0)),
            pl.BlockSpec((1, block_d), lambda j, k: (0, j)),
            pl.BlockSpec((1, block_d), lambda j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_d, out_bins), lambda j, k: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((d, out_bins), jnp.float32),
        interpret=interpret,
    )(values, weights, lo, hi)


# ============================================================================
# matrix-free bootstrap path: in-kernel weight generation + binning
# ============================================================================
def _fph_kernel(scal_ref, x_ref, lo_ref, hi_ref, *refs, nbins: int,
                out_bins: int, d: int, block_b: int, block_n: int,
                use_tpu_prng: bool, has_mask: bool = False):
    if has_mask:
        m_ref, out_ref = refs
    else:
        m_ref, (out_ref,) = None, refs
    i = pl.program_id(0)        # B-tile index
    t = pl.program_id(1)        # n-tile index (contraction)

    w = _poisson_tile(scal_ref[0], i, t, pl.num_programs(1),
                      (block_b, block_n), scal_ref[1],
                      block_n, use_tpu_prng,
                      valid=None if m_ref is None else m_ref[...])  # (bB, bn)
    x = x_ref[...].astype(jnp.float32)                       # (bn, dp)
    idx = _bin_indices(x, lo_ref[...], hi_ref[...], nbins)   # (bn, dp)
    mass = finite_mass_mask(x)                               # (bn, dp)

    @pl.when(t == 0)
    def _init():
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    bn = x.shape[0]
    bins = jax.lax.broadcasted_iota(jnp.int32, (bn, out_bins), 1)
    # per-dim masked one-hot: out[:, c·ob:(c+1)·ob] is dimension c's (B,
    # nbins) counts — d lane-aligned dots reusing the one (bB, bn) weight
    # tile, same layout discipline as fused_poisson_kmeans' kp·dp columns.
    # Only the d REAL columns get a dot; the lane padding of x (dp >= d,
    # ops.py pads to 128 like every other fused kernel) is never read.
    for c in range(d):
        onehot = ((idx[:, c:c + 1] == bins).astype(jnp.float32)
                  * mass[:, c:c + 1])                        # (bn, ob)
        out_ref[:, c * out_bins:(c + 1) * out_bins] += jax.lax.dot(
            w, onehot, preferred_element_type=jnp.float32)


def _fph_binblocked_kernel(scal_ref, xt_ref, lo_ref, hi_ref, *refs,
                           nbins: int, nb_j: int, block_bins: int,
                           block_b: int, block_n: int, use_tpu_prng: bool,
                           has_mask: bool = False):
    """Output-tiled variant of ``_fph_kernel``: grid axis 1 enumerates
    (dimension, bin-block) pairs ``cj = c·nb_j + j`` so each kernel
    instance holds only a (block_b, block_bins) slice of the output in
    VMEM instead of the whole (block_b, d·out_bins) row block — the
    ROADMAP "TPU tiling of the fused hist kernel's output" knob for large
    d·nbins.

    The weight tile is keyed by (seed, i, t) only — regenerating it per
    (c, j) cell trades PRNG recompute for VMEM residency, and keeps the
    implicit weight matrix bit-identical to every other fused path.  x
    arrives TRANSPOSED as (dp, 1, n) so the value row for dimension c is
    selected by the BlockSpec's squeezed leading axis (no traced lane
    slicing in-kernel, and the (1, block_n) block spans the full second-
    minor axis, as the TPU's (8, 128) tiling rule requires for any d);
    lo/hi arrive as (dp, 1, 1) and are selected the same way.
    """
    if has_mask:
        m_ref, out_ref = refs
    else:
        m_ref, (out_ref,) = None, refs
    i = pl.program_id(0)        # B-tile index
    cj = pl.program_id(1)       # flattened (dim, bin-block) index
    t = pl.program_id(2)        # n-tile index (contraction)
    j = cj % nb_j               # bin-block within the dimension

    w = _poisson_tile(scal_ref[0], i, t, pl.num_programs(2),
                      (block_b, block_n), scal_ref[1],
                      block_n, use_tpu_prng,
                      valid=None if m_ref is None else m_ref[...])  # (bB, bn)
    x = xt_ref[...].astype(jnp.float32)                       # (1, bn)

    @pl.when(t == 0)
    def _init():
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    # bin against the TRUE nbins, then localize into this block's window
    idx = _bin_indices(x, lo_ref[...], hi_ref[...], nbins)    # (1, bn)
    mass = finite_mass_mask(x)                                # (1, bn)
    bn = x.shape[1]
    local = (idx - j * block_bins).reshape(bn, 1)
    bins = jax.lax.broadcasted_iota(jnp.int32, (bn, block_bins), 1)
    onehot = (local == bins).astype(jnp.float32) * mass.reshape(bn, 1)
    out_ref[...] += jax.lax.dot(w, onehot,
                                preferred_element_type=jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("B", "nbins", "d_valid", "block_b",
                                    "block_n", "block_bins", "interpret",
                                    "use_tpu_prng"))
def fused_poisson_hist_binblocked_kernel(seed: jax.Array, n_valid: jax.Array,
                                         values_t: jax.Array, lo: jax.Array,
                                         hi: jax.Array, B: int, nbins: int,
                                         d_valid: int, block_bins: int,
                                         block_b: int = 128,
                                         block_n: int = 512,
                                         interpret: bool = True,
                                         use_tpu_prng: bool = False,
                                         mask=None) -> jax.Array:
    """Raw entry for the output-tiled fused hist kernel.

    values_t is the TRANSPOSED (dp, 1, n) value matrix (n pre-padded to
    block_n, dp >= d_valid); lo/hi are (dp, 1, 1).
    ``block_bins`` (a 128 multiple) is the per-instance output window —
    out_bins = nbins padded up to a block_bins multiple, nb_j = out_bins /
    block_bins output blocks per dimension (>= 2 is the interesting
    regime).  Returns (B, d_valid·out_bins) f32; callers reshape to
    (B, d_valid, out_bins) and slice [..., :nbins] (bins past the true
    nbins stay empty: binning is against the true nbins).
    """
    dp, _, n = values_t.shape
    assert B % block_b == 0 and n % block_n == 0, ((B, n), (block_b, block_n))
    assert block_bins % 128 == 0 and block_bins > 0, block_bins
    assert d_valid <= dp, (d_valid, dp)
    out_bins = nbins + (-nbins) % block_bins
    nb_j = out_bins // block_bins

    kern = functools.partial(_fph_binblocked_kernel, nbins=nbins, nb_j=nb_j,
                             block_bins=block_bins, block_b=block_b,
                             block_n=block_n, use_tpu_prng=use_tpu_prng,
                             has_mask=mask is not None)
    scal = jnp.stack([jnp.asarray(seed, jnp.int32),
                      jnp.asarray(n_valid, jnp.int32)])
    grid = (B // block_b, d_valid * nb_j, n // block_n)
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec((None, 1, block_n),
                     lambda i, cj, t: (cj // nb_j, 0, t)),
        pl.BlockSpec((None, 1, 1), lambda i, cj, t: (cj // nb_j, 0, 0)),
        pl.BlockSpec((None, 1, 1), lambda i, cj, t: (cj // nb_j, 0, 0)),
    ]
    operands = [scal, values_t, lo, hi]
    if mask is not None:
        in_specs.append(pl.BlockSpec((1, block_n), lambda i, cj, t: (0, t)))
        operands.append(mask)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_b, block_bins),
                               lambda i, cj, t: (i, cj)),
        out_shape=jax.ShapeDtypeStruct((B, d_valid * out_bins), jnp.float32),
        interpret=interpret,
    )(*operands)


@functools.partial(jax.jit,
                   static_argnames=("B", "nbins", "d_valid", "block_b",
                                    "block_n", "interpret", "use_tpu_prng"))
def fused_poisson_hist_kernel(seed: jax.Array, n_valid: jax.Array,
                              values: jax.Array, lo: jax.Array,
                              hi: jax.Array, B: int, nbins: int,
                              d_valid: int,
                              block_b: int = 128, block_n: int = 512,
                              interpret: bool = True,
                              use_tpu_prng: bool = False,
                              mask=None) -> jax.Array:
    """Matrix-free bootstrap histogram sketch: B per-resample (d, nbins)
    count states under implicit in-kernel Poisson(1) weights.

    values (n, dp) f32 pre-padded on n AND on the lane dim (dp = d padded
    to 128, same lane-width discipline as the other fused kernels; ops.py
    handles both); ``d_valid`` is the real dimension count — padded lanes
    are never contracted.  ``n_valid`` masks weight columns >= the unpadded
    row count, so padded rows (which would otherwise land real mass in bin
    0) contribute nothing.  lo/hi are (1, dp) f32 (padding spans must be
    nonzero).  ``B`` must be a ``block_b`` multiple.  Returns
    (B, d_valid·out_bins) f32 with out_bins = nbins lane-padded to 128 —
    callers reshape to (B, d_valid, out_bins) and slice [..., :nbins].
    """
    n, dp = values.shape
    assert B % block_b == 0 and n % block_n == 0, ((B, n), (block_b, block_n))
    assert d_valid <= dp, (d_valid, dp)
    out_bins = nbins + (-nbins) % 128

    kern = functools.partial(_fph_kernel, nbins=nbins, out_bins=out_bins,
                             d=d_valid, block_b=block_b, block_n=block_n,
                             use_tpu_prng=use_tpu_prng,
                             has_mask=mask is not None)
    scal = jnp.stack([jnp.asarray(seed, jnp.int32),
                      jnp.asarray(n_valid, jnp.int32)])
    grid = (B // block_b, n // block_n)
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec((block_n, dp), lambda i, t: (t, 0)),
        pl.BlockSpec((1, dp), lambda i, t: (0, 0)),
        pl.BlockSpec((1, dp), lambda i, t: (0, 0)),
    ]
    operands = [scal, values, lo, hi]
    if mask is not None:
        in_specs.append(pl.BlockSpec((1, block_n), lambda i, t: (0, t)))
        operands.append(mask)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_b, d_valid * out_bins),
                               lambda i, t: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, d_valid * out_bins), jnp.float32),
        interpret=interpret,
    )(*operands)
