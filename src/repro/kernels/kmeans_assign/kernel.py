"""Fused k-means assignment+accumulate Pallas kernels.

``kmeans_assign_kernel`` performs one weighted Lloyd assignment pass: each
(bn, d) tile of x is read into VMEM once, distances to the resident (k, d)
centroid block are computed on the MXU, the argmin/one-hot assignment lives
only tile-locally, and the weighted per-cluster (sums, counts, inertia) are
accumulated in place — so neither the (n, k) distance matrix nor the (n, k)
one-hot ever exists in HBM.

``fused_poisson_kmeans_kernel`` is the matrix-free bootstrap-over-k-means
hot path: the Poisson(1) resample weight tile is generated *inside* the
kernel from the same counter-based PRNG tile discipline as
kernels/weighted_stats.fused_poisson_moments (keyed by (seed, b-tile,
n-tile), so the implicit weight matrix is bit-identical to
``poisson_counts(seed, B, n)`` under matching blocks) and contracted against
the tile-local assignment — the (B, n) weight matrix never exists either,
and peak live state is the O(B·k·d) per-resample accumulators.

Grids: ``(n/bn,)`` for the single-state pass; ``(B/bB, n/bn)`` for the
fused bootstrap pass with the contraction axis n LAST so output tiles are
revisited sequentially and accumulated in place (same discipline as
weighted_stats).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.weighted_stats.kernel import _poisson_tile

_F32_MAX = float(jnp.finfo(jnp.float32).max)


def _assign_tile(x: jax.Array, cent: jax.Array, k_valid: int):
    """Tile-local assignment: x (bn, d) against cent (k, d).

    Returns (one-hot A (bn, k) f32, min-d² (bn,) f32).  d² is clamped at 0
    (f32 cancellation in the expanded form can go slightly negative for
    points at/near a centroid); centroid rows >= ``k_valid`` (sublane
    padding) are masked to +inf so they never win the argmin.

    Shared verbatim by the Pallas kernels and the jnp scan lowering so the
    two lowerings accumulate identical tile values in identical order.
    """
    x = x.astype(jnp.float32)
    cent = cent.astype(jnp.float32)
    xx = jnp.sum(x * x, -1, keepdims=True)                   # (bn, 1)
    cc = jnp.sum(cent * cent, -1)                            # (k,)
    xc = jax.lax.dot_general(x, cent, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    d2 = jnp.maximum(xx - 2.0 * xc + cc[None, :], 0.0)       # (bn, k)
    col = jax.lax.broadcasted_iota(jnp.int32, d2.shape, 1)
    if k_valid < cent.shape[0]:
        d2 = jnp.where(col < k_valid, d2, _F32_MAX)
    a = jnp.argmin(d2, -1)
    assign = (col == a[:, None]).astype(jnp.float32)         # (bn, k)
    return assign, jnp.min(d2, -1)


# ============================================================================
# single-state weighted assignment pass
# ============================================================================
def _ka_kernel(x_ref, w_ref, c_ref, sums_ref, counts_ref, inertia_ref, *,
               k_valid: int):
    t = pl.program_id(0)        # n-tile index (contraction)

    x = x_ref[...].astype(jnp.float32)       # (bn, dp)
    w = w_ref[...].astype(jnp.float32)       # (bn, 1); padded rows are 0
    assign, min_d2 = _assign_tile(x, c_ref[...], k_valid)

    @pl.when(t == 0)
    def _init():
        sums_ref[...] = jnp.zeros(sums_ref.shape, sums_ref.dtype)
        counts_ref[...] = jnp.zeros(counts_ref.shape, counts_ref.dtype)
        inertia_ref[...] = jnp.zeros(inertia_ref.shape, inertia_ref.dtype)

    wx = x * w                                               # (bn, dp)
    sums_ref[...] += jax.lax.dot_general(
        assign, wx, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                  # (kp, dp)
    counts_ref[...] += jax.lax.dot_general(
        assign, w, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                  # (kp, 1)
    inertia_ref[...] += jnp.sum(w[:, 0] * min_d2)


@functools.partial(jax.jit,
                   static_argnames=("k_valid", "block_n", "interpret"))
def kmeans_assign_kernel(values: jax.Array, weights: jax.Array,
                         centroids: jax.Array, k_valid: int,
                         block_n: int = 512, interpret: bool = True):
    """Raw kernel entry: shapes must already be padded to block multiples.

    values (n, dp) f32; weights (n, 1) f32 (padded rows zeroed); centroids
    (kp, dp) f32 with real rows < ``k_valid``.  Returns
    (sums (kp, dp), counts (kp, 1), inertia (1, 1)) — all f32.
    """
    n, dp = values.shape
    kp = centroids.shape[0]
    assert n % block_n == 0, (n, block_n)
    assert centroids.shape[1] == dp, (centroids.shape, values.shape)

    kern = functools.partial(_ka_kernel, k_valid=k_valid)
    return pl.pallas_call(
        kern,
        grid=(n // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, dp), lambda t: (t, 0)),
            pl.BlockSpec((block_n, 1), lambda t: (t, 0)),
            pl.BlockSpec((kp, dp), lambda t: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((kp, dp), lambda t: (0, 0)),
            pl.BlockSpec((kp, 1), lambda t: (0, 0)),
            pl.BlockSpec((1, 1), lambda t: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((kp, dp), jnp.float32),
            jax.ShapeDtypeStruct((kp, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(values, weights, centroids)


# ============================================================================
# matrix-free bootstrap path: in-kernel weight generation + assignment
# ============================================================================
def _fpk_kernel(scal_ref, x_ref, c_ref, *refs,
                k_valid: int, block_b: int, block_n: int, dp: int,
                use_tpu_prng: bool, has_mask: bool = False):
    if has_mask:
        m_ref, (sums_ref, counts_ref, inertia_ref) = refs[0], refs[1:]
    else:
        m_ref, (sums_ref, counts_ref, inertia_ref) = None, refs
    i = pl.program_id(0)        # B-tile index
    t = pl.program_id(1)        # n-tile index (contraction)

    w = _poisson_tile(scal_ref[0], i, t, pl.num_programs(1),
                      (block_b, block_n), scal_ref[1],
                      block_n, use_tpu_prng,
                      valid=None if m_ref is None else m_ref[...])  # (bB, bn)
    x = x_ref[...].astype(jnp.float32)                       # (bn, dp)
    assign, min_d2 = _assign_tile(x, c_ref[...], k_valid)    # (bn, kp)

    @pl.when(t == 0)
    def _init():
        sums_ref[...] = jnp.zeros(sums_ref.shape, sums_ref.dtype)
        counts_ref[...] = jnp.zeros(counts_ref.shape, counts_ref.dtype)
        inertia_ref[...] = jnp.zeros(inertia_ref.shape, inertia_ref.dtype)

    counts_ref[...] += jax.lax.dot(w, assign,
                                   preferred_element_type=jnp.float32)
    inertia_ref[...] += jax.lax.dot(w, min_d2[:, None],
                                    preferred_element_type=jnp.float32)
    # per-cluster masked moment: sums[:, j·dp:(j+1)·dp] is cluster j's (B, d)
    # weighted point sum — kp lane-aligned dots instead of a (bn, kp·dp)
    # VMEM blowup (k is small; the (B, n) weight tile is reused for all kp).
    for j in range(assign.shape[1]):
        sums_ref[:, j * dp:(j + 1) * dp] += jax.lax.dot(
            w, assign[:, j:j + 1] * x, preferred_element_type=jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("B", "k_valid", "block_b", "block_n",
                                    "interpret", "use_tpu_prng"))
def fused_poisson_kmeans_kernel(seed: jax.Array, n_valid: jax.Array,
                                values: jax.Array, centroids: jax.Array,
                                B: int, k_valid: int,
                                block_b: int = 128, block_n: int = 512,
                                interpret: bool = True,
                                use_tpu_prng: bool = False,
                                mask=None):
    """Matrix-free bootstrap-over-k-means: B per-resample (sums, counts,
    inertia) states under implicit in-kernel Poisson(1) weights.

    values (n, dp) f32 pre-padded (ops.py handles it); ``n_valid`` masks
    weight columns >= the unpadded row count (padded x rows are zero, so the
    assignment of masked rows contributes nothing once their weight is 0).
    Returns (sums (B, kp·dp), counts (B, kp), inertia (B, 1)) — all f32;
    ``B`` must be a ``block_b`` multiple.
    """
    n, dp = values.shape
    kp = centroids.shape[0]
    assert B % block_b == 0 and n % block_n == 0, ((B, n), (block_b, block_n))
    assert centroids.shape[1] == dp, (centroids.shape, values.shape)

    kern = functools.partial(_fpk_kernel, k_valid=k_valid, block_b=block_b,
                             block_n=block_n, dp=dp,
                             use_tpu_prng=use_tpu_prng,
                             has_mask=mask is not None)
    scal = jnp.stack([jnp.asarray(seed, jnp.int32),
                      jnp.asarray(n_valid, jnp.int32)])
    grid = (B // block_b, n // block_n)
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec((block_n, dp), lambda i, t: (t, 0)),
        pl.BlockSpec((kp, dp), lambda i, t: (0, 0)),
    ]
    operands = [scal, values, centroids]
    if mask is not None:
        in_specs.append(pl.BlockSpec((1, block_n), lambda i, t: (0, t)))
        operands.append(mask)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((block_b, kp * dp), lambda i, t: (i, 0)),
            pl.BlockSpec((block_b, kp), lambda i, t: (i, 0)),
            pl.BlockSpec((block_b, 1), lambda i, t: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, kp * dp), jnp.float32),
            jax.ShapeDtypeStruct((B, kp), jnp.float32),
            jax.ShapeDtypeStruct((B, 1), jnp.float32),
        ],
        interpret=interpret,
    )(*operands)
