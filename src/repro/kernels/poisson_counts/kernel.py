"""In-kernel PRNG → Poisson(1) bootstrap-weight Pallas kernel.

The (B, n) Poisson weight matrix of the distributed bootstrap never has to
round-trip through HBM: each VMEM tile seeds the TPU PRNG with
(seed, tile_i, tile_j), draws uniform bits, and converts them to Poisson(1)
counts by CDF inversion (P(K > 9) < 1.1e-7, so a 10-term ladder is exact to
float32 resolution).  Paired with weighted_stats this makes resampling a
pure compute kernel — generate weights in VMEM, contract, discard.

Seeding is per-tile: (seed, tile_i, tile_j) fully determines a tile, so a
fixed (seed, block config) reproduces the same matrix call-to-call, and
different shards/steps decorrelate by folding their id into ``seed``
before the call (as core/distributed.py does at the jax.random level).
The TPU PRNG takes at most two seed words, so the hardware path seeds with
(seed, tile_i·n_tiles + tile_j), with n_tiles the call's static tile count
on the n axis — still one stream per (seed, tile) within a call.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Poisson(1) CDF ladder: counts = #{thresholds < u}.
_CDF = []
_acc = 0.0
for _k in range(10):
    _acc += math.exp(-1.0) / math.factorial(_k)
    _CDF.append(_acc)


def _threefry_bits(seed, i, j, shape):
    """Tile-local counter-based bits for interpret/CPU fallback semantics."""
    key = jax.random.fold_in(jax.random.fold_in(
        jax.random.PRNGKey(seed), i), j)
    return jax.random.bits(key, shape, dtype=jnp.uint32)


def _tpu_prng_bits(seed, i, j, n_tiles, shape):
    """Hardware-PRNG bits for tile (i, j) of a grid with ``n_tiles`` tiles
    on the j axis.  Mosaic seeds from at most two words, so the tile enters
    as the single index i·n_tiles + j, injective within a call."""
    pltpu.prng_seed(seed, i * n_tiles + j)
    return pltpu.bitcast(pltpu.prng_random_bits(shape), jnp.uint32)


def _poisson_from_bits(bits: jax.Array) -> jax.Array:
    # the shifted bits are < 2^24, so going through int32 (Mosaic has no
    # uint32 -> f32 cast) is exact and leaves the threefry path unchanged.
    u = ((bits >> jnp.uint32(8)).astype(jnp.int32).astype(jnp.float32)
         * jnp.float32(1.0 / (1 << 24)))
    counts = jnp.zeros(bits.shape, jnp.float32)
    for c in _CDF:
        counts += (u > jnp.float32(c)).astype(jnp.float32)
    return counts


def _pc_kernel(seed_ref, out_ref, *, use_tpu_prng: bool):
    i = pl.program_id(0)
    j = pl.program_id(1)
    if use_tpu_prng:
        bits = _tpu_prng_bits(seed_ref[0], i, j, pl.num_programs(1),
                              out_ref.shape)
    else:
        bits = _threefry_bits(seed_ref[0], i, j, out_ref.shape)
    out_ref[...] = _poisson_from_bits(bits)


@functools.partial(jax.jit,
                   static_argnames=("B", "n", "block_b", "block_n",
                                    "interpret", "use_tpu_prng"))
def poisson_counts_kernel(seed: jax.Array, B: int, n: int,
                          block_b: int = 128, block_n: int = 512,
                          interpret: bool = True,
                          use_tpu_prng: bool = False) -> jax.Array:
    """(B, n) Poisson(1) weights from a scalar int32 seed.

    Shapes must be pre-padded to block multiples (ops.py handles this).
    """
    assert B % block_b == 0 and n % block_n == 0
    grid = (B // block_b, n // block_n)
    kern = functools.partial(_pc_kernel, use_tpu_prng=use_tpu_prng)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec((block_b, block_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((B, n), jnp.float32),
        interpret=interpret,
    )(seed.reshape((1,)).astype(jnp.int32))
