"""Fused weighted-moments Pallas kernels.

``weighted_moments_kernel`` computes, for every bootstrap resample b (a row
of the weight matrix W):

    w_tot[b] = Σ_i W[b,i]
    s1[b,:]  = Σ_i W[b,i] · X[i,:]
    s2[b,:]  = Σ_i W[b,i] · X[i,:]²

in a single pass: the (bB, bn) weight tile is read once from VMEM and feeds
two MXU contractions (against X and X²) plus a VPU row-sum — 3 outputs for
one HBM read of W, which is what makes the B-resample loop compute-bound
instead of bandwidth-bound (DESIGN.md §2).

``fused_poisson_moments_kernel`` goes one step further and is the
*matrix-free* bootstrap hot path: the Poisson(1) weight tile is never read
from HBM at all — it is generated inside the kernel from a counter-based
PRNG keyed by ``(seed, b-tile, n-tile)`` (the same threefry/tile discipline
as kernels/poisson_counts, so the implicit weight matrix is bit-identical
to ``poisson_counts(seed, B, n)`` under matching block shapes) and
immediately contracted.  Peak HBM traffic drops from O(B·n) to O(n·d + B·d)
and the (B, n) matrix never exists anywhere.

Grid: (B/bB, d/bd, n/bn); the contraction axis n is the LAST grid axis so
output tiles are revisited sequentially and accumulated in place.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.poisson_counts.kernel import (_poisson_from_bits,
                                                 _threefry_bits,
                                                 _tpu_prng_bits)


def _ws_kernel(w_ref, x_ref, wtot_ref, s1_ref, s2_ref):
    j = pl.program_id(1)        # d-tile index
    k = pl.program_id(2)        # n-tile index (contraction)

    w = w_ref[...].astype(jnp.float32)       # (bB, bn)
    x = x_ref[...].astype(jnp.float32)       # (bn, bd)

    @pl.when(k == 0)
    def _init_moments():
        s1_ref[...] = jnp.zeros(s1_ref.shape, s1_ref.dtype)
        s2_ref[...] = jnp.zeros(s2_ref.shape, s2_ref.dtype)

    s1_ref[...] += jax.lax.dot(w, x, preferred_element_type=jnp.float32)
    s2_ref[...] += jax.lax.dot(w, x * x, preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_and(j == 0, k == 0))
    def _init_wtot():
        wtot_ref[...] = jnp.zeros(wtot_ref.shape, wtot_ref.dtype)

    @pl.when(j == 0)
    def _acc_wtot():
        wtot_ref[...] += jnp.sum(w, axis=1, keepdims=True)


@functools.partial(jax.jit,
                   static_argnames=("block_b", "block_n", "block_d",
                                    "interpret"))
def weighted_moments_kernel(weights: jax.Array, values: jax.Array,
                            block_b: int = 128, block_n: int = 512,
                            block_d: int = 128, interpret: bool = True):
    """Raw kernel entry: shapes must already be padded to block multiples.

    weights: (B, n) f32;  values: (n, d) f32.
    Returns (w_tot (B, 1), s1 (B, d), s2 (B, d)) — all f32.
    """
    B, n = weights.shape
    n2, d = values.shape
    assert n == n2, (weights.shape, values.shape)
    assert B % block_b == 0 and n % block_n == 0 and d % block_d == 0, (
        (B, n, d), (block_b, block_n, block_d))

    grid = (B // block_b, d // block_d, n // block_n)
    return pl.pallas_call(
        _ws_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, block_n), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_n, block_d), lambda i, j, k: (k, j)),
        ],
        out_specs=[
            pl.BlockSpec((block_b, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((block_b, block_d), lambda i, j, k: (i, j)),
            pl.BlockSpec((block_b, block_d), lambda i, j, k: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, d), jnp.float32),
            jax.ShapeDtypeStruct((B, d), jnp.float32),
        ],
        interpret=interpret,
    )(weights, values)


# ============================================================================
# matrix-free path: in-kernel weight generation + contraction
# ============================================================================
def _poisson_tile(seed, i, k, n_tiles, shape, n_valid, block_n: int,
                  use_tpu_prng: bool, valid=None) -> jax.Array:
    """Poisson(1) weight tile for grid position (i, k), padding masked to 0.

    Identical per-tile seeding to kernels/poisson_counts (same fold-in order,
    same CDF ladder), so the implicit weight matrix equals
    ``poisson_counts(seed, B, n)`` under matching block shapes.
    ``n_tiles`` (the call's tile count on the n axis) only enters the
    hardware-PRNG seed, see ``_tpu_prng_bits``.

    ``valid`` (optional (1, block_n) f32 of exact 0.0/1.0) is this tile's
    slice of an arbitrary validity mask, multiplied in AFTER the prefix
    mask — the kernel-side mirror of ``ops.implicit_weight_tile``'s
    ``valid`` (w·1.0 == w and w·0.0 == 0.0 exactly, so a prefix-shaped
    mask reproduces the ``n_valid`` path bit for bit).
    """
    if use_tpu_prng:
        bits = _tpu_prng_bits(seed, i, k, n_tiles, shape)
    else:
        bits = _threefry_bits(seed, i, k, shape)
    w = _poisson_from_bits(bits)
    col = k * block_n + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    w = jnp.where(col < n_valid, w, 0.0)
    if valid is not None:
        w = w * valid
    return w


def _fpm_kernel(scal_ref, x_ref, *refs, block_b: int, block_n: int,
                use_tpu_prng: bool, dtype=jnp.float32, has_mask: bool = False):
    if has_mask:
        m_ref, (wtot_ref, s1_ref, s2_ref) = refs[0], refs[1:]
    else:
        m_ref, (wtot_ref, s1_ref, s2_ref) = None, refs
    i = pl.program_id(0)        # B-tile index
    j = pl.program_id(1)        # d-tile index
    k = pl.program_id(2)        # n-tile index (contraction)

    w = _poisson_tile(scal_ref[0], i, k, pl.num_programs(2),
                      (block_b, block_n), scal_ref[1],
                      block_n, use_tpu_prng,
                      valid=None if m_ref is None else m_ref[...])
    x = x_ref[...].astype(jnp.float32)       # (bn, bd)

    @pl.when(k == 0)
    def _init_moments():
        s1_ref[...] = jnp.zeros(s1_ref.shape, s1_ref.dtype)
        s2_ref[...] = jnp.zeros(s2_ref.shape, s2_ref.dtype)

    # dtype=bf16: inputs enter the MXU in bf16, accumulators stay f32
    # (bf16-multiply/f32-accumulate).  Weights are small Poisson(1)
    # integers — exact in bf16; x² is squared in f32 then rounded ONCE.
    s1_ref[...] += jax.lax.dot(w.astype(dtype), x.astype(dtype),
                               preferred_element_type=jnp.float32)
    s2_ref[...] += jax.lax.dot(w.astype(dtype), (x * x).astype(dtype),
                               preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_and(j == 0, k == 0))
    def _init_wtot():
        wtot_ref[...] = jnp.zeros(wtot_ref.shape, wtot_ref.dtype)

    @pl.when(j == 0)
    def _acc_wtot():
        wtot_ref[...] += jnp.sum(w, axis=1, keepdims=True)


@functools.partial(jax.jit,
                   static_argnames=("B", "block_b", "block_n", "block_d",
                                    "interpret", "use_tpu_prng", "dtype"))
def fused_poisson_moments_kernel(seed: jax.Array, n_valid: jax.Array,
                                 values: jax.Array, B: int,
                                 block_b: int = 128, block_n: int = 512,
                                 block_d: int = 128, interpret: bool = True,
                                 use_tpu_prng: bool = False,
                                 dtype=jnp.float32, mask=None):
    """Matrix-free bootstrap moments: weights generated in VMEM, never in HBM.

    values: (n, d) f32, pre-padded to block multiples (ops.py handles this);
    ``n_valid`` is the unpadded row count — weight columns >= n_valid are
    masked to zero so ``w_tot`` ignores the padding (padded X rows are zero,
    so s1/s2 are unaffected either way).  ``mask`` (optional (1, n) f32 of
    exact 0.0/1.0, zero-padded like values) multiplies the weight tiles —
    arbitrary interior validity holes, not just a prefix.  ``B`` must be a
    block_b multiple.  Returns (w_tot (B, 1), s1 (B, d), s2 (B, d)) — f32.
    """
    n, d = values.shape
    assert B % block_b == 0 and n % block_n == 0 and d % block_d == 0, (
        (B, n, d), (block_b, block_n, block_d))

    grid = (B // block_b, d // block_d, n // block_n)
    kern = functools.partial(_fpm_kernel, block_b=block_b, block_n=block_n,
                             use_tpu_prng=use_tpu_prng, dtype=dtype,
                             has_mask=mask is not None)
    scal = jnp.stack([jnp.asarray(seed, jnp.int32),
                      jnp.asarray(n_valid, jnp.int32)])
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec((block_n, block_d), lambda i, j, k: (k, j)),
    ]
    operands = [scal, values]
    if mask is not None:
        in_specs.append(pl.BlockSpec((1, block_n), lambda i, j, k: (0, k)))
        operands.append(mask)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((block_b, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((block_b, block_d), lambda i, j, k: (i, j)),
            pl.BlockSpec((block_b, block_d), lambda i, j, k: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, d), jnp.float32),
            jax.ShapeDtypeStruct((B, d), jnp.float32),
        ],
        interpret=interpret,
    )(*operands)


# ============================================================================
# grouped (GROUP BY) variant: per-key accumulator slots, one weight stream
# ============================================================================
def _fpm_grouped_kernel(scal_ref, x_ref, g_ref, *refs, block_b: int,
                        block_n: int, num_groups: int, use_tpu_prng: bool,
                        dtype=jnp.float32, has_mask: bool = False):
    """Keyed segment-reduction of the implicit weight tile: the tile is
    drawn ONCE per grid step (same (seed, b-tile, n-tile) keying as
    ``_fpm_kernel``) and routed into each key's accumulator slot by an
    exact 0/1 key-mask multiply — a static per-key loop of the SAME dot /
    row-sum ops as the ungrouped kernel, so key g's moments are bitwise
    what the ungrouped kernel produces under ``mask = (key == g)``.  No
    (block_b·n) weight tile is ever re-drawn per key and no (block_n,
    num_groups) one-hot is built: the key mask is a (1, block_n) compare
    broadcast into the weight multiply."""
    if has_mask:
        m_ref, (wtot_ref, s1_ref, s2_ref) = refs[0], refs[1:]
    else:
        m_ref, (wtot_ref, s1_ref, s2_ref) = None, refs
    i = pl.program_id(0)        # B-tile index
    k = pl.program_id(1)        # n-tile index (contraction)

    w = _poisson_tile(scal_ref[0], i, k, pl.num_programs(1),
                      (block_b, block_n), scal_ref[1],
                      block_n, use_tpu_prng,
                      valid=None if m_ref is None else m_ref[...])
    gid = g_ref[...]                         # (1, block_n) f32 keys
    x = x_ref[...].astype(jnp.float32)       # (bn, d)

    @pl.when(k == 0)
    def _init():
        wtot_ref[...] = jnp.zeros(wtot_ref.shape, wtot_ref.dtype)
        s1_ref[...] = jnp.zeros(s1_ref.shape, s1_ref.dtype)
        s2_ref[...] = jnp.zeros(s2_ref.shape, s2_ref.dtype)

    x2 = x * x
    for g in range(num_groups):
        wg = w * (gid == g).astype(jnp.float32)          # (bB, bn)
        s1_ref[:, g, :] += jax.lax.dot(
            wg.astype(dtype), x.astype(dtype),
            preferred_element_type=jnp.float32)
        s2_ref[:, g, :] += jax.lax.dot(
            wg.astype(dtype), x2.astype(dtype),
            preferred_element_type=jnp.float32)
        wtot_ref[:, g] += jnp.sum(wg, axis=1)


@functools.partial(jax.jit,
                   static_argnames=("B", "num_groups", "block_b", "block_n",
                                    "block_d", "interpret", "use_tpu_prng",
                                    "dtype"))
def fused_poisson_moments_grouped_kernel(seed: jax.Array, n_valid: jax.Array,
                                         values: jax.Array,
                                         group_ids: jax.Array, B: int,
                                         num_groups: int,
                                         block_b: int = 128,
                                         block_n: int = 512,
                                         block_d: int = 128,
                                         interpret: bool = True,
                                         use_tpu_prng: bool = False,
                                         dtype=jnp.float32, mask=None):
    """GROUP BY bootstrap moments: one implicit Poisson(1) stream, G keyed
    accumulator slots.

    values: (n, d) f32, pre-padded to block multiples; ``group_ids``
    (1, n) f32 of integer keys 0..num_groups-1 (zero-padded — padding
    columns carry zero weight via ``n_valid``/``mask`` so their key is
    irrelevant).  Returns (w_tot (B, G), s1 (B, G, d), s2 (B, G, d)).

    VMEM note: the s1/s2 accumulator blocks are (block_b, G, d) — G scales
    the resident accumulators, so large G·d wants a smaller ``block_b``
    (same escape hatch as weighted_hist's ``block_bins``; see ROADMAP
    Known modeling limits)."""
    n, d = values.shape
    assert B % block_b == 0 and n % block_n == 0 and d % block_d == 0, (
        (B, n, d), (block_b, block_n, block_d))
    assert group_ids.shape == (1, n), group_ids.shape

    grid = (B // block_b, n // block_n)
    G = num_groups
    kern = functools.partial(_fpm_grouped_kernel, block_b=block_b,
                             block_n=block_n, num_groups=G,
                             use_tpu_prng=use_tpu_prng, dtype=dtype,
                             has_mask=mask is not None)
    scal = jnp.stack([jnp.asarray(seed, jnp.int32),
                      jnp.asarray(n_valid, jnp.int32)])
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec((block_n, d), lambda i, k: (k, 0)),
        pl.BlockSpec((1, block_n), lambda i, k: (0, k)),
    ]
    operands = [scal, values, group_ids]
    if mask is not None:
        in_specs.append(pl.BlockSpec((1, block_n), lambda i, k: (0, k)))
        operands.append(mask)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((block_b, G), lambda i, k: (i, 0)),
            pl.BlockSpec((block_b, G, d), lambda i, k: (i, 0, 0)),
            pl.BlockSpec((block_b, G, d), lambda i, k: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, G), jnp.float32),
            jax.ShapeDtypeStruct((B, G, d), jnp.float32),
            jax.ShapeDtypeStruct((B, G, d), jnp.float32),
        ],
        interpret=interpret,
    )(*operands)


# ============================================================================
# streaming variant: double-buffered async HBM->VMEM copies on the n axis
# ============================================================================
def _fpm_stream_kernel(scal_ref, x_hbm_ref, *refs, block_b: int,
                       block_n: int, nt: int, use_tpu_prng: bool,
                       dtype=jnp.float32, has_mask: bool = False):
    """emit_pipeline-style n-axis streaming: x lives in HBM (memory_space
    ANY); each (block_n, d) tile is DMA'd into one slot of a 2-deep VMEM
    scratch while the other slot's tile is being contracted, so the HBM
    load of n-tile t+1 overlaps compute on tile t.  Weight keying, masking
    and f32 accumulation order are identical to ``_fpm_kernel`` with the
    n axis as the last grid dimension — outputs are bit-identical."""
    if has_mask:
        m_hbm_ref = refs[0]
        wtot_ref, s1_ref, s2_ref, xs, xsem, ms, msem = refs[1:]
    else:
        m_hbm_ref = None
        wtot_ref, s1_ref, s2_ref, xs, xsem = refs
    i = pl.program_id(0)        # B-tile index

    wtot_ref[...] = jnp.zeros(wtot_ref.shape, wtot_ref.dtype)
    s1_ref[...] = jnp.zeros(s1_ref.shape, s1_ref.dtype)
    s2_ref[...] = jnp.zeros(s2_ref.shape, s2_ref.dtype)

    def x_dma(slot, t):
        return pltpu.make_async_copy(
            x_hbm_ref.at[pl.ds(t * block_n, block_n), :],
            xs.at[slot], xsem.at[slot])

    def m_dma(slot, t):
        return pltpu.make_async_copy(
            m_hbm_ref.at[:, pl.ds(t * block_n, block_n)],
            ms.at[slot], msem.at[slot])

    x_dma(0, 0).start()
    if has_mask:
        m_dma(0, 0).start()

    def body(t, _):
        slot = jax.lax.rem(t, 2)
        nxt = jax.lax.rem(t + 1, 2)

        @pl.when(t + 1 < nt)
        def _prefetch():
            x_dma(nxt, t + 1).start()
            if has_mask:
                m_dma(nxt, t + 1).start()

        x_dma(slot, t).wait()
        valid = None
        if has_mask:
            m_dma(slot, t).wait()
            valid = ms[slot]
        w = _poisson_tile(scal_ref[0], i, t, nt, (block_b, block_n),
                          scal_ref[1], block_n, use_tpu_prng, valid=valid)
        x = xs[slot].astype(jnp.float32)
        s1_ref[...] += jax.lax.dot(w.astype(dtype), x.astype(dtype),
                                   preferred_element_type=jnp.float32)
        s2_ref[...] += jax.lax.dot(w.astype(dtype), (x * x).astype(dtype),
                                   preferred_element_type=jnp.float32)
        wtot_ref[...] += jnp.sum(w, axis=1, keepdims=True)
        return ()

    jax.lax.fori_loop(0, nt, body, ())


@functools.partial(jax.jit,
                   static_argnames=("B", "block_b", "block_n", "block_d",
                                    "interpret", "use_tpu_prng", "dtype"))
def fused_poisson_moments_stream_kernel(seed: jax.Array, n_valid: jax.Array,
                                        values: jax.Array, B: int,
                                        block_b: int = 128,
                                        block_n: int = 512,
                                        block_d: int = 128,
                                        interpret: bool = True,
                                        use_tpu_prng: bool = False,
                                        dtype=jnp.float32, mask=None):
    """Double-buffered streaming entry: same contract (and bit-identical
    outputs) as ``fused_poisson_moments_kernel``, but x (and the optional
    mask) stay in HBM and the kernel overlaps each tile's DMA with the
    previous tile's contraction — the on-device mirror of the host-side
    driver in core/streaming.py.  The full lane-padded d is kept resident
    (``block_d`` only asserts the lane padding), so VMEM holds
    2·block_n·d + the (block_b, d) accumulators."""
    n, d = values.shape
    assert B % block_b == 0 and n % block_n == 0 and d % block_d == 0, (
        (B, n, d), (block_b, block_n, block_d))
    nt = n // block_n

    kern = functools.partial(_fpm_stream_kernel, block_b=block_b,
                             block_n=block_n, nt=nt,
                             use_tpu_prng=use_tpu_prng, dtype=dtype,
                             has_mask=mask is not None)
    scal = jnp.stack([jnp.asarray(seed, jnp.int32),
                      jnp.asarray(n_valid, jnp.int32)])
    in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pl.ANY)]
    operands = [scal, values]
    scratch = [pltpu.VMEM((2, block_n, d), jnp.float32),
               pltpu.SemaphoreType.DMA((2,))]
    if mask is not None:
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        operands.append(mask)
        scratch += [pltpu.VMEM((2, 1, block_n), jnp.float32),
                    pltpu.SemaphoreType.DMA((2,))]
    return pl.pallas_call(
        kern,
        grid=(B // block_b,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_b, d), lambda i: (i, 0)),
            pl.BlockSpec((block_b, d), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, d), jnp.float32),
            jax.ShapeDtypeStruct((B, d), jnp.float32),
        ],
        scratch_shapes=scratch,
        interpret=interpret,
    )(*operands)
