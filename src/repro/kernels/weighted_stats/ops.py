"""jit'd public wrappers for weighted_stats: padding + platform dispatch.

On TPU the Pallas kernels run compiled; everywhere else they run in
interpret mode (tests) or fall back to jnp paths (fast CPU path for the
benchmarks — interpret mode is a correctness tool, not a perf tool).

Two entry points:

* ``weighted_moments``       — contract an explicit (B, n) weight matrix.
* ``fused_poisson_moments``  — matrix-free: Poisson(1) weights are generated
  from a counter-based PRNG *inside* the contraction (Pallas kernel on TPU,
  a tile-by-tile ``lax.scan`` on CPU) so the (B, n) matrix never
  materializes; peak live memory is O(B·block_n + B·d).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels.poisson_counts.kernel import (_poisson_from_bits,
                                                 _threefry_bits)
from repro.kernels.weighted_stats.kernel import (
    fused_poisson_moments_grouped_kernel, fused_poisson_moments_kernel,
    fused_poisson_moments_stream_kernel, weighted_moments_kernel)
from repro.kernels.weighted_stats.ref import weighted_moments_ref


def _pad_to(x: jax.Array, mult: int, axis: int,
            value: float = 0.0) -> jax.Array:
    """Zero-pad (or ``value``-pad) ``axis`` up to a multiple of ``mult``.
    Shared by the kernel ops wrappers (weighted_hist imports it too)."""
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def weight_tile_blocks(B: int, n: int, block_b: int = 128,
                       block_n: int = 512) -> Tuple[int, int]:
    """Clamped (block_b, block_n) for the (implicit) weight-matrix tiling —
    hardware-aligned defaults that also stay small for tiny test shapes.

    EVERY fused path (fused_poisson_moments, fused_poisson_kmeans,
    fused_poisson_hist, poisson_counts, implicit_weights) must pick its
    weight-tile blocks through THIS helper: the PRNG is keyed per
    (seed, b-tile, n-tile), so two paths agree bit-for-bit on the implicit
    weight matrix — the common-random-numbers / delta-maintenance
    contract — only if they agree on this clamp.
    """
    return min(block_b, max(8, B)), min(block_n, max(128, n))


def _pick_blocks(B: int, n: int, d: int) -> Tuple[int, int, int]:
    """Tiles for the explicit-W kernel (same clamp + fixed lane width).

    VMEM budget (f32): bB·bn (W) + bn·bd (X, X²) + 2·bB·bd (acc) — with the
    defaults 128·512 + 512·128 + 2·128·128 floats ≈ 0.7 MB, far under the
    ~16 MB/core VMEM of v5e, leaving room for double buffering.
    """
    bb, bn = weight_tile_blocks(B, n)
    bd = 128                    # lane width: fixed regardless of d
    return bb, bn, bd


def weighted_moments(weights: jax.Array, values: jax.Array,
                     backend: str | None = None):
    """weights (B, n) × values (n, d) -> (w_tot (B,), s1 (B,d), s2 (B,d)).

    backend: None = auto (pallas on TPU, jnp elsewhere), "pallas",
    "pallas_interpret", "jnp".
    """
    if values.ndim == 1:
        values = values[:, None]
    B, n = weights.shape
    d = values.shape[1]

    if backend is None:
        backend = ("pallas" if jax.default_backend() == "tpu" else "jnp")

    if backend == "jnp":
        w_tot, s1, s2 = weighted_moments_ref(weights, values)
        return w_tot[:, 0], s1, s2

    interpret = backend != "pallas"
    bb, bn, bd = _pick_blocks(B, n, d)
    wp = _pad_to(_pad_to(weights.astype(jnp.float32), bb, 0), bn, 1)
    xp = _pad_to(_pad_to(values.astype(jnp.float32), bn, 0), bd, 1)
    w_tot, s1, s2 = weighted_moments_kernel(
        wp, xp, block_b=bb, block_n=bn, block_d=bd, interpret=interpret)
    return w_tot[:B, 0], s1[:B, :d], s2[:B, :d]


# ============================================================================
# matrix-free path
# ============================================================================
def implicit_weight_tile(seed, n_valid, t, B: int, block_b: int,
                         block_n: int, valid=None) -> jax.Array:
    """The (B, block_n) implicit Poisson(1) weight tile at n-tile ``t``:
    the scan-lowering analogue of the kernels' in-VMEM per-tile draw (same
    threefry fold-in order, same CDF ladder, columns >= ``n_valid`` masked
    to 0).

    ``valid`` (optional (block_n,) f32 of exact 0.0/1.0) is this tile's
    slice of an arbitrary validity mask — interior holes from failed
    shards, not just a prefix.  The tile is multiplied by it AFTER the
    prefix mask; since w·1.0 == w and w·0.0 == 0.0 exactly in f32, a
    prefix-shaped ``valid`` reproduces the ``n_valid`` masking bit for bit.

    EVERY matrix-free scan lowering (fused moments here,
    kernels/kmeans_assign's fused bootstrap) must draw its weights through
    this helper — it is what keeps the implicit matrix bit-identical to
    ``implicit_weights(seed, B, n)`` across statistics, which the delta-
    maintenance / common-random-numbers discipline relies on."""
    def one(i):
        bits = _threefry_bits(seed, i, t, (block_b, block_n))
        return _poisson_from_bits(bits)
    w = jax.vmap(one)(jnp.arange(B // block_b)).reshape(B, block_n)
    cols = jnp.arange(block_n, dtype=jnp.int32)
    mask = (t * block_n + cols) < n_valid
    w = jnp.where(mask[None, :], w, 0.0)
    if valid is not None:
        w = w * valid[None, :]
    return w


@functools.partial(jax.jit, static_argnames=("B", "block_b", "block_n",
                                             "dtype"))
def _fused_scan(seed, n_valid, xp, B, block_b, block_n,
                dtype=jnp.float32, maskp=None):
    """CPU/matrix-free oracle of the fused kernel: same tile decomposition,
    same per-tile threefry bits and CDF ladder, same k-sequential f32
    accumulation — but expressed as a jnp scan so XLA:CPU runs it at full
    speed.  Peak live memory per step is (B, block_n).

    ``dtype=bfloat16`` is the reduced-precision input study: the weight
    tile (small Poisson(1) integers, exactly representable in bf16) and x
    enter the contraction in bf16 while the s1/s2 accumulators stay f32 —
    i.e. the MXU bf16-multiply/f32-accumulate mode.  x² is squared in f32
    FIRST and then rounded once to bf16 (squaring an already-rounded bf16
    x would double the relative error of the second moment)."""
    n, d = xp.shape
    nb_n = n // block_n
    xc = xp.reshape(nb_n, block_n, d)
    # ``maskp=None`` keeps the pre-mask jaxpr byte-identical (None is a
    # valid empty-pytree jit operand, so one jitted function serves both).
    maskc = None if maskp is None else maskp.reshape(nb_n, block_n)

    def body(carry, k):
        w_tot, s1, s2 = carry
        w = implicit_weight_tile(seed, n_valid, k, B, block_b, block_n,
                                 valid=None if maskc is None else maskc[k])
        xk = xc[k]
        return (w_tot + jnp.sum(w, axis=1, keepdims=True),
                s1 + jax.lax.dot(w.astype(dtype), xk.astype(dtype),
                                 preferred_element_type=jnp.float32),
                s2 + jax.lax.dot(w.astype(dtype),
                                 (xk * xk).astype(dtype),
                                 preferred_element_type=jnp.float32)), None

    init = (jnp.zeros((B, 1), jnp.float32),
            jnp.zeros((B, d), jnp.float32),
            jnp.zeros((B, d), jnp.float32))
    (w_tot, s1, s2), _ = jax.lax.scan(body, init,
                                      jnp.arange(nb_n, dtype=jnp.int32))
    return w_tot, s1, s2


@functools.partial(jax.jit, static_argnames=("B", "block_b", "block_n",
                                             "num_groups", "dtype"))
def _grouped_fused_scan(seed, n_valid, xp, gp, B, block_b, block_n,
                        num_groups, dtype=jnp.float32, maskp=None):
    """GROUP BY scan lowering: one implicit weight tile per step, keyed
    into ``num_groups`` accumulator slots by an exact 0/1 key-mask
    multiply.  A static per-key loop applies the SAME dot / row-sum ops as
    ``_fused_scan`` to ``w * (gid == g)``, so slot g is bitwise what
    ``_fused_scan`` produces under ``maskp = (gid == g)`` (0/1 mask
    multiplies compose exactly: (w·valid)·keymask ≡ w·(valid·keymask)).
    Peak live memory per step stays (B, block_n) — the (n, G) one-hot
    never materializes."""
    n, d = xp.shape
    nb_n = n // block_n
    xc = xp.reshape(nb_n, block_n, d)
    gc = gp.reshape(nb_n, block_n)
    maskc = None if maskp is None else maskp.reshape(nb_n, block_n)

    def body(carry, k):
        w_tot, s1, s2 = carry
        w = implicit_weight_tile(seed, n_valid, k, B, block_b, block_n,
                                 valid=None if maskc is None else maskc[k])
        xk = xc[k]
        xk2 = xk * xk
        gid = gc[k]
        wt_new, s1_new, s2_new = [], [], []
        for g in range(num_groups):
            wg = w * (gid == g).astype(jnp.float32)[None, :]
            wt_new.append(w_tot[:, g] + jnp.sum(wg, axis=1))
            s1_new.append(s1[:, g] + jax.lax.dot(
                wg.astype(dtype), xk.astype(dtype),
                preferred_element_type=jnp.float32))
            s2_new.append(s2[:, g] + jax.lax.dot(
                wg.astype(dtype), xk2.astype(dtype),
                preferred_element_type=jnp.float32))
        return (jnp.stack(wt_new, axis=1), jnp.stack(s1_new, axis=1),
                jnp.stack(s2_new, axis=1)), None

    init = (jnp.zeros((B, num_groups), jnp.float32),
            jnp.zeros((B, num_groups, d), jnp.float32),
            jnp.zeros((B, num_groups, d), jnp.float32))
    (w_tot, s1, s2), _ = jax.lax.scan(body, init,
                                      jnp.arange(nb_n, dtype=jnp.int32))
    return w_tot, s1, s2


def fused_poisson_moments(seed, values: jax.Array, B: int,
                          backend: str | None = None,
                          block_b: int = 128, block_n: int = 512,
                          n_valid=None, dtype=jnp.float32,
                          valid_mask=None, stream: bool = False,
                          group_ids=None, num_groups: int | None = None):
    """Matrix-free bootstrap moments from an int32 seed (no weight matrix).

    values (n, d) or (n,) -> (w_tot (B,), s1 (B,d), s2 (B,d)) where the
    implicit weights are Poisson(1), keyed per (block_b, block_n) tile by
    (seed, b-tile, n-tile) — bit-identical to
    ``poisson_counts(seed, B, n)`` with the same blocks (see
    ``implicit_weights``).

    ``n_valid`` (traced scalar, default n) masks weight columns >= n_valid
    to zero — callers that pass pre-padded values (e.g. the chunked
    bootstrap's ragged tail) use it so ``w_tot`` ignores padding.

    ``valid_mask`` (traced (n,) f32 of exact 0.0/1.0, default all-valid)
    is the ARBITRARY-mask generalization: the implicit weight tile is
    multiplied by the matching mask slice, so interior holes (failed
    shards, ft/) run on the fused path.  A prefix-shaped mask is bitwise
    identical to the equivalent ``n_valid`` (multiplying f32 by exactly
    1.0/0.0 is exact); both may be combined.

    ``stream=True`` (Pallas backends) routes through the double-buffered
    DMA kernel: x stays in HBM/ANY memory and each (block_n, d) tile is
    async-copied into a 2-slot VMEM scratch while the previous tile is
    contracted — emit_pipeline-style overlap of the n-axis loads, same
    (seed, b-tile, n-tile) weight keying, bit-identical outputs.

    ``dtype`` is the contraction input precision (ROADMAP bf16 study):
    ``jnp.bfloat16`` feeds w and x to the dots in bf16 with f32
    accumulators — halves the X-side HBM/VMEM traffic on TPU for ~1e-3
    relative moment error (weights are small exact integers; see
    benchmarks/kernelbench.run_bootstrap for the quantified cv error).

    ``group_ids`` (traced (n,) integer keys 0..num_groups-1, float storage
    is fine) switches on the GROUP BY path: the SAME implicit weight
    stream is segment-reduced into ``num_groups`` keyed accumulator slots
    per tile (exact 0/1 key-mask multiplies — no (n, G) one-hot), and the
    outputs gain a G axis: (w_tot (B, G), s1 (B, G, d), s2 (B, G, d)).
    Slot g is BITWISE equal to the ungrouped call under
    ``valid_mask = (group_ids == g)`` — i.e. to bootstrapping key g's rows
    alone under the same seed (common random numbers across keys).

    backend: None = auto (pallas on TPU, scan elsewhere), "pallas",
    "pallas_interpret", "scan".
    """
    if values.ndim == 1:
        values = values[:, None]
    n, d = values.shape
    if backend is None:
        backend = "pallas" if jax.default_backend() == "tpu" else "scan"
    if n_valid is None:
        n_valid = n

    bb, bn = weight_tile_blocks(B, n, block_b, block_n)
    Bp = B + (-B) % bb
    seed = jnp.asarray(seed, jnp.int32)
    n_valid = jnp.asarray(n_valid, jnp.int32)
    dtype = jnp.dtype(dtype)
    xp = _pad_to(values.astype(jnp.float32), bn, 0)
    mp = None
    if valid_mask is not None:
        mp = _pad_to(jnp.asarray(valid_mask, jnp.float32).reshape(n), bn, 0)

    if group_ids is not None:
        if num_groups is None or int(num_groups) < 1:
            raise ValueError("group_ids requires num_groups >= 1, got "
                             f"{num_groups!r}")
        if stream:
            raise ValueError("stream=True is not supported with group_ids "
                             "(the grouped kernel keeps its G·d "
                             "accumulators resident instead)")
        G = int(num_groups)
        # padding columns keep key 0 — their weights are already exactly
        # zero via the n_valid prefix mask / zero-padded valid_mask.
        gp = _pad_to(jnp.asarray(group_ids, jnp.float32).reshape(n), bn, 0)
        if backend == "scan":
            w_tot, s1, s2 = _grouped_fused_scan(seed, n_valid, xp, gp, Bp,
                                                bb, bn, G, dtype=dtype,
                                                maskp=mp)
            return w_tot[:B], s1[:B], s2[:B]
        bd = 128
        xp = _pad_to(xp, bd, 1)
        w_tot, s1, s2 = fused_poisson_moments_grouped_kernel(
            seed, n_valid, xp, gp[None, :], Bp, G,
            block_b=bb, block_n=bn, block_d=bd,
            interpret=(backend != "pallas"),
            use_tpu_prng=(backend == "pallas"), dtype=dtype,
            mask=None if mp is None else mp[None, :])
        return w_tot[:B], s1[:B, :, :d], s2[:B, :, :d]

    if backend == "scan":
        w_tot, s1, s2 = _fused_scan(seed, n_valid, xp, Bp, bb, bn,
                                    dtype=dtype, maskp=mp)
        return w_tot[:B, 0], s1[:B], s2[:B]

    bd = 128                    # lane width: fixed regardless of d
    xp = _pad_to(xp, bd, 1)
    kern = (fused_poisson_moments_stream_kernel if stream
            else fused_poisson_moments_kernel)
    w_tot, s1, s2 = kern(
        seed, n_valid, xp, Bp,
        block_b=bb, block_n=bn, block_d=bd,
        interpret=(backend != "pallas"),
        use_tpu_prng=(backend == "pallas"), dtype=dtype,
        mask=None if mp is None else mp[None, :])
    return w_tot[:B, 0], s1[:B, :d], s2[:B, :d]


@functools.partial(jax.jit, static_argnames=("B", "n", "block_b", "block_n"))
def implicit_weights(seed, B: int, n: int, block_b: int = 128,
                     block_n: int = 512) -> jax.Array:
    """Materialize the (B, n) weight matrix the threefry-lowered fused paths
    ("scan", "pallas_interpret") use implicitly: same per-tile fold-in and
    CDF ladder, expressed as one vmapped jnp computation (fast on CPU; also
    bit-identical to ``poisson_counts(..., backend="pallas_interpret")``).

    Used as the test oracle and as the fused_rng fallback for statistics
    without a moment decomposition.  Note: on TPU the compiled kernel draws
    its bits from the hardware PRNG (``use_tpu_prng=True``), which is
    distributionally identical but NOT bit-identical to this matrix.  The
    hardware PRNG takes two seed words, so a tile is seeded with (seed,
    b_tile * n_tiles + n_tile), n_tiles being the call's static tile count
    on the n axis: on the chip too, (seed, tile) fully determines a tile,
    and every kernel family draws the same tile for the same (seed, B, n).
    """
    bb, bn = weight_tile_blocks(B, n, block_b, block_n)
    nb_b = (B + (-B) % bb) // bb
    nb_n = (n + (-n) % bn) // bn
    seed = jnp.asarray(seed, jnp.int32)

    def tile(i, k):
        return _poisson_from_bits(_threefry_bits(seed, i, k, (bb, bn)))

    w = jax.vmap(lambda i: jax.vmap(lambda k: tile(i, k))(
        jnp.arange(nb_n)))(jnp.arange(nb_b))     # (nb_b, nb_n, bb, bn)
    w = w.transpose(0, 2, 1, 3).reshape(nb_b * bb, nb_n * bn)
    return w[:B, :n]
