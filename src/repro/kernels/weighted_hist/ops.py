"""jit'd public wrappers for weighted_hist: padding + platform dispatch.

backend: None = auto (pallas on TPU, jnp scatter-add / scan elsewhere),
"pallas", "pallas_interpret", "jnp"/"scan".

Two entry points:

* ``weighted_histogram``   — single-state sketch from explicit weights.
* ``fused_poisson_hist``   — matrix-free bootstrap sketch: B per-resample
  (d, nbins) histograms under implicit in-kernel Poisson(1) weights drawn
  with the shared ``implicit_weight_tile`` discipline, so neither the
  (B, n) weight matrix nor the (n, d, nbins) one-hot ever materializes;
  peak live state is O(B·d·nbins) plus one (block_n, d·nbins) tile-local
  one-hot.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.weighted_hist.kernel import (
    fused_poisson_hist_binblocked_kernel, fused_poisson_hist_kernel,
    weighted_hist_kernel)
from repro.kernels.weighted_hist.ref import (_bin_indices, finite_mass_mask,
                                             weighted_hist_scatter_ref)
from repro.kernels.weighted_stats.ops import (_pad_to, implicit_weight_tile,
                                              weight_tile_blocks)


def weighted_histogram(values: jax.Array, weights: jax.Array,
                       lo: jax.Array, hi: jax.Array, nbins: int,
                       backend: str | None = None,
                       block_n: int = 256, block_d: int = 8) -> jax.Array:
    """values (n, d) or (n,), weights (n,), lo/hi (d,) -> (d, nbins) f32.

    The (n, d, nbins) one-hot tensor never materializes on any backend.
    """
    if values.ndim == 1:
        values = values[:, None]
    n, d = values.shape
    lo = jnp.broadcast_to(jnp.asarray(lo, jnp.float32), (d,))
    hi = jnp.broadcast_to(jnp.asarray(hi, jnp.float32), (d,))
    w = jnp.asarray(weights, jnp.float32)

    if backend is None:
        backend = "pallas" if jax.default_backend() == "tpu" else "jnp"
    if backend == "jnp":
        return weighted_hist_scatter_ref(values, w, lo, hi, nbins)

    bn = min(block_n, max(8, n))
    bd = min(block_d, max(1, d))
    xp = _pad_to(_pad_to(values.astype(jnp.float32), bn, 0), bd, 1)
    wp = _pad_to(w[:, None], bn, 0)              # zero rows: no mass
    lop = _pad_to(lo[None, :], bd, 1)
    hip = _pad_to(hi[None, :], bd, 1, value=1.0)  # avoid zero span in padding
    counts = weighted_hist_kernel(xp, wp, lop, hip, nbins,
                                  block_n=bn, block_d=bd,
                                  interpret=(backend != "pallas"))
    return counts[:d, :nbins]


# ============================================================================
# matrix-free bootstrap path
# ============================================================================
@functools.partial(jax.jit, static_argnames=("B", "nbins", "block_b",
                                             "block_n"))
def _fused_hist_scan(seed, n_valid, xp, lo, hi, B, nbins, block_b, block_n,
                     maskp=None):
    """CPU lowering of the fused kernel: scan over n-tiles, weights from the
    SHARED ``implicit_weight_tile`` (same per-tile threefry bits and CDF
    ladder as every fused path), binning from the shared ref rule.

    Accumulation is a per-tile scatter-add (O(B·bn·d) work) rather than the
    kernel's one-hot MXU dots (O(B·bn·d·nbins) flops — the right trade on
    a TPU where the one-hot stays in VMEM, ~3× the wall time on XLA:CPU).
    The two lowerings are still BIT-identical: histogram counts are sums of
    small integer weights, exact in f32 under any summation order.  Peak
    live state per step is the (B, block_n) weight tile plus the
    (B, d·nbins) accumulator — the (n, d, nbins) tensor never exists."""
    n, d = xp.shape
    nt = n // block_n
    xc = xp.reshape(nt, block_n, d)
    maskc = None if maskp is None else maskp.reshape(nt, block_n)

    def body(counts, t):
        w = implicit_weight_tile(seed, n_valid, t, B,
                                 block_b, block_n,
                                 valid=None if maskc is None
                                 else maskc[t])              # (B, bn)
        xt = xc[t]
        idx = _bin_indices(xt, lo[None, :], hi[None, :], nbins)  # (bn, d)
        flat = (idx + jnp.arange(d, dtype=jnp.int32)[None, :]
                * nbins).reshape(-1)                         # (bn·d,)
        wm = (w[:, :, None] * finite_mass_mask(xt)[None, :, :]
              ).reshape(B, block_n * d)
        return counts.at[:, flat].add(wm), None

    init = jnp.zeros((B, d * nbins), jnp.float32)
    counts, _ = jax.lax.scan(body, init, jnp.arange(nt, dtype=jnp.int32))
    return counts.reshape(B, d, nbins)


@functools.partial(jax.jit, static_argnames=("B", "nbins", "num_groups",
                                             "block_b", "block_n"))
def _grouped_fused_hist_scan(seed, n_valid, xp, gp, lo, hi, B, nbins,
                             num_groups, block_b, block_n, maskp=None):
    """GROUP BY sketch lowering: one implicit weight tile per step, keyed
    into ``num_groups`` (d, nbins) sketch slots by exact 0/1 key-mask
    multiplies — the accumulator is the ungrouped (B, d·nbins) scatter
    target replicated per key (flattened to (B, G·d·nbins)), with each
    key's scatter using the SAME bin indices and finite-mass mask on
    ``w * (gid == g)``.  Counts are sums of small integer weights — exact
    in f32 — so slot g is bitwise ``_fused_hist_scan`` under
    ``maskp = (gid == g)``.  Neither the (n, G) one-hot nor any (B, n)
    matrix materializes."""
    n, d = xp.shape
    nt = n // block_n
    xc = xp.reshape(nt, block_n, d)
    gc = gp.reshape(nt, block_n)
    maskc = None if maskp is None else maskp.reshape(nt, block_n)

    def body(counts, t):
        w = implicit_weight_tile(seed, n_valid, t, B,
                                 block_b, block_n,
                                 valid=None if maskc is None
                                 else maskc[t])              # (B, bn)
        xt = xc[t]
        gid = gc[t]
        idx = _bin_indices(xt, lo[None, :], hi[None, :], nbins)  # (bn, d)
        flat = (idx + jnp.arange(d, dtype=jnp.int32)[None, :]
                * nbins).reshape(-1)                         # (bn·d,)
        fm = finite_mass_mask(xt)                            # (bn, d)
        for g in range(num_groups):
            wg = w * (gid == g).astype(jnp.float32)[None, :]
            wm = (wg[:, :, None] * fm[None, :, :]).reshape(B, block_n * d)
            counts = counts.at[:, g * d * nbins + flat].add(wm)
        return counts, None

    init = jnp.zeros((B, num_groups * d * nbins), jnp.float32)
    counts, _ = jax.lax.scan(body, init, jnp.arange(nt, dtype=jnp.int32))
    return counts.reshape(B, num_groups, d, nbins)


def fused_poisson_hist(seed, values: jax.Array, lo, hi, nbins: int, B: int,
                       backend: str | None = None,
                       block_b: int = 128, block_n: int = 512,
                       n_valid=None, valid_mask=None,
                       block_bins: int | None = None,
                       group_ids=None,
                       num_groups: int | None = None) -> jax.Array:
    """Matrix-free bootstrap histogram sketch from an int32 seed.

    values (n, d) or (n,), lo/hi scalar or (d,) -> (B, d, nbins) f32 counts
    where the implicit weights are Poisson(1), keyed per (block_b, block_n)
    tile by (seed, b-tile, n-tile) — the same matrix as
    ``weighted_stats.ops.implicit_weights(seed, B, n)``, which is what lets
    Quantile share one stream with every other fused statistic (common
    random numbers / delta maintenance).

    ``n_valid`` (traced scalar, default n) masks weight columns >= n_valid
    to zero — without it the zero-padded tail would land real mass in each
    dimension's bin 0.  ``valid_mask`` (traced (n,) f32 of exact 0.0/1.0)
    multiplies the weight tiles — arbitrary interior validity holes; a
    prefix-shaped mask reproduces the ``n_valid`` result bit for bit
    (see ``implicit_weight_tile``).

    ``block_bins`` (Pallas backends only; a 128 multiple) tiles the
    d·nbins OUTPUT axis: each kernel instance keeps only a
    (block_b, block_bins) output window in VMEM instead of the whole
    (block_b, d·out_bins) row block — the knob for large d·nbins where the
    default kernel's output block would not fit VMEM.  The weight tile is
    regenerated per output window from the same (seed, b-tile, n-tile)
    keying, so results are identical; the trade is PRNG recompute for
    output residency.  ``None`` (default) keeps the single-block kernel.

    ``group_ids`` (traced (n,) integer keys 0..num_groups-1) switches on
    the GROUP BY path: the SAME implicit weight stream feeds ``num_groups``
    keyed sketch slots and the result gains a G axis —
    (B, num_groups, d, nbins) — with slot g BITWISE equal to the ungrouped
    call under ``valid_mask = (group_ids == g)``.  The grouped sketch is
    scan-lowered (the G·d·nbins output would multiply the Pallas kernel's
    VMEM-resident one-hot output block; see ROADMAP Known modeling
    limits) — auto backend resolves to "scan" and an explicit Pallas
    backend raises.

    backend: None = auto (pallas on TPU, scan elsewhere), "pallas",
    "pallas_interpret", "scan".
    """
    if values.ndim == 1:
        values = values[:, None]
    n, d = values.shape
    if backend is None:
        backend = ("scan" if group_ids is not None
                   else "pallas" if jax.default_backend() == "tpu"
                   else "scan")
    if backend not in ("pallas", "pallas_interpret", "scan"):
        raise ValueError(f"unknown fused_poisson_hist backend: {backend!r}")
    if group_ids is not None and backend != "scan":
        raise ValueError(
            "fused_poisson_hist(group_ids=...) is scan-only: the grouped "
            "sketch's G·d·nbins output block does not fit the Pallas "
            "kernel's VMEM residency model (tile the keys or use "
            f"backend='scan', got backend={backend!r})")
    if n_valid is None:
        n_valid = n

    bb, bn = weight_tile_blocks(B, n, block_b, block_n)
    Bp = B + (-B) % bb
    seed = jnp.asarray(seed, jnp.int32)
    n_valid = jnp.asarray(n_valid, jnp.int32)
    lo = jnp.broadcast_to(jnp.asarray(lo, jnp.float32), (d,))
    hi = jnp.broadcast_to(jnp.asarray(hi, jnp.float32), (d,))
    xp = _pad_to(values.astype(jnp.float32), bn, 0)
    mp = None
    if valid_mask is not None:
        mp = _pad_to(jnp.asarray(valid_mask, jnp.float32).reshape(n), bn, 0)

    if group_ids is not None:
        if num_groups is None or int(num_groups) < 1:
            raise ValueError("group_ids requires num_groups >= 1, got "
                             f"{num_groups!r}")
        # padding columns keep key 0 — their weights are exactly zero via
        # the n_valid prefix mask / zero-padded valid_mask.
        gp = _pad_to(jnp.asarray(group_ids, jnp.float32).reshape(n), bn, 0)
        counts = _grouped_fused_hist_scan(seed, n_valid, xp, gp, lo, hi,
                                          Bp, nbins, int(num_groups),
                                          bb, bn, maskp=mp)
        return counts[:B]

    if backend == "scan":
        counts = _fused_hist_scan(seed, n_valid, xp, lo, hi, Bp, nbins,
                                  bb, bn, maskp=mp)
        return counts[:B]

    mp2 = None if mp is None else mp[None, :]
    # lane-width discipline (same as the other fused kernels): x/lo/hi are
    # padded to 128 lanes; only the d real columns are ever contracted.
    if block_bins is not None:
        # output-tiled variant: x transposed so the BlockSpec (not a traced
        # lane slice) selects each dimension's value row.
        counts = fused_poisson_hist_binblocked_kernel(
            seed, n_valid, xp.T[:, None, :], lo[:, None, None],
            hi[:, None, None], Bp, nbins,
            d_valid=d, block_bins=block_bins, block_b=bb, block_n=bn,
            interpret=(backend != "pallas"),
            use_tpu_prng=(backend == "pallas"), mask=mp2)
        out_bins = nbins + (-nbins) % block_bins
        return counts.reshape(Bp, d, out_bins)[:B, :, :nbins]
    xpp = _pad_to(xp, 128, 1)
    lop = _pad_to(lo[None, :], 128, 1)
    hip = _pad_to(hi[None, :], 128, 1, value=1.0)  # nonzero padding span
    counts = fused_poisson_hist_kernel(
        seed, n_valid, xpp, lop, hip, Bp, nbins, d_valid=d,
        block_b=bb, block_n=bn,
        interpret=(backend != "pallas"),
        use_tpu_prng=(backend == "pallas"), mask=mp2)
    out_bins = nbins + (-nbins) % 128
    return counts.reshape(Bp, d, out_bins)[:B, :, :nbins]
