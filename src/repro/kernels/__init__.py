"""Pallas TPU kernels for EARL's compute hot spots.

The paper's §4 optimizes the resampling loop — on TPU that loop is a dense
(B, n) weight matrix contracted against the sample (DESIGN.md §2), so the
hot spots are:

  weighted_stats/   fused (w_tot, Σw·x, Σw·x²) for all B resamples in one
                    MXU pass over VMEM tiles; ``fused_poisson_moments`` is
                    the matrix-free bootstrap hot path — Poisson(1) weights
                    are generated *inside* the contraction from a
                    counter-based PRNG, so the (B, n) weight matrix never
                    exists anywhere (peak live memory O(B·d)).
  poisson_counts/   in-kernel PRNG → Poisson(1) bootstrap weights (no HBM
                    round-trip for the (B, n) weight matrix); also the
                    tile/seeding machinery the fused path reuses and the
                    materialization oracle for its tests.
  kmeans_assign/    fused k-means assignment+accumulate for KMeansStep:
                    distances, argmin and the weighted (sums, counts,
                    inertia) per x tile with the centroid block resident in
                    VMEM — neither the (n, k) distance matrix nor the one-
                    hot ever exists in HBM; ``fused_poisson_kmeans`` adds
                    the in-kernel Poisson(1) weight generation (same tile
                    discipline as weighted_stats), the matrix-free
                    bootstrap-over-k-means hot path (peak O(B·k·d)).
  weighted_hist/    fused weighted-histogram sketch for Quantile/Median:
                    per-tile one-hot in VMEM + MXU bin accumulate, so the
                    (n, d, nbins) one-hot tensor never materializes.
                    Histograms are mergeable synopses (psum across shards).
  flash_attention/  blockwise causal/sliding-window attention used by the
                    serving/eval path of the model zoo (keeps the early-
                    accurate eval statistic's forward pass roofline-bound).

Each subpackage: kernel.py (pl.pallas_call + BlockSpec), ops.py (jit'd
wrapper w/ padding + platform dispatch), ref.py (pure-jnp oracle).
"""
