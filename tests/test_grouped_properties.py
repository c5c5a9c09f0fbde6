"""Hypothesis property suite for grouped segment-reduction (ISSUE-7
satellite): random keys, masks, shapes and dtypes must preserve the two
load-bearing invariants of the GROUP BY kernels —

* grouped ≡ per-key oracle BITWISE: slot g of a grouped fused call equals
  the ungrouped call under ``valid_mask = (key == g)`` (common random
  numbers — one shared implicit Poisson(1) stream, exact 0/1 key masks);
* scan ≡ Pallas(interpret) bitwise under no mask, prefix masks, and
  interior-hole masks (both lowerings share the tile weight math; the scan
  is fed the kernel's lane-padded x so both contract the same shapes).

Deterministic fixed-case coverage of the same contracts lives in
tests/test_grouped.py; this module extends it across the input space and
is skipped wholesale when hypothesis is not installed (the pattern of
tests/test_properties.py).
"""
import numpy as np
import pytest

import jax.numpy as jnp

hypothesis = pytest.importorskip(
    "hypothesis", reason="hypothesis not installed in this environment")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.kernels.weighted_hist.ops import fused_poisson_hist  # noqa: E402
from repro.kernels.weighted_stats.ops import \
    fused_poisson_moments  # noqa: E402

_settings = settings(max_examples=30, deadline=None)


def _tree_bitwise(a, b):
    import jax
    ok = jax.tree_util.tree_map(
        lambda u, v: bool(np.array_equal(np.asarray(u), np.asarray(v))),
        a, b)
    assert all(jax.tree_util.tree_leaves(ok)), ok


class TestGroupedSegmentReductionProperties:
    @given(n=st.integers(2, 257), g=st.integers(1, 5),
           b=st.integers(1, 9), seed=st.integers(0, 2**20),
           dtype=st.sampled_from(["float32", "bfloat16"]))
    @_settings
    def test_grouped_equals_per_key_oracle_bitwise(self, n, g, b, seed,
                                                   dtype):
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.normal(size=(n, 2)).astype(np.float32))
        gid = jnp.asarray(rng.integers(0, g, size=n).astype(np.float32))
        dt = jnp.dtype(dtype)
        wt, s1, s2 = fused_poisson_moments(seed, x, b, group_ids=gid,
                                           num_groups=g, dtype=dt)
        for gg in range(g):
            ref = fused_poisson_moments(
                seed, x, b, valid_mask=(gid == gg).astype(jnp.float32),
                dtype=dt)
            _tree_bitwise((wt[:, gg], s1[:, gg], s2[:, gg]), ref)

    @given(n=st.integers(2, 257), g=st.integers(1, 4),
           b=st.integers(1, 9), seed=st.integers(0, 2**20),
           mode=st.sampled_from(["none", "prefix", "holes"]))
    @_settings
    def test_scan_equals_pallas_under_masks(self, n, g, b, seed, mode,
                                            lane_padded):
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.normal(size=(n, 2)).astype(np.float32))
        gid = jnp.asarray(rng.integers(0, g, size=n).astype(np.float32))
        if mode == "none":
            mask = None
        elif mode == "prefix":
            mask = jnp.asarray(
                (np.arange(n) < rng.integers(0, n + 1)).astype(np.float32))
        else:
            mask = jnp.asarray((rng.random(n) > 0.5).astype(np.float32))
        s = fused_poisson_moments(seed, lane_padded(x), b, backend="scan",
                                  valid_mask=mask, group_ids=gid,
                                  num_groups=g)
        k = fused_poisson_moments(seed, x, b, backend="pallas_interpret",
                                  valid_mask=mask, group_ids=gid,
                                  num_groups=g)
        _tree_bitwise((s[0], s[1][..., :2], s[2][..., :2]), k)

    @given(n=st.integers(2, 200), g=st.integers(1, 4),
           seed=st.integers(0, 2**20))
    @_settings
    def test_grouped_hist_equals_per_key_oracle(self, n, g, seed):
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.normal(size=(n, 1)).astype(np.float32))
        gid = jnp.asarray(rng.integers(0, g, size=n).astype(np.float32))
        counts = fused_poisson_hist(seed, x, -4.0, 4.0, 16, 4,
                                    group_ids=gid, num_groups=g)
        for gg in range(g):
            ref = fused_poisson_hist(
                seed, x, -4.0, 4.0, 16, 4,
                valid_mask=(gid == gg).astype(jnp.float32))
            _tree_bitwise(counts[:, gg], ref)
