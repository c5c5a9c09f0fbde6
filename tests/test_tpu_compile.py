"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Nothing runs: each case lowers an ops-layer entry point with
``backend="pallas"`` (hardware PRNG, compiled Mosaic) at real widths —
B=256 resamples over n=2^20 rows — and compiles it for one chip of a
``v5e:2x2`` topology description, so the chip's compiler refuses here
what interpret mode accepts (unsupported casts, seeding, block shapes that
break the (8, 128) tiling rule, VMEM overruns).  The compiled program must
hold the kernel (``tpu_custom_call``): a silent fallback to a scan
lowering would pass interpret-mode tests and never reach the chip.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and xdist workers import
every test file.
"""
from __future__ import annotations

import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.reduce_api import Mean, Median, Std, StatisticGroup
from repro.kernels.fused_multi.ops import fused_poisson_multi
from repro.kernels.kmeans_assign.ops import fused_poisson_kmeans
from repro.kernels.weighted_hist.ops import fused_poisson_hist
from repro.kernels.weighted_stats.ops import fused_poisson_moments

B = 256
N = 1 << 20


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # noqa: BLE001 — skip reason
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    # a described-chip compile is written to the persistent cache but can
    # never be read back without the chip; keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, sharding, *shapes):
    args = [_sds(s, d, sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"


SEED = ((), jnp.int32)


def _x(d):
    return ((N, d), jnp.float32)


MASK = ((N,), jnp.float32)


@pytest.mark.parametrize("variant", ["plain", "masked", "stream", "grouped"])
def test_moments(one_chip, variant):
    d = 4
    if variant == "plain":
        fn = lambda s, x: fused_poisson_moments(s, x, B, backend="pallas")
        shapes = (SEED, _x(d))
    elif variant == "masked":
        fn = lambda s, x, m: fused_poisson_moments(
            s, x, B, backend="pallas", valid_mask=m)
        shapes = (SEED, _x(d), MASK)
    elif variant == "stream":
        fn = lambda s, x, m: fused_poisson_moments(
            s, x, B, backend="pallas", valid_mask=m, stream=True)
        shapes = (SEED, _x(d), MASK)
    else:
        fn = lambda s, x, g: fused_poisson_moments(
            s, x, B, backend="pallas", group_ids=g, num_groups=8)
        shapes = (SEED, _x(d), ((N,), jnp.float32))
    _compile(fn, one_chip, *shapes)


@pytest.mark.parametrize("d,block_bins", [(4, None), (1, 128), (4, 128)])
def test_hist(one_chip, d, block_bins):
    fn = lambda s, x: fused_poisson_hist(s, x, -4.0, 4.0, 256, B,
                                         backend="pallas",
                                         block_bins=block_bins)
    _compile(fn, one_chip, SEED, _x(d))


def test_kmeans(one_chip):
    cent = jnp.asarray(np.random.default_rng(0).normal(size=(5, 2)),
                       jnp.float32)
    fn = lambda s, x: fused_poisson_kmeans(s, x, cent, B, backend="pallas")
    _compile(fn, one_chip, SEED, _x(2))


def test_multi_mean_median_std(one_chip):
    group = StatisticGroup((Mean(), Median(lo=-4.0, hi=4.0), Std()))
    fn = lambda s, x: fused_poisson_multi(group, s, x, B, backend="pallas")
    _compile(fn, one_chip, SEED, _x(1))


def test_scan_stream_step_folds_the_estimate_in_the_kernel(one_chip):
    """The scan cell's step: the paper-jobs group over a masked 2^20-row
    chunk.  The point estimate's median sketch comes out of the group
    kernel; no scatter-add is left in the program."""
    from repro.core.fold import empty_carry, fold_chunk_jit
    from repro.core.reduce_api import Quantile, split_params
    group = StatisticGroup((Mean(), Quantile(0.5, nbins=2048, lo=9.0,
                                             hi=11.0), Std()),
                           backend="pallas")
    spec, params = split_params(group)
    carry = jax.tree_util.tree_map(
        lambda a: _sds(a.shape, a.dtype, one_chip),
        jax.eval_shape(lambda: empty_carry(group, B, 1)))
    text = fold_chunk_jit.lower(
        *carry, _sds((N, 1), jnp.float32, one_chip),
        _sds((N,), jnp.float32, one_chip), _sds((), jnp.int32, one_chip),
        _sds((), jnp.int32, one_chip), params, spec, B).compile().as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"
    # an HLO scatter instruction, not a frame name in the metadata
    assert re.search(r"\bscatter\(", text) is None, \
        "the estimate's scatter-add is back"


def test_poisson_counts(one_chip):
    from repro.kernels.poisson_counts.ops import poisson_counts
    _compile(lambda s: poisson_counts(s, B, N, backend="pallas"), one_chip,
             SEED)
