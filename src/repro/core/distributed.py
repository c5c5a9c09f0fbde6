"""Distributed EARL: the bootstrap over mesh-sharded data (DESIGN.md §2).

MapReduce mapping:
  mapper  -> per-shard state update under shard-local Poisson weights
  combine -> Statistic.merge (associative)
  reducer -> psum of states across the 'data' (and 'pod') mesh axes,
             finalize replicated.

Shard independence is exactly why the Poisson engine is the distributed
default: weights for items on shard d depend only on (key, d, item), never
on other shards — no global multinomial coordination (DESIGN.md §7.1).

``distributed_bootstrap`` builds a jitted shard_map program for a given mesh;
``distributed_earl_estimate`` wraps it in the expand-until-accurate loop.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import accuracy, fold
from repro.core.bootstrap import BootstrapResult, seed_from_key
from repro.core.reduce_api import Statistic, _as_2d


def _poisson_for_shard(key: jax.Array, shard_id: jax.Array, B: int,
                       n_local: int) -> jax.Array:
    k = jax.random.fold_in(key, shard_id)
    return jax.random.poisson(k, 1.0, (B, n_local)).astype(jnp.float32)


def build_bootstrap_step(mesh: Mesh, stat: Statistic, B: int,
                         data_axes: Sequence[str] = ("data",),
                         donate: bool = True,
                         backend: Optional[str] = None):
    """Returns jitted fn (values_sharded, mask_sharded, key) -> (thetas, est).

    values: (n_global, d) sharded over ``data_axes`` on dim 0.
    mask:   (n_global,) 1.0 for real rows, 0.0 for padding — enables
            ragged global samples (n not divisible by the data axis) and
            ft/ shard-loss reweighting (zero a lost shard's mask).

    ``backend="fused_rng"`` generates each shard's Poisson(1) weights
    inside the fused kernels (stream keyed by (seed_from_key(key), shard)
    via ``offset_seed``) instead of materializing the (B, n_local) matrix;
    the shard's mask slice multiplies the implicit weight tiles
    (``valid_mask``), so ARBITRARY masks work — interior holes from ft/
    failed-shard loss included — and a prefix mask (what
    ``pad_to_shards`` produces) reproduces the historical n_valid-based
    masking bit for bit.

    Cross-shard reduction goes through ``Statistic.psum_state`` (NOT a raw
    tree-psum: Quantile's HistogramState carries non-additive lo/hi leaves
    that a blind psum would scale by the shard count).
    """
    if backend not in (None, "fused_rng"):
        raise ValueError(f"unknown distributed backend: {backend!r}")
    data_axes = tuple(data_axes)
    axis_sizes = [mesh.shape[a] for a in data_axes]
    nshards = 1
    for s in axis_sizes:
        nshards *= s

    def shard_fn(values, mask, key):
        # flat shard index across the (pod, data) axes
        idx = jnp.zeros((), jnp.int32)
        for a in data_axes:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        n_local, dim = values.shape
        if backend == "fused_rng":
            states, _ = fold.fold_rows(stat, None, None, values,
                                       seed_from_key(key), idx, B,
                                       valid_mask=mask)
        else:
            w = _poisson_for_shard(key, idx, B, n_local) * mask[None, :]

            def upd(w_row):
                return stat.update(stat.init_state(dim), values, w_row)

            states = jax.vmap(upd)(w)                   # B-leading pytree
        states = stat.psum_state(states, data_axes)
        thetas = jax.vmap(stat.finalize)(states)

        est_state = stat.update(stat.init_state(dim), values, mask)
        est_state = stat.psum_state(est_state, data_axes)
        estimate = stat.finalize(est_state)
        return thetas, estimate

    in_specs = (P(data_axes, None), P(data_axes), P())
    out_specs = (P(), P())
    fn = jax.shard_map(shard_fn, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return jax.jit(fn, donate_argnums=(0, 1) if donate else ())


def pad_to_shards(values: jax.Array, nshards: int):
    """Pad rows to a multiple of nshards; returns (padded, mask)."""
    x = _as_2d(values)
    n = x.shape[0]
    pad = (-n) % nshards
    xp = jnp.pad(x, ((0, pad), (0, 0)))
    mask = jnp.pad(jnp.ones((n,), jnp.float32), (0, pad))
    return xp, mask


def shard_values(mesh: Mesh, values: jax.Array,
                 data_axes: Sequence[str] = ("data",)):
    """Place (pad, shard) values over the data axes of the mesh."""
    data_axes = tuple(data_axes)
    nshards = 1
    for a in data_axes:
        nshards *= mesh.shape[a]
    xp, mask = pad_to_shards(values, nshards)
    xs = jax.device_put(xp, NamedSharding(mesh, P(data_axes, None)))
    ms = jax.device_put(mask, NamedSharding(mesh, P(data_axes)))
    return xs, ms


@dataclasses.dataclass
class DistributedEarl:
    """Mesh-wide EARL estimator with growing global samples.

    Used by train/earl_eval.py and the ft/ recovery path.  The sample is a
    global sharded array; expansion re-places a longer prefix (in a real
    multi-host deployment each host feeds only its local rows — the
    placement API is identical).
    """
    mesh: Mesh
    stat: Statistic
    B: int
    sigma: float = 0.05
    data_axes: Sequence[str] = ("data",)
    backend: Optional[str] = None   # "fused_rng" = in-kernel shard weights

    def __post_init__(self):
        self._step = build_bootstrap_step(self.mesh, self.stat, self.B,
                                          self.data_axes, donate=False,
                                          backend=self.backend)

    def estimate(self, values: jax.Array, key: jax.Array,
                 p: float = 1.0) -> BootstrapResult:
        xs, ms = shard_values(self.mesh, values, self.data_axes)
        thetas, est = self._step(xs, ms, key)
        thetas = self.stat.correct(thetas, p)
        est = self.stat.correct(est, p)
        return BootstrapResult(
            estimate=est, thetas=thetas,
            report=accuracy.report_for(
                thetas, num_groups=getattr(self.stat, "num_groups", None)),
            B=self.B, n=int(_as_2d(values).shape[0]))

    def estimate_with_loss_mask(self, values: jax.Array, mask: jax.Array,
                                key: jax.Array, p: float = 1.0
                                ) -> BootstrapResult:
        """ft/ path: ``mask`` already encodes lost shards (zeros).

        Works on every backend: the fused backend multiplies its implicit
        weight tiles by the mask slice (interior holes included), the
        default backend multiplies the materialized matrix — same
        estimator either way."""
        xs = jax.device_put(_as_2d(values),
                            NamedSharding(self.mesh,
                                          P(tuple(self.data_axes), None)))
        ms = jax.device_put(mask,
                            NamedSharding(self.mesh,
                                          P(tuple(self.data_axes))))
        thetas, est = self._step(xs, ms, key)
        thetas = self.stat.correct(thetas, p)
        est = self.stat.correct(est, p)
        n_eff = int(jnp.sum(mask))
        return BootstrapResult(
            estimate=est, thetas=thetas,
            report=accuracy.report_for(
                thetas, num_groups=getattr(self.stat, "num_groups", None)),
            B=self.B, n=n_eff)

    def estimate_elastic(self, values: jax.Array, key: jax.Array,
                         events, policy):
        """Mid-run degradation: shards in ``events`` that died or missed
        the deadline feed masked partial psums (their ``valid_mask`` slice
        is zero — survivors' work is NOT recomputed), the CI widens via
        ``correct(p_surviving)``, and ``policy`` turns ``meets_bound`` into
        continue-approximate vs checkpoint-restart.

        ``events`` is an ``ft.ShardEvents``, ``policy`` an
        ``ft.FailurePolicy``; returns an ``ft.ElasticReport``.  (Lazy
        import: ft/ sits above core/ in the layer order.)"""
        from repro.ft.policy import elastic_estimate
        return elastic_estimate(self, values, key, events, policy)
