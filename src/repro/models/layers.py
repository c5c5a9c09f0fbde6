"""Model blocks: GQA attention (full/SWA/cross), SwiGLU MLP, sort-based
capacity-routed MoE, RG-LRU recurrence, xLSTM (mLSTM/sLSTM) cells.

Conventions
  * params are plain nested dicts of jnp arrays (param_dtype), cast to
    cfg.compute_dtype at use; norms/softmax/recurrences run in fp32.
  * every block fn returns ``(y, new_cache)``; cache=None in train mode.
  * sequence caches for SWA layers are ring buffers of size window —
    the KV memory win that makes long_500k feasible on windowed archs.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import ops as fa_ops
from repro.models.config import ModelConfig

Params = Dict[str, Any]
Cache = Optional[Dict[str, Any]]


# ---------------------------------------------------------------------------
# small pieces
# ---------------------------------------------------------------------------
def _cdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.compute_dtype)


def _pdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))
    return out.astype(x.dtype)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, Dh); positions: (S,) or (B, S)."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[..., None].astype(jnp.float32) * freqs       # (B,S,half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


def dense_init(key, shape, in_axis_size, dtype) -> jax.Array:
    std = in_axis_size ** -0.5
    return (jax.random.normal(key, shape) * std).astype(dtype)


def _split(key, n):
    return list(jax.random.split(key, n))


def einsum32(spec, *args):
    """bf16-in, fp32-accumulate einsum (MXU semantics)."""
    return jnp.einsum(spec, *args, preferred_element_type=jnp.float32)


def mmc(cfg: ModelConfig, spec, *args):
    """Projection einsum whose OUTPUT dtype follows cfg.matmul_out_dtype.

    With "compute" (bf16), the TP partial-sum all-reduce that GSPMD fuses
    onto the dot output moves in bf16 — half the wire bytes of the fp32
    baseline (EXPERIMENTS.md §Perf, iteration H1).  MXU accumulation is
    fp32 either way."""
    if cfg.matmul_out_dtype == "compute":
        out_dt = _cdtype(cfg)
    else:
        out_dt = jnp.dtype(cfg.matmul_out_dtype)
    return jnp.einsum(spec, *args, preferred_element_type=out_dt)


# ---------------------------------------------------------------------------
# self attention (full / swa / local / global) with KV cache
# ---------------------------------------------------------------------------
def init_attention(key, cfg: ModelConfig) -> Params:
    d, hq, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    pd = _pdtype(cfg)
    ks = _split(key, 4)
    return {
        "wq": dense_init(ks[0], (d, hq, dh), d, pd),
        "wk": dense_init(ks[1], (d, hkv, dh), d, pd),
        "wv": dense_init(ks[2], (d, hkv, dh), d, pd),
        "wo": dense_init(ks[3], (hq, dh, d), hq * dh, pd),
    }


def init_attn_cache(cfg: ModelConfig, batch: int, cache_len: int,
                    window: int) -> Dict[str, jax.Array]:
    """Ring-buffer KV cache.  For windowed layers the buffer is the window
    (ring); for full layers it is the whole context."""
    hkv, dh = cfg.n_kv_heads, cfg.head_dim_
    L = min(cache_len, window) if window else cache_len
    cd = _cdtype(cfg)
    return {
        "k": jnp.zeros((batch, hkv, L, dh), cd),
        "v": jnp.zeros((batch, hkv, L, dh), cd),
        "slot_pos": jnp.full((L,), -1, jnp.int32),   # absolute pos per slot
    }


def self_attention(cfg: ModelConfig, p: Params, x: jax.Array, *,
                   window: int, positions: jax.Array,
                   cache: Cache = None, causal: bool = True,
                   mode: str = "train",
                   cache_len: int | None = None) -> Tuple[jax.Array, Cache]:
    b, s, d = x.shape
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    cd = _cdtype(cfg)
    xc = x.astype(cd)

    q = mmc(cfg, "bsd,dhk->bshk", xc, p["wq"].astype(cd)).astype(cd)
    k = mmc(cfg, "bsd,dhk->bshk", xc, p["wk"].astype(cd)).astype(cd)
    v = mmc(cfg, "bsd,dhk->bshk", xc, p["wv"].astype(cd)).astype(cd)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    q = q * (dh ** -0.5)
    qh = q.transpose(0, 2, 1, 3)                      # (B, Hq, S, Dh)
    kh = k.transpose(0, 2, 1, 3)
    vh = v.transpose(0, 2, 1, 3)

    if mode != "decode":
        out = fa_ops.flash_attention(
            qh, kh, vh, causal=causal, window=window or None, scale=1.0,
            block_q=cfg.attn_block_q, block_k=cfg.attn_block_k,
            backend=cfg.attention_backend)
        y = out.transpose(0, 2, 1, 3)
        y = mmc(cfg, "bshk,hkd->bsd", y.astype(cd),
                p["wo"].astype(cd)).astype(x.dtype)
        if mode == "train":
            return y, None
        # prefill: materialize the KV cache (ring layout for SWA layers);
        # cache_len > s reserves room for subsequent decode steps
        assert positions.ndim == 1
        cl = cache_len if cache_len is not None else s
        L = min(window, cl) if window else cl
        idxs = jnp.arange(max(s - L, 0), s)
        pos_abs = positions[idxs]
        slots = pos_abs % L if window else idxs
        kc = jnp.zeros((b, hkv, L, dh), cd).at[:, :, slots].set(
            kh[:, :, idxs].astype(cd))
        vc = jnp.zeros((b, hkv, L, dh), cd).at[:, :, slots].set(
            vh[:, :, idxs].astype(cd))
        slot_pos = jnp.full((L,), -1, jnp.int32).at[slots].set(pos_abs)
        return y, {"k": kc, "v": vc, "slot_pos": slot_pos}

    # ---- cached decode: s == 1, ring-buffer update ----------------------
    assert s == 1, "cached path is single-token decode"
    L = cache["k"].shape[2]
    group = hq // hkv
    pos = positions.reshape(-1)[0]                   # scalar absolute pos
    slot = (pos % L) if window else jnp.clip(pos, 0, L - 1)
    newk = jax.lax.dynamic_update_slice(
        cache["k"], kh.astype(cache["k"].dtype), (0, 0, slot, 0))
    newv = jax.lax.dynamic_update_slice(
        cache["v"], vh.astype(cache["v"].dtype), (0, 0, slot, 0))
    slot_pos = cache["slot_pos"].at[slot].set(pos)

    svalid = slot_pos >= 0
    if causal:
        svalid &= slot_pos <= pos
    if window:
        svalid &= slot_pos > pos - window
    qg = qh.reshape(b, hkv, group, 1, dh)            # GQA grouping
    scores = einsum32("bhgqk,bhsk->bhgqs", qg.astype(cd), newk.astype(cd))
    scores = jnp.where(svalid[None, None, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    ctx = einsum32("bhgqs,bhsk->bhgqk", probs, newv.astype(jnp.float32))
    ctx = ctx.reshape(b, hq, 1, dh).transpose(0, 2, 1, 3)
    y = einsum32("bshk,hkd->bsd", ctx.astype(cd), p["wo"].astype(cd))
    return y.astype(x.dtype), {"k": newk, "v": newv, "slot_pos": slot_pos}


# ---------------------------------------------------------------------------
# cross attention (VLM xattn layers, whisper decoder)
# ---------------------------------------------------------------------------
def init_cross_attention(key, cfg: ModelConfig) -> Params:
    p = init_attention(key, cfg)
    p["gate"] = jnp.zeros((), _pdtype(cfg))           # zero-init gated xattn
    return p


def cross_attention(cfg: ModelConfig, p: Params, x: jax.Array,
                    aux: Optional[jax.Array], cache: Cache = None,
                    mode: str = "train") -> Tuple[jax.Array, Cache]:
    """x: (B, S, d) queries; aux: (B, Ta, d) keys/values (no rope).

    decode mode reads projected aux K/V from the cache (computed once at
    prefill); prefill emits that cache."""
    b, s, d = x.shape
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    cd = _cdtype(cfg)
    xc = x.astype(cd)
    q = mmc(cfg, "bsd,dhk->bshk", xc, p["wq"].astype(cd)).astype(cd)
    if mode == "decode":
        kh, vh = cache["k"], cache["v"]
    else:
        auxc = aux.astype(cd)
        kh = mmc(cfg, "btd,dhk->bthk", auxc, p["wk"].astype(cd)) \
            .astype(cd).transpose(0, 2, 1, 3)
        vh = mmc(cfg, "btd,dhk->bthk", auxc, p["wv"].astype(cd)) \
            .astype(cd).transpose(0, 2, 1, 3)
    qh = (q * (dh ** -0.5)).transpose(0, 2, 1, 3)
    out = fa_ops.flash_attention(
        qh, kh, vh, causal=False, window=None, scale=1.0,
        block_q=cfg.attn_block_q, block_k=cfg.attn_block_k,
        backend=("direct" if mode == "decode" else cfg.attention_backend))
    y = out.transpose(0, 2, 1, 3)
    y = mmc(cfg, "bshk,hkd->bsd", y.astype(cd), p["wo"].astype(cd))
    y = jnp.tanh(p["gate"].astype(jnp.float32)) * y.astype(jnp.float32)
    new_cache = {"k": kh, "v": vh} if mode != "train" else None
    return y.astype(x.dtype), new_cache


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------
def init_mlp(key, cfg: ModelConfig) -> Params:
    d, f = cfg.d_model, cfg.d_ff
    pd = _pdtype(cfg)
    ks = _split(key, 3)
    return {
        "w_gate": dense_init(ks[0], (d, f), d, pd),
        "w_up": dense_init(ks[1], (d, f), d, pd),
        "w_down": dense_init(ks[2], (f, d), f, pd),
    }


def mlp(cfg: ModelConfig, p: Params, x: jax.Array) -> jax.Array:
    cd = _cdtype(cfg)
    xc = x.astype(cd)
    g = mmc(cfg, "bsd,df->bsf", xc, p["w_gate"].astype(cd))
    u = mmc(cfg, "bsd,df->bsf", xc, p["w_up"].astype(cd))
    h = (jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32)
         ).astype(cd)
    y = mmc(cfg, "bsf,fd->bsd", h, p["w_down"].astype(cd))
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# MoE: top-k routing, sort-based capacity dispatch (no (T,E,C) one-hot)
# ---------------------------------------------------------------------------
def init_moe(key, cfg: ModelConfig) -> Params:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    pd = _pdtype(cfg)
    ks = _split(key, 5)
    p = {
        "router": dense_init(ks[0], (d, e), d, pd),
        "we_gate": dense_init(ks[1], (e, d, f), d, pd),
        "we_up": dense_init(ks[2], (e, d, f), d, pd),
        "we_down": dense_init(ks[3], (e, f, d), f, pd),
    }
    if cfg.dense_residual:
        p["dense"] = init_mlp(ks[4], cfg)
    return p


def _moe_route_compute(cfg: ModelConfig, p: Params, x: jax.Array
                       ) -> jax.Array:
    """Sort-based capacity routing + expert FFNs over the tokens of ``x``.

    Tokens are argsorted by expert id; each token-slot gets a rank within
    its expert and is dropped beyond capacity C = ceil(T·k·cf / E).  The
    dispatch/combine are gathers + scatter-adds (memory ops), not the
    (T,E,C) one-hot einsum whose FLOPs rival the experts themselves.

    Returns y in fp32, WITHOUT the dense residual (caller adds it).  Under
    shard_map the expert weights arrive f-sharded, so y is a partial sum
    the caller psums over the model axis.
    """
    b, s, d = x.shape
    t = b * s
    e, k = cfg.num_experts, cfg.top_k
    cap = int(math.ceil(t * k * cfg.capacity_factor / e))
    cd = _cdtype(cfg)

    xt = x.reshape(t, d)
    logits = einsum32("td,de->te", xt.astype(cd), p["router"].astype(cd))
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate, eidx = jax.lax.top_k(probs, k)                     # (T, k)
    gate = gate / jnp.sum(gate, -1, keepdims=True)

    flat_e = eidx.reshape(-1)                                # (T·k,)
    flat_t = jnp.repeat(jnp.arange(t), k)
    flat_g = gate.reshape(-1)

    order = jnp.argsort(flat_e, stable=True)
    se, st, sg = flat_e[order], flat_t[order], flat_g[order]
    idx = jnp.arange(t * k)
    is_start = jnp.concatenate([jnp.ones((1,), bool), se[1:] != se[:-1]])
    seg_start = jax.lax.associative_scan(jnp.maximum,
                                         jnp.where(is_start, idx, 0))
    rank = idx - seg_start
    keep = rank < cap
    slot = jnp.where(keep, se * cap + rank, e * cap)          # drop -> last

    buf = jnp.zeros((e * cap + 1, d), cd)
    buf = buf.at[slot].set(xt[st].astype(cd), mode="drop")
    expert_in = buf[:e * cap].reshape(e, cap, d)

    g_h = mmc(cfg, "ecd,edf->ecf", expert_in, p["we_gate"].astype(cd))
    u_h = mmc(cfg, "ecd,edf->ecf", expert_in, p["we_up"].astype(cd))
    h = (jax.nn.silu(g_h.astype(jnp.float32)) * u_h.astype(jnp.float32)
         ).astype(cd)
    out = mmc(cfg, "ecf,efd->ecd", h, p["we_down"].astype(cd))

    outf = jnp.concatenate([out.reshape(e * cap, d).astype(jnp.float32),
                            jnp.zeros((1, d), jnp.float32)], 0)
    contrib = outf[slot] * (sg * keep)[:, None]
    y = jnp.zeros((t, d), jnp.float32).at[st].add(contrib)
    return y.reshape(b, s, d)


def moe_ffn(cfg: ModelConfig, p: Params, x: jax.Array) -> jax.Array:
    """GSPMD-global MoE: one routing problem over all tokens; the XLA
    partitioner handles the dispatch scatter (baseline; §Perf H2 shows the
    collective cost this induces at 256 chips)."""
    y = _moe_route_compute(cfg, p, x)
    if cfg.dense_residual:
        y = y + mlp(cfg, p["dense"], x).astype(jnp.float32)
    return y.astype(x.dtype)


def moe_ffn_shard_map(cfg: ModelConfig, p: Params, x: jax.Array
                      ) -> jax.Array:
    """Group-local MoE (GShard groups = data shards) with TP-sharded
    expert weights — §Perf iteration H2.

    shard_map over the full mesh: tokens stay on their data shard (local
    routing, capacity per group), every shard holds all experts' weights
    f-sliced over "model"; the ONLY collective is one fp32 psum of the
    (local tokens, d) output over the model axis per layer — activation-
    sized, vs the token all-gathers GSPMD emits for global routing.

    Falls back to the GSPMD path when no mesh context is installed (CPU
    smoke tests) or the mesh lacks a model axis.
    """
    from repro.models.act_shard import current_mapping, current_mesh
    mesh = current_mesh()
    mapping = current_mapping()
    if mesh is None or mapping is None or "mlp" not in mapping:
        return moe_ffn(cfg, p, x)

    batch_axes = tuple(name for name, _ in mapping.get("batch", ()))
    model_axes = tuple(name for name, _ in mapping["mlp"])
    batch_ways = math.prod(mesh.shape[a] for a in batch_axes) \
        if batch_axes else 1
    if not model_axes or x.shape[0] % batch_ways != 0 \
            or cfg.d_ff % math.prod(mesh.shape[a] for a in model_axes):
        return moe_ffn(cfg, p, x)

    from jax.sharding import PartitionSpec as P

    def body(p_loc, x_loc):
        y = _moe_route_compute(cfg, p_loc, x_loc)
        if cfg.dense_residual:
            y = y + _mlp_partial(cfg, p_loc["dense"], x_loc)
        y = jax.lax.psum(y, model_axes)
        return y.astype(x.dtype)

    p_specs = {
        "router": P(),
        "we_gate": P(None, None, model_axes),
        "we_up": P(None, None, model_axes),
        "we_down": P(None, model_axes, None),
    }
    if cfg.dense_residual:
        p_specs["dense"] = {
            "w_gate": P(None, model_axes),
            "w_up": P(None, model_axes),
            "w_down": P(model_axes, None),
        }
    x_spec = P(batch_axes if batch_axes else None, None, None)
    fn = jax.shard_map(body, mesh=mesh, in_specs=(p_specs, x_spec),
                       out_specs=x_spec, check_vma=False)
    return fn({k: p[k] for k in p_specs}, x)


def _mlp_partial(cfg: ModelConfig, p: Params, x: jax.Array) -> jax.Array:
    """SwiGLU on an f-sharded weight slice; returns fp32 partial sums
    (caller psums over the model axis)."""
    cd = _cdtype(cfg)
    xc = x.astype(cd)
    g = mmc(cfg, "bsd,df->bsf", xc, p["w_gate"].astype(cd))
    u = mmc(cfg, "bsd,df->bsf", xc, p["w_up"].astype(cd))
    h = (jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32)
         ).astype(cd)
    return mmc(cfg, "bsf,fd->bsd", h, p["w_down"].astype(cd)) \
        .astype(jnp.float32)


# ---------------------------------------------------------------------------
# RG-LRU recurrent block (RecurrentGemma / Griffin)
# ---------------------------------------------------------------------------
_LRU_C = 8.0


def init_rglru(key, cfg: ModelConfig) -> Params:
    d, r, cw = cfg.d_model, cfg.rnn_width_, cfg.conv_width
    pd = _pdtype(cfg)
    ks = _split(key, 7)
    return {
        "w_x": dense_init(ks[0], (d, r), d, pd),
        "w_y": dense_init(ks[1], (d, r), d, pd),        # gelu gate branch
        "conv": dense_init(ks[2], (cw, r), cw, pd),
        "w_a": dense_init(ks[3], (r, r), r, pd),
        "w_i": dense_init(ks[4], (r, r), r, pd),
        "lam": (jax.random.uniform(ks[5], (r,), minval=0.7, maxval=0.95)
                .astype(pd)),
        "w_out": dense_init(ks[6], (r, d), r, pd),
    }


def init_rglru_cache(cfg: ModelConfig, batch: int) -> Dict[str, jax.Array]:
    r, cw = cfg.rnn_width_, cfg.conv_width
    return {
        "lru": jnp.zeros((batch, r), jnp.float32),
        "conv_state": jnp.zeros((batch, cw - 1, r), _cdtype(cfg)),
    }


def _causal_conv(u: jax.Array, kern: jax.Array,
                 state: Optional[jax.Array]) -> Tuple[jax.Array, jax.Array]:
    """Depthwise causal conv. u: (B,S,r), kern: (cw,r)."""
    cw = kern.shape[0]
    if state is None:
        up = jnp.pad(u, ((0, 0), (cw - 1, 0), (0, 0)))
    else:
        up = jnp.concatenate([state.astype(u.dtype), u], axis=1)
    out = sum(up[:, i:i + u.shape[1]] * kern[i][None, None, :]
              for i in range(cw))
    new_state = up[:, -(cw - 1):] if cw > 1 else None
    return out, new_state


def rglru_block(cfg: ModelConfig, p: Params, x: jax.Array, *,
                cache: Cache = None, mode: str = "train"
                ) -> Tuple[jax.Array, Cache]:
    b, s, d = x.shape
    cd = _cdtype(cfg)
    xc = x.astype(cd)
    u = mmc(cfg, "bsd,dr->bsr", xc, p["w_x"].astype(cd)).astype(cd)
    gate_branch = mmc(cfg, "bsd,dr->bsr", xc, p["w_y"].astype(cd))

    conv_state = cache["conv_state"] if mode == "decode" else None
    u_raw = u
    u, new_conv = _causal_conv(u, p["conv"].astype(cd), conv_state)
    if mode == "prefill":
        cw = cfg.conv_width
        new_conv = jnp.pad(u_raw, ((0, 0), (cw - 1, 0), (0, 0)))[:, -(cw - 1):] \
            if cw > 1 else None

    uf = u.astype(jnp.float32)
    rt = jax.nn.sigmoid(einsum32("bsr,rq->bsq", u, p["w_a"].astype(cd)))
    it = jax.nn.sigmoid(einsum32("bsr,rq->bsq", u, p["w_i"].astype(cd)))
    log_a = -_LRU_C * jax.nn.softplus(p["lam"].astype(jnp.float32)) * rt
    a = jnp.exp(log_a)
    gated = jnp.sqrt(jnp.maximum(1.0 - a * a, 1e-12)) * (it * uf)

    if mode == "decode":
        h = a[:, 0] * cache["lru"] + gated[:, 0]
        new_h = h
        h = h[:, None, :]
    else:
        def combine(c1, c2):
            a1, b1 = c1
            a2, b2 = c2
            return a1 * a2, b1 * a2 + b2
        _, h = jax.lax.associative_scan(combine, (a, gated), axis=1)
        new_h = h[:, -1]

    y = jax.nn.gelu(gate_branch.astype(jnp.float32)) * h
    y = mmc(cfg, "bsr,rd->bsd", y.astype(cd), p["w_out"].astype(cd))
    new_cache = (None if mode == "train"
                 else {"lru": new_h, "conv_state": new_conv})
    return y.astype(x.dtype), new_cache


# ---------------------------------------------------------------------------
# xLSTM: mLSTM (matrix memory, chunkwise-parallel) and sLSTM (scan)
# ---------------------------------------------------------------------------
def init_mlstm(key, cfg: ModelConfig) -> Params:
    d, h, dh = cfg.d_model, cfg.n_heads, cfg.head_dim_
    pd = _pdtype(cfg)
    ks = _split(key, 6)
    return {
        "wq": dense_init(ks[0], (d, h, dh), d, pd),
        "wk": dense_init(ks[1], (d, h, dh), d, pd),
        "wv": dense_init(ks[2], (d, h, dh), d, pd),
        "wi": dense_init(ks[3], (d, h), d, pd),
        "wf": dense_init(ks[4], (d, h), d, pd) ,
        "wo": dense_init(ks[5], (h, dh, d), h * dh, pd),
    }


def init_mlstm_cache(cfg: ModelConfig, batch: int) -> Dict[str, jax.Array]:
    h, dh = cfg.n_heads, cfg.head_dim_
    return {
        "mC": jnp.zeros((batch, h, dh, dh), jnp.float32),
        "mn": jnp.zeros((batch, h, dh), jnp.float32),
        "mm": jnp.full((batch, h), -1e30, jnp.float32),
    }


def _mlstm_chunk(q, k, v, ig, lf, carry):
    """One chunk of the stabilized mLSTM recurrence.

    q,k,v: (B,H,c,dh) fp32; ig: (B,H,c) input gate pre-act;
    lf: (B,H,c) log forget gate;  carry: (C (B,H,dh,dh), n (B,H,dh), m (B,H)).
    """
    C, nvec, m = carry
    F = jnp.cumsum(lf, axis=-1)                       # (B,H,c)
    logw = ig - F                                     # i[s] - F[s]
    m_loc = jax.lax.cummax(logw, axis=2)
    m_new = jnp.maximum(m[..., None] , m_loc) + F     # running stabilizer/t
    # inter-chunk: scale carried state
    inter_scale = jnp.exp(m[..., None] + F - m_new)   # (B,H,c)
    h_inter = jnp.einsum("bhck,bhkl->bhcl", q, C) * inter_scale[..., None]
    n_inter = jnp.einsum("bhck,bhk->bhc", q, nvec) * inter_scale
    # intra-chunk quadratic
    s_qk = jnp.einsum("bhck,bhsk->bhcs", q, k)
    decay = (F[..., :, None] - F[..., None, :] + ig[..., None, :]
             - m_new[..., :, None])
    tri = jnp.tril(jnp.ones(decay.shape[-2:], bool))
    D = jnp.where(tri, jnp.exp(decay), 0.0)
    w = s_qk * D
    h_intra = jnp.einsum("bhcs,bhsl->bhcl", w, v)
    n_intra = jnp.sum(w, axis=-1)
    denom = jnp.maximum(jnp.abs(n_inter + n_intra),
                        jnp.exp(-m_new))
    h = (h_inter + h_intra) / denom[..., None]
    # end-of-chunk carry update
    Fe = F[..., -1]                                   # (B,H)
    m_carry = jnp.maximum(m + Fe, jnp.max(logw, -1) + Fe)
    c_scale = jnp.exp(m + Fe - m_carry)
    kv_w = jnp.exp(Fe[..., None] - F + ig - m_carry[..., None])
    C_new = (C * c_scale[..., None, None]
             + jnp.einsum("bhsk,bhsl,bhs->bhkl", k, v, kv_w))
    n_new = nvec * c_scale[..., None] + jnp.einsum("bhsk,bhs->bhk", k, kv_w)
    return h, (C_new, n_new, m_carry)


def mlstm_block(cfg: ModelConfig, p: Params, x: jax.Array, *,
                cache: Cache = None, mode: str = "train"
                ) -> Tuple[jax.Array, Cache]:
    b, s, d = x.shape
    h_, dh = cfg.n_heads, cfg.head_dim_
    cd = _cdtype(cfg)
    xc = x.astype(cd)
    q = mmc(cfg, "bsd,dhk->bhsk", xc,
            p["wq"].astype(cd)).astype(jnp.float32) * (dh ** -0.5)
    k = mmc(cfg, "bsd,dhk->bhsk", xc,
            p["wk"].astype(cd)).astype(jnp.float32) * (dh ** -0.5)
    v = mmc(cfg, "bsd,dhk->bhsk", xc,
            p["wv"].astype(cd)).astype(jnp.float32)
    ig = einsum32("bsd,dh->bhs", xc, p["wi"].astype(cd))
    lf = -jax.nn.softplus(-einsum32("bsd,dh->bhs", xc, p["wf"].astype(cd)))

    if mode == "decode":
        carry = (cache["mC"], cache["mn"], cache["mm"])
        hout, (C, nvec, m) = _mlstm_chunk(q, k, v, ig, lf, carry)
        y = mmc(cfg, "bhsk,hkd->bsd", hout.astype(cd), p["wo"].astype(cd))
        return y.astype(x.dtype), {"mC": C, "mn": nvec, "mm": m}

    c = min(cfg.mlstm_chunk, s)
    pad = (-s) % c
    def padc(t):
        return jnp.pad(t, ((0, 0), (0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 3))
    qp, kp, vp, igp, lfp = map(padc, (q, k, v, ig, lf))
    nchunk = (s + pad) // c

    def body(carry, inputs):
        qi, ki, vi, igi, lfi = inputs
        hout, carry = _mlstm_chunk(qi, ki, vi, igi, lfi, carry)
        return carry, hout

    def chunks(t):
        return jnp.moveaxis(
            t.reshape(t.shape[0], t.shape[1], nchunk, c, *t.shape[3:]), 2, 0)

    carry0 = (jnp.zeros((b, h_, dh, dh), jnp.float32),
              jnp.zeros((b, h_, dh), jnp.float32),
              jnp.full((b, h_), -1e30, jnp.float32))
    carry, hs = jax.lax.scan(body, carry0,
                             tuple(map(chunks, (qp, kp, vp, igp, lfp))))
    hout = jnp.moveaxis(hs, 0, 2).reshape(b, h_, s + pad, dh)[:, :, :s]
    y = mmc(cfg, "bhsk,hkd->bsd", hout.astype(cd), p["wo"].astype(cd))
    new_cache = None
    if mode == "prefill":
        # NOTE: with padding the carry includes pad steps; exact only when
        # c divides s (true for the assigned shapes; asserted here).
        assert pad == 0, "prefill length must be a multiple of mlstm_chunk"
        new_cache = {"mC": carry[0], "mn": carry[1], "mm": carry[2]}
    return y.astype(x.dtype), new_cache


def init_slstm(key, cfg: ModelConfig) -> Params:
    d, h, dh = cfg.d_model, cfg.n_heads, cfg.head_dim_
    pd = _pdtype(cfg)
    ks = _split(key, 3)
    return {
        "wx": dense_init(ks[0], (d, 4, h, dh), d, pd),      # z, i, f, o
        "r": dense_init(ks[1], (h, dh, 4, dh), dh, pd),
        "wo": dense_init(ks[2], (h, dh, d), h * dh, pd),
    }


def init_slstm_cache(cfg: ModelConfig, batch: int) -> Dict[str, jax.Array]:
    h, dh = cfg.n_heads, cfg.head_dim_
    z = jnp.zeros((batch, h, dh), jnp.float32)
    return {"sc": z, "sn": z, "sh": z,
            "sm": jnp.full((batch, h, dh), -1e30, jnp.float32)}


def _slstm_step(p_r, state, gx):
    """gx: (B, 4, H, dh) input projections for one step."""
    c, n, hprev, m = state
    rec = jnp.einsum("bhk,hkgl->bghl", hprev, p_r)
    g = gx.astype(jnp.float32) + rec
    z = jnp.tanh(g[:, 0])
    i_t = g[:, 1]
    f_t = g[:, 2]
    o = jax.nn.sigmoid(g[:, 3])
    m_new = jnp.maximum(f_t + m, i_t)
    ip = jnp.exp(i_t - m_new)
    fp = jnp.exp(f_t + m - m_new)
    c_new = fp * c + ip * z
    n_new = fp * n + ip
    h_new = o * c_new / jnp.maximum(n_new, 1e-6)
    return (c_new, n_new, h_new, m_new), h_new


def slstm_block(cfg: ModelConfig, p: Params, x: jax.Array, *,
                cache: Cache = None, mode: str = "train"
                ) -> Tuple[jax.Array, Cache]:
    b, s, d = x.shape
    h_, dh = cfg.n_heads, cfg.head_dim_
    cd = _cdtype(cfg)
    gx = einsum32("bsd,dghk->bsghk", x.astype(cd), p["wx"].astype(cd))
    rmat = p["r"].astype(jnp.float32)

    if mode == "decode":
        state = (cache["sc"], cache["sn"], cache["sh"], cache["sm"])
        state, hnew = _slstm_step(rmat, state, gx[:, 0])
        y = einsum32("bhk,hkd->bd", hnew.astype(cd),
                     p["wo"].astype(cd))[:, None]
        c, n, hh, m = state
        return y.astype(x.dtype), {"sc": c, "sn": n, "sh": hh, "sm": m}

    def body(state, gxi):
        return _slstm_step(rmat, state, gxi)

    z = jnp.zeros((b, h_, dh), jnp.float32)
    state0 = (z, z, z, jnp.full((b, h_, dh), -1e30, jnp.float32))
    state, hs = jax.lax.scan(body, state0, jnp.moveaxis(gx, 1, 0))
    hs = jnp.moveaxis(hs, 0, 1)                        # (B,S,H,dh)
    y = mmc(cfg, "bshk,hkd->bsd", hs.astype(cd), p["wo"].astype(cd))
    new_cache = None
    if mode == "prefill":
        c, n, hh, m = state
        new_cache = {"sc": c, "sn": n, "sh": hh, "sm": m}
    return y.astype(x.dtype), new_cache
