"""Shared building blocks: norms, projections, embeddings, RoPE, GQA
attention (train / prefill / decode), MLP variants.

Conventions:
  * params are dict pytrees of jnp arrays; every init has a matching
    `*_specs` returning the same structure with tuples of logical axis
    names (None = replicated axis).
  * activations: (batch, seq, d_model); attention heads kept as a
    separate axis only inside the attention op.
  * dtype policy: params in cfg.param_dtype, math in cfg.dtype with f32
    for softmax/norm accumulation.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from ..kernels import ops as kops

# logical axis names (mapped to mesh axes by distributed/sharding.py)
EMBED, MLP, HEADS, KV_HEADS, HEAD_DIM, VOCAB, LAYERS, EXPERTS, STATE = (
    "embed", "mlp", "heads", "kv_heads", "head_dim", "vocab", "layers",
    "experts", "state",
)


def _norm_init(key, shape, dtype):
    return jnp.ones(shape, dtype)


def he_init(key, shape, dtype, fan_in=None):
    fan_in = fan_in if fan_in is not None else shape[0]
    std = 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape) * std).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rmsnorm_init(cfg):
    return {"scale": jnp.ones((cfg.d_model,), cfg.param_dtype)}


def rmsnorm_specs():
    return {"scale": (EMBED,)}


def rmsnorm(params, x, eps=1e-5):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * params["scale"].astype(jnp.float32)).astype(x.dtype)


def layernorm_init(cfg):
    return {
        "scale": jnp.ones((cfg.d_model,), cfg.param_dtype),
        "bias": jnp.zeros((cfg.d_model,), cfg.param_dtype),
    }


def layernorm_specs():
    return {"scale": (EMBED,), "bias": (EMBED,)}


def layernorm(params, x, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    out = out * params["scale"].astype(jnp.float32) + params["bias"].astype(
        jnp.float32
    )
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------


def embedding_init(key, cfg):
    return {
        "table": he_init(key, (cfg.vocab, cfg.d_model), cfg.param_dtype,
                         fan_in=cfg.d_model),
    }


def embedding_specs():
    return {"table": (VOCAB, EMBED)}


def embed(params, tokens):
    # the table may be sharded over its vocab rows, so the gather's output
    # sharding is ambiguous: the gathered rows follow the tokens' sharding
    table = params["table"]
    out = NamedSharding(jax.typeof(table).sharding.mesh,
                        PartitionSpec(*jax.typeof(tokens).sharding.spec, None))
    return table.at[tokens].get(out_sharding=out)


def unembed(params, x):
    # tied unembedding: logits in f32 for a stable softmax/loss
    return jnp.einsum(
        "btd,vd->btv", x.astype(jnp.float32),
        params["table"].astype(jnp.float32),
    )


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float = 10000.0):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x, positions, theta: float = 10000.0):
    """x: (..., seq, heads, head_dim); positions: (..., seq)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                       # (d/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., seq, d/2)
    cos = jnp.cos(ang)[..., None, :]                   # (..., seq, 1, d/2)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# GQA attention
# ---------------------------------------------------------------------------


def attention_init(key, cfg):
    ks = jax.random.split(key, 4)
    hd = cfg.head_dim
    p = {
        "wq": he_init(ks[0], (cfg.d_model, cfg.n_heads, hd), cfg.param_dtype),
        "wk": he_init(ks[1], (cfg.d_model, cfg.n_kv_heads, hd), cfg.param_dtype),
        "wv": he_init(ks[2], (cfg.d_model, cfg.n_kv_heads, hd), cfg.param_dtype),
        "wo": he_init(ks[3], (cfg.n_heads, hd, cfg.d_model), cfg.param_dtype,
                      fan_in=cfg.n_heads * hd),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.n_heads, hd), cfg.param_dtype)
        p["bk"] = jnp.zeros((cfg.n_kv_heads, hd), cfg.param_dtype)
        p["bv"] = jnp.zeros((cfg.n_kv_heads, hd), cfg.param_dtype)
    return p


def attention_specs(cfg):
    s = {
        "wq": (EMBED, HEADS, HEAD_DIM),
        "wk": (EMBED, KV_HEADS, HEAD_DIM),
        "wv": (EMBED, KV_HEADS, HEAD_DIM),
        "wo": (HEADS, HEAD_DIM, EMBED),
    }
    if cfg.qkv_bias:
        s["bq"] = (HEADS, HEAD_DIM)
        s["bk"] = (KV_HEADS, HEAD_DIM)
        s["bv"] = (KV_HEADS, HEAD_DIM)
    return s


def _qkv(params, x, cfg, positions, rope: bool = True):
    q = jnp.einsum("btd,dhk->bthk", x, params["wq"].astype(x.dtype))
    k = jnp.einsum("btd,dhk->bthk", x, params["wk"].astype(x.dtype))
    v = jnp.einsum("btd,dhk->bthk", x, params["wv"].astype(x.dtype))
    if cfg.qkv_bias:
        q = q + params["bq"].astype(x.dtype)
        k = k + params["bk"].astype(x.dtype)
        v = v + params["bv"].astype(x.dtype)
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def attention_apply(params, x, cfg, positions=None, causal: bool = True,
                    rope: bool = True, kv_override=None):
    """Full-sequence attention (train/prefill).  Returns (out, (k, v)).

    kv_override: (k, v) from another sequence => cross-attention."""
    b, t, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(t), (b, t))
    if kv_override is None:
        q, k, v = _qkv(params, x, cfg, positions, rope)
    else:
        q = jnp.einsum("btd,dhk->bthk", x, params["wq"].astype(x.dtype))
        if cfg.qkv_bias:
            q = q + params["bq"].astype(x.dtype)
        if rope:
            q = apply_rope(q, positions, cfg.rope_theta)
        k, v = kv_override
    group = cfg.n_heads // cfg.n_kv_heads
    # (B,T,H,D) -> (B,H,T,D) for the kernel
    qh = q.transpose(0, 2, 1, 3)
    kh = k.transpose(0, 2, 1, 3)
    vh = v.transpose(0, 2, 1, 3)
    chunk = min(cfg.attn_chunk, kh.shape[2])
    # the jnp path on every backend: the Pallas flash-attention kernel does
    # not lower for the TPU (its (1, bq) row-statistics blocks break
    # Mosaic's (8, 128) tiling)
    out = jax.vmap(
        lambda qq, kk, vv: kops.attention(
            qq, kk, vv, causal=causal, group=group, chunk=chunk,
            unroll=cfg.scan_unroll, impl="ref",
        )
    )(qh, kh, vh)
    out = out.transpose(0, 2, 1, 3)  # (B,T,H,D)
    y = jnp.einsum("bthk,hkd->btd", out, params["wo"].astype(x.dtype))
    return y, (k, v)


def attention_decode(params, x, cfg, cache, pos, rope: bool = True,
                     cross: bool = False):
    """Single-token decode.  x: (B, 1, d); cache: {"k","v"}: (B, S, Hkv, D);
    pos: scalar current position.  Returns (out, new_cache)."""
    b = x.shape[0]
    positions = jnp.full((b, 1), pos, dtype=jnp.int32)
    if cross:
        q = jnp.einsum("btd,dhk->bthk", x, params["wq"].astype(x.dtype))
        if cfg.qkv_bias:
            q = q + params["bq"].astype(x.dtype)
        if rope:
            q = apply_rope(q, positions, cfg.rope_theta)
        k_all, v_all = cache["k"], cache["v"]
        valid = jnp.ones((k_all.shape[1],), bool)
        new_cache = cache
    else:
        q, k, v = _qkv(params, x, cfg, positions, rope)
        k_all = jax.lax.dynamic_update_slice_in_dim(cache["k"],
                                                    k.astype(cache["k"].dtype),
                                                    pos, axis=1)
        v_all = jax.lax.dynamic_update_slice_in_dim(cache["v"],
                                                    v.astype(cache["v"].dtype),
                                                    pos, axis=1)
        valid = jnp.arange(k_all.shape[1]) <= pos
        new_cache = {"k": k_all, "v": v_all}

    group = cfg.n_heads // cfg.n_kv_heads
    qg = q[:, 0].reshape(b, cfg.n_kv_heads, group, cfg.head_dim)
    scores = jnp.einsum(
        "bhgk,bshk->bhgs", qg.astype(jnp.float32),
        k_all.astype(jnp.float32),
    ) * (cfg.head_dim ** -0.5)
    scores = jnp.where(valid[None, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhgs,bshk->bhgk", probs, v_all.astype(jnp.float32))
    ctx = ctx.reshape(b, 1, cfg.n_heads, cfg.head_dim).astype(x.dtype)
    y = jnp.einsum("bthk,hkd->btd", ctx, params["wo"].astype(x.dtype))
    return y, new_cache


def attention_cache_spec(cfg, batch: int, max_seq: int, dtype):
    shp = (batch, max_seq, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jax.ShapeDtypeStruct(shp, dtype),
            "v": jax.ShapeDtypeStruct(shp, dtype)}


def attention_cache_init(cfg, batch: int, max_seq: int, dtype):
    shp = (batch, max_seq, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shp, dtype), "v": jnp.zeros(shp, dtype)}


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def mlp_init(key, cfg):
    k1, k2, k3 = jax.random.split(key, 3)
    if cfg.mlp_variant == "swiglu":
        return {
            "wi": he_init(k1, (cfg.d_model, cfg.d_ff), cfg.param_dtype),
            "wg": he_init(k2, (cfg.d_model, cfg.d_ff), cfg.param_dtype),
            "wo": he_init(k3, (cfg.d_ff, cfg.d_model), cfg.param_dtype,
                          fan_in=cfg.d_ff),
        }
    return {
        "wi": he_init(k1, (cfg.d_model, cfg.d_ff), cfg.param_dtype),
        "wo": he_init(k2, (cfg.d_ff, cfg.d_model), cfg.param_dtype,
                      fan_in=cfg.d_ff),
    }


def mlp_specs(cfg):
    if cfg.mlp_variant == "swiglu":
        return {"wi": (EMBED, MLP), "wg": (EMBED, MLP), "wo": (MLP, EMBED)}
    return {"wi": (EMBED, MLP), "wo": (MLP, EMBED)}


def mlp_apply(params, x, cfg):
    h = jnp.einsum("btd,df->btf", x, params["wi"].astype(x.dtype))
    if cfg.mlp_variant == "swiglu":
        g = jnp.einsum("btd,df->btf", x, params["wg"].astype(x.dtype))
        h = jax.nn.silu(g) * h
    elif cfg.mlp_variant == "relu2":   # nemotron squared-ReLU
        h = jnp.square(jax.nn.relu(h))
    else:
        h = jax.nn.gelu(h)
    return jnp.einsum("btf,fd->btd", h, params["wo"].astype(x.dtype))
