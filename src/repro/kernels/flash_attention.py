"""Flash attention (chunked online-softmax) with explicit VMEM tiling.

The LM stack's memory hot-spot: naive attention materializes an (Sq, Skv)
score matrix per head in HBM; at 32k context that is 4 GiB/head — the
memory-roofline killer the dry-run exposes.  The tiled form keeps one
(bq, bk) score tile in VMEM, carrying the online-softmax state (running
max m, normalizer l, accumulator acc) across the kv grid dimension.

This is the paper's loop-tiling insight applied to the attention loop
nest: tile the kv loop so q/acc tiles are reused across kv blocks.

GQA is handled in the BlockSpec index_map (kv head = q head // group) —
grouped heads never materialize repeated K/V.

Block sizes: bq=bk=512, d≤256 → q/k/v/acc tiles ≈ 4×512×256×4B = 2 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, bq: int, bk: int, offset: int,
            skv: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                          # (bq, d)
    k = k_ref[0]                          # (bk, d)
    v = v_ref[0]                          # (bk, d)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                             # (bq, bk)

    kj = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    s = jnp.where(kj < skv, s, NEG_INF)  # mask kv padding
    if causal:
        qi = pl.program_id(1) * bq + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bk), 0) + offset
        s = jnp.where(kj <= qi, s, NEG_INF)

    m_prev = m_ref[0]                     # (bq,)
    l_prev = l_ref[0]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=1)
    acc = acc_ref[0] * corr[:, None] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_ref[0] = m_new
    l_ref[0] = l_new
    acc_ref[0] = acc

    @pl.when(j == pl.num_programs(2) - 1)
    def _fin():
        o_ref[0] = (acc_ref[0] / jnp.maximum(l_ref[0], 1e-30)[:, None]).astype(
            o_ref.dtype
        )


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, group: int = 1, scale=None,
                    bq: int = 512, bk: int = 512,
                    interpret: bool) -> jax.Array:
    """q: (H, Sq, D); k/v: (H//group, Skv, D).  Returns (H, Sq, D).

    Causal alignment assumes q positions are the LAST Sq positions of the
    kv sequence (standard prefill/decode layout)."""
    h, sq, d = q.shape
    hk, skv, _ = k.shape
    assert h == hk * group
    scale = float(scale if scale is not None else d ** -0.5)
    bq_ = min(bq, sq)
    bk_ = min(bk, skv)
    pq, pk_ = (-sq) % bq_, (-skv) % bk_
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0)))
    if pk_:
        k = jnp.pad(k, ((0, 0), (0, pk_), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk_), (0, 0)))
    gq, gkv = q.shape[1] // bq_, k.shape[1] // bk_
    offset = skv - sq  # causal alignment

    kern = functools.partial(
        _kernel, scale=scale, causal=causal, bq=bq_, bk=bk_, offset=offset,
        skv=skv,
    )
    out, _, _, _ = pl.pallas_call(
        kern,
        out_shape=(
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((h, q.shape[1]), jnp.float32),
            jax.ShapeDtypeStruct((h, q.shape[1]), jnp.float32),
            jax.ShapeDtypeStruct(q.shape, jnp.float32),
        ),
        grid=(h, gq, gkv),
        in_specs=[
            pl.BlockSpec((1, bq_, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk_, d), lambda b, i, j, g=group: (b // g, j, 0)),
            pl.BlockSpec((1, bk_, d), lambda b, i, j, g=group: (b // g, j, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, bq_, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq_), lambda b, i, j: (b, i)),
            pl.BlockSpec((1, bq_), lambda b, i, j: (b, i)),
            pl.BlockSpec((1, bq_, d), lambda b, i, j: (b, i, 0)),
        ),
        interpret=interpret,
    )(q, k, v)
    return out[:, :sq, :]
