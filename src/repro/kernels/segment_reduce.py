"""vecmerger / dictmerger kernel: keyed aggregation without atomics.

The paper (§7.7) shows the optimal vecmerger strategy is
platform-specific: thread-local copies on CPU, aggregation trees on GPU.
The TPU-native strategy implemented here is different again — and only
expressible because builders are declarative: each block builds a one-hot
matrix of its segment ids and feeds the **MXU** with

    out[:, K] += vals_block @ onehot(seg_block, K)^T

turning scatter-accumulation into dense systolic matmuls (no atomics, no
divergence; deterministic).  K (number of segments / vecmerger width) must
fit a VMEM-resident accumulator tile: K ≤ 4096 covers MoE expert counts
and the benchmark's key-count workload; larger K falls back to the ref
path (sort + segment-sum).

Layout: segment ids stream as 1-D blocks whose length is a multiple of
1024 (the tiling XLA gives a 1-D 32-bit array on the TPU); values stream
lane-major as ``(D, n)`` rows, so the one-hot is built ``(K, B)`` with
the ids broadcast down the sublanes — no relayout — and contracted on the
lanes.  K is walked in ``K_CHUNK`` slices so the one-hot tile stays at
``K_CHUNK × B`` whatever the segment count.  The matmul runs at HIGHEST
precision: the default would round f32 values to bf16 on the MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_N = 1024
MAX_K = 4096
K_CHUNK = 512
#: autotune grid for the row-block dim: multiples of 1024 (the TPU tiling
#: of a 1-D 32-bit operand).  Small blocks shrink the per-step one-hot
#: tile; big blocks amortize grid steps.
BLOCK_CANDIDATES = (1024, 2048, 4096)


def _kernel(seg_ref, val_ref, o_ref, *, kpad: int):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    seg = seg_ref[...]                       # (B,) int32
    vals = val_ref[...]                      # (D, B)
    for k0 in range(0, kpad, K_CHUNK):
        kc = min(K_CHUNK, kpad - k0)
        ids = jax.lax.broadcasted_iota(jnp.int32, (kc, seg.shape[0]), 0) + k0
        onehot = (ids == seg[None, :]).astype(vals.dtype)      # (kc, B)
        o_ref[:, k0:k0 + kc] += jax.lax.dot_general(
            vals, onehot, (((1,), (1,)), ((), ())),
            preferred_element_type=o_ref.dtype,
            precision=jax.lax.Precision.HIGHEST,
        )


def _segment_rows(seg_ids, vals, num_segments: int, block: int,
                  interpret: bool):
    """(D, n) value rows merged by segment id into (D, K)."""
    assert num_segments <= MAX_K, "K too large for VMEM tile; use ref path"
    d, n = vals.shape
    if n == 0:
        return jnp.zeros((d, num_segments), vals.dtype)
    npad = (block - n % block) % block
    if npad:
        # padding rows carry value 0 into segment 0: the sum identity
        seg_ids = jnp.pad(seg_ids, (0, npad))
        vals = jnp.pad(vals, ((0, 0), (0, npad)))
    kpad = -(-num_segments // 128) * 128
    out = pl.pallas_call(
        functools.partial(_kernel, kpad=kpad),
        out_shape=jax.ShapeDtypeStruct((d, kpad), vals.dtype),
        grid=(vals.shape[1] // block,),
        in_specs=[
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((d, block), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((d, kpad), lambda i: (0, 0)),
        interpret=interpret,
    )(seg_ids.astype(jnp.int32), vals)
    return out[:, :num_segments]


def segment_sum(seg_ids: jax.Array, vals: jax.Array, num_segments: int, *,
                block: int = BLOCK_N, interpret: bool) -> jax.Array:
    """out[s] = sum(vals[seg_ids == s]).  seg_ids int32 in [0, K)."""
    return _segment_rows(seg_ids, vals[None, :], num_segments, block,
                         interpret)[0]


def segment_sum_vectors(seg_ids: jax.Array, vals: jax.Array,
                        num_segments: int, *, block: int = BLOCK_N,
                        interpret: bool) -> jax.Array:
    """vals: (n, d) rows merged into out: (K, d) by segment id — MoE
    combine / expert-bucket accumulation, and the dictmerger route's
    fused (sums, counts) pass."""
    return _segment_rows(seg_ids, vals.T, num_segments, block,
                         interpret).T
