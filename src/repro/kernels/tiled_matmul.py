"""Loop tiling (paper Table 3) as explicit BlockSpec VMEM tiling.

The paper tiles nested loops so a block of the inner vector stays in
cache across outer iterations (its Listing 4 example: reuse blocks of x
across rows of v).  On TPU the cache is software-managed VMEM and the
compute unit is the 128×128 MXU, so the tiled form is a blocked matmul:

    C[i,j] = sum_k A[i,k] @ B[k,j]

with (bm, bk) × (bk, bn) tiles resident in VMEM and a (bm, bn) f32
accumulator carried across the k grid dimension.  Tile sizes default to
MXU-aligned 256/512 multiples; (256×512 + 512×256 + 256×256) f32 tiles =
1.25 MiB in flight, leaving VMEM headroom for double-buffered prefetch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


#: autotune grids per tile dim — MXU-aligned (multiples of 128); the
#: planner's autotuner sweeps the cross product and bakes the winner.
BM_CANDIDATES = (128, 256)
BN_CANDIDATES = (128, 256)
BK_CANDIDATES = (256, 512)


def _kernel(a_ref, b_ref, o_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=o_ref.dtype
    )


def tiled_matmul(a: jax.Array, b: jax.Array, *, bm: int = 256, bn: int = 256,
                 bk: int = 512, interpret: bool) -> jax.Array:
    """C = A @ B with explicit VMEM tiling.  Shapes padded to tiles."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2
    pm, pk, pn = (-m) % bm, (-k) % bk, (-n) % bn
    if pm or pk:
        a = jnp.pad(a, ((0, pm), (0, pk)))
    if pk or pn:
        b = jnp.pad(b, ((0, pk), (0, pn)))
    gm, gn, gk = a.shape[0] // bm, b.shape[1] // bn, a.shape[1] // bk
    out = pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((a.shape[0], b.shape[1]), a.dtype),
        grid=(gm, gn, gk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        interpret=interpret,
    )(a, b)
    return out[:m, :n]
