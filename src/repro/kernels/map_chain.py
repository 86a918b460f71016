"""Fused elementwise map-chain kernel.

The Weld optimizer collapses a chain of library map operators into ONE
loop; the planner routes that loop here so the whole chain executes as a
single Pallas pass:

    result(for(v1..vk, vecbuilder, (b,i,x) => merge(b, f(x))))

The body ``f`` arrives as a jnp-traceable callable staged from the IR, so
one kernel serves every elementwise chain (Black-Scholes, dataframe
column math, normalization...).  Each grid step loads one VMEM-resident
block per input column, applies the fused body on the VPU, and writes one
output block — intermediates never touch HBM, which is the paper's fusion
argument restated at the kernel level.

Block size: 8×1024 lanes per column (f32: 32 KiB/column) — matches the
filter_reduce tile so several columns plus the output stay well inside
VMEM with double-buffering headroom.
"""
from __future__ import annotations

from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK = 8 * 1024
#: autotune grid (matches filter_reduce: these kernels share tile math).
BLOCK_CANDIDATES = (1024, 8 * 1024, 32 * 1024)


def map_elementwise(fn: Callable, arrays: Sequence[jax.Array], *,
                    block: int = BLOCK, interpret: bool) -> jax.Array:
    """out[i] = fn(a1[i], ..., ak[i]) for equal-length 1-D arrays.

    Inputs are padded to a block multiple; ``fn`` must be total on the
    padded zeros (padding rows are sliced off before returning).
    """
    arrays = [jnp.asarray(a) for a in arrays]
    n = arrays[0].shape[0]
    out_sd = jax.eval_shape(
        fn, *[jax.ShapeDtypeStruct((), a.dtype) for a in arrays]
    )
    if n == 0:
        return jnp.zeros((0,), out_sd.dtype)
    npad = (block - n % block) % block
    if npad:
        arrays = [jnp.pad(a, (0, npad)) for a in arrays]
    total = arrays[0].shape[0]

    def _kernel(*refs):
        o_ref = refs[-1]
        val = fn(*[r[...] for r in refs[:-1]])
        o_ref[...] = jnp.broadcast_to(val, o_ref.shape).astype(o_ref.dtype)

    out = pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((total,), out_sd.dtype),
        grid=(total // block,),
        in_specs=[pl.BlockSpec((block,), lambda i: (i,)) for _ in arrays],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        interpret=interpret,
    )(*arrays)
    return out[:n]
