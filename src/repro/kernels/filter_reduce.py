"""Predicated filter+reduce kernel — the fused form of Listing 10.

    result(for(v, merger[+,0], (b,i,x) => if(p(x)) merge(b,x) else b))

TPU adaptation: the branch becomes a VPU select (predication is mandatory
on SPMD hardware), and the reduction happens block-wise in VMEM with a
running scalar accumulator across grid steps.  The predicate is supplied
as precomputed comparison bounds so one kernel serves Q6-style multi-column
conjunctions: keep = all(lo_k <= col_k < hi_k).

Block size: 8×1024 f32 = 32 KiB per column tile — several columns fit VMEM
(~16 MiB) with room for double buffering; the lane dim (1024) is a multiple
of the 128-wide VPU registers.  ``BLOCK`` is the default; the planner's
autotuner sweeps ``BLOCK_CANDIDATES`` per (kernel, dtype, size-bucket)
and bakes the winner into the plan.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK = 8 * 1024
#: autotune grid — all 1024-lane multiples so every candidate stays
#: VPU-register aligned; small end bounds padding waste on short columns.
BLOCK_CANDIDATES = (1024, 8 * 1024, 32 * 1024)


def _row_sum(v):
    """Sum of a 1-D block as a (1, 1) tile."""
    return jnp.sum(v[None, :], axis=1, keepdims=True)


def _kernel(x_ref, pred_ref, o_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...]
    keep = pred_ref[...]
    # reduce to (1, 1), not to a scalar: Mosaic lowers a scalar result
    # through jnp, which would widen an int32 sum to 64 bits under x64
    o_ref[...] += _row_sum(jnp.where(keep, x, jnp.zeros_like(x)))


def filter_reduce_sum(x: jax.Array, pred: jax.Array, *,
                      block: int = BLOCK, interpret: bool) -> jax.Array:
    """sum(x[pred]) in one pass.  x: (n,) float; pred: (n,) bool.
    n is padded to a block multiple with pred=False."""
    n = x.shape[0]
    if n == 0:
        return jnp.zeros((), x.dtype)
    npad = (block - n % block) % block
    if npad:
        x = jnp.pad(x, (0, npad))
        pred = jnp.pad(pred, (0, npad))
    grid = (x.shape[0] // block,)
    out = pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((1, 1), x.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((1, 1), lambda i: (0, 0)),
        interpret=interpret,
    )(x, pred)
    return out[0, 0]


def _kernel_multi(vals_ref, pred_ref, o_ref):
    """Multi-aggregate form: A value rows share ONE predicate mask and
    one grid pass — the struct-of-mergers (weldrel ``agg``) case fused
    into a single launch instead of one kernel call per aggregate."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    vals = vals_ref[...]                      # (A, B)
    keep = pred_ref[...]                      # (B,)
    contrib = jnp.sum(
        jnp.where(keep[None, :], vals, jnp.zeros_like(vals)), axis=1
    )
    o_ref[...] += contrib[None, :]


def filter_reduce_sum_multi(vals: jax.Array, pred: jax.Array, *,
                            block: int = BLOCK,
                            interpret: bool) -> jax.Array:
    """Row-wise predicated sums: vals (A, n), pred (n,) -> (A,) where
    out[a] = sum(vals[a][pred]).  One pass; the predicate and the column
    tiles are loaded once for all A aggregates."""
    a, n = vals.shape
    if n == 0:
        return jnp.zeros((a,), vals.dtype)
    npad = (block - n % block) % block
    if npad:
        vals = jnp.pad(vals, ((0, 0), (0, npad)))
        pred = jnp.pad(pred, (0, npad))
    grid = (vals.shape[1] // block,)
    out = pl.pallas_call(
        _kernel_multi,
        out_shape=jax.ShapeDtypeStruct((1, a), vals.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((a, block), lambda i: (0, i)),
            pl.BlockSpec((block,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((1, a), lambda i: (0, 0)),
        interpret=interpret,
    )(vals, pred)
    return out[0]


def _kernel_fused_pred(cols_ref, lo_ref, hi_ref, val_ref, o_ref):
    """Q6 shape: keep = AND_k(lo_k <= col_k < hi_k); sum val where keep."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    cols = cols_ref[...]          # (K, B)
    lo = lo_ref[...]              # (K, 1)
    hi = hi_ref[...]              # (K, 1)
    keep = jnp.all((cols >= lo) & (cols < hi), axis=0)   # (B,)
    v = val_ref[...]
    o_ref[...] += _row_sum(jnp.where(keep, v, jnp.zeros_like(v)))


def filter_reduce_q6(cols: jax.Array, lo: jax.Array, hi: jax.Array,
                     val: jax.Array, *, block: int = BLOCK,
                     interpret: bool) -> jax.Array:
    """cols: (K, n) predicate columns; lo/hi: (K,) bounds; val: (n,).
    Computes sum(val[all(lo<=cols<hi)]) in a single fused pass."""
    k, n = cols.shape
    npad = (block - n % block) % block
    if npad:
        cols = jnp.pad(cols, ((0, 0), (0, npad)), constant_values=jnp.inf)
        val = jnp.pad(val, (0, npad))
    grid = (cols.shape[1] // block,)
    out = pl.pallas_call(
        _kernel_fused_pred,
        out_shape=jax.ShapeDtypeStruct((1, 1), val.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((k, block), lambda i: (0, i)),
            pl.BlockSpec((k, 1), lambda i: (0, 0)),
            pl.BlockSpec((k, 1), lambda i: (0, 0)),
            pl.BlockSpec((block,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((1, 1), lambda i: (0, 0)),
        interpret=interpret,
    )(cols, lo.reshape(k, 1), hi.reshape(k, 1), val)
    return out[0, 0]
