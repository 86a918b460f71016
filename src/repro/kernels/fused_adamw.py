"""Fused AdamW update — the framework's weld-fused optimizer hot-spot.

Plain AdamW is ~10 elementwise ops per parameter: executed per-op (the
function-call interface) that is 10 HBM round-trips per step.  Expressed
as one Weld loop it fuses to a single pass; this kernel is that fused
pass as an explicit Pallas kernel: reads (p, g, m, v) tiles into VMEM
once, performs the whole update chain on the VPU, writes (p, m, v) once —
4 reads + 3 writes instead of ~20 accesses, i.e. ~3x less HBM traffic for
a purely memory-bound step.

Block: 4 arrays × 64 KiB f32 tiles (16384 lanes) = 512 KiB VMEM in-flight.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK = 16 * 1024
#: autotune grid — 4 arrays in flight per block, so the top end (64 KiB
#: lanes = 1 MiB f32 in-flight) still leaves VMEM double-buffer headroom.
BLOCK_CANDIDATES = (4 * 1024, 16 * 1024, 64 * 1024)


def _kernel(p_ref, g_ref, m_ref, v_ref, lr_ref, t_ref,
            po_ref, mo_ref, vo_ref, *,
            b1: float, b2: float, eps: float, wd: float):
    p = p_ref[...]
    g = g_ref[...]
    m = m_ref[...]
    v = v_ref[...]
    lr = lr_ref[0]
    t = t_ref[0]

    m_new = b1 * m + (1.0 - b1) * g
    v_new = b2 * v + (1.0 - b2) * g * g
    # bias correction
    c1 = 1.0 - jnp.power(jnp.float32(b1), t)
    c2 = 1.0 - jnp.power(jnp.float32(b2), t)
    m_hat = m_new / c1
    v_hat = v_new / c2
    update = m_hat / (jnp.sqrt(v_hat) + eps) + wd * p
    po_ref[...] = p - lr * update
    mo_ref[...] = m_new
    vo_ref[...] = v_new


def adamw_update(p: jax.Array, g: jax.Array, m: jax.Array, v: jax.Array,
                 lr, step, *, b1: float = 0.9, b2: float = 0.999,
                 eps: float = 1e-8, wd: float = 0.01,
                 block: int = BLOCK, interpret: bool):
    """One fused AdamW step over a flat f32 parameter shard.
    Returns (p_new, m_new, v_new)."""
    n = p.shape[0]
    npad = (block - n % block) % block
    if npad:
        p, g, m, v = (jnp.pad(a, (0, npad)) for a in (p, g, m, v))
    grid = (p.shape[0] // block,)
    lr = jnp.asarray(lr, jnp.float32).reshape(1)
    t = jnp.asarray(step, jnp.float32).reshape(1)
    shp = jax.ShapeDtypeStruct(p.shape, p.dtype)
    po, mo, vo = pl.pallas_call(
        functools.partial(_kernel, b1=b1, b2=b2, eps=eps, wd=wd),
        out_shape=(shp, shp, shp),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((1,), lambda i: (0,)),
            pl.BlockSpec((1,), lambda i: (0,)),
        ],
        out_specs=(
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
        ),
        interpret=interpret,
    )(p, g, m, v, lr, t)
    if npad:
        po, mo, vo = po[:n], mo[:n], vo[:n]
    return po, mo, vo
