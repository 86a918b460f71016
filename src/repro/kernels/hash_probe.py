"""Dictionary probe kernel — the gather side of the hash-join plan.

Given a dict in the backend's sorted-front-packed column layout
(``WDict``: keys ascending for the first ``count`` slots), find each
query key's slot and whether it exists.  The TPU-native strategy
replaces a divergent binary search per lane with a **broadcast
membership sweep**: the table's keys sit in SMEM, and each query block
(a VMEM vector tile) is compared against them one key at a time,

    hit_c = (queries == table[c]);  pos = where(hit_c, c, pos)

for ``c < count`` — every compare is a full-width VPU op against a
scalar broadcast, no gather, no relayout.  Keys are unique, so at most
one ``c`` hits a query.  The sweep's length is the dict's live count,
bounded by its capacity (<= ``hash_table.MAX_CAP``).

The value gather itself happens outside the kernel (``vals[pos]``): the
positions serve any value dtype/struct without specializing the kernel.
That split is what lets weldrel's horizontally fused join probe reuse
ONE launch for every output column — inner joins front-pack by the
found mask, left joins keep every row and select per-dtype fills where
``found`` is false, anti joins front-pack by its negation — all from
the same ``(pos, found)`` pair (``kernelplan.registry``,
``_exec_hash_probe_fused``).  The front-pack is one stable sort keyed
on the mask that carries every output column as a payload
(``jaxgen.front_pack``), so no output column is gathered through a
permutation; the build columns' gathers ``vals[pos]`` are the only
gathers left.

Contract (shared with ``ref.dict_probe``): queries and table keys share
one key space (int32 for a single key column of at most 32 bits, else
the packed int64 space — ref/interpret only); returns ``(pos, found)``
with ``pos`` int32, zeroed where not found.

``group_probe`` is the m:n-join variant: the SAME sweep also selects
each matching group's fan-out (CSR ``offsets`` diffs, in SMEM beside the
keys), so membership, slot positions, and the expansion's match-count
pass are one launch; the expansion itself (exclusive scan +
repeat/gather) runs outside, shared by every output column
(``kernelplan.registry._exec_group_probe``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_N = 4096
#: autotune grid for the query block: multiples of 1024 (the TPU tiling of
#: a 1-D 32-bit operand); bigger blocks amortize the scalar sweep over
#: more query lanes.
BLOCK_CANDIDATES = (1024, 4096, 8192)


def _sweep(q, cnt, keys_ref, sizes_ref=None):
    """(pos, size) per query lane; pos is -1 on a miss."""
    def body(c, carry):
        pos, size = carry
        hit = q == keys_ref[c]
        pos = jnp.where(hit, c, pos)
        if sizes_ref is not None:
            size = jnp.where(hit, sizes_ref[c], size)
        return pos, size

    init = (jnp.full(q.shape, -1, jnp.int32), jnp.zeros(q.shape, jnp.int32))
    return jax.lax.fori_loop(0, cnt, body, init)


def _kernel(cnt_ref, keys_ref, q_ref, pos_ref, found_ref):
    pos, _ = _sweep(q_ref[...], cnt_ref[0, 0], keys_ref)
    found_ref[...] = (pos >= 0).astype(jnp.int32)
    pos_ref[...] = jnp.maximum(pos, 0)


def _group_kernel(cnt_ref, keys_ref, sizes_ref, q_ref, pos_ref, found_ref,
                  size_ref):
    pos, size = _sweep(q_ref[...], cnt_ref[0, 0], keys_ref, sizes_ref)
    found_ref[...] = (pos >= 0).astype(jnp.int32)
    pos_ref[...] = jnp.maximum(pos, 0)
    size_ref[...] = size


def _probe_call(kernel, tables, queries, count, block: int, n_out: int,
                interpret: bool):
    """Launch one sweep kernel: ``tables`` ride whole in SMEM, queries
    stream in ``block``-lane VMEM tiles; returns ``n_out`` int32 columns
    trimmed to the query count."""
    n = queries.shape[0]
    npad = (block - n % block) % block
    if npad:
        queries = jnp.pad(queries, (0, npad))
    # the sweep reads SMEM at every c < count: clamp a poisoned or
    # oversized count into the table
    cnt = jnp.clip(jnp.asarray(count, jnp.int32), 0,
                   tables[0].shape[0]).reshape(1, 1)
    smem = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)
    lane = pl.BlockSpec((block,), lambda i: (i,))
    col = jax.ShapeDtypeStruct((queries.shape[0],), jnp.int32)
    outs = pl.pallas_call(
        kernel,
        out_shape=(col,) * n_out,
        grid=(queries.shape[0] // block,),
        in_specs=[smem((1, 1), lambda i: (0, 0))]
        + [smem(t.shape, lambda i: (0,)) for t in tables] + [lane],
        out_specs=(lane,) * n_out,
        interpret=interpret,
    )(cnt, *tables, queries)
    return [o[:n] for o in outs]


def group_probe(table_keys: jax.Array, offsets: jax.Array, count,
                queries: jax.Array, *, block: int = BLOCK_N,
                interpret: bool):
    """(pos, found, sizes) per query against a groupbuilder's sorted
    key column + CSR offsets — the membership AND match-count pass of
    the m:n join expansion in ONE launch (``sizes`` is 0 on a miss).
    Contract shared with ``ref.group_probe``."""
    cap = table_keys.shape[0]
    n = queries.shape[0]
    if n == 0 or cap == 0:
        z = jnp.zeros((n,), jnp.int32)
        return z, jnp.zeros((n,), bool), z
    sizes = (offsets[1:] - offsets[:-1]).astype(jnp.int32)
    pos, found, size = _probe_call(
        _group_kernel, (table_keys.astype(queries.dtype), sizes), queries,
        count, block, 3, interpret)
    return pos, found.astype(bool), size


def dict_probe(table_keys: jax.Array, count, queries: jax.Array, *,
               block: int = BLOCK_N, interpret: bool):
    """pos/found per query against sorted-front-packed dict keys."""
    n = queries.shape[0]
    if n == 0 or table_keys.shape[0] == 0:
        return jnp.zeros((n,), jnp.int32), jnp.zeros((n,), bool)
    pos, found = _probe_call(_kernel, (table_keys.astype(queries.dtype),),
                             queries, count, block, 2, interpret)
    return pos, found.astype(bool)
