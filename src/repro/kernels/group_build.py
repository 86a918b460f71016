"""Group (CSR) build kernel — groupbuilder with sparse keys.

The m:n hash-join build side stores *every* build row under its key
(key -> growing vector of row ids), not one accumulated value.  The
TPU-native layout is CSR: one ``offsets`` array over ascending-key
compact slots plus the row payloads sorted by slot — variable-length
groups with no pointer chasing, and the probe side can fetch a group's
fan-out as ``offsets[s+1] - offsets[s]``.

The build composes three steps:

1. **hash-to-slot** (reused from :mod:`.hash_table`): the open-addressing
   Pallas kernel assigns every row a table slot, so rows with equal
   keys share a slot;
2. **rank compaction** (jnp glue, same as the dictmerger hash route):
   table slots are renumbered into ascending-key compact ids, matching
   the backend's sorted-front-packed dict layout;
3. **slot histogram** (the Pallas kernel in this module): per-slot row
   counts accumulated in a table that persists across grid steps, then
   an exclusive scan into the CSR ``offsets``.

Like the insert chain, the histogram is inherently random-access, so the
kernel walks each row block with a ``fori_loop`` while the grid streams
blocks sequentially, with the slot block and the counts table in SMEM —
the same serial-grid pattern as ``hash_table``.

Contract (shared with ``ref.group_build``):

* ``keys`` are int32 or packed int64 (see :mod:`.hash_table`); rows
  equal to ``hash_table.empty_of(dtype)`` are padding/masked and park at
  slot ``capacity``;
* returns ``(cslots, offsets, used)``: ``cslots[i]`` in ``[0, capacity]``
  is row ``i``'s ascending-key compact slot (``capacity`` = parked),
  ``offsets`` is the ``(capacity+1,)`` int32 CSR boundary array over
  the first ``used`` slots, and ``used`` counts distinct keys inserted.
  ``used > capacity`` signals overflow; callers must poison then (which
  keys survive into the truncated slots is implementation-defined —
  the ref oracle keeps the smallest, the hash table whatever fit).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .hash_table import empty_of, hash_to_slot, table_size

BLOCK_N = 1024
#: autotune grid for the row block (shared shape with hash_table: the
#: serial insert/count chains bound the per-step latency).
BLOCK_CANDIDATES = (1024, 2048, 4096)


def _hist_kernel(slots_ref, cnt_ref, *, nslots: int):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        def clear(s, c):
            cnt_ref[s] = 0
            return c

        jax.lax.fori_loop(0, nslots, clear, 0)

    def bump(j, c):
        s = slots_ref[j]
        cnt_ref[s] = cnt_ref[s] + 1
        return c

    jax.lax.fori_loop(0, slots_ref.shape[0], bump, 0)


def slot_hist(slots: jax.Array, num_slots: int, *, block: int = BLOCK_N,
              interpret: bool) -> jax.Array:
    """Per-slot row counts: ``out[s] = sum(slots == s)``; slots int32 in
    ``[0, num_slots)``.  Serial accumulation in an SMEM counts table."""
    n = slots.shape[0]
    if n == 0:
        return jnp.zeros((num_slots,), jnp.int32)
    npad = (block - n % block) % block
    if npad:
        # padding parks in the last slot, which group_build never reads
        slots = jnp.pad(slots, (0, npad), constant_values=num_slots - 1)
    smem = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)
    return pl.pallas_call(
        functools.partial(_hist_kernel, nslots=num_slots),
        out_shape=jax.ShapeDtypeStruct((num_slots,), jnp.int32),
        grid=(slots.shape[0] // block,),
        in_specs=[smem((block,), lambda i: (i,))],
        out_specs=smem((num_slots,), lambda i: (0,)),
        interpret=interpret,
    )(slots.astype(jnp.int32))


def group_build(keys: jax.Array, capacity: int, *, block: int = BLOCK_N,
                interpret: bool):
    """CSR group build over int32 / packed int64 keys; see the module
    contract."""
    cap = int(capacity)
    ctab = table_size(cap)
    n = keys.shape[0]
    slots, table, used = hash_to_slot(keys, ctab, block=block,
                                      interpret=interpret)
    if n == 0:
        return (jnp.zeros((0,), jnp.int32),
                jnp.zeros((cap + 1,), jnp.int32),
                used)
    # table slot -> ascending-key compact id (identical renumbering to
    # the dictmerger hash route, so probes see the sorted layout)
    big = jnp.iinfo(table.dtype).max
    tsort = jnp.where(table == empty_of(table.dtype), big, table)
    order = jnp.argsort(tsort)
    rank = jnp.zeros((ctab,), jnp.int32).at[order].set(
        jnp.arange(ctab, dtype=jnp.int32))
    cslots = jnp.where(slots < ctab, rank[jnp.clip(slots, 0, ctab - 1)],
                       jnp.int32(cap))
    cslots = jnp.where(cslots < cap, cslots, jnp.int32(cap))
    counts = slot_hist(cslots, cap + 1, block=block,
                       interpret=interpret)[:cap]
    offsets = jnp.concatenate([
        jnp.zeros((1,), jnp.int32),
        jnp.cumsum(counts).astype(jnp.int32),
    ])
    return cslots, offsets, used
