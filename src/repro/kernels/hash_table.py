"""Open-addressing hash-to-slot kernel — dictmerger builds with sparse keys.

The dense group-by route (``segment_reduce``) requires int keys in
``[0, capacity)``; this kernel lifts that restriction.  It assigns every
input key a *slot* in an open-addressing table (linear probing,
Fibonacci hashing), so rows with equal keys share a slot and distinct
keys get distinct slots.  Downstream value accumulation is then an
ordinary segment reduction over the slot ids — the existing one-hot MXU
``segment_sum`` kernels — followed by a sort-based compaction into the
backend's sorted-front-packed dict layout.

TPU adaptation: inserts are inherently serial (a later row must observe
an earlier row's insert), so the kernel walks each row block with a
``fori_loop`` while the grid streams blocks sequentially.  Every access
is a scalar load or store at a data-dependent address, which the TPU
serves from SMEM: the key block, the slot block and the table all live
there, and the table persists across grid steps in the output ref,
exactly like the running accumulator in ``filter_reduce``.

Slot numbering is implementation-defined: the Pallas kernel yields hash
positions, the jnp oracle (``ref.hash_to_slot``) yields ascending-key
compact ids.  Callers must only rely on the slots/table contract below,
which is what ``kernelplan.registry`` normalizes into a sorted dict.

Contract (shared with ``ref.hash_to_slot``):

* ``keys`` are int32 (a single key column of at most 32 bits — the only
  width Mosaic lowers) or int64 (the packed key space, see jaxgen
  ``_pack_keys``; ref/interpret only); rows equal to ``empty_of(dtype)``
  are padding/masked and get slot ``cap_table``;
* returns ``(slots, table_keys, used)`` with ``slots[i]`` in
  ``[0, cap_table]`` (``cap_table`` = parked), ``table_keys[slot]`` the
  key occupying a slot (the sentinel when free, same dtype as ``keys``),
  and ``used`` the number of distinct keys inserted.  A full table drops
  rows but then ``used == cap_table``, which callers size
  (``cap_table >= 2*capacity``) so overflow is always detectable as
  ``used > capacity``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: sentinel for "no key" in the packed 64-bit space: reserved, never a
#: valid packed key in practice (single-column int keys keep their full
#: value; multi-column keys pack 32 bits per column, so hitting INT64_MIN
#: needs a -2^31 leading key — the build adapter detects the clash and
#: poisons the dict rather than conflate).
EMPTY = int(np.iinfo(np.int64).min)

#: largest dict capacity the hash route serves; the table itself is
#: 2*capacity rounded up to a power of two (load factor <= 0.5).
MAX_CAP = 65536

#: Fibonacci multiplicative hashing constants (golden-ratio reciprocal)
#: for the 64- and 32-bit key spaces.
_GOLD64 = np.uint64(0x9E3779B97F4A7C15)
_GOLD32 = np.uint32(0x9E3779B9)

BLOCK_N = 1024
#: autotune grid for the row block: bigger blocks amortize grid steps,
#: smaller ones bound the per-step serial insert chain.
BLOCK_CANDIDATES = (1024, 2048, 4096)


def empty_of(dtype) -> int:
    """The reserved "no key" value of a key space (its dtype's minimum)."""
    return int(np.iinfo(np.dtype(dtype)).min)


def table_size(capacity: int) -> int:
    """Power-of-two open-addressing table for `capacity` distinct keys."""
    c = 16
    while c < 2 * capacity:
        c <<= 1
    return c


def _hash0(k, cap_table: int):
    """Initial probe position: high bits of the Fibonacci product in the
    key's own width."""
    lg = int(cap_table).bit_length() - 1
    if jnp.dtype(k.dtype).itemsize <= 4:
        ku = k.astype(jnp.uint32) * _GOLD32
        return (ku >> np.uint32(32 - lg)).astype(jnp.int32)
    ku = k.astype(jnp.uint64) * _GOLD64
    return (ku >> np.uint64(64 - lg)).astype(jnp.int32)


def _kernel(keys_ref, slots_ref, table_ref, used_ref, *, cap_table: int,
            empty: int):
    mask = cap_table - 1

    @pl.when(pl.program_id(0) == 0)
    def _init():
        def clear(s, c):
            table_ref[s] = jnp.asarray(empty, table_ref.dtype)
            return c

        jax.lax.fori_loop(0, cap_table, clear, 0)
        used_ref[0, 0] = 0

    def insert(j, used):
        k = keys_ref[j]
        valid = k != empty

        def probe_cond(s):
            t, slot, done = s
            return jnp.logical_not(done) & (t < cap_table)

        def probe_body(s):
            t, slot, done = s
            cur = table_ref[slot]
            hit = (cur == k) | (cur == empty)
            nxt = jnp.where(hit, slot, (slot + 1) & mask)
            return t + 1, nxt, hit

        _, slot, done = jax.lax.while_loop(
            probe_cond, probe_body, (0, _hash0(k, cap_table), ~valid))
        cur = table_ref[slot]
        do_store = valid & done & (cur == empty)
        table_ref[slot] = jnp.where(do_store, k, cur)
        slots_ref[j] = jnp.where(valid & done, slot, cap_table)
        return used + do_store.astype(jnp.int32)

    used_ref[0, 0] += jax.lax.fori_loop(0, keys_ref.shape[0], insert, 0)


def hash_to_slot(keys: jax.Array, cap_table: int, *, block: int = BLOCK_N,
                 interpret: bool):
    """Assign an open-addressing slot to every key; see module contract."""
    assert cap_table & (cap_table - 1) == 0, "table size must be pow2"
    empty = empty_of(keys.dtype)
    n = keys.shape[0]
    if n == 0:
        return (jnp.zeros((0,), jnp.int32),
                jnp.full((cap_table,), empty, keys.dtype),
                jnp.zeros((), jnp.int32))
    npad = (block - n % block) % block
    if npad:
        keys = jnp.pad(keys, (0, npad), constant_values=empty)
    smem = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)
    slots, table, used = pl.pallas_call(
        functools.partial(_kernel, cap_table=cap_table, empty=empty),
        out_shape=(
            jax.ShapeDtypeStruct((keys.shape[0],), jnp.int32),
            jax.ShapeDtypeStruct((cap_table,), keys.dtype),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ),
        grid=(keys.shape[0] // block,),
        in_specs=[smem((block,), lambda i: (i,))],
        out_specs=(
            smem((block,), lambda i: (i,)),
            smem((cap_table,), lambda i: (0,)),
            smem((1, 1), lambda i: (0, 0)),
        ),
        interpret=interpret,
    )(keys)
    return slots[:n], table, used[0, 0]
