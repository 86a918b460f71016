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


# -- exact integer group sums (the dictmerger route over integer values) -----
#
# A group-by over a dense key of at most ``MAX_GROUPS`` values whose merged
# values are integers must equal the int64 answer bit for bit, where the
# one-hot MXU form above rounds.  Each value enters the kernel as 32-bit
# containers (an int64 as its low and high halves, a narrower int as
# itself, sign-extended) and each container as two 16-bit words:
#
#     lo = x & 0xFFFF             in [0, 2**16)
#     hi = x >> 16                in [-2**15, 2**15)  (the top container:
#                                                      it carries the sign)
#     hi = (x >> 16) & 0xFFFF     in [0, 2**16)       (any other container)
#
# so ``x == lo + hi * 2**16`` and a value is the sum of its words times
# powers of 2**16, modulo 2**64.  The kernel sums every word per group on
# the VPU in int32, per lane: a lane of one chunk's partial adds at most
# ``CHUNK_ROWS / 128 = 2**15`` words, each of magnitude at most 2**16 - 1,
# so it stays within 2**15 * (2**16 - 1) = 2,147,450,880 < 2**31 - 1 and
# never wraps.  A count is a word of 1 per row.  ``combine_partials``
# then adds the partials of every chunk and lane as 16-bit halves in
# int32 and carries the digits up (see there), and assembles each value
# modulo 2**64, the ring NumPy's int64 sums wrap in: NumPy's result
# exactly.

#: dense group keys the exact route takes: every group costs each word a
#: compare-select and an add per row.
MAX_GROUPS = 64
#: rows per partial: (CHUNK_ROWS / 128) * (2**16 - 1) < 2**31.
CHUNK_ROWS = 1 << 22
#: rows per grid step: 512 sublane rows of 128 lanes (a divisor of
#: CHUNK_ROWS, so no step straddles two partials).
GROUP_BLOCK = 1 << 16
#: chunks the int32 combine takes: 2**29 rows (8.9 x TPC-H SF10's lineitem).
MAX_CHUNKS = 128
LANES = 128


def containers(dtypes) -> tuple:
    """For each value dtype, its 32-bit containers' ``top`` flags: one
    container for a value of at most 32 bits, two for a 64-bit one."""
    return tuple((True,) if jnp.dtype(d).itemsize <= 4 else (False, True)
                 for d in dtypes)


def words_per_row(dtypes) -> int:
    """Words the kernel sums per row and group: two per container, and
    the count."""
    return 1 + 2 * sum(len(c) for c in containers(dtypes))


def group_planes(keys, values, block: int = GROUP_BLOCK):
    """The kernel's input: the key and every value's containers as one
    ``(1 + C, rows / 128, 128)`` int32 stack, rows padded to a multiple of
    ``block`` with key -1 (a row of no group).  ``keys`` are int32 group
    ids, -1 for a row left out; ``values`` integer columns."""
    cols = [keys.astype(jnp.int32)]
    for v in values:
        if jnp.dtype(v.dtype).itemsize <= 4:
            cols.append(v.astype(jnp.int32))
        else:
            v = v.astype(jnp.int64)
            # integer narrowing keeps the low 32 bits
            cols += [v.astype(jnp.int32), (v >> 32).astype(jnp.int32)]
    n = keys.shape[0]
    npad = (-n) % block
    planes = [jnp.pad(c, (0, npad), constant_values=-1 if k == 0 else 0)
              for k, c in enumerate(cols)]
    return jnp.stack(planes).reshape(len(cols), (n + npad) // LANES, LANES)


def _group_kernel(planes_ref, o_ref, *, n_groups: int, tops: tuple,
                  steps_per_chunk: int):
    @pl.when(pl.program_id(0) % steps_per_chunk == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    width = 1 + 2 * len(tops)
    key = planes_ref[0]                                  # (R, 128) int32
    masks = [key == g for g in range(n_groups)]

    def add(g, w, x):
        r = g * width + w
        o_ref[0, r:r + 1, :] += jnp.sum(
            jnp.where(masks[g], x, 0), axis=0, keepdims=True)

    one = jnp.ones_like(key)
    for g in range(n_groups):
        add(g, 0, one)
    for c, top in enumerate(tops):
        x = planes_ref[1 + c]
        lo = x & 0xFFFF
        hi = x >> 16 if top else (x >> 16) & 0xFFFF
        for g in range(n_groups):
            add(g, 1 + 2 * c, lo)
            add(g, 2 + 2 * c, hi)


def group_sum_words(planes, n_groups: int, tops: tuple, *,
                    block: int = GROUP_BLOCK, interpret: bool):
    """Per-chunk, per-lane int32 partials ``(chunks, G * W, 128)`` of the
    words of ``group_planes`` output: row ``g * W`` counts group ``g``'s
    rows, rows ``g * W + 1 + 2c`` and ``+ 2 + 2c`` sum container ``c``'s
    low and high words.  A partial adds at most ``CHUNK_ROWS / 128``
    words of magnitude below 2**16 (a count adds ones), so it lies in
    (-2**31, 2**31): exact in int32."""
    assert n_groups <= MAX_GROUPS, "too many groups for the exact route"
    assert CHUNK_ROWS % block == 0 and block % (8 * LANES) == 0
    c1, r, _ = planes.shape
    rows = block // LANES
    steps = r // rows
    spc = CHUNK_ROWS // block
    chunks = max(-(-steps // spc), 1)
    width = 1 + 2 * len(tops)
    if steps == 0:
        return jnp.zeros((1, n_groups * width, LANES), jnp.int32)
    return pl.pallas_call(
        functools.partial(_group_kernel, n_groups=n_groups, tops=tops,
                          steps_per_chunk=spc),
        out_shape=jax.ShapeDtypeStruct((chunks, n_groups * width, LANES),
                                       jnp.int32),
        grid=(steps,),
        in_specs=[pl.BlockSpec((c1, rows, LANES), lambda i: (0, i, 0))],
        out_specs=pl.BlockSpec((1, n_groups * width, LANES),
                               lambda i: (i // spc, 0, 0)),
        interpret=interpret,
    )(planes)


def combine_partials(partials, n_groups: int, per_value):
    """int64 group counts ``(G,)`` and one int64 sum ``(G,)`` per value
    from the kernel's partials.  ``per_value`` gives each value's number
    of containers (``len`` of its ``containers`` entry).

    Only the last step touches 64-bit values: XLA on the TPU emulates
    them, and its 64-bit sums and products of these totals came out
    wrong, so the totals stay in int32 digits of 16 bits until then.
    Each partial splits into a low half in [0, 2**16) and a signed high
    half; a half summed over 128 lanes and at most ``MAX_CHUNKS`` chunks
    stays below 2**16 * 128 * 128 = 2**30, and a digit position adds one
    low and one high sum (< 2**31).  Carries then run up the positions in
    int32, and four 16-bit digits make the value modulo 2**64."""
    chunks, rows, _ = partials.shape
    assert chunks <= MAX_CHUNKS, "too many rows for the int32 combine"
    width = 1 + 2 * sum(per_value)
    parts = partials.reshape(chunks, n_groups, width, LANES)
    lo = (parts & 0xFFFF).sum(axis=(0, 3), dtype=jnp.int32)  # < 2**30
    hi = (parts >> 16).sum(axis=(0, 3), dtype=jnp.int32)     # |.| < 2**29

    def value(first: int, words: int):
        """Words ``first .. first + words - 1`` (word j at 16 * j bits) as
        int64, modulo 2**64."""
        digits, carry = [], jnp.zeros((n_groups,), jnp.int32)
        for pos in range(4):
            d = carry
            if pos < words:
                d = d + lo[:, first + pos]
            if 0 < pos <= words:
                d = d + hi[:, first + pos - 1]
            digits.append(d & 0xFFFF)
            carry = d >> 16
        low = digits[0] | (digits[1] << 16)      # the low and high 32
        high = digits[2] | (digits[3] << 16)     # bits, as int32 patterns
        return ((high.astype(jnp.int64) << 32)
                | (low.astype(jnp.int64) & 0xFFFFFFFF))

    sums, w = [], 1
    for k in per_value:
        sums.append(value(w, 2 * k))
        w += 2 * k
    return value(0, 1), tuple(sums)
