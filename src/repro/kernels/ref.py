"""Pure-jnp oracles for every Pallas kernel.

These are the reference semantics kernels are validated against
(interpret=True allclose sweeps in tests/test_kernels.py), AND the
execution path used on CPU (benchmarks) and in the dry-run lowering
(kernels are the TPU target; HLO cost analysis uses these — conservative,
since the Pallas forms strictly reduce HBM traffic)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def filter_reduce_sum(x, pred):
    return jnp.sum(jnp.where(pred, x, jnp.zeros_like(x)))


def filter_reduce_sum_multi(vals, pred):
    """vals (A, n), pred (n,) -> (A,) predicated row sums."""
    return jnp.sum(jnp.where(pred[None, :], vals, jnp.zeros_like(vals)),
                   axis=1)


def filter_reduce_q6(cols, lo, hi, val):
    keep = jnp.all((cols >= lo[:, None]) & (cols < hi[:, None]), axis=0)
    return jnp.sum(jnp.where(keep, val, jnp.zeros_like(val)))


def segment_sum(seg_ids, vals, num_segments):
    return jax.ops.segment_sum(vals, seg_ids, num_segments=num_segments)


def hash_to_slot(keys, cap_table):
    """Sort-based oracle for the open-addressing slot assignment: rows
    with equal keys share a slot, distinct keys get distinct slots.
    Slot numbering is ascending-key compact ids (the Pallas kernel uses
    hash positions instead — only the slots/table CONTRACT is shared,
    see kernels/hash_table.py)."""
    from .hash_table import empty_of

    empty = empty_of(keys.dtype)
    n = keys.shape[0]
    if n == 0:
        return (jnp.zeros((0,), jnp.int32),
                jnp.full((cap_table,), empty, keys.dtype),
                jnp.zeros((), jnp.int32))
    valid = keys != empty
    big = jnp.iinfo(keys.dtype).max
    pk = jnp.where(valid, keys, big)
    order = jnp.argsort(pk, stable=True)
    sk = pk[order]
    sval = valid[order]
    is_new = jnp.concatenate([sval[:1], (sk[1:] != sk[:-1]) & sval[1:]])
    seg = jnp.cumsum(is_new.astype(jnp.int32)) - 1
    seg = jnp.where(sval & (seg < cap_table), seg, cap_table)
    slots = jnp.zeros((n,), jnp.int32).at[order].set(seg)
    used = is_new.sum().astype(jnp.int32)
    table = jnp.full((cap_table,), empty, keys.dtype).at[
        jnp.where(is_new, seg, cap_table)
    ].set(jnp.where(is_new, sk, empty), mode="drop")
    return slots, table, used


def dict_probe(table_keys, count, queries):
    """Binary-search oracle for the one-hot membership probe: table keys
    are sorted ascending for the first `count` slots (parked slots are
    neutralized here so a stale tail cannot break the search)."""
    cap = table_keys.shape[0]
    n = queries.shape[0]
    if n == 0:
        return jnp.zeros((0,), jnp.int32), jnp.zeros((0,), bool)
    big = jnp.iinfo(jnp.int64).max
    cnt = jnp.asarray(count, jnp.int32)
    neut = jnp.where(jnp.arange(cap) < cnt, table_keys.astype(jnp.int64), big)
    q = queries.astype(jnp.int64)
    pos = jnp.searchsorted(neut, q).astype(jnp.int32)
    posc = jnp.clip(pos, 0, cap - 1)
    found = (neut[posc] == q) & (posc < cnt)
    return jnp.where(found, posc, jnp.int32(0)), found


def group_build(keys, capacity):
    """Sort-based oracle for the CSR group build: rows with equal keys
    share an ascending-key compact slot; ``offsets`` are the CSR group
    boundaries over those slots; ``used`` counts distinct valid keys
    (``used > capacity`` = overflow, callers poison — the contract
    shared with kernels/group_build.py)."""
    from .hash_table import empty_of

    cap = int(capacity)
    n = keys.shape[0]
    if n == 0:
        return (jnp.zeros((0,), jnp.int32),
                jnp.zeros((cap + 1,), jnp.int32),
                jnp.zeros((), jnp.int32))
    valid = keys != empty_of(keys.dtype)
    big = jnp.iinfo(keys.dtype).max
    pk = jnp.where(valid, keys, big)
    order = jnp.argsort(pk, stable=True)
    sk = pk[order]
    sval = valid[order]
    is_new = jnp.concatenate([sval[:1], (sk[1:] != sk[:-1]) & sval[1:]])
    seg = jnp.cumsum(is_new.astype(jnp.int32)) - 1
    seg = jnp.where(sval & (seg < cap), seg, cap)
    cslots = jnp.zeros((n,), jnp.int32).at[order].set(seg)
    used = is_new.sum().astype(jnp.int32)
    counts = jax.ops.segment_sum(
        jnp.where(seg < cap, 1, 0), seg, num_segments=cap + 1
    )[:cap]
    offsets = jnp.concatenate([
        jnp.zeros((1,), jnp.int32),
        jnp.cumsum(counts).astype(jnp.int32),
    ])
    return cslots, offsets, used


def group_probe(table_keys, offsets, count, queries):
    """Binary-search oracle for the fused membership + match-count probe
    of the m:n expansion: ``(pos, found, sizes)`` per query, ``sizes``
    read off the CSR offsets (0 on a miss)."""
    cap = table_keys.shape[0]
    n = queries.shape[0]
    if n == 0 or cap == 0:
        z = jnp.zeros((n,), jnp.int32)
        return z, jnp.zeros((n,), bool), z
    big = jnp.iinfo(jnp.int64).max
    cnt = jnp.asarray(count, jnp.int32)
    neut = jnp.where(jnp.arange(cap) < cnt, table_keys.astype(jnp.int64), big)
    q = queries.astype(jnp.int64)
    pos = jnp.searchsorted(neut, q).astype(jnp.int32)
    posc = jnp.clip(pos, 0, cap - 1)
    found = (neut[posc] == q) & (posc < cnt)
    sizes = (offsets[1:] - offsets[:-1]).astype(jnp.int32)[posc]
    return (jnp.where(found, posc, jnp.int32(0)), found,
            jnp.where(found, sizes, jnp.int32(0)))


def group_sum_exact(keys, values, n_groups):
    """int64 counts and sums per group id in [0, n_groups); rows with any
    other id are left out."""
    seg = jnp.where((keys >= 0) & (keys < n_groups), keys, n_groups)

    def total(v):
        return jax.ops.segment_sum(v.astype(jnp.int64), seg,
                                   num_segments=n_groups + 1)[:n_groups]

    return total(jnp.ones(keys.shape, jnp.int64)), tuple(
        total(v) for v in values)


def segment_sum_vectors(seg_ids, vals, num_segments):
    return jax.ops.segment_sum(vals, seg_ids, num_segments=num_segments)


def adamw_update(p, g, m, v, lr, step, *, b1=0.9, b2=0.999, eps=1e-8,
                 wd=0.01):
    lr = jnp.asarray(lr, p.dtype)
    t = jnp.asarray(step, p.dtype)
    m_new = b1 * m + (1.0 - b1) * g
    v_new = b2 * v + (1.0 - b2) * g * g
    m_hat = m_new / (1.0 - jnp.power(jnp.asarray(b1, p.dtype), t))
    v_hat = v_new / (1.0 - jnp.power(jnp.asarray(b2, p.dtype), t))
    p_new = p - lr * (m_hat / (jnp.sqrt(v_hat) + eps) + wd * p)
    return p_new, m_new, v_new


def tiled_matmul(a, b):
    return jnp.dot(a, b, preferred_element_type=a.dtype)


def map_elementwise(fn, arrays):
    out = fn(*[jnp.asarray(a) for a in arrays])
    return jnp.broadcast_to(out, jnp.asarray(arrays[0]).shape)


def attention(q, k, v, *, causal=True, group=1, scale=None):
    """q: (H, Sq, D); k/v: (H//group, Skv, D) — dense reference."""
    h, sq, d = q.shape
    scale = float(scale if scale is not None else d ** -0.5)
    if group > 1:
        k = jnp.repeat(k, group, axis=0)
        v = jnp.repeat(v, group, axis=0)
    s = jnp.einsum("hqd,hkd->hqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    skv = k.shape[1]
    if causal:
        offset = skv - sq
        qi = jnp.arange(sq)[:, None] + offset
        kj = jnp.arange(skv)[None, :]
        s = jnp.where(kj <= qi, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hqk,hkd->hqd", p, v.astype(jnp.float32)).astype(q.dtype)


def chunked_attention(q, k, v, *, causal=True, group=1, scale=None,
                      chunk=1024, unroll=False):
    """Memory-bounded jnp attention (lax.scan over kv chunks with online
    softmax) — the production ref path for long sequences; equals
    `attention` but with O(Sq*chunk) live score memory."""
    h, sq, d = q.shape
    hk, skv, _ = k.shape
    scale = float(scale if scale is not None else d ** -0.5)
    pad = (-skv) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
    nck = k.shape[1] // chunk
    kc = k.reshape(hk, nck, chunk, d).transpose(1, 0, 2, 3)
    vc = v.reshape(hk, nck, chunk, d).transpose(1, 0, 2, 3)
    offset = skv - sq
    qf = q.astype(jnp.float32)

    def step(carry, inp):
        m_prev, l_prev, acc = carry
        jc, kb, vb = inp
        if group > 1:
            kb = jnp.repeat(kb, group, axis=0)
            vb = jnp.repeat(vb, group, axis=0)
        s = jnp.einsum("hqd,hkd->hqk", qf, kb.astype(jnp.float32)) * scale
        kj = jc * chunk + jnp.arange(chunk)[None, :]
        s = jnp.where(kj[None] < skv, s, -1e30)
        if causal:
            qi = jnp.arange(sq)[:, None] + offset
            s = jnp.where(kj[None] <= qi[None], s, -1e30)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "hqk,hkd->hqd", p, vb.astype(jnp.float32))
        return (m_new, l_new, acc), None

    init = (
        jnp.full((h, sq), -1e30, jnp.float32),
        jnp.zeros((h, sq), jnp.float32),
        jnp.zeros((h, sq, d), jnp.float32),
    )
    (m, l, acc), _ = jax.lax.scan(
        step, init, (jnp.arange(nck), kc, vc), unroll=bool(unroll)
    )
    return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
