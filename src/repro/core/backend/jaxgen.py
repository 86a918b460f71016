"""Lower optimized Weld IR to a fused JAX program.

The emitter *interprets the IR while tracing*: running the emitted closure
under ``jax.jit`` stages one XLA program for the whole multi-library
workflow — the Weld evaluation point becomes exactly one compiled
executable, which is the paper's central mechanism.

Loop lowering ("vectorization", paper Table 3, adapted per DESIGN.md §2):

* A parallel ``for`` is evaluated in **vector form**: the element parameter
  is bound to the whole (tiled-by-XLA) array, builders become accumulator
  objects collecting masked contributions, and conditional control flow
  becomes predication masks.  This is the TPU-native analogue of the
  paper's AVX2 vectorization — the VPU consumes whole-array ops.
* Bodies that use their element as a *vector* (nested loops, e.g. a dot
  per row) fall back to ``jax.vmap`` over a scalar-world evaluation —
  the un-nesting transform the paper applies for its GPU backend.
* There is deliberately no sequential fallback: anything else raises
  ``WeldCompileError`` (see DESIGN.md §8.2 — SPMD hardware has no cheap
  dynamic parallelism, so we refuse rather than silently serialize).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import faults
from .. import ir
from .. import wtypes as wt
from ..cudf import has_cudf, lookup_cudf_jax
from ..errors import ResourceError, WeldError
from .values import WDict, WGroup, WVec


class WeldCompileError(WeldError):
    """The generic lowering refuses this program shape (not a kernel
    failure — see ``errors.KernelCompileError`` for those)."""


#: memory_limit breaches are typed ResourceError; the old name stays an
#: alias so existing imports/catch sites keep working.
WeldMemoryError = ResourceError


class _NeedsVmap(Exception):
    """Raised when a loop body needs its element as a vector."""


_NP_OF = {
    "bool": jnp.bool_, "i8": jnp.int8, "i32": jnp.int32,
    "i64": jnp.int64, "f32": jnp.float32, "f64": jnp.float64,
}


def _jdtype(ty: wt.Scalar):
    return _NP_OF[ty.kind]


# ---------------------------------------------------------------------------
# Builder accumulators
# ---------------------------------------------------------------------------


class _Acc:
    """Base accumulator.  Contributions are ('single', value) or
    ('batch', value, mask_or_None); struct values are tuples of arrays."""

    def __init__(self, bt: wt.BuilderType):
        self.bt = bt
        self.contribs: List[tuple] = []

    def add_single(self, value, mask=None):
        self.contribs.append(("single", value, mask))

    def add_batch(self, value, mask):
        self.contribs.append(("batch", value, mask))


class _MergerAcc(_Acc):
    def __init__(self, bt: wt.Merger, init=None):
        super().__init__(bt)
        self.init = init

    def finalize(self):
        acc = _identity_value(self.bt.elem, self.bt.op)
        if self.init is not None:
            acc = _combine(self.bt.op, acc, self.init)
        for kind, value, mask in self.contribs:
            if kind == "single":
                if mask is not None:
                    value = _select_struct(mask, value,
                                           _identity_value(self.bt.elem, self.bt.op))
                acc = _combine(self.bt.op, acc, value)
            else:
                red = _masked_reduce(self.bt, value, mask)
                acc = _combine(self.bt.op, acc, red)
        return acc


class _VecBuilderAcc(_Acc):
    def __init__(self, bt: wt.VecBuilder):
        super().__init__(bt)
        self.segments: List[tuple] = []  # sealed per enclosing loop

    def seal(self):
        """Called when an enclosing For finishes: fix the ordering of the
        contributions it produced (interleaved across merge sites)."""
        if not self.contribs:
            return
        batches = [(v, m) for k, v, m in self.contribs if k == "batch"]
        singles = [(v, m) for k, v, m in self.contribs if k == "single"]
        self.contribs = []
        if batches:
            vals = _interleave([b[0] for b in batches])
            masks = [
                b[1] if b[1] is not None
                else jnp.ones(_lead(b[0]), dtype=bool)
                for b in batches
            ]
            mask = _interleave(masks) if any(
                b[1] is not None for b in batches
            ) else None
            self.segments.append(("batch", vals, mask))
        for v, m in singles:
            self.segments.append(("single", v, m))

    def finalize(self):
        self.seal()
        if not self.segments:
            dt = _jdtype(self.bt.elem) if isinstance(self.bt.elem, wt.Scalar) else None
            if dt is None:
                raise WeldCompileError("empty struct vecbuilder")
            return WVec(jnp.zeros((0,), dtype=dt))
        if len(self.segments) == 1 and self.segments[0][0] == "batch":
            _, vals, mask = self.segments[0]
            if mask is None:
                return WVec(vals)
            return _compact(vals, mask)
        # general: concatenate segments (singles become length-1 batches)
        parts_v, parts_m = [], []
        for kind, v, m in self.segments:
            if kind == "single":
                v = jax.tree_util.tree_map(lambda a: jnp.asarray(a)[None], v)
                m = jnp.ones((1,), bool) if m is None else jnp.asarray(m)[None]
            else:
                m = jnp.ones(_lead(v), bool) if m is None else m
            parts_v.append(v)
            parts_m.append(m)
        vals = _concat_struct(parts_v)
        mask = jnp.concatenate(parts_m)
        return _compact(vals, mask)


class _VecMergerAcc(_Acc):
    def __init__(self, bt: wt.VecMerger, base):
        super().__init__(bt)
        if not isinstance(base, WVec):
            raise WeldCompileError("vecmerger needs a vector base")
        if not base.is_dense:
            raise WeldCompileError("vecmerger base must be dense")
        self.base = base

    def finalize(self):
        out = self.base.data
        ident = _identity_value(self.bt.elem, self.bt.op)
        for kind, value, mask in self.contribs:
            idx, v = value  # struct {i64, T}
            if kind == "single":
                idx = jnp.asarray(idx)[None]
                v = jax.tree_util.tree_map(lambda a: jnp.asarray(a)[None], v)
                mask = None if mask is None else jnp.asarray(mask)[None]
            if mask is not None:
                idx = jnp.where(mask, idx, 0)
                v = _select_struct(mask, v, ident)
            op = self.bt.op
            if op == "+":
                out = out.at[idx].add(v)
            elif op == "*":
                out = out.at[idx].multiply(v)
            elif op == "min":
                out = out.at[idx].min(v)
            elif op == "max":
                out = out.at[idx].max(v)
        return WVec(out)


class _DictMergerAcc(_Acc):
    def __init__(self, bt, capacity: int):
        super().__init__(bt)
        self.capacity = int(capacity)


class _GroupAcc(_Acc):
    def __init__(self, bt, capacity: int):
        super().__init__(bt)
        self.capacity = int(capacity)


def _finalize_keyed(acc, is_group: bool):
    """Shared finalize for dictmerger/groupbuilder: sort by packed key +
    segment-reduce (the TPU-native 'global builder' strategy — atomic-free)."""
    parts_k, parts_v, parts_m = [], [], []
    for kind, value, mask in acc.contribs:
        k, v = value
        if kind == "single":
            k = jax.tree_util.tree_map(lambda a: jnp.asarray(a)[None], k)
            v = jax.tree_util.tree_map(lambda a: jnp.asarray(a)[None], v)
            mask = None if mask is None else jnp.asarray(mask)[None]
        n = _lead(k)
        parts_k.append(k)
        parts_v.append(v)
        parts_m.append(jnp.ones(n, bool) if mask is None else mask)
    if not parts_k:
        raise WeldCompileError("empty dict builder")
    keys = _concat_struct(parts_k)
    vals = _concat_struct(parts_v)
    mask = jnp.concatenate(parts_m)

    packed = _pack_keys(keys)
    big = jnp.iinfo(jnp.int64).max
    packed = jnp.where(mask, packed, big)
    order = jnp.argsort(packed, stable=True)
    sp = packed[order]
    sk = _gather_struct(keys, order)
    sv = _gather_struct(vals, order)
    n = sp.shape[0]
    valid = sp != big
    is_new = jnp.concatenate([valid[:1], (sp[1:] != sp[:-1]) & valid[1:]])
    seg = jnp.cumsum(is_new.astype(jnp.int32)) - 1       # segment id per row
    seg = jnp.where(valid, seg, acc.capacity)            # park invalid rows
    count = is_new.sum()
    cap = acc.capacity
    # more distinct keys than capacity must POISON (negative count —
    # the same convention the kernel adapters use), never silently
    # truncate: the segment arrays below are only `cap` wide, so any
    # overflow would otherwise drop whole groups on the floor.  The
    # dict.build/group.build failpoints force the flag for tests.
    overflow = count > cap
    if faults.poisoned("group.build" if is_group else "dict.build"):
        overflow = True
    count = jnp.where(overflow, -count - 1, count)

    first_idx = jnp.where(is_new, jnp.arange(n), n)
    starts = jnp.sort(first_idx)[:cap]                   # first row per segment
    out_keys = _gather_struct(sk, jnp.clip(starts, 0, n - 1))

    if is_group:
        # values stay sorted-by-key; offsets via counts per segment
        ones = jnp.where(valid, 1, 0)
        sizes = jax.ops.segment_sum(ones, seg, num_segments=cap + 1)[:cap]
        offsets = jnp.concatenate(
            [jnp.zeros((1,), sizes.dtype), jnp.cumsum(sizes)]
        )
        return WGroup(out_keys, sv, offsets, count)

    opname = acc.bt.op
    segfn = {
        "+": jax.ops.segment_sum,
        "*": jax.ops.segment_prod,
        "min": jax.ops.segment_min,
        "max": jax.ops.segment_max,
    }[opname]

    def red(col):
        return segfn(col, seg, num_segments=cap + 1)[:cap]

    out_vals = jax.tree_util.tree_map(red, sv)
    return WDict(out_keys, out_vals, count)


# ---------------------------------------------------------------------------
# small helpers
# ---------------------------------------------------------------------------


def _lead(v) -> int:
    leaf = v[0] if isinstance(v, tuple) else v
    return leaf.shape[0]


def _interleave(vals: List):
    """[(n,...) x k] -> (n*k, ...) interleaved per-iteration."""
    if len(vals) == 1:
        return vals[0]
    if isinstance(vals[0], tuple):
        return tuple(
            _interleave([v[f] for v in vals]) for f in range(len(vals[0]))
        )
    stacked = jnp.stack(vals, axis=1)
    return stacked.reshape((-1,) + stacked.shape[2:])


def _concat_struct(parts: List):
    if isinstance(parts[0], tuple):
        return tuple(
            jnp.concatenate([p[f] for p in parts])
            for f in range(len(parts[0]))
        )
    return jnp.concatenate(parts)


def _gather_struct(v, idx):
    if isinstance(v, tuple):
        return tuple(f[idx] for f in v)
    return v[idx]


def _select_struct(mask, a, b):
    if isinstance(a, tuple):
        b = b if isinstance(b, tuple) else tuple(b for _ in a)
        return tuple(_select_struct(mask, x, y) for x, y in zip(a, b))
    return jnp.where(mask, a, b)


def _identity_value(ty, op):
    if isinstance(ty, wt.Struct):
        return tuple(_identity_value(f, op) for f in ty.fields)
    return jnp.asarray(wt.merge_identity(op, ty), dtype=_jdtype(ty))


def _combine(op, a, b):
    if isinstance(a, tuple):
        return tuple(_combine(op, x, y) for x, y in zip(a, b))
    return {
        "+": jnp.add, "*": jnp.multiply,
        "min": jnp.minimum, "max": jnp.maximum,
    }[op](a, b)


def _masked_reduce(bt: wt.Merger, value, mask):
    ident = _identity_value(bt.elem, bt.op)
    if mask is not None:
        value = _select_struct(mask, value, ident)
    fn = {
        "+": jnp.sum, "*": jnp.prod, "min": jnp.min, "max": jnp.max,
    }[bt.op]

    def red(x, iv):
        if hasattr(x, "shape") and x.ndim >= 1:
            if x.shape[0] == 0:  # empty loop: min/max have no jnp identity
                return jnp.asarray(iv)
            return fn(x, axis=0)
        return x

    return jax.tree_util.tree_map(red, value, ident)


def front_pack(keep, cols):
    """Stable partition by ``keep``: kept rows first, each side in row
    order — ``argsort(~keep, stable=True)`` applied to every column, as
    ONE sort keyed on ``~keep`` that carries the columns as payloads, so
    the permutation moves them in one pass instead of a gather per
    column.  ``cols`` is a column or a (nested) tuple of them (struct
    fields), each as long as ``keep``; the result has its structure."""
    leaves, tree = jax.tree_util.tree_flatten(cols)
    packed = jax.lax.sort((~keep, *leaves), num_keys=1, is_stable=True)
    return jax.tree_util.tree_unflatten(tree, packed[1:])


def _compact(vals, mask) -> WVec:
    """Front-pack valid elements (stable) — TPU compaction via sort."""
    return WVec(front_pack(mask, vals), count=mask.sum())


def _pack_keys(keys):
    """Pack a (possibly struct) key into one i64 for sorting.  A single
    int column keeps its full 64-bit value (injective — join keys must
    not conflate); multi-field struct keys are bit-packed 32 bits per
    field and floats are bit-cast (order-preserving for the grouping
    use case — equality only matters, not order)."""
    cols = list(keys) if isinstance(keys, tuple) else [keys]
    if len(cols) == 1 and not jnp.issubdtype(cols[0].dtype, jnp.floating):
        return cols[0].astype(jnp.int64)
    packed = jnp.zeros(_lead(keys), dtype=jnp.int64)
    for c in cols:
        if jnp.issubdtype(c.dtype, jnp.floating):
            # normalize -0.0 to +0.0 BEFORE the bitcast: IEEE equality
            # says they match, the bit patterns do not (mirrored in
            # weldrel._pack_host — the two packings must stay
            # byte-identical)
            c = jnp.where(c == 0, jnp.zeros_like(c), c)
            c = jax.lax.bitcast_convert_type(
                c.astype(jnp.float32), jnp.int32
            ).astype(jnp.int64)
        else:
            c = c.astype(jnp.int64)
        packed = packed * jnp.int64(1 << 32) + (c & jnp.int64(0xFFFFFFFF))
    return packed


def _dict_find(d: WDict, key):
    """Locate `key` (scalar, (n,) column, or tuple thereof for struct
    keys) in a dict's sorted-front-packed key columns.

    Returns ``(pos, found, scalar)`` — clipped slot positions, a hit
    mask, and whether the input was a single key.  Works batched, which
    is what lets a probe loop (hash-join) lower as whole-column gathers
    instead of a per-element vmap.  Parked slots (>= count) are
    neutralized to +inf before the binary search: dicts produced under a
    filter mask carry arbitrary key bits there.  A poisoned dict
    (negative count, see the kernelized group-by overflow guard) matches
    nothing."""
    packed_keys = _pack_keys(d.keys)
    cap = packed_keys.shape[0]
    valid_n = jnp.maximum(jnp.asarray(d.count, jnp.int64), 0)
    big = jnp.iinfo(jnp.int64).max
    kt = (
        tuple(jnp.asarray(a) for a in key)
        if isinstance(key, tuple) else jnp.asarray(key)
    )
    lead = kt[0] if isinstance(kt, tuple) else kt
    scalar = lead.ndim == 0
    if scalar:
        kt = jax.tree_util.tree_map(lambda a: a[None], kt)
    q = _pack_keys(kt)
    if cap == 0:  # empty build side (static): nothing can match
        zeros = jnp.zeros(q.shape, jnp.int64)
        return zeros, zeros.astype(bool), scalar
    table = jnp.where(jnp.arange(cap) < valid_n, packed_keys, big)
    pos = jnp.clip(jnp.searchsorted(table, q), 0, cap - 1)
    found = (table[pos] == q) & (pos < valid_n)
    return pos, found, scalar


def _group_find(g: WGroup, key):
    """Locate batched probe keys in a groupbuilder result's sorted key
    columns.  Returns ``(pos, found, sizes)`` — clipped slot positions,
    a hit mask, and the per-query group size (0 on a miss).  Parked
    slots (>= count) are neutralized before the binary search; a
    poisoned group (negative count) matches nothing."""
    packed = _pack_keys(g.keys)
    cap = packed.shape[0]
    valid_n = jnp.maximum(jnp.asarray(g.count, jnp.int64), 0)
    kt = (
        tuple(jnp.asarray(a) for a in key)
        if isinstance(key, tuple) else jnp.asarray(key)
    )
    q = _pack_keys(kt)
    if cap == 0:  # statically empty build side: nothing can match
        z = jnp.zeros(q.shape, jnp.int64)
        return z.astype(jnp.int32), z.astype(bool), z
    big = jnp.iinfo(jnp.int64).max
    table = jnp.where(jnp.arange(cap) < valid_n, packed, big)
    pos = jnp.clip(jnp.searchsorted(table, q), 0, cap - 1).astype(jnp.int32)
    found = (table[pos] == q) & (pos < valid_n)
    offs = jnp.asarray(g.offsets, jnp.int64)
    sizes_all = offs[1:] - offs[:-1]
    sizes = jnp.where(found, sizes_all[pos], jnp.int64(0))
    return pos, found, sizes


def expand_rows(cnt, out_cap: int):
    """Two-phase variable-length expansion: per-row repeat counts ->
    ``(rows, ordinals, total)``.  ``rows[j]`` is the source row of output
    slot ``j`` (exclusive-scan offsets + binary search), ``ordinals[j]``
    its position within that row's run; ``total`` is the dynamic output
    length materialized into the static ``out_cap`` buffer."""
    n = cnt.shape[0]
    cnt = jnp.asarray(cnt, jnp.int64)
    total = cnt.sum() if n else jnp.int64(0)
    if out_cap == 0 or n == 0:
        z = jnp.zeros((out_cap,), jnp.int64)
        return z, z, total
    ends = jnp.cumsum(cnt)
    starts = ends - cnt
    j = jnp.arange(out_cap, dtype=jnp.int64)
    rows = jnp.clip(jnp.searchsorted(ends, j, side="right"), 0, n - 1)
    ordinals = j - starts[rows]
    return rows, ordinals, total


def group_expand(g: WGroup, pos, found, sizes, mask, how: str,
                 out_cap: int, col_specs):
    """Materialize an m:n probe's expanded output columns: match counts
    -> exclusive scan -> repeat/gather, all columns sharing ONE
    expansion index.  ``col_specs`` entries are ``("expr", col)`` (a
    whole probe-side column, repeated per match) or ``("gather", data,
    fill)`` (a build-side column gathered through the group's stored
    row payload; ``fill`` selects left-join miss rows).  Poison
    (negative group count, or a dynamic total exceeding the static
    capacity) propagates as a negative output count."""
    n = pos.shape[0]
    if how == "inner":
        cnt = jnp.where(found & mask, sizes, jnp.int64(0))
    elif how == "left":  # misses emit ONE fill row each
        cnt = jnp.where(mask, jnp.where(found, sizes, jnp.int64(1)),
                        jnp.int64(0))
    else:
        raise WeldCompileError(f"group expansion how={how!r}")
    rows, ordinals, total = expand_rows(cnt, out_cap)
    total = jnp.where(total > out_cap, -total - 1, total)
    total = jnp.where(jnp.asarray(g.count, jnp.int64) < 0,
                      jnp.int64(-1), total)
    vals = g.values
    if isinstance(vals, tuple):
        raise WeldCompileError("group expansion needs a scalar payload")
    nv = vals.shape[0]
    offs = jnp.asarray(g.offsets, jnp.int64)
    if n == 0 or out_cap == 0:
        frow = jnp.zeros((out_cap,), bool)
        payload = jnp.zeros((out_cap,), jnp.int64)
    else:
        frow = found[rows]
        grp = jnp.clip(pos[rows], 0, offs.shape[0] - 2)
        if nv == 0:
            payload = jnp.zeros((out_cap,), jnp.int64)
        else:
            bpos = jnp.clip(offs[grp] + ordinals, 0, nv - 1)
            payload = jnp.asarray(vals)[bpos]
    outs = []
    for spec in col_specs:
        if spec[0] == "expr":
            col = spec[1]
            out = col[rows] if (n and out_cap) else jnp.zeros(
                (out_cap,), col.dtype)
        else:
            rv, fill = spec[1], spec[2]
            if rv.shape[0] == 0 or out_cap == 0:
                out = jnp.zeros((out_cap,), rv.dtype)
                if fill is not None:
                    out = jnp.full((out_cap,), jnp.asarray(fill, rv.dtype))
            else:
                out = rv[jnp.clip(payload, 0, rv.shape[0] - 1)]
            if how == "left" and out_cap:
                out = jnp.where(frow, out, jnp.asarray(fill, rv.dtype))
        outs.append(out)
    return tuple(WVec(o, count=total) for o in outs)


@dataclass
class GroupProbeShape:
    """Destructured m:n probe loop (see :func:`match_group_probe`)."""

    d: "ir.Ident"                 # the groupbuilder dict
    key_parts: list               # per-probe-row key column exprs
    pred: Optional["ir.Expr"]     # optional elementwise row predicate
    how: str                      # "inner" | "left"
    cols: list                    # ("expr", e) | ("gather", rcol Ident)
    fills: list                   # per-column left-miss Literal (or None)
    builders: list                # the output NewBuilder(VecBuilder)s


def match_group_probe(loop: ir.For) -> Optional[GroupProbeShape]:
    """Structurally match weldrel's m:n join probe loop — the canonical
    variable-length-expansion form shared by the generic lowering and
    the kernel planner's ``group_probe`` route:

        for(V.., {vecbuilder..}, (b,i,x) =>
            [if(pred,]
              [if(keyexists(d, k),]                        # left only
                for(grouplookup(d, k), b, (b2,i2,r) =>
                    {merge(b2.$k, f(x) | lookup(RCOL, r))..})
              [, {merge(b.$k, f(x) | fill)..})]            # left misses
            [, b)])

    Returns ``None`` when the loop is anything else (the generic
    accumulator lowering then applies)."""
    nb = loop.builder
    if not (isinstance(nb, ir.MakeStruct) and nb.items and all(
            isinstance(p, ir.NewBuilder) and isinstance(p.ty, wt.VecBuilder)
            and isinstance(p.ty.elem, wt.Scalar) for p in nb.items)):
        return None
    if len(loop.func.params) != 3:
        return None
    b, i, x = loop.func.params
    body = loop.func.body
    pred: Optional[ir.Expr] = None
    if (isinstance(body, ir.If) and isinstance(body.on_false, ir.Ident)
            and body.on_false.name == b.name):
        pred, body = body.cond, body.on_true
    how, miss, ke = "inner", None, None
    if isinstance(body, ir.If) and isinstance(body.cond, ir.KeyExists):
        how, ke, miss, body = "left", body.cond, body.on_false, body.on_true
    if not (isinstance(body, ir.For) and len(body.iters) == 1
            and body.iters[0].is_plain
            and isinstance(body.iters[0].data, ir.GroupLookup)):
        return None
    gl = body.iters[0].data
    d = gl.expr
    if not (isinstance(d, ir.Ident) and isinstance(d.ty, wt.DictType)
            and isinstance(d.ty.val, wt.Vec)):
        return None
    if how == "left" and not (
            isinstance(ke.expr, ir.Ident) and ke.expr.name == d.name
            and ir.canon_key(ke.key) == ir.canon_key(gl.key)):
        return None
    if not (isinstance(body.builder, ir.Ident)
            and body.builder.name == b.name):
        return None
    if len(body.func.params) != 3:
        return None
    bi, ii, ri = body.func.params
    ibody = body.func.body
    if not (isinstance(ibody, ir.MakeStruct)
            and len(ibody.items) == len(nb.items)):
        return None

    def merge_into(item: ir.Expr, k: int, bname: str) -> Optional[ir.Expr]:
        if (isinstance(item, ir.Merge)
                and isinstance(item.builder, ir.GetField)
                and item.builder.index == k
                and isinstance(item.builder.expr, ir.Ident)
                and item.builder.expr.name == bname):
            return item.value
        return None

    cols: list = []
    fills: list = []
    for k, item in enumerate(ibody.items):
        v = merge_into(item, k, bi.name)
        if v is None:
            return None
        if (isinstance(v, ir.Lookup) and v.default is None
                and isinstance(v.expr, ir.Ident)
                and isinstance(v.expr.ty, wt.Vec)
                and isinstance(v.index, ir.Ident)
                and v.index.name == ri.name):
            cols.append(("gather", v.expr))
        else:
            if set(ir.free_vars(v)) & {ri.name, ii.name, bi.name, d.name}:
                return None
            cols.append(("expr", v))
        fills.append(None)
    if how == "left":
        if not (isinstance(miss, ir.MakeStruct)
                and len(miss.items) == len(nb.items)):
            return None
        for k, item in enumerate(miss.items):
            mv = merge_into(item, k, b.name)
            if mv is None:
                return None
            kind, payload = cols[k]
            if kind == "gather":
                if not isinstance(mv, ir.Literal):
                    return None
                fills[k] = mv
            elif ir.canon_key(mv) != ir.canon_key(payload):
                return None  # probe columns must fill with themselves
    key = gl.key
    key_parts = (
        list(key.items) if isinstance(key, ir.MakeStruct) else [key]
    )
    for e2 in key_parts + ([pred] if pred is not None else []):
        if d.name in ir.free_vars(e2):
            return None
    return GroupProbeShape(d=d, key_parts=key_parts, pred=pred, how=how,
                           cols=cols, fills=fills, builders=list(nb.items))


_UNARY_JAX = {
    "neg": jnp.negative,
    "not": jnp.logical_not,
    "exp": jnp.exp,
    "log": jnp.log,
    "sqrt": jnp.sqrt,
    "erf": jax.lax.erf,
    "sin": jnp.sin,
    "cos": jnp.cos,
    "tanh": jnp.tanh,
    "abs": jnp.abs,
    "sigmoid": jax.nn.sigmoid,
    "floor": jnp.floor,
    "rsqrt": jax.lax.rsqrt,
}


def _binop_jax(op, a, b):
    if op in ("+", "-", "*"):
        return {"+": jnp.add, "-": jnp.subtract, "*": jnp.multiply}[op](a, b)
    if op == "/":
        if jnp.issubdtype(jnp.result_type(a), jnp.integer):
            return jax.lax.div(jnp.asarray(a), jnp.asarray(b))  # C trunc-div
        return jnp.divide(a, b)
    if op == "%":
        if jnp.issubdtype(jnp.result_type(a), jnp.integer):
            return jax.lax.rem(jnp.asarray(a), jnp.asarray(b))
        return jnp.mod(a, b)
    if op == "pow":
        return jnp.power(a, b)
    if op in ("min", "max"):
        return (jnp.minimum if op == "min" else jnp.maximum)(a, b)
    if op in ir.CMP_OPS:
        return {
            "==": jnp.equal, "!=": jnp.not_equal, "<": jnp.less,
            "<=": jnp.less_equal, ">": jnp.greater, ">=": jnp.greater_equal,
        }[op](a, b)
    if op == "&&":
        return jnp.logical_and(a, b)
    if op == "||":
        return jnp.logical_or(a, b)
    raise WeldCompileError(f"binop {op}")


# ---------------------------------------------------------------------------
# static const-eval for iter bounds / capacities
# ---------------------------------------------------------------------------


def _static_eval(e: ir.Expr, shapes: Dict[str, tuple]) -> Optional[int]:
    if isinstance(e, ir.Literal):
        return int(e.value)
    if isinstance(e, ir.Len) and isinstance(e.expr, ir.Ident):
        shp = shapes.get(e.expr.name)
        return None if shp is None else int(shp[0])
    if isinstance(e, ir.BinOp):
        a = _static_eval(e.left, shapes)
        b = _static_eval(e.right, shapes)
        if a is None or b is None:
            return None
        return int({
            "+": a + b, "-": a - b, "*": a * b,
            "/": int(a / b) if b else 0,
            "min": min(a, b), "max": max(a, b),
        }.get(e.op, None)) if e.op in ("+", "-", "*", "/", "min", "max") else None
    return None


# ---------------------------------------------------------------------------
# The emitter
# ---------------------------------------------------------------------------


class _LoopCtx:
    def __init__(self, n: int, mask, per_elem: frozenset, parent=None):
        self.n = n
        self.mask = mask  # (n,) bool or None
        self.per_elem = per_elem
        self.parent = parent
        self.touched: List[_Acc] = []  # vecbuilders merged in this loop


class Emitter:
    def __init__(self, input_shapes: Dict[str, tuple],
                 memory_limit: Optional[int] = None,
                 kernel_impl: Optional[str] = None,
                 measure: bool = False):
        self.input_shapes = input_shapes
        self.memory_limit = memory_limit
        self.kernel_impl = kernel_impl
        self.measure = measure
        self.est_bytes = 0
        #: dynamic counts of every dict/group this program probed —
        #: emit_program ORs their signs into the output counts so a
        #: probe against a poisoned (overflowed) collection can never
        #: decode as a plausible empty/partial result
        self.taints: List[object] = []

    def _note_taint(self, coll) -> None:
        count = getattr(coll, "count", None)
        if count is not None:
            self.taints.append(count)

    @staticmethod
    def _ret_dtype(x: ir.KernelCall) -> str:
        from ..kernelplan.autotune import _np_dtype_of

        return str(np.dtype(_np_dtype_of(x.ret_ty)))

    # -- entry ---------------------------------------------------------------

    def run(self, expr: ir.Expr, env: Dict[str, object]):
        return self.ev(expr, dict(env), None)

    # -- main dispatch ---------------------------------------------------------

    def ev(self, x: ir.Expr, env, ctx: Optional[_LoopCtx]):
        m = getattr(self, "_ev_" + type(x).__name__, None)
        if m is None:
            raise WeldCompileError(f"cannot lower {type(x).__name__}")
        return m(x, env, ctx)

    # -- leaves ---------------------------------------------------------------

    def _ev_Literal(self, x: ir.Literal, env, ctx):
        return jnp.asarray(x.value, dtype=_jdtype(x.ty))

    def _ev_Ident(self, x: ir.Ident, env, ctx):
        if x.name not in env:
            raise WeldCompileError(f"unbound {x.name}")
        return env[x.name]

    def _ev_Let(self, x: ir.Let, env, ctx):
        v = self.ev(x.value, env, ctx)
        env2 = dict(env)
        env2[x.name] = v
        if ctx is not None and self._depends_per_elem(x.value, ctx):
            ctx2 = _LoopCtx(ctx.n, ctx.mask, ctx.per_elem | {x.name}, ctx.parent)
            ctx2.touched = ctx.touched  # share accumulator-seal tracking
            ctx = ctx2
        return self.ev(x.body, env2, ctx)

    def _ev_BinOp(self, x: ir.BinOp, env, ctx):
        return _binop_jax(x.op, self.ev(x.left, env, ctx),
                          self.ev(x.right, env, ctx))

    def _ev_UnaryOp(self, x: ir.UnaryOp, env, ctx):
        v = self.ev(x.expr, env, ctx)
        if x.op in ("exp", "log", "sqrt", "erf", "sin", "cos", "tanh",
                    "sigmoid", "rsqrt"):
            v = _to_float(v)
        return _UNARY_JAX[x.op](v)

    def _ev_Cast(self, x: ir.Cast, env, ctx):
        return jnp.asarray(self.ev(x.expr, env, ctx)).astype(_jdtype(x.ty))

    def _ev_Select(self, x: ir.Select, env, ctx):
        c = self.ev(x.cond, env, ctx)
        t = self.ev(x.on_true, env, ctx)
        f = self.ev(x.on_false, env, ctx)
        return _select_struct(c, t, f) if isinstance(t, tuple) else jnp.where(c, t, f)

    def _ev_If(self, x: ir.If, env, ctx):
        bty = self._is_builder_expr(x.on_true, env)
        if not bty:
            return self._ev_Select(ir.Select(x.cond, x.on_true, x.on_false),
                                   env, ctx)
        # control flow over builders -> predication masks
        c = self.ev(x.cond, env, ctx)
        if ctx is None:
            raise WeldCompileError("builder If outside a loop")
        c = jnp.broadcast_to(c, (ctx.n,))
        mask_t = c if ctx.mask is None else ctx.mask & c
        mask_f = ~c if ctx.mask is None else ctx.mask & ~c
        ctx_t = _LoopCtx(ctx.n, mask_t, ctx.per_elem, ctx.parent)
        ctx_t.touched = ctx.touched
        ctx_f = _LoopCtx(ctx.n, mask_f, ctx.per_elem, ctx.parent)
        ctx_f.touched = ctx.touched
        t = self.ev(x.on_true, env, ctx_t)
        self.ev(x.on_false, env, ctx_f)
        return t  # same accumulator objects on both paths

    def _ev_MakeStruct(self, x: ir.MakeStruct, env, ctx):
        return tuple(self.ev(i, env, ctx) for i in x.items)

    def _ev_GetField(self, x: ir.GetField, env, ctx):
        v = self.ev(x.expr, env, ctx)
        return v[x.index]

    def _ev_MakeVec(self, x: ir.MakeVec, env, ctx):
        items = [self.ev(i, env, ctx) for i in x.items]
        return WVec(jnp.stack([jnp.asarray(i) for i in items]))

    def _ev_Len(self, x: ir.Len, env, ctx):
        if ctx is not None and self._depends_per_elem(x.expr, ctx):
            raise _NeedsVmap()
        v = self.ev(x.expr, env, ctx)
        if isinstance(v, WVec):
            return jnp.asarray(v.length(), dtype=jnp.int64)
        raise WeldCompileError("len of non-vec")

    def _ev_Lookup(self, x: ir.Lookup, env, ctx):
        if ctx is not None and self._depends_per_elem(x.expr, ctx):
            raise _NeedsVmap()
        coll = self.ev(x.expr, env, ctx)
        idx = self.ev(x.index, env, ctx)
        if isinstance(coll, WVec):
            return _gather_struct(coll.data, idx)  # gather (vectorized ok)
        if isinstance(coll, WDict):
            # scalar OR whole-column probe (vectorized loop bodies bind
            # the key to a column).  With a `default` the miss mask from
            # the SAME find selects the fill — one probe pass, no second
            # search; without one, missing keys yield an arbitrary slot's
            # value — guard with KeyExists, as the frames do.
            self._note_taint(coll)
            pos, found, scalar = _dict_find(coll, idx)

            def gather(a):
                if a.shape[0] == 0:  # empty dict: type-correct zeros
                    return jnp.zeros(pos.shape, a.dtype)
                return a[pos]

            out = jax.tree_util.tree_map(gather, coll.vals)
            if x.default is not None:
                dflt = self.ev(x.default, env, ctx)
                out = _select_struct(found, out, dflt)
            if scalar:
                out = jax.tree_util.tree_map(lambda a: a[0], out)
            return out
        raise WeldCompileError("lookup on unsupported value")

    def _ev_KeyExists(self, x: ir.KeyExists, env, ctx):
        d = self.ev(x.expr, env, ctx)
        k = self.ev(x.key, env, ctx)
        self._note_taint(d)
        if isinstance(d, WGroup):
            pos, found, _ = _group_find(d, k)
            return found
        pos, found, scalar = _dict_find(d, k)
        return found[0] if scalar else found

    def _ev_GroupLookup(self, x: ir.GroupLookup, env, ctx):
        raise WeldCompileError(
            "grouplookup has data-dependent length and lowers only as "
            "the iteration source of an m:n probe loop (the shape "
            "match_group_probe recognizes); restructure the program "
            "around that canonical expansion form"
        )

    def _ev_CUDF(self, x: ir.CUDF, env, ctx):
        if ctx is not None and any(
            self._depends_per_elem(a, ctx) for a in x.args
        ):
            raise _NeedsVmap()
        if not has_cudf(x.name) and not x.name.startswith("linalg."):
            raise WeldCompileError(f"unknown cudf {x.name}")
        args = [self.ev(a, env, ctx) for a in x.args]
        uw = [a.data if isinstance(a, WVec) and a.is_dense else a for a in args]
        if any(isinstance(a, WVec) for a in uw):
            raise WeldCompileError(f"cudf {x.name} on padded vector")
        if x.name == "linalg.dot":
            out = jnp.dot(uw[0], uw[1])
        elif x.name == "linalg.matvec":
            out = uw[0] @ uw[1]
        elif x.name == "linalg.matmul":
            out = uw[0] @ uw[1]
        else:
            out = lookup_cudf_jax(x.name)(*uw)
        if isinstance(x.ret_ty, wt.Vec):
            return WVec(out)
        return out

    def _ev_KernelCall(self, x: ir.KernelCall, env, ctx):
        if ctx is not None and any(
            self._depends_per_elem(a, ctx) for a in x.args
        ):
            raise _NeedsVmap()
        from ..kernelplan import registry as kreg

        spec = kreg.get(x.kernel)
        args = [self.ev(a, env, ctx) for a in x.args]
        params = dict(x.params)
        if self.memory_limit is not None and spec.footprint is not None:
            # kernel calls pay padding + scratch out of the same budget
            # the vecbuilder size hints feed — a kernelized plan cannot
            # silently blow the evaluation's memory estimate
            self.est_bytes += self._kernel_footprint(spec, args, x, params)
            if self.est_bytes > self.memory_limit:
                raise WeldMemoryError(
                    f"estimated temp bytes {self.est_bytes} (incl. kernel "
                    f"{x.kernel} padding/scratch) exceed memory limit "
                    f"{self.memory_limit}"
                )
        fns = [self._stage_elem_fn(lam, env) for lam in x.fns]
        if self.measure:
            return self._measured_kernel_call(x, spec, args, params, fns)
        # per-launch label: device profiles (and jaxpr dumps) name each
        # kernel launch after the IR loop it was planned from
        with jax.named_scope(f"weld.{x.kernel}"):
            return kreg.execute_spec(args=args, params=params, fns=fns,
                                     impl=self.kernel_impl, spec=spec,
                                     dtype=self._ret_dtype(x))

    def _measured_kernel_call(self, x: ir.KernelCall, spec, args, params,
                              fns):
        """Eager-replay path: time this launch, record a span and a cost
        ledger entry carrying the planner's ``predicted_ns`` next to the
        measured wall time."""
        from .. import obs

        block = {k: v for k, v in params.items()
                 if k in ("block", "bm", "bn", "bk")}
        from ..kernelplan import registry as kreg

        with obs.span(f"kernel.{x.kernel}", n=params.get("n_rows"),
                      impl=self.kernel_impl, **block) as sp:
            out = kreg.execute_spec(args=args, params=params, fns=fns,
                                    impl=self.kernel_impl, spec=spec,
                                    dtype=self._ret_dtype(x))
            out = jax.block_until_ready(out)
        predicted = params.get("predicted_ns")
        sp.set("predicted_ns", predicted)
        sp.set("measured_ns", sp.dur_ns)
        from ..kernelplan.autotune import _np_dtype_of

        dtype = str(np.dtype(_np_dtype_of(x.ret_ty)))
        obs.ledger.record(
            kernel=x.kernel, dtype=dtype, n=params.get("n_rows") or 0,
            predicted_ns=predicted, measured_ns=sp.dur_ns or 0,
            impl=self.kernel_impl, params=block,
        )
        return out

    @staticmethod
    def _kernel_footprint(spec, args, x: ir.KernelCall, params) -> int:
        def shape_of(v):
            if isinstance(v, WVec):
                leaf = v.data[0] if isinstance(v.data, tuple) else v.data
                return tuple(leaf.shape)
            return getattr(v, "shape", None) and tuple(v.shape) or ()

        try:
            return int(spec.footprint(
                [shape_of(a) for a in args], wt.elem_bytes(x.ret_ty), params
            ))
        except Exception:
            return 0  # accounting must never break a valid plan

    def _stage_elem_fn(self, lam: ir.Lambda, env):
        """Per-element IR lambda -> jnp-traceable callable (whole-column
        evaluation via this emitter, closing over the current env)."""
        base_env = dict(env)

        def fn(*vals):
            env2 = dict(base_env)
            for p, v in zip(lam.params, vals):
                env2[p.name] = v
            return self.ev(lam.body, env2, None)

        return fn

    # -- builders -------------------------------------------------------------

    def _ev_NewBuilder(self, x: ir.NewBuilder, env, ctx):
        bt = x.ty
        if isinstance(bt, wt.Merger):
            init = self.ev(x.arg, env, ctx) if x.arg is not None else None
            return _MergerAcc(bt, init)
        if isinstance(bt, wt.VecBuilder):
            if x.size_hint is not None and self.memory_limit is not None:
                n = _static_eval(x.size_hint, self.input_shapes)
                if n is not None and isinstance(bt.elem, wt.Scalar):
                    self.est_bytes += n * np.dtype(bt.elem.np_dtype).itemsize
                    if self.est_bytes > self.memory_limit:
                        raise WeldMemoryError(
                            f"estimated temp bytes {self.est_bytes} exceed "
                            f"memory limit {self.memory_limit}"
                        )
            return _VecBuilderAcc(bt)
        if isinstance(bt, wt.VecMerger):
            base = self.ev(x.arg, env, ctx)
            return _VecMergerAcc(bt, base)
        if isinstance(bt, (wt.DictMerger, wt.GroupBuilder)):
            cap = 1024
            if x.arg is not None:
                c = _static_eval(x.arg, self.input_shapes)
                if c is not None:
                    cap = c
            cls = _DictMergerAcc if isinstance(bt, wt.DictMerger) else _GroupAcc
            return cls(bt, cap)
        raise WeldCompileError(f"cannot build {bt}")

    def _ev_Merge(self, x: ir.Merge, env, ctx):
        acc = self.ev(x.builder, env, ctx)
        if not isinstance(acc, _Acc):
            raise WeldCompileError("merge into non-builder value")
        val = self.ev(x.value, env, ctx)
        if ctx is None:
            acc.add_single(val)
        else:
            val = self._broadcast_elem(val, ctx)
            acc.add_batch(val, ctx.mask)
            if isinstance(acc, _VecBuilderAcc) and acc not in ctx.touched:
                ctx.touched.append(acc)
        return acc

    def _ev_Result(self, x: ir.Result, env, ctx):
        if ctx is None and isinstance(x.builder, ir.For):
            shape = match_group_probe(x.builder)
            if shape is not None:
                return self._lower_group_probe(x.builder, shape, env)
        acc = self.ev(x.builder, env, ctx)
        if isinstance(acc, tuple):
            return tuple(self._finalize(a) for a in acc)
        return self._finalize(acc)

    def _lower_group_probe(self, loop: ir.For, shape: GroupProbeShape, env):
        """Generic (kernel-free) lowering of the m:n join probe: one
        binary-search membership pass over the group's sorted keys, then
        the shared two-phase expansion (match counts -> exclusive scan ->
        repeat/gather) with every output column riding one expansion
        index.  Output length is data-dependent; the static buffer
        capacity comes from the vecbuilders' size hints."""
        g = self.ev(shape.d, env, None)
        if not isinstance(g, WGroup):
            raise WeldCompileError("group probe expects a groupbuilder dict")
        seqs = [self.ev(it, env, None) for it in loop.iters]
        n = min(s.capacity() for s in seqs)
        mask = None
        for s in seqs:
            if not s.is_dense:
                m = jnp.arange(n) < s.count
                mask = m if mask is None else mask & m
        b_p, i_p, x_p = loop.func.params
        env2 = dict(env)
        env2[i_p.name] = jnp.arange(n, dtype=jnp.int64)
        env2[x_p.name] = (
            _first_n(seqs[0].data, n) if len(seqs) == 1
            else tuple(_first_n(s.data, n) for s in seqs)
        )

        def col(v):
            a = jnp.asarray(v)
            return a if a.ndim >= 1 and a.shape[0] == n \
                else jnp.broadcast_to(a, (n,) + a.shape)

        key_cols = [col(self.ev(kp, env2, None)) for kp in shape.key_parts]
        key = tuple(key_cols) if len(key_cols) > 1 else key_cols[0]
        pos, found, sizes = _group_find(g, key)
        pm = mask
        if shape.pred is not None:
            pv = col(self.ev(shape.pred, env2, None)).astype(bool)
            pm = pv if pm is None else pm & pv
        if pm is None:
            pm = jnp.ones((n,), bool)
        hint = shape.builders[0].size_hint
        out_cap = (
            _static_eval(hint, self.input_shapes)
            if hint is not None else None
        )
        if out_cap is None:
            raise WeldCompileError(
                "m:n group probe needs a static output capacity "
                "(vecbuilder size hint)"
            )
        if self.memory_limit is not None:
            self.est_bytes += sum(
                int(out_cap) * np.dtype(p.ty.elem.np_dtype).itemsize
                for p in shape.builders
            )
            if self.est_bytes > self.memory_limit:
                raise WeldMemoryError(
                    f"estimated temp bytes {self.est_bytes} (incl. m:n "
                    f"join expansion) exceed memory limit "
                    f"{self.memory_limit}"
                )
        col_specs = []
        for (kind, payload), fill in zip(shape.cols, shape.fills):
            if kind == "expr":
                col_specs.append(("expr", col(self.ev(payload, env2, None))))
            else:
                rv = self.ev(payload, env, None)
                if not isinstance(rv, WVec) or not rv.is_dense:
                    raise WeldCompileError(
                        "group probe gathers need dense build columns"
                    )
                col_specs.append(
                    ("gather", rv.data,
                     None if fill is None else fill.value)
                )
        return group_expand(g, pos, found, sizes, pm, shape.how,
                            int(out_cap), col_specs)

    def _finalize(self, acc):
        if isinstance(acc, (_MergerAcc, _VecBuilderAcc, _VecMergerAcc)):
            return acc.finalize()
        if isinstance(acc, _DictMergerAcc):
            return _finalize_keyed(acc, is_group=False)
        if isinstance(acc, _GroupAcc):
            return _finalize_keyed(acc, is_group=True)
        raise WeldCompileError("result of non-builder")

    # -- loops ----------------------------------------------------------------

    def _ev_Iter(self, x: ir.Iter, env, ctx):
        data = self.ev(x.data, env, ctx)
        if not isinstance(data, WVec):
            raise WeldCompileError("iter over non-vec")
        start = _static_eval(x.start, self.input_shapes) if x.start is not None else 0
        end = (
            _static_eval(x.end, self.input_shapes)
            if x.end is not None else None
        )
        stride = (
            _static_eval(x.stride, self.input_shapes)
            if x.stride is not None else 1
        )
        if (x.start is not None and start is None) or \
           (x.end is not None and end is None) or \
           (x.stride is not None and stride is None):
            raise WeldCompileError("iter bounds must be statically evaluable")
        if start == 0 and end is None and stride == 1:
            return data
        if not data.is_dense:
            raise WeldCompileError("cannot slice a padded (filtered) vector")
        arr = data.data
        sl = (slice(start, end, stride),)
        arr = tuple(a[sl] for a in arr) if isinstance(arr, tuple) else arr[sl]
        return WVec(arr)

    def _ev_For(self, x: ir.For, env, ctx):
        # nested loop whose data depends on the enclosing element -> vmap
        if ctx is not None and any(
            self._depends_per_elem(it, ctx) for it in x.iters
        ):
            raise _NeedsVmap()

        acc_tree = self.ev(x.builder, env, ctx)
        seqs = [self.ev(it, env, ctx) for it in x.iters]
        lens = {s.capacity() for s in seqs}
        n = min(lens)
        mask = None
        for s in seqs:
            if not s.is_dense:
                m = jnp.arange(n) < s.count
                mask = m if mask is None else (mask & m)

        b_p, i_p, x_p = x.func.params
        idx = jnp.arange(n, dtype=jnp.int64)
        if len(seqs) == 1:
            elem = _first_n(seqs[0].data, n)
        else:
            elem = tuple(_first_n(s.data, n) for s in seqs)

        env2 = dict(env)
        env2[b_p.name] = acc_tree
        env2[i_p.name] = idx
        env2[x_p.name] = elem
        loop = _LoopCtx(n, mask, frozenset({i_p.name, x_p.name}), ctx)
        # Decide the lowering BEFORE evaluating: evaluation mutates the
        # accumulators, so a mid-body fallback would double-merge.
        if self._body_needs_vmap(x.func.body, {i_p.name, x_p.name}):
            out = self._for_via_vmap(x, acc_tree, idx, elem, mask, env, loop)
        else:
            try:
                out = self.ev(x.func.body, env2, loop)
            except _NeedsVmap as exc:  # pre-scan missed a case: hard error
                raise WeldCompileError(
                    "loop body unexpectedly needed per-element vector "
                    "evaluation"
                ) from exc
        # seal vecbuilder ordering for this loop
        for a in loop.touched:
            if isinstance(a, _VecBuilderAcc):
                a.seal()
        return out

    def _body_needs_vmap(self, body: ir.Expr, per_elem: set) -> bool:
        """Pre-scan: does the body use its element/index as a *vector*
        (inner For / Len / Lookup / CUDF over per-element data)?"""

        def dep(e: ir.Expr, pe: set) -> bool:
            return bool(set(ir.free_vars(e)) & pe)

        def scan(e: ir.Expr, pe: set) -> bool:
            if isinstance(e, ir.For):
                if any(dep(it, pe) for it in e.iters):
                    return True
                # the inner loop introduces its own element names; per-elem
                # names from this level may still leak into its body
                return scan(e.builder, pe) or scan(e.func.body, pe)
            if isinstance(e, (ir.Len, ir.Lookup)):
                tgt = e.expr
                if dep(tgt, pe):
                    return True
            if isinstance(e, ir.CUDF):
                if any(dep(a, pe) for a in e.args):
                    return True
            if isinstance(e, ir.Let):
                pe2 = pe | {e.name} if dep(e.value, pe) else pe
                return scan(e.value, pe) or scan(e.body, pe2)
            if isinstance(e, ir.Lambda):
                return scan(e.body, pe)
            return any(scan(c, pe) for c in e.children())

        return scan(body, set(per_elem))

    def _for_via_vmap(self, x: ir.For, acc_tree, idx, elem, mask, env, loop):
        """Un-nesting fallback: the body needs its element as a vector.
        Supports (lets*) [If(cond,] Merge(b, V) [, b)] bodies — V computed
        per element under jax.vmap."""
        b_p, i_p, x_p = x.func.params
        body = x.func.body
        lets: List[Tuple[str, ir.Expr]] = []
        while isinstance(body, ir.Let):
            lets.append((body.name, body.value))
            body = body.body
        cond_expr = None
        if isinstance(body, ir.If):
            merge_branch, other = body.on_true, body.on_false
            cond_expr = body.cond
            if not isinstance(merge_branch, ir.Merge):
                merge_branch, other = body.on_false, body.on_true
                cond_expr = ir.UnaryOp("not", body.cond)
            if not isinstance(merge_branch, ir.Merge):
                raise WeldCompileError(
                    "cannot lower nested loop body (no merge branch)"
                )
            body = merge_branch
        if not isinstance(body, ir.Merge):
            raise WeldCompileError(
                "unsupported nested-vector loop body; restructure with "
                "flat edge lists or weldnp 2-D ops (DESIGN.md §8.2)"
            )
        target = self.ev(body.builder, dict(env, **{b_p.name: acc_tree}), None)

        def per_elem(i_s, x_s):
            env_s = dict(env)
            env_s[i_p.name] = i_s
            env_s[x_p.name] = _wrap_rows(x_s, x.iters, self, env)
            for nm, val in lets:
                env_s[nm] = self.ev(val, env_s, None)
            v = self.ev(body.value, env_s, None)
            keep = (
                jnp.asarray(True)
                if cond_expr is None
                else self.ev(cond_expr, env_s, None)
            )
            return v, keep

        vals, keeps = jax.vmap(per_elem)(idx, elem)
        m = keeps if cond_expr is not None else None
        if mask is not None:
            m = mask if m is None else (m & mask)
        if not isinstance(target, _Acc):
            raise WeldCompileError("nested loop must merge into a builder")
        target.add_batch(vals, m)
        if isinstance(target, _VecBuilderAcc) and target not in loop.touched:
            loop.touched.append(target)
        return acc_tree

    # -- helpers --------------------------------------------------------------

    def _depends_per_elem(self, e: ir.Expr, ctx: _LoopCtx) -> bool:
        names = set(ir.free_vars(e))
        c = ctx
        while c is not None:
            if names & c.per_elem:
                return True
            c = c.parent
        return False

    def _broadcast_elem(self, val, ctx: _LoopCtx):
        def bc(a):
            a = jnp.asarray(a)
            if a.ndim >= 1 and a.shape[0] == ctx.n:
                return a
            return jnp.broadcast_to(a, (ctx.n,) + a.shape)

        return jax.tree_util.tree_map(bc, val)

    def _is_builder_expr(self, e: ir.Expr, env) -> bool:
        try:
            t = ir.typeof(e, {k: None for k in ()})
            return isinstance(t, wt.BuilderType)
        except Exception:
            pass
        # structural fallback: Merge / NewBuilder / structs thereof /
        # idents bound to accumulators
        if isinstance(e, (ir.Merge, ir.NewBuilder)):
            return True
        if isinstance(e, ir.MakeStruct):
            return any(self._is_builder_expr(i, env) for i in e.items)
        if isinstance(e, ir.Let):
            return self._is_builder_expr(e.body, env)
        if isinstance(e, ir.GetField):
            return self._is_builder_expr(e.expr, env)
        if isinstance(e, ir.Ident):
            v = env.get(e.name)
            if isinstance(v, _Acc):
                return True
            if isinstance(v, tuple):
                return all(isinstance(i, _Acc) for i in v)
        return False


def _to_float(v):
    v = jnp.asarray(v)
    if jnp.issubdtype(v.dtype, jnp.integer) or v.dtype == jnp.bool_:
        return v.astype(jnp.float64)
    return v


def _first_n(data, n):
    if isinstance(data, tuple):
        return tuple(a[:n] for a in data)
    return data[:n]


def _wrap_rows(x_s, iters, emitter, env):
    """Inside vmap, an element of vec[vec[T]] is a row — re-wrap as WVec so
    inner loops can iterate it."""

    def wrap(a):
        if hasattr(a, "ndim") and a.ndim >= 1:
            return WVec(a)
        return a

    if isinstance(x_s, tuple):
        return tuple(wrap(a) for a in x_s)
    return wrap(x_s)


# ---------------------------------------------------------------------------
# Program entry
# ---------------------------------------------------------------------------


def emit_program(expr: ir.Expr, input_names: List[str],
                 input_types: Dict[str, wt.WeldType],
                 input_shapes: Dict[str, tuple],
                 memory_limit: Optional[int] = None,
                 kernel_impl: Optional[str] = None,
                 measure: bool = False):
    """Returns fn(*arrays) evaluating the program; wrap in jax.jit.

    With ``measure=True`` the closure must be run *unjitted*: every
    ``KernelCall`` is individually timed (``block_until_ready``) and
    recorded as an obs span + cost-ledger entry.
    """

    def fn(*arrays):
        env = {}
        for name, arr in zip(input_names, arrays):
            ty = input_types[name]
            env[name] = _wrap_input(arr, ty)
        em = Emitter(input_shapes, memory_limit, kernel_impl=kernel_impl,
                     measure=measure)
        out = em.run(expr, env)
        if em.taints:
            # the program probed dynamic-count dicts/groups: a negative
            # count on ANY of them poisons every countable output, so a
            # probe against an overflowed build can never decode as a
            # plausible empty result (the kernel probe adapters already
            # guarantee this; here the generic lowering matches them)
            bad = jnp.asarray(False)
            for t in em.taints:
                bad = bad | (jnp.asarray(t) < 0)
            out = _apply_taint(out, bad)
        return out

    return fn


def _apply_taint(v, bad):
    """Poison the dynamic counts of ``v`` where ``bad`` (traced bool)."""
    if isinstance(v, WVec):
        if v.count is None:
            n = v.capacity()
            return WVec(v.data, jnp.where(bad, jnp.int64(-1), jnp.int64(n)))
        c = jnp.asarray(v.count)
        return WVec(v.data, jnp.where(bad, -abs(c) - 1, c))
    if isinstance(v, WDict):
        c = jnp.asarray(v.count)
        return WDict(v.keys, v.vals, jnp.where(bad, -abs(c) - 1, c))
    if isinstance(v, WGroup):
        c = jnp.asarray(v.count)
        return WGroup(v.keys, v.values, v.offsets, jnp.where(bad, -abs(c) - 1, c))
    if isinstance(v, tuple):
        return tuple(_apply_taint(x, bad) for x in v)
    return v


def _wrap_input(arr, ty: wt.WeldType):
    if isinstance(ty, wt.Vec):
        return WVec(arr)
    return arr
