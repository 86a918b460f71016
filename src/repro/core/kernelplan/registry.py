"""Declarative registry of Pallas kernels reachable from the IR planner.

Each :class:`KernelSpec` describes one kernel in ``repro.kernels.ops``:
the IR pattern family it accelerates (loop shape + builder kind), the
scalar kinds it accepts, its static-shape constraints, and the backend
adapter that invokes the entry point on traced values.  The planner
(`repro.core.kernelplan.planner`) consults this table — patterns are
matched *by family*, so registering/unregistering a spec is the ablation
knob for a kernel, no planner change needed.

Adapters receive backend values (``WVec``/arrays), the static params
baked into the ``KernelCall`` node, the staged per-element callables, and
the ``impl`` knob (ref / interpret / pallas) which is forwarded to
``repro.kernels.ops`` so the existing resolution machinery applies.
Tuned block sizes arrive the same way: the autotuner appends ``block``
(or ``bm``/``bn``/``bk``) to the call's params and adapters forward them.

Beyond the adapter, each spec now carries the hooks the adaptive
planner needs:

* ``cost`` — roofline pricing of the match (see ``cost.py``); drives
  ``mode="auto"`` routing;
* ``tune_space`` / ``make_bench`` — the tunable-parameter grid and a
  synthetic-workload builder the autotuner times it with;
* ``footprint`` — padding + scratch bytes of one call, charged against
  the evaluation's ``memory_limit`` budget by the emitter (the same
  budget vecbuilder size hints feed).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...kernels import filter_reduce as _fr
from ...kernels import group_build as _gb
from ...kernels import hash_probe as _hp
from ...kernels import hash_table as _ht
from ...kernels import map_chain as _mc
from ...kernels import ops as kops
from ...kernels import segment_reduce as _sr
from ...kernels import tiled_matmul as _tm
from ..backend.jaxgen import _pack_keys, front_pack, group_expand
from ..backend.values import WDict, WGroup, WVec
from . import cost as _cost


class KernelPlanError(RuntimeError):
    """An annotated kernel call could not be executed (planner bug or a
    runtime-shape violation of a registry constraint)."""


# ---------------------------------------------------------------------------
# Spec + registry
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class KernelSpec:
    #: registry key; also the ``KernelCall.kernel`` tag and stats suffix.
    name: str
    #: entry point, dotted (module:function) — documentation + dispatch.
    entry: str
    #: IR pattern family the planner matches (see planner.py).
    pattern: str
    #: builder kind of the matched loop ("merger[+]", "vecmerger[+]",
    #: "dictmerger[+]", "vecbuilder", or "-" for non-loop patterns).
    builder: str
    #: scalar kinds accepted for the merged element / operands.
    elem_kinds: Tuple[str, ...]
    description: str
    #: static bound on segment count / dict capacity (None = unbounded).
    max_segments: Optional[int] = None
    #: backend adapter: (args, params, fns, impl) -> backend value.
    execute: Callable = None
    #: roofline cost hook: (meta dict) -> cost.CostEstimate.  None means
    #: "always route" (no model; pre-cost-gate behavior).
    cost: Optional[Callable] = None
    #: tunable-parameter grid, e.g. {"block": (1024, 8192, 32768)}.
    #: Empty = nothing to tune.
    tune_space: Dict[str, tuple] = field(default_factory=dict)
    #: synthetic-workload builder for the autotuner:
    #: (meta, params, impl) -> zero-arg timed callable.
    make_bench: Optional[Callable] = None
    #: HBM overhead accounting: (arg_shapes, itemsize, params) -> bytes of
    #: padding + scratch this call adds beyond its natural inputs/outputs.
    footprint: Optional[Callable] = None
    #: module-default value per tunable (what runs untuned; also what the
    #: autotuner bakes into the plan when timing is unavailable).
    tune_defaults: Dict[str, int] = field(default_factory=dict)
    #: scalar kinds the Pallas kernel compiles for on the TPU (Mosaic
    #: lowers no 64-bit element): the planner rejects an ``impl="pallas"``
    #: match whose kernel operands fall outside them, and never launches it.
    tpu_kinds: Tuple[str, ...] = ("f32", "i32")


_REGISTRY: Dict[str, KernelSpec] = {}


def register(spec: KernelSpec) -> KernelSpec:
    _REGISTRY[spec.name] = spec
    return spec


def unregister(name: str) -> None:
    _REGISTRY.pop(name, None)


def get(name: str) -> KernelSpec:
    if name not in _REGISTRY:
        raise KernelPlanError(f"no registered kernel {name!r}")
    return _REGISTRY[name]


def available(name: str) -> Optional[KernelSpec]:
    return _REGISTRY.get(name)


def all_specs() -> Tuple[KernelSpec, ...]:
    return tuple(_REGISTRY.values())


def fingerprint() -> str:
    """Stable key of the registered-kernel set — part of the compile-cache
    key, so register/unregister (the ablation knob) and default-block
    changes force a recompile rather than serving a stale executable."""
    return ",".join(sorted(
        f"{s.name}:{s.entry}:{sorted(s.tune_defaults.items())}"
        for s in _REGISTRY.values()
    ))


def describe() -> str:
    """Human-readable registry dump (docs / debugging)."""
    lines = []
    for s in _REGISTRY.values():
        lines.append(
            f"{s.name:24s} {s.pattern:16s} {s.builder:14s} "
            f"[{','.join(s.elem_kinds)}] -> {s.entry}"
        )
    return "\n".join(lines)


def _poison_value(out):
    """Negate every dynamic count in a kernel result (the ``poison``
    fault action): downstream probes and decode then see exactly what a
    real capacity overflow produces."""
    if isinstance(out, WVec):
        if out.count is None:
            return WVec(out.data, jnp.int64(-1))
        c = jnp.asarray(out.count)
        return WVec(out.data, -abs(c) - 1)
    if isinstance(out, WDict):
        c = jnp.asarray(out.count)
        return WDict(out.keys, out.vals, -abs(c) - 1)
    if isinstance(out, WGroup):
        c = jnp.asarray(out.count)
        return WGroup(out.keys, out.values, out.offsets, -abs(c) - 1)
    if isinstance(out, tuple):
        return tuple(_poison_value(v) for v in out)
    return out


def execute_spec(spec: KernelSpec, args, params, fns, impl,
                 dtype=None):
    """Every planned kernel launch funnels through here.

    Arms the ``kernel.<name>`` failpoints (``raise`` simulates a
    stage/compile failure, ``poison`` a capacity overflow) and wraps any
    backend failure into a typed
    :class:`~repro.core.errors.KernelCompileError` carrying the
    quarantine key ``(kernel, impl, dtype, n)`` — the recovery layer
    records the offender and degrades the evaluation to the generic
    lowering.
    """
    from .. import faults
    from ..errors import KernelCompileError, ResourceError

    site = f"kernel.{spec.name}"
    try:
        faults.maybe_raise(site)
        out = spec.execute(args, params, fns, impl)
    except (ResourceError, KernelCompileError):
        raise  # already typed; budget breaches are not kernel failures
    except Exception as e:
        raise KernelCompileError(
            f"kernel {spec.name!r} (impl={impl}) failed to stage/launch: "
            f"{type(e).__name__}: {e}",
            kernel=spec.name, impl=impl, dtype=dtype,
            n=dict(params).get("n_rows"),
        ) from e
    if faults.poisoned(site):
        out = _poison_value(out)
    return out


# ---------------------------------------------------------------------------
# Adapter helpers
# ---------------------------------------------------------------------------


def _dense_data(v, what: str):
    if not isinstance(v, WVec):
        raise KernelPlanError(f"{what}: expected a vector value")
    if not v.is_dense:
        raise KernelPlanError(f"{what}: kernel path requires a dense vector")
    return v.data


def _elem_of(arrays):
    return arrays[0] if len(arrays) == 1 else tuple(arrays)


def _as_col(v, n):
    """Broadcast a staged per-element result to a full (n,) column."""
    v = jnp.asarray(v)
    if v.ndim >= 1 and v.shape[0] == n:
        return v
    return jnp.broadcast_to(v, (n,) + v.shape)


def _hash_keys(keys):
    """The hash kernels' key space for a (possibly struct) key: a single
    int column of at most 32 bits keys an int32 table — the only width
    Mosaic lowers — and every other key packs into the i64 space the
    generic lowering compares in (jaxgen ``_pack_keys``).  Build and
    probe sides derive it from the same key dtype, so they always agree."""
    if (not isinstance(keys, tuple)
            and jnp.issubdtype(keys.dtype, jnp.integer)
            and jnp.dtype(keys.dtype).itemsize <= 4):
        return keys.astype(jnp.int32)
    return _pack_keys(keys)


# ---------------------------------------------------------------------------
# Adapters
# ---------------------------------------------------------------------------


def _exec_filter_reduce(args, params, fns, impl):
    """(iters...) + staged val/pred bodies -> scalar (or struct of) sums.

    Multi-aggregate calls (weldrel's struct-of-mergers ``agg``) stack
    the staged value columns and take the fused multi-output kernel, so
    the predicate mask and the column tiles are loaded once for ALL
    aggregates instead of once per aggregate.  ``multi=False`` in params
    forces the per-aggregate path (parity tests / ablation)."""
    arrays = [_dense_data(a, "filter_reduce") for a in args]
    n = arrays[0].shape[0]
    idx = jnp.arange(n, dtype=jnp.int64)
    elem = _elem_of(arrays)
    n_aggs = params["n_aggs"]
    block = params.get("block")
    if params["has_pred"]:
        pred = _as_col(fns[n_aggs](idx, elem), n).astype(bool)
    else:
        pred = jnp.ones((n,), dtype=bool)
    vals = [_as_col(fns[k](idx, elem), n) for k in range(n_aggs)]
    fuse = (
        params.get("multi", True)
        and n_aggs > 1
        and len({v.dtype for v in vals}) == 1
    )
    if fuse:
        fused = kops.filter_reduce_sum_multi(jnp.stack(vals), pred,
                                             impl=impl, block=block)
        outs = [fused[k] for k in range(n_aggs)]
    else:
        outs = [kops.filter_reduce_sum(v, pred, impl=impl, block=block)
                for v in vals]
    return tuple(outs) if params["struct"] else outs[0]


def _exec_vecmerger_segment_sum(args, params, fns, impl):
    """base + scatter-add of staged {index, value} pairs via segment_sum."""
    base = _dense_data(args[0], "vecmerger base")
    arrays = [_dense_data(a, "vecmerger") for a in args[1:]]
    n = arrays[0].shape[0]
    idx = jnp.arange(n, dtype=jnp.int64)
    elem = _elem_of(arrays)
    seg = _as_col(fns[0](idx, elem), n).astype(jnp.int32)
    vals = _as_col(fns[1](idx, elem), n).astype(base.dtype)
    k = base.shape[0]
    out = base + kops.segment_sum(seg, vals, num_segments=k, impl=impl,
                                  block=params.get("block"))
    return WVec(out)


def _exec_dict_group_sum(args, params, fns, impl):
    """Dense-int-key group-by-sum + presence compaction: float values by
    one-hot MXU accumulation, integer values (``exact`` in params) by the
    exact word kernel, whose sums and counts equal int64 arithmetic.

    The route assumes keys in [0, capacity).  Rows failing the (optional)
    loop predicate are masked out; rows that PASS the predicate but carry
    an out-of-range key cannot be aggregated here — the generic sort path
    would have kept them — so the result is flagged (negative count) and
    decoding raises instead of returning a silently-short dict.
    """
    arrays = [_dense_data(a, "dict group") for a in args]
    n = arrays[0].shape[0]
    idx = jnp.arange(n, dtype=jnp.int64)
    elem = _elem_of(arrays)
    cap = int(params["capacity"])
    keys = _as_col(fns[0](idx, elem), n).astype(jnp.int64)
    if params.get("has_pred"):
        mask = _as_col(fns[-1](idx, elem), n).astype(bool)
    else:
        mask = jnp.ones((n,), dtype=bool)
    inrange = (keys >= 0) & (keys < cap)
    overflow = jnp.any(mask & ~inrange)
    valid = mask & inrange
    if params.get("exact"):
        counts, outs = _exact_group_sums(fns, params, idx, elem, n, cap,
                                         jnp.where(valid, keys, -1), impl)
    else:
        vals = _as_col(fns[1](idx, elem), n)
        # invalid rows contribute zero to segment 0 (sum identity)
        seg = jnp.where(valid, keys, 0).astype(jnp.int32)
        vals_m = jnp.where(valid, vals, jnp.zeros((), vals.dtype))
        ones = jnp.where(valid, 1, 0).astype(vals.dtype)
        # one fused launch for sums + presence counts (shared seg-id loads)
        both = kops.segment_sum_vectors(
            seg, jnp.stack([vals_m, ones], axis=1), num_segments=cap,
            impl=impl, block=params.get("block"))
        outs, counts = (both[:, 0],), both[:, 1]
    present = counts > 0
    key_dtype = np.dtype(params.get("key_np", "int64"))
    keys_out, vals_out = front_pack(  # keys ascending
        present, (jnp.arange(cap, dtype=key_dtype), tuple(outs)))
    count = present.sum()
    # Overflow guards, layered: the negative count makes host decode raise
    # (WDict.to_numpy); poisoned keys/values cover traced consumers that
    # never decode — KeyExists sees no keys, Lookup yields NaN, so a wrong
    # aggregate cannot propagate as a plausible number.
    count = jnp.where(overflow, -count - 1, count)
    keys_out = jnp.where(overflow, jnp.full_like(keys_out, -1), keys_out)
    vals_out = tuple(
        jnp.where(overflow, jnp.full_like(v, jnp.nan), v)
        if jnp.issubdtype(v.dtype, jnp.floating) else v for v in vals_out)
    return WDict(keys_out, vals_out if len(vals_out) > 1 else vals_out[0],
                 count)


def _exact_group_sums(fns, params, idx, elem, n, cap, seg, impl):
    """The exact route's counts and one column per value field, in the
    field's dtype: a literal field is that many times the count, every
    other the word kernel's int64 sum, narrowed as the field wraps."""
    lits, nps = params["lits"], params["val_nps"]
    computed = [_as_col(fns[1 + k](idx, elem), n)
                for k in range(sum(v is None for v in lits))]
    counts, sums = kops.group_sum_exact(seg.astype(jnp.int32), computed,
                                        cap, impl=impl,
                                        block=params.get("block"))
    sums = iter(sums)
    outs = tuple(
        (next(sums) if lit is None else counts if lit == 1
         else counts * lit).astype(np_dt)
        for lit, np_dt in zip(lits, nps))
    return counts, outs


def _exec_dict_hash_build(args, params, fns, impl):
    """Dictmerger build with arbitrary (sparse) int keys: open-addressing
    hash-to-slot kernel, then per-column segment accumulation over the
    slot ids, then sort-based compaction into the backend's
    sorted-front-packed WDict layout.

    Key space is the same packed-i64 space the generic lowering compares
    in (jaxgen ``_pack_keys``), so probing a hash-built dict and a
    generic dict is indistinguishable.  Overflow (more distinct keys than
    the builder capacity, or a key colliding with the reserved EMPTY
    sentinel) poisons the result with the same negative-count convention
    as the dense group-by route."""
    arrays = [_dense_data(a, "hash build") for a in args]
    n = arrays[0].shape[0]
    idx = jnp.arange(n, dtype=jnp.int64)
    elem = _elem_of(arrays)
    cap = int(params["capacity"])
    nk = int(params.get("n_keys", 1))
    nv = int(params.get("n_vals", 1))
    block = params.get("block")
    key_cols = [_as_col(fns[j](idx, elem), n) for j in range(nk)]
    vals = [_as_col(fns[nk + j](idx, elem), n) for j in range(nv)]
    if params.get("has_pred"):
        mask = _as_col(fns[nk + nv](idx, elem), n).astype(bool)
    else:
        mask = jnp.ones((n,), dtype=bool)
    packed = _hash_keys(tuple(key_cols) if nk > 1 else key_cols[0])
    empty = _ht.empty_of(packed.dtype)
    sentinel_clash = jnp.any(mask & (packed == empty))
    pk = jnp.where(mask, packed, empty)
    ctab = _ht.table_size(cap)
    slots, table, used = kops.hash_to_slot(pk, ctab, impl=impl, block=block)
    overflow = (used > cap) | sentinel_clash
    # table slot -> compact position in ascending packed order (matches
    # the generic keyed finalize, so lookups/decodes are layout-identical)
    tsort = jnp.where(table == empty, jnp.iinfo(table.dtype).max, table)
    order = jnp.argsort(tsort)
    rank = jnp.zeros((ctab,), jnp.int32).at[order].set(
        jnp.arange(ctab, dtype=jnp.int32))
    cslots = jnp.where(slots < ctab, rank[jnp.clip(slots, 0, ctab - 1)],
                       jnp.int32(cap))
    cslots = jnp.where(cslots < cap, cslots, jnp.int32(cap))  # parked/overflow
    key_nps = params.get("key_nps") or (params.get("key_np", "int64"),)
    keys_fin = _recover_key_cols(key_cols, mask, cslots, cap, key_nps,
                                 overflow)
    outs = []
    for v in vals:
        vm = jnp.where(mask, v, jnp.zeros((), v.dtype))
        outs.append(kops.segment_sum(cslots, vm, num_segments=cap,
                                     impl=impl))
    count = jnp.minimum(used.astype(jnp.int64), cap)
    count = jnp.where(overflow, -count - 1, count)
    keys_out = tuple(keys_fin) if nk > 1 else keys_fin[0]
    poisoned = []
    for v in outs:
        if jnp.issubdtype(v.dtype, jnp.floating):
            v = jnp.where(overflow, jnp.full_like(v, jnp.nan), v)
        poisoned.append(v)
    vals_out = tuple(poisoned) if params.get("struct_val") else poisoned[0]
    return WDict(keys_out, vals_out, count)


def _recover_key_cols(key_cols, mask, slots, cap, key_nps, overflow):
    """Per-slot raw key recovery shared by the keyed build adapters:
    every row in a slot holds one key, so a masked ``segment_max`` per
    field reads it back (packing may have dropped high bits); parked
    rows carry slot ``cap`` and fall off the ``[:cap]`` slice, and
    overflow poisons the columns to -1."""
    outs = []
    for kc, knp in zip(key_cols, key_nps):
        src = jnp.where(mask, kc, jnp.iinfo(kc.dtype).min)
        ko = jax.ops.segment_max(src, slots.astype(jnp.int32),
                                 num_segments=cap + 1)[:cap]
        ko = ko.astype(np.dtype(knp))
        outs.append(jnp.where(overflow, jnp.full_like(ko, -1), ko))
    return outs


def _probe_membership(args, params, fns, impl, nk, n_iters=None):
    """Shared prologue of the probe adapters: stage the probe-side
    columns, pack the (possibly multi-column) query keys into the i64
    key space, neutralize the table's parked slots, and run ONE
    membership kernel — ``dict_probe`` for dict tables, the fused
    membership + match-count ``group_probe`` for group (m:n) tables.
    Returns ``(n, idx, elem, pos, found, sizes, cap)`` with ``sizes``
    None for dicts."""
    d = args[0]
    if not isinstance(d, (WDict, WGroup)):
        raise KernelPlanError("probe: expected a dict/group value")
    is_group = isinstance(d, WGroup)
    tail = args[1:] if n_iters is None else args[1:1 + n_iters]
    arrays = [_dense_data(a, "hash probe") for a in tail]
    n = arrays[0].shape[0]
    idx = jnp.arange(n, dtype=jnp.int64)
    elem = _elem_of(arrays)
    key_cols = [_as_col(fns[j](idx, elem), n) for j in range(nk)]
    keys_q = _hash_keys(tuple(key_cols) if nk > 1 else key_cols[0])
    packed_t = _hash_keys(d.keys)
    cap = packed_t.shape[0]
    cnt = jnp.maximum(jnp.asarray(d.count, jnp.int64), 0)
    sizes = jnp.zeros((n,), jnp.int64) if is_group else None
    if cap == 0:
        pos = jnp.zeros((n,), jnp.int32)
        found = jnp.zeros((n,), dtype=bool)
    else:
        big = jnp.iinfo(packed_t.dtype).max
        neut = jnp.where(jnp.arange(cap) < cnt, packed_t, big)
        if is_group:
            pos, found, sizes = kops.group_probe(
                neut, d.offsets, cnt, keys_q, impl=impl,
                block=params.get("block"))
            sizes = sizes.astype(jnp.int64)
        else:
            pos, found = kops.dict_probe(neut, cnt, keys_q, impl=impl,
                                         block=params.get("block"))
    return n, idx, elem, pos, found, sizes, cap


def _exec_hash_probe(args, params, fns, impl):
    """Probe a dict with per-row keys; keep matching rows (front-packed)
    and emit either the looked-up value column (``gather``) or a staged
    elementwise expression over the probe row.  The positional probe
    kernel serves every value dtype — the gather itself is a plain jnp
    indexing outside the kernel.

    Fused calls (``cols`` in params — weldrel's horizontally fused join
    probe) dispatch to :func:`_exec_hash_probe_fused`: ONE membership
    kernel launch shared by every output column."""
    if "cols" in params:
        return _exec_hash_probe_fused(args, params, fns, impl)
    d = args[0]
    n, idx, elem, pos, found, _, cap = _probe_membership(
        args, params, fns, impl, nk=1)
    gather = bool(params.get("gather"))
    if params.get("has_pred"):
        mask = _as_col(fns[1 if gather else 2](idx, elem), n).astype(bool)
        found = found & mask
    if gather:
        field = int(params.get("field", -1))
        vcol = d.vals[field] if isinstance(d.vals, tuple) else d.vals
        if cap == 0 or vcol.shape[0] == 0:
            out = jnp.zeros((n,), vcol.dtype)
        else:
            out = vcol[jnp.clip(pos, 0, vcol.shape[0] - 1)]
    else:
        out = _as_col(fns[1](idx, elem), n)
    count = jnp.where(jnp.asarray(d.count, jnp.int64) < 0,
                      jnp.int64(-1), found.sum().astype(jnp.int64))
    return WVec(front_pack(found, out), count=count)


def _exec_hash_probe_fused(args, params, fns, impl):
    """Horizontally fused join probe: ONE ``dict_probe`` launch computes
    the found-mask/positions for the (possibly multi-column, packed)
    keys, then EVERY output column reuses them — build-side columns as
    plain gathers, probe-side columns as staged expressions, and ONE
    front-pack sort carrying every column (:func:`front_pack`).

    ``how`` selects the row semantics: ``inner`` keeps found rows,
    ``anti`` keeps misses (left columns only), and ``left`` keeps every
    row — misses in gathered columns fill from the per-column ``fills``
    (the planner lifts them off the ``lookup(d, k, fill)`` defaults)
    instead of front-packing, so no second probe pass exists anywhere."""
    d = args[0]
    how = params["how"]
    nk = int(params.get("n_keys", 1))
    n, idx, elem, pos, found, _, cap = _probe_membership(
        args, params, fns, impl, nk=nk)
    mask = None
    if params.get("has_pred"):
        mask = _as_col(fns[-1](idx, elem), n).astype(bool)
    outs = []
    for (kind, j), fill in zip(params["cols"], params["fills"]):
        if kind == "expr":
            col = _as_col(fns[nk + j](idx, elem), n)
        else:
            vcol = d.vals[j] if isinstance(d.vals, tuple) else d.vals
            if cap == 0 or vcol.shape[0] == 0:
                col = jnp.zeros((n,), vcol.dtype)
            else:
                col = vcol[jnp.clip(pos, 0, vcol.shape[0] - 1)]
            if how == "left":
                col = jnp.where(found, col, jnp.asarray(fill, vcol.dtype))
        outs.append(col)
    keep = {"inner": found, "anti": ~found, "left": None}[how]
    if mask is not None:
        keep = mask if keep is None else keep & mask
    poisoned = jnp.asarray(d.count, jnp.int64) < 0
    if keep is None:  # left join, no predicate: every row survives
        count = jnp.where(poisoned, jnp.int64(-1), jnp.int64(n))
        return tuple(WVec(c, count=count) for c in outs)
    count = jnp.where(poisoned, jnp.int64(-1),
                      keep.sum().astype(jnp.int64))
    # ONE shared front-pack: a sort that carries every column
    return tuple(WVec(c, count=count) for c in front_pack(keep, tuple(outs)))


def _exec_group_build(args, params, fns, impl):
    """Groupbuilder build (the m:n join build side): hash-to-slot over
    the packed keys, slot-histogram compaction into CSR offsets, and the
    payload column sorted by (ascending key, build-row order) — the
    layout the generic keyed finalize produces, so the probe side is
    indistinguishable.  Overflow (more distinct keys than the builder
    capacity, or a key hitting the reserved EMPTY sentinel) poisons via
    the shared negative-count convention."""
    arrays = [_dense_data(a, "group build") for a in args]
    n = arrays[0].shape[0]
    idx = jnp.arange(n, dtype=jnp.int64)
    elem = _elem_of(arrays)
    cap = int(params["capacity"])
    nk = int(params.get("n_keys", 1))
    block = params.get("block")
    key_cols = [_as_col(fns[j](idx, elem), n) for j in range(nk)]
    val = _as_col(fns[nk](idx, elem), n)
    if params.get("has_pred"):
        mask = _as_col(fns[nk + 1](idx, elem), n).astype(bool)
    else:
        mask = jnp.ones((n,), dtype=bool)
    packed = _hash_keys(tuple(key_cols) if nk > 1 else key_cols[0])
    empty = _ht.empty_of(packed.dtype)
    sentinel_clash = jnp.any(mask & (packed == empty))
    pk = jnp.where(mask, packed, empty)
    cslots, offsets, used = kops.group_build(pk, cap, impl=impl, block=block)
    overflow = (used > cap) | sentinel_clash
    # CSR payload ordering: ascending compact slot, stable — within a
    # group, build-row order (identical to the generic keyed finalize)
    order = jnp.argsort(cslots, stable=True)
    values = val[order]
    key_nps = params.get("key_nps") or ("int64",)
    keys_fin = _recover_key_cols(key_cols, mask, cslots, cap, key_nps,
                                 overflow)
    keys_out = tuple(keys_fin) if nk > 1 else keys_fin[0]
    count = jnp.minimum(used.astype(jnp.int64), cap)
    count = jnp.where(overflow, -count - 1, count)
    return WGroup(keys_out, values, offsets, count)


def _exec_group_probe(args, params, fns, impl):
    """The m:n join fan-out probe: ONE fused membership + match-count
    launch (``kops.group_probe``) for the packed keys, then the shared
    two-phase expansion (exclusive scan over the per-row counts, binary
    search back to source rows, repeat/gather) materializes EVERY output
    column through one expansion index — probe columns repeat, build
    columns gather through the group's stored row ids, left-join misses
    emit one fill row.  Poison propagates as a negative output count."""
    d = args[0]
    if not isinstance(d, WGroup):
        raise KernelPlanError("group_probe: expected a groupbuilder value")
    if isinstance(d.values, tuple):
        raise KernelPlanError("group_probe: scalar payloads only")
    how = params["how"]
    nk = int(params.get("n_keys", 1))
    n_iters = int(params.get("n_iters", 1))
    n, idx, elem, pos, found, sizes, cap = _probe_membership(
        args, params, fns, impl, nk=nk, n_iters=n_iters)
    if params.get("has_pred"):
        mask = _as_col(fns[-1](idx, elem), n).astype(bool)
    else:
        mask = jnp.ones((n,), dtype=bool)
    col_specs = []
    for (kind, j), fill in zip(params["cols"], params["fills"]):
        if kind == "expr":
            col_specs.append(("expr", _as_col(fns[nk + j](idx, elem), n)))
        else:
            rv = _dense_data(args[j], "group probe gather")
            col_specs.append(("gather", rv, fill))
    return group_expand(d, pos, found, sizes, mask, how,
                        int(params["out_cap"]), col_specs)


def _tiles(params) -> dict:
    return {k: params.get(k) for k in ("bm", "bn", "bk")}


def _exec_matmul(args, params, fns, impl):
    a = _dense_data(args[0], "matmul lhs")
    b = _dense_data(args[1], "matmul rhs")
    ct = jnp.result_type(a, b)
    return WVec(kops.matmul(a.astype(ct), b.astype(ct), impl=impl,
                            **_tiles(params)))


def _exec_matvec(args, params, fns, impl):
    a = _dense_data(args[0], "matvec lhs")
    b = _dense_data(args[1], "matvec rhs")
    ct = jnp.result_type(a, b)
    out = kops.matmul(a.astype(ct), b[:, None].astype(ct), impl=impl,
                      **_tiles(params))
    return WVec(out[:, 0])


def _exec_map_elementwise(args, params, fns, impl):
    arrays = [_dense_data(a, "map chain") for a in args]

    def body(*cols):
        # the staged lambda is (i, x); map-chain matching guarantees the
        # index is unused, so bind a dummy scalar.
        return fns[0](0, _elem_of(list(cols)))

    return WVec(kops.map_elementwise(body, arrays, impl=impl,
                                     block=params.get("block")))


# ---------------------------------------------------------------------------
# Footprints: padding + scratch bytes one call adds to the HBM budget.
# (arg_shapes are the dense arg shapes at trace time; itemsize is the
# result element width.)  Charged by the emitter against memory_limit.
# ---------------------------------------------------------------------------


def _pad_of(n: int, block: int) -> int:
    return (-n) % max(block, 1)


def _fp_filter_reduce(arg_shapes, itemsize, params):
    n = arg_shapes[0][0] if arg_shapes and arg_shapes[0] else 0
    pad = _pad_of(n, params.get("block") or _fr.BLOCK)
    aggs = params.get("n_aggs", 1)
    # staged value columns (one per agg; stacked when fused) + pred mask
    scratch = aggs * (n + pad) * itemsize + (n + pad)
    return pad * len(arg_shapes) * itemsize + scratch


def _fp_vecmerger(arg_shapes, itemsize, params):
    n = arg_shapes[1][0] if len(arg_shapes) > 1 and arg_shapes[1] else 0
    pad = _pad_of(n, params.get("block") or _sr.BLOCK_N)
    # staged seg-id (i32) and value columns + the padded tails
    return (n + pad) * (4 + itemsize) + pad * itemsize * (len(arg_shapes) - 1)


def _fp_dict_group(arg_shapes, itemsize, params):
    n = arg_shapes[0][0] if arg_shapes and arg_shapes[0] else 0
    cap = int(params.get("capacity", 0))
    pad = _pad_of(n, params.get("block") or _sr.BLOCK_N)
    if params.get("exact"):
        # staged keys/mask/values, the padded int32 plane stack (the key
        # and two 16-bit words per container) and the chunk partials
        planes = 1 + (params["words"] - 1) // 2
        return ((n + pad) * (4 * planes + 9 + 8 * len(params["lits"]))
                + cap * params["words"] * 128 * 4
                * (-(-(n + pad) // _sr.CHUNK_ROWS)))
    # staged keys/mask + the stacked (n, 2) value matrix + K-compaction
    return (n + pad) * (4 + 2 * itemsize + 1) + cap * (3 * itemsize + 8)


def _fp_hash_build(arg_shapes, itemsize, params):
    n = arg_shapes[0][0] if arg_shapes and arg_shapes[0] else 0
    cap = int(params.get("capacity", 0))
    ctab = _ht.table_size(cap) if cap else 16
    pad = _pad_of(n, params.get("block") or _ht.BLOCK_N)
    nv = int(params.get("n_vals", 1))
    # staged packed keys + slots + per-column staged values, the VMEM
    # table + rank permutation, and the compacted key/value columns
    return ((n + pad) * (8 + 4 + nv * itemsize)
            + ctab * (8 + 8) + cap * (nv * itemsize + 8))


def _fp_hash_probe(arg_shapes, itemsize, params):
    n = arg_shapes[1][0] if len(arg_shapes) > 1 and arg_shapes[1] else 0
    block = params.get("block") or _hp.BLOCK_N
    pad = _pad_of(n, block)
    cap = int(params.get("k", 0))
    cols = max(len(params.get("cols", ())), 1)
    # staged packed queries + pos/found columns + the (per output
    # column) gathered/compacted outputs, plus the neutralized key
    # table and the block x cap one-hot tile — shared across columns
    return ((n + pad) * (8 + 4 + 1 + cols * itemsize) + n * cols * itemsize
            + cap * 8 + block * cap * 5)


def _fp_group_build(arg_shapes, itemsize, params):
    n = arg_shapes[0][0] if arg_shapes and arg_shapes[0] else 0
    cap = int(params.get("capacity", 0))
    ctab = _ht.table_size(cap) if cap else 16
    pad = _pad_of(n, params.get("block") or _gb.BLOCK_N)
    # staged packed keys + slots + payload column + the ordering sort,
    # the VMEM table + rank + counts, and the CSR offsets/key columns
    return ((n + pad) * (8 + 4 + itemsize + 8)
            + ctab * (8 + 8) + (cap + 1) * 4 + cap * 8)


def _fp_group_probe(arg_shapes, itemsize, params):
    n = arg_shapes[1][0] if len(arg_shapes) > 1 and arg_shapes[1] else 0
    block = params.get("block") or _hp.BLOCK_N
    pad = _pad_of(n, block)
    cap = int(params.get("k", 0))
    out = int(params.get("out_cap", 0))
    cols = max(len(params.get("cols", ())), 1)
    # staged packed queries + pos/found/size columns, the one-hot tile
    # (keys + sizes lanes), and the expanded output buffers every
    # column shares (the expansion-factor term of the memory budget)
    return ((n + pad) * (8 + 4 + 1 + 4) + out * (cols * itemsize + 8 + 8)
            + cap * (8 + 4) + block * cap * 6)


def _fp_matmul(arg_shapes, itemsize, params):
    if len(arg_shapes) < 2 or not arg_shapes[0] or not arg_shapes[1]:
        return 0
    m, k = arg_shapes[0][0], arg_shapes[0][1] if len(arg_shapes[0]) > 1 else 1
    n = arg_shapes[1][1] if len(arg_shapes[1]) > 1 else 1
    bm = params.get("bm") or 256
    bn = params.get("bn") or 256
    bk = params.get("bk") or 512
    mp, kp, np_ = m + _pad_of(m, bm), k + _pad_of(k, bk), n + _pad_of(n, bn)
    return (mp * kp - m * k + kp * np_ - k * n + mp * np_ - m * n) * itemsize


def _fp_map_chain(arg_shapes, itemsize, params):
    n = arg_shapes[0][0] if arg_shapes and arg_shapes[0] else 0
    pad = _pad_of(n, params.get("block") or _mc.BLOCK)
    return pad * (len(arg_shapes) + 1) * itemsize


# ---------------------------------------------------------------------------
# Autotune benches: synthetic workloads matching the tuned call's shape.
# (meta carries n / k / dims / dtype; params is one candidate point.)
# ---------------------------------------------------------------------------


def _bench_filter_reduce(meta, params, impl):
    n = int(meta["n"])
    x = jnp.ones((n,), meta.get("dtype", jnp.float64))
    p = jnp.ones((n,), bool)

    def go():
        jax.block_until_ready(kops.filter_reduce_sum(
            x, p, impl=impl, block=params.get("block")))

    return go


def _bench_vecmerger(meta, params, impl):
    n = int(meta["n"])
    k = int(meta.get("k") or 256)
    seg = (jnp.arange(n, dtype=jnp.int32) % max(min(k, _sr.MAX_K), 1))
    vals = jnp.ones((n,), meta.get("dtype", jnp.float64))

    def go():
        jax.block_until_ready(kops.segment_sum(
            seg, vals, num_segments=min(k, _sr.MAX_K), impl=impl,
            block=params.get("block")))

    return go


def _bench_dict_group(meta, params, impl):
    n = int(meta["n"])
    k = int(meta.get("k") or 256)
    seg = (jnp.arange(n, dtype=jnp.int32) % max(min(k, _sr.MAX_K), 1))
    vals = jnp.ones((n, 2), meta.get("dtype", jnp.float64))

    def go():
        jax.block_until_ready(kops.segment_sum_vectors(
            seg, vals, num_segments=min(k, _sr.MAX_K), impl=impl,
            block=params.get("block")))

    return go


def _bench_hash_build(meta, params, impl):
    # the insert chain is serial: cap the synthetic size so first-touch
    # tuning stays cheap (relative block ordering stabilizes well below
    # real workload sizes)
    n = min(int(meta["n"]), 8192)
    k = max(int(meta.get("k") or 256), 1)
    keys = (jnp.arange(n, dtype=jnp.int32) % k) * 7 + 3
    ctab = _ht.table_size(k)

    def go():
        jax.block_until_ready(kops.hash_to_slot(
            keys, ctab, impl=impl, block=params.get("block")))

    return go


def _bench_hash_probe(meta, params, impl):
    n = int(meta["n"])
    k = max(int(meta.get("k") or 256), 1)
    table = jnp.arange(k, dtype=jnp.int32) * 3
    queries = (jnp.arange(n, dtype=jnp.int32) % (2 * k)) * 3  # ~50% hits

    def go():
        jax.block_until_ready(kops.dict_probe(
            table, k, queries, impl=impl, block=params.get("block")))

    return go


def _bench_group_build(meta, params, impl):
    # the insert/histogram chains are serial: cap the synthetic size so
    # first-touch tuning stays cheap (same rationale as hash_build)
    n = min(int(meta["n"]), 8192)
    k = max(int(meta.get("k") or 256), 1)
    keys = (jnp.arange(n, dtype=jnp.int32) % k) * 7 + 3

    def go():
        jax.block_until_ready(kops.group_build(
            keys, k, impl=impl, block=params.get("block")))

    return go


def _bench_group_probe(meta, params, impl):
    n = int(meta["n"])
    k = max(int(meta.get("k") or 256), 1)
    table = jnp.arange(k, dtype=jnp.int32) * 3
    offsets = (jnp.arange(k + 1, dtype=jnp.int32) * 4)  # fan-out 4
    queries = (jnp.arange(n, dtype=jnp.int32) % (2 * k)) * 3  # ~50% hits

    def go():
        jax.block_until_ready(kops.group_probe(
            table, offsets, k, queries, impl=impl,
            block=params.get("block")))

    return go


def _bench_matmul(meta, params, impl):
    m, k, n = (int(d) for d in meta["dims"])
    a = jnp.ones((m, k), meta.get("dtype", jnp.float64))
    b = jnp.ones((k, n), meta.get("dtype", jnp.float64))

    def go():
        jax.block_until_ready(kops.matmul(
            a, b, impl=impl, bm=params.get("bm"), bn=params.get("bn"),
            bk=params.get("bk")))

    return go


def _bench_map_chain(meta, params, impl):
    n = int(meta["n"])
    x = jnp.ones((n,), meta.get("dtype", jnp.float64))

    def go():
        jax.block_until_ready(kops.map_elementwise(
            lambda c: c * 2.0 + 1.0, [x], impl=impl,
            block=params.get("block")))

    return go


# ---------------------------------------------------------------------------
# The shipped registry (one entry per reachable Pallas kernel)
# ---------------------------------------------------------------------------

register(KernelSpec(
    name="filter_reduce_sum",
    entry="repro.kernels.ops:filter_reduce_sum",
    pattern="filter_reduce",
    builder="merger[+]",
    elem_kinds=("f32", "f64", "i32", "i64"),
    description="predicated sum over a (possibly multi-column) loop; the "
                "fused form of Listing 10 / TPC-H Q6; multi-aggregate "
                "struct matches fuse into one multi-output launch",
    execute=_exec_filter_reduce,
    cost=_cost.cost_filter_reduce,
    tune_space={"block": _fr.BLOCK_CANDIDATES},
    tune_defaults={"block": _fr.BLOCK},
    make_bench=_bench_filter_reduce,
    footprint=_fp_filter_reduce,
))

register(KernelSpec(
    name="vecmerger_segment_sum",
    entry="repro.kernels.ops:segment_sum",
    pattern="vecmerger_scatter",
    builder="vecmerger[+]",
    elem_kinds=("f32", "f64"),
    description="scatter-add into a dense base vector as one-hot MXU "
                "segment sums (PageRank's edge scan)",
    max_segments=_sr.MAX_K,  # beyond this, kops serves the ref scatter:
                             # the cost gate prices that route as a loss
    tpu_kinds=("f32",),
    execute=_exec_vecmerger_segment_sum,
    cost=_cost.cost_vecmerger,
    tune_space={"block": _sr.BLOCK_CANDIDATES},
    tune_defaults={"block": _sr.BLOCK_N},
    make_bench=_bench_vecmerger,
    footprint=_fp_vecmerger,
))

register(KernelSpec(
    name="dict_group_sum",
    entry="repro.kernels.ops:segment_sum_vectors",
    pattern="dict_group",
    builder="dictmerger[+]",
    elem_kinds=("f32", "f64", "i32", "i64"),
    description="group-by-sum with dense int keys in [0, capacity) via "
                "segment_sum + presence compaction; integer values (a "
                "scalar or weldrel's multi-aggregate struct) over at most "
                "64 keys take the exact word kernel (group_sum_exact)",
    max_segments=_sr.MAX_K,
    tpu_kinds=("f32",),
    execute=_exec_dict_group_sum,
    cost=_cost.cost_dict_group,
    tune_space={"block": _sr.BLOCK_CANDIDATES},
    tune_defaults={"block": _sr.BLOCK_N},
    make_bench=_bench_dict_group,
    footprint=_fp_dict_group,
))

register(KernelSpec(
    name="dict_hash_build",
    entry="repro.kernels.ops:hash_to_slot",
    pattern="dict_hash_build",
    builder="dictmerger[+]",
    elem_kinds=("f32", "f64", "i32", "i64"),
    description="open-addressing hash build for sparse/non-dense int "
                "keys, scalar or multi-column struct (hash-join build "
                "side; also the group-by fallback beyond the dense "
                "segment route's capacity)",
    max_segments=_ht.MAX_CAP,
    tpu_kinds=("i32", "f32"),
    execute=_exec_dict_hash_build,
    cost=_cost.cost_hash_build,
    tune_space={"block": _ht.BLOCK_CANDIDATES},
    tune_defaults={"block": _ht.BLOCK_N},
    make_bench=_bench_hash_build,
    footprint=_fp_hash_build,
))

register(KernelSpec(
    name="hash_probe",
    entry="repro.kernels.ops:dict_probe",
    pattern="hash_probe",
    builder="vecbuilder",
    elem_kinds=("f32", "f64", "i32", "i64"),
    description="key-sweep dict probe: one membership launch shared "
                "by every join output column (inner filter / left "
                "fill-on-miss / anti), gathers outside the kernel",
    max_segments=_ht.MAX_CAP,
    tpu_kinds=("i32",),
    execute=_exec_hash_probe,
    cost=_cost.cost_hash_probe,
    tune_space={"block": _hp.BLOCK_CANDIDATES},
    tune_defaults={"block": _hp.BLOCK_N},
    make_bench=_bench_hash_probe,
    footprint=_fp_hash_probe,
))

register(KernelSpec(
    name="group_build",
    entry="repro.kernels.ops:group_build",
    pattern="group_build",
    builder="groupbuilder",
    elem_kinds=("i32", "i64"),
    description="CSR group build (key -> growing vector of build-row "
                "payloads) via hash-to-slot + slot-histogram compaction "
                "— the m:n hash-join build side",
    max_segments=_ht.MAX_CAP,
    tpu_kinds=("i32",),
    execute=_exec_group_build,
    cost=_cost.cost_group_build,
    tune_space={"block": _gb.BLOCK_CANDIDATES},
    tune_defaults={"block": _gb.BLOCK_N},
    make_bench=_bench_group_build,
    footprint=_fp_group_build,
))

register(KernelSpec(
    name="group_probe",
    entry="repro.kernels.ops:group_probe",
    pattern="group_probe",
    builder="vecbuilder",
    elem_kinds=("bool", "i8", "i32", "i64", "f32", "f64"),
    description="m:n join fan-out probe: ONE fused membership + "
                "match-count launch shared by every output column, "
                "then the two-phase expansion (scan + repeat/gather) "
                "outside the kernel",
    max_segments=_ht.MAX_CAP,
    tpu_kinds=("i32",),
    execute=_exec_group_probe,
    cost=_cost.cost_group_probe,
    tune_space={"block": _hp.BLOCK_CANDIDATES},
    tune_defaults={"block": _hp.BLOCK_N},
    make_bench=_bench_group_probe,
    footprint=_fp_group_probe,
))

register(KernelSpec(
    name="matmul",
    entry="repro.kernels.ops:matmul",
    pattern="linalg.matmul",
    builder="-",
    elem_kinds=("f32", "f64"),
    description="tiled VMEM-blocked matmul for raised 2-D dot loops",
    tpu_kinds=("f32",),
    execute=_exec_matmul,
    cost=_cost.cost_matmul,
    tune_space={"bm": _tm.BM_CANDIDATES, "bn": _tm.BN_CANDIDATES,
                "bk": _tm.BK_CANDIDATES},
    tune_defaults={"bm": 256, "bn": 256, "bk": 512},
    make_bench=_bench_matmul,
    footprint=_fp_matmul,
))

register(KernelSpec(
    name="matvec",
    entry="repro.kernels.ops:matmul",
    pattern="linalg.matvec",
    builder="-",
    elem_kinds=("f32", "f64"),
    description="matrix-vector product through the tiled matmul kernel",
    tpu_kinds=("f32",),
    execute=_exec_matvec,
    cost=_cost.cost_matmul,
    tune_space={"bm": _tm.BM_CANDIDATES, "bk": _tm.BK_CANDIDATES},
    tune_defaults={"bm": 256, "bk": 512},
    make_bench=None,  # shares the matmul entry; tuned via matmul dims
    footprint=_fp_matmul,
))

register(KernelSpec(
    name="map_elementwise",
    entry="repro.kernels.ops:map_elementwise",
    pattern="map_chain",
    builder="vecbuilder",
    elem_kinds=("f32", "f64", "i32", "i64"),
    description="fused elementwise map chain staged into one Pallas pass "
                "(Black-Scholes-style operator chains)",
    execute=_exec_map_elementwise,
    cost=_cost.cost_map_chain,
    tune_space={"block": _mc.BLOCK_CANDIDATES},
    tune_defaults={"block": _mc.BLOCK},
    make_bench=_bench_map_chain,
    footprint=_fp_map_chain,
))
