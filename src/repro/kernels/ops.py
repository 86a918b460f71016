"""Public jit'd wrappers for the Pallas kernels.

``impl`` selects the execution path:
  * "pallas"    — the Pallas kernel compiled for the accelerator
  * "interpret" — the Pallas kernel body interpreted on CPU (validation)
  * "ref"       — the pure-jnp oracle (CPU benchmarks, dry-run lowering)
An omitted ``impl`` resolves from the backend, once per process:
"pallas" when JAX's default backend is a TPU, "ref" elsewhere.  An
explicit ``impl=`` always wins.  Resolution happens OUTSIDE jit (impl is
a static argument of the inner jit).

Every Pallas kernel traces in 32-bit mode when its operands are 32-bit:
the Weld runtime runs the process with x64 on, and Mosaic lowers no
64-bit index map, loop counter or scalar.  64-bit operands are accepted
by the ref and interpret paths only — the kernel planner rejects them for
"pallas" before anything is staged.

Block sizes are tunable: every entry takes an optional block override
(``block=``, or ``bm``/``bn``/``bk`` for the matmul) resolved to the
kernel module's default when omitted.  The kernel planner's autotuner
(``repro.core.kernelplan.autotune``) sweeps each module's
``*_CANDIDATES`` grid and passes the per-(dtype, size-bucket) winner
through these knobs; the ref oracle ignores them by construction.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Literal, Optional

import jax
import jax.numpy as jnp

from . import filter_reduce as _fr
from . import flash_attention as _fa
from . import fused_adamw as _aw
from . import group_build as _gb
from . import hash_probe as _hp
from . import hash_table as _ht
from . import map_chain as _mc
from . import ref as _ref
from . import segment_reduce as _sr
from . import tiled_matmul as _tm

Impl = Literal["pallas", "interpret", "ref"]

#: the process default, resolved from the backend on first use.
DEFAULT_IMPL: Optional[Impl] = None


def default_impl() -> Impl:
    """The kernel path an omitted ``impl`` takes: "pallas" on a TPU
    backend, "ref" elsewhere."""
    global DEFAULT_IMPL
    if DEFAULT_IMPL is None:
        DEFAULT_IMPL = "pallas" if jax.default_backend() == "tpu" else "ref"
    return DEFAULT_IMPL


def set_default_impl(impl: Impl) -> None:
    global DEFAULT_IMPL
    DEFAULT_IMPL = impl


def _resolve(impl: Optional[str]) -> str:
    return default_impl() if impl is None else impl


def _kernel_mode(*operands):
    """x64 mode to trace a Pallas kernel in: off when every operand is
    32-bit or narrower (what Mosaic lowers), the process mode otherwise."""
    if any(jnp.dtype(a.dtype).itemsize > 4
           for a in jax.tree_util.tree_leaves(operands)):
        return contextlib.nullcontext()
    return jax.enable_x64(False)


# -- filter+reduce -------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("impl", "block"))
def _frs(x, pred, impl, block):
    if impl == "ref":
        return _ref.filter_reduce_sum(x, pred)
    with _kernel_mode(x):
        return _fr.filter_reduce_sum(x, pred, block=block,
                                     interpret=(impl == "interpret"))


def filter_reduce_sum(x, pred, impl: Optional[Impl] = None,
                      block: Optional[int] = None):
    return _frs(x, pred, impl=_resolve(impl), block=block or _fr.BLOCK)


@functools.partial(jax.jit, static_argnames=("impl", "block"))
def _frsm(vals, pred, impl, block):
    if impl == "ref":
        return _ref.filter_reduce_sum_multi(vals, pred)
    with _kernel_mode(vals):
        return _fr.filter_reduce_sum_multi(vals, pred, block=block,
                                           interpret=(impl == "interpret"))


def filter_reduce_sum_multi(vals, pred, impl: Optional[Impl] = None,
                            block: Optional[int] = None):
    """Predicated row sums: vals (A, n) + pred (n,) -> (A,) in ONE pass
    (the multi-aggregate fusion of filter_reduce_sum)."""
    return _frsm(vals, pred, impl=_resolve(impl), block=block or _fr.BLOCK)


@functools.partial(jax.jit, static_argnames=("impl", "block"))
def _frq6(cols, lo, hi, val, impl, block):
    if impl == "ref":
        return _ref.filter_reduce_q6(cols, lo, hi, val)
    with _kernel_mode(cols, lo, hi, val):
        return _fr.filter_reduce_q6(cols, lo, hi, val, block=block,
                                    interpret=(impl == "interpret"))


def filter_reduce_q6(cols, lo, hi, val, impl: Optional[Impl] = None,
                     block: Optional[int] = None):
    return _frq6(cols, lo, hi, val, impl=_resolve(impl),
                 block=block or _fr.BLOCK)


# -- segment reduce -------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("num_segments", "impl", "block"))
def _ss(seg_ids, vals, num_segments, impl, block):
    if impl == "ref":
        return _ref.segment_sum(seg_ids, vals, num_segments)
    with _kernel_mode(vals):
        return _sr.segment_sum(seg_ids.astype(jnp.int32), vals, num_segments,
                               block=block, interpret=(impl == "interpret"))


def segment_sum(seg_ids, vals, num_segments: int,
                impl: Optional[Impl] = None, block: Optional[int] = None):
    impl = _resolve(impl)
    if num_segments > _sr.MAX_K:
        impl = "ref"
    return _ss(seg_ids, vals, num_segments=num_segments, impl=impl,
               block=block or _sr.BLOCK_N)


@functools.partial(jax.jit, static_argnames=("num_segments", "impl", "block"))
def _ssv(seg_ids, vals, num_segments, impl, block):
    if impl == "ref":
        return _ref.segment_sum_vectors(seg_ids, vals, num_segments)
    with _kernel_mode(vals):
        return _sr.segment_sum_vectors(seg_ids.astype(jnp.int32), vals,
                                       num_segments, block=block,
                                       interpret=(impl == "interpret"))


def segment_sum_vectors(seg_ids, vals, num_segments: int,
                        impl: Optional[Impl] = None,
                        block: Optional[int] = None):
    impl = _resolve(impl)
    if num_segments > _sr.MAX_K:
        impl = "ref"
    return _ssv(seg_ids, vals, num_segments=num_segments, impl=impl,
                block=block or _sr.BLOCK_N)


# -- exact group sums (integer dictmerger route) ----------------------------------


@functools.partial(jax.jit, static_argnames=("n_groups", "impl", "block"))
def _gse(keys, values, n_groups, impl, block):
    if impl == "ref":
        return _ref.group_sum_exact(keys, values, n_groups)
    per = _sr.containers(v.dtype for v in values)
    tops = tuple(t for c in per for t in c)
    planes = _sr.group_planes(keys, values, block)
    with _kernel_mode(planes):
        part = _sr.group_sum_words(planes, n_groups, tops, block=block,
                                   interpret=(impl == "interpret"))
    return _sr.combine_partials(part, n_groups, [len(c) for c in per])


def group_sum_exact(keys, values, n_groups: int,
                    impl: Optional[Impl] = None, block: Optional[int] = None):
    """Exact per-group counts and sums of integer columns: ``keys`` int32
    group ids in ``[0, n_groups)`` (any other id leaves its row out),
    ``values`` a sequence of integer columns.  Returns ``(counts,
    sums)``, int64 ``(n_groups,)`` arrays, ``sums`` one per value."""
    return _gse(keys, tuple(values), n_groups=n_groups, impl=_resolve(impl),
                block=block or _sr.GROUP_BLOCK)


# -- dict build / probe (hash-join route) -----------------------------------------


@functools.partial(jax.jit, static_argnames=("cap_table", "impl", "block"))
def _hts(keys, cap_table, impl, block):
    if impl == "ref":
        return _ref.hash_to_slot(keys, cap_table)
    with _kernel_mode(keys):
        return _ht.hash_to_slot(keys, cap_table, block=block,
                                interpret=(impl == "interpret"))


def hash_to_slot(keys, cap_table: int, impl: Optional[Impl] = None,
                 block: Optional[int] = None):
    """Open-addressing slot assignment for int32 or packed int64 keys;
    rows equal to ``hash_table.empty_of(keys.dtype)`` park at slot
    ``cap_table``.  Returns ``(slots, table_keys, used)`` — see
    kernels/hash_table.py."""
    return _hts(keys, cap_table=cap_table, impl=_resolve(impl),
                block=block or _ht.BLOCK_N)


@functools.partial(jax.jit, static_argnames=("impl", "block"))
def _dp(table_keys, count, queries, impl, block):
    if impl == "ref":
        return _ref.dict_probe(table_keys, count, queries)
    with _kernel_mode(table_keys, queries):
        return _hp.dict_probe(table_keys, count.astype(jnp.int32), queries,
                              block=block, interpret=(impl == "interpret"))


def dict_probe(table_keys, count, queries, impl: Optional[Impl] = None,
               block: Optional[int] = None):
    """(pos, found) per query against a sorted-front-packed dict key
    column; ``pos`` is zeroed where not found."""
    return _dp(table_keys, jnp.asarray(count), queries, impl=_resolve(impl),
               block=block or _hp.BLOCK_N)


# -- group build / probe (m:n hash-join route) ------------------------------------


@functools.partial(jax.jit, static_argnames=("capacity", "impl", "block"))
def _gbd(keys, capacity, impl, block):
    if impl == "ref":
        return _ref.group_build(keys, capacity)
    with _kernel_mode(keys):
        return _gb.group_build(keys, capacity, block=block,
                               interpret=(impl == "interpret"))


def group_build(keys, capacity: int, impl: Optional[Impl] = None,
                block: Optional[int] = None):
    """CSR group build over int32 / packed int64 keys: rows with equal keys share
    an ascending-key compact slot.  Returns ``(cslots, offsets, used)``
    — see kernels/group_build.py for the contract."""
    return _gbd(keys, capacity=capacity, impl=_resolve(impl),
                block=block or _gb.BLOCK_N)


@functools.partial(jax.jit, static_argnames=("impl", "block"))
def _gpr(table_keys, offsets, count, queries, impl, block):
    if impl == "ref":
        return _ref.group_probe(table_keys, offsets, count, queries)
    with _kernel_mode(table_keys, queries):
        return _hp.group_probe(table_keys, offsets.astype(jnp.int32),
                               count.astype(jnp.int32), queries, block=block,
                               interpret=(impl == "interpret"))


def group_probe(table_keys, offsets, count, queries,
                impl: Optional[Impl] = None, block: Optional[int] = None):
    """(pos, found, sizes) per query against a groupbuilder's sorted key
    column + CSR offsets — membership and the m:n expansion's
    match-count pass in one launch; ``sizes`` is 0 where not found."""
    return _gpr(table_keys, offsets, jnp.asarray(count), queries,
                impl=_resolve(impl), block=block or _hp.BLOCK_N)


# -- fused adamw ----------------------------------------------------------------


@functools.partial(jax.jit,
                   static_argnames=("b1", "b2", "eps", "wd", "impl", "block"))
def _adamw(p, g, m, v, lr, step, b1, b2, eps, wd, impl, block):
    kw = dict(b1=b1, b2=b2, eps=eps, wd=wd)
    if impl == "ref":
        return _ref.adamw_update(p, g, m, v, lr, step, **kw)
    with _kernel_mode(p, g, m, v):
        return _aw.adamw_update(p, g, m, v, lr, step, block=block,
                                interpret=(impl == "interpret"), **kw)


def adamw_update(p, g, m, v, lr, step, b1=0.9, b2=0.999, eps=1e-8, wd=0.01,
                 impl: Optional[Impl] = None, block: Optional[int] = None):
    return _adamw(p, g, m, v, lr, step, b1=b1, b2=b2, eps=eps, wd=wd,
                  impl=_resolve(impl), block=block or _aw.BLOCK)


# -- tiled matmul -----------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("impl", "bm", "bn", "bk"))
def _mm(a, b, impl, bm, bn, bk):
    if impl == "ref":
        return _ref.tiled_matmul(a, b)
    with _kernel_mode(a, b):
        return _tm.tiled_matmul(a, b, bm=bm, bn=bn, bk=bk,
                                interpret=(impl == "interpret"))


def matmul(a, b, impl: Optional[Impl] = None, bm: Optional[int] = None,
           bn: Optional[int] = None, bk: Optional[int] = None):
    return _mm(a, b, impl=_resolve(impl), bm=bm or 256, bn=bn or 256,
               bk=bk or 512)


# -- fused elementwise map chain --------------------------------------------------


def map_elementwise(fn, arrays, impl: Optional[Impl] = None,
                    block: Optional[int] = None):
    """Apply a staged elementwise body to 1-D columns in one fused pass.

    ``fn`` is a jnp-traceable callable (built by the kernel planner from
    IR), so there is no outer jit here — the caller is always inside the
    program's jit and the kernel inlines into its trace.
    """
    impl = _resolve(impl)
    if impl == "ref":
        return _ref.map_elementwise(fn, arrays)
    with _kernel_mode(arrays):
        return _mc.map_elementwise(fn, arrays, block=block or _mc.BLOCK,
                                   interpret=(impl == "interpret"))


# -- attention --------------------------------------------------------------------


@functools.partial(
    jax.jit,
    static_argnames=("causal", "group", "scale", "impl", "chunk", "unroll"),
)
def _attn(q, k, v, causal, group, scale, chunk, unroll, impl):
    if impl == "ref":
        return _ref.chunked_attention(q, k, v, causal=causal, group=group,
                                      scale=scale, chunk=chunk,
                                      unroll=unroll)
    with _kernel_mode(q, k, v):
        return _fa.flash_attention(q, k, v, causal=causal, group=group,
                                   scale=scale,
                                   interpret=(impl == "interpret"))


def attention(q, k, v, causal: bool = True, group: int = 1, scale=None,
              chunk: int = 1024, unroll: bool = False,
              impl: Optional[Impl] = None):
    return _attn(q, k, v, causal=causal, group=group, scale=scale,
                 chunk=chunk, unroll=unroll, impl=_resolve(impl))
