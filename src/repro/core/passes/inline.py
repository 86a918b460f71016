"""Let-inlining: exposes producer→consumer loop chains to the fusion pass.

A binding `let n = v; body` is inlined when
  * `v` is trivial (Ident/Literal), or
  * `n` is used exactly once in `body` and that use is not under a Lambda
    (inlining into a loop body would re-evaluate `v` every iteration), or
  * `v` is an elementwise map (scalar arithmetic on each element) and
    every use of `n` is an input of one consumer loop, directly or through
    other elementwise maps, none under a Lambda: each use then fuses its
    own copy into that loop and recomputes the element there, instead of
    the vector being written once and read back (one library expression
    used by two others, as `a * b` in `a * b` and `(a * b) * c` when both
    are aggregated in one pass).
"""
from __future__ import annotations

from typing import Dict

from .. import ir
from .. import wtypes as wt


def _count_uses(e: ir.Expr, name: str, in_lambda: bool = False):
    """Returns (total_uses, uses_under_lambda)."""
    total = lam = 0
    stack = [(e, in_lambda)]
    while stack:
        x, under = stack.pop()
        if isinstance(x, ir.Ident):
            if x.name == name:
                total += 1
                lam += 1 if under else 0
            continue
        if isinstance(x, ir.Let) and x.name == name:
            stack.append((x.value, under))
            continue  # shadowed in body
        if isinstance(x, ir.Lambda):
            if any(p.name == name for p in x.params):
                continue
            stack.append((x.body, True))
            continue
        for c in x.children():
            stack.append((c, under))
    return total, lam


def _is_loop_result(e: ir.Expr) -> bool:
    return isinstance(e, ir.Result) and isinstance(e.builder, ir.For)


#: node kinds of an elementwise map's merged value
_SCALAR_NODES = (ir.Literal, ir.Ident, ir.BinOp, ir.UnaryOp, ir.Cast,
                 ir.Select, ir.MakeStruct, ir.GetField)


def _is_elementwise_map(e: ir.Expr) -> bool:
    """`result(for(iters, vecbuilder, |b, i, x| merge(b, f(x))))` with `f`
    scalar arithmetic on `x` and literals, over plain iters of bound
    names or of such maps: cheap to recompute where it is consumed."""
    if not _is_loop_result(e):
        return False
    loop = e.builder
    if not (isinstance(loop.builder, ir.NewBuilder)
            and isinstance(loop.builder.ty, wt.VecBuilder)
            and all(it.is_plain and (isinstance(it.data, ir.Ident)
                                     or _is_elementwise_map(it.data))
                    for it in loop.iters)):
        return False
    b, _, x = loop.func.params
    body = loop.func.body
    if not (isinstance(body, ir.Merge) and isinstance(body.builder, ir.Ident)
            and body.builder.name == b.name):
        return False

    def scalar(v: ir.Expr) -> bool:
        if isinstance(v, ir.Ident):
            return v.name == x.name
        return isinstance(v, _SCALAR_NODES) and all(
            scalar(c) for c in v.children())

    return scalar(body.value)


def _uses_through_maps(loop: ir.For, name: str) -> int:
    """Uses of `name` that reach `loop` as its input, directly or through
    elementwise maps that are its inputs."""
    n = 0
    for it in loop.iters:
        if isinstance(it.data, ir.Ident):
            n += it.data.name == name
        elif _is_elementwise_map(it.data):
            n += _uses_through_maps(it.data.builder, name)
    return n


def _one_consumer_loop(body: ir.Expr, name: str, total: int) -> bool:
    """True if one For of `body` takes all `total` uses of `name` as its
    input, directly or through elementwise maps: copies of `name`'s map
    then fuse into that one loop and add no pass over the data."""
    stack = [body]
    while stack:
        x = stack.pop()
        if isinstance(x, ir.For) and _uses_through_maps(x, name) == total:
            return True
        stack.extend(x.children())
    return False


def _sole_use_is_iter_data(body: ir.Expr, name: str) -> bool:
    """True if the only use of `name` is as the data of a For's Iter —
    the position vertical fusion consumes."""
    hits = []

    def rec(x: ir.Expr):
        if isinstance(x, ir.For):
            for it in x.iters:
                if isinstance(it.data, ir.Ident) and it.data.name == name:
                    hits.append("iter")
                else:
                    rec(it)
            rec(x.builder)
            rec(x.func)
            return
        if isinstance(x, ir.Ident) and x.name == name:
            hits.append("other")
            return
        if isinstance(x, ir.Let) and x.name == name:
            rec(x.value)
            return
        if isinstance(x, ir.Lambda) and any(p.name == name for p in x.params):
            return
        for c in x.children():
            rec(c)

    rec(body)
    return hits == ["iter"]


def _substitute_copies(e: ir.Expr, name: str, value: ir.Expr) -> ir.Expr:
    """`e` with each use of `name` replaced by its own copy of `value`,
    binders renamed (they stay globally unique)."""

    def rec(x: ir.Expr) -> ir.Expr:
        if isinstance(x, ir.Ident):
            return ir.rename_binders(value) if x.name == name else x
        return x.map_children(rec)

    return rec(e)


def inline_lets(e: ir.Expr, stats: Dict[str, int]) -> ir.Expr:
    def rec(x: ir.Expr) -> ir.Expr:
        x = x.map_children(rec)
        if isinstance(x, ir.Let):
            trivial = isinstance(x.value, (ir.Ident, ir.Literal))
            total, under_lam = _count_uses(x.body, x.name)
            if total == 0 and not trivial:
                # dead binding (value is pure in Weld IR) — drop it
                stats["inline.dead"] = stats.get("inline.dead", 0) + 1
                return x.body
            inlinable = trivial or (total == 1 and under_lam == 0)
            if inlinable and _is_loop_result(x.value) and not trivial:
                # keep loops at let-level (horizontal fusion matches the
                # chain) unless the single use is a consumer loop's input,
                # where inlining enables vertical fusion.
                inlinable = _sole_use_is_iter_data(x.body, x.name)
            if inlinable:
                stats["inline.lets"] = stats.get("inline.lets", 0) + 1
                return ir.substitute(x.body, {x.name: x.value})
            if (total > 1 and under_lam == 0
                    and _is_elementwise_map(x.value)
                    and _one_consumer_loop(x.body, x.name, total)):
                stats["inline.recomputed"] = (
                    stats.get("inline.recomputed", 0) + 1)
                return _substitute_copies(x.body, x.name, x.value)
        return x

    return rec(e)
