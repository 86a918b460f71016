"""TPC-H Q6, the forecasting revenue change query (clause 2.4.6).

    SELECT sum(l_extendedprice * l_discount) AS revenue FROM lineitem
    WHERE l_shipdate >= DATE AND l_shipdate < DATE + 1 year
      AND l_discount BETWEEN DISCOUNT - 0.01 AND DISCOUNT + 0.01
      AND l_quantity < QUANTITY

A streaming filter and sum over four lineitem columns.
"""
from __future__ import annotations

import numpy as np

from bench import compare as cmp
from bench import tpch_gen

READS = {"lineitem": ["l_shipdate", "l_discount", "l_quantity",
                      "l_extendedprice"]}


def _bounds(params):
    lo = tpch_gen.day(params["date"])
    y = int(params["date"][:4]) + 1
    hi = tpch_gen.day(f"{y}{params['date'][4:]}")
    d = params["discount"]
    return (np.int32(lo), np.int32(hi), np.float32(round(d - 0.01, 2)),
            np.float32(round(d + 0.01, 2)), np.float32(params["quantity"]))


def reads(params):
    return READS


def tables(data, params):
    from repro.frames import weldrel

    return {"lineitem": weldrel.Table(
        {c: data["lineitem"][c] for c in READS["lineitem"]})}


def build(tables, params):
    from repro.frames import weldrel

    lo, hi, d_lo, d_hi, qty = _bounds(params)
    li = tables["lineitem"]
    pred = ((li.col("l_shipdate") >= lo) & (li.col("l_shipdate") < hi)
            & (li.col("l_discount") >= d_lo)
            & (li.col("l_discount") <= d_hi)
            & (li.col("l_quantity") < qty))
    return weldrel.Query(li).filter(pred).stage().agg(
        {"revenue": (li.col("l_extendedprice") * li.col("l_discount"),
                     "+")})


def _revenue(li, params, cast):
    """Q6 with every measure, bound and product passed through ``cast``
    and the sum taken in f64."""
    lo, hi, d_lo, d_hi, qty = _bounds(params)
    disc, quant = cast(li["l_discount"]), cast(li["l_quantity"])
    m = ((li["l_shipdate"] >= lo) & (li["l_shipdate"] < hi)
         & (disc >= cast(d_lo)) & (disc <= cast(d_hi))
         & (quant < cast(qty)))
    rev = cast(cast(li["l_extendedprice"][m]) * disc[m])
    return {"revenue": float(rev.astype(np.float64).sum())}


def reference(data, params):
    """The answer in f64 arithmetic over the f32 columns."""
    return _revenue(data["lineitem"], params,
                    lambda a: np.asarray(a, np.float64))


def control(data, params):
    """The reference with every measure and product rounded to bfloat16."""
    return _revenue(data["lineitem"], params, cmp.to_bf16)


def compare(got, want):
    return cmp.scalars(got, want)


def essential_bytes(data, params, want):
    """Each column read once, the one sum written once."""
    li = data["lineitem"]
    return sum(li[c].nbytes for c in READS["lineitem"]) + 4
