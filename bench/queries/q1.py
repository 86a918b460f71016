"""TPC-H Q1, the pricing summary report query (clause 2.4.1), over
lineitem stored with exact decimals.

    SELECT l_returnflag, l_linestatus,
           sum(l_quantity), sum(l_extendedprice),
           sum(l_extendedprice * (1 - l_discount)),
           sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)),
           avg(l_quantity), avg(l_extendedprice), avg(l_discount), count(*)
    FROM lineitem WHERE l_shipdate <= DATE '1998-12-01' - DELTA days
    GROUP BY l_returnflag, l_linestatus
    ORDER BY l_returnflag, l_linestatus

The ``DECIMAL(15,2)`` columns are stored as a columnar engine stores
them, scaled int32 in hundredths, and the ``CHAR(1)`` flags as
dictionary codes.  Every sum is then an exact integer: quantity and
price in hundredths, the discounted price in 1e-4 and the charge in
1e-6 units, each summed in int64; an average is its sum over the count,
``sum / count`` (Python's correctly rounded int division).  So the answer
has one right value, and the check is equality.
"""
from __future__ import annotations

import numpy as np

from bench import tpch_gen

DECIMALS = ("l_quantity", "l_extendedprice", "l_discount", "l_tax")
READS = {"lineitem": list(DECIMALS) + ["l_returnflag", "l_linestatus",
                                       "l_shipdate"]}
#: the flags' dictionaries, in the generator's code order (A/N/R, F/O)
DICTIONARIES = {"l_returnflag": (b"A", b"N", b"R"),
                "l_linestatus": (b"F", b"O")}


def reads(params):
    return READS


def hundredths(col: np.ndarray) -> np.ndarray:
    """An f32 column of hundredths as exact int32 hundredths.  Exact: the
    generator's quantities and discounts are exact, and a price below
    104,950 is within half an f32 ulp (< 0.0079 / 2) of its cents."""
    out = np.rint(col.astype(np.float64) * 100).astype(np.int32)
    out.flags.writeable = False
    return out


def columns(data) -> dict:
    li = data["lineitem"]
    return {c: hundredths(li[c]) if c in DECIMALS else li[c]
            for c in READS["lineitem"]}


def tables(data, params):
    from repro.frames import weldrel

    return {"lineitem": weldrel.Table(columns(data),
                                      dictionaries=DICTIONARIES)}


def _cut(params) -> int:
    return tpch_gen.day("1998-12-01") - int(params["delta_days"])


def build(tables, params):
    from repro.frames import weldrel

    li = tables["lineitem"]

    def dec(name):
        return li.col(name).astype(np.int64)

    qty, price, disc = (dec(c) for c in ("l_quantity", "l_extendedprice",
                                         "l_discount"))
    disc_price = price * (100 - disc)
    charge = disc_price * (100 + dec("l_tax"))
    cut = np.int32(_cut(params))
    q = weldrel.Query(li).filter(li.col("l_shipdate") <= cut)
    return q.stage().group_agg(
        [li.col("l_returnflag"), li.col("l_linestatus")],
        {"sum_qty": (qty, "+"), "sum_base_price": (price, "+"),
         "sum_disc_price": (disc_price, "+"), "sum_charge": (charge, "+"),
         "avg_qty": (qty, "avg"), "avg_price": (price, "avg"),
         "avg_disc": (disc, "avg")})


def _q1(data, params, acc) -> dict:
    """Q1 over the scaled columns, each sum taken by ``acc``."""
    li = columns(data)
    m = li["l_shipdate"] <= _cut(params)
    rf, ls = li["l_returnflag"][m], li["l_linestatus"][m]
    qty, price, disc, tax = (li[c][m].astype(np.int64) for c in DECIMALS)
    disc_price = price * (100 - disc)
    charge = disc_price * (100 + tax)
    out = {}
    for a, flag in enumerate(DICTIONARIES["l_returnflag"]):
        for b, status in enumerate(DICTIONARIES["l_linestatus"]):
            g = (rf == a) & (ls == b)
            n = int(g.sum())
            if n:
                s_qty, s_price, s_dp, s_charge, s_disc = (
                    acc(x[g]) for x in (qty, price, disc_price, charge,
                                        disc))
                out[(flag, status)] = (s_qty, s_price, s_dp, s_charge,
                                       s_qty / n, s_price / n, s_disc / n, n)
    return out


def reference(data, params):
    """Every sum in int64, as NumPy adds it."""
    return _q1(data, params, lambda x: int(x.sum(dtype=np.int64)))


def control(data, params):
    """The reference with 32-bit accumulators, the precision below the
    configuration's int64 sums (they wrap)."""
    return _q1(data, params,
               lambda x: int(x.astype(np.int32).sum(dtype=np.int32)))


def compare(got, want):
    """Every group key and every value must be equal, averages included
    (both are the same division of the same integers)."""
    bad = len(set(got) ^ set(want))
    for k in set(got) & set(want):
        g, w = got[k], want[k]
        bad += abs(len(g) - len(w)) + sum(a != b for a, b in zip(g, w))
    return {"mismatches": bad}


def essential_bytes(data, params, want):
    """The seven columns read once (4 bytes a value), the answer's 64-bit
    values written once."""
    li = data["lineitem"]
    return (sum(li[c].size * 4 for c in READS["lineitem"])
            + sum(8 * len(v) for v in want.values()))
