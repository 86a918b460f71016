"""Seeded TPC-H tables, stored as a deployment stores them.

The domains follow the TPC-H specification, clause 4.2 (dbgen's
formulas for the retail price and for the part -> supplier mapping),
with int32 keys and dates and f32 measures.  Dates are day numbers
since 1992-01-01.  The draws run on the default device in one jitted
call and come back to the host as NumPy columns: the same seed gives
the same tables.

Only the columns a configuration lists are returned; the generator
draws every base column of a table so that a column's values do not
depend on which other columns were asked for.
"""
from __future__ import annotations

import datetime
import functools

import jax
import jax.numpy as jnp
import numpy as np

#: TPC-H rows per scale factor.
ROWS_PER_SF = {"lineitem": 6_001_215, "supplier": 10_000,
               "partsupp": 800_000}
PART_PER_SF = 200_000
#: day numbers (since 1992-01-01) that fix the column domains
ORDER_DAYS = 2_406            # orders run 1992-01-01 .. 1998-08-02
CURRENT_DAY = 1_263           # 1995-06-17: returnflag / linestatus cut
EPOCH = datetime.date(1992, 1, 1)
#: 0.00 .. 0.10 as the f32 nearest each (a device's division may miss by
#: an ulp, and Q6's BETWEEN bounds must meet the values exactly)
HUNDREDTHS = np.array([k / 100 for k in range(11)], np.float32)

COLUMNS = {
    "lineitem": ("l_suppkey", "l_quantity", "l_extendedprice",
                 "l_discount", "l_tax", "l_returnflag", "l_linestatus",
                 "l_shipdate"),
    "supplier": ("s_suppkey", "s_nationkey", "s_acctbal"),
    "partsupp": ("ps_suppkey", "ps_partkey", "ps_availqty",
                 "ps_supplycost"),
}


def day(iso: str) -> int:
    """Day number of an ISO date (``"1994-01-01"`` -> 731)."""
    return (datetime.date.fromisoformat(iso) - EPOCH).days


def rows(table: str, sf: float) -> int:
    n = int(round(ROWS_PER_SF[table] * sf))
    return max(n, 4) if table == "supplier" else max(n, 1)


def _supp_of(partkey, i, n_s):
    # dbgen: the i-th of a part's four suppliers
    return (partkey + i * (n_s // 4 + (partkey - 1) // n_s)) % n_s + 1


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _draw(key, n_l, n_s, n_p, wanted):
    i32, f32 = jnp.int32, jnp.float32
    k = jax.random.split(key, 16)

    def ints(j, n, lo, hi):
        return jax.random.randint(k[j], (n,), lo, hi, dtype=i32)

    partkey = ints(0, n_l, 1, n_p + 1)
    qty = ints(1, n_l, 1, 51)
    retail_cents = (90_000 + (partkey // 10) % 20_001
                    + 100 * (partkey % 1_000))
    orderdate = ints(2, n_l, 0, ORDER_DAYS)
    ship = orderdate + ints(3, n_l, 1, 122)
    receipt = ship + ints(4, n_l, 1, 31)
    flag_ar = ints(5, n_l, 0, 2) * 2                 # A=0 or R=2
    lineitem = {
        "l_suppkey": _supp_of(partkey, ints(6, n_l, 0, 4), n_s),
        "l_quantity": qty.astype(f32),
        # qty * cents < 2**24, so the product is exact before the division
        "l_extendedprice": (qty * retail_cents).astype(f32) / f32(100),
        "l_discount": jnp.asarray(HUNDREDTHS)[ints(7, n_l, 0, 11)],
        "l_tax": jnp.asarray(HUNDREDTHS)[ints(8, n_l, 0, 9)],
        "l_returnflag": jnp.where(receipt <= CURRENT_DAY, flag_ar,
                                  1).astype(i32),    # N=1
        "l_linestatus": (ship > CURRENT_DAY).astype(i32),  # F=0, O=1
        "l_shipdate": ship.astype(i32),
    }
    supplier = {
        "s_suppkey": jnp.arange(1, n_s + 1, dtype=i32),
        "s_nationkey": ints(9, n_s, 0, 25),
        "s_acctbal": ints(10, n_s, -99_999, 1_000_000).astype(f32)
        / f32(100),
    }
    ps_part = jnp.repeat(jnp.arange(1, n_p + 1, dtype=i32), 4)
    partsupp = {
        "ps_suppkey": _supp_of(ps_part,
                               jnp.tile(jnp.arange(4, dtype=i32), n_p), n_s),
        "ps_partkey": ps_part,
        "ps_availqty": ints(11, 4 * n_p, 1, 10_000),
        "ps_supplycost": ints(12, 4 * n_p, 100, 100_001).astype(f32)
        / f32(100),
    }
    drawn = {"lineitem": lineitem, "supplier": supplier,
             "partsupp": partsupp}
    return {t: {c: drawn[t][c] for c in cols} for t, cols in wanted}


def make_tables(sf: float, seed: int, columns: dict) -> dict:
    """``{table: {column: np.ndarray}}`` for the tables and columns in
    ``columns`` (``{table: [column, ...]}``), drawn from ``seed`` (any
    whole number below 2**64)."""
    for t, cols in columns.items():
        unknown = set(cols) - set(COLUMNS.get(t, ()))
        if unknown:
            raise KeyError(f"the generator has no column(s) "
                           f"{sorted(unknown)} of table {t!r}")
    seed = int(seed)
    key = jax.random.fold_in(jax.random.key(np.uint32(seed & 0xFFFFFFFF)),
                             np.uint32((seed >> 32) & 0xFFFFFFFF))
    n_p = max(int(round(PART_PER_SF * sf)), 1)
    wanted = tuple((t, tuple(cols)) for t, cols in columns.items())
    host = jax.device_get(_draw(key, rows("lineitem", sf),
                                rows("supplier", sf), n_p, wanted))
    return {t: {c: np.asarray(v) for c, v in cols.items()}
            for t, cols in host.items()}
