"""An inner equi-join of a probe table against a build table, as
``weldrel`` serves it: every probe column plus every build column but the
key, probe-row-major, matches within a probe row in build-row order.

The mix names the tables, their columns and the keys:

    {"probe": {"table": "lineitem", "columns": [...], "key": "l_suppkey"},
     "build": {"table": "supplier", "columns": [...], "key": "s_suppkey"}}

With unique build keys this is an m:1 join (a hash build and probe);
with duplicate build keys an m:n join (a group build and an expansion).
"""
from __future__ import annotations

import numpy as np

from bench import compare as cmp


def _side(data, params, side):
    p = params[side]
    return {c: data[p["table"]][c] for c in p["columns"]}


def reads(params):
    out: dict = {}
    for side in ("probe", "build"):
        p = params[side]
        out.setdefault(p["table"], []).extend(p["columns"])
    return out


def tables(data, params):
    from repro.frames import weldrel

    return {side: weldrel.Table(_side(data, params, side))
            for side in ("probe", "build")}


def build(tables, params):
    from repro.frames import weldrel

    return weldrel.Query(tables["probe"]).stage().join(
        tables["build"], on=params["probe"]["key"],
        right_on=params["build"]["key"], how="inner")


def _join(probe, build, pkey, bkey):
    order = np.argsort(build[bkey], kind="stable")
    bk = build[bkey][order]
    lo = np.searchsorted(bk, probe[pkey], side="left")
    cnt = np.searchsorted(bk, probe[pkey], side="right") - lo
    rows = np.repeat(np.arange(probe[pkey].size), cnt)
    starts = np.concatenate([[0], np.cumsum(cnt)])[:-1]
    brow = order[lo[rows] + np.arange(rows.size) - starts[rows]]
    out = {c: v[rows] for c, v in probe.items()}
    out.update({c: v[brow] for c, v in build.items() if c != bkey})
    return out


def reference(data, params):
    """The join by sort and binary search, in NumPy."""
    return _join(_side(data, params, "probe"), _side(data, params, "build"),
                 params["probe"]["key"], params["build"]["key"])


def control(data, params):
    """The reference with the f32 columns stored in bfloat16."""
    def bf16(cols):
        return {c: cmp.to_bf16(v) if v.dtype == np.float32 else v
                for c, v in cols.items()}
    return _join(bf16(_side(data, params, "probe")),
                 bf16(_side(data, params, "build")),
                 params["probe"]["key"], params["build"]["key"])


def answer(got):
    """The served ``weldrel.Table`` as ``{column: np.ndarray}``."""
    if isinstance(got, dict):
        return got
    return {c: np.asarray(got.col(c).obj.data) for c in got.cols}


def compare(got, want):
    return cmp.columns(answer(got), want)


def essential_bytes(data, params, want):
    """Each input column read once, each result column written once."""
    ins = [*_side(data, params, "probe").values(),
           *_side(data, params, "build").values()]
    return (sum(v.nbytes for v in ins)
            + sum(np.asarray(v).nbytes for v in want.values()))
