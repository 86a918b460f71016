"""The comparison that decides ``correct``.

Every query module reduces one served answer against its reference to a
few numbers, each held to a limit from the mix file:

* ``max_rel_err``: the widest relative gap of an f32 sum from its f64
  reference;
* ``mismatches``: the count of values that must be equal and are not
  (integer results, group keys, gathered join columns, row counts).
"""
from __future__ import annotations

import numpy as np
from ml_dtypes import bfloat16  # NumPy's bfloat16, shipped with JAX


def to_bf16(a) -> np.ndarray:
    """Round f32 values to bfloat16 (the control's precision) and back to
    f32, so that the arithmetic after it sees only bf16 values."""
    return np.asarray(a, np.float32).astype(bfloat16).astype(np.float32)


def scalars(got: dict, want: dict) -> dict:
    """Numbers for answers that are a dict of scalars or of tuples of
    scalars (an aggregate, a grouped aggregate).  Float references are
    f32 sums held to a relative gap; integer ones must be equal."""
    rel, bad = 0.0, 0
    bad += len(set(got) ^ set(want))
    for k in set(got) & set(want):
        g, w = got[k], want[k]
        g = g if isinstance(g, tuple) else (g,)
        w = w if isinstance(w, tuple) else (w,)
        bad += abs(len(g) - len(w))
        for a, b in zip(g, w):
            if isinstance(b, (float, np.floating)):
                gap = abs(float(a) - float(b))
                rel = max(rel, gap / abs(float(b)) if b else gap)
                if not np.isfinite(float(a)):
                    rel = float("inf")
            elif int(a) != int(b):
                bad += 1
    return {"max_rel_err": rel, "mismatches": bad}


def columns(got: dict, want: dict) -> dict:
    """Numbers for a table answer: every value of every column must equal
    the reference's (gathered columns are copied, not computed)."""
    bad = 0
    for c in set(got) | set(want):
        if c not in got or c not in want:
            bad += len(want.get(c, got.get(c)))
            continue
        g, w = np.asarray(got[c]), np.asarray(want[c])
        if g.shape != w.shape:
            bad += max(g.size, w.size)
        else:
            bad += int(np.count_nonzero(g != w))
    return {"mismatches": bad}


def worst(readings: list) -> dict:
    """The worst reading of each number over many answers."""
    out: dict = {}
    for r in readings:
        for k, v in r.items():
            out[k] = max(out.get(k, v), v)
    return out
