"""Hash-join correctness and routing: weldrel.Query.join against a NumPy
oracle on the eager, lazy-generic, and kernelized paths; kernel-level
ref/interpret parity for the open-addressing build and the one-hot
probe; planner routing decisions (probed dicts take the hash route, the
dense group-by route is untouched, the cost gate rejects tiny inputs)."""
import re

import numpy as np
import pytest

from repro.frames import weldrel

rng = np.random.RandomState(13)


def np_join(lcols, rcols, on, m=None):
    """m:1 inner-join oracle; right keys must be unique."""
    lk, rk = lcols[on], rcols[on]
    mask = np.ones(lk.shape[0], bool) if m is None else m
    order = np.argsort(rk, kind="stable")
    rks = rk[order]
    if rks.size:
        pos = np.clip(np.searchsorted(rks, lk), 0, rks.size - 1)
        found = rks[pos] == lk
    else:
        found = np.zeros(lk.shape[0], bool)
    sel = mask & found
    out = {c: v[sel] for c, v in lcols.items()}
    if rks.size:
        gidx = order[pos[sel]]
        for c, v in rcols.items():
            if c != on:
                out[c] = v[gidx]
    else:
        for c, v in rcols.items():
            if c != on:
                out[c] = v[:0]
    return out


def _got(table):
    return {c: np.asarray(weldrel._host(table.cols[c])) for c in table.cols}


def _check(table, want):
    got = _got(table)
    assert set(got) == set(want)
    for c in want:
        np.testing.assert_allclose(got[c], want[c], rtol=1e-12)


def _data(n=1500, k=64, key_lo=0, key_hi=100, scale=1):
    lcols = {"key": (rng.randint(key_lo, key_hi, n) * scale).astype(np.int64),
             "lv": rng.rand(n)}
    rcols = {"key": (np.arange(k) * scale).astype(np.int64),
             "rv": rng.rand(k),
             "rw": rng.randint(0, 9, k).astype(np.int64)}
    return lcols, rcols


# ---------------------------------------------------------------------------
# oracle parity on all three execution paths
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["eager", "off", "always", "auto"])
def test_join_matches_numpy_oracle(mode):
    lcols, rcols = _data()
    want = np_join(lcols, rcols, "key")
    if mode == "eager":
        t = weldrel.Table(lcols, eager=True)
        r = weldrel.Table(rcols, eager=True)
        out = weldrel.Query(t).join(r, on="key")
    else:
        t = weldrel.Table(lcols, eager=False)
        r = weldrel.Table(rcols, eager=False)
        out = weldrel.Query(t).join(r, on="key", kernelize=mode)
    _check(out, want)


def test_join_kernelized_routes_and_matches():
    lcols, rcols = _data()
    t = weldrel.Table(lcols, eager=False)
    r = weldrel.Table(rcols, eager=False)
    st: dict = {}
    out = weldrel.Query(t).join(r, on="key", kernelize="always",
                                collect_stats=st)
    assert st["kernelize.dict_hash_build"] == 1
    # 4 output columns (key, lv, rv, rw) share ONE fused probe launch
    assert st["kernelize.hash_probe"] == 1
    assert st["kernelplan"]["routed"]["hash_probe"] == 1
    _check(out, np_join(lcols, rcols, "key"))


def _hlo_ops(txt, op):
    """``(result shape, operand shapes)`` of every ``op`` instruction of
    an HLO module's text."""
    shape, found = {}, []
    line_re = re.compile(r"\s*(?:ROOT )?([\w.\-]+) = (\([^)]*\)|\S+) "
                         r"(\w[\w\-]*)\(([^)]*)\)")
    for line in txt.splitlines():
        m = line_re.match(line)
        if m:
            shape[m.group(1)] = m.group(2)
            if m.group(3) == op:
                found.append((m.group(2), m.group(4)))
    return [(res, [shape.get(o.strip()) for o in ops.split(",")])
            for res, ops in found]


def test_join_probe_front_packs_with_one_carried_sort():
    """The m:1 inner join's 4 output columns (2 probe, 2 build) leave the
    probe through ONE sort over the probe's rows that carries them, not
    an argsort and a gather of each column through its order: no gather
    reads an operand of the probe's length, and the only gathers to it
    are the 2 build columns'."""
    lcols, rcols = _data()
    n, k = lcols["key"].size, rcols["key"].size
    cq = weldrel.Query(weldrel.Table(lcols)).stage().join(
        weldrel.Table(rcols), on="key", kernelize="always",
        kernel_impl="interpret").compile()
    h = cq.handle
    txt = h._jitted.lower(*h._low.arrays).compiler_ir("hlo").as_hlo_text()
    probe = [ops for _, ops in _hlo_ops(txt, "sort")
             if ops[0].startswith(f"pred[{n}]")]
    assert len(probe) == 1 and len(probe[0]) == 1 + 4
    assert all(o.split("{")[0].endswith(f"[{n}]") for o in probe[0])
    gathers = _hlo_ops(txt, "gather")
    assert not [ops for _, ops in gathers if f"[{n}]" in ops[0]]
    into = sorted(ops[0].split("{")[0] for res, ops in gathers
                  if res.split("{")[0].endswith(f"[{n}]"))
    assert into == [f"f64[{k}]", f"s64[{k}]"]


def test_join_with_filter_predicate():
    lcols, rcols = _data()
    for eager in (False, True):
        t = weldrel.Table(lcols, eager=eager)
        r = weldrel.Table(rcols, eager=eager)
        q = weldrel.Query(t).filter(t.col("lv") > 0.5)
        kw = {} if eager else {"kernelize": "always"}
        out = q.join(r, on="key", **kw)
        _check(out, np_join(lcols, rcols, "key", m=lcols["lv"] > 0.5))


def test_join_sparse_keys_kernelized():
    """Keys far outside any dense [0, capacity) range: the dense group-by
    route would poison these — the hash route must handle them."""
    lcols, rcols = _data(scale=1_000_003)
    lcols["key"] -= 5  # include negative-ish offsets of the lattice
    rcols["key"] -= 5
    t = weldrel.Table(lcols, eager=False)
    r = weldrel.Table(rcols, eager=False)
    st: dict = {}
    out = weldrel.Query(t).join(r, on="key", kernelize="always",
                                collect_stats=st)
    assert st["kernelize.dict_hash_build"] == 1
    _check(out, np_join(lcols, rcols, "key"))


def test_join_duplicate_probe_keys_and_misses():
    lcols = {"key": np.array([3, 3, 3, 99, 5, 3], np.int64),
             "lv": np.arange(6.0)}
    rcols = {"key": np.array([5, 3], np.int64), "rv": np.array([0.5, 0.25])}
    want = np_join(lcols, rcols, "key")
    for mode in ("eager", "off", "always"):
        if mode == "eager":
            out = weldrel.Query(weldrel.Table(lcols, eager=True)).join(
                weldrel.Table(rcols, eager=True), on="key")
        else:
            out = weldrel.Query(weldrel.Table(lcols, eager=False)).join(
                weldrel.Table(rcols, eager=False), on="key", kernelize=mode)
        _check(out, want)


@pytest.mark.parametrize("which", ["left", "right", "both"])
def test_join_empty_sides(which):
    lcols, rcols = _data(n=200, k=16)
    if which in ("left", "both"):
        lcols = {c: v[:0] for c, v in lcols.items()}
    if which in ("right", "both"):
        rcols = {c: v[:0] for c, v in rcols.items()}
    want = np_join(lcols, rcols, "key")
    for mode in ("eager", "off", "always"):
        if mode == "eager":
            out = weldrel.Query(weldrel.Table(lcols, eager=True)).join(
                weldrel.Table(rcols, eager=True), on="key")
        else:
            out = weldrel.Query(weldrel.Table(lcols, eager=False)).join(
                weldrel.Table(rcols, eager=False), on="key", kernelize=mode)
        got = _got(out)
        assert all(got[c].size == 0 for c in got)
        assert set(got) == set(want)


@pytest.mark.parametrize("eager", [True, False])
def test_join_validate_m1_rejects_duplicate_build_keys(eager):
    """Duplicate build keys are legal by default now (m:n); the pandas
    ``validate="m:1"`` knob restores the old rejection, with a
    row-count diagnostic."""
    lcols = {"key": np.array([1, 7], np.int64)}
    rcols = {"key": np.array([7, 7], np.int64), "rv": np.array([1.0, 2.0])}
    t = weldrel.Table(lcols, eager=eager)
    r = weldrel.Table(rcols, eager=eager)
    with pytest.raises(ValueError,
                       match=r"m:1.*1 duplicate key rows.*1 distinct"):
        weldrel.Query(t).join(r, on="key", validate="m:1")
    with pytest.raises(ValueError, match="validate"):
        weldrel.Query(weldrel.Table(lcols, eager=eager)).join(
            weldrel.Table(rcols, eager=eager), on="key", validate="1:1")
    # default: key 7 fans out to both build rows
    out = weldrel.Query(weldrel.Table(lcols, eager=eager)).join(
        weldrel.Table(rcols, eager=eager), on="key")
    got = _got(out)
    np.testing.assert_array_equal(got["key"], [7, 7])
    np.testing.assert_allclose(got["rv"], [1.0, 2.0])


def test_join_suffix_and_right_on():
    lcols = {"k": np.array([1, 2, 3], np.int64), "v": np.arange(3.0)}
    rcols = {"rk": np.array([2, 3], np.int64), "v": np.array([9.0, 8.0])}
    t = weldrel.Table(lcols, eager=False)
    r = weldrel.Table(rcols, eager=False)
    out = weldrel.Query(t).join(r, on="k", right_on="rk", kernelize="off")
    got = _got(out)
    assert set(got) == {"k", "v", "v_r"}
    np.testing.assert_array_equal(got["k"], [2, 3])
    np.testing.assert_allclose(got["v_r"], [9.0, 8.0])


def test_join_interpret_impl_parity():
    lcols, rcols = _data(n=300, k=16)
    t = weldrel.Table(lcols, eager=False)
    r = weldrel.Table(rcols, eager=False)
    a = weldrel.Query(t).join(r, on="key", kernelize="always",
                              kernel_impl="ref")
    b = weldrel.Query(t).join(r, on="key", kernelize="always",
                              kernel_impl="interpret")
    for c in a.cols:
        np.testing.assert_allclose(_got(a)[c], _got(b)[c], rtol=1e-12)


def test_join_rejects_unsupported_shapes():
    t = weldrel.Table({"k": np.array([1], np.int64)})
    r = weldrel.Table({"k": np.array([1], np.int64)})
    with pytest.raises(NotImplementedError):
        weldrel.Query(t).join(r, on="k", how="outer")
    with pytest.raises(TypeError):
        weldrel.Query(t).join(weldrel.Query(r), on="k")
    with pytest.raises(ValueError, match="at most 2"):
        weldrel.Query(weldrel.Table({
            "a": np.array([1], np.int64), "b": np.array([1], np.int64),
            "c": np.array([1], np.int64)})).join(
            weldrel.Table({"a": np.array([1], np.int64),
                           "b": np.array([1], np.int64),
                           "c": np.array([1], np.int64)}),
            on=["a", "b", "c"])


def test_join_keys_beyond_32_bits_do_not_conflate():
    """Single int key columns pack full-width: keys that agree in the
    low 32 bits (e.g. 1 vs 2^32+1) must not be conflated on any path."""
    lcols = {"key": np.array([1, 2 ** 32 + 1, 5], np.int64),
             "lv": np.arange(3.0)}
    rcols = {"key": np.array([2 ** 32 + 1], np.int64),
             "rv": np.array([7.0])}
    want = np_join(lcols, rcols, "key")
    assert want["key"].tolist() == [2 ** 32 + 1]
    for mode in ("eager", "off", "always"):
        if mode == "eager":
            out = weldrel.Query(weldrel.Table(lcols, eager=True)).join(
                weldrel.Table(rcols, eager=True), on="key")
        else:
            out = weldrel.Query(weldrel.Table(lcols, eager=False)).join(
                weldrel.Table(rcols, eager=False), on="key", kernelize=mode)
        _check(out, want)


@pytest.mark.parametrize("eager", [True, False])
def test_join_undersized_capacity_raises(eager):
    lcols = {"key": np.arange(10, dtype=np.int64)}
    rcols = {"key": np.arange(8, dtype=np.int64), "rv": rng.rand(8)}
    t = weldrel.Table(lcols, eager=eager)
    r = weldrel.Table(rcols, eager=eager)
    with pytest.raises(ValueError, match="capacity"):
        weldrel.Query(t).join(r, on="key", capacity=4)


# ---------------------------------------------------------------------------
# routing decisions
# ---------------------------------------------------------------------------


def test_probe_not_routed_beyond_vmem_capacity():
    """A build side beyond the hash kernels' capacity bound must keep
    BOTH sides on the generic lowering under kernelize='always' — the
    probe's one-hot tile cannot exceed its VMEM budget either."""
    from repro.kernels.hash_table import MAX_CAP

    k = MAX_CAP + 512
    n = 4096
    lcols = {"key": rng.randint(0, k, n).astype(np.int64), "lv": rng.rand(n)}
    rcols = {"key": np.arange(k, dtype=np.int64), "rv": rng.rand(k)}
    t = weldrel.Table(lcols, eager=False)
    r = weldrel.Table(rcols, eager=False)
    st: dict = {}
    out = weldrel.Query(t).join(r, on="key", kernelize="always",
                                collect_stats=st)
    assert st.get("kernelize.dict_hash_build", 0) == 0, st.get("kernelplan")
    assert st.get("kernelize.hash_probe", 0) == 0, st.get("kernelplan")
    _check(out, np_join(lcols, rcols, "key"))


def test_join_auto_routes_large_and_rejects_tiny():
    n, k = 300_000, 20_000
    lcols = {"key": rng.randint(0, 2 * k, n).astype(np.int64),
             "lv": rng.rand(n)}
    rcols = {"key": np.arange(k, dtype=np.int64), "rv": rng.rand(k)}
    t = weldrel.Table(lcols, eager=False)
    r = weldrel.Table(rcols, eager=False)
    st: dict = {}
    out = weldrel.Query(t).join(r, on="key", kernelize="auto",
                                collect_stats=st)
    assert st.get("kernelize.dict_hash_build", 0) == 1, st.get("kernelplan")
    assert st.get("kernelize.hash_probe", 0) >= 1, st.get("kernelplan")
    _check(out, np_join(lcols, rcols, "key"))
    # tiny inputs: padding + launch overhead dominate -> gate keeps jnp
    lcols2, rcols2 = _data(n=100, k=8)
    st2: dict = {}
    out2 = weldrel.Query(weldrel.Table(lcols2, eager=False)).join(
        weldrel.Table(rcols2, eager=False), on="key", kernelize="auto",
        collect_stats=st2)
    assert st2["kernelize.matched"] == 0, st2.get("kernelplan")
    assert st2["kernelplan"]["rejected"].get("hash_probe", 0) >= 1
    _check(out2, np_join(lcols2, rcols2, "key"))


def test_groupby_hash_route_beyond_dense_capacity():
    """Capacities beyond the dense segment tile (4096) used to fall back
    to the generic sort path; the hash route now serves them."""
    from repro.frames import welddf

    n = 50_000
    key = rng.randint(0, 20_000, n).astype(np.int64)
    val = rng.rand(n)
    df = welddf.DataFrame({"k": key, "v": val})
    st: dict = {}
    d1 = df.groupby_sum("k", "v", capacity=32768, kernelize=True,
                        collect_stats=st)
    assert st["kernelize.dict_hash_build"] == 1
    d0 = df.groupby_sum("k", "v", capacity=32768, kernelize=False)
    assert set(d1) == set(d0)
    for kk in d0:
        np.testing.assert_allclose(d1[kk], d0[kk], rtol=1e-10)


def test_dense_groupby_route_unchanged():
    """Probing is what selects the hash build; a plain in-range group-by
    must still take the dense segment route."""
    from repro.frames import welddf

    key = rng.randint(0, 50, 4096).astype(np.int64)
    val = rng.rand(4096)
    df = welddf.DataFrame({"k": key, "v": val})
    st: dict = {}
    df.groupby_sum("k", "v", capacity=64, kernelize=True, collect_stats=st)
    assert st.get("kernelize.dict_group_sum", 0) == 1
    assert st.get("kernelize.dict_hash_build", 0) == 0


def test_hash_build_sparse_keys_decode_correctly():
    """Sparse keys through the hash route: capacity 4097 skips the dense
    route (tile bound 4096) and lands on dict_hash_build; the decoded
    dict must agree with the generic lowering."""
    from repro.core import ir, macros as M
    from repro.core.lazy import Evaluate, NewWeldObject

    keys = NewWeldObject(np.arange(100, dtype=np.int64) * 11, None)
    vals = NewWeldObject(rng.rand(100), None)
    kid = ir.Ident(keys.obj_id, keys.weld_type())
    vid = ir.Ident(vals.obj_id, vals.weld_type())
    d = M.groupby_agg(kid, vid, "+", capacity=4097)
    obj = NewWeldObject([keys, vals], d)
    st: dict = {}
    out = Evaluate(obj, kernelize="always", collect_stats=st)
    assert st.get("kernelize.dict_hash_build", 0) == 1
    assert len(out.value) == 100
    want = Evaluate(obj, kernelize=False).value
    assert set(out.value) == set(want)
    for kk in want:
        np.testing.assert_allclose(out.value[kk], want[kk], rtol=1e-10)


def test_hash_build_overflow_recovers_by_regrowing():
    """An undersized hash build poisons the dict; the recovery runtime
    re-stamps the capacity and retries instead of surfacing the poison.
    With recovery disabled the typed CapacityError reaches the caller."""
    import warnings

    from repro.core import ir, macros as M, recovery
    from repro.core.errors import CapacityError
    from repro.core.lazy import Evaluate, NewWeldObject

    def mk():
        keys = NewWeldObject(np.arange(8000, dtype=np.int64) * 3, None)
        vals = NewWeldObject(rng.rand(8000), None)
        kid = ir.Ident(keys.obj_id, keys.weld_type())
        vid = ir.Ident(vals.obj_id, vals.weld_type())
        d = M.groupby_agg(kid, vid, "+", capacity=4097)  # 8000 > 4097
        return NewWeldObject([keys, vals], d)

    st: dict = {}
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        out = Evaluate(mk(), kernelize="always", collect_stats=st)
    assert st["recovery.attempts"] >= 2
    assert any("regrow" in e["action"] for e in st["recovery.events"])
    assert any("weld recovery" in str(x.message) for x in w)
    assert len(out.value) == 8000
    want = Evaluate(mk(), kernelize=False).value
    assert set(out.value) == set(want)
    with recovery.disabled():
        with pytest.raises(CapacityError):
            Evaluate(mk(), kernelize="always")


# ---------------------------------------------------------------------------
# kernel-level parity: ref oracle vs interpreted Pallas kernels
# ---------------------------------------------------------------------------


def test_hash_to_slot_contract_both_impls():
    from repro.kernels import ops as kops
    from repro.kernels.hash_table import EMPTY, table_size

    keys = np.concatenate([
        rng.randint(-50, 50, 300).astype(np.int64) * 999_983,
        np.array([EMPTY] * 5, np.int64),
    ])
    rng.shuffle(keys)
    C = table_size(128)
    for impl in ("ref", "interpret"):
        slots, table, used = map(np.asarray, kops.hash_to_slot(
            np.asarray(keys), C, impl=impl))
        valid = keys != EMPTY
        assert used == np.unique(keys[valid]).size
        assert (slots[~valid] == C).all()
        assert (table[slots[valid]] == keys[valid]).all()
        # distinct keys -> distinct slots
        uniq = {}
        for kk, s in zip(keys[valid], slots[valid]):
            assert uniq.setdefault(kk, s) == s
        assert len(set(uniq.values())) == len(uniq)


def test_dict_probe_parity_both_impls():
    from repro.kernels import ops as kops

    cap, count = 64, 40
    table = np.sort(rng.choice(10_000, count, replace=False)).astype(np.int64)
    table = np.concatenate([table, np.full(cap - count, 77_777, np.int64)])
    big = np.iinfo(np.int64).max
    neut = np.where(np.arange(cap) < count, table, big)
    queries = rng.randint(0, 10_000, 500).astype(np.int64)
    got = {}
    for impl in ("ref", "interpret"):
        pos, found = map(np.asarray, kops.dict_probe(
            neut, count, queries, impl=impl))
        got[impl] = (pos, found)
        want_found = np.isin(queries, table[:count])
        np.testing.assert_array_equal(found, want_found)
        np.testing.assert_array_equal(
            table[pos[found]], queries[found])
        assert (pos[~found] == 0).all()
    np.testing.assert_array_equal(got["ref"][0], got["interpret"][0])


# ---------------------------------------------------------------------------
# left / anti / multi-key joins: pandas-oracle parity on every path
# (pandas is a dev-only dependency — only the oracle tests skip without
# it, never this module's routing/correctness tests above)
# ---------------------------------------------------------------------------

try:
    import pandas as pd
except ImportError:  # pragma: no cover - dev envs ship pandas
    pd = None

needs_pandas = pytest.mark.skipif(pd is None, reason="pandas not installed")

MODES = ("eager", "off", "auto", "always")


def pd_join(lcols, rcols, on, how, m=None, suffix="_r"):
    """pandas oracle for weldrel's join semantics.  Left-join misses in
    non-float right columns are converted from pandas' NaN-upcast back
    to weldrel's per-dtype sentinel fills (0 / False)."""
    on = [on] if isinstance(on, str) else list(on)
    ldf = pd.DataFrame(lcols)
    if m is not None:
        ldf = ldf[m]
    rdf = pd.DataFrame(rcols)
    if how == "anti":
        mg = ldf.merge(rdf[on], on=on, how="left", indicator=True)
        out = mg[mg["_merge"] == "left_only"]
        return {c: out[c].to_numpy() for c in ldf.columns}
    mg = ldf.merge(rdf, on=on, how=how, suffixes=("", suffix))
    out = {c: mg[c].to_numpy() for c in ldf.columns}
    for c in rdf.columns:
        if c in on:
            continue
        name = c + suffix if c in ldf.columns else c
        v = mg[name].to_numpy()
        want_dt = np.asarray(rcols[c]).dtype
        if how == "left" and not np.issubdtype(want_dt, np.floating):
            miss = np.isnan(v.astype(np.float64))
            v = np.where(miss, np.zeros((), want_dt), v).astype(want_dt)
        out[name] = v
    return out


def _run_join(lcols, rcols, on, how, mode, pred_col=None, pred_thresh=None,
              collect_stats=None):
    eager = mode == "eager"
    t = weldrel.Table(lcols, eager=eager)
    r = weldrel.Table(rcols, eager=eager)
    q = weldrel.Query(t)
    if pred_col is not None:
        q = q.filter(t.col(pred_col) > pred_thresh)
    kw = {} if eager else {"kernelize": mode}
    return q.join(r, on=on, how=how, collect_stats=collect_stats, **kw)


@needs_pandas
@pytest.mark.parametrize("how", ["left", "anti"])
@pytest.mark.parametrize("mode", MODES)
def test_left_anti_join_pandas_parity(how, mode):
    lcols, rcols = _data()
    want = pd_join(lcols, rcols, "key", how)
    _check(_run_join(lcols, rcols, "key", how, mode), want)


@needs_pandas
@pytest.mark.parametrize("how", ["inner", "left", "anti"])
@pytest.mark.parametrize("mode", MODES)
def test_multi_key_join_pandas_parity(how, mode):
    n = 1200
    lcols = {"a": rng.randint(0, 12, n).astype(np.int64),
             "b": rng.randint(0, 7, n).astype(np.int64),
             "lv": rng.rand(n)}
    ga, gb = np.meshgrid(np.arange(10), np.arange(5))
    rcols = {"a": ga.ravel().astype(np.int64),
             "b": gb.ravel().astype(np.int64),
             "rv": rng.rand(50),
             "ri": rng.randint(0, 9, 50).astype(np.int64)}
    want = pd_join(lcols, rcols, ["a", "b"], how)
    _check(_run_join(lcols, rcols, ["a", "b"], how, mode), want)


@needs_pandas
@pytest.mark.parametrize("how", ["inner", "left", "anti"])
@pytest.mark.parametrize("mode", MODES)
def test_filtered_left_anti_multi_key_parity(how, mode):
    lcols = {"a": rng.randint(0, 9, 800).astype(np.int64),
             "b": rng.randint(0, 4, 800).astype(np.int64),
             "lv": rng.rand(800)}
    rcols = {"a": np.repeat(np.arange(7), 3).astype(np.int64),
             "b": np.tile(np.arange(3), 7).astype(np.int64),
             "rv": rng.rand(21)}
    want = pd_join(lcols, rcols, ["a", "b"], how, m=lcols["lv"] > 0.35)
    got = _run_join(lcols, rcols, ["a", "b"], how, mode,
                    pred_col="lv", pred_thresh=0.35)
    _check(got, want)


@pytest.mark.parametrize("how", ["left", "anti"])
@pytest.mark.parametrize("mode", MODES)
def test_all_miss_probe(how, mode):
    """Every probe key misses: left fills every right cell, anti keeps
    every row; dtypes must survive exactly."""
    lcols = {"key": (rng.randint(0, 50, 300) + 1000).astype(np.int64),
             "lv": rng.rand(300)}
    rcols = {"key": np.arange(20, dtype=np.int64), "rv": rng.rand(20),
             "ri": rng.randint(1, 9, 20).astype(np.int64)}
    got = _got(_run_join(lcols, rcols, "key", how, mode))
    np.testing.assert_array_equal(got["key"], lcols["key"])
    if how == "left":
        assert np.isnan(got["rv"]).all() and got["rv"].dtype == np.float64
        assert (got["ri"] == 0).all() and got["ri"].dtype == np.int64
    else:
        assert set(got) == {"key", "lv"}


@pytest.mark.parametrize("mode", MODES)
def test_left_join_fill_dtypes(mode):
    """Miss fills are per-dtype sentinels (NaN / 0 / False), never a
    silent float upcast — int and bool columns keep their dtype."""
    lcols = {"key": np.array([0, 1, 5, 7], np.int64)}
    rcols = {"key": np.array([1, 5], np.int64),
             "f": np.array([0.5, 0.25]),
             "i": np.array([3, 4], np.int64),
             "g": np.array([1.5, 2.5], np.float32)}
    got = _got(_run_join(lcols, rcols, "key", "left", mode))
    assert got["f"].dtype == np.float64 and np.isnan(got["f"][[0, 3]]).all()
    assert got["i"].dtype == np.int64
    np.testing.assert_array_equal(got["i"], [0, 3, 4, 0])
    assert got["g"].dtype == np.float32 and np.isnan(got["g"][[0, 3]]).all()
    np.testing.assert_allclose(got["g"][[1, 2]], [1.5, 2.5])


@needs_pandas
@pytest.mark.parametrize("how", ["left", "anti"])
@pytest.mark.parametrize("which", ["left", "right", "both"])
def test_left_anti_join_empty_sides(how, which):
    lcols, rcols = _data(n=150, k=12)
    if which in ("left", "both"):
        lcols = {c: v[:0] for c, v in lcols.items()}
    if which in ("right", "both"):
        rcols = {c: v[:0] for c, v in rcols.items()}
    want = pd_join(lcols, rcols, "key", how)
    for mode in MODES:
        got = _got(_run_join(lcols, rcols, "key", how, mode))
        assert set(got) == set(want)
        for c in want:
            np.testing.assert_allclose(
                got[c], np.asarray(want[c], got[c].dtype))


@needs_pandas
def test_left_anti_fused_single_probe_routing():
    """An N-output-column left/anti join must launch exactly one build
    and ONE fused probe under kernelize='always'."""
    lcols, rcols = _data()
    for how, ncols in (("left", 4), ("anti", 2)):
        st: dict = {}
        out = _run_join(lcols, rcols, "key", how, "always",
                        collect_stats=st)
        assert len(out.cols) == ncols
        assert st.get("kernelize.hash_probe", 0) == 1, st.get("kernelplan")
        if how == "left":
            assert st.get("kernelize.dict_hash_build", 0) == 1
        _check(out, pd_join(lcols, rcols, "key", how))


@pytest.mark.parametrize("how", ["inner", "left", "anti"])
def test_left_anti_multi_key_interpret_impl_parity(how):
    lcols = {"a": rng.randint(0, 8, 256).astype(np.int64),
             "b": rng.randint(0, 4, 256).astype(np.int64),
             "lv": rng.rand(256)}
    rcols = {"a": np.repeat(np.arange(6), 3).astype(np.int64),
             "b": np.tile(np.arange(3), 6).astype(np.int64),
             "rv": rng.rand(18)}
    outs = {}
    for impl in ("ref", "interpret"):
        t = weldrel.Table(lcols, eager=False)
        r = weldrel.Table(rcols, eager=False)
        outs[impl] = _got(weldrel.Query(t).join(
            r, on=["a", "b"], how=how, kernelize="always",
            kernel_impl=impl))
    for c in outs["ref"]:
        np.testing.assert_allclose(outs["ref"][c], outs["interpret"][c])


# ---------------------------------------------------------------------------
# pinned key semantics: NaN keys raise, name collisions raise,
# packed-space overflow raises — identically on every path
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("side", ["probe", "build"])
def test_nan_join_keys_raise_everywhere(mode, side):
    lk = np.array([1.0, np.nan, 3.0]) if side == "probe" \
        else np.array([1.0, 2.0, 3.0])
    rk = np.array([1.0, np.nan]) if side == "build" \
        else np.array([1.0, 2.0])
    lcols = {"key": lk, "lv": np.arange(3.0)}
    rcols = {"key": rk, "rv": np.arange(float(rk.size))}
    with pytest.raises(ValueError, match="NaN"):
        _run_join(lcols, rcols, "key", "inner", mode)


@pytest.mark.parametrize("eager", [True, False])
def test_join_output_name_collision_raises(eager):
    """Left already has `v` and `v_r`; right's `v` would suffix onto the
    existing `v_r` — silently overwriting before this fix."""
    lcols = {"key": np.array([1, 2], np.int64),
             "v": np.arange(2.0), "v_r": np.arange(2.0)}
    rcols = {"key": np.array([1, 2], np.int64), "v": np.array([9.0, 8.0])}
    t = weldrel.Table(lcols, eager=eager)
    r = weldrel.Table(rcols, eager=eager)
    with pytest.raises(ValueError, match="collision"):
        weldrel.Query(t).join(r, on="key")
    # a different suffix resolves it
    out = weldrel.Query(t).join(r, on="key", suffix="_right")
    assert set(out.cols) == {"key", "v", "v_r", "v_right"}


@pytest.mark.parametrize("mode", MODES)
def test_float_join_keys_compare_at_f32_on_every_path(mode):
    """Float keys live in the packed key space's f32 bitcast on the
    dict paths; the eager compare and the uniqueness check now use the
    SAME packing, so build keys distinct only beyond f32 precision
    raise (the dictmerger would silently sum them) and identical
    payloads match identically everywhere."""
    lcols = {"key": np.array([0.5, 2.25, 7.0]), "lv": np.arange(3.0)}
    rcols = {"key": np.array([2.25, 0.5]), "rv": np.array([10.0, 20.0])}
    got = _got(_run_join(lcols, rcols, "key", "inner", mode))
    np.testing.assert_allclose(got["key"], [0.5, 2.25])
    np.testing.assert_allclose(got["rv"], [20.0, 10.0])
    # f32-colliding f64 build keys: conflated by the packed space, and
    # no longer caught by a uniqueness guard (m:n made duplicates
    # legal) — the explicit conflation check must reject them up front
    bad = {"key": np.array([1.0, 1.0 + 1e-12]), "rv": np.array([1.0, 2.0])}
    with pytest.raises(ValueError, match="conflate"):
        _run_join(lcols, bad, "key", "inner", mode)


@pytest.mark.parametrize("mode", MODES)
def test_mismatched_key_dtypes_raise_everywhere(mode):
    """An int key against a float key would silently bitcast-collide on
    the eager packed compare while the lazy dict raises a type error —
    pinned: every path raises the same ValueError up front."""
    lcols = {"key": np.array([1065353216], np.int64)}  # f32 bits of 1.0
    rcols = {"key": np.array([1.0]), "rv": np.array([99.0])}
    with pytest.raises(ValueError, match="dtype mismatch"):
        _run_join(lcols, rcols, "key", "inner", mode)


@pytest.mark.parametrize("how", ["inner", "left"])
@pytest.mark.parametrize("mode", MODES)
def test_bool_value_column_all_paths(how, mode):
    """Bool build-side value columns ride the dictmerger as i8 and cast
    back at the probe; left-join misses fill with False."""
    lcols = {"key": np.array([0, 1, 5, 2], np.int64)}
    rcols = {"key": np.array([1, 2, 3], np.int64),
             "flag": np.array([True, False, True])}
    got = _got(_run_join(lcols, rcols, "key", how, mode))
    assert got["flag"].dtype == np.bool_
    if how == "inner":
        np.testing.assert_array_equal(got["key"], [1, 2])
        np.testing.assert_array_equal(got["flag"], [True, False])
    else:
        np.testing.assert_array_equal(got["flag"],
                                      [False, True, False, False])


@pytest.mark.parametrize("eager", [True, False])
def test_multi_key_beyond_32_bits_raises(eager):
    lcols = {"a": np.array([2 ** 33, 1], np.int64),
             "b": np.array([0, 1], np.int64)}
    rcols = {"a": np.array([1], np.int64), "b": np.array([1], np.int64),
             "rv": np.array([1.0])}
    t = weldrel.Table(lcols, eager=eager)
    r = weldrel.Table(rcols, eager=eager)
    with pytest.raises(ValueError, match="32 bits"):
        weldrel.Query(t).join(r, on=["a", "b"])
    # INT32_MIN packs onto the hash EMPTY sentinel — reserved, raises
    l2 = {"a": np.array([-(2 ** 31), 1], np.int64),
          "b": np.array([0, 1], np.int64)}
    t2 = weldrel.Table(l2, eager=eager)
    with pytest.raises(ValueError, match="32 bits"):
        weldrel.Query(t2).join(r, on=["a", "b"])


@pytest.mark.parametrize("mode", MODES)
def test_negative_zero_float_keys_match_everywhere(mode):
    """IEEE says -0.0 == 0.0; the packed bitcast disagrees unless the
    packing normalizes — a probe 0.0 must match a build -0.0 on every
    path.  A build side holding both zeros is a GENUINE duplicate
    (IEEE-equal keys), so it now fans out as an m:n group instead of
    raising — and must NOT trip the f32-conflation guard."""
    lcols = {"key": np.array([0.0, 1.0]), "lv": np.arange(2.0)}
    rcols = {"key": np.array([-0.0, 1.0]), "rv": np.array([5.0, 6.0])}
    got = _got(_run_join(lcols, rcols, "key", "inner", mode))
    np.testing.assert_allclose(got["rv"], [5.0, 6.0])
    dup = {"key": np.array([0.0, -0.0]), "rv": np.array([1.0, 2.0])}
    got2 = _got(_run_join(lcols, dup, "key", "inner", mode))
    np.testing.assert_allclose(got2["key"], [0.0, 0.0])
    np.testing.assert_allclose(got2["rv"], [1.0, 2.0])


# ---------------------------------------------------------------------------
# m:n joins (duplicate build-side keys): groupbuilder expansion on every
# path, pandas-oracle parity, exact cross-path ordering, routing
# ---------------------------------------------------------------------------


def _mn_data(n=900, k=24, fanout_lo=1, fanout_hi=5, seed=11):
    r = np.random.RandomState(seed)
    reps = r.randint(fanout_lo, fanout_hi + 1, k)
    rcols = {"key": np.repeat(np.arange(k), reps).astype(np.int64)}
    nr = rcols["key"].size
    rcols["rv"] = r.rand(nr)
    rcols["ri"] = r.randint(0, 9, nr).astype(np.int64)
    lcols = {"key": r.randint(0, 2 * k, n).astype(np.int64),
             "lv": r.rand(n)}
    return lcols, rcols


@needs_pandas
@pytest.mark.parametrize("how", ["inner", "left"])
@pytest.mark.parametrize("mode", MODES)
def test_mn_join_pandas_parity(how, mode):
    lcols, rcols = _mn_data()
    want = pd_join(lcols, rcols, "key", how)
    got = _got(_run_join(lcols, rcols, "key", how, mode))
    assert set(got) == set(want)
    # row-SET parity (pandas orders matches differently); sizes first
    assert got["key"].shape == want["key"].shape
    cols = sorted(want)
    def keyed(d):
        return sorted(zip(*[np.asarray(d[c]).tolist() for c in cols]),
                      key=repr)
    for a, b in zip(keyed(got), keyed(want)):
        np.testing.assert_allclose(
            np.array(a, np.float64), np.array(b, np.float64),
            rtol=1e-12, equal_nan=True)


@pytest.mark.parametrize("how", ["inner", "left"])
def test_mn_join_exact_order_across_paths(how):
    """All three lazy paths must equal the eager oracle EXACTLY —
    probe-row-major, matches within a probe row in build-row order."""
    lcols, rcols = _mn_data(n=400, k=12, seed=3)
    ref = _got(_run_join(lcols, rcols, "key", how, "eager"))
    for mode in ("off", "auto", "always"):
        got = _got(_run_join(lcols, rcols, "key", how, mode))
        for c in ref:
            np.testing.assert_array_equal(got[c], ref[c],
                                          err_msg=f"{how}/{mode}/{c}")


@needs_pandas
@pytest.mark.parametrize("how", ["inner", "left"])
@pytest.mark.parametrize("mode", MODES)
def test_mn_multi_key_filtered_parity(how, mode):
    r = np.random.RandomState(8)
    lcols = {"a": r.randint(0, 6, 500).astype(np.int64),
             "b": r.randint(0, 3, 500).astype(np.int64),
             "lv": r.rand(500)}
    rcols = {"a": np.repeat(np.arange(5), 6).astype(np.int64),
             "b": np.tile(np.arange(3), 10).astype(np.int64),  # dups!
             "rv": r.rand(30)}
    m = lcols["lv"] > 0.4
    want = pd_join(lcols, rcols, ["a", "b"], how, m=m)
    got = _got(_run_join(lcols, rcols, ["a", "b"], how, mode,
                         pred_col="lv", pred_thresh=0.4))
    assert got["a"].shape == want["a"].shape
    cols = sorted(want)
    def keyed(d):
        return sorted(zip(*[np.asarray(d[c]).tolist() for c in cols]),
                      key=repr)
    for a, b in zip(keyed(got), keyed(want)):
        np.testing.assert_allclose(
            np.array(a, np.float64), np.array(b, np.float64),
            rtol=1e-12, equal_nan=True)


@pytest.mark.parametrize("how", ["inner", "left"])
@pytest.mark.parametrize("mode", MODES)
def test_mn_fanout_32_and_empty_probe(how, mode):
    r = np.random.RandomState(9)
    rcols = {"key": np.repeat(np.arange(4), 32).astype(np.int64),
             "rv": r.rand(128)}
    lcols = {"key": r.randint(0, 8, 60).astype(np.int64), "lv": r.rand(60)}
    ref = _got(_run_join(lcols, rcols, "key", how, "eager"))
    got = _got(_run_join(lcols, rcols, "key", how, mode))
    for c in ref:
        np.testing.assert_array_equal(got[c], ref[c])
    sel = np.isin(lcols["key"], rcols["key"])
    want_rows = 32 * int(sel.sum()) + (0 if how == "inner"
                                       else int((~sel).sum()))
    assert got["key"].shape[0] == want_rows
    # empty probe side
    empty = {c: v[:0] for c, v in lcols.items()}
    got0 = _got(_run_join(empty, rcols, "key", how, mode))
    assert all(v.size == 0 for v in got0.values())


@pytest.mark.parametrize("mode", MODES)
def test_mn_all_miss_left_fill_dtypes(mode):
    """m:n build side, every probe key missing: left keeps each row once
    with per-dtype sentinel fills (incl. bool, which the m:n gather
    path carries natively, no i8 encode)."""
    lcols = {"key": np.array([100, 101, 102], np.int64)}
    rcols = {"key": np.array([1, 1, 2], np.int64),
             "f": np.array([0.5, 0.25, 0.125]),
             "i": np.array([3, 4, 5], np.int64),
             "g": np.array([True, False, True])}
    got = _got(_run_join(lcols, rcols, "key", "left", mode))
    np.testing.assert_array_equal(got["key"], lcols["key"])
    assert got["f"].dtype == np.float64 and np.isnan(got["f"]).all()
    assert got["i"].dtype == np.int64 and (got["i"] == 0).all()
    assert got["g"].dtype == np.bool_ and (~got["g"]).all()
    gi = _got(_run_join(lcols, rcols, "key", "inner", mode))
    assert all(v.size == 0 for v in gi.values())


def test_mn_join_routes_one_group_build_and_probe():
    """An m:n join under kernelize='always' must launch exactly ONE
    group_build and ONE group_probe, whatever the output width."""
    lcols, rcols = _mn_data()
    st: dict = {}
    out = _run_join(lcols, rcols, "key", "inner", "always",
                    collect_stats=st)
    assert len(out.cols) == 4
    assert st.get("kernelize.group_build", 0) == 1, st.get("kernelplan")
    assert st.get("kernelize.group_probe", 0) == 1, st.get("kernelplan")
    assert st.get("kernelize.hash_probe", 0) == 0
    # m:1 joins must keep the dictmerger route (no group expansion)
    uniq = {"key": np.arange(24, dtype=np.int64),
            "rv": np.random.RandomState(0).rand(24)}
    st2: dict = {}
    _run_join(lcols, uniq, "key", "inner", "always", collect_stats=st2)
    assert st2.get("kernelize.group_probe", 0) == 0
    assert st2.get("kernelize.hash_probe", 0) == 1


@pytest.mark.parametrize("how", ["inner", "left"])
def test_mn_join_interpret_impl_parity(how):
    lcols, rcols = _mn_data(n=300, k=10, seed=21)
    outs = {}
    for impl in ("ref", "interpret"):
        t = weldrel.Table(lcols, eager=False)
        r = weldrel.Table(rcols, eager=False)
        outs[impl] = _got(weldrel.Query(t).join(
            r, on="key", how=how, kernelize="always", kernel_impl=impl))
    for c in outs["ref"]:
        np.testing.assert_allclose(outs["ref"][c], outs["interpret"][c],
                                   equal_nan=True)


# ---------------------------------------------------------------------------
# kernel-level parity: ref oracle vs interpreted Pallas kernels for the
# group build / probe pair, plus poison/overflow propagation
# ---------------------------------------------------------------------------


def test_group_build_contract_both_impls():
    from repro.kernels import ops as kops
    from repro.kernels.hash_table import EMPTY

    keys = np.concatenate([
        rng.randint(-30, 30, 300).astype(np.int64) * 999_983,
        np.array([EMPTY] * 5, np.int64),
    ])
    rng.shuffle(keys)
    valid = keys != EMPTY
    cap = np.unique(keys[valid]).size
    got = {}
    for impl in ("ref", "interpret"):
        cs, offs, used = map(np.asarray, kops.group_build(
            np.asarray(keys), cap, impl=impl))
        got[impl] = (cs, offs, used)
        assert used == cap
        assert (cs[~valid] == cap).all()
        uk = np.unique(keys[valid])
        for s, kk in enumerate(uk):
            # equal keys share one slot; slots ascend with key order;
            # CSR sizes equal the per-key multiplicities
            assert (cs[keys == kk] == s).all()
            assert offs[s + 1] - offs[s] == (keys == kk).sum()
        assert offs[0] == 0 and offs[cap] == valid.sum()
    for a, b in zip(got["ref"], got["interpret"]):
        np.testing.assert_array_equal(a, b)


def test_group_probe_parity_both_impls():
    from repro.kernels import ops as kops

    cap, count = 48, 32
    table = np.sort(rng.choice(5000, count, replace=False)).astype(np.int64)
    table = np.concatenate([table, np.full(cap - count, 88_888, np.int64)])
    big = np.iinfo(np.int64).max
    neut = np.where(np.arange(cap) < count, table, big)
    sizes = rng.randint(1, 6, cap)
    offsets = np.concatenate([[0], np.cumsum(sizes)]).astype(np.int32)
    queries = rng.randint(0, 5000, 400).astype(np.int64)
    got = {}
    for impl in ("ref", "interpret"):
        pos, found, sz = map(np.asarray, kops.group_probe(
            neut, offsets, count, queries, impl=impl))
        got[impl] = (pos, found, sz)
        want_found = np.isin(queries, table[:count])
        np.testing.assert_array_equal(found, want_found)
        np.testing.assert_array_equal(table[pos[found]], queries[found])
        np.testing.assert_array_equal(sz[found], sizes[pos[found]])
        assert (pos[~found] == 0).all() and (sz[~found] == 0).all()
    for a, b in zip(got["ref"], got["interpret"]):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("impl", ["ref", "interpret"])
def test_group_build_overflow_poisons_and_probe_propagates(impl):
    """More distinct build keys than the builder capacity: the group
    build flags a NEGATIVE count, decode raises, and a probe against
    the poisoned group propagates count=-1 into its output vector."""
    import jax.numpy as jnp

    from repro.core.backend.values import WVec
    from repro.core.kernelplan import registry as kreg

    keys = WVec(jnp.asarray(np.arange(64, dtype=np.int64) * 3))
    params = {"capacity": 16, "n_keys": 1, "key_nps": ("int64",),
              "has_pred": False}
    fns = [lambda i, x: x, lambda i, x: i]
    g = kreg._exec_group_build([keys], params, fns, impl)
    assert int(np.asarray(g.count)) < 0
    with pytest.raises(RuntimeError, match="distinct keys"):
        g.to_numpy()
    probe = WVec(jnp.asarray(np.arange(10, dtype=np.int64)))
    pparams = {"how": "inner", "n_keys": 1, "n_iters": 1,
               "cols": (("expr", 0),), "fills": (None,), "out_cap": 10,
               "has_pred": False}
    pfns = [lambda i, x: x, lambda i, x: x]
    outs = kreg._exec_group_probe([g, probe], pparams, pfns, impl)
    assert int(np.asarray(outs[0].count)) == -1
    with pytest.raises(RuntimeError, match="poisoned"):
        outs[0].to_numpy()


def test_composed_dict_build_parity_ref_vs_interpret():
    """The full build pipeline (hash/sort -> segment -> compaction) must
    produce identical sorted dicts from both slot-assignment impls."""
    lcols = {"key": rng.randint(0, 40, 400).astype(np.int64),
             "lv": rng.rand(400)}
    rcols = {"key": np.arange(40, dtype=np.int64), "rv": rng.rand(40)}
    t = weldrel.Table(lcols, eager=False)
    r = weldrel.Table(rcols, eager=False)
    a = weldrel.Query(t).join(r, on="key", kernelize="always",
                              kernel_impl="ref")
    b = weldrel.Query(t).join(r, on="key", kernelize="always",
                              kernel_impl="interpret")
    for c in a.cols:
        np.testing.assert_allclose(_got(a)[c], _got(b)[c], rtol=1e-12)
