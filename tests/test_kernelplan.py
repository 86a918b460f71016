"""Kernel planner golden tests: fused IR loops route onto the Pallas
kernel library, mismatches fall back to the jnp emitter unchanged, and
kernelized results agree with the generic backend."""
import numpy as np
import pytest

from repro.core import ir, macros as M, wtypes as wt
from repro.core import kernelplan as kp
from repro.core.lazy import Evaluate, NewWeldObject, build_program
from repro.core.passes import optimize

rng = np.random.RandomState(42)
N = 4096


def _ident(o):
    return ir.Ident(o.obj_id, o.weld_type())


def _q6_like_obj(n=N):
    """Fused filter+reduce: sum(price*disc where price < 0.5)."""
    price = NewWeldObject(rng.rand(n), None)
    disc = NewWeldObject(rng.rand(n), None)
    expr = M.filter_reduce(
        M.zip_map([_ident(price), _ident(disc)],
                  lambda p, d: ir.MakeStruct((p, d))),
        lambda x: ir.BinOp("<", ir.GetField(x, 0), ir.Literal(0.5, wt.F64)),
        "+",
        lambda x: ir.BinOp("*", ir.GetField(x, 0), ir.GetField(x, 1)),
    )
    obj = NewWeldObject([price, disc], expr)
    want = (np.asarray(price.data) * np.asarray(disc.data))[
        np.asarray(price.data) < 0.5
    ].sum()
    return obj, want


# ---------------------------------------------------------------------------
# golden: optimized programs are annotated with the expected KernelCall
# ---------------------------------------------------------------------------


def test_planner_annotates_q6_filter_reduce():
    obj, _ = _q6_like_obj()
    prog = build_program(obj)
    shapes = {k: tuple(np.asarray(v[2]).shape) for k, v in prog.inputs.items()}
    opt = optimize(prog.expr, stats={}, input_shapes=shapes)
    stats: dict = {}
    planned = kp.plan_kernels(opt, input_shapes=shapes, stats=stats)
    calls = [n for n in ir.walk(planned) if isinstance(n, ir.KernelCall)]
    assert stats["kernelize.matched"] == 1
    assert stats["kernelize.filter_reduce_sum"] == 1
    assert [c.kernel for c in calls] == ["filter_reduce_sum"]
    assert dict(calls[0].params)["has_pred"] is True


def test_planner_annotates_segment_reduce():
    """PageRank-style vecmerger scatter routes to segment_sum."""
    idxs = NewWeldObject(rng.randint(0, 100, N).astype(np.int64), None)
    vals = NewWeldObject(rng.rand(N), None)
    base = NewWeldObject(np.zeros(100), None)
    expr = M.scatter_add(_ident(base), _ident(idxs), _ident(vals))
    obj = NewWeldObject([base, idxs, vals], expr)
    prog = build_program(obj)
    shapes = {k: tuple(np.asarray(v[2]).shape) for k, v in prog.inputs.items()}
    opt = optimize(prog.expr, stats={}, input_shapes=shapes)
    stats: dict = {}
    planned = kp.plan_kernels(opt, input_shapes=shapes, stats=stats)
    assert stats.get("kernelize.vecmerger_segment_sum", 0) == 1
    assert any(isinstance(n, ir.KernelCall) for n in ir.walk(planned))


def test_planner_annotates_dict_groupby():
    keys = NewWeldObject(rng.randint(0, 32, N).astype(np.int64), None)
    vals = NewWeldObject(rng.rand(N), None)
    expr = M.groupby_agg(_ident(keys), _ident(vals), "+", capacity=64)
    obj = NewWeldObject([keys, vals], expr)
    prog = build_program(obj)
    shapes = {k: tuple(np.asarray(v[2]).shape) for k, v in prog.inputs.items()}
    opt = optimize(prog.expr, stats={}, input_shapes=shapes)
    stats: dict = {}
    kp.plan_kernels(opt, input_shapes=shapes, stats=stats)
    assert stats.get("kernelize.dict_group_sum", 0) == 1


def test_planner_annotates_matmul():
    from repro.frames import weldnp

    a = weldnp.array(rng.rand(32, 16))
    b = weldnp.array(rng.rand(16, 8))
    prog = build_program(a.dot(b).obj)
    shapes = {k: tuple(np.asarray(v[2]).shape) for k, v in prog.inputs.items()}
    opt = optimize(prog.expr, stats={}, input_shapes=shapes)
    stats: dict = {}
    kp.plan_kernels(opt, input_shapes=shapes, stats=stats)
    assert stats.get("kernelize.matmul", 0) == 1


# ---------------------------------------------------------------------------
# end-to-end parity: kernelized == jnp-only
# ---------------------------------------------------------------------------


def test_q6_kernelized_matches_jnp():
    obj, want = _q6_like_obj()
    st: dict = {}
    r1 = Evaluate(obj, kernelize=True, collect_stats=st)
    r0 = Evaluate(obj, kernelize=False)
    assert st["kernelize.filter_reduce_sum"] == 1
    np.testing.assert_allclose(r1.value, r0.value, rtol=1e-12)
    np.testing.assert_allclose(r1.value, want, rtol=1e-10)


def test_reduce_without_filter_kernelized():
    """Unconditional map+reduce (Black-Scholes shape) also routes."""
    x = rng.rand(N)
    xo = NewWeldObject(x, None)
    expr = M.reduce_(
        M.map_(_ident(xo), lambda v: ir.BinOp(
            "*", ir.UnaryOp("exp", v), ir.Literal(2.0, wt.F64))),
        "+",
    )
    obj = NewWeldObject([xo], expr)
    st: dict = {}
    r1 = Evaluate(obj, kernelize=True, collect_stats=st)
    assert st["kernelize.filter_reduce_sum"] == 1
    assert dict(
        [(k, v) for k, v in st.items() if k == "kernelize.matched"]
    )["kernelize.matched"] == 1
    np.testing.assert_allclose(r1.value, (np.exp(x) * 2.0).sum(), rtol=1e-10)


def test_segment_reduce_kernelized_matches_jnp():
    idxs = rng.randint(0, 100, N).astype(np.int64)
    vals = rng.rand(N)
    base = rng.rand(100)
    io = NewWeldObject(idxs, None)
    vo = NewWeldObject(vals, None)
    bo = NewWeldObject(base, None)
    expr = M.scatter_add(_ident(bo), _ident(io), _ident(vo))
    obj = NewWeldObject([bo, io, vo], expr)
    st: dict = {}
    r1 = np.asarray(Evaluate(obj, kernelize=True, collect_stats=st).value)
    r0 = np.asarray(Evaluate(obj, kernelize=False).value)
    assert st["kernelize.vecmerger_segment_sum"] == 1
    np.testing.assert_allclose(r1, r0, rtol=1e-12)
    want = base.copy()
    np.add.at(want, idxs, vals)
    np.testing.assert_allclose(r1, want, rtol=1e-10)


def test_groupby_kernelized_matches_jnp():
    from repro.frames import welddf

    state = rng.randint(0, 50, N).astype(np.int64)
    crime = rng.rand(N)
    df = welddf.DataFrame({"state": state, "crime": crime})
    st: dict = {}
    d1 = df.groupby_sum("state", "crime", capacity=64, kernelize=True,
                        collect_stats=st)
    d0 = df.groupby_sum("state", "crime", capacity=64, kernelize=False)
    assert st["kernelize.dict_group_sum"] == 1
    assert set(d1) == set(d0)
    for k in d0:
        np.testing.assert_allclose(d1[k], d0[k], rtol=1e-10)


def test_masked_groupby_kernelized_matches_jnp():
    from repro.frames import welddf

    state = rng.randint(0, 50, N).astype(np.int64)
    crime = rng.rand(N)
    df = welddf.DataFrame({"state": state, "crime": crime})
    fdf = df[df["crime"] > 0.5]
    st: dict = {}
    d1 = fdf.groupby_sum("state", "crime", capacity=64, kernelize=True,
                         collect_stats=st)
    d0 = fdf.groupby_sum("state", "crime", capacity=64, kernelize=False)
    assert st["kernelize.dict_group_sum"] == 1
    assert set(d1) == set(d0)
    for k in d0:
        np.testing.assert_allclose(d1[k], d0[k], rtol=1e-10)


def test_matmul_kernelized_matches_jnp():
    from repro.frames import weldnp

    A, B = rng.rand(48, 24), rng.rand(24, 16)
    wa, wb = weldnp.array(A), weldnp.array(B)
    st: dict = {}
    got = np.asarray(wa.dot(wb).evaluate(kernelize=True, collect_stats=st))
    assert st["kernelize.matmul"] == 1
    np.testing.assert_allclose(got.reshape(48, 16), A @ B, rtol=1e-12)


def test_map_chain_kernelized_matches_jnp():
    from repro.frames import weldnp

    x = rng.rand(N)
    wx = weldnp.array(x)
    y = weldnp.exp(wx * 2.0) + 1.0
    st: dict = {}
    got = np.asarray(y.evaluate(kernelize=True, collect_stats=st))
    assert st["kernelize.map_elementwise"] == 1
    np.testing.assert_allclose(got, np.exp(x * 2.0) + 1.0, rtol=1e-12)


# ---------------------------------------------------------------------------
# fallback: mismatches lower exactly as before
# ---------------------------------------------------------------------------


def test_non_plus_reduce_falls_back():
    """max-reduce has no kernel; planner must leave it to the emitter."""
    x = rng.rand(N)
    xo = NewWeldObject(x, None)
    expr = M.reduce_(_ident(xo), "max")
    obj = NewWeldObject([xo], expr)
    st: dict = {}
    r1 = Evaluate(obj, kernelize=True, collect_stats=st)
    assert st["kernelize.matched"] == 0
    np.testing.assert_allclose(r1.value, x.max(), rtol=1e-12)


def test_big_capacity_groupby_falls_back():
    """capacity beyond the VMEM tile bound must not route."""
    from repro.frames import welddf

    state = rng.randint(0, 50, N).astype(np.int64)
    crime = rng.rand(N)
    df = welddf.DataFrame({"state": state, "crime": crime})
    st: dict = {}
    d1 = df.groupby_sum("state", "crime", capacity=1 << 17, kernelize=True,
                        collect_stats=st)
    assert st["kernelize.matched"] == 0
    d0 = df.groupby_sum("state", "crime", capacity=1 << 17, kernelize=False)
    assert set(d1) == set(d0)


def test_default_capacity_groupby_routes():
    """The frames' default capacity (4096) must fit the kernel tile."""
    from repro.frames import welddf

    state = rng.randint(0, 50, N).astype(np.int64)
    crime = rng.rand(N)
    df = welddf.DataFrame({"state": state, "crime": crime})
    st: dict = {}
    d1 = df.groupby_sum("state", "crime", kernelize=True, collect_stats=st)
    assert st["kernelize.dict_group_sum"] == 1
    d0 = df.groupby_sum("state", "crime", kernelize=False)
    assert set(d1) == set(d0)
    for k in d0:
        np.testing.assert_allclose(d1[k], d0[k], rtol=1e-10)


def test_out_of_range_keys_recover_not_drop():
    """Keys outside [0, capacity) can't be represented by the dense-key
    route; the poison flag triggers the recovery ladder (regrow until
    the key fits, else generic fallback) instead of silently dropping
    rows.  With recovery disabled the typed CapacityError surfaces."""
    import warnings

    from repro.core import recovery
    from repro.core.errors import CapacityError
    from repro.frames import welddf

    key = np.array([100, 100, 1, 2], dtype=np.int64)
    val = np.array([1.0, 2.0, 3.0, 4.0])
    df = welddf.DataFrame({"k": key, "v": val})
    d0 = df.groupby_sum("k", "v", capacity=64, kernelize=False)
    assert d0 == {1: 3.0, 2: 4.0, 100: 3.0}
    st: dict = {}
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        d1 = df.groupby_sum("k", "v", capacity=64, kernelize=True,
                            collect_stats=st)
    assert d1 == d0
    assert st["recovery.attempts"] >= 2
    assert any("weld recovery" in str(x.message) for x in w)
    with recovery.disabled():
        with pytest.raises(CapacityError, match="outside \\[0, capacity\\)"):
            df.groupby_sum("k", "v", capacity=64, kernelize=True)


def test_float_key_groupby_falls_back():
    from repro.frames import welddf

    key = rng.rand(N)  # float keys: no dense-int routing
    val = rng.rand(N)
    df = welddf.DataFrame({"k": key, "v": val})
    st: dict = {}
    df.groupby_sum("k", "v", capacity=8192, kernelize=True, collect_stats=st)
    assert st["kernelize.matched"] == 0


def test_default_is_auto_and_off_disables_planning():
    """No knob -> cost-gated "auto" planning (stats carry the decision
    log); kernelize=False/"off" bypasses the planner entirely."""
    assert kp.DEFAULT_KERNELIZE == "auto"
    obj, want = _q6_like_obj()
    st: dict = {}
    r = Evaluate(obj, collect_stats=st)
    assert st["kernelplan"]["mode"] == "auto"
    assert st["kernelplan"]["costs"]  # every candidate was priced
    np.testing.assert_allclose(r.value, want, rtol=1e-10)
    st_off: dict = {}
    r0 = Evaluate(obj, kernelize="off", collect_stats=st_off)
    assert not any(k.startswith("kernel") for k in st_off)
    np.testing.assert_allclose(r0.value, want, rtol=1e-10)
    with pytest.raises(ValueError):
        Evaluate(obj, kernelize="sometimes")


# ---------------------------------------------------------------------------
# impl resolution: interpret (Pallas body on CPU) vs ref oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("pattern", ["q6", "mapchain"])
def test_interpret_matches_ref(pattern):
    if pattern == "q6":
        obj, _ = _q6_like_obj(512)
        ri = Evaluate(obj, kernelize=True, kernel_impl="interpret")
        rr = Evaluate(obj, kernelize=True, kernel_impl="ref")
        np.testing.assert_allclose(ri.value, rr.value, rtol=1e-12)
    else:
        from repro.frames import weldnp

        x = rng.rand(512)
        wx = weldnp.array(x)
        y = weldnp.exp(wx * 2.0) + 1.0
        gi = np.asarray(y.evaluate(kernelize=True, kernel_impl="interpret"))
        gr = np.asarray(y.evaluate(kernelize=True, kernel_impl="ref"))
        np.testing.assert_allclose(gi, gr, rtol=1e-12)


def test_overflowed_dict_lookup_is_poisoned_not_plausible():
    """In-IR Lookup into an overflowed kernelized dict must not return a
    plausible-but-wrong number: float sums are NaN-poisoned and KeyExists
    sees no keys (host decode raises separately)."""
    keys = NewWeldObject(np.array([100, 1, 2], dtype=np.int64), None)
    vals = NewWeldObject(np.array([3.0, 3.0, 4.0]), None)
    d = M.groupby_agg(_ident(keys), _ident(vals), "+", capacity=8)
    obj = NewWeldObject([keys, vals], ir.Lookup(d, ir.Literal(2, wt.I64)))
    g0 = Evaluate(obj, kernelize=False).value
    assert g0 == 4.0
    assert np.isnan(Evaluate(obj, kernelize=True).value)
    d2 = M.groupby_agg(_ident(keys), _ident(vals), "+", capacity=8)
    obj2 = NewWeldObject([keys, vals],
                         ir.KeyExists(d2, ir.Literal(1, wt.I64)))
    assert not bool(Evaluate(obj2, kernelize=True).value)


def test_unregister_invalidates_compile_cache():
    """register/unregister is the ablation knob; a cached kernelized
    executable must not survive a registry change."""
    from repro.frames import welddf

    df = welddf.DataFrame({"k": np.array([1, 2, 2], dtype=np.int64),
                           "v": np.array([1.0, 2.0, 3.0])})
    st: dict = {}
    df.groupby_sum("k", "v", capacity=16, kernelize=True, collect_stats=st)
    assert st["kernelize.dict_group_sum"] == 1
    spec = kp.get("dict_group_sum")
    kp.unregister("dict_group_sum")
    try:
        st2: dict = {}
        d = df.groupby_sum("k", "v", capacity=16, kernelize=True,
                           collect_stats=st2)
        assert st2.get("kernelize.dict_group_sum", 0) == 0
        assert d == {1: 1.0, 2: 5.0}
    finally:
        kp.register(spec)


def test_program_evaluate_threads_kernelize():
    """lazy.Program.evaluate exposes the knob and the planner stats."""
    obj, want = _q6_like_obj()
    prog = build_program(obj)
    value, compile_ms, from_cache, stats = prog.evaluate(kernelize=True)
    assert stats["kernelize.filter_reduce_sum"] == 1
    np.testing.assert_allclose(np.asarray(value), want, rtol=1e-10)
    v0, *_ = prog.evaluate(kernelize=False)
    np.testing.assert_allclose(np.asarray(value), np.asarray(v0), rtol=1e-12)


def test_registry_describes_all_kernels():
    names = {s.name for s in kp.all_specs()}
    assert {"filter_reduce_sum", "vecmerger_segment_sum", "dict_group_sum",
            "matmul", "matvec", "map_elementwise"} <= names
    text = kp.describe()
    assert "repro.kernels.ops" in text


# ---------------------------------------------------------------------------
# cost gate (mode="auto"): tiny inputs reject, large dense inputs route,
# oversized vecmerger scatter rejects
# ---------------------------------------------------------------------------


def test_cost_gate_rejects_tiny_input():
    """Padding + launch overhead dominate a tiny reduce: the gate must
    keep the jnp lowering (and still compute the right answer)."""
    obj, want = _q6_like_obj(256)
    st: dict = {}
    r = Evaluate(obj, kernelize="auto", collect_stats=st)
    assert st["kernelize.matched"] == 0
    assert st["kernelplan"]["rejected"].get("filter_reduce_sum", 0) == 1
    (entry,) = st["kernelplan"]["costs"]
    assert entry["routed"] is False
    assert entry["kernel_us"] > entry["jnp_us"]  # the losing estimate
    np.testing.assert_allclose(r.value, want, rtol=1e-10)


def test_cost_gate_routes_large_dense_input():
    obj, want = _q6_like_obj(500_000)
    st: dict = {}
    r = Evaluate(obj, kernelize="auto", collect_stats=st)
    assert st["kernelize.filter_reduce_sum"] == 1
    assert st["kernelplan"]["routed"] == {"filter_reduce_sum": 1}
    np.testing.assert_allclose(r.value, want, rtol=1e-8)


def test_cost_gate_rejects_large_key_vecmerger():
    """K beyond the VMEM tile bound degrades the kernel route to the
    same scatter the jnp lowering does — auto must not route it."""
    n, k = 100_000, 50_000
    idxs = rng.randint(0, k, n).astype(np.int64)
    vals = rng.rand(n)
    base = np.zeros(k)
    io, vo, bo = (NewWeldObject(a, None) for a in (idxs, vals, base))
    expr = M.scatter_add(_ident(bo), _ident(io), _ident(vo))
    obj = NewWeldObject([bo, io, vo], expr)
    st: dict = {}
    r = np.asarray(Evaluate(obj, kernelize="auto", collect_stats=st).value)
    assert st["kernelize.matched"] == 0
    assert st["kernelplan"]["rejected"].get("vecmerger_segment_sum", 0) == 1
    want = base.copy()
    np.add.at(want, idxs, vals)
    np.testing.assert_allclose(r, want, rtol=1e-10)


def test_cost_gate_unknown_size_is_conservative():
    """A match whose iter length is not statically known cannot be
    priced — auto must fall back to jnp rather than gamble."""
    from repro.core.kernelplan import cost

    spec = kp.get("filter_reduce_sum")
    est = cost.estimate(spec, {"kernel": "filter_reduce_sum", "n": None})
    assert est.routed is False
    assert "unknown" in est.why


# ---------------------------------------------------------------------------
# autotune: cache hit / invalidate / fingerprint-keyed compile cache
# ---------------------------------------------------------------------------


@pytest.fixture
def tuner(tmp_path, monkeypatch):
    from repro.core.kernelplan import autotune

    monkeypatch.setenv(autotune.ENV_CACHE,
                       str(tmp_path / "autotune.json"))
    autotune.clear_cache(disk=False)
    yield autotune
    autotune.clear_cache(disk=False)


def test_autotune_times_grid_then_hits_cache(tuner, monkeypatch):
    spec = kp.get("filter_reduce_sum")
    meta = {"n": 2000, "dtype": np.float64}
    timed = []
    real = tuner._time_candidate
    monkeypatch.setattr(tuner, "_time_candidate",
                        lambda go: timed.append(1) or real(go))
    params, cached = tuner.tune(spec, meta, impl="interpret")
    assert not cached
    assert params["block"] in spec.tune_space["block"]
    assert len(timed) == len(spec.tune_space["block"])
    import json, os
    assert os.path.exists(tuner.cache_path())
    disk = json.load(open(tuner.cache_path()))
    assert any(k.startswith("filter_reduce_sum|float64|") for k in disk)
    # same size-bucket: cache hit, no re-timing
    timed.clear()
    params2, cached2 = tuner.tune(spec, {"n": 1800, "dtype": np.float64},
                                  impl="interpret")
    assert cached2 and params2 == params and not timed


def test_autotune_invalidate_and_fingerprint(tuner):
    spec = kp.get("filter_reduce_sum")
    f0 = tuner.fingerprint()
    tuner.tune(spec, {"n": 1500, "dtype": np.float64}, impl="interpret")
    f1 = tuner.fingerprint()
    assert f1 != f0  # new tuning must change the compile-cache key
    assert tuner.invalidate("filter_reduce_sum") == 1
    assert tuner.lookup("filter_reduce_sum", np.float64, 1500,
                        "interpret") is None
    assert tuner.fingerprint() != f1


def test_autotune_ref_impl_uses_defaults_without_cache(tuner):
    """The jnp oracle ignores block sizes: no timing, no cache writes."""
    spec = kp.get("filter_reduce_sum")
    params, cached = tuner.tune(spec, {"n": 4096, "dtype": np.float64},
                                impl="ref")
    assert params == spec.tune_defaults and not cached
    assert tuner.lookup("filter_reduce_sum", np.float64, 4096, "ref") is None


def test_tuned_plan_prints_block_shape():
    """pretty() surfaces the chosen block on KernelCall nodes."""
    from repro.core.kernelplan import autotune
    from repro.core.pretty import pretty

    obj, _ = _q6_like_obj(1024)
    prog = build_program(obj)
    shapes = {k: tuple(np.asarray(v[2]).shape) for k, v in prog.inputs.items()}
    opt = optimize(prog.expr, stats={}, input_shapes=shapes)
    planned = kp.plan_kernels(opt, input_shapes=shapes, stats={})
    tuned = autotune.tune_plan(planned, impl="ref")
    text = pretty(tuned)
    assert "kernel[filter_reduce_sum]@{block=" in text


# ---------------------------------------------------------------------------
# multi-aggregate fusion: one kernel launch for a struct of mergers
# ---------------------------------------------------------------------------


def test_multi_agg_fused_matches_per_aggregate_kernel():
    from repro.kernels import ops as kops

    vals = rng.rand(3, 5000)
    pred = rng.rand(5000) > 0.4
    fused = np.asarray(kops.filter_reduce_sum_multi(
        vals, pred, impl="interpret"))
    single = np.array([
        np.asarray(kops.filter_reduce_sum(vals[a], pred, impl="interpret"))
        for a in range(3)
    ])
    np.testing.assert_allclose(fused, single, rtol=1e-12)
    np.testing.assert_allclose(fused, vals[:, pred].sum(axis=1), rtol=1e-10)


def test_weldrel_multi_agg_is_one_kernel_call_and_parity():
    """Three aggregates over the same filtered scan: ONE filter_reduce
    launch (shared predicate mask + column loads), identical results to
    the jnp lowering and to the forced per-aggregate path."""
    from repro.frames import weldrel

    n = 4096
    c = {"a": rng.rand(n), "b": rng.rand(n), "p": rng.rand(n)}
    t = weldrel.Table(c)

    def agg(kernelize, st=None):
        q = weldrel.Query(t).filter(t.col("p") < 0.5)
        return q.agg({"x": (t.col("a"), "+"),
                      "y": (t.col("b"), "+"),
                      "z": (t.col("a") * t.col("b"), "+")},
                     kernelize=kernelize, collect_stats=st)

    st: dict = {}
    r1 = agg(True, st)
    assert st["kernelize.filter_reduce_sum"] == 1  # one call, three aggs
    r0 = agg(False)
    for key in ("x", "y", "z"):
        np.testing.assert_allclose(r1[key], r0[key], rtol=1e-10)
    # forced per-aggregate path (multi=False) agrees with the fused one
    mask = c["p"] < 0.5
    np.testing.assert_allclose(r1["x"], c["a"][mask].sum(), rtol=1e-10)
    np.testing.assert_allclose(r1["z"], (c["a"] * c["b"])[mask].sum(),
                               rtol=1e-10)


def test_multi_agg_forced_per_aggregate_path_parity():
    """The adapter's multi=False ablation param takes the per-aggregate
    path and must agree with the fused kernel."""
    from repro.core.backend.values import WVec
    from repro.core.kernelplan import registry as kreg

    n = 3000
    a, b = rng.rand(n), rng.rand(n)
    i = ir.Ident("i", wt.I64)
    x = ir.Ident("x", wt.Struct((wt.F64, wt.F64)))
    fns = [
        ir.Lambda((i, x), ir.GetField(x, 0)),
        ir.Lambda((i, x), ir.GetField(x, 1)),
        ir.Lambda((i, x), ir.BinOp("<", ir.GetField(x, 0),
                                   ir.Literal(0.5, wt.F64))),
    ]

    def run(multi):
        import jax.numpy as jnp
        from repro.core.backend.jaxgen import Emitter

        em = Emitter({}, None, kernel_impl="ref")
        staged = [em._stage_elem_fn(f, {}) for f in fns]
        return kreg.get("filter_reduce_sum").execute(
            [WVec(jnp.asarray(a)), WVec(jnp.asarray(b))],
            {"n_aggs": 2, "has_pred": True, "struct": True, "multi": multi},
            staged, "ref",
        )

    fused = [np.asarray(v) for v in run(True)]
    per_agg = [np.asarray(v) for v in run(False)]
    np.testing.assert_allclose(fused, per_agg, rtol=1e-12)
    mask = a < 0.5
    np.testing.assert_allclose(fused[0], a[mask].sum(), rtol=1e-10)
    np.testing.assert_allclose(fused[1], b[mask].sum(), rtol=1e-10)


# ---------------------------------------------------------------------------
# memory accounting: kernel padding/scratch feeds the memory_limit budget
# ---------------------------------------------------------------------------


def test_kernel_footprint_charged_to_memory_limit():
    from repro.core.backend.jaxgen import WeldMemoryError
    from repro.core.runtime import clear_cache

    obj, want = _q6_like_obj(8192)
    clear_cache()
    # generous limit: fine (and routed)
    st: dict = {}
    r = Evaluate(obj, kernelize=True, memory_limit=1 << 22, collect_stats=st)
    assert st["kernelize.filter_reduce_sum"] == 1
    np.testing.assert_allclose(r.value, want, rtol=1e-10)
    # tight limit: the kernelized plan's staging/padding must trip it...
    with pytest.raises(WeldMemoryError, match="kernel"):
        Evaluate(obj, kernelize=True, memory_limit=16 * 1024)
    # ...while the jnp-only lowering (no kernel scratch) stays within
    r0 = Evaluate(obj, kernelize=False, memory_limit=16 * 1024)
    np.testing.assert_allclose(r0.value, want, rtol=1e-10)


# ---------------------------------------------------------------------------
# stats contract: documented key namespaces (loops.*, kernelize.*,
# kernelplan.*, compile_ms) survive cache hit vs miss, and the returned
# stats are a COPY — caller-side mutation must never poison the cache
# ---------------------------------------------------------------------------

DOCUMENTED_STATS = ("loops.before", "loops.after", "kernelize.matched",
                    "kernelplan", "compile_ms", "bounds.certificate",
                    "bounds.peak_bytes", "bounds.admitted")


def test_stats_namespaces_survive_cache_hit_and_miss():
    from repro.core import runtime

    runtime.clear_cache()
    obj, _ = _q6_like_obj(2777)
    st_miss: dict = {}
    r_miss = Evaluate(obj, kernelize=True, collect_stats=st_miss)
    assert r_miss.from_cache is False
    st_hit: dict = {}
    r_hit = Evaluate(obj, kernelize=True, collect_stats=st_hit)
    assert r_hit.from_cache is True
    for key in DOCUMENTED_STATS:
        assert key in st_miss, f"miss stats lost {key}"
        assert key in st_hit, f"hit stats lost {key}"
    assert st_hit["kernelize.filter_reduce_sum"] == 1
    assert st_hit["kernelplan"]["routed"] == st_miss["kernelplan"]["routed"]
    # compile_ms in the stats dict is the REAL compile cost (cached in
    # the entry), even though WeldResult.compile_ms reports 0 on a hit
    assert st_hit["compile_ms"] == st_miss["compile_ms"] > 0


def test_cached_stats_returned_as_copy_mutation_cannot_poison():
    from repro.core import runtime

    runtime.clear_cache()
    obj, _ = _q6_like_obj(2779)
    st1: dict = {}
    Evaluate(obj, kernelize=True, collect_stats=st1)
    # poison attempt: mutate scalars AND nested containers of the
    # returned stats (dict(stats) used to share the nested dicts/lists
    # with the cache entry)
    st1["kernelplan"]["routed"]["fake_kernel"] = 99
    st1["kernelplan"]["costs"].append({"kernel": "fake"})
    st1["loops.after"] = -1
    st1["kernelize.matched"] = 0
    st1["bounds.admitted"] = False
    st1["bounds.builders"].append("fake builder line")
    st2: dict = {}
    r2 = Evaluate(obj, kernelize=True, collect_stats=st2)
    assert r2.from_cache is True
    assert "fake_kernel" not in st2["kernelplan"]["routed"]
    assert all(c.get("kernel") != "fake"
               for c in st2["kernelplan"]["costs"])
    assert st2["loops.after"] >= 0
    assert st2["kernelize.matched"] == 1
    assert st2["bounds.admitted"] is True
    assert "fake builder line" not in st2["bounds.builders"]


# ---------------------------------------------------------------------------
# front_pack: one carried sort == argsort(~keep, stable) + a gather per column
# ---------------------------------------------------------------------------


def _keep(kind, n, r):
    return {"all": np.ones(n, bool), "none": np.zeros(n, bool),
            "rand70": r.random(n) < 0.7,
            "alternating": np.arange(n) % 2 == 0}[kind]


def _column(kind, n, r):
    if kind == "int32":
        return r.integers(-2 ** 31, 2 ** 31, n, dtype=np.int32)
    if kind == "float32":  # NaN and -0.0 must move as bit patterns
        v = r.standard_normal(n).astype(np.float32)
        v[::7] = np.nan
        v[3::7] = -0.0
        return v
    if kind == "int64":
        return r.integers(2 ** 32, 2 ** 62, n, dtype=np.int64) * \
            np.where(r.random(n) < 0.5, -1, 1)
    if kind == "bool":
        return r.random(n) < 0.5
    return (_column("int64", n, r), (_column("float32", n, r),
                                     _column("bool", n, r)))


def _bits(v):
    a = np.asarray(v)
    return a.view(np.uint32) if a.dtype == np.float32 else a


@pytest.mark.parametrize("n", [0, 1, 4097])
@pytest.mark.parametrize("col", ["int32", "float32", "int64", "bool",
                                 "struct"])
@pytest.mark.parametrize("keep", ["all", "none", "rand70", "alternating"])
def test_front_pack_is_argsort_and_gather_bit_for_bit(keep, col, n):
    import jax
    import jax.numpy as jnp

    from repro.core.backend.jaxgen import front_pack

    r = np.random.default_rng(16)
    k = jnp.asarray(_keep(keep, n, r))
    cols = jax.tree_util.tree_map(jnp.asarray, _column(col, n, r))
    got = front_pack(k, cols)
    order = jnp.argsort(~k, stable=True)
    want = jax.tree_util.tree_map(lambda c: c[order], cols)
    assert (jax.tree_util.tree_structure(got)
            == jax.tree_util.tree_structure(want))
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(_bits(g), _bits(w))
