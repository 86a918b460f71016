"""The TPU path's host-side contracts, checked on the CPU.

* the kernel impl resolves from the backend (ref on CPU, pallas on TPU);
* ``impl="pallas"`` plans reject what Mosaic cannot lower — 64-bit
  elements and packed multi-column keys — with a ``why`` naming it;
* an autotune grid whose every candidate fails raises instead of caching;
* a kernel-compiler failure at ``jit(...).lower()/.compile()`` surfaces
  as a typed ``KernelCompileError``;
* the cost gate's peaks are keyed by device kind;
* the persistent compile cache lives where ``JAX_COMPILATION_CACHE_DIR``
  says, else at one fixed path in the checkout;
* the hash kernels' 32-bit key space (what the chip runs) agrees with
  the oracles in interpret mode.
"""
import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.runtime  # noqa: F401  (x64 on, as in the program)
from repro.core import recovery
from repro.core.errors import KernelCompileError
from repro.core.kernelplan import registry as kreg
from repro.frames import weldrel
from repro.kernels import hash_table, ops

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
rng = np.random.RandomState(7)


def test_default_impl_resolves_to_ref_on_cpu(monkeypatch):
    monkeypatch.setattr(ops, "DEFAULT_IMPL", None)
    assert ops.default_impl() == "ref"
    assert ops.DEFAULT_IMPL == "ref"


def test_default_impl_resolves_to_pallas_on_tpu(monkeypatch):
    monkeypatch.setattr(ops, "DEFAULT_IMPL", None)
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "tpu")
    assert ops.default_impl() == "pallas"


def _rejections(stats):
    return {c["kernel"]: c["why"] for c in stats["kernelplan"]["costs"]
            if not c["routed"]}


@pytest.mark.parametrize("dtype,kind", [(np.float64, "f64"),
                                        (np.int64, "i64")])
def test_pallas_plan_rejects_64bit_elements(dtype, kind):
    x = (rng.rand(5000) * 100).astype(dtype)
    t = weldrel.Table({"x": x}, eager=False)
    st = {}
    with recovery.disabled():
        got = weldrel.Query(t).filter(t.col("x") > dtype(10)).agg(
            {"s": (t.col("x"), "+")}, kernelize="always",
            kernel_impl="pallas", collect_stats=st)
    assert st["kernelplan"]["routed"] == {}
    assert f"dtype {kind}" in _rejections(st)["filter_reduce_sum"]
    np.testing.assert_allclose(got["s"], x[x > 10].sum(), rtol=1e-9)


def test_pallas_plan_rejects_packed_two_column_key():
    n = 3000
    cols = {"a": rng.randint(0, 3, n).astype(np.int32),
            "b": rng.randint(0, 2, n).astype(np.int32),
            "v": rng.rand(n).astype(np.float32)}
    t = weldrel.Table(cols, eager=False)
    st = {}
    with recovery.disabled():
        got = weldrel.Query(t).group_agg(
            [t.col("a"), t.col("b")], {"s": (t.col("v"), "+")}, capacity=8,
            kernelize="always", kernel_impl="pallas", collect_stats=st)
    assert "packed 64-bit key (2 columns)" in \
        _rejections(st)["dict_hash_build"]
    for (a, b), (s, cnt) in got.items():
        m = (cols["a"] == a) & (cols["b"] == b)
        assert cnt == m.sum()
        np.testing.assert_allclose(s, cols["v"][m].sum(dtype=np.float64),
                                   rtol=1e-4)


def test_pallas_plan_keeps_32bit_routes():
    """The same gate lets int32 keys and f32 values through: only the
    dtype decides, never the impl alone."""
    from repro.core import ir, wtypes as wt
    from repro.core.kernelplan.planner import plan_kernels

    kt = wt.I32
    x = ir.Ident("x", wt.Vec(kt))
    b = ir.Ident("b", wt.DictMerger(kt, wt.F32, "+"))
    i = ir.Ident("i", wt.I64)
    e = ir.Ident("e", kt)
    loop = ir.Result(ir.For(
        (ir.Iter(x),), ir.NewBuilder(b.ty, arg=ir.Literal(16, wt.I64)),
        ir.Lambda((b, i, e), ir.Merge(b, ir.MakeStruct(
            (e, ir.Cast(e, wt.F32)))))))
    st2 = {}
    plan_kernels(loop, input_shapes={"x": (64,)}, stats=st2, mode="always",
                 impl="pallas")
    assert st2["kernelplan"]["routed"] == {"dict_group_sum": 1}


@pytest.fixture
def tuner(tmp_path, monkeypatch):
    from repro.core.kernelplan import autotune

    monkeypatch.setenv(autotune.ENV_CACHE, str(tmp_path / "autotune.json"))
    autotune.clear_cache(disk=False)
    yield autotune
    autotune.clear_cache(disk=False)


def test_autotune_all_candidates_failing_raises_and_caches_nothing(tuner):
    spec = kreg.get("filter_reduce_sum")

    def broken(meta, params, impl):
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    spec = dataclasses.replace(spec, make_bench=broken)
    with pytest.raises(KernelCompileError, match="every filter_reduce_sum"):
        tuner.tune(spec, {"n": 5000, "dtype": np.float32}, impl="pallas")
    assert tuner.lookup("filter_reduce_sum", np.float32, 5000,
                        "pallas") is None
    assert not os.path.exists(tuner.cache_path())


def test_kernel_compiler_failure_is_typed(monkeypatch):
    """A Mosaic error raised while the program lowers/compiles names the
    routed kernel in a KernelCompileError (not a generic crash)."""
    from repro.core import runtime

    real = runtime.emit_program

    def emit(*a, **kw):
        fn = real(*a, **kw)

        def failing(*args):
            fn(*args)
            raise RuntimeError("Mosaic failed to compile TPU kernel: test")

        return failing

    monkeypatch.setattr(runtime, "emit_program", emit)
    x = rng.rand(4096).astype(np.float32)
    t = weldrel.Table({"x": x}, eager=False)
    with recovery.disabled():
        with pytest.raises(KernelCompileError) as ei:
            weldrel.Query(t).filter(t.col("x") > np.float32(0.5)).agg(
                {"s": (t.col("x"), "+")}, kernelize="always",
                kernel_impl="ref")
    assert ei.value.kernel == "filter_reduce_sum"
    assert "Mosaic" in str(ei.value)


def test_cost_gate_peaks_keyed_by_device_kind(monkeypatch):
    from repro.core.kernelplan import cost
    from repro.roofline import analysis

    kind, peaks, is_target = analysis.device_peaks()
    assert (kind, is_target) == (analysis.TARGET_KIND, True)
    assert peaks["hbm_bw"] == 819e9
    est = cost.estimate(kreg.get("filter_reduce_sum"),
                        {"n": 1 << 20, "elem_bytes": 4})
    assert "peaks=TPU v5 lite (target)" in est.why

    class FakeTPU:
        platform, device_kind = "tpu", "TPU v99"

    monkeypatch.setattr(jax, "devices", lambda *a: [FakeTPU()])
    with pytest.raises(KeyError, match="TPU v99"):
        cost.estimate(kreg.get("filter_reduce_sum"),
                      {"n": 1 << 20, "elem_bytes": 4})
    FakeTPU.device_kind = "TPU v5 lite"
    est = cost.estimate(kreg.get("filter_reduce_sum"),
                        {"n": 1 << 20, "elem_bytes": 4})
    assert "peaks=TPU v5 lite" in est.why and "(target)" not in est.why


def _cache_dir(env):
    code = ("import jax, repro.core.runtime; "
            "print(jax.config.jax_compilation_cache_dir)")
    env = dict(env, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    return out.stdout.strip().splitlines()[-1]


def test_compile_cache_dir_env_wins(tmp_path):
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert _cache_dir(env) == str(tmp_path)


def test_compile_cache_dir_defaults_to_checkout():
    from repro.core import runtime

    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    got = _cache_dir(env)
    checkout = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                            ".."))
    assert got == runtime.CHECKOUT_CACHE_DIR
    assert got == os.path.join(checkout, ".jax_cache")
    ignored = subprocess.run(["git", "check-ignore", "-q", got],
                             cwd=checkout).returncode
    assert ignored in (0, 128)  # ignored by git (128: not a git checkout)


# -- the 32-bit key space the chip runs, in interpret mode -------------------


def test_hash_kernels_int32_key_space_both_impls():
    empty = hash_table.empty_of(np.int32)
    keys = np.concatenate([rng.randint(-500, 500, 400).astype(np.int32),
                           np.full(6, empty, np.int32)])
    rng.shuffle(keys)
    valid = keys != empty
    uk = np.unique(keys[valid])
    ctab = hash_table.table_size(uk.size)
    queries = rng.randint(-600, 600, 3000).astype(np.int32)
    got = {}
    for impl in ("ref", "interpret"):
        slots, table, used = map(np.asarray, ops.hash_to_slot(
            jnp.asarray(keys), ctab, impl=impl))
        assert table.dtype == np.int32 and int(used) == uk.size
        assert (slots[~valid] == ctab).all()
        assert (table[slots[valid]] == keys[valid]).all()
        cs, offs, gused = map(np.asarray, ops.group_build(
            jnp.asarray(keys), uk.size, impl=impl))
        assert int(gused) == uk.size and (cs[~valid] == uk.size).all()
        for s, kk in enumerate(uk):
            assert (cs[keys == kk] == s).all()
            assert offs[s + 1] - offs[s] == (keys == kk).sum()
        pos, found = map(np.asarray, ops.dict_probe(
            jnp.asarray(uk), uk.size, jnp.asarray(queries), impl=impl))
        np.testing.assert_array_equal(found, np.isin(queries, uk))
        np.testing.assert_array_equal(uk[pos[found]], queries[found])
        gpos, gfound, sizes = map(np.asarray, ops.group_probe(
            jnp.asarray(uk), jnp.asarray(offs), uk.size,
            jnp.asarray(queries), impl=impl))
        np.testing.assert_array_equal(gfound, found)
        np.testing.assert_array_equal(
            sizes[found], (offs[1:] - offs[:-1])[gpos[found]])
        got[impl] = (pos, found, offs, cs, sizes)
    for a, b in zip(got["ref"], got["interpret"]):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("fanout", [1, 3])
def test_int32_key_join_interpret_matches_numpy(fanout):
    """m:1 and m:n joins on int32 keys take the 32-bit hash kernels."""
    n, k = 2000, 50
    rk = np.repeat(np.arange(k, dtype=np.int32) * 7 - 100, fanout)
    r = weldrel.Table({"rk": rk, "rv": rng.rand(rk.size).astype(np.float32)},
                      eager=False)
    lk = rng.randint(0, k + 10, n).astype(np.int32) * 7 - 100
    lv = rng.rand(n).astype(np.float32)
    t = weldrel.Table({"lk": lk, "lv": lv}, eager=False)
    st = {}
    with recovery.disabled():
        out = weldrel.Query(t).join(r, on="lk", right_on="rk",
                                    kernelize="always",
                                    kernel_impl="interpret",
                                    collect_stats=st)
    want = {"dict_hash_build", "hash_probe"} if fanout == 1 else \
        {"group_build", "group_probe"}
    assert want <= set(st["kernelplan"]["routed"])
    order = np.argsort(rk, kind="stable")
    lo = np.searchsorted(rk[order], lk, side="left")
    cnt = np.searchsorted(rk[order], lk, side="right") - lo
    rows = np.repeat(np.arange(n), cnt)
    starts = np.concatenate([[0], np.cumsum(cnt)])[:-1]
    brow = order[lo[rows] + np.arange(rows.size) - starts[rows]]
    np.testing.assert_array_equal(np.asarray(out.col("lv").obj.data),
                                  lv[rows])
    np.testing.assert_array_equal(np.asarray(out.col("rv").obj.data),
                                  r.col("rv").obj.data[brow])
