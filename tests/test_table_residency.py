"""Device-resident weldrel tables: a lazy Table's columns are uploaded
by the first program that binds them and bound from the device by every
later one; the Table holds a read-only snapshot of its host columns."""
import sys
import threading
import time

import numpy as np
import pytest

from repro.core import lazy, obs, runtime
from repro.core.serve import QueryServer
from repro.frames import weldnp
from repro.frames.weldrel import Query, Table


@pytest.fixture(autouse=True)
def clean(tmp_path, monkeypatch):
    monkeypatch.setenv("WELD_AUTOTUNE_CACHE", str(tmp_path / "autotune.json"))
    monkeypatch.setenv("WELD_COST_LEDGER", str(tmp_path / "ledger.jsonl"))
    obs.disable()
    obs.clear()
    runtime.clear_cache()
    yield
    obs.disable()
    obs.clear()
    runtime.clear_cache()


def _columns(n=5000, seed=0):
    rng = np.random.default_rng(seed)
    return {"p": rng.normal(size=n), "d": rng.uniform(0, 0.1, n),
            "q": rng.integers(0, 50, n)}


def _revenue(t: Table):
    """A Q6-shaped staged query: filter on two columns, sum a product."""
    pred = (t.col("q") < 24) & (t.col("d") >= 0.05)
    return Query(t).filter(pred).stage().agg(
        {"rev": (t.col("p") * t.col("d"), "+")})


def _want(cols):
    m = (cols["q"] < 24) & (cols["d"] >= 0.05)
    return float((cols["p"][m] * cols["d"][m]).sum())


def _encode_spans(fn):
    obs.enable()
    pos = obs.mark()
    try:
        out = fn()
    finally:
        obs.disable()
    return out, [s for s in obs.spans_since(pos) if s.name == "encode"]


def _slot(t: Table, name: str):
    return t.col(name).obj.resident


def test_a_table_queried_twice_through_the_server_uploads_once():
    cols = _columns()
    t = Table(cols)
    with QueryServer(workers=2) as srv:
        first, enc1 = _encode_spans(lambda: srv.run(_revenue(t)))
        second, enc2 = _encode_spans(lambda: srv.run(_revenue(t)))
    assert first["rev"] == pytest.approx(_want(cols), rel=1e-12)
    assert second["rev"] == pytest.approx(_want(cols), rel=1e-12)
    (e1,), (e2,) = enc1, enc2
    assert e1.tags["inputs"] == e2.tags["inputs"] == 3
    assert e1.counters == {"bytes": sum(a.nbytes for a in cols.values()),
                           "resident": 0}
    assert e2.counters == {"bytes": 0, "resident": 3}
    assert all(_slot(t, c).value is not None for c in cols)


def test_plain_arrays_upload_at_every_evaluation():
    x = weldnp.array(np.arange(100, dtype=np.float64))
    for _ in range(2):
        _, (enc,) = _encode_spans(lambda: (x * 2.0).sum().evaluate())
        assert enc.counters == {"bytes": 800, "resident": 0}
    assert x.obj.resident is None


def test_writing_the_callers_array_changes_no_answer():
    cols = _columns()
    want = _want(cols)
    t = Table(cols)
    before = Query(t).filter(t.col("q") < 24).agg(
        {"rev": (t.col("p"), "+")})
    late = Table(cols)  # not bound yet: its snapshot is what it copied
    cols["p"][:] = 1e6
    cols["q"][:] = 0
    assert _revenue(t).compile().run()["rev"] == pytest.approx(want)
    assert _revenue(late).compile().run()["rev"] == pytest.approx(want)
    assert Query(t).filter(t.col("q") < 24).agg(
        {"rev": (t.col("p"), "+")}) == before
    for tbl in (t, late, Table(cols, eager=True)):
        for c in cols:
            host = tbl.cols[c].obj.data if not tbl.eager \
                else tbl.cols[c]._eager
            assert not host.flags.writeable
            with pytest.raises(ValueError):
                host[0] = 0


def test_a_read_only_column_is_taken_uncopied_and_a_view_of_a_writeable_one_is_copied():
    frozen = np.arange(10.0)
    frozen.flags.writeable = False
    base = np.arange(10.0)
    view = base[:]
    view.flags.writeable = False
    t = Table({"a": frozen, "b": view})
    assert t.col("a").obj.data is frozen
    assert not np.shares_memory(t.col("b").obj.data, base)
    base[0] = 99.0
    assert t.col("b").obj.data[0] == 0.0


@pytest.mark.parametrize("validate", ["m:1", None], ids=["m1", "mn"])
def test_a_join_answer_holds_no_device_buffer_until_it_is_bound(validate):
    rng = np.random.default_rng(1)
    k = 40
    build_k = np.arange(k) if validate else np.repeat(np.arange(k), 2)
    probe = Table({"k": rng.integers(0, k, 600), "x": rng.normal(size=600)})
    build = Table({"k": build_k, "w": rng.normal(size=build_k.size)})
    with QueryServer(workers=1) as srv:
        out = srv.run(Query(probe).stage().join(build, on="k",
                                                validate=validate))
    assert set(out.cols) == {"k", "x", "w"}
    for c in out.cols:
        assert _slot(out, c) is not None and _slot(out, c).value is None
        assert not out.cols[c].obj.data.flags.writeable
    got = Query(out).agg({"s": (out.col("w"), "+")})
    assert got["s"] == pytest.approx(float(out.cols["w"].obj.data.sum()))
    assert _slot(out, "w").value is not None
    assert _slot(out, "x").value is None  # not referenced by that query


def test_compiled_query_rebinds_a_table_from_its_resident_buffers():
    cols, cols2 = _columns(seed=2), _columns(seed=3)
    t, t2 = Table(cols), Table(cols2)
    cq = _revenue(t).compile()
    assert cq.run()["rev"] == pytest.approx(_want(cols), rel=1e-12)
    got, (enc,) = _encode_spans(lambda: cq.run(table=t2))
    assert got["rev"] == pytest.approx(_want(cols2), rel=1e-12)
    assert enc.counters == {"bytes": sum(a.nbytes for a in cols2.values()),
                            "resident": 0}
    got, (enc,) = _encode_spans(lambda: cq.run(table=t2))
    assert got["rev"] == pytest.approx(_want(cols2), rel=1e-12)
    assert enc.counters == {"bytes": 0, "resident": 3}
    low = cq.handle._low
    assert all(a is not _slot(t2, c).value
               for a in low.arrays for c in cols2)  # the handle keeps t's
    assert cq.run()["rev"] == pytest.approx(_want(cols), rel=1e-12)


def test_concurrent_first_binds_fill_one_buffer(monkeypatch):
    cols = _columns(n=2000, seed=4)
    t = Table(cols)
    encode = lazy.ArrayEncoder.encode
    calls = []

    def slow_encode(self, obj):
        calls.append(id(obj))
        time.sleep(0.01)
        return encode(self, obj)

    monkeypatch.setattr(lazy.ArrayEncoder, "encode", slow_encode)
    n = 12
    barrier = threading.Barrier(n)
    bound, errors = [None] * n, []

    def first_bind(i):
        try:
            prog = _revenue(t).program()
            barrier.wait(timeout=30)
            bound[i] = runtime.lower(prog).arrays
        except Exception as e:  # noqa: BLE001 - reported below
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=first_bind, args=(i,))
                   for i in range(n)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(th.is_alive() for th in threads)
    assert errors == []
    assert len(calls) == len(cols)
    filled = [_slot(t, c).value for c in cols]
    assert all(v is not None for v in filled)
    for arrays in bound:
        assert {id(a) for a in arrays} == {id(v) for v in filled}
