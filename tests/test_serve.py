"""weldserve: AOT staging, the single-flight LRU compile cache, the
concurrent QueryServer, ledger calibration, and the cache/ledger
lifecycle bugfixes (stale-key refile leak, admission-contract degrade,
torn-write ledger reads)."""
import json
import os
import threading

import numpy as np
import pytest

from repro.core import obs, runtime
from repro.core.analysis import bounds as _bounds
from repro.core.errors import ResourceError
from repro.core.kernelplan import autotune, calibrate, quarantine
from repro.core.lazy import Evaluate
from repro.core.obs import ledger
from repro.core.serve import QueryServer
from repro.frames import weldnp
from repro.frames.weldrel import Query, Table, _host


@pytest.fixture(autouse=True)
def hermetic(tmp_path, monkeypatch):
    """Fresh caches + ledger + health file per test: no cross-test
    tuning state, no calibration bleed from a developer's real ledger."""
    monkeypatch.setenv("WELD_AUTOTUNE_CACHE", str(tmp_path / "autotune.json"))
    monkeypatch.setenv("WELD_COST_LEDGER", str(tmp_path / "ledger.jsonl"))
    monkeypatch.setenv(quarantine.ENV_FILE, str(tmp_path / "health.json"))
    quarantine.clear(disk=False)
    autotune.clear_cache(disk=False)
    calibrate.invalidate()
    runtime.clear_cache()
    yield
    runtime.clear_cache()
    calibrate.invalidate()
    autotune.clear_cache(disk=False)
    quarantine.clear(disk=False)


def _tables(n=20000, k=100, seed=0):
    rng = np.random.default_rng(seed)
    probe = {"k": rng.integers(0, k, n), "x": rng.normal(size=n)}
    build = {"k": np.arange(k), "w": rng.normal(size=k)}
    return probe, build


def _oracle_join(probe, build, **kw):
    return Query(Table(dict(probe), eager=True)).join(
        Table(dict(build), eager=True), **kw)


def _assert_tables_equal(got: Table, want: Table):
    assert sorted(got.cols) == sorted(want.cols)
    for c in got.cols:
        np.testing.assert_array_equal(
            np.asarray(_host(got.cols[c])), np.asarray(_host(want.cols[c])),
            err_msg=f"column {c}")


# ---------------------------------------------------------------------------
# staged AOT handles
# ---------------------------------------------------------------------------


def test_compiled_query_join_matches_oracle():
    probe, build = _tables()
    cq = Query(Table(dict(probe))).compile().join(
        Table(dict(build)), on="k", validate="m:1")
    _assert_tables_equal(cq.run(), _oracle_join(probe, build, on="k",
                                                validate="m:1"))
    assert cq.stats["cache.misses"] >= 1
    assert "compile_ms" in cq.stats


def test_compiled_query_agg_and_group_agg():
    probe, _ = _tables(n=5000, k=8)
    t = Table(dict(probe))
    cq = Query(t).compile().agg({"s": (t.col("x"), "+"),
                                 "m": (t.col("x"), "max")})
    out = cq.run()
    assert out["s"] == pytest.approx(probe["x"].sum())
    assert out["m"] == pytest.approx(probe["x"].max())

    t2 = Table(dict(probe))
    cg = Query(t2).compile().group_agg(
        [t2.col("k")], {"s": (t2.col("x"), "+")})
    got = cg.run()
    te = Table(dict(probe), eager=True)
    want = Query(te).group_agg([te.col("k")], {"s": (te.col("x"), "+")})
    assert set(got) == set(want)
    for key in want:
        assert got[key][0] == pytest.approx(want[key][0])
        assert got[key][1] == want[key][1]


def test_compiled_query_rebind_zero_recompiles():
    probe, build = _tables()
    cq = Query(Table(dict(probe))).compile().join(
        Table(dict(build)), on="k", validate="m:1")
    cq.run()
    misses = runtime.cache_stats()["cache.misses"]

    probe2, build2 = _tables(seed=7)
    out = cq.run(table=Table(dict(probe2)), right=Table(dict(build2)))
    assert runtime.cache_stats()["cache.misses"] == misses, \
        "re-binding same-shape inputs must not recompile"
    _assert_tables_equal(out, _oracle_join(probe2, build2, on="k",
                                           validate="m:1"))


def test_compiled_query_rebind_shape_mismatch_raises():
    probe, build = _tables()
    cq = Query(Table(dict(probe))).compile().join(
        Table(dict(build)), on="k", validate="m:1")
    smaller, _ = _tables(n=123)
    with pytest.raises(ValueError, match="signature"):
        cq.run(table=Table(dict(smaller)))
    with pytest.raises(KeyError, match="alias"):
        cq.run(nonsense=Table(dict(probe)))


def test_stage_requires_lazy_table():
    probe, build = _tables(n=100, k=10)
    with pytest.raises(ValueError, match="lazy"):
        Query(Table(dict(probe), eager=True)).stage().join(
            Table(dict(build), eager=True), on="k")


def test_explain_carries_cost_source():
    probe, build = _tables()
    cq = Query(Table(dict(probe))).compile().join(
        Table(dict(build)), on="k", validate="m:1")
    rendered = cq.explain().render()
    assert "source=roofline" in rendered


# ---------------------------------------------------------------------------
# concurrent serving
# ---------------------------------------------------------------------------


def test_concurrent_mixed_queries_byte_identical_single_flight():
    """N worker threads x mixed same-shape/different-shape joins and
    group-bys: byte-identical to the serial oracle, exactly ONE compile
    per distinct (plan, shape) key."""
    pa, ba = _tables(n=20000, k=100, seed=1)
    pb, bb = _tables(n=7000, k=50, seed=2)

    def staged_join_a():
        return Query(Table(dict(pa))).stage().join(
            Table(dict(ba)), on="k", validate="m:1")

    def staged_join_b():  # different shape -> distinct key
        return Query(Table(dict(pb))).stage().join(
            Table(dict(bb)), on="k", validate="m:1")

    def staged_join_mn():  # m:n build side (duplicate keys)
        dup = {"k": np.concatenate([ba["k"], ba["k"]]),
               "w": np.concatenate([ba["w"], ba["w"] + 1.0])}
        return Query(Table(dict(pa))).stage().join(
            Table(dict(dup)), on="k")

    def staged_group():
        t = Table(dict(pa))
        return Query(t).stage().group_agg(
            [t.col("k")], {"s": (t.col("x"), "+")})

    makers = [staged_join_a, staged_join_b, staged_join_mn, staged_group]
    # serial oracles (eager paths / fresh-cache lazy for group_agg)
    dup = {"k": np.concatenate([ba["k"], ba["k"]]),
           "w": np.concatenate([ba["w"], ba["w"] + 1.0])}
    te = Table(dict(pa), eager=True)
    oracles = [
        _oracle_join(pa, ba, on="k", validate="m:1"),
        _oracle_join(pb, bb, on="k", validate="m:1"),
        _oracle_join(pa, dup, on="k"),
        Query(te).group_agg([te.col("k")], {"s": (te.col("x"), "+")}),
    ]

    runtime.clear_cache()
    reqs = [makers[i % len(makers)]() for i in range(24)]
    with QueryServer(workers=6) as srv:
        results = [f.result() for f in [srv.submit(q) for q in reqs]]
    st = srv.stats()

    distinct = len(makers)
    assert st["cache.misses"] == distinct, st
    assert st["cache.hits"] + st["cache.waits"] == len(reqs) - distinct, st
    assert runtime.cache_size() == distinct
    assert st["serve.completed"] == len(reqs)
    assert st["serve.shed"] == 0

    for i, got in enumerate(results):
        want = oracles[i % len(makers)]
        if isinstance(got, Table):
            _assert_tables_equal(got, want)
        else:
            assert set(got) == set(want)
            for key in want:
                np.testing.assert_allclose(
                    np.asarray(got[key], dtype=float),
                    np.asarray(want[key], dtype=float))


@pytest.fixture
def traced():
    obs.disable()
    obs.clear()
    obs.enable()
    yield
    obs.disable()
    obs.clear()


def test_request_spans_share_req_and_nest_under_serve_request(traced):
    """Every span a served request opens on its worker carries the
    request's id and a parent inside it; ``serve.queue`` spans the
    wait from ``submit`` on the caller's thread to the worker."""
    probe, build = _tables(n=5000, k=20)
    with QueryServer(workers=2) as srv:
        futs = [srv.submit(Query(Table(dict(probe))).stage().join(
            Table(dict(build)), on="k", validate="m:1")) for _ in range(4)]
        for f in futs:
            f.result()
    spans = obs.spans()
    reqs = [sp for sp in spans if sp.name == "serve.request"]
    assert len(reqs) == 4 and len({sp.req for sp in reqs}) == 4
    assert all(sp.tags["op"] == "join" for sp in reqs)
    by_sid = {sp.sid: sp for sp in spans}
    for req in reqs:
        mine = [sp for sp in spans if sp.req == req.req]
        names = {sp.name for sp in mine}
        assert {"serve.queue", "encode", "weld.compile", "weld.run",
                "upload", "execute", "decode", "fetch",
                "frames.finalize"} <= names, names
        queue = [sp for sp in mine if sp.name == "serve.queue"]
        assert len(queue) == 1 and queue[0].parent is None
        assert queue[0].start_ns + queue[0].dur_ns == req.start_ns
        for sp in mine:
            if sp is req or sp is queue[0]:
                continue
            up = sp
            while up.parent is not None and up is not req:
                up = by_sid[up.parent]
                assert up.req == req.req and up.tid == req.tid
            assert up is req, sp.name
    run = [sp for sp in spans if sp.name == "weld.run"][0]
    chain = {sp.name: sp for sp in spans
             if sp.req == run.req and sp.parent is not None}
    assert chain["fetch"].parent == chain["decode"].sid
    assert chain["decode"].parent == run.sid
    assert chain["frames.finalize"].parent == by_sid[run.parent].sid


def test_traced_serving_never_replays_nor_writes_the_ledger(traced):
    """Tracing on (as ``WELD_TRACE=1`` sets it) re-executes nothing
    outside ``explain(analyze=True)``: a served kernelized query has no
    eager replay and leaves the cost ledger empty."""
    probe, build = _tables(n=4000, k=32)
    dup = {"k": np.concatenate([build["k"], build["k"]]),
           "w": np.concatenate([build["w"], build["w"] + 1.0])}
    with QueryServer(workers=1, kernelize="always") as srv:
        for _ in range(2):
            srv.run(Query(Table(dict(probe))).stage().join(
                Table(dict(dup)), on="k"))
    names = {sp.name for sp in obs.spans()}
    assert any(sp.name == "kernelplan" and sp.tags.get("matched")
               for sp in obs.spans())  # the served plan is kernelized
    assert "execute" in names
    assert "measure.replay" not in names
    assert not any(n.startswith("kernel.") for n in names)
    assert ledger.read(os.environ["WELD_COST_LEDGER"]) == []
    rep = Query(Table(dict(probe))).explain(analyze=True).join(
        Table(dict(dup)), on="k", kernelize="always")
    assert rep.kernel_spans()
    assert ledger.read(os.environ["WELD_COST_LEDGER"])


def test_single_flight_one_compile_under_thundering_herd():
    probe, build = _tables()
    reqs = [Query(Table(dict(probe))).stage().join(
        Table(dict(build)), on="k", validate="m:1") for _ in range(16)]
    runtime.clear_cache()
    start = threading.Barrier(8)

    outs = []
    errs = []
    lock = threading.Lock()

    with QueryServer(workers=8) as srv:
        def fire(q):
            start.wait()
            try:
                r = srv.run(q)
                with lock:
                    outs.append(r)
            except BaseException as e:  # pragma: no cover
                with lock:
                    errs.append(e)

        threads = [threading.Thread(target=fire, args=(q,)) for q in reqs]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
    assert not errs
    st = runtime.cache_stats()
    assert st["cache.misses"] == 1, st
    assert st["cache.hits"] + st["cache.waits"] == len(reqs) - 1, st
    oracle = _oracle_join(probe, build, on="k", validate="m:1")
    for o in outs:
        _assert_tables_equal(o, oracle)


def test_cache_eviction_bounded(monkeypatch):
    monkeypatch.setenv(runtime.ENV_CACHE_MAX, "2")
    runtime.clear_cache()
    for i in range(5):  # distinct probe shapes -> distinct cache keys
        probe, build = _tables(n=1000 + 100 * i, k=20)
        Query(Table(dict(probe))).join(
            Table(dict(build)), on="k", validate="m:1")
    st = runtime.cache_stats()
    assert runtime.cache_size() <= 2
    assert st["cache.evictions"] >= 3
    assert st["cache.misses"] == 5


def test_serve_sheds_with_typed_resource_error():
    probe, build = _tables()
    staged = Query(Table(dict(probe))).stage().join(
        Table(dict(build)), on="k", validate="m:1")
    runtime.clear_cache()
    with QueryServer(workers=2, memory_limit=64) as srv:
        fut = srv.submit(staged)
        with pytest.raises(ResourceError, match="at admission"):
            fut.result()
        st = srv.stats()
    assert st["serve.shed"] == 1
    assert st["serve.errors"] == 0, "a shed is not an error"
    assert runtime.cache_size() == 0, \
        "a shed plan must never enter the compile cache"


def test_serve_accepts_weldobject():
    a = weldnp.array(np.arange(1000, dtype=np.float64))
    b = (a * 2.0) + 1.0
    with QueryServer(workers=2) as srv:
        out = srv.run(b.obj)
    np.testing.assert_allclose(
        np.asarray(out), np.arange(1000, dtype=np.float64) * 2.0 + 1.0)


# ---------------------------------------------------------------------------
# satellite bugfix: stale-key refile leak
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_first_encounter_tuning_files_one_entry():
    """First-encounter autotuning refreshes the fingerprint mid-compile;
    the executable must be re-filed under the refreshed key ONLY — the
    pre-tuning key can never match again and caching it leaked one dead
    entry per tuned plan."""
    rng = np.random.default_rng(0)
    x = weldnp.array(rng.normal(size=4096))
    runtime.clear_cache()
    st = {}
    Evaluate((x * 2.0).sum().obj, kernelize="always",
             kernel_impl="interpret", collect_stats=st)
    assert st.get("kernelplan", {}).get("autotune"), \
        "expected a first-encounter tuning event"
    assert runtime.cache_size() == 1, \
        "refile must evict the stale pre-tuning key (leak: size grew to 2)"
    misses = runtime.cache_stats()["cache.misses"]
    res = Evaluate((x * 2.0).sum().obj, kernelize="always",
                   kernel_impl="interpret")
    assert res.from_cache
    assert runtime.cache_stats()["cache.misses"] == misses
    assert runtime.cache_size() == 1


# ---------------------------------------------------------------------------
# satellite bugfix: admission must degrade, not die
# ---------------------------------------------------------------------------


def test_admission_certificate_failure_degrades(monkeypatch):
    """The bounds contract says analysis failures only disable
    admission — that must cover certificate *evaluation* (peak/
    certificate/builder_lines), not just analyze()."""
    def boom(self, shapes=None):
        raise RuntimeError("injected: certificate evaluation fault")

    monkeypatch.setattr(_bounds.BoundsReport, "peak", boom)
    probe, build = _tables(n=2000, k=20)
    st = {}
    out = Query(Table(dict(probe))).join(
        Table(dict(build)), on="k", validate="m:1",
        memory_limit=1 << 31, collect_stats=st)
    _assert_tables_equal(out, _oracle_join(probe, build, on="k",
                                           validate="m:1"))
    assert "injected" in st.get("bounds.degraded", "")
    assert "bounds.certificate" not in st
    assert "bounds.admitted" not in st


# ---------------------------------------------------------------------------
# satellite bugfix: ledger torn writes + bare-filename path
# ---------------------------------------------------------------------------


def test_ledger_read_skips_torn_tail_with_warning(tmp_path):
    p = tmp_path / "torn.jsonl"
    good = {"kernel": "hash_probe", "dtype": "float64", "n": 4096,
            "bucket": 4096, "predicted_ns": 1000, "measured_ns": 1200}
    with open(p, "w") as f:
        f.write(json.dumps(good) + "\n")
        f.write(json.dumps(good) + "\n")
        f.write(json.dumps(good)[:17])  # killed mid-append
    with pytest.warns(RuntimeWarning, match=r"torn\.jsonl.*line 3"):
        recs = ledger.read(str(p))
    assert len(recs) == 2


def test_ledger_path_bare_autotune_filename_is_absolute(monkeypatch):
    monkeypatch.delenv("WELD_COST_LEDGER", raising=False)
    monkeypatch.setenv("WELD_AUTOTUNE_CACHE", "autotune.json")
    p = ledger.ledger_path()
    assert os.path.isabs(p)
    assert os.path.dirname(p) == os.getcwd()


# ---------------------------------------------------------------------------
# calibration overlay
# ---------------------------------------------------------------------------


def _seed_ledger(kernel, dtype, n, measured_ns, count=3):
    for _ in range(count):
        ledger.record(kernel, dtype, n, None, measured_ns)
    calibrate.invalidate()


def test_calibrate_overlay_switches_source_and_routing():
    from repro.core.kernelplan import cost
    from repro.core.kernelplan import registry as reg

    spec = reg.get("filter_reduce_sum")
    meta = {"kernel": "filter_reduce_sum", "n": 200000, "cols": 1,
            "n_aggs": 1, "ops": 1, "dtype": "float64"}
    base = cost.estimate(spec, meta)
    assert base.source == "roofline"
    assert "source=roofline" in base.why

    # a huge measured median must flip the gate to reject
    _seed_ledger("filter_reduce_sum", "float64", 200000, int(5e9))
    est = cost.estimate(spec, meta)
    assert est.source == "measured"
    assert "source=measured" in est.why
    assert not est.routed
    assert est.kernel_s == pytest.approx(5.0)

    # a tiny one must route
    os.remove(ledger.ledger_path())
    calibrate.invalidate()
    _seed_ledger("filter_reduce_sum", "float64", 200000, 10)
    est = cost.estimate(spec, meta)
    assert est.source == "measured" and est.routed


def test_calibrate_needs_min_samples_and_honors_disable(monkeypatch):
    from repro.core.kernelplan import cost
    from repro.core.kernelplan import registry as reg

    spec = reg.get("filter_reduce_sum")
    meta = {"kernel": "filter_reduce_sum", "n": 200000, "cols": 1,
            "n_aggs": 1, "ops": 1, "dtype": "float64"}
    _seed_ledger("filter_reduce_sum", "float64", 200000, int(5e9), count=2)
    est = cost.estimate(spec, meta)
    assert est.source == "roofline", "2 samples < min_samples must stay roofline"

    _seed_ledger("filter_reduce_sum", "float64", 200000, int(5e9), count=1)
    assert cost.estimate(spec, meta).source == "measured"

    monkeypatch.setenv("WELD_CALIBRATE", "0")
    assert cost.estimate(spec, meta).source == "roofline"


def test_quarantined_entries_keep_exact_why(monkeypatch):
    """Calibration must not touch the quarantine path: its why string is
    load-bearing (exact-match asserted by the recovery tests)."""
    monkeypatch.setattr(
        quarantine, "is_quarantined",
        lambda kernel, impl=None, dtype=None, n=None: True)
    # seed medians so the overlay WOULD fire if it saw these candidates
    _seed_ledger("hash_probe", "int64", 2000, 10)
    probe, build = _tables(n=2000, k=20)
    st = {}
    runtime.clear_cache()
    Query(Table(dict(probe))).join(Table(dict(build)), on="k",
                                   validate="m:1", collect_stats=st)
    costs = st.get("kernelplan", {}).get("costs", [])
    qrows = [c for c in costs if c.get("why") == "quarantined"]
    assert qrows and all(not c["routed"] for c in qrows)
