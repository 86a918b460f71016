"""weldtrace observability tests: the span tracer, Chrome-trace export,
EXPLAIN [ANALYZE], and the predicted-vs-measured cost ledger."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import obs
from repro.core.obs import ledger


@pytest.fixture(autouse=True)
def clean_tracer(tmp_path, monkeypatch):
    """Every test starts with tracing off, an empty span log, and a
    private ledger/autotune location."""
    monkeypatch.setenv("WELD_COST_LEDGER",
                       str(tmp_path / "cost_ledger.jsonl"))
    monkeypatch.setenv("WELD_AUTOTUNE_CACHE",
                       str(tmp_path / "autotune.json"))
    obs.disable()
    obs.clear()
    yield
    obs.disable()
    obs.clear()


# ---------------------------------------------------------------------------
# tracer core
# ---------------------------------------------------------------------------


def test_disabled_tracer_is_noop():
    assert not obs.enabled()
    sp = obs.span("anything", tag=1)
    assert sp is obs.NOOP
    sp.set("x", 2).count("y")
    with obs.span("nested"):
        pass
    obs.event("evt")
    assert obs.spans() == []


def test_spans_nest_and_time():
    obs.enable()
    with obs.span("outer", who="t") as outer:
        with obs.span("inner") as inner:
            inner.count("items", 3)
        with obs.span("inner2"):
            pass
    spans = obs.spans()
    assert [s.name for s in spans] == ["outer", "inner", "inner2"]
    assert outer.depth == 0 and inner.depth == 1
    assert outer.dur_ns >= inner.dur_ns >= 0
    # children sit inside the parent interval
    assert inner.start_ns >= outer.start_ns
    assert inner.start_ns + inner.dur_ns <= outer.start_ns + outer.dur_ns
    assert inner.counters == {"items": 3}
    assert outer.tags == {"who": "t"}


def test_mark_and_spans_since():
    obs.enable()
    with obs.span("before"):
        pass
    pos = obs.mark()
    with obs.span("after"):
        pass
    assert [s.name for s in obs.spans_since(pos)] == ["after"]


def test_event_is_instant_and_keeps_nesting():
    obs.enable()
    with obs.span("parent"):
        obs.event("tick", n=1)
        with obs.span("child"):
            pass
    spans = {s.name: s for s in obs.spans()}
    assert spans["tick"].dur_ns == 0
    assert spans["tick"].depth == 1
    assert spans["child"].depth == 1  # event didn't leak onto the stack


def test_env_enable(monkeypatch):
    from repro.core.obs import tracer

    monkeypatch.setenv(tracer.ENV_TRACE, "1")
    assert tracer._env_enabled()
    monkeypatch.setenv(tracer.ENV_TRACE, "0")
    assert not tracer._env_enabled()
    monkeypatch.setenv(tracer.ENV_TRACE, "false")
    assert not tracer._env_enabled()
    monkeypatch.delenv(tracer.ENV_TRACE)
    assert not tracer._env_enabled()


def test_chrome_export_valid_and_monotonic(tmp_path):
    obs.enable()
    with obs.span("a", kind="outer"):
        with obs.span("b"):
            obs.event("e")
    path = obs.dump_chrome(str(tmp_path / "trace.json"))
    data = json.loads(open(path).read())
    evs = data["traceEvents"]
    assert [e["name"] for e in evs] == ["a", "b", "e"]
    for e in evs:
        assert e["ph"] == "X"
        assert e["ts"] >= 0 and e["dur"] >= 0
    a, b = evs[0], evs[1]
    assert b["ts"] >= a["ts"]
    assert b["ts"] + b["dur"] <= a["ts"] + a["dur"]
    assert evs[0]["args"]["kind"] == "outer"


def test_format_tree_renders_nesting():
    obs.enable()
    with obs.span("root", q=1):
        with obs.span("leaf"):
            pass
    txt = obs.format_tree()
    lines = txt.splitlines()
    assert lines[0].startswith("root") and "q=1" in lines[0]
    assert lines[1].startswith("  leaf")


def test_unserializable_tag_survives_chrome_export():
    obs.enable()
    with obs.span("s", obj=object()):
        pass
    data = obs.to_chrome()
    json.dumps(data)  # must not raise
    assert "object" in data["traceEvents"][0]["args"]["obj"]


# ---------------------------------------------------------------------------
# pipeline integration: spans appear through runtime/passes/planner
# ---------------------------------------------------------------------------


def _join_tables(n=4096, k=64, fanout=4):
    from repro.frames import weldrel

    rng = np.random.RandomState(7)
    rkey = np.repeat(np.arange(k, dtype=np.int64), fanout)
    right = weldrel.Table({"key": rkey, "rate": rng.rand(rkey.size)})
    left = weldrel.Table({
        "key": rng.randint(0, 2 * k, n).astype(np.int64),
        "price": rng.rand(n),
    })
    return weldrel, left, right


def test_evaluate_emits_pipeline_spans():
    from repro.core import runtime
    from repro.frames import weldnp

    runtime.clear_cache()
    obs.enable()
    x = weldnp.array(np.arange(1000, dtype=np.float64))
    ((x + 1.0) * 2.0).evaluate()
    names = [s.name for s in obs.spans()]
    for want in ("weld.evaluate", "encode", "cache.lookup", "optimize",
                 "pass.fusion", "jit_compile", "execute", "decode"):
        assert want in names, (want, names)
    # second run: cache hit — compile-side spans absent, execute present
    pos = obs.mark()
    ((x + 1.0) * 2.0).evaluate()
    names2 = [s.name for s in obs.spans_since(pos)]
    assert "execute" in names2 and "optimize" not in names2
    hit = [s for s in obs.spans_since(pos) if s.name == "cache.lookup"]
    assert hit and hit[0].tags["hit"] is True


# ---------------------------------------------------------------------------
# EXPLAIN [ANALYZE]
# ---------------------------------------------------------------------------


def test_explain_reports_plan_without_tracing():
    weldrel, left, right = _join_tables()
    rep = weldrel.Query(left).explain().join(right, on="key",
                                             kernelize="always")
    assert not obs.enabled()  # explain() alone must not flip tracing on
    txt = rep.render()
    assert "EXPLAIN weldrel.join" in txt
    assert "kernel[group_build]" in txt
    assert "routed kernels" in txt
    assert rep.spans == []
    kernels = {r["kernel"] for r in rep.kernels()}
    assert {"group_build", "group_probe"} <= kernels
    # the report still carries the operator's result
    assert "price" in rep.result.cols


def test_explain_analyze_mn_join_measures_group_kernels():
    """Acceptance: explain(analyze=True) on a kernelized m:n join shows
    group_build AND group_probe launches with predicted + measured."""
    weldrel, left, right = _join_tables()
    rep = weldrel.Query(left).explain(analyze=True).join(
        right, on="key", kernelize="always")
    assert not obs.enabled()  # restored afterwards
    rows = {r["kernel"]: r for r in rep.kernel_spans()}
    for kern in ("group_build", "group_probe"):
        assert kern in rows, rows
        assert rows[kern]["predicted_ns"], rows[kern]
        assert rows[kern]["measured_ns"], rows[kern]
        assert rows[kern]["ratio"] > 0
    txt = rep.render()
    assert "EXPLAIN ANALYZE" in txt
    assert "predicted vs measured" in txt
    assert "span tree" in txt


def test_explain_rejects_eager_tables():
    from repro.frames import weldrel

    t = weldrel.Table({"a": np.arange(4)}, eager=True)
    with pytest.raises(ValueError, match="lazy"):
        weldrel.Query(t).explain().agg({"s": (t.col("a"), "+")})


def test_group_agg_accepts_collect_stats():
    weldrel, left, _ = _join_tables()
    st: dict = {}
    out = weldrel.Query(left).group_agg(
        [left.col("key")], {"s": (left.col("price"), "+")},
        capacity=256, kernelize="auto", collect_stats=st)
    assert out and "loops.before" in st


# ---------------------------------------------------------------------------
# cost ledger + report CLI
# ---------------------------------------------------------------------------


def test_ledger_roundtrip_and_summary(tmp_path):
    path = str(tmp_path / "l.jsonl")
    for i in range(3):
        rec = ledger.record("k1", "float64", 5000, predicted_ns=1000,
                            measured_ns=2000 + i, path=path)
        assert rec["bucket"] == 8192
    ledger.record("k2", "int64", 100, predicted_ns=None,
                  measured_ns=500, path=path)
    with open(path, "a") as f:
        f.write("{corrupt json\n")  # truncated tail must be skipped
    recs = ledger.read(path)
    assert len(recs) == 4
    rows = ledger.summarize(recs)
    by_kernel = {r["kernel"]: r for r in rows}
    assert by_kernel["k1"]["calls"] == 3
    assert by_kernel["k1"]["ratio"] == pytest.approx(2.0, abs=0.01)
    assert by_kernel["k1"]["log2_err"] == pytest.approx(1.0, abs=0.01)
    assert by_kernel["k2"]["ratio"] is None  # no prediction recorded
    txt = ledger.format_report(rows)
    assert "k1" in txt and "k2" in txt


def test_traced_execution_appends_ledger(tmp_path):
    weldrel, left, right = _join_tables()
    path = os.environ["WELD_COST_LEDGER"]
    weldrel.Query(left).explain(analyze=True).join(right, on="key",
                                                   kernelize="always")
    recs = ledger.read(path)
    kernels = {r["kernel"] for r in recs}
    assert {"group_build", "group_probe"} <= kernels
    for r in recs:
        assert r["measured_ns"] > 0
        assert r["bucket"] >= 1024


def test_cost_report_cli(tmp_path):
    path = str(tmp_path / "l.jsonl")
    ledger.record("group_probe", "float64", 4096, predicted_ns=1500,
                  measured_ns=4500, path=path)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "cost_report.py"),
         "--ledger", path, "--json"],
        capture_output=True, text=True, check=True,
    )
    data = json.loads(out.stdout)
    assert data["records"] == 1
    assert data["groups"][0]["kernel"] == "group_probe"
    assert data["groups"][0]["ratio"] == pytest.approx(3.0, abs=0.01)


def test_repro_obs_alias():
    import repro.obs as topobs

    assert topobs.enable is obs.enable
    assert topobs.ledger is ledger


# ---------------------------------------------------------------------------
# request ids, parents, recorded intervals, the profiler's clock
# ---------------------------------------------------------------------------


def test_request_ids_and_parents_propagate():
    obs.enable()
    with obs.span("outside") as outside:
        pass
    with obs.request("req.a") as a:
        with obs.span("child") as child:
            with obs.span("grandchild") as grand:
                obs.event("tick")
    with obs.request("req.b") as b:
        pass
    assert outside.req is None and outside.parent is None
    assert a.req is not None and b.req is not None and a.req != b.req
    assert a.parent is None
    assert child.req == grand.req == a.req
    assert child.parent == a.sid and grand.parent == child.sid
    tick = [s for s in obs.spans() if s.name == "tick"][0]
    assert tick.parent == grand.sid and tick.req == a.req
    assert len({s.sid for s in obs.spans()}) == len(obs.spans())


def test_record_files_a_finished_interval():
    import time

    obs.enable()
    t0 = time.perf_counter_ns()
    with obs.request("req") as r:
        rec = obs.record("waited", t0, r.start_ns, req=r.req, why="test")
    assert rec.dur_ns == r.start_ns - t0 >= 0
    assert rec.req == r.req and rec.parent is None
    assert rec.tags == {"why": "test"}
    assert rec in obs.spans()
    obs.disable()
    assert obs.record("off", 0, 1) is obs.NOOP


def test_chrome_export_carries_req_and_parent():
    obs.enable()
    with obs.request("root") as root:
        with obs.span("leaf"):
            pass
    evs = obs.to_chrome()["traceEvents"]
    by = {e["name"]: e["args"] for e in evs}
    assert by["root"]["req"] == root.req and "parent" not in by["root"]
    assert by["leaf"]["req"] == root.req
    assert by["leaf"]["parent"] == by["root"]["id"] == root.sid


def test_disabled_tracing_annotates_nothing_and_blocks_on_no_input(
        monkeypatch):
    """Tracing off: no profiler annotation is made and the runtime never
    waits on the inputs' upload; tracing on, the ``upload`` span does."""
    import jax

    from repro.core import runtime
    from repro.core.lazy import build_program
    from repro.core.obs import tracer
    from repro.frames import weldnp

    made, blocked = [], []
    real_block = jax.block_until_ready

    class Counting(tracer.TraceAnnotation):
        def __init__(self, *a, **kw):
            made.append(a)
            super().__init__(*a, **kw)

    def block(x):
        blocked.append(x)
        return real_block(x)

    monkeypatch.setattr(tracer, "TraceAnnotation", Counting)
    monkeypatch.setattr(jax, "block_until_ready", block)
    x = weldnp.array(np.arange(64, dtype=np.float64))
    handle = runtime.compile_program(build_program((x + 1.0).obj))
    inputs = handle._low.arrays
    blocked.clear()
    handle.run()
    with obs.request("r"):
        obs.span("s")
        obs.event("e")
    assert made == []
    assert not any(b is inputs for b in blocked)
    obs.enable()
    handle.run()
    assert any(b is inputs for b in blocked)
    assert ("upload",) in made and ("execute",) in made


def test_encode_and_fetch_count_their_bytes_exactly():
    import jax

    from repro.core import runtime
    from repro.frames.weldrel import Query, Table

    rng = np.random.default_rng(3)
    t = Table({"k": rng.integers(0, 50, 3000), "x": rng.normal(size=3000)})
    b = Table({"k": np.arange(50), "w": rng.normal(size=50)})
    prog = Query(t).stage().join(b, on="k", validate="m:1").program()
    want_in = sum(np.asarray(d).nbytes for _, _, d in prog.inputs.values())
    obs.enable()
    pos = obs.mark()
    handle = runtime.compile_program(prog)
    handle.run()
    spans = obs.spans_since(pos)
    out = handle._jitted(*handle._low.arrays)
    want_out = sum(x.nbytes for x in jax.tree_util.tree_leaves(out))
    encode = [s for s in spans if s.name == "encode"]
    fetch = [s for s in spans if s.name == "fetch"]
    assert len(encode) == 1 and len(fetch) == 1
    assert encode[0].counters["bytes"] == want_in
    assert fetch[0].counters["bytes"] == want_out
    decode = [s for s in spans if s.name == "decode"][0]
    assert fetch[0].parent == decode.sid


def test_spans_land_in_the_profilers_host_plane(tmp_path):
    """With weldtrace on under ``jax.profiler``, every span a served
    request opens is an event of the trace's host plane, of the same
    length and nesting, and ``bench.trace.label`` names a gap by one."""
    import jax

    from repro.core.serve import QueryServer
    from repro.frames.weldrel import Query, Table

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from bench import trace

    rng = np.random.default_rng(5)
    t = Table({"k": rng.integers(0, 100, 20000), "x": rng.normal(size=20000)})
    b = Table({"k": np.arange(100), "w": rng.normal(size=100)})
    with QueryServer(workers=1) as srv:
        srv.run(Query(t).stage().join(b, on="k", validate="m:1"))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        obs.enable()
        pos = obs.mark()
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            srv.run(Query(t).stage().join(b, on="k", validate="m:1"))
        finally:
            jax.profiler.stop_trace()
            obs.disable()
    # events are instants, and the queue's record began on another thread
    spans = [s for s in obs.spans_since(pos)
             if s.dur_ns and s.name != "serve.queue"]
    host = trace.load(str(tmp_path))["host"]
    by_name: dict = {}
    for ev in sorted(host, key=lambda e: e[1]):
        by_name.setdefault(ev[0], []).append(ev)
    seen: dict = {}
    where = {}
    for sp in spans:
        evs = by_name.get(sp.name, [])
        k = seen.get(sp.name, 0)
        assert k < len(evs), f"{sp.name} missing from the host plane"
        seen[sp.name] = k + 1
        _, start, dur = evs[k]
        assert abs(dur - sp.dur_ns) <= max(0.05 * sp.dur_ns, 100_000), \
            (sp.name, dur, sp.dur_ns)
        where[sp.sid] = (start, start + dur)
    for sp in spans:
        if sp.parent in where:
            lo, hi = where[sp.parent]
            s, e = where[sp.sid]
            assert lo <= s and e <= hi, sp.name
    names = {sp.name for sp in spans}
    assert {"serve.request", "encode", "weld.compile", "weld.run", "upload",
            "execute", "decode", "fetch", "frames.finalize"} <= names
    fin = [sp for sp in spans if sp.name == "frames.finalize"][0]
    assert trace.label(where[fin.sid], host) == "frames.finalize"
