#!/usr/bin/env python
"""CI serving smoke (run from tools/ci.sh).

Drives the weldserve stack end to end and asserts the §7.8 economics
actually hold under concurrency:

* 8 worker threads x 32 mixed staged queries (two join shapes, an m:n
  variant, a group-by): results byte-identical to the serial eager
  oracle, exactly ONE compile per distinct (plan, shape) key proven via
  the ``cache.*`` counters, ``cache_size()`` bounded by
  ``WELD_COMPILE_CACHE_MAX``;
* AOT re-binding: a ``CompiledQuery.run(**tables)`` against fresh
  same-shape tables spends zero additional compiles;
* admission: a provably over-budget query sheds with a typed
  ``ResourceError`` and never enters the compile cache;
* calibration: ledger medians seeded from an authentic EXPLAIN ANALYZE
  run overlay the roofline estimates — the recompiled plan's ``explain()``
  shows ``source=measured`` provenance WITHOUT flipping any routing
  decision (the seeded medians equal the roofline predictions).

State is confined to a temp directory (autotune cache + ledger) so the
smoke never pollutes — or depends on — the developer's caches.
"""
from __future__ import annotations

import os
import sys
import tempfile

_TOOLS = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(_TOOLS, "..", "src"))

_td = tempfile.mkdtemp(prefix="weld-serve-smoke-")
os.environ["WELD_AUTOTUNE_CACHE"] = os.path.join(_td, "autotune.json")
os.environ["WELD_COST_LEDGER"] = os.path.join(_td, "cost_ledger.jsonl")
os.environ["WELD_COMPILE_CACHE_MAX"] = "8"

import numpy as np  # noqa: E402

from repro.core import runtime  # noqa: E402
from repro.core.errors import ResourceError  # noqa: E402
from repro.core.kernelplan import calibrate  # noqa: E402
from repro.core.obs import ledger  # noqa: E402
from repro.core.serve import QueryServer  # noqa: E402
from repro.frames.weldrel import Query, Table, _host  # noqa: E402


def _tables(n, k, seed):
    rng = np.random.RandomState(seed)
    probe = {"k": rng.randint(0, k, n).astype(np.int64),
             "x": rng.rand(n)}
    build = {"k": np.arange(k, dtype=np.int64), "w": rng.rand(k)}
    return probe, build


def _assert_tables_equal(got, want, label):
    assert sorted(got.cols) == sorted(want.cols), label
    for c in got.cols:
        np.testing.assert_array_equal(
            np.asarray(_host(got.cols[c])), np.asarray(_host(want.cols[c])),
            err_msg=f"{label}: column {c}")


def main() -> int:
    pa, ba = _tables(n=20000, k=100, seed=1)
    pb, bb = _tables(n=7000, k=50, seed=2)
    dup = {"k": np.concatenate([ba["k"], ba["k"]]),
           "w": np.concatenate([ba["w"], ba["w"] + 1.0])}

    makers = [
        lambda: Query(Table(dict(pa))).stage().join(
            Table(dict(ba)), on="k", validate="m:1"),
        lambda: Query(Table(dict(pb))).stage().join(
            Table(dict(bb)), on="k", validate="m:1"),
        lambda: Query(Table(dict(pa))).stage().join(
            Table(dict(dup)), on="k"),
        lambda: _staged_group(pa),
    ]

    def _staged_group(cols):
        t = Table(dict(cols))
        return Query(t).stage().group_agg(
            [t.col("k")], {"s": (t.col("x"), "+")})

    def _eager_join(probe, build, **kw):
        return Query(Table(dict(probe), eager=True)).join(
            Table(dict(build), eager=True), **kw)

    te = Table(dict(pa), eager=True)
    oracles = [
        _eager_join(pa, ba, on="k", validate="m:1"),
        _eager_join(pb, bb, on="k", validate="m:1"),
        _eager_join(pa, dup, on="k"),
        Query(te).group_agg([te.col("k")], {"s": (te.col("x"), "+")}),
    ]

    # -- admission shedding (first: cold cache, empty ledger) ------------
    runtime.clear_cache()
    with QueryServer(workers=2, memory_limit=64) as tiny:
        try:
            tiny.run(makers[0]())
            raise AssertionError("64-byte budget must shed the join")
        except ResourceError as e:
            assert "admission" in str(e), e
    assert tiny.stats()["serve.shed"] == 1
    assert runtime.cache_size() == 0, "a shed plan must never be cached"
    print("admission: over-budget query shed with typed ResourceError, "
          "nothing cached")

    # -- concurrent serving: 8 threads x 32 mixed queries ----------------
    runtime.clear_cache()
    n_req, distinct = 32, len(makers)
    reqs = [makers[i % distinct]() for i in range(n_req)]
    with QueryServer(workers=8) as srv:
        futs = [srv.submit(q) for q in reqs]
        results = [f.result() for f in futs]
    st = srv.stats()
    assert st["cache.misses"] == distinct, \
        f"single-flight broken: {distinct} plans, {st['cache.misses']} compiles"
    assert st["cache.hits"] + st["cache.waits"] == n_req - distinct, st
    assert runtime.cache_size() <= 8, st
    assert st["serve.completed"] == n_req and st["serve.shed"] == 0, st
    for i, got in enumerate(results):
        want = oracles[i % distinct]
        if isinstance(got, Table):
            _assert_tables_equal(got, want, f"request {i}")
        else:  # group-by dict: float sums may differ in the last ulp
            assert set(got) == set(want), f"request {i}"
            for key in want:
                np.testing.assert_allclose(
                    np.asarray(got[key], dtype=float),
                    np.asarray(want[key], dtype=float),
                    err_msg=f"request {i} group {key}")
    print(f"serve: {n_req} requests / 8 threads -> "
          f"{st['cache.misses']} compiles ({distinct} distinct plans), "
          f"{st['cache.hits']} hits, {st['cache.waits']} waits, "
          f"results byte-identical to serial oracle")

    # -- AOT re-binding: zero recompiles ---------------------------------
    cq = makers[0]().compile()
    misses0 = runtime.cache_stats()["cache.misses"]
    pa2, ba2 = _tables(n=20000, k=100, seed=9)
    out = cq.run(table=Table(dict(pa2)), right=Table(dict(ba2)))
    assert runtime.cache_stats()["cache.misses"] == misses0, \
        "same-shape rebind must not recompile"
    _assert_tables_equal(out, _eager_join(pa2, ba2, on="k", validate="m:1"),
                         "rebind")
    print("rebind: same-shape run(**tables) spent 0 recompiles")

    # -- calibration: measured medians overlay the roofline --------------
    # a fresh ledger for the calibration below
    os.environ["WELD_COST_LEDGER"] = os.path.join(_td, "ledger_cal.jsonl")
    calibrate.invalidate()
    runtime.clear_cache()
    # authentic records: EXPLAIN ANALYZE of an always-routed m:n join
    # writes one ledger row per kernel launch (predicted AND measured)
    Query(Table(dict(pa))).explain(analyze=True).join(
        Table(dict(dup)), on="k", kernelize="always")
    recs = ledger.read()
    assert recs, "EXPLAIN ANALYZE must seed the cost ledger"
    # pre-calibration baseline under auto: routing decisions + provenance
    base = makers[2]().compile()
    base_costs = {c["kernel"]: bool(c["routed"])
                  for c in base.stats["kernelplan"]["costs"]}
    assert "source=roofline" in base.explain().render()
    # seed medians that EQUAL the roofline predictions so provenance
    # switches to measured while every routing decision stays put
    need = calibrate.min_samples() + 2
    for r in {(r["kernel"], r["dtype"], r["bucket"]): r
              for r in recs if r.get("predicted_ns")}.values():
        for _ in range(need):
            ledger.record(r["kernel"], r["dtype"], r["n"],
                          r["predicted_ns"], r["predicted_ns"])
    calibrate.invalidate()
    runtime.clear_cache()
    cal = makers[2]().compile()
    rendered = cal.explain().render()
    assert "source=measured" in rendered, rendered
    cal_costs = {c["kernel"]: bool(c["routed"])
                 for c in cal.stats["kernelplan"]["costs"]
                 if c.get("source") == "measured"}
    assert cal_costs, "no measured-provenance cost rows after seeding"
    for kern, routed in cal_costs.items():
        assert base_costs.get(kern) == routed, \
            (f"calibration flipped routing for {kern}: "
             f"{base_costs.get(kern)} -> {routed}")
    print(f"calibration: {len(cal_costs)} kernels repriced from ledger "
          f"medians (source=measured), routing decisions unchanged")
    return 0


if __name__ == "__main__":
    sys.exit(main())
