"""Backend ablation: generic jnp lowering vs. kernel-planned lowering.

For each workload the SAME fused Weld program is compiled three ways —

* ``kernelize="off"``  — the plain vector emitter (jnp-only backend);
* ``kernelize="auto"`` — the default: the roofline cost gate decides
  per matched loop whether the Pallas route can win;
* ``kernelize="always"`` — every match routed unconditionally (the
  PR-1 behavior; shows what the gate saves us from on losing routes).

Every kernelized result is validated against the jnp-only result before
timing, and the planner's routing decisions are asserted so a silent
fallback (or a silent route) can't masquerade as a win: Q6, group-by
and Black-Scholes must ROUTE under auto, while the large-key PageRank
vecmerger scatter must be COST-GATED back to the jnp lowering.

``--smoke`` (used by tools/ci.sh) runs a reduced size and *fails* if
any auto-mode workload is slower than the jnp baseline by more than
``--tol`` — a cost-gate regression breaks CI instead of landing
silently.

On this CPU container the kernels resolve to their ref (pure-jnp) paths,
so timings measure planner + dispatch overhead and XLA's view of the
restructured program; on a TPU the impl resolves to "pallas" and the
same plan drives the real kernels.
"""
from __future__ import annotations

import numpy as np

from repro.core.lazy import NewWeldObject
from repro.frames import welddf, weldrel

from .bench_pagerank import make_graph, pagerank_native_iter, \
    weld_pagerank_iter
from .bench_tpch import make_lineitem, q6_native
from .common import RowCollector, Suite, merge_routing, time_fn, write_results
from .workloads import black_scholes_native, black_scholes_weld_expr, \
    make_bs_data


def _q6(c, kernelize, collect_stats=None):
    t = weldrel.Table(c)
    q = weldrel.Query(t).filter(
        (t.col("ship") >= 365) & (t.col("ship") < 730)
        & (t.col("disc") >= 0.05) & (t.col("disc") <= 0.07)
        & (t.col("qty") < 24.0)
    )
    return q.agg({"rev": (t.col("price") * t.col("disc"), "+")},
                 kernelize=kernelize, collect_stats=collect_stats)["rev"]


def run(emit, n=1_000_000, smoke=False, tol=0.35, routing=None):
    s = Suite(emit)
    routing = routing if routing is not None else {}
    ratios = []  # (workload, auto_us/jnp_us, closure) for the smoke gate

    def triple(tag, key, fn):
        """Time kernelize=off / auto / always for one workload closure."""
        us_off = time_fn(lambda: fn("off"))
        s.record(f"kernelplan/{tag}_jnp", us_off, baseline_of=key)
        us_auto = time_fn(lambda: fn("auto"))
        s.record(f"kernelplan/{tag}_auto", us_auto, vs=key)
        us_always = time_fn(lambda: fn("always"))
        s.record(f"kernelplan/{tag}_kernelized", us_always, vs=key)
        ratios.append((tag, us_auto / us_off, fn))
        return us_off, us_auto, us_always

    def auto_vs_jnp(fn):
        return time_fn(lambda: fn("auto")) / time_fn(lambda: fn("off"))

    # Routing asserts encode the expected cost-gate decisions, which are
    # size-dependent: below the crossover the gate correctly rejects, so
    # only assert "must route" at sizes safely above it.
    big = n >= 100_000

    # -- TPC-H Q6: fused filter+reduce (multi-agg kernel) ------------------
    c = make_lineitem(n)
    want = q6_native(c)
    st: dict = {}
    got = _q6(c, "auto", st)
    merge_routing(routing, st)
    if big:
        assert st.get("kernelize.filter_reduce_sum", 0) >= 1, \
            f"auto must route Q6 at n={n}: {st.get('kernelplan')}"
    assert abs(got - want) < 1e-6 * max(abs(want), 1)
    got_always = _q6(c, "always")  # validate the forced kernel route too
    assert abs(got_always - want) < 1e-6 * max(abs(want), 1)
    triple("q6", "kq6", lambda kz: _q6(c, kz))

    # -- PageRank: vecmerger scatter — the gate must REJECT (large K) ------
    src, dst, deg, nv = make_graph(n_vertices=max(n // 10, 1000),
                                   n_edges=max(n // 2, 10_000))
    rank0 = np.full(nv, 1.0 / nv)
    src_o = NewWeldObject(src, None)
    dst_o = NewWeldObject(dst, None)
    invdeg_o = NewWeldObject(1.0 / deg, None)
    want = pagerank_native_iter(rank0, src, dst, deg, nv)
    st = {}
    got = weld_pagerank_iter(rank0, src_o, dst_o, invdeg_o, nv,
                             kernelize="auto", collect_stats=st)
    merge_routing(routing, st)
    if nv > 4096:  # beyond the VMEM tile bound the route can never win
        assert st.get("kernelize.vecmerger_segment_sum", 0) == 0, \
            f"auto must gate the large-K vecmerger: {st.get('kernelplan')}"
        assert st["kernelplan"]["rejected"].get(
            "vecmerger_segment_sum", 0) >= 1
    np.testing.assert_allclose(got, want, rtol=1e-10)
    # the forced route is the one that times the kernel — validate it too
    st_always: dict = {}
    got_always = weld_pagerank_iter(rank0, src_o, dst_o, invdeg_o, nv,
                                    kernelize="always",
                                    collect_stats=st_always)
    assert st_always.get("kernelize.vecmerger_segment_sum", 0) >= 1, \
        st_always.get("kernelplan")
    np.testing.assert_allclose(got_always, want, rtol=1e-10)
    triple("pagerank", "kpr",
           lambda kz: weld_pagerank_iter(rank0, src_o, dst_o, invdeg_o, nv,
                                         kernelize=kz))

    # -- group-by: dictmerger -> dense segment_sum -------------------------
    rng = np.random.RandomState(11)
    state = rng.randint(0, 50, n).astype(np.int64)
    crime = rng.rand(n)
    df = welddf.DataFrame({"state": state, "crime": crime})
    st = {}
    d1 = df.groupby_sum("state", "crime", capacity=64, kernelize="auto",
                        collect_stats=st)
    merge_routing(routing, st)
    gb_routed = st.get("kernelize.dict_group_sum", 0) >= 1
    if big:
        assert gb_routed, \
            f"auto must route the group-by at n={n}: {st.get('kernelplan')}"
    d0 = df.groupby_sum("state", "crime", capacity=64, kernelize="off")
    assert set(d1) == set(d0)
    for k in d0:
        assert abs(d1[k] - d0[k]) < 1e-6 * max(abs(d0[k]), 1)
    gb_fn = lambda kz: df.groupby_sum("state", "crime", capacity=64,  # noqa: E731
                                      kernelize=kz)
    gb_off, gb_auto, _ = triple("groupby", "kgb", gb_fn)
    if smoke and gb_routed:
        win = gb_off / gb_auto
        if win < 1.5:  # re-measure once before blaming the code
            win = max(win, 1.0 / auto_vs_jnp(gb_fn))
        assert win >= 1.5, (
            f"group-by kernel route regressed: {win:.2f}x "
            f"(expected >= 1.5x; >= 2x at full size)"
        )

    # -- Black-Scholes: map chain + unfiltered reduce ----------------------
    d = make_bs_data(n)
    want = black_scholes_native(d)
    expr = black_scholes_weld_expr(d)
    st = {}
    got = expr.evaluate(kernelize="auto", collect_stats=st)
    merge_routing(routing, st)
    if big:
        assert st.get("kernelize.filter_reduce_sum", 0) >= 1, \
            f"auto must route Black-Scholes at n={n}: {st.get('kernelplan')}"
    assert abs(float(got) - want) < 1e-4 * abs(want)
    got_always = expr.evaluate(kernelize="always")
    assert abs(float(got_always) - want) < 1e-4 * abs(want)
    triple("blackscholes", "kbs", lambda kz: expr.evaluate(kernelize=kz))

    if smoke:
        # Wall-clock ratios on shared CI hardware are noisy (the same
        # executable can measure ±30% across runs); the routing-decision
        # asserts above are the primary gate, and this timing backstop
        # re-measures before declaring a regression so jitter alone
        # can't fail CI.
        still_bad = []
        for t, r, fn in ratios:
            if r <= 1.0 + tol:
                continue
            r2 = auto_vs_jnp(fn)
            if min(r, r2) > 1.0 + tol:
                still_bad.append((t, min(r, r2)))
        assert not still_bad, (
            f"auto-mode routes slower than jnp beyond tol={tol} "
            f"(reproduced on re-measure): "
            + ", ".join(f"{t}={r:.2f}x" for t, r in still_bad)
        )


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced size + hard assertions (CI gate)")
    ap.add_argument("--n", type=int, default=None)
    ap.add_argument("--tol", type=float, default=0.35,
                    help="max allowed auto/jnp slowdown in --smoke")
    args = ap.parse_args()
    n = args.n or (300_000 if args.smoke else 1_000_000)
    print("name,us_per_call,derived")
    emit = RowCollector(lambda line: print(line, flush=True))
    routing: dict = {}
    run(emit, n=n, smoke=args.smoke, tol=args.tol, routing=routing)
    write_results("kernelplan_ablation", emit.rows,
                  config={"n": n, "smoke": args.smoke, "tol": args.tol},
                  routing=routing)
    if args.smoke:
        print("# kernelplan smoke ablation OK")


if __name__ == "__main__":
    main()
