"""Hash-join ablation: generic jnp lowering vs. the two-kernel hash plan.

A fact-to-dimension (m:1) join — the Spark SQL workload the paper's
§6 port leans on — timed three ways over the SAME fused Weld program,
plus left/anti/multi-key variants that must each take exactly ONE
horizontally fused probe launch (all output columns share one
membership kernel), plus m:n fan-out configs (fanout 1/4/32, duplicate
build keys) that must each take exactly ONE ``group_build`` and ONE
``group_probe`` launch (the groupbuilder expansion route):

* ``kernelize="off"``   — generic lowering (vectorized binary-search
  probe + sort-based dictmerger build);
* ``kernelize="auto"``  — the default: the roofline cost gate decides
  per matched loop (build -> ``dict_hash_build``, probes ->
  ``hash_probe``) whether the kernel route can win;
* ``kernelize="always"``— every match routed unconditionally.

Every configuration is validated against a NumPy oracle before timing,
and ``--smoke`` (run from tools/ci.sh) asserts the expected routing
decisions: at the large config BOTH the open-addressing hash build and
the one-hot probe kernels must be selected under auto, while the tiny
config must be cost-gated back to the jnp lowering — so a routing
regression fails CI instead of landing silently.

On this CPU container the kernels resolve to their ref (pure-jnp) paths;
on a TPU the impl resolves to "pallas" and the same plan drives the real
kernels.
"""
from __future__ import annotations

import numpy as np

from repro.frames import weldrel

from .common import RowCollector, Suite, merge_routing, time_fn, \
    write_results


def make_join_data(n: int, k: int, seed: int = 3):
    rng = np.random.RandomState(seed)
    lcols = {
        "key": rng.randint(0, 2 * k, n).astype(np.int64),  # ~50% match
        "qty": rng.rand(n) * 40.0,
        "price": rng.rand(n) * 100.0,
    }
    rcols = {
        "key": np.arange(k, dtype=np.int64),
        "rate": rng.rand(k),
    }
    return lcols, rcols


def make_mn_data(n: int, k: int, fanout: int, seed: int = 7):
    """An m:n config: every build key appears `fanout` times.  At
    fanout=1 one key row is duplicated so the m:n (groupbuilder) path
    still engages — an all-unique build side takes the m:1 route."""
    rng = np.random.RandomState(seed)
    rkey = np.repeat(np.arange(k, dtype=np.int64), fanout)
    if fanout == 1:
        rkey = np.concatenate([rkey, rkey[:1]])
    rcols = {"key": rkey, "rate": rng.rand(rkey.size)}
    lcols = {
        "key": rng.randint(0, 2 * k, n).astype(np.int64),  # ~50% match
        "qty": rng.rand(n) * 40.0,
        "price": rng.rand(n) * 100.0,
    }
    return lcols, rcols


def np_join_revenue(lcols, rcols):
    """Oracle: join on key, revenue = sum(price * rate over matches)."""
    sel = np.isin(lcols["key"], rcols["key"])
    idx = np.searchsorted(rcols["key"], lcols["key"][sel])
    return (lcols["price"][sel] * rcols["rate"][idx]).sum(), int(sel.sum())


def weld_join(lcols, rcols, kernelize, how="inner", on="key",
              collect_stats=None):
    t = weldrel.Table(lcols, eager=False)
    r = weldrel.Table(rcols, eager=False)
    return weldrel.Query(t).join(r, on=on, how=how, kernelize=kernelize,
                                 collect_stats=collect_stats)


def _validate(lcols, rcols, kernelize):
    out = weld_join(lcols, rcols, kernelize)
    want_rev, want_rows = np_join_revenue(lcols, rcols)
    price = weldrel._host(out.cols["price"])
    rate = weldrel._host(out.cols["rate"])
    assert price.shape[0] == want_rows, (price.shape, want_rows)
    got = float((price * rate).sum())
    assert abs(got - want_rev) < 1e-6 * max(abs(want_rev), 1), \
        (got, want_rev, kernelize)


def run(emit, n=1_000_000, smoke=False, tol=0.35, routing=None):
    s = Suite(emit)
    k = max(n // 20, 64)
    routing = routing if routing is not None else {}

    # -- large config: both kernels must route under auto ------------------
    lcols, rcols = make_join_data(n, k)
    st: dict = {}
    weld_join(lcols, rcols, "auto", collect_stats=st)
    merge_routing(routing, st)
    if smoke:
        routed = st.get("kernelplan", {}).get("routed", {})
        assert st.get("kernelize.dict_hash_build", 0) >= 1, \
            f"auto must route the hash build at n={n}: {routed}"
        # the 4 output columns (key, qty, price, rate) share ONE
        # horizontally fused probe launch — N probes would be a
        # fusion regression
        assert st.get("kernelize.hash_probe", 0) == 1, \
            f"auto must route ONE fused probe at n={n}: {routed}"
    for kz in ("off", "auto", "always"):
        _validate(lcols, rcols, kz)

    us_off = time_fn(lambda: weld_join(lcols, rcols, "off"))
    s.record("join/inner_jnp", us_off, baseline_of="kj")
    us_auto = time_fn(lambda: weld_join(lcols, rcols, "auto"))
    s.record("join/inner_auto", us_auto, vs="kj")
    us_always = time_fn(lambda: weld_join(lcols, rcols, "always"))
    s.record("join/inner_kernelized", us_always, vs="kj")

    # -- left / anti / multi-key: one fused probe each, oracle-checked -----
    sel = np.isin(lcols["key"], rcols["key"])
    for how, want_rows in (("left", lcols["key"].shape[0]),
                           ("anti", int((~sel).sum()))):
        sth: dict = {}
        out = weld_join(lcols, rcols, "always", how=how, collect_stats=sth)
        merge_routing(routing, sth)
        rows = weldrel._host(out.cols["key"]).shape[0]
        assert rows == want_rows, (how, rows, want_rows)
        if how == "left":
            rate = weldrel._host(out.cols["rate"])
            assert int(np.isnan(rate).sum()) == int((~sel).sum()), how
        if smoke:
            assert sth.get("kernelize.hash_probe", 0) == 1, \
                f"{how} join must take ONE fused probe: {sth.get('kernelplan')}"
        us_h = time_fn(lambda: weld_join(lcols, rcols, "always", how=how))
        s.record(f"join/{how}_kernelized", us_h, vs="kj")

    mlcols = {"key": lcols["key"] % 1000, "key2": lcols["key"] % 7,
              "price": lcols["price"]}
    mrcols = {"key": np.arange(min(k, 1000), dtype=np.int64) ,
              "key2": (np.arange(min(k, 1000)) % 7).astype(np.int64),
              "rate": rcols["rate"][:min(k, 1000)]}
    stm: dict = {}
    outm = weld_join(mlcols, mrcols, "always", on=["key", "key2"],
                     collect_stats=stm)
    merge_routing(routing, stm)
    if smoke:
        assert stm.get("kernelize.dict_hash_build", 0) == 1, \
            f"multi-key build must route: {stm.get('kernelplan')}"
        assert stm.get("kernelize.hash_probe", 0) == 1, \
            f"multi-key join must take ONE fused probe: {stm.get('kernelplan')}"
    # multi-key oracle: packed tuples
    lt = set(zip(mrcols["key"].tolist(), mrcols["key2"].tolist()))
    wantm = sum(1 for a, b in zip(mlcols["key"].tolist(),
                                  mlcols["key2"].tolist()) if (a, b) in lt)
    rowsm = weldrel._host(outm.cols["price"]).shape[0]
    assert rowsm == wantm, (rowsm, wantm)
    s.record("join/multikey_kernelized",
             time_fn(lambda: weld_join(mlcols, mrcols, "always",
                                       on=["key", "key2"])))

    # -- m:n fan-out configs: groupbuilder expansion, ONE group_probe ------
    n_mn = min(n, 200_000)
    for fanout in (1, 4, 32):
        kmn = max(min(k, 2048) // max(fanout, 1), 8)
        ml, mr = make_mn_data(n_mn, kmn, fanout)
        stg: dict = {}
        outg = weld_join(ml, mr, "always", collect_stats=stg)
        merge_routing(routing, stg)
        # expansion-size oracle: sum of per-probe-row build match counts
        uniq, cnts = np.unique(mr["key"], return_counts=True)
        cnt_map = np.zeros(2 * kmn, np.int64)
        cnt_map[uniq] = cnts
        want_rows = int(cnt_map[ml["key"]].sum())
        rows = weldrel._host(outg.cols["price"]).shape[0]
        assert rows == want_rows, (fanout, rows, want_rows)
        rows0 = weldrel._host(
            weld_join(ml, mr, "off").cols["price"]).shape[0]
        assert rows0 == want_rows, (fanout, rows0, want_rows)
        if smoke:
            # exactly ONE group build + ONE fan-out probe per m:n join,
            # whatever the output width (N launches = a fusion regression)
            assert stg.get("kernelize.group_build", 0) == 1, \
                f"m:n fanout={fanout} build: {stg.get('kernelplan')}"
            assert stg.get("kernelize.group_probe", 0) == 1, \
                f"m:n fanout={fanout} probe: {stg.get('kernelplan')}"
        s.record(f"join/mn_fanout{fanout}_jnp",
                 time_fn(lambda: weld_join(ml, mr, "off")),
                 baseline_of=f"mn{fanout}")
        s.record(f"join/mn_fanout{fanout}_kernelized",
                 time_fn(lambda: weld_join(ml, mr, "always")),
                 vs=f"mn{fanout}")

    # -- tiny config: the cost gate must keep the jnp lowering -------------
    tl, tr = make_join_data(256, 32, seed=5)
    st2: dict = {}
    weld_join(tl, tr, "auto", collect_stats=st2)
    merge_routing(routing, st2)
    if smoke:
        assert st2.get("kernelize.matched", 0) == 0, \
            f"auto must gate the tiny join: {st2.get('kernelplan')}"
    for kz in ("off", "auto"):
        _validate(tl, tr, kz)
    s.record("join/tiny_auto_gated", time_fn(lambda: weld_join(tl, tr, "auto")))

    if smoke and us_auto > us_off * (1.0 + tol):
        # re-measure once so shared-CI timing jitter can't fail the gate
        us_auto2 = time_fn(lambda: weld_join(lcols, rcols, "auto"))
        us_off2 = time_fn(lambda: weld_join(lcols, rcols, "off"))
        assert min(us_auto / us_off, us_auto2 / us_off2) <= 1.0 + tol, (
            f"auto-mode join slower than jnp beyond tol={tol}: "
            f"{us_auto / us_off:.2f}x (re-measured "
            f"{us_auto2 / us_off2:.2f}x)"
        )


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced size + routing assertions (CI gate)")
    ap.add_argument("--n", type=int, default=None)
    ap.add_argument("--tol", type=float, default=0.35,
                    help="max allowed auto/jnp slowdown in --smoke")
    args = ap.parse_args()
    n = args.n or (300_000 if args.smoke else 1_000_000)
    print("name,us_per_call,derived")
    emit = RowCollector(lambda line: print(line, flush=True))
    routing: dict = {}
    run(emit, n=n, smoke=args.smoke, tol=args.tol, routing=routing)
    write_results("join_hash", emit.rows,
                  config={"n": n, "smoke": args.smoke, "tol": args.tol},
                  routing=routing)
    if args.smoke:
        print("# join smoke ablation OK")


if __name__ == "__main__":
    main()
