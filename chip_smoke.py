#!/usr/bin/env python3
"""Smoke run of Weld's served relational path on one TPU chip.

Generates seeded TPC-H tables at ``--sf`` (default 1: 6,001,215 lineitem,
10,000 supplier and 800,000 partsupp rows), stored as a deployment stores
them — int32 keys and dates, f32 measures — and drives six queries
through the entry points a user calls (``weldrel.Query`` → ``Evaluate``,
``CompiledQuery.run``, ``QueryServer.submit``; the dense group-by through
``welddf``), with the Pallas kernels compiled by Mosaic:

* Q6 — filtered revenue sum (``filter_reduce_sum``);
* Q1 — filtered multi-aggregate group-by on (returnflag, linestatus): its
  two-column key packs into 64 bits, so the TPU gate keeps it generic;
* Q1 over exact decimals — the same query with the measures as int32
  hundredths and the flags as dictionary columns: one dense key, int64
  sums on the exact group route (``dict_group_sum``), equal to the
  reference bit for bit;
* revenue by ship date — a dense int32 key of 2,557 days
  (``dict_group_sum``);
* lineitem ⋈ supplier on suppkey, m:1 (``dict_hash_build`` +
  ``hash_probe``);
* supplier ⋈ partsupp on suppkey, m:n with ~80 matches per key
  (``group_build`` + ``group_probe``).

Every query first runs with ``kernelize="always"`` and recovery disabled:
its result must match a plain NumPy reference within the per-dtype
tolerances below, the expected kernels must be routed with impl
``pallas``, and no recovery event or quarantine entry may appear.  The
same staged weldrel queries then go through a ``QueryServer`` under the
default ``"auto"`` routing and are compared again.

    python chip_smoke.py [--sf 1] [--seed 0]

Lines before the last are smoke timings (compile and run wall times of
one process), not benchmark results.  The last line is one JSON object,
``{"ok": true, "device": {...}}``.  Without a TPU, or when any phase
fails, the script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

#: TPC-H rows per scale factor.
LINEITEM_PER_SF = 6_001_215
SUPPLIER_PER_SF = 10_000
PART_PER_SF = 200_000
#: day numbers (since 1992-01-01) of the dates the queries use
ORDER_DAYS = 2_406            # orders run 1992-01-01 .. 1998-08-02
CURRENT_DAY = 1_263           # 1995-06-17: returnflag / linestatus cut
Q6_FROM, Q6_TO = 731, 1_096   # [1994-01-01, 1995-01-01)
Q1_CUT = 2_436                # 1998-12-01 - 90 days
SHIP_DAYS = 2_557             # 1992-01-01 .. 1998-12-31

#: relative tolerance of an f32 result, fixed before any run: sums over up
#: to 6M f32 rows (eps 1.2e-7 x sqrt(6e6) ~ 3e-4, with margin).  Integer
#: results and gathered join columns must match exactly.
F32_RTOL = 1e-3


def make_tables(sf: float, seed: int) -> dict:
    """Seeded TPC-H columns following the spec's domains (dbgen's
    formulas for prices and the part -> supplier mapping)."""
    rng = np.random.default_rng(seed)
    n_l = int(round(LINEITEM_PER_SF * sf))
    n_s = max(int(round(SUPPLIER_PER_SF * sf)), 4)
    n_p = max(int(round(PART_PER_SF * sf)), 1)

    def supp_of(partkey, i):
        # dbgen: the i-th of a part's four suppliers
        return ((partkey + i * (n_s // 4 + (partkey - 1) // n_s)) % n_s
                + 1).astype(np.int32)

    partkey = rng.integers(1, n_p + 1, n_l)
    retail = (90_000 + (partkey // 10) % 20_001 + 100 * (partkey % 1_000)) / 100
    qty = rng.integers(1, 51, n_l)
    orderdate = rng.integers(0, ORDER_DAYS, n_l)
    ship = orderdate + rng.integers(1, 122, n_l)
    receipt = ship + rng.integers(1, 31, n_l)
    flag_ar = rng.integers(0, 2, n_l) * 2          # A=0 or R=2
    lineitem = {
        "l_suppkey": supp_of(partkey, rng.integers(0, 4, n_l)),
        "l_quantity": qty.astype(np.float32),
        "l_extendedprice": (qty * retail).astype(np.float32),
        "l_discount": (rng.integers(0, 11, n_l) / 100).astype(np.float32),
        "l_tax": (rng.integers(0, 9, n_l) / 100).astype(np.float32),
        "l_returnflag": np.where(receipt <= CURRENT_DAY, flag_ar,
                                 1).astype(np.int32),          # N=1
        "l_linestatus": (ship > CURRENT_DAY).astype(np.int32),  # F=0, O=1
        "l_shipdate": ship.astype(np.int32),
    }
    supplier = {
        "s_suppkey": np.arange(1, n_s + 1, dtype=np.int32),
        "s_nationkey": rng.integers(0, 25, n_s).astype(np.int32),
        "s_acctbal": (rng.integers(-99_999, 1_000_000, n_s)
                      / 100).astype(np.float32),
    }
    ps_part = np.repeat(np.arange(1, n_p + 1), 4)
    partsupp = {
        "ps_suppkey": supp_of(ps_part, np.tile(np.arange(4), n_p)),
        "ps_partkey": ps_part.astype(np.int32),
        "ps_availqty": rng.integers(1, 10_000, 4 * n_p).astype(np.int32),
        "ps_supplycost": (rng.integers(100, 100_001, 4 * n_p)
                          / 100).astype(np.float32),
    }
    return {"lineitem": lineitem, "supplier": supplier,
            "partsupp": partsupp}


# -- NumPy references (same semantics, f64 accumulation) ----------------------


def _q6_mask(li):
    return ((li["l_shipdate"] >= np.int32(Q6_FROM))
            & (li["l_shipdate"] < np.int32(Q6_TO))
            & (li["l_discount"] >= np.float32(0.05))
            & (li["l_discount"] <= np.float32(0.07))
            & (li["l_quantity"] < np.float32(24)))


def ref_q6(t):
    li = t["lineitem"]
    m = _q6_mask(li)
    rev = (li["l_extendedprice"][m] * li["l_discount"][m]).astype(np.float64)
    return {"revenue": rev.sum()}


def ref_q1(t):
    li = t["lineitem"]
    m = li["l_shipdate"] <= np.int32(Q1_CUT)
    price = li["l_extendedprice"][m].astype(np.float64)
    disc = li["l_discount"][m].astype(np.float64)
    tax = li["l_tax"][m].astype(np.float64)
    cols = [li["l_quantity"][m].astype(np.float64), price,
            price * (1 - disc), price * (1 - disc) * (1 + tax)]
    group = li["l_returnflag"][m] * 2 + li["l_linestatus"][m]
    sums = [np.bincount(group, weights=c, minlength=6) for c in cols]
    counts = np.bincount(group, minlength=6)
    return {(int(g) // 2, int(g) % 2): tuple(s[g] for s in sums)
            + (int(counts[g]),) for g in np.flatnonzero(counts)}


#: the flags' dictionaries, in the generator's code order
Q1_DICTS = {"l_returnflag": (b"A", b"N", b"R"), "l_linestatus": (b"F", b"O")}
DECIMALS = ("l_quantity", "l_extendedprice", "l_discount", "l_tax")


def decimal_lineitem(t) -> dict:
    """Q1's columns as a columnar engine stores the spec's types: the
    DECIMAL(15,2) measures as int32 hundredths (exact from the f32
    draws), the flags as their codes, the ship date as is."""
    li = t["lineitem"]
    out = {c: li[c] for c in ("l_returnflag", "l_linestatus", "l_shipdate")}
    out.update({c: np.rint(li[c].astype(np.float64) * 100).astype(np.int32)
                for c in DECIMALS})
    return out


def ref_q1_decimal(li):
    """Q1's sums in int64 and its averages as sum / count."""
    m = li["l_shipdate"] <= np.int32(Q1_CUT)
    qty, price, disc, tax = (li[c][m].astype(np.int64) for c in DECIMALS)
    disc_price = price * (100 - disc)
    group = li["l_returnflag"][m] * 2 + li["l_linestatus"][m]
    out = {}
    for g in range(6):
        s = group == g
        n = int(s.sum())
        if n:
            sq, sp, sd = (int(x[s].sum()) for x in (qty, price, disc))
            out[(Q1_DICTS["l_returnflag"][g // 2],
                 Q1_DICTS["l_linestatus"][g % 2])] = (
                sq, sp, int(disc_price[s].sum()),
                int((disc_price[s] * (100 + tax[s])).sum()),
                sq / n, sp / n, sd / n, n)
    return out


def ref_ship_revenue(t):
    li = t["lineitem"]
    sums = np.bincount(li["l_shipdate"], weights=li["l_extendedprice"]
                       .astype(np.float64), minlength=SHIP_DAYS)
    present = np.bincount(li["l_shipdate"], minlength=SHIP_DAYS) > 0
    return {int(d): sums[d] for d in np.flatnonzero(present)}


def ref_join(probe, build, pkey, bkey):
    """Inner equi-join: probe-row-major, build rows in build order."""
    order = np.argsort(build[bkey], kind="stable")
    bk = build[bkey][order]
    lo = np.searchsorted(bk, probe[pkey], side="left")
    cnt = np.searchsorted(bk, probe[pkey], side="right") - lo
    rows = np.repeat(np.arange(probe[pkey].size), cnt)
    starts = np.concatenate([[0], np.cumsum(cnt)])[:-1]
    brow = order[lo[rows] + np.arange(rows.size) - starts[rows]]
    out = {c: v[rows] for c, v in probe.items()}
    out.update({c: v[brow] for c, v in build.items() if c != bkey})
    return out


# -- comparison ------------------------------------------------------------


def _close(got, want, what):
    got, want = np.asarray(got), np.asarray(want)
    if want.dtype == np.float64:  # an f32 sum against its f64 reference
        ok = got.shape == want.shape and np.allclose(
            got, want, rtol=F32_RTOL, atol=0)
    else:
        ok = got.shape == want.shape and np.array_equal(got, want)
    if not ok:
        raise AssertionError(f"{what}: result differs from the NumPy "
                             f"reference ({got!r:.200} vs {want!r:.200})")


def check_scalars(got: dict, want: dict, what: str):
    if set(got) != set(want):
        raise AssertionError(f"{what}: keys {sorted(got)} != {sorted(want)}")
    for k in want:
        g, w = got[k], want[k]
        g = g if isinstance(g, tuple) else (g,)
        w = w if isinstance(w, tuple) else (w,)
        for j, (a, b) in enumerate(zip(g, w)):
            _close(a, b, f"{what}[{k}][{j}]")


def check_table(got, want: dict, what: str):
    cols = {c: np.asarray(got.col(c).obj.data) for c in got.cols}
    if set(cols) != set(want):
        raise AssertionError(f"{what}: columns {sorted(cols)} != "
                             f"{sorted(want)}")
    for c in want:
        _close(cols[c], want[c], f"{what}.{c}")


# -- the run ----------------------------------------------------------------


def run(sf: float, seed: int, impl=None, log=print):
    """Every phase of the smoke; raises on the first failure.  ``impl``
    None takes the backend's kernel path (pallas on a TPU)."""
    from repro.core import recovery
    from repro.core.kernelplan import quarantine
    from repro.core.serve import QueryServer
    from repro.frames import welddf, weldrel
    from repro.kernels import hash_table
    from repro.kernels import ops as kops

    impl_name = impl or kops.default_impl()
    on_tpu = impl_name == "pallas"
    t0 = time.perf_counter()
    t = make_tables(sf, seed)
    log(f"[smoke] sf={sf} seed={seed} impl={impl_name}: "
        f"{t['lineitem']['l_suppkey'].size} lineitem, "
        f"{t['supplier']['s_suppkey'].size} supplier, "
        f"{t['partsupp']['ps_suppkey'].size} partsupp rows generated in "
        f"{time.perf_counter() - t0:.1f} s")

    def table(name, cols):
        return weldrel.Table({c: t[name][c] for c in cols})

    li_cols = ["l_shipdate", "l_discount", "l_quantity", "l_extendedprice",
               "l_tax", "l_returnflag", "l_linestatus"]
    join_li = ["l_suppkey", "l_extendedprice"]

    # each query: entry(filtered Query) -> the proxy the operator is called
    # on (the Query itself for Evaluate, .compile() or .stage())
    def q6(entry, **kw):
        li = table("lineitem", li_cols)
        pred = ((li.col("l_shipdate") >= np.int32(Q6_FROM))
                & (li.col("l_shipdate") < np.int32(Q6_TO))
                & (li.col("l_discount") >= np.float32(0.05))
                & (li.col("l_discount") <= np.float32(0.07))
                & (li.col("l_quantity") < np.float32(24)))
        return entry(weldrel.Query(li).filter(pred)).agg(
            {"revenue": (li.col("l_extendedprice") * li.col("l_discount"),
                         "+")}, **kw)

    def q1(entry, **kw):
        li = table("lineitem", li_cols)
        price = li.col("l_extendedprice")
        disc_price = price * (np.float32(1) - li.col("l_discount"))
        q = weldrel.Query(li).filter(li.col("l_shipdate") <= np.int32(Q1_CUT))
        return entry(q).group_agg(
            [li.col("l_returnflag"), li.col("l_linestatus")],
            {"sum_qty": (li.col("l_quantity"), "+"),
             "sum_base_price": (price, "+"),
             "sum_disc_price": (disc_price, "+"),
             "sum_charge": (disc_price * (np.float32(1) + li.col("l_tax")),
                            "+")},
            capacity=8, **kw)

    dec_li = decimal_lineitem(t)

    def q1_decimal(entry, **kw):
        li = weldrel.Table(dec_li, dictionaries=Q1_DICTS)

        def dec(c):
            return li.col(c).astype(np.int64)

        qty, price, disc = (dec(c) for c in ("l_quantity",
                                             "l_extendedprice", "l_discount"))
        disc_price = price * (100 - disc)
        q = weldrel.Query(li).filter(li.col("l_shipdate") <= np.int32(Q1_CUT))
        return entry(q).group_agg(
            [li.col("l_returnflag"), li.col("l_linestatus")],
            {"sum_qty": (qty, "+"), "sum_base_price": (price, "+"),
             "sum_disc_price": (disc_price, "+"),
             "sum_charge": (disc_price * (100 + dec("l_tax")), "+"),
             "avg_qty": (qty, "avg"), "avg_price": (price, "avg"),
             "avg_disc": (disc, "avg")}, **kw)

    def join_m1(entry, **kw):
        return entry(weldrel.Query(table("lineitem", join_li))).join(
            table("supplier", ["s_suppkey", "s_nationkey", "s_acctbal"]),
            on="l_suppkey", right_on="s_suppkey", **kw)

    def join_mn(entry, **kw):
        return entry(weldrel.Query(
            table("supplier", ["s_suppkey", "s_acctbal"]))).join(
            table("partsupp", list(t["partsupp"])),
            on="s_suppkey", right_on="ps_suppkey", **kw)

    # a build side beyond the hash kernels' capacity keeps the generic
    # lowering: the planner declines it before pricing
    n_s = t["supplier"]["s_suppkey"].size
    fits = n_s <= hash_table.MAX_CAP
    if not fits:
        log(f"[smoke] {n_s} suppliers > hash capacity {hash_table.MAX_CAP}:"
            " both joins keep the generic lowering")
    weld_queries = {
        # name: (query, reference, kernels it must route on the chip)
        "q6": (q6, ref_q6(t), {"filter_reduce_sum"}),
        "q1": (q1, ref_q1(t), set()),
        "q1_decimal": (q1_decimal, ref_q1_decimal(dec_li),
                       {"dict_group_sum"}),
        "join_m1": (join_m1, ref_join(
            {c: t["lineitem"][c] for c in join_li}, t["supplier"],
            "l_suppkey", "s_suppkey"),
            {"dict_hash_build", "hash_probe"} if fits else set()),
        "join_mn": (join_mn, ref_join(
            {c: t["supplier"][c] for c in ("s_suppkey", "s_acctbal")},
            t["partsupp"], "s_suppkey", "ps_suppkey"),
            {"group_build", "group_probe"} if fits else set()),
    }

    def check(name, got, want):
        if name.startswith("join"):
            check_table(got, want, name)
        elif name == "q1_decimal":
            if got != want or list(got) != list(want):
                raise AssertionError(f"q1_decimal: {got} differs from the "
                                     f"exact reference {want}")
        else:
            check_scalars(got, want, name)

    def check_plan(name, stats, kernels, quiet=False):
        kp = stats.get("kernelplan", {})
        routed = set(kp.get("routed", {}))
        if kp.get("impl") != impl_name:
            raise AssertionError(f"{name}: planned for impl "
                                 f"{kp.get('impl')!r}, not {impl_name!r}")
        if not kernels <= routed:
            raise AssertionError(f"{name}: expected kernels "
                                 f"{sorted(kernels)} routed, got "
                                 f"{sorted(routed)} ({kp.get('costs')})")
        for c in [] if quiet else kp.get("costs", []):
            if not c["routed"]:
                log(f"[smoke] {name}: gate kept {c['kernel']} generic: "
                    f"{c['why']}")
        rec = sorted(k for k in stats if k.startswith("recovery."))
        if rec:
            raise AssertionError(f"{name}: recovery events {rec}")
        if quarantine.entries():
            raise AssertionError(f"{name}: quarantined "
                                 f"{sorted(quarantine.entries())}")
        return routed

    failures = []

    def phase(name, fn):
        """Run one phase; a failure is logged and the smoke goes on, so
        one run reports every phase (and still fails at the end)."""
        try:
            fn()
        except Exception as e:  # noqa: BLE001 - reported, re-raised below
            failures.append(name)
            log(f"[smoke] FAIL {name}: {type(e).__name__}: {e}"[:2000])

    def always(name, query, want, kernels):
        st: dict = {}
        t1 = time.perf_counter()
        got = query(lambda q: q, kernelize="always", kernel_impl=impl,
                    collect_stats=st)
        t_eval = time.perf_counter() - t1
        routed = check_plan(name, st, kernels)
        if on_tpu and name == "q1" and not any(
                "packed 64-bit key" in c.get("why", "")
                for c in st["kernelplan"]["costs"]):
            raise AssertionError("q1: the packed two-column key was not "
                                 "rejected by the TPU gate")
        cq = query(lambda q: q.compile(), kernelize="always",
                   kernel_impl=impl)
        t2 = time.perf_counter()
        got_aot = cq.run()
        t_run = time.perf_counter() - t2
        log(f"[smoke timing] {name}: evaluate (compile+run) "
            f"{t_eval * 1e3:.1f} ms, CompiledQuery.run {t_run * 1e3:.1f}"
            f" ms (compile cache hit: {cq.from_cache}); kernels "
            f"{sorted(routed) or '-'}")
        check(name, got, want)
        check(name, got_aot, want)
        check_plan(name, cq.stats, kernels, quiet=True)

    def ship_revenue():
        # the dense single-key group-by: welddf's dictmerger route
        df = welddf.DataFrame({c: t["lineitem"][c]
                               for c in ("l_shipdate", "l_extendedprice")})
        times = []
        for _ in range(2):  # the second evaluation hits the compile cache
            st: dict = {}
            t1 = time.perf_counter()
            got = df.groupby_sum("l_shipdate", "l_extendedprice",
                                 capacity=SHIP_DAYS, kernelize="always",
                                 kernel_impl=impl, collect_stats=st)
            times.append(time.perf_counter() - t1)
        routed = check_plan("ship_revenue", st, {"dict_group_sum"})
        log(f"[smoke timing] ship_revenue: evaluate (compile+run) "
            f"{times[0] * 1e3:.1f} ms, evaluate (cache hit) "
            f"{times[1] * 1e3:.1f} ms; kernels {sorted(routed)}")
        check_scalars(got, ref_ship_revenue(t), "ship_revenue")

    # phase 1: kernelize="always", recovery disabled, Evaluate then AOT
    with recovery.disabled():
        for name, (query, want, kernels) in weld_queries.items():
            phase(name, lambda: always(name, query, want, kernels))
        phase("ship_revenue", ship_revenue)

    # phase 2: the same staged queries through the server, default "auto"
    staged = {name: query(lambda q: q.stage(), kernel_impl=impl)
              for name, (query, _, _) in weld_queries.items()}
    t1 = time.perf_counter()
    with QueryServer(workers=2) as srv:
        futs = {name: srv.submit(sq) for name, sq in staged.items()}
        for name, fut in futs.items():
            phase(f"server.{name}", lambda: check(
                name, fut.result(), weld_queries[name][1]))
        sstats = srv.stats()
    log(f"[smoke timing] QueryServer (auto, {len(staged)} queries): "
        f"{(time.perf_counter() - t1) * 1e3:.1f} ms; {sstats}")
    if quarantine.entries():
        failures.append("quarantine")
        log(f"[smoke] FAIL quarantined {sorted(quarantine.entries())}")
    if failures:
        raise AssertionError(f"smoke phases failed: {failures}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sf", type=float, default=1.0,
                    help="TPC-H scale factor (default 1)")
    ap.add_argument("--seed", type=int, default=0,
                    help="data generator seed (default 0)")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform!r}); the smoke "
              "run needs the chip", file=sys.stderr)
        return 2
    with tempfile.TemporaryDirectory(prefix="weld-smoke-") as tmp:
        # a clean kernel-health file, autotune cache and cost ledger: a
        # stale quarantine entry must not hide a kernel from this run
        for env, fname in (("WELD_KERNEL_HEALTH", "kernel_health.json"),
                           ("WELD_AUTOTUNE_CACHE", "autotune.json"),
                           ("WELD_COST_LEDGER", "cost_ledger.jsonl")):
            os.environ[env] = os.path.join(tmp, fname)
        run(args.sf, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
