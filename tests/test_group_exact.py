"""The exact group route: TPC-H Q1 over decimals stored as scaled int32
and flags stored as dictionary codes, grouped through one dense key.

Every path (eager, lazy generic, kernelized with the Pallas body
interpreted) must equal a plain NumPy int64 reference exactly; the
kernel's word/partial combine must stay exact past 2**24 and 2**31; the
planner routes declared dictionary keys and still refuses an undeclared
two-column key on the TPU gate."""
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.runtime  # noqa: F401  (x64 on, as in the program)
from repro.core import recovery
from repro.core.passes import optimize
from repro.core.kernelplan.planner import plan_kernels
from repro.frames import weldrel
from repro.kernels import ops, segment_reduce as sr

rng = np.random.default_rng(15)

DICTS = {"l_returnflag": (b"A", b"N", b"R"), "l_linestatus": (b"F", b"O")}
CUT = 2_436  # 1998-12-01 - 90 days, as day numbers since 1992-01-01


def decimal_lineitem(n: int, seed: int) -> dict:
    """Seeded lineitem columns in Q1's stored types, with rows at the
    domains' edges: price 104,950.00, discount 0.10, tax 0.08."""
    r = np.random.default_rng(seed)
    cols = {
        "l_quantity": r.integers(1, 51, n) * 100,
        "l_extendedprice": r.integers(90_000, 10_495_001, n),
        "l_discount": r.integers(0, 11, n),
        "l_tax": r.integers(0, 9, n),
        "l_returnflag": r.integers(0, 3, n),
        "l_linestatus": r.integers(0, 2, n),
        "l_shipdate": r.integers(0, 2_557, n),
    }
    cols = {c: v.astype(np.int32) for c, v in cols.items()}
    edge = slice(0, min(n, 7))
    cols["l_quantity"][edge] = 5_000
    cols["l_extendedprice"][edge] = 10_495_000
    cols["l_discount"][edge] = 10
    cols["l_tax"][edge] = 8
    cols["l_shipdate"][edge] = CUT
    return cols


def q1_reference(cols: dict, cut: int = CUT) -> dict:
    """Q1 in plain NumPy int64: sums per (returnflag, linestatus), the
    averages as sum / count, ordered by flag then status."""
    m = cols["l_shipdate"] <= cut
    qty, price, disc, tax = (cols[c][m].astype(np.int64) for c in
                             ("l_quantity", "l_extendedprice",
                              "l_discount", "l_tax"))
    disc_price = price * (100 - disc)
    charge = disc_price * (100 + tax)
    rf, ls = cols["l_returnflag"][m], cols["l_linestatus"][m]
    out = {}
    for a, flag in enumerate(DICTS["l_returnflag"]):
        for b, status in enumerate(DICTS["l_linestatus"]):
            g = (rf == a) & (ls == b)
            n = int(g.sum())
            if n:
                sq, sp, sd = (int(x[g].sum()) for x in (qty, price, disc))
                out[(flag, status)] = (sq, sp, int(disc_price[g].sum()),
                                       int(charge[g].sum()),
                                       sq / n, sp / n, sd / n, n)
    return out


def q1(t, entry=lambda q: q, **kw):
    """Q1 as a user writes it: the discounted price is one expression,
    summed and multiplied into the charge."""
    li = t

    def dec(c):
        return li.col(c).astype(np.int64)

    qty, price, disc = (dec(c) for c in ("l_quantity", "l_extendedprice",
                                         "l_discount"))
    disc_price = price * (100 - disc)
    q = weldrel.Query(li).filter(li.col("l_shipdate") <= np.int32(CUT))
    return entry(q).group_agg(
        [li.col("l_returnflag"), li.col("l_linestatus")],
        {"sum_qty": (qty, "+"), "sum_base_price": (price, "+"),
         "sum_disc_price": (disc_price, "+"),
         "sum_charge": (disc_price * (100 + dec("l_tax")), "+"),
         "avg_qty": (qty, "avg"), "avg_price": (price, "avg"),
         "avg_disc": (disc, "avg")}, **kw)


PATHS = {
    "eager": dict(eager=True, kw={}),
    "lazy_generic": dict(eager=False, kw=dict(kernelize=False)),
    "kernelized": dict(eager=False, kw=dict(kernelize="always",
                                            kernel_impl="interpret")),
}


@pytest.mark.parametrize("path", list(PATHS))
@pytest.mark.parametrize("n", [1, 3_000, 70_001])
def test_q1_equals_the_int64_reference_on_every_path(path, n):
    cols = decimal_lineitem(n, seed=n)
    p = PATHS[path]
    t = weldrel.Table(cols, eager=p["eager"], dictionaries=DICTS)
    st: dict = {}
    kw = dict(p["kw"], collect_stats=st) if not p["eager"] else {}
    with recovery.disabled():
        got = q1(t, **kw)
    want = q1_reference(cols)
    assert got == want
    assert list(got) == list(want)  # ordered by the dictionaries' codes
    if path == "kernelized":
        assert st["kernelplan"]["routed"] == {"dict_group_sum": 1}
        assert st["kernelplan"]["dense_group.keys"] == 6
        # qty, price, disc as one container each; the two products as two
        assert st["kernelplan"]["dense_group.words"] == 1 + 2 * 7


def test_staged_query_through_the_server_equals_the_reference():
    from repro.core.serve import QueryServer

    cols = decimal_lineitem(5_000, seed=3)
    t = weldrel.Table(cols, dictionaries=DICTS)
    with QueryServer(workers=2) as srv:
        futs = [srv.submit(q1(t, entry=lambda q: q.stage(),
                              kernel_impl="interpret")) for _ in range(2)]
        answers = [f.result() for f in futs]
    assert all(a == q1_reference(cols) for a in answers)


def test_one_dictionary_key_decodes_to_the_bare_value():
    codes = rng.integers(0, 3, 500).astype(np.int32)
    v = rng.integers(-2 ** 31, 2 ** 31, 500).astype(np.int64) << 20
    want = {}
    for k, name in enumerate((b"x", b"y", b"z")):
        m = codes == k
        if m.any():
            want[name] = (int(v[m].sum()), int(m.sum()))
    for eager in (True, False):
        t = weldrel.Table({"k": codes, "v": v}, eager=eager,
                          dictionaries={"k": (b"x", b"y", b"z")})
        kw = {} if eager else dict(kernelize="always",
                                   kernel_impl="interpret")
        assert weldrel.Query(t).group_agg([t.col("k")],
                                          {"s": (t.col("v"), "+")},
                                          **kw) == want


def test_avg_over_undeclared_keys_is_the_sum_over_the_count():
    k = rng.integers(0, 5, 300).astype(np.int64)
    v = rng.integers(0, 100, 300).astype(np.int64)
    for eager in (True, False):
        t = weldrel.Table({"k": k, "v": v}, eager=eager)
        got = weldrel.Query(t).group_agg(
            [t.col("k")], {"s": (t.col("v"), "+"), "a": (t.col("v"), "avg")},
            capacity=16)
        for key, (s, a, n) in got.items():
            m = k == key
            assert (s, n) == (int(v[m].sum()), int(m.sum()))
            assert a == s / n


@pytest.mark.parametrize("bad, match", [
    (np.array([0, 1, 3], np.int32), "outside its 3-value dictionary"),
    (np.array([0, -1, 2], np.int32), "outside its 3-value dictionary"),
    (np.array([0.0, 1.0, 2.0]), "not integer codes"),
])
def test_an_out_of_dictionary_code_raises_at_table_construction(bad, match):
    with pytest.raises((ValueError, TypeError), match=match):
        weldrel.Table({"k": bad}, dictionaries={"k": (b"A", b"N", b"R")})


# -- the kernel and its combine ---------------------------------------------


def _word_partials(per_lane: list) -> np.ndarray:
    """Kernel partials of one group and one 32-bit container whose count,
    low-word and high-word rows hold ``per_lane`` in every lane of every
    chunk (2 chunks)."""
    rows = np.array(per_lane, np.int64)[:, None]
    return np.broadcast_to(rows, (2, 3, 128)).astype(np.int32)


@pytest.mark.parametrize("lane", [2 ** 24 + 1, 2 ** 31 - 1])
def test_combine_is_exact_past_2_24_and_2_31(lane):
    part = _word_partials([lane, lane, lane])
    counts, (s,) = sr.combine_partials(jnp.asarray(part), 1, [1])
    tot = 2 * 128 * lane
    assert int(counts[0]) == tot
    assert int(s[0]) == tot + tot * 2 ** 16
    # the same partials added in int32 wrap, and in f32 round
    with np.errstate(over="ignore"):
        assert int(part[:, 0].sum(dtype=np.int32)) != tot
    assert int(part[:, 0].astype(np.float32).sum(dtype=np.float32)) != tot


def test_combine_is_exact_at_its_bounds():
    """Every partial at its extreme, over the most chunks the int32
    combine takes: the digit sums reach their bounds without wrapping."""
    for lane in (2 ** 31 - 1, -(2 ** 31) + 1):
        part = np.full((sr.MAX_CHUNKS, 5, 128), lane, np.int32)
        counts, (a, b) = sr.combine_partials(jnp.asarray(part), 1, [1, 1])
        tot = sr.MAX_CHUNKS * 128 * lane
        assert int(counts[0]) == tot
        assert int(a[0]) == int(b[0]) == tot + tot * 2 ** 16


def test_kernel_partials_over_several_chunks_equal_numpy(monkeypatch):
    """A chunk bounds what one int32 lane adds; shrink it so a small input
    spans several, and check every word against NumPy."""
    monkeypatch.setattr(sr, "CHUNK_ROWS", 4 * 1024)
    n, g = 20_000, 5
    keys = rng.integers(-1, g + 1, n).astype(np.int32)
    v32 = rng.integers(-2 ** 31, 2 ** 31, n).astype(np.int32)
    v64 = rng.integers(-2 ** 62, 2 ** 62, n)
    per = sr.containers([v32.dtype, v64.dtype])
    tops = tuple(t for c in per for t in c)
    planes = sr.group_planes(jnp.asarray(keys), [jnp.asarray(v32),
                                                 jnp.asarray(v64)],
                             block=1024)
    part = sr.group_sum_words(planes, g, tops, block=1024, interpret=True)
    assert part.shape[0] == 5  # 20 steps of 1024 rows, 4 a chunk
    counts, (s32, s64) = sr.combine_partials(part, g, [1, 2])
    for k in range(g):
        m = keys == k
        assert int(counts[k]) == m.sum()
        assert int(s32[k]) == int(v32[m].astype(np.int64).sum())
        assert int(s64[k]) == int(v64[m].sum())


@pytest.mark.parametrize("impl", ["ref", "interpret"])
def test_group_sum_exact_wraps_like_numpy_int64(impl):
    n, g = 3_000, 3
    keys = rng.integers(0, g, n).astype(np.int32)
    big = np.full(n, 2 ** 62 + 12_345, np.int64)  # sums pass 2**63
    counts, (s,) = ops.group_sum_exact(jnp.asarray(keys), [jnp.asarray(big)],
                                       g, impl=impl)
    with np.errstate(over="ignore"):
        for k in range(g):
            assert int(s[k]) == int(big[keys == k].sum())
            assert int(counts[k]) == int((keys == k).sum())


# -- the planner -------------------------------------------------------------


def _plan(t, keys, impl="pallas", n=60_012_150, **kw):
    sq = weldrel.Query(t).filter(t.col("l_shipdate") <= np.int32(CUT)) \
        .stage().group_agg(keys, {"s": (t.col("l_quantity")
                                        .astype(np.int64), "+")}, **kw)
    prog = sq.program()
    shapes = {k: (n,) for k in prog.inputs}
    st: dict = {}
    plan_kernels(optimize(prog.expr, stats={}, input_shapes=shapes),
                 input_shapes=shapes, stats=st, mode="auto", impl=impl)
    return st["kernelplan"]


def test_declared_dictionary_key_routes_on_the_tpu_gate():
    t = weldrel.Table(decimal_lineitem(100, 1), dictionaries=DICTS)
    kp = _plan(t, [t.col("l_returnflag"), t.col("l_linestatus")])
    assert kp["routed"] == {"dict_group_sum": 1}
    assert kp["rejected"] == {}
    assert (kp["dense_group.keys"], kp["dense_group.words"]) == (6, 3)


def test_undeclared_two_column_key_is_still_refused_as_packed():
    t = weldrel.Table(decimal_lineitem(100, 1))
    kp = _plan(t, [t.col("l_returnflag"), t.col("l_linestatus")],
               capacity=8)
    assert kp["routed"] == {}
    assert any("packed 64-bit key" in c["why"] for c in kp["costs"])
    assert "dense_group.keys" not in kp


def test_a_value_shared_by_two_aggregates_still_routes():
    """Q1's discounted price is summed and also multiplied into the
    charge: each use fuses its own copy into the one group-by loop, so
    the pass routes as it does when every value is built apart."""
    from repro.core.passes import loop_count

    t = weldrel.Table(decimal_lineitem(100, 1), dictionaries=DICTS)
    prog = q1(t, entry=lambda q: q.stage()).program()
    shapes = {k: (60_012_150,) for k in prog.inputs}
    opt_stats: dict = {}
    opt = optimize(prog.expr, stats=opt_stats, input_shapes=shapes)
    assert opt_stats["inline.recomputed"] >= 1
    assert loop_count(opt) == 1
    st: dict = {}
    plan_kernels(opt, input_shapes=shapes, stats=st, mode="auto",
                 impl="pallas")
    kp = st["kernelplan"]
    assert kp["routed"] == {"dict_group_sum": 1}
    assert (kp["dense_group.keys"], kp["dense_group.words"]) == (6, 15)
