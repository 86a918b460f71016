"""weldtrace on the exact group route: the host finish of a group-by over
dictionary keys is a ``group.finish`` span of the request, inside
``frames.finalize``, counting its groups and decoded words; the
set-up's ``kernelplan`` span counts the dense key range and the words
the kernel sums per row, and, for a join, the output columns its
probe's one front-pack sort carries."""
import numpy as np
import pytest

from repro.core import obs
from repro.core.serve import QueryServer
from repro.frames import weldrel


@pytest.fixture(autouse=True)
def clean_tracer(tmp_path, monkeypatch):
    monkeypatch.setenv("WELD_COST_LEDGER",
                       str(tmp_path / "cost_ledger.jsonl"))
    monkeypatch.setenv("WELD_AUTOTUNE_CACHE",
                       str(tmp_path / "autotune.json"))
    obs.disable()
    obs.clear()
    yield
    obs.disable()
    obs.clear()


def _staged(t):
    v = t.col("v").astype(np.int64)
    return weldrel.Query(t).stage().group_agg(
        [t.col("a"), t.col("b")],
        {"s": (v, "+"), "m": (v, "avg")},
        kernelize="always", kernel_impl="interpret")


def test_group_finish_carries_the_request_and_its_counters():
    rng = np.random.default_rng(7)
    n = 2_000
    t = weldrel.Table({"a": rng.integers(0, 3, n).astype(np.int32),
                       "b": rng.integers(0, 2, n).astype(np.int32),
                       "v": rng.integers(0, 10 ** 6, n).astype(np.int32)},
                      dictionaries={"a": (b"A", b"N", b"R"),
                                    "b": (b"F", b"O")})
    obs.enable()
    with QueryServer(workers=1) as srv:
        got = srv.submit(_staged(t)).result()
    spans = obs.spans()
    finish = [s for s in spans if s.name == "group.finish"]
    assert len(finish) == 1
    (fin,) = finish
    req = [s for s in spans if s.name == "serve.request"][0]
    parent = {s.sid: s for s in spans}[fin.parent]
    assert fin.req == req.req and parent.name == "frames.finalize"
    # one shared sum and the count per group (the avg reuses the sum)
    assert fin.counters == {"groups": len(got), "words": 2 * len(got)}
    plan = [s for s in spans if s.name == "kernelplan"]
    assert plan and plan[0].counters == {"dense_group.keys": 6,
                                         "dense_group.words": 3}


def test_undeclared_keys_open_no_group_finish():
    t = weldrel.Table({"a": np.arange(10, dtype=np.int32) % 3,
                       "b": np.arange(10, dtype=np.int32) % 2,
                       "v": np.arange(10, dtype=np.int32)})
    obs.enable()
    with QueryServer(workers=1) as srv:
        srv.submit(_staged(t)).result()
    assert not [s for s in obs.spans() if s.name == "group.finish"]


@pytest.mark.parametrize("how,pred,cols", [("inner", False, 4),
                                           ("left", False, 0),
                                           ("left", True, 4),
                                           ("anti", False, 2)])
def test_kernelplan_span_counts_the_front_packed_columns(how, pred, cols):
    from repro.core import runtime

    rng = np.random.default_rng(16)
    n, k = 777, 37
    t = weldrel.Table({"key": rng.integers(0, 50, n), "lv": rng.random(n)})
    r = weldrel.Table({"key": np.arange(k), "rv": rng.random(k),
                       "rw": rng.integers(0, 9, k)})
    q = weldrel.Query(t)
    if pred:
        q = q.filter(t.col("lv") > 0.5)
    runtime.clear_cache()
    obs.enable()
    q.join(r, on="key", how=how, kernelize="always", kernel_impl="ref")
    plan = [s for s in obs.spans() if s.name == "kernelplan"]
    assert len(plan) == 1
    assert plan[0].counters == {"frontpack.cols": cols}
