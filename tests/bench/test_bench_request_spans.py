"""The per-request readers of weldtrace's spans on a hand-made span log:
each metric, the self time of ``serve.request``, which requests count,
and None where a span or the request ids are absent; and the entries in
BENCHMARK.json that name them."""
from __future__ import annotations

import json
import os
import sys
import types

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_tiny  # noqa: E402
from bench import harness  # noqa: E402

MS = 1_000_000  # ns
CELLS = ["tpch-sf10.q6", "tpch-sf1.join-m1", "tpch-sf1.join-mn"]
NEW = {  # name: (unit, layer)
    "queue_ms": ("ms", "serve"), "serve_self_ms": ("ms", "serve"),
    "upload_wait_ms": ("ms", "runtime"), "execute_ms": ("ms", "device"),
    "fetch_ms": ("ms", "runtime"), "h2d_mb": ("MB", "runtime"),
    "d2h_mb": ("MB", "runtime"), "finalize_ms": ("ms", "frames"),
}


def _sp(sid, name, start_ms, dur_ms, req, parent=None, **counters):
    return types.SimpleNamespace(
        sid=sid, name=name, start_ns=int(start_ms * MS),
        dur_ns=int(dur_ms * MS), req=req, parent=parent, counters=counters)


def _request(base, req, t0, fetched):
    """One request at ``t0`` ms: 2 ms queued, a 100-ms serve.request whose
    direct children cover [t0+5, t0+30) and [t0+25, t0+90) (overlapping)
    and one child that runs 5 ms past its end."""
    r = base
    return [
        _sp(r, "serve.request", t0, 100, req),
        _sp(r + 1, "encode", t0 + 5, 25, req, r, bytes=4_000_000),
        _sp(r + 2, "weld.run", t0 + 25, 65, req, r),
        _sp(r + 3, "upload", t0 + 26, 10, req, r + 2),
        _sp(r + 4, "execute", t0 + 36, 40, req, r + 2),
        _sp(r + 5, "decode", t0 + 76, 14, req, r + 2),
        _sp(r + 6, "fetch", t0 + 77, 8, req, r + 5, bytes=fetched),
        _sp(r + 7, "frames.finalize", t0 + 95, 10, req, r),
        _sp(r + 8, "serve.queue", t0 - 2, 2, req),
    ]


def _log():
    # an older request (warm-up) that the span half does not hold, then
    # the half's two requests, and spans outside any request
    return (_request(1, 1, 0, 0)
            + [_sp(20, "weld.compile", 150, 3, None)]
            + _request(30, 2, 200, 1_000_000)
            + _request(40, 3, 400, 3_000_000))


def _read(name, run):
    return harness._module(
        os.path.join(bench_tiny.ROOT, "bench", "metrics", name + ".py"),
        f"test_metric_{name}").read(run)


def test_each_reader_on_a_hand_made_log():
    run = {"span_log": _log(), "span_queries": 2}
    got = {name: _read(name, run) for name in NEW}
    # self: 100 - union([5,30), [25,90), [95,100)) = 100 - 90
    assert got == {
        "queue_ms": pytest.approx(2.0), "serve_self_ms": pytest.approx(10.0),
        "upload_wait_ms": pytest.approx(10.0),
        "execute_ms": pytest.approx(40.0), "fetch_ms": pytest.approx(8.0),
        "h2d_mb": pytest.approx(4.0), "d2h_mb": pytest.approx(2.0),
        "finalize_ms": pytest.approx(10.0)}


def test_a_reader_without_its_span_or_request_ids_gives_none():
    log = [sp for sp in _log() if sp.name != "fetch"]
    run = {"span_log": log, "span_queries": 2}
    assert _read("fetch_ms", run) is None
    assert _read("d2h_mb", run) is None
    assert _read("execute_ms", run) == pytest.approx(40.0)
    for sp in log:  # a program whose spans name no request
        sp.req = None
    for name in NEW:
        assert _read(name, run) is None, name
        assert _read(name, {"span_log": _log()}) is None, name


def test_only_the_span_halfs_requests_count():
    run = {"span_log": _log(), "span_queries": 1}
    assert _read("d2h_mb", run) == pytest.approx(3.0)
    run = {"span_log": _log(), "span_queries": 5}
    assert _read("d2h_mb", run) == pytest.approx(4.0 / 3)


def test_benchmark_json_names_each_reader_for_every_cell():
    with open(os.path.join(bench_tiny.ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    entries = {m["name"]: m for m in spec["per_layer"]}
    for name, (unit, layer) in NEW.items():
        m = entries[name]
        assert (m["unit"], m["layer"], m["source"], m["moves"]) == (
            unit, layer, "program_span", "latency_p50_ms")
        assert m["workloads"] == CELLS
    for cell in CELLS:
        found = {m["name"] for m in
                 harness.load_cell(cell, bench_tiny.ROOT)["per_layer"]}
        assert set(NEW) <= found, cell


def test_a_traced_run_reports_them_and_uploads_the_referenced_columns(
        tmp_path):
    workload = "tpch-sf1.join-m1"
    root = bench_tiny.tiny_root(tmp_path)
    result = bench_tiny.run(root, workload, traced=True, seconds=0.6)
    assert result["correct"] is True
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(NEW) <= set(got)
    cell = harness.load_cell(workload, root)
    data, _ = harness.prepare(cell, 2 ** 33 + 11)
    columns = sum(v.nbytes for cols in data.values() for v in cols.values())
    # the referenced columns, and the few scalars the program adds
    assert columns <= got["h2d_mb"] * 1e6 <= columns + 1024
    assert got["d2h_mb"] > 0 and got["execute_ms"] > 0
