"""A configuration, a traffic mix, a query and a per-layer metric added as
new files, with their entries in BENCHMARK.json, are found by name and
run, with no edit to any file the benchmark already has."""
from __future__ import annotations

import hashlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_tiny  # noqa: E402

QUERY = '''
import numpy as np
from bench import compare as cmp

READS = {"lineitem": ["l_quantity", "l_extendedprice"]}


def reads(params):
    return READS


def tables(data, params):
    from repro.frames import weldrel
    return {"lineitem": weldrel.Table(dict(data["lineitem"]))}


def build(tables, params):
    from repro.frames import weldrel
    li = tables["lineitem"]
    q = weldrel.Query(li).filter(
        li.col("l_quantity") < np.float32(params["below"]))
    return q.stage().agg({"price": (li.col("l_extendedprice"), "+")})


def reference(data, params):
    li = data["lineitem"]
    m = li["l_quantity"] < params["below"]
    return {"price": float(li["l_extendedprice"][m].astype(np.float64)
                           .sum())}


def control(data, params):
    return reference({"lineitem": {c: cmp.to_bf16(v) for c, v in
                                   data["lineitem"].items()}}, params)


def compare(got, want):
    return cmp.scalars(got, want)


def essential_bytes(data, params, want):
    return sum(v.nbytes for v in data["lineitem"].values()) + 4
'''

METRIC = '''
def read(run):
    return run.get("span_queries")
'''


def _digests(root):
    out = {}
    for d, _, files in os.walk(os.path.join(root, "bench")):
        for f in files:
            if "__pycache__" not in d:
                p = os.path.join(d, f)
                with open(p, "rb") as fh:
                    out[p] = hashlib.sha256(fh.read()).hexdigest()
    return out


def test_new_config_mix_query_and_metric_are_found_by_name(tmp_path):
    root = bench_tiny.tiny_root(tmp_path)
    before = _digests(root)
    b = os.path.join(root, "bench")
    with open(os.path.join(b, "configs", "tiny-db.json"), "w") as f:
        json.dump({"name": "tiny-db", "scale_factor": 0.0005, "streams": 2,
                   "columns": {"lineitem": {"l_quantity": "float32",
                                            "l_extendedprice": "float32"}}},
                  f)
    with open(os.path.join(b, "mixes", "cheap.json"), "w") as f:
        json.dump({"query": "sumprice", "params": {"below": 10},
                   "limits": {"max_rel_err": 1e-5, "mismatches": 0}}, f)
    with open(os.path.join(b, "queries", "sumprice.py"), "w") as f:
        f.write(QUERY)
    with open(os.path.join(b, "metrics", "span_queries.py"), "w") as f:
        f.write(METRIC)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "tiny-db", "source": "test",
                            "file": "bench/configs/tiny-db.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny-db.cheap", "config": "tiny-db",
                              "traffic": "cheap", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "span_queries", "unit": "queries",
                              "better": "higher", "source": "host_clock",
                              "layer": "frames", "moves": "qps",
                              "workloads": ["tiny-db.cheap"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)

    traced = bench_tiny.run(root, "tiny-db.cheap", traced=True,
                            seconds=0.6)
    assert traced["correct"] is True
    assert traced["metrics"]["span_queries"]["value"] >= 1
    assert traced["metrics"]["span_queries"]["unit"] == "queries"
    plain = bench_tiny.run(root, "tiny-db.cheap", seconds=0.3)
    assert plain["correct"] is True
    assert "span_queries" not in plain["metrics"]
    assert "qps" in plain["metrics"]
    after = _digests(root)
    assert {p: after[p] for p in before} == before
    # the metric is the new cell's alone: an old cell does not report it
    old = bench_tiny.run(root, "tpch-sf1.join-mn", traced=True,
                         seconds=0.4)
    assert "span_queries" not in old["metrics"]
