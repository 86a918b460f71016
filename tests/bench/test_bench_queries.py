"""Each served query of the benchmark equals its plain reference, and the
control (the reference in bfloat16) fails the cell's check."""
from __future__ import annotations

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_tiny  # noqa: E402
from bench import harness  # noqa: E402


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_tiny.tiny_root(tmp_path_factory.mktemp("queries"))


@pytest.mark.parametrize("workload", bench_tiny.workloads())
def test_served_query_equals_reference(root, workload):
    from repro.core.serve import QueryServer

    cell = harness.load_cell(workload, root)
    data, tables = harness.prepare(cell, seed=12345)
    with QueryServer(workers=cell["streams"]) as srv:
        futs = [srv.submit(cell["query"].build(tables, cell["params"]))
                for _ in range(cell["streams"])]
        answers = [f.result() for f in futs]
    want = cell["query"].reference(data, cell["params"])
    limits = cell["mix"]["limits"]
    for got in answers:
        numbers = cell["query"].compare(got, want)
        assert all(numbers[k] <= limits[k] for k in limits), numbers
    if "mismatches" in limits:
        assert all(cell["query"].compare(a, want)["mismatches"] == 0
                   for a in answers)


@pytest.mark.parametrize("workload", bench_tiny.workloads())
def test_control_in_bfloat16_is_not_correct(root, workload):
    cell = harness.load_cell(workload, root)
    data, _ = harness.prepare(cell, seed=2 ** 32 + 3)
    want = cell["query"].reference(data, cell["params"])
    control = cell["query"].control(data, cell["params"])
    numbers = cell["query"].compare(control, want)
    limits = cell["mix"]["limits"]
    assert any(numbers[k] > limits[k] for k in limits), numbers


def test_same_seed_same_tables_other_seed_other_tables(root):
    cell = harness.load_cell("tpch-sf1.join-m1", root)
    a, _ = harness.prepare(cell, seed=5)
    b, _ = harness.prepare(cell, seed=5)
    c, _ = harness.prepare(cell, seed=6)
    col = a["lineitem"]["l_extendedprice"]
    assert (col == b["lineitem"]["l_extendedprice"]).all()
    assert not (col == c["lineitem"]["l_extendedprice"]).all()
