"""The trace reduction on a hand-made trace: busy union, idle gaps and
their labels, and the op ranking, each worked out by hand."""
from __future__ import annotations

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_tiny  # noqa: E402,F401
from bench import trace  # noqa: E402

US = 1_000  # ns
# window [100, 1100) us; ops on chip 0: [150,250) fusion, [200,300) kernel
# (overlaps), [500,600) fusion, [1050,1200) kernel (runs past the end)
EVENTS = {
    "chips": 1,
    "device": [["fusion", 150 * US, 100 * US, 0],
               ["kernel", 200 * US, 100 * US, 0],
               ["fusion", 500 * US, 100 * US, 0],
               ["kernel", 1050 * US, 150 * US, 0],
               ["copy", 20 * US, 50 * US, 0]],       # before the window
    "host": [["bench.window", 100 * US, 1000 * US],
             ["bench.wait_result", 100 * US, 1000 * US],
             ["bench.build_query", 320 * US, 150 * US],  # in [300, 500)
             ["TransferToDevice", 610 * US, 300 * US]],  # in [600, 1050)
}


def test_busy_is_the_union_of_op_intervals_inside_the_window():
    r = trace.reduce(EVENTS)
    # [150,300) + [500,600) + [1050,1100) = 150 + 100 + 50
    assert r["busy_s"] == pytest.approx(300e-6)
    assert r["window_s"] == pytest.approx(1000e-6)


def test_gaps_are_ranked_and_named_by_the_host():
    gaps = trace.reduce(EVENTS)["breakdown"]["idle_gaps"]
    # [600,1050) 450, [300,500) 200, [100,150) 50
    assert [g[0] for g in gaps] == ["TransferToDevice", "bench.build_query",
                                    "bench.wait_result"]
    assert [g[1] for g in gaps] == pytest.approx([450e-6, 200e-6, 50e-6])


def test_ops_are_ranked_by_time_inside_the_window():
    ops = trace.reduce(EVENTS)["breakdown"]["device_ops"]
    # fusion 100 + 100; kernel 100 + 50; copy lies outside
    assert ops == [["fusion", pytest.approx(200e-6)],
                   ["kernel", pytest.approx(150e-6)]]


def test_a_trace_without_the_window_annotation_is_refused():
    with pytest.raises(RuntimeError, match="bench.window"):
        trace.reduce(dict(EVENTS, host=EVENTS["host"][1:]))
