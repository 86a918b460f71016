"""The trace reduction on 18 ms recorded on a TPU v5e in the profiled half
of a ``tpch-sf10.q6`` run: three Q6 requests, five ops each, back to back.
Every expected number below is worked out by hand from the fixture."""
from __future__ import annotations

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_tiny  # noqa: E402,F401
from bench import trace  # noqa: E402

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "trace_v5e_q6.json")

# per request (ns): mul.1, pad.4, compare_and_fusion, pad_convert_fusion,
# _frs.1 (the filter_reduce_sum kernel), each starting 1-2 ns after the
# last one ended
DURATIONS = [
    [1070891, 730531, 953124, 367013, 1074141],
    [1071545, 730573, 953293, 366091, 1073525],
    [1070421, 730820, 953106, 366490, 1074096],
]


@pytest.fixture(scope="module")
def reduced():
    with open(FIXTURE) as f:
        return trace.reduce(json.load(f))


def test_busy_is_the_sum_of_the_back_to_back_ops(reduced):
    assert reduced["window_s"] == pytest.approx(0.018)
    assert sum(map(sum, DURATIONS)) == 12_585_660
    assert reduced["busy_s"] == pytest.approx(12_585_660e-9)


def test_ops_ranked_by_their_summed_time(reduced):
    ops = dict(reduced["breakdown"]["device_ops"])
    assert list(ops) == ["%_frs.1", "%mul.1", "%compare_and_fusion",
                         "%pad.4", "%pad_convert_fusion"]
    assert ops["%_frs.1"] == pytest.approx(
        (1074141 + 1073525 + 1074096) * 1e-9)
    assert ops["%mul.1"] == pytest.approx(
        (1070891 + 1071545 + 1070421) * 1e-9)


def test_gaps_longer_than_a_microsecond_named_by_the_host(reduced):
    # window starts 4,668,061 ns before the first op; the last op ends at
    # 16,181,522 + 1,074,096 = 17,255,618 ns, 744,382 ns before its end;
    # the gaps between ops (1-943 ns) are jitter and are not listed
    assert reduced["breakdown"]["idle_gaps"] == [
        ["bench.wait_result", pytest.approx(4_668_061e-9)],
        ["bench.build_query", pytest.approx(744_382e-9)],
    ]
