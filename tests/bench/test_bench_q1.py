"""The query module and metrics of ``tpch-sf10-dec.q1``: the exact f32 ->
hundredths conversion at the domains' edges, the kernel's roofline
reader, and a wrong integer sum caught by the check."""
from __future__ import annotations

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_tiny  # noqa: E402,F401  (puts the checkout on sys.path)
from bench import harness  # noqa: E402
from bench.queries import q1  # noqa: E402

ROOFLINE = harness._module(
    os.path.join(bench_tiny.ROOT, "bench", "metrics",
                 "group_reduce_roofline.py"), "group_reduce_roofline")


def test_hundredths_are_exact_over_the_domains():
    cents = np.arange(90_000, 10_495_001, 7, dtype=np.int64)
    cents = np.append(cents, 10_495_000)
    as_f32 = (cents.astype(np.float32) / np.float32(100))  # the generator's
    assert (q1.hundredths(as_f32) == cents).all()
    for k in range(11):
        assert q1.hundredths(np.array([k / 100], np.float32))[0] == k


def test_group_reduce_roofline_reads_the_kernels_own_ops():
    ops = [["%_gse.1 = s32[15,90,128]{2,1,0} custom-call(...)", 3e-3],
           ["__gse.2___s32_15_90_128", 1e-3],
           ["%fusion.16 = (s32[60012150]) fusion(...)", 9e-3],
           ["%_gsex = s32[1] custom-call()", 5e-3]]
    run = {"device_trace": {"queries": 2, "breakdown": {"device_ops": ops}},
           "essential_bytes": 819e6, "peaks": {"hbm_bw": 819e9}}
    # 1 ms of HBM traffic over 2 ms of kernel per query
    assert ROOFLINE.read(run) == 50.0
    run["device_trace"]["breakdown"]["device_ops"] = ops[2:]
    assert ROOFLINE.read(run) is None


def test_a_wrong_q1_sum_is_not_correct(tmp_path, monkeypatch):
    """One integer sum of q1's decoded answer off by one: every answer of
    the window fails its check."""
    from repro.core import runtime

    root = bench_tiny.tiny_root(tmp_path)
    decode = runtime.decode_value

    def bump(v, ty):
        out = decode(v, ty)
        if isinstance(out, dict) and out:
            k = min(out)
            out = dict(out)
            out[k] = (out[k][0] + 1,) + tuple(out[k][1:])
        return out

    runtime.clear_cache()
    monkeypatch.setattr(runtime, "decode_value", bump)
    result = bench_tiny.run(root, "tpch-sf10-dec.q1", seconds=0.3)
    runtime.clear_cache()
    assert result["attempted"] > 0
    assert result["correct"] is False
    assert result["failed"] == result["attempted"]
    assert result["checks"]["mismatches"]["value"] >= 1
