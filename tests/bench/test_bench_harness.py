"""The harness refuses to run off the chip, and its check catches a
broken timed path."""
from __future__ import annotations

import os
import subprocess
import sys
import types

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench_tiny  # noqa: E402
from bench import harness  # noqa: E402


def _run_py(root, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, os.path.join(root, "bench", "run.py"), *args],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)


def test_exits_without_a_tpu_and_prints_no_result(tmp_path):
    root = bench_tiny.tiny_root(tmp_path)
    os.symlink(os.path.join(bench_tiny.ROOT, "src"),
               os.path.join(root, "src"))
    p = _run_py(root, "--workload", "tpch-sf10.q6", "--seed", "1",
                "--seconds", "1", "--trace", "0")
    assert p.returncode == 2, p.stderr
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_exits_when_only_the_benchmark_is_there(tmp_path):
    root = bench_tiny.tiny_root(tmp_path)
    p = _run_py(root, "--workload", "tpch-sf1.join-mn", "--seed", "1",
                "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


def _fake_jax_devices(monkeypatch, kind, n=1):
    import jax

    dev = types.SimpleNamespace(platform="tpu", device_kind=kind)
    monkeypatch.setattr(jax, "devices", lambda *a: [dev] * n)


def test_device_kind_without_peaks_is_refused(monkeypatch):
    _fake_jax_devices(monkeypatch, "TPU v99 imaginary")
    with pytest.raises(harness.NoChip, match="no published peaks"):
        harness.device_info(1)


def test_too_few_chips_is_refused(monkeypatch):
    _fake_jax_devices(monkeypatch, "TPU v5 lite", n=1)
    with pytest.raises(harness.NoChip, match="asks for 4 chips"):
        harness.device_info(4)
    _fake_jax_devices(monkeypatch, "TPU v5 lite", n=4)
    assert harness.device_info(4)["peaks"]["hbm_bw"] == 819e9


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_tiny.tiny_root(tmp_path_factory.mktemp("faults"))


def _alter_answer(monkeypatch):
    """An answer altered where it is produced: the runtime's decode."""
    from repro.core import runtime

    decode = runtime.decode_value

    def bump(x):
        if isinstance(x, dict):
            return {k: bump(v) for k, v in x.items()}
        if isinstance(x, tuple):
            return tuple(bump(v) for v in x)
        if isinstance(x, np.ndarray) and x.size:
            x = x.copy()
            x[x.size // 2] += 1
            return x
        if isinstance(x, (float, np.floating)):
            return x * 1.01
        return x

    monkeypatch.setattr(runtime, "decode_value",
                        lambda v, ty: bump(decode(v, ty)))


def _drop_half_the_rows(monkeypatch):
    """Half of the batch left out: every column shipped to the device
    loses its second half."""
    from repro.core import lazy

    encode = lazy.ArrayEncoder.encode
    monkeypatch.setattr(lazy.ArrayEncoder, "encode",
                        lambda self, obj: encode(self, obj)[
                            : max(len(obj) // 2, 1)])


@pytest.mark.parametrize("fault", [_alter_answer, _drop_half_the_rows],
                         ids=["answer_altered", "half_the_rows"])
@pytest.mark.parametrize("workload", bench_tiny.workloads())
def test_broken_timed_path_is_not_correct(root, monkeypatch, workload,
                                          fault):
    from repro.core import runtime

    runtime.clear_cache()
    fault(monkeypatch)
    result = bench_tiny.run(root, workload, seconds=0.3)
    runtime.clear_cache()
    assert result["attempted"] > 0
    assert result["correct"] is False
    assert result["failed"] > 0
    assert list(result)[-1] == "checks"


@pytest.mark.parametrize("traced", [False, True], ids=["trace0", "trace1"])
def test_sound_run_is_correct_and_reports_the_cells_metrics(root, traced):
    result = bench_tiny.run(root, "tpch-sf1.join-mn", traced=traced,
                            seconds=0.6)
    assert result["correct"] is True and result["failed"] == 0
    assert list(result)[-1] == "checks"
    if traced:
        assert {"frames_ms", "encode_ms", "decode_ms",
                "compile_s"} <= set(result["metrics"])
        assert {"busy_s", "window_s"} <= set(result["device"])
    else:
        assert set(result["metrics"]) == {"qps", "latency_p50_ms",
                                          "setup_s"}
