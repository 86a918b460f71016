"""Helpers for the benchmark's CPU tests: a copy of the benchmark whose
configurations are cut to a tiny scale, and a run of one of its cells
with the harness's look for a chip skipped."""
from __future__ import annotations

import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import harness, peaks  # noqa: E402

SF = 0.001
#: what device_info would report for a chip, minus the chip
FAKE_DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1,
               "peaks": peaks.PEAKS["TPU v5 lite"]}


def tiny_root(tmp_path, sf: float = SF) -> str:
    """A checkout holding ``BENCHMARK.json`` and ``bench/`` with every
    configuration at scale factor ``sf``."""
    root = str(tmp_path / "checkout")
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    cfg_dir = os.path.join(root, "bench", "configs")
    for name in os.listdir(cfg_dir):
        path = os.path.join(cfg_dir, name)
        with open(path) as f:
            cfg = json.load(f)
        cfg["scale_factor"] = sf
        with open(path, "w") as f:
            json.dump(cfg, f)
    return root


def run(root: str, workload: str, traced: bool = False,
        seconds: float = 0.5, seed: int = 2 ** 33 + 11) -> dict:
    """One run of a cell on the CPU, as ``bench/run.py`` makes it."""
    cell = harness.load_cell(workload, root)
    return harness.run_cell(cell, seed, seconds, traced, FAKE_DEVICE,
                            time.perf_counter(), log=lambda msg: None)


def workloads() -> list:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]
