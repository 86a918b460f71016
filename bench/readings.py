#!/usr/bin/env python3
"""The readings a cell's limits are set from, in one process.

    python3 bench/readings.py --workload tpch-sf1.join-m1 --seeds 1,2,3 \\
        --control-seeds 4,5,6 --seconds 6

For each seed of ``--seeds`` the program serves the cell's traffic for a
short window, as a benchmark run does, and every answer is compared with
the reference: the worst of each number is the program's reading on that
seed (the lower readings).  For each seed of ``--control-seeds`` the
control, the reference computed in bfloat16, stands in the program's
place (the upper readings).  One JSON line per seed, then a summary:
the largest program reading and the smallest control reading of each
number, beside the limit the mix holds.  Benchmark runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _seeds(text):
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, default=[])
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness

    with harness.fresh_state(ROOT):
        cell = harness.load_cell(args.workload, ROOT)
        try:
            harness.device_info(cell["chips"])
        except harness.NoChip as e:
            print(f"readings: {e}", file=sys.stderr)
            return 2
        from repro.core.serve import QueryServer

        query, params = cell["query"], cell["params"]
        lower: dict = {}
        upper: dict = {}
        for seed in args.seeds:
            data, tables = harness.prepare(cell, seed)
            with QueryServer(workers=cell["streams"]) as srv:
                harness.warm_up(srv, cell, tables)
                win = harness.drive(srv, cell, tables, args.seconds)
            want = query.reference(data, params)
            checked = harness.check(cell, win["records"], want)
            got = {k: v["value"] for k, v in checked["numbers"].items()}
            for k, v in got.items():
                lower[k] = max(lower.get(k, v), v)
            print(json.dumps({"seed": seed, "kind": "program",
                              "answers": len(win["records"]),
                              "failed": checked["failed"],
                              "errors": checked["errors"],
                              "numbers": got}), flush=True)
        for seed in args.control_seeds:
            data, _ = harness.prepare(cell, seed)
            got = query.compare(query.control(data, params),
                                query.reference(data, params))
            for k, v in got.items():
                upper[k] = min(upper.get(k, v), v)
            print(json.dumps({"seed": seed, "kind": "control",
                              "numbers": got}), flush=True)
    print(json.dumps({"summary": cell["name"], "lower": lower,
                      "upper": upper, "limits": cell["mix"]["limits"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
