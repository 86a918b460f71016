#!/usr/bin/env python3
"""Run one cell of the chip benchmark and print its result line.

    python3 bench/run.py --workload tpch-sf10.q6 --seed 7 --seconds 40 \\
        --trace 0

With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` its per-layer ones, from a window split in two halves: the
first with the profiler on (device busy and idle time, the breakdown),
the second with weldtrace on (the program's spans).  The last line of
standard output is one JSON object with ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` when traced) and,
last, ``checks``: each number compared with its limit, which are also
the last lines of standard error.  Without a TPU, with fewer chips than
the cell asks for, or on a chip whose peaks ``bench/peaks.py`` does not
hold, the run exits with code 2 and prints no result.
"""
from __future__ import annotations

import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True,
                    help="a cell of BENCHMARK.json, <config>.<traffic>")
    ap.add_argument("--seed", type=int, required=True,
                    help="draws the tables (any whole number below 2**64)")
    ap.add_argument("--seconds", type=float, required=True,
                    help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness

    with harness.fresh_state(ROOT):
        cell = harness.load_cell(args.workload, ROOT)
        try:
            device = harness.device_info(cell["chips"])
        except harness.NoChip as e:
            print(f"bench: {e}; the benchmark runs on the chip only",
                  file=sys.stderr)
            return 2
        result = harness.run_cell(cell, args.seed, args.seconds,
                                  bool(args.trace), device, STARTED)
    harness.print_checks(result)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
