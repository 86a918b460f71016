#!/usr/bin/env python
"""Summarize the weldtrace cost ledger: calibration error per kernel.

The ledger (``~/.cache/weld-repro/cost_ledger.jsonl`` by default, or
``$WELD_COST_LEDGER``) accumulates one record per measured kernel launch
— the planner's roofline ``predicted_ns`` next to the replay's
``measured_ns``.  This CLI groups records by (kernel, dtype,
size-bucket) and reports median predicted/measured times, their ratio,
and the mean |log2 ratio| calibration error.

    PYTHONPATH=src python tools/cost_report.py [--ledger PATH]
        [--kernel NAME] [--json]
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "src")
)

from repro.core.obs import ledger  # noqa: E402


def _calibrate_dump(path: str, kernel: str | None) -> int:
    """Print the gate's own view of the ledger: one JSON row per
    (kernel, dtype, size-bucket) with the median it would overlay and
    whether the group clears the sample floor.  This goes through
    ``kernelplan.calibrate`` itself, so what it prints is BY
    CONSTRUCTION what ``cost.estimate`` would use."""
    from repro.core.kernelplan import calibrate  # noqa: E402

    floor = calibrate.min_samples()
    rows = []
    for (kern, dtype, bucket), g in sorted(calibrate.medians(path).items()):
        if kernel and kern != kernel:
            continue
        rows.append({
            "kernel": kern,
            "dtype": dtype,
            "bucket": bucket,
            "calls": g["calls"],
            "measured_ns_median": g["measured_ns"],
            "eligible": g["calls"] >= floor,
            "min_samples": floor,
        })
    print(json.dumps({"ledger": path, "enabled": calibrate.enabled(),
                      "groups": rows}, indent=1))
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--ledger", default=None,
                    help="ledger path (default: $WELD_COST_LEDGER or "
                         "next to the autotune cache)")
    ap.add_argument("--kernel", default=None,
                    help="only report this kernel")
    ap.add_argument("--json", action="store_true",
                    help="emit the summary rows as JSON")
    ap.add_argument("--calibrate-dump", action="store_true",
                    help="emit the EXACT per-(kernel, dtype, bucket) "
                         "medians the serving cost gate overlays on the "
                         "roofline estimates, as JSON rows")
    args = ap.parse_args()

    path = args.ledger or ledger.ledger_path()
    if args.calibrate_dump:
        return _calibrate_dump(path, args.kernel)
    records = ledger.read(path)
    if args.kernel:
        records = [r for r in records if r.get("kernel") == args.kernel]
    rows = ledger.summarize(records)
    if args.json:
        print(json.dumps({"ledger": path, "records": len(records),
                          "groups": rows}, indent=1))
    else:
        print(f"# ledger: {path} ({len(records)} records)")
        if rows:
            print(ledger.format_report(rows))
        else:
            print("# no records — run Query.explain(analyze=True) on a "
                  "kernelized query")
    return 0


if __name__ == "__main__":
    sys.exit(main())
