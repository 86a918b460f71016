#!/usr/bin/env python
"""CI explain/trace smoke (run from tools/ci.sh with WELD_TRACE=1).

Compiles a kernelized m:n hash join AND a group-by query with tracing
on, then asserts the whole observability surface end to end:

* the Chrome-trace export is valid JSON with the expected span names
  and monotonic nested spans (children inside their parents);
* ``Query.explain(analyze=True)`` shows ``group_build``/``group_probe``
  launches with BOTH predicted and measured times, from the per-kernel
  spans of its eager replay (the only place the replay runs);
* the cost ledger received records and ``tools/cost_report.py``
  summarizes it without error.

State is confined to a temp directory (autotune cache + ledger) so the
smoke never pollutes — or depends on — the developer's caches.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile

_TOOLS = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(_TOOLS, "..", "src"))

_td = tempfile.mkdtemp(prefix="weld-trace-smoke-")
os.environ["WELD_AUTOTUNE_CACHE"] = os.path.join(_td, "autotune.json")
os.environ["WELD_COST_LEDGER"] = os.path.join(_td, "cost_ledger.jsonl")
os.environ.setdefault("WELD_TRACE", "1")

import numpy as np  # noqa: E402

from repro import obs  # noqa: E402
from repro.frames import weldrel  # noqa: E402


def main() -> int:
    assert obs.enabled(), "WELD_TRACE=1 must enable tracing at import"

    n, k, fanout = 8192, 64, 4
    rng = np.random.RandomState(7)
    rkey = np.repeat(np.arange(k, dtype=np.int64), fanout)
    right = weldrel.Table({"key": rkey, "rate": rng.rand(rkey.size)})
    left = weldrel.Table({
        "key": rng.randint(0, 2 * k, n).astype(np.int64),
        "price": rng.rand(n),
    })

    # -- m:n join under EXPLAIN ANALYZE ---------------------------------
    rep = weldrel.Query(left).explain(analyze=True).join(
        right, on="key", kernelize="always")
    launches = {r["kernel"]: r for r in rep.kernel_spans()}
    for kern in ("group_build", "group_probe"):
        r = launches.get(kern)
        assert r, f"missing measured {kern} launch: {launches}"
        assert r["predicted_ns"] and r["measured_ns"], (kern, r)
        assert f"kernel.{kern}" in {sp.name for sp in rep.spans}, kern
    text = rep.render()
    for needle in ("EXPLAIN ANALYZE", "kernel[group_build]",
                   "kernel[group_probe]", "predicted vs measured"):
        assert needle in text, f"explain output missing {needle!r}"
    print("explain(analyze=True): group_build + group_probe measured OK")

    # -- group-by query, plain tracing: no replay outside explain --------
    pos = obs.mark()
    st: dict = {}
    grouped = weldrel.Query(left).group_agg(
        [left.col("key")], {"s": (left.col("price"), "+")},
        capacity=2 * k, kernelize="auto", collect_stats=st)
    assert grouped, "group-by returned nothing"
    replayed = {sp.name for sp in obs.spans_since(pos)
                if sp.name.startswith(("measure.replay", "kernel."))}
    assert not replayed, f"traced query replayed: {sorted(replayed)}"

    # -- trace export: valid JSON, expected names, monotonic nesting ----
    trace_path = os.path.join(_td, "trace.json")
    obs.dump_chrome(trace_path)
    with open(trace_path) as f:
        trace = json.load(f)
    events = trace["traceEvents"]
    names = {e["name"] for e in events}
    for want in ("weld.evaluate", "optimize", "pass.fusion", "kernelplan",
                 "jit_compile", "upload", "execute", "decode", "fetch",
                 "cache.lookup"):
        assert want in names, f"trace missing span {want!r}: {sorted(names)}"
    assert all(e["ph"] == "X" and e["dur"] >= 0 for e in events)
    # nesting: every span sits inside its parent
    spans = obs.spans()
    by_sid = {sp.sid: sp for sp in spans}
    for sp in spans:
        parent = by_sid.get(sp.parent)
        if parent is not None:
            end = parent.start_ns + (parent.dur_ns or 0)
            assert sp.start_ns >= parent.start_ns, (sp.name, parent.name)
            assert sp.start_ns + (sp.dur_ns or 0) <= end + 1_000_000, \
                (sp.name, parent.name)
    print(f"chrome trace OK: {len(events)} events, nesting monotonic")

    # -- ledger + report CLI --------------------------------------------
    ledger_path = os.environ["WELD_COST_LEDGER"]
    assert os.path.exists(ledger_path), "ledger not written"
    out = subprocess.run(
        [sys.executable, os.path.join(_TOOLS, "cost_report.py"),
         "--ledger", ledger_path],
        capture_output=True, text=True, check=True,
    )
    assert "group_build" in out.stdout and "group_probe" in out.stdout, \
        out.stdout
    print("cost_report.py OK:")
    print(out.stdout)
    return 0


if __name__ == "__main__":
    sys.exit(main())
