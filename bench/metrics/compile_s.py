"""Seconds in weldtrace's ``weld.compile`` spans during set-up: Weld's
optimize, plan and autotune stages and XLA's compile (or its persistent
cache)."""


def read(run):
    spans = run.get("setup_spans", {}).get("weld.compile")
    if not spans:
        return None
    return sum(spans)
