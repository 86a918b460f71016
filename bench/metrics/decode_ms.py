"""Mean time per query in weldtrace's ``decode`` span (the result back
to host values), in the traced run's span half."""


def read(run):
    spans, n = run.get("spans"), run.get("span_queries")
    if not spans or not n or not spans.get("decode"):
        return None
    return sum(spans["decode"]) / n * 1e3
