"""Mean time per query to build its ``StagedQuery`` through weldrel (IR
construction and a join's host pre-scan of the keys), from the
benchmark's own span, in the traced run's span half."""


def read(run):
    spans, n = run.get("spans"), run.get("span_queries")
    if not spans or not n or not spans.get("frames"):
        return None
    return sum(spans["frames"]) / n * 1e3
