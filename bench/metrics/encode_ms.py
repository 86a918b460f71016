"""Mean time per query in weldtrace's ``encode`` span, in the traced
run's span half: the runtime encodes the referenced columns on the host
and hands each to ``jnp.asarray``, which returns once its upload is
queued.  The upload itself is ``upload_ms``."""


def read(run):
    spans, n = run.get("spans"), run.get("span_queries")
    if not spans or not n or not spans.get("encode"):
        return None
    return sum(spans["encode"]) / n * 1e3
