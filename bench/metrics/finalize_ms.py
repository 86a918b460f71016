"""Mean time per query in weldtrace's ``frames.finalize`` span, in the
traced run's span half: weldrel turning the decoded host values into the
query's answer."""
from bench import request_spans


def read(run):
    return request_spans.mean_ms(run, "frames.finalize")
