"""Mean time per query from ``QueryServer.submit`` to a worker starting
it (weldtrace's ``serve.queue``), in the traced run's span half."""
from bench import request_spans


def read(run):
    return request_spans.mean_ms(run, "serve.queue")
