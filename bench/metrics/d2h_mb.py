"""Mean MB (1e6 bytes) per query copied from the device to the host,
padding included, from the ``bytes`` counter of weldtrace's ``fetch``
span, in the traced run's span half."""
from bench import request_spans


def read(run):
    return request_spans.mean_mb(run, "fetch")
