"""Mean time per query in weldtrace's ``serve.request`` that none of its
direct children covers: stitching the program, forming the compile-cache
key, and whatever else the server does for a request outside a span, in
the traced run's span half."""
from bench import request_spans


def read(run):
    return request_spans.self_ms(run, "serve.request")
