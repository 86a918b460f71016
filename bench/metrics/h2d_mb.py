"""Mean MB (1e6 bytes) per query handed to ``jnp.asarray`` for upload,
from the ``bytes`` counter of weldtrace's ``encode`` span, in the traced
run's span half."""
from bench import request_spans


def read(run):
    return request_spans.mean_mb(run, "encode")
