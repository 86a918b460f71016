"""Mean time per query in weldtrace's ``upload`` span, in the traced run's
span half: the launch, queued at once, then the wait for the request's
own inputs to land on the device (behind any other request's uploads)."""
from bench import request_spans


def read(run):
    return request_spans.mean_ms(run, "upload")
