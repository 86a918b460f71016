"""Mean time per query in weldtrace's ``execute`` span, in the traced
run's span half: from the request's inputs having landed (its launch
already queued) to its result being ready, so the device's queue (other
requests' work) and its run."""
from bench import request_spans


def read(run):
    return request_spans.mean_ms(run, "execute")
