"""Mean time per query in weldtrace's ``fetch`` span, in the traced run's
span half: the device-to-host copies of the answer, inside ``decode``."""
from bench import request_spans


def read(run):
    return request_spans.mean_ms(run, "fetch")
