"""Mean time per query in weldtrace's ``group.finish`` span, in the
traced run's span half: weldrel's host finish of a group-by over
dictionary keys (decoding the dense group ids to dictionary values, the
averages from the exact sums, the ordering), inside ``frames.finalize``."""
from bench import request_spans


def read(run):
    return request_spans.mean_ms(run, "group.finish")
