"""Median latency, from a stream starting a request (building its query)
to the finalized host answer, over every request of the window."""
import numpy as np


def read(run):
    w = run.get("window")
    if not w or not w["latencies_s"]:
        return None
    return float(np.percentile(w["latencies_s"], 50)) * 1e3
