"""Mean time per query in which a host-to-device upload was in flight:
the union, over the profiled half, of the TPU client relaying each
referenced column out into the chip's tiling (``XlaLinearize``) and of
its copy from issue to done (``bench/trace.py``), over that half's
queries.  Weldtrace's ``encode`` span closes before this work, which
runs on the client's own threads."""


def read(run):
    t = run.get("device_trace")
    if not t or not t["queries"] or not t["upload_s"]:
        return None
    return t["upload_s"] / t["queries"] * 1e3
