"""The exact group kernel's share of Q1's HBM roofline, in percent: the
bytes Q1 must move (``q1.essential_bytes``: its seven columns once, the
answer once) over the chip's HBM peak times the kernel's own device time
per query in the profiled half.  That time is the breakdown's device ops
named after the kernel's jitted wrapper, ``_gse`` in
``repro.kernels.ops``: the trace names the op ``%_gse.<n> = ...``
(``__gse.<n>___...`` once the name is made a plain word); None where the
trace holds no such op."""
import re

KERNEL = re.compile(r"[%_]_gse(\.\d+)?(?=[\s_=]|$)")


def read(run):
    t = run.get("device_trace")
    nbytes = run.get("essential_bytes")
    if not t or not t["queries"] or not nbytes:
        return None
    kernel_s = sum(s for name, s in t["breakdown"]["device_ops"]
                   if KERNEL.match(name))
    if kernel_s <= 0:
        return None
    return nbytes / (run["peaks"]["hbm_bw"] * kernel_s / t["queries"]) * 100
