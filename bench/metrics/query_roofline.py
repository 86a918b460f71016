"""The query's share of its HBM roofline, in percent: the bytes the query
must move (each input column read once, the result written once, from
shapes) over the chip's HBM peak times the device's busy time per query
in the profiled half."""


def read(run):
    t = run.get("device_trace")
    nbytes = run.get("essential_bytes")
    if not t or not t["queries"] or t["busy_s"] <= 0 or not nbytes:
        return None
    per_query_s = t["busy_s"] / t["queries"]
    return nbytes / (run["peaks"]["hbm_bw"] * per_query_s) * 100
