"""Share of the profiled window in which no operation ran on the device,
in percent: 1 - the union of the device-op intervals over the window."""


def read(run):
    t = run.get("device_trace")
    if not t or t["window_s"] <= 0:
        return None
    return (1 - t["busy_s"] / t["window_s"]) * 100
