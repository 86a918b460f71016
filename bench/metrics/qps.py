"""Queries completed per second: the window's correct answers over the
time from the window's start to the last of them."""


def read(run):
    w = run.get("window")
    if not w or not w["n_ok"] or w["last_done_s"] <= 0:
        return None
    return w["n_ok"] / w["last_done_s"]
