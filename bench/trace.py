"""From a profiler trace to the device metrics and the breakdown.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
what the reduction needs, as plain lists that a test fixture can hold:

    {"device": [[name, start_ns, dur_ns, chip], ...],   # op events
     "chips": n,
     "host": [[name, start_ns, dur_ns], ...]}     # host annotations

``reduce`` then gives the profiled window (the benchmark's own
``bench.window`` annotation), the device's busy time (the union of the
op intervals in that window, averaged over the chips), the time in which
a host-to-device upload was in flight, the operations that took most
time (summed over the chips), and the longest idle gaps of any chip,
each named by what the host was doing in it.
"""
from __future__ import annotations

import glob
import os

#: the device line whose events are the executed operations
OP_LINE = "XLA Ops"
WINDOW = "bench.window"
#: host annotations that say only that a stream waits on the server
WAITING = ("bench.wait_result",)
TOP = 10
#: idle gaps shorter than this are the clock's jitter between back-to-back
#: ops, not idleness worth naming
MIN_GAP_NS = 1_000
#: the host events of an upload: the TPU client relays a column out into
#: the chip's tiling, then issues its copy, which is done when it landed
LINEARIZE = "XlaLinearize"
H2D_ISSUE = "tpu::System::TransferToDevice"
H2D_DONE = "tpu::System::TransferToDevice=>IssueEvent=>Done"
#: the name an idle gap gets when an upload was in flight in it
UPLOAD = "host-to-device upload"


def load(trace_dir: str) -> dict:
    """The op events of every TPU plane and the host plane's events from
    the one ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one profiler trace under "
                           f"{trace_dir}, found {len(paths)}")
    data = ProfileData.from_file(paths[0])
    device, host, chips = [], [], 0
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OP_LINE:
                    device += [[e.name, int(e.start_ns), int(e.duration_ns),
                                chips] for e in line.events]
            chips += 1
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [[e.name, int(e.start_ns), int(e.duration_ns)]
                         for e in line.events if e.duration_ns > 0]
    return {"device": device, "chips": chips, "host": host}


def window(events: dict) -> tuple:
    """``(start_ns, end_ns)`` of the benchmark's profiled window."""
    spans = [(s, s + d) for n, s, d in events["host"] if n == WINDOW]
    if len(spans) != 1:
        raise RuntimeError(f"expected one {WINDOW!r} annotation in the "
                           f"trace, found {len(spans)}")
    return spans[0]


def uploads(events: dict) -> list:
    """``[(start, end)]`` in which a host-to-device upload was in flight:
    a column being relaid out, or copies issued and not all done.  A done
    with no copy in flight is of a copy issued before the trace began."""
    host = events["host"]
    marks = sorted([(s, 1) for n, s, _ in host if n == H2D_ISSUE]
                   + [(s + d, -1) for n, s, d in host if n == H2D_DONE])
    copies, in_flight, since = [], 0, None
    for t, step in marks:
        if step > 0:
            if in_flight == 0:
                since = t
            in_flight += 1
        elif in_flight > 0:
            in_flight -= 1
            if in_flight == 0:
                copies.append((since, t))
    return [(s, s + d) for n, s, d in host if n == LINEARIZE] + copies


def union(intervals, lo: int, hi: int) -> list:
    """The union of ``[(start, end)]`` clipped to ``[lo, hi]``, sorted and
    merged."""
    out: list = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def gaps(busy: list, lo: int, hi: int) -> list:
    """The idle intervals of ``[lo, hi]`` between merged busy ones."""
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def label(gap: tuple, host: list) -> str:
    """What the host was doing in an idle gap: the host event that
    overlaps it most, preferring any event to a stream's mere wait on the
    server; ``"none"`` when no host event overlaps it."""
    s, e = gap
    best, best_key = "none", None
    for name, hs, hd in host:
        if name == WINDOW:
            continue
        ov = min(e, hs + hd) - max(s, hs)
        if ov <= 0:
            continue
        key = (name not in WAITING, ov, -hd)
        if best_key is None or key > best_key:
            best, best_key = name, key
    return best


def reduce(events: dict) -> dict:
    """Busy, upload and window seconds, and the breakdown, of one
    trace."""
    lo, hi = window(events)
    upload = union(uploads(events), lo, hi)
    host = events["host"] + [[UPLOAD, s, e - s] for s, e in upload]
    chips = max(events["chips"], 1)
    busy_ns, idle = 0, []
    for chip in range(chips):
        busy = union([(s, s + d) for _, s, d, c in events["device"]
                      if c == chip], lo, hi)
        busy_ns += sum(e - s for s, e in busy)
        idle += gaps(busy, lo, hi)
    ops: dict = {}
    for name, s, d, _ in events["device"]:
        clipped = min(s + d, hi) - max(s, lo)
        if clipped > 0:
            ops[name] = ops.get(name, 0) + clipped
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]
    idle = sorted((g for g in idle if g[1] - g[0] >= MIN_GAP_NS),
                  key=lambda g: g[0] - g[1])[:TOP]
    return {
        "busy_s": busy_ns / chips / 1e9,
        "window_s": (hi - lo) / 1e9,
        "upload_s": sum(e - s for s, e in upload) / 1e9,
        "breakdown": {
            "device_ops": [[n, ns / 1e9] for n, ns in top_ops],
            "idle_gaps": [[label(g, host), (g[1] - g[0]) / 1e9]
                          for g in idle],
        },
    }
