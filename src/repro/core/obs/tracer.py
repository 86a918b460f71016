"""weldtrace: a zero-dependency span tracer for the evaluation pipeline.

Spans are nested wall-clock intervals with free-form tags and counters.
Tracing is OFF by default; when disabled, ``span()`` hands back a shared
no-op object so instrumented code pays one flag check per call site.
Enable with ``repro.obs.enable()`` or ``WELD_TRACE=1`` in the
environment.

Each span knows its ``parent`` (the span open on the same thread when it
opened) and the request it belongs to (``req``): ``request()`` opens a
span with a fresh request id, and every span opened inside it on that
thread inherits the id.  ``record()`` files an interval that started
elsewhere, such as a request's wait in a queue.

While tracing is on, every span opened by ``span()``/``request()`` also
enters a ``jax.profiler.TraceAnnotation`` of the same name (with its
``req`` as metadata), so a running ``jax.profiler`` trace holds the
program's spans in its host plane, on the clock of the device's ops.

Finished spans accumulate in a process-global list (pre-order: a span is
registered when it *opens*, its duration is filled in when it closes) and
can be exported as Chrome-trace/Perfetto JSON (``to_chrome``) or a
human-readable tree (``format_tree``).
"""
from __future__ import annotations

import itertools
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation

__all__ = [
    "Span",
    "enable",
    "disable",
    "enabled",
    "clear",
    "span",
    "request",
    "record",
    "event",
    "mark",
    "spans",
    "spans_since",
    "to_chrome",
    "dump_chrome",
    "format_tree",
]

ENV_TRACE = "WELD_TRACE"


def _env_enabled(env: Optional[dict] = None) -> bool:
    v = (env if env is not None else os.environ).get(ENV_TRACE, "")
    return str(v).strip().lower() not in ("", "0", "false", "no", "off")


class Span:
    """One timed interval.  ``dur_ns`` is None while the span is open.

    ``sid`` numbers the span in the log; ``parent`` is the ``sid`` of the
    span that was open on the same thread when this one opened (None at
    the top); ``req`` is the id of the request the span belongs to (None
    outside any ``request()``)."""

    __slots__ = ("name", "tags", "counters", "start_ns", "dur_ns",
                 "depth", "tid", "sid", "parent", "req", "_ann")

    def __init__(self, name: str, tags: Optional[Dict[str, Any]] = None,
                 depth: int = 0, tid: int = 0, parent: Optional[int] = None,
                 req: Optional[int] = None):
        self.name = name
        self.tags: Dict[str, Any] = dict(tags) if tags else {}
        self.counters: Dict[str, float] = {}
        self.start_ns = time.perf_counter_ns()
        self.dur_ns: Optional[int] = None
        self.depth = depth
        self.tid = tid
        self.sid = 0
        self.parent = parent
        self.req = req
        self._ann = None

    def set(self, key: str, value: Any) -> "Span":
        self.tags[key] = value
        return self

    def count(self, key: str, delta: float = 1) -> "Span":
        self.counters[key] = self.counters.get(key, 0) + delta
        return self

    def __enter__(self) -> "Span":
        return self

    def __exit__(self, *exc) -> None:
        _close(self)

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        dur = "open" if self.dur_ns is None else f"{self.dur_ns / 1e3:.1f}us"
        return f"Span({self.name!r}, {dur}, tags={self.tags})"


class _NoopSpan:
    """Shared do-nothing span returned while tracing is disabled."""

    __slots__ = ()

    def set(self, key: str, value: Any) -> "_NoopSpan":
        return self

    def count(self, key: str, delta: float = 1) -> "_NoopSpan":
        return self

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc) -> None:
        pass

    # mirror Span's readable attrs so callers can poke them unconditionally
    name = ""
    tags: Dict[str, Any] = {}
    counters: Dict[str, float] = {}
    start_ns = 0
    dur_ns = 0
    depth = 0
    tid = 0
    sid = 0
    parent = None
    req = None


NOOP = _NoopSpan()

_enabled = _env_enabled()
_lock = threading.Lock()
_spans: List[Span] = []
_tls = threading.local()
_sids = itertools.count(1)
_requests = itertools.count(1)


def enable() -> None:
    """Turn tracing on for the whole process."""
    global _enabled
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False


def enabled() -> bool:
    return _enabled


def clear() -> None:
    """Drop all recorded spans (open stacks on other threads survive)."""
    with _lock:
        _spans.clear()


def _stack() -> List[Span]:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


def _register(sp: Span) -> None:
    with _lock:
        sp.sid = next(_sids)
        _spans.append(sp)


def _child(name: str, tags: dict, req: Optional[int] = None) -> Span:
    """A registered span under the one open on this thread, in whose
    request it is unless given its own."""
    st = _stack()
    top = st[-1] if st else None
    if req is None and top is not None:
        req = top.req
    sp = Span(name, tags, depth=len(st), tid=threading.get_ident(),
              parent=None if top is None else top.sid, req=req)
    _register(sp)
    return sp


def _open(name: str, tags: dict, req: Optional[int]) -> Span:
    sp = _child(name, tags, req)
    _stack().append(sp)
    sp._ann = (TraceAnnotation(name) if sp.req is None
               else TraceAnnotation(name, req=sp.req))
    sp._ann.__enter__()
    return sp


def span(name: str, **tags):
    """Open a span.  Use as a context manager::

        with obs.span("optimize", passes=6) as sp:
            ...
            sp.set("iterations", 3)

    Returns the shared no-op span when tracing is disabled.
    """
    if not _enabled:
        return NOOP
    return _open(name, tags, None)


def request(name: str, **tags):
    """Open a span that starts a new request: it gets a fresh ``req``,
    which every span opened inside it on this thread inherits."""
    if not _enabled:
        return NOOP
    return _open(name, tags, next(_requests))


def record(name: str, start_ns: int, end_ns: int,
           req: Optional[int] = None, **tags):
    """File a finished interval on the ``perf_counter_ns`` clock, such as
    one that began on another thread.  It has no parent and, having no
    live extent, no profiler annotation."""
    if not _enabled:
        return NOOP
    sp = Span(name, tags, tid=threading.get_ident(), req=req)
    sp.start_ns = start_ns
    sp.dur_ns = end_ns - start_ns
    _register(sp)
    return sp


def _close(sp: Span) -> None:
    sp.dur_ns = time.perf_counter_ns() - sp.start_ns
    if sp._ann is not None:
        sp._ann.__exit__(None, None, None)
        sp._ann = None
    st = _stack()
    # tolerate out-of-order exits (exceptions unwind the whole stack)
    while st and st[-1] is not sp:
        st.pop()
    if st:
        st.pop()


def event(name: str, **tags):
    """Record an instantaneous (zero-duration) span."""
    if not _enabled:
        return NOOP
    sp = _child(name, tags)
    sp.dur_ns = 0
    return sp


def mark() -> int:
    """A position in the span log; pair with :func:`spans_since`."""
    with _lock:
        return len(_spans)


def spans() -> List[Span]:
    with _lock:
        return list(_spans)


def spans_since(pos: int) -> List[Span]:
    with _lock:
        return list(_spans[pos:])


# ---------------------------------------------------------------- exports

def _args_of(sp: Span) -> Dict[str, Any]:
    args: Dict[str, Any] = {"id": sp.sid}
    if sp.parent is not None:
        args["parent"] = sp.parent
    if sp.req is not None:
        args["req"] = sp.req
    for k, v in sp.tags.items():
        try:
            json.dumps(v)
            args[k] = v
        except (TypeError, ValueError):
            args[k] = repr(v)
    for k, v in sp.counters.items():
        args[f"count.{k}"] = v
    return args


def to_chrome(span_list: Optional[List[Span]] = None) -> dict:
    """Chrome-trace ("trace event") JSON object.  Load the dumped file at
    ``chrome://tracing`` or https://ui.perfetto.dev."""
    sl = spans() if span_list is None else span_list
    events = []
    for sp in sl:
        events.append({
            "name": sp.name,
            "ph": "X",
            "ts": sp.start_ns / 1e3,          # Chrome wants microseconds
            "dur": (sp.dur_ns or 0) / 1e3,
            "pid": os.getpid(),
            "tid": sp.tid,
            "args": _args_of(sp),
        })
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def dump_chrome(path: str, span_list: Optional[List[Span]] = None) -> str:
    with open(path, "w") as f:
        json.dump(to_chrome(span_list), f)
    return path


def format_tree(span_list: Optional[List[Span]] = None,
                min_ns: int = 0) -> str:
    """Human-readable indented tree of the recorded spans."""
    sl = spans() if span_list is None else span_list
    lines = []
    base = min((sp.depth for sp in sl), default=0)
    for sp in sl:
        if sp.dur_ns is not None and sp.dur_ns < min_ns and sp.dur_ns > 0:
            continue
        pad = "  " * (sp.depth - base)
        dur = "..." if sp.dur_ns is None else f"{sp.dur_ns / 1e6:10.3f} ms"
        bits = [f"{k}={v}" for k, v in sp.tags.items()]
        bits += [f"{k}={v:g}" for k, v in sp.counters.items()]
        tagtxt = (" [" + ", ".join(bits) + "]") if bits else ""
        lines.append(f"{pad}{sp.name:<{max(1, 40 - len(pad))}} {dur}{tagtxt}")
    return "\n".join(lines)
