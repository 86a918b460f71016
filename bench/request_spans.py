"""Weldtrace's spans of the traced run's span half, by request, for the
metrics that read the program's own spans per query.

``obs.request`` gives each ``serve.request`` an id (``req``), and every
span opened inside it on the worker's thread carries the same id, as
does its ``serve.queue`` record.  Ids grow in the order requests start,
and the profiled half traces nothing, so the span half's requests are
the last ``run["span_queries"]`` ids that opened a ``serve.request``.
The spans come from ``run["span_log"]`` where the run holds one, else
from the process's own log.  A program whose spans carry no ``req``
gives no requests, and every reader then returns None.
"""
from __future__ import annotations

REQUEST = "serve.request"


def by_request(run: dict):
    """The spans of each request of the span half, oldest first; None
    where the run has no span half or its spans carry no request."""
    n = run.get("span_queries")
    if not n:
        return None
    log = run.get("span_log")
    if log is None:
        from repro.core import obs

        log = obs.spans()
    reqs: dict = {}
    for sp in log:
        r = getattr(sp, "req", None)
        if r is not None:
            reqs.setdefault(r, []).append(sp)
    served = sorted(r for r, sps in reqs.items()
                    if any(sp.name == REQUEST for sp in sps))
    return [reqs[r] for r in served[-n:]] or None


def _per_query(run: dict, value) -> float | None:
    """The sum over the span half's requests of ``value(spans)``, which
    gives None for a request without the span, over their number; None
    where no request has it."""
    reqs = by_request(run)
    if not reqs:
        return None
    got = [v for v in (value(sps) for sps in reqs) if v is not None]
    if not got:
        return None
    return sum(got) / len(reqs)


def _named(sps: list, name: str) -> list:
    return [sp for sp in sps if sp.name == name and sp.dur_ns is not None]


def mean_ms(run: dict, name: str) -> float | None:
    """Mean time per query in spans named ``name``, in ms."""
    def total(sps):
        hit = _named(sps, name)
        return sum(sp.dur_ns for sp in hit) / 1e6 if hit else None

    return _per_query(run, total)


def mean_mb(run: dict, name: str, counter: str = "bytes") -> float | None:
    """Mean per query of the ``counter`` of spans named ``name``, in MB
    (1e6 bytes)."""
    def total(sps):
        hit = [sp for sp in _named(sps, name) if counter in sp.counters]
        return sum(sp.counters[counter] for sp in hit) / 1e6 if hit else None

    return _per_query(run, total)


def self_ms(run: dict, name: str = REQUEST) -> float | None:
    """Mean per query of the time in spans named ``name`` that none of
    their direct children covers, in ms."""
    def own(sps):
        hit = _named(sps, name)
        if not hit:
            return None
        out = 0
        for sp in hit:
            lo, hi = sp.start_ns, sp.start_ns + sp.dur_ns
            kids = sorted(
                (max(c.start_ns, lo), min(c.start_ns + c.dur_ns, hi))
                for c in sps
                if c.parent == sp.sid and c.dur_ns is not None)
            covered, at = 0, lo
            for s, e in kids:
                s = max(s, at)
                if e > s:
                    covered += e - s
                    at = e
            out += sp.dur_ns - covered
        return out / 1e6

    return _per_query(run, own)
