"""What the readers of the program's own spans share (``exec/trace.py``
stamps every span's two ends on ``time.perf_counter_ns()``: the clock
of the driver's ``t0``/``t1``, so a client's request, the program's
spans and its background ring lie on one axis).

Every function gives ``None`` (or an empty table) where the program
has no such span, clock or ring, as a program from before these spans
has not: a reader then reports nothing."""

from __future__ import annotations

from .readers import per_refresh, percentile
from .xplane import _clip, _union

DISPATCH, WAIT = "device.dispatch", "device.wait"


def clocked(ctx) -> bool:
    """Whether the program's spans carry the one clock."""
    return any(hasattr(t.root, "start_ns")
               for traces in ctx["spans"].values() for t in traces)


def by_qid(ctx, tracer: str, kind: str) -> dict:
    return {t.qid: t for t in ctx["spans"][tracer]
            if t.qid and t.kind == kind and hasattr(t.root, "start_ns")}


def named(trace, name: str) -> list:
    return [s for s in trace.spans if s.name == name and s.end_ns]


def total_ms(trace, name: str) -> float:
    return sum(s.end_ns - s.start_ns for s in named(trace, name)) / 1e6


def device_interval(trace):
    """(first ``device.dispatch`` start, last ``device.wait`` end) of a
    trace, ns; None when it enqueued nothing or never waited."""
    starts = [s.start_ns for s in named(trace, DISPATCH)]
    ends = [s.end_ns for s in named(trace, WAIT)]
    if not starts or not ends:
        return None
    return min(starts), max(ends)


def requests(ctx) -> dict:
    """{qid: (broker trace, the PEM's fragment trace, its device
    interval)} of the requests whose both traces are in hand."""
    pem = by_qid(ctx, "pem", "fragment")
    out = {}
    for qid, b in by_qid(ctx, "broker", "distributed").items():
        dev = device_interval(pem[qid]) if qid in pem else None
        if dev is not None and b.root.end_ns:
            out[qid] = (b, pem[qid], dev)
    return out


def median_per_refresh(ctx, by_request: dict):
    """Summed over a refresh's requests, median over the refreshes."""
    if not by_request:
        return None
    return percentile(per_refresh(ctx, by_request), 50)


def covered_ns(intervals, lo: float, hi: float) -> float:
    """Length of the union of ``intervals`` inside [lo, hi]."""
    return sum(e - s for s, e in _union(_clip(intervals, lo, hi)))


def window_ns(ctx) -> tuple:
    """The window's two ends on the spans' clock."""
    w = ctx["window"]
    return w["t_open"] * 1e9, w["t_close"] * 1e9


def fragment_device_intervals(ctx) -> list:
    """Per fragment of every engine trace (PEM and Kelvin): [first
    ``device.dispatch`` start, last ``device.*`` end]."""
    out = []
    for tracer in ("pem", "kelvin"):
        for t in ctx["spans"][tracer]:
            frags: dict = {}
            for s in t.spans:
                if s.name in (DISPATCH, WAIT) and getattr(s, "end_ns", 0):
                    frags.setdefault(s.parent_id, []).append(s)
            for spans in frags.values():
                starts = [s.start_ns for s in spans if s.name == DISPATCH]
                if starts:
                    out.append((min(starts), max(s.end_ns for s in spans)))
    return out


def background_intervals(ctx):
    """The background ring's entries as (start, end) ns; None where the
    program has no ring."""
    try:
        from pixie_tpu.exec import trace
    except ImportError:
        return None
    ring = getattr(trace, "background", None)
    if ring is None:
        return None
    lo, _hi = window_ns(ctx)
    return [(e["start_ns"], e["end_ns"]) for e in ring.entries(int(lo))]
