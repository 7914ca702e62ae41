"""A fold's empty group state made: the time inside a request's
``state.init`` spans, one a fold, on every PEM tracer of the stack
(``pem`` and ``pem.<n>``) and on the Kelvin's merge trace (its
re-aggregation of a join's rows is a fold too). Part of ``pem_head_ms``
before a request's first dispatch, of ``device_interval_ms`` and
``merge_ms`` after it. Since PR 48 a span holds ONE program's enqueue
(``programs`` 1, ``leaves``); before it a handful of eager array
constructions, and the two ends were the same. Summed over a refresh's
requests, median over the window's refreshes. Nothing on a program that
stamps no such span."""

from ..span_readers import by_qid, median_per_refresh, total_ms
from .pem_spread_ms import pem_tracers

SPAN = "state.init"


def read(ctx):
    engines = [(tracer, "fragment") for tracer in pem_tracers(ctx)]
    if "kelvin" in ctx["spans"]:
        engines.append(("kelvin", "merge"))
    traces = [t for tracer, kind in engines
              for t in by_qid(ctx, tracer, kind).values()]
    if not any(s.name == SPAN for t in traces for s in t.spans):
        return None
    by_request: dict = {}
    for t in traces:
        by_request[t.qid] = by_request.get(t.qid, 0.0) + total_ms(t, SPAN)
    return median_per_refresh(ctx, by_request)
