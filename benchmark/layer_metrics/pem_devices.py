"""Distinct devices the PEMs' programs ran on: the ``device`` attribute
of the ``device.dispatch`` spans of every PEM tracer of the stack
(``pem`` and ``pem.<n>``), distinct over a refresh's requests, median
over the window's refreshes. Four PEMs, a chip each, read 4; fewer means
two nodes shared a chip. Nothing on a program whose spans name no
device."""

from ..readers import percentile
from ..span_readers import DISPATCH, by_qid, named
from .pem_spread_ms import pem_tracers


def read(ctx):
    devices: dict = {}
    for tracer in pem_tracers(ctx):
        for qid, t in by_qid(ctx, tracer, "fragment").items():
            devices.setdefault(qid, set()).update(
                s.attributes["device"] for s in named(t, DISPATCH)
                if "device" in s.attributes)
    distinct = [
        len(set().union(*(devices[r["qid"]] for r in recs)))
        for recs in ctx["window"]["refreshes"]
        if all(devices.get(r["qid"]) for r in recs)
    ]
    return percentile(distinct, 50)
