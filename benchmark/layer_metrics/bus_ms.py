"""A request's hops over the bus: the ``bus.deliver`` spans of its three
traces (the execute message onto the PEM's dispatcher thread, each
bridge payload onto the Kelvin's, the results onto the broker's), each
from the enqueue on the subscription's queue to the handler's entry.
Summed over a refresh's requests, median over the window's refreshes.
Nothing on a program that stamps no such span."""

from ..span_readers import median_per_refresh, total_ms
from .unnamed_ms import request_traces

HOP = "bus.deliver"


def read(ctx):
    requests = request_traces(ctx)
    if not any(s.name == HOP for traces in requests.values()
               for t in traces for s in t.spans):
        return None
    return median_per_refresh(ctx, {
        qid: sum(total_ms(t, HOP) for t in traces)
        for qid, traces in requests.items()
    })
