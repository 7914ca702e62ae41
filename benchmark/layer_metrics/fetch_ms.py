"""Time inside the engines' ``device.fetch`` spans, PEM and Kelvin: in
a ``device.wait``, from the instant the path's own sync has returned to
the last leaf on the host, a copy a leaf. The program has run by then:
the spans say "waiting for the device" and the device is idle. Summed
over a refresh's requests, median over the window's refreshes. Nothing
on a program that stamps no such span."""

from ..span_readers import median_per_refresh, total_ms
from .dict_udf_strings import engine_traces

FETCH = "device.fetch"


def read(ctx):
    requests = engine_traces(ctx)
    if not any(s.name == FETCH for traces in requests.values()
               for t in traces for s in t.spans):
        return None
    return median_per_refresh(ctx, {
        qid: sum(total_ms(t, FETCH) for t in traces)
        for qid, traces in requests.items()
    })
