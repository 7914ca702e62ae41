"""Time inside the engines' ``dict_udf`` spans (one around every bind
of a UDF that maps a column's dictionary: the look-up of its remembered
image, and the UDF over whatever strings the image lacks), PEM and
Kelvin. Summed over a refresh's requests, median over the window's
refreshes; 0 once a request binds nothing. Nothing on a program that
has no such span (told by its usage record's counter)."""

from ..span_readers import median_per_refresh, total_ms
from .dict_udf_strings import engine_traces


def read(ctx):
    return median_per_refresh(ctx, {
        qid: sum(total_ms(t, "dict_udf") for t in traces)
        for qid, traces in engine_traces(ctx).items()
        if all(hasattr(t.usage, "dict_udf_strings") for t in traces)
    })
