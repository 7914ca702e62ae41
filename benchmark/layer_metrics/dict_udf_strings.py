"""Strings a refresh's dictionary-side UDFs were run on: the engines'
``usage.dict_udf_strings`` (the ``strings`` of their ``dict_udf``
spans: one around every bind of a UDF that maps a column's dictionary),
PEM and Kelvin. Summed over a refresh's requests, median over the
window's refreshes; 0 once the images are remembered. Nothing on a
program whose usage record has no such counter."""

from ..span_readers import by_qid, median_per_refresh


def engine_traces(ctx) -> dict:
    """{qid: the request's engine traces, PEM's and Kelvin's}."""
    out: dict = {}
    for tracer, kind in (("pem", "fragment"), ("kelvin", "merge")):
        for qid, t in by_qid(ctx, tracer, kind).items():
            out.setdefault(qid, []).append(t)
    return out


def read(ctx):
    return median_per_refresh(ctx, {
        qid: sum(t.usage.dict_udf_strings for t in traces)
        for qid, traces in engine_traces(ctx).items()
        if all(hasattr(t.usage, "dict_udf_strings") for t in traces)
    })
