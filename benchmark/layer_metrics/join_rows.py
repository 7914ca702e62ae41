"""Rows the engines' joins took in: ``build_rows`` + ``probe_rows`` of
the ``join`` spans, PEM and Kelvin (the spans' ``rows_out`` is the
usage record's ``join_rows_out``). Summed over a refresh's requests,
median over the window's refreshes. Nothing on a program whose engines
leave no ``join`` span."""

from ..span_readers import by_qid, median_per_refresh, named


def join_traces(ctx) -> dict:
    """{qid: the engine traces of the request that hold a ``join`` span}."""
    out: dict = {}
    for tracer, kind in (("pem", "fragment"), ("kelvin", "merge")):
        for qid, t in by_qid(ctx, tracer, kind).items():
            if named(t, "join"):
                out.setdefault(qid, []).append(t)
    return out


def read(ctx):
    return median_per_refresh(ctx, {
        qid: sum(s.attributes["build_rows"] + s.attributes["probe_rows"]
                 for t in traces for s in named(t, "join"))
        for qid, traces in join_traces(ctx).items()
    })
