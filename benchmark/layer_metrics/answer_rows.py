"""Rows of the answers a refresh's requests handed back: the engines'
``usage.answer_rows`` (the ``rows`` of their ``payload`` spans of kind
``result``: one a result sink, on the engine that runs it, here the
Kelvin). Summed over a refresh's requests, median over the window's
refreshes. Nothing on a program whose usage record has no such
counter."""

from ..span_readers import median_per_refresh
from .dict_udf_strings import engine_traces


def usage_counter(ctx, name: str):
    """``usage.<name>`` summed over the engines' traces of a request,
    then ``median_per_refresh``; None where no usage record has it."""
    return median_per_refresh(ctx, {
        qid: sum(getattr(t.usage, name) for t in traces)
        for qid, traces in engine_traces(ctx).items()
        if all(hasattr(t.usage, name) for t in traces)
    })


def read(ctx):
    return usage_counter(ctx, "answer_rows")
