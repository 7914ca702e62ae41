"""The broker trace's ``compile`` span (PxL to a verified, bounded
plan), median per request."""

from ..readers import percentile, span_ms


def read(ctx):
    return percentile([span_ms(t, "compile")
                       for t in ctx["spans"]["broker"]], 50)
