"""Quantile digests a refresh's window folds carry: the ``digests``
attribute of the PEM's ``device.dispatch`` spans of fold programs (the
[slots, K] carries the program holds: one a ``quantiles`` aggregate, so
three plucked quantiles of one column are three), summed over a
request's fold dispatches and over a refresh's requests, median over the
window's refreshes. A request that folds its range a window a dispatch
counts its carries once a window. Nothing on a program whose spans carry
no ``digests``."""

from ..span_readers import DISPATCH, by_qid, median_per_refresh, named


def read(ctx):
    states = {}
    for qid, t in by_qid(ctx, "pem", "fragment").items():
        held = [s.attributes["digests"] for s in named(t, DISPATCH)
                if "digests" in s.attributes]
        if held:
            states[qid] = sum(held)
    return median_per_refresh(ctx, states)
