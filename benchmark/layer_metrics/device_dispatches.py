"""Programs enqueued for a request: ``device.dispatch`` spans of the
PEM's fragment and the Kelvin's merge. Summed over a refresh's
requests, median over the window's refreshes."""

from ..span_readers import DISPATCH, by_qid, median_per_refresh, named, requests


def read(ctx):
    kelvin = by_qid(ctx, "kelvin", "merge")
    return median_per_refresh(ctx, {
        qid: len(named(pem, DISPATCH)) + (
            len(named(kelvin[qid], DISPATCH)) if qid in kelvin else 0
        )
        for qid, (_b, pem, _d) in requests(ctx).items()
    })
