"""The PEM's ``device.dispatch`` spans: what enqueueing its programs
costs the host. Summed over a refresh's requests, median over the
window's refreshes."""

from ..span_readers import DISPATCH, median_per_refresh, requests, total_ms


def read(ctx):
    return median_per_refresh(ctx, {
        qid: total_ms(pem, DISPATCH)
        for qid, (_b, pem, _d) in requests(ctx).items()
    })
