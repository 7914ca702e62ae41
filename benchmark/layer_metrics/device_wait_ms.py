"""The PEM's ``device.wait`` spans: the query thread asks for the result
until its bytes are on the host. Summed over a refresh's requests,
median over the window's refreshes."""

from ..span_readers import WAIT, median_per_refresh, requests, total_ms


def read(ctx):
    return median_per_refresh(ctx, {
        qid: total_ms(pem, WAIT) for qid, (_b, pem, _d) in requests(ctx).items()
    })
