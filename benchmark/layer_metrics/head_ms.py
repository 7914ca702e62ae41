"""Broker root start to the PEM's first ``device.dispatch`` start:
everything a request does before the chip has work. Summed over a
refresh's requests, median over the window's refreshes."""

from ..span_readers import median_per_refresh, requests


def read(ctx):
    return median_per_refresh(ctx, {
        qid: (dev[0] - b.root.start_ns) / 1e6
        for qid, (b, _pem, dev) in requests(ctx).items()
    })
