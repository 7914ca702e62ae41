"""The PEM's first ``device.dispatch`` start to its last ``device.wait``
end: with ``head_ms`` and ``tail_ms`` it makes up the broker's root
span, request by request. Summed over a refresh's requests, median
over the window's refreshes."""

from ..span_readers import median_per_refresh, requests


def read(ctx):
    return median_per_refresh(ctx, {
        qid: (dev[1] - dev[0]) / 1e6
        for qid, (_b, _pem, dev) in requests(ctx).items()
    })
