"""The PEM's last ``device.wait`` end to the broker root's end: payload,
publish, bus hops, the Kelvin's merge, ``await``, ``finish``. Summed
over a refresh's requests, median over the window's refreshes."""

from ..span_readers import median_per_refresh, requests


def read(ctx):
    return median_per_refresh(ctx, {
        qid: (b.root.end_ns - dev[1]) / 1e6
        for qid, (b, _pem, dev) in requests(ctx).items()
    })
