"""The PEM's root start to its first ``device.dispatch`` start: the
engine's host work before the chip has any (``plan.walk``,
``fragment.bind``, ``window.select`` and what none of them covers).
With the broker's stages and the execute message's ``bus.deliver`` it
makes up ``head_ms``. Summed over a refresh's requests, median over the
window's refreshes."""

from ..span_readers import median_per_refresh, requests


def read(ctx):
    return median_per_refresh(ctx, {
        qid: (dev[0] - pem.root.start_ns) / 1e6
        for qid, (_b, pem, dev) in requests(ctx).items()
    })
