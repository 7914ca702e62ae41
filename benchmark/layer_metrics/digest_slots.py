"""Groups x centroids of the largest quantile digest a refresh's window
folds hold: the ``digest_slots`` attribute of the PEM's
``device.dispatch`` spans of fold programs (beside ``fold``, ``digests``
and ``digest_bins``). The largest over a refresh's requests, median over
the window's refreshes. Nothing on a program whose spans carry no
``digest_slots``."""

from ..readers import percentile
from ..span_readers import DISPATCH, by_qid, named


def read(ctx):
    slots = {
        qid: max((s.attributes["digest_slots"] for s in named(t, DISPATCH)
                  if "digest_slots" in s.attributes), default=None)
        for qid, t in by_qid(ctx, "pem", "fragment").items()
    }
    largest = [
        max(slots[r["qid"]] for r in recs)
        for recs in ctx["window"]["refreshes"]
        if all(slots.get(r["qid"]) is not None for r in recs)
    ]
    return percentile(largest, 50)
