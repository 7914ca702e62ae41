"""The largest capacity a refresh's window folds were compiled at: the
``slots`` attribute of the PEM's ``device.dispatch`` spans of fold
programs (beside ``fold`` and ``group``). The largest over a refresh's
requests, median over the window's refreshes. Nothing on a program
whose spans carry no ``slots``."""

from ..readers import percentile
from ..span_readers import DISPATCH, by_qid, named


def read(ctx):
    slots = {
        qid: max((s.attributes["slots"] for s in named(t, DISPATCH)
                  if "slots" in s.attributes), default=None)
        for qid, t in by_qid(ctx, "pem", "fragment").items()
    }
    largest = [
        max(slots[r["qid"]] for r in recs)
        for recs in ctx["window"]["refreshes"]
        if all(slots.get(r["qid"]) is not None for r in recs)
    ]
    return percentile(largest, 50)
