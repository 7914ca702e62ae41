"""The largest remap table a refresh's window folds read as an operand:
the ``remap_entries`` attribute of the PEM's ``device.dispatch`` spans
of fold programs (a dictionary-side UDF's image of its column's
dictionary, padded to its bucket). The largest over a refresh's
requests, median over the window's refreshes. Nothing on a program
whose spans carry no ``remap_entries``."""

from ..readers import percentile
from ..span_readers import DISPATCH, by_qid, named


def read(ctx):
    entries = {
        qid: max((s.attributes["remap_entries"] for s in named(t, DISPATCH)
                  if "remap_entries" in s.attributes), default=None)
        for qid, t in by_qid(ctx, "pem", "fragment").items()
    }
    largest = [
        max(entries[r["qid"]] for r in recs)
        for recs in ctx["window"]["refreshes"]
        if all(entries.get(r["qid"]) is not None for r in recs)
    ]
    return percentile(largest, 50)
