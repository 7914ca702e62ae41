"""How much of what the PEM's fold programs were handed had been asked
for: 100 x the rows in the requests' ranges (``range_rows``) over the
rows the programs folded (``rows``), both attributes of the PEM's
``device.dispatch`` spans of fold programs whose windows came resident
with a range (beside ``fold``, ``group`` and ``slots``). Summed over a
refresh's requests, median over the window's refreshes. What is missing
from 100 is padding the device pays for: a window's tail past the rows
in range, up to the length the program was compiled at. Nothing on a
program whose spans carry no ``rows``."""

from ..readers import percentile
from ..span_readers import DISPATCH, by_qid, named


def read(ctx):
    handed = {}  # qid -> (rows in range, rows folded)
    for qid, t in by_qid(ctx, "pem", "fragment").items():
        folds = [s.attributes for s in named(t, DISPATCH)
                 if "rows" in s.attributes]
        if folds:
            handed[qid] = (sum(a["range_rows"] for a in folds),
                           sum(a["rows"] for a in folds))
    fill = [
        100.0 * sum(handed[r["qid"]][0] for r in recs)
        / sum(handed[r["qid"]][1] for r in recs)
        for recs in ctx["window"]["refreshes"]
        if all(r["qid"] in handed for r in recs)
    ]
    return percentile(fill, 50)
