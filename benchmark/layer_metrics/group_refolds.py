"""Re-folds after a group-capacity overflow: ``rebucket`` spans of the
PEM's fragment and the Kelvin's merge (0 expected once the capacity is
remembered). Summed over a refresh's requests, median over the window's
refreshes. Nothing on a program that has no such span (its usage record
has no ``rebuckets``)."""

from ..span_readers import by_qid, median_per_refresh, named


def read(ctx):
    pem = by_qid(ctx, "pem", "fragment")
    if not any(hasattr(t.usage, "rebuckets") for t in pem.values()):
        return None
    kelvin = by_qid(ctx, "kelvin", "merge")
    return median_per_refresh(ctx, {
        qid: len(named(t, "rebucket")) + (
            len(named(kelvin[qid], "rebucket")) if qid in kelvin else 0
        )
        for qid, t in pem.items()
    })
