"""Partial-agg states the Kelvin's merges folded: the Kelvin trace's
``usage.merge_payloads`` (the ``payloads`` of its ``merge_finalize``
dispatches: k a merge, folded by k - 1 merges in one program). Summed
over a refresh's requests, median over the window's refreshes: four
PEMs and two scripts a refresh read 8; every one-PEM cell would read 1 a
script. More than a script's k means a merge ran again (a re-fold after
an overflow). Nothing on a program without the counter."""

from ..span_readers import by_qid, median_per_refresh


def read(ctx):
    return median_per_refresh(ctx, {
        qid: t.usage.merge_payloads
        for qid, t in by_qid(ctx, "kelvin", "merge").items()
        if hasattr(t.usage, "merge_payloads")
    })
