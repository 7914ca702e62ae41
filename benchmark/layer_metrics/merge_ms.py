"""The Kelvin's merge trace, root start to root end: bridge payloads
to a merged, finalized, limited result on the host. ``merge.wait``
(install until the last payload is in) lies before the root and is
left out. Summed over a refresh's requests, median over the window's
refreshes."""

from ..span_readers import by_qid, median_per_refresh


def read(ctx):
    return median_per_refresh(ctx, {
        qid: (t.root.end_ns - t.root.start_ns) / 1e6
        for qid, t in by_qid(ctx, "kelvin", "merge").items()
        if t.root.end_ns
    })
