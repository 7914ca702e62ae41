"""``merge_ordered`` runs of the Kelvin's merges: the Kelvin trace's
``usage.merge_rebins`` (the ``rebins`` of the ``device.wait`` of a
``merge_finalize`` that folded k >= 2 keyed states in one k-way fold:
k - 1 a digest carry where two states filled one merged slot, 0 where
every digest moved to its slot as it was shipped). Summed over a
refresh's requests, median over the window's refreshes: 0 in
``http_cluster_4chip.cluster_recent``, whose service graph's key holds
the pod (the four nodes' edges are disjoint) and whose ``px/http_stats``
holds no digest; 3 would mean one digest merge joined four nodes' groups.
Nothing on a program without the counter."""

from ..span_readers import by_qid, median_per_refresh


def read(ctx):
    return median_per_refresh(ctx, {
        qid: t.usage.merge_rebins
        for qid, t in by_qid(ctx, "kelvin", "merge").items()
        if hasattr(t.usage, "merge_rebins")
    })
