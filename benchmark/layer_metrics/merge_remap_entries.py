"""Entries of the key-id remaps the Kelvin's merge programs read: the
Kelvin trace's ``usage.merge_remap_entries`` (the ``remap_entries`` of
its ``merge_finalize`` dispatches: one table, padded to its bucket, a
payload and string key column whose dictionary is not a prefix of the
canonical one). Summed over a refresh's requests, median over the
window's refreshes. 0 means every agent's dictionaries were equal: the
cell would then measure four copies of a one-PEM merge. Nothing on a
program without the counter."""

from ..span_readers import by_qid, median_per_refresh


def read(ctx):
    return median_per_refresh(ctx, {
        qid: t.usage.merge_remap_entries
        for qid, t in by_qid(ctx, "kelvin", "merge").items()
        if hasattr(t.usage, "merge_remap_entries")
    })
