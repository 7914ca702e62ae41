"""Bytes of shipped states the Kelvin put on its device to merge them:
the Kelvin trace's ``usage.merge_upload_bytes`` (the ``upload_bytes`` of
its ``merge_finalize`` dispatches: the compacted states of every
payload, host arrays that a PEM fetched from its own device and the bus
carried: the device -> host -> device hop of a state). Summed over a
refresh's requests, median over the window's refreshes, in MB. Nothing
on a program without the counter."""

from ..span_readers import by_qid, median_per_refresh


def read(ctx):
    uploaded = median_per_refresh(ctx, {
        qid: t.usage.merge_upload_bytes
        for qid, t in by_qid(ctx, "kelvin", "merge").items()
        if hasattr(t.usage, "merge_upload_bytes")
    })
    return None if uploaded is None else uploaded / 1e6
