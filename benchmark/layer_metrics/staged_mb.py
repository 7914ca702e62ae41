"""Bytes a refresh moved from the host to the device for table windows:
the PEM's ``usage.bytes_staged`` (windows staged for the request) plus
its ``usage.bytes_restaged`` (windows the device cache did not hold and
the scan staged again: padded planes, every column). 0 while every
window in range is resident. Summed over a refresh's requests, median
over the window's refreshes, in MB. A program whose usage record has no
``bytes_restaged`` is read by ``bytes_staged`` alone."""

from ..span_readers import by_qid, median_per_refresh


def read(ctx):
    staged = median_per_refresh(ctx, {
        qid: t.usage.bytes_staged + getattr(t.usage, "bytes_restaged", 0)
        for qid, t in by_qid(ctx, "pem", "fragment").items()
    })
    return None if staged is None else staged / 1e6
