"""Bytes of quantile digests a refresh's bridge payloads carried from
the PEM to the Kelvin: the PEM trace's ``usage.digest_bytes`` (the
[slots, K] mean and weight planes of the ``quantiles`` aggregates in the
shipped states; part of the bytes ``usage.wire_bytes`` counts, which
``wire_mb`` reads in the cells it lists: not this one yet). Summed over
a refresh's requests, median over the window's refreshes, in MB. While
each plucked quantile keeps a state of its own this is ``fetch_mb`` less
the integer planes' 12 MB; it parts from it when one digest is shared.
Nothing on a program without the counter."""

from ..span_readers import by_qid, median_per_refresh


def read(ctx):
    shipped = median_per_refresh(ctx, {
        qid: t.usage.digest_bytes
        for qid, t in by_qid(ctx, "pem", "fragment").items()
        if hasattr(t.usage, "digest_bytes")
    })
    return None if shipped is None else shipped / 1e6
