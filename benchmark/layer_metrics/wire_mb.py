"""Bytes a refresh's bridge payloads carried from the PEM to the Kelvin:
the PEM trace's ``usage.wire_bytes`` (the keyed states of the script's
aggregate chains). Summed over a refresh's requests, median over the
window's refreshes, in MB."""

from ..span_readers import by_qid, median_per_refresh


def read(ctx):
    wire = median_per_refresh(ctx, {
        qid: t.usage.wire_bytes
        for qid, t in by_qid(ctx, "pem", "fragment").items()
        if hasattr(t.usage, "wire_bytes")
    })
    return None if wire is None else wire / 1e6
