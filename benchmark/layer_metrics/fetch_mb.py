"""Bytes a refresh's results and shipped states crossed from the device
to the host by: the engines' ``usage.bytes_fetched`` (the ``bytes`` of
their ``device.fetch`` spans and of the ``device.wait`` spans that fetch
by one batched get), PEM and Kelvin. Summed over a refresh's requests,
median over the window's refreshes, in MB. Nothing on a program whose
usage record has no such counter."""

from ..span_readers import median_per_refresh
from .dict_udf_strings import engine_traces


def read(ctx):
    fetched = median_per_refresh(ctx, {
        qid: sum(t.usage.bytes_fetched for t in traces)
        for qid, traces in engine_traces(ctx).items()
        if all(hasattr(t.usage, "bytes_fetched") for t in traces)
    })
    return None if fetched is None else fetched / 1e6
