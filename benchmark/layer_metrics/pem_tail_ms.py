"""The PEM's last ``device.wait`` end to its ``publish`` end: the
payload built (``payload``), the trace's sinks (``trace.sinks``: usage,
metrics, export, the telemetry fold) and the bridge payloads and stats
published (``publish``). The head of ``tail_ms``. Summed over a
refresh's requests, median over the window's refreshes."""

from ..span_readers import median_per_refresh, named, requests


def read(ctx):
    out = {}
    for qid, (_b, pem, dev) in requests(ctx).items():
        publish = named(pem, "publish")
        if publish:
            out[qid] = (max(s.end_ns for s in publish) - dev[1]) / 1e6
    return median_per_refresh(ctx, out)
