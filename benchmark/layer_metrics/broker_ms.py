"""Request time on the client's clock less the PEM engine's query span,
summed over a refresh's requests, median over the window's refreshes:
planning, dispatch, bus, merge on the Kelvin, forwarding and decoding."""

from ..readers import engine_ms, per_refresh, percentile, request_ms


def read(ctx):
    engine = engine_ms(ctx)
    rest = {qid: ms - engine[qid] for qid, ms in request_ms(ctx).items()
            if qid in engine}
    return percentile(per_refresh(ctx, rest), 50)
