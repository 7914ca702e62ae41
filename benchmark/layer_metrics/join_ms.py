"""Time inside the engines' ``join`` spans (one around every ``JoinOp``:
the dictionaries' alignment, the strategy's build and probe, the output
rows' assembly), PEM and Kelvin. Summed over a refresh's requests,
median over the window's refreshes. Nothing on a program whose engines
leave no ``join`` span."""

from ..span_readers import median_per_refresh, total_ms
from .join_rows import join_traces


def read(ctx):
    return median_per_refresh(ctx, {
        qid: sum(total_ms(t, "join") for t in traces)
        for qid, traces in join_traces(ctx).items()
    })
