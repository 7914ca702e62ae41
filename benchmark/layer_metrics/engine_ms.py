"""The query spans on the PEM engine's tracer, summed over a refresh's
requests, median over the window's refreshes."""

from ..readers import engine_ms, per_refresh, percentile


def read(ctx):
    return percentile(per_refresh(ctx, engine_ms(ctx)), 50)
