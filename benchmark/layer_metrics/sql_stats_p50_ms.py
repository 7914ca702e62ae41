"""Median time of the refresh's px/sql_stats request, client's clock."""

from ..readers import percentile, request_ms


def read(ctx):
    return percentile(list(request_ms(ctx, "sql_stats").values()), 50)
