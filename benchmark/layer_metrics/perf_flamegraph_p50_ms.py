"""Median time of the refresh's px/perf_flamegraph request, client's
clock."""

from ..readers import percentile, request_ms


def read(ctx):
    return percentile(list(request_ms(ctx, "perf_flamegraph").values()), 50)
