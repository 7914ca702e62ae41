"""Median time of the refresh's px/net_flow_graph request, client's clock."""

from ..readers import percentile, request_ms


def read(ctx):
    return percentile(list(request_ms(ctx, "net_flow_graph").values()), 50)
