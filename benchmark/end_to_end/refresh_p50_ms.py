"""Median refresh time of the window, on the client's clock."""

from ..readers import percentile, refresh_ms


def read(ctx):
    return percentile(refresh_ms(ctx), 50)
