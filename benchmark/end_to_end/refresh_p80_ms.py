"""80th percentile of the window's refresh times: the highest that
50 or more refreshes support with ten samples beyond it."""

from ..readers import percentile, refresh_ms


def read(ctx):
    return percentile(refresh_ms(ctx), 80)
