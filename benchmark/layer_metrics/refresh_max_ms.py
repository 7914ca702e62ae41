"""The slowest refresh of the window (PR 23 saw one of 8 s in a run
whose median was 0.6 s: kept visible)."""

from ..readers import refresh_ms


def read(ctx):
    ms = refresh_ms(ctx)
    return float(ms.max()) if len(ms) else None
