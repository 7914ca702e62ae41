"""Busy time of the program's background ring (heartbeat turns, sweeps,
telemetry folds, pollers, collections of 1 ms or more) a second of
window: the union of the entries, so a part inside its turn counts
once."""

from ..span_readers import background_intervals, covered_ns, window_ns


def read(ctx):
    entries = background_intervals(ctx)
    if entries is None:
        return None
    lo, hi = window_ns(ctx)
    return covered_ns(entries, lo, hi) / 1e6 / ((hi - lo) / 1e9)
