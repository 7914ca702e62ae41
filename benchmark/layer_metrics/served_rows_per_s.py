"""Rows in each completed request's time range, summed over the
window's requests, over all the window's seconds: the mean rate, so a
refresh that stalls shows here where the median does not. Per-layer
only: the check's runs spread it by 0.41 % and 1.48 % (PR 24's refusal),
too wide for a bound that would add to what ``refresh_p50_ms`` holds."""

from ..readers import window_s


def read(ctx):
    requests = sum(len(recs) for recs in ctx["window"]["refreshes"])
    if not requests:
        return None
    return requests * ctx["rows_in_range"] / window_s(ctx)
