"""Background time (collections included) that overlaps the window's
slowest refresh. Beside ``refresh_max_ms`` less ``refresh_p50_ms`` it
says whether the late refresh was the program's own doing."""

from ..span_readers import background_intervals, covered_ns


def read(ctx):
    entries = background_intervals(ctx)
    refreshes = ctx["window"]["refreshes"]
    if entries is None or not refreshes:
        return None
    slowest = max(refreshes, key=lambda recs: recs[-1]["t1"] - recs[0]["t0"])
    return covered_ns(entries, slowest[0]["t0"] * 1e9,
                      slowest[-1]["t1"] * 1e9) / 1e6
