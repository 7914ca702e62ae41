"""Device time of the all-gather and all-reduce operations per refresh,
averaged over the chips, from the traced window."""

from ..readers import op_seconds


def read(ctx):
    secs = op_seconds(
        ctx, lambda k: "all-gather" in k or "all-reduce" in k
    )
    if not secs:
        return None
    return secs * 1e3 / ctx["trace"]["refreshes"]
