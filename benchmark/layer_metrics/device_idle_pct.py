"""1 - the union of device operation intervals over the traced window."""


def read(ctx):
    t = ctx["trace"]
    if t is None:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
