"""Share of device time inside Pallas kernels (``tpu_custom_call``).
0 today: no shipped script reaches a kernel on these tables."""

from ..readers import op_seconds


def read(ctx):
    total = op_seconds(ctx, lambda k: True)
    if not total:
        return None
    kernels = op_seconds(
        ctx, lambda k: "tpu_custom_call" in k or "pallas" in k
    )
    return 100.0 * kernels / total
