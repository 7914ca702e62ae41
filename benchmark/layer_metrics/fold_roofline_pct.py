"""The window fold against the HBM roofline: the least time a chip
could take to read its share of what the traced requests' scripts
need, over the device time of the fold programs in the trace
(``FOLD_PROGRAMS``: per window, scan-folded, and the mesh's step).
Memory-bound: the fold does a handful of integer operations per byte."""

from ..fold_bytes import fold_bytes
from ..readers import op_seconds

FOLD_PROGRAMS = ("jit_update", "jit_update_all", "jit_step")


def read(ctx):
    secs = op_seconds(ctx, lambda k: k.split("/")[0] in FOLD_PROGRAMS)
    if not secs:
        return None
    need = sum(
        fold_bytes(ctx["config"], req, ctx["rows_in_range"])
        for req in ctx["requests"]
    ) * ctx["trace"]["refreshes"] / ctx["chips"]
    return 100.0 * (need / ctx["peaks"]["hbm_bytes_per_s"]) / secs
