"""The device's idle share as the program sees it, over the whole
(untraced) window: 1 - the union, over every fragment of the PEM's and
the Kelvin's traces, of [first ``device.dispatch`` start, last
``device.wait`` end]. Stands beside ``device_idle_pct``, which the
device trace gives."""

from ..span_readers import (
    clocked, covered_ns, fragment_device_intervals, window_ns,
)


def read(ctx):
    if not clocked(ctx):
        return None
    intervals = fragment_device_intervals(ctx)
    if not intervals:
        return None
    lo, hi = window_ns(ctx)
    return 100.0 * (1.0 - covered_ns(intervals, lo, hi) / (hi - lo))
