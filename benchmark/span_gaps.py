"""Idle gaps of the busiest chip by the program's own spans.

    python3 -m benchmark.span_gaps <file.xplane.pb[.gz]>

``exec/trace.py`` enters a ``jax.profiler.TraceAnnotation`` with every
span (and every turn of a background task), so a profiler session's
trace holds them on the host threads' lines, on the device operations'
clock. ``xplane.reduce`` labels an idle gap by the benchmark driver's
own marks (``request:<label>``); this goes one level in: each idle
nanosecond goes to the *narrowest* program annotation that covers it,
on whatever thread, and what no program annotation covers is reported
as such. The planes and the interval arithmetic are ``xplane``'s.
"""

from __future__ import annotations

import gzip
import json
import sys
import tempfile

import numpy as np

from . import xplane

#: Names ``exec/trace.py``'s spans and background turns carry.
PROGRAM_SPANS = (
    "snapshot", "compile", "plan", "admit", "register", "dispatch",
    "dispatch.retry", "await", "await.results", "await.stats", "finish",
    "failover", "device.dispatch", "device.wait", "window.stage",
    "window.stall", "materialize", "publish", "heartbeat",
    "telemetry.fold", "tracker.sweep", "device_memory.poll",
)
#: ``query:<kind>``: an engine trace's root while it runs.
PROGRAM_PREFIXES = ("heartbeat.", "collector.", "query:")
UNCOVERED = "(no program span)"


def is_program_span(name: str) -> bool:
    return name in PROGRAM_SPANS or name.startswith(PROGRAM_PREFIXES)


def load(path: str) -> dict:
    """``xplane.load``'s planes plus ``"program"``: [[name, start, dur]]
    of the program's annotations on every host thread."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with tempfile.NamedTemporaryFile(suffix=".xplane.pb") as f:
            f.write(gzip.open(path).read())
            f.flush()
            return load(f.name)
    events = xplane.load(path)
    events["program"] = [
        [e.name, float(e.start_ns), float(e.duration_ns)]
        for plane in ProfileData.from_file(path).planes
        if plane.name == "/host:CPU"
        for line in plane.lines for e in line.events
        if is_program_span(e.name)
    ]
    return events


def idle_gaps(events: dict):
    """(lo, hi, gaps): the traced window and the merged idle intervals
    of the chip that was busy longest inside it."""
    marks = [h for h in events["host"] if h[0] == xplane.WINDOW_MARK]
    if not marks:
        raise ValueError(f"no {xplane.WINDOW_MARK} annotation in the trace")
    lo = min(m[1] for m in marks)
    hi = max(m[1] + m[2] for m in marks)
    busy = {
        chip: xplane._union(xplane._clip(
            [(s, s + d) for _n, s, d in dev["ops"]], lo, hi
        ))
        for chip, dev in events["devices"].items() if dev["ops"]
    }
    if not busy:
        raise ValueError("no device operation in the trace")
    chip = max(busy, key=lambda c: sum(e - s for s, e in busy[c]))
    gaps, cur = [], lo
    for s, e in busy[chip]:
        if s > cur:
            gaps.append([cur, s])
        cur = max(cur, e)
    if hi > cur:
        gaps.append([cur, hi])
    return lo, hi, gaps


def reduce(events: dict) -> dict:
    """{"window_s", "idle_s", "in_requests_s", "named_in_requests_s",
    "by_span": {name: idle seconds under it as the narrowest cover}}.
    ``in_requests_s`` is the idle time inside the driver's
    ``request:*`` marks; ``named_in_requests_s`` the part of it under a
    program span (all of which are narrower than a request)."""
    lo, hi, gaps = idle_gaps(events)
    spans = sorted(events["program"], key=lambda a: a[2])  # narrowest first
    starts = np.asarray([a[1] for a in spans])
    ends = np.asarray([a[1] + a[2] for a in spans])
    requests = xplane._union(xplane._clip(
        [(h[1], h[1] + h[2]) for h in events["host"]
         if h[0].startswith("request:")], lo, hi,
    ))
    # Cut the gaps at every annotation edge: inside a piece the set of
    # covering annotations does not change.
    edges = np.unique(np.concatenate([starts, ends])) if spans else []
    by_span: dict = {}
    named = []
    for g_lo, g_hi in gaps:
        inner = [x for x in edges[np.searchsorted(edges, g_lo, "right"):
                                  np.searchsorted(edges, g_hi, "left")]]
        cuts = [g_lo, *inner, g_hi]
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            cover = np.nonzero((starts <= mid) & (ends > mid))[0]
            name = spans[cover[0]][0] if len(cover) else UNCOVERED
            by_span[name] = by_span.get(name, 0.0) + (b - a) / 1e9
            if len(cover):
                named.append((a, b))
    return {
        "window_s": (hi - lo) / 1e9,
        "idle_s": sum(e - s for s, e in gaps) / 1e9,
        "in_requests_s": xplane._overlap(gaps, requests) / 1e9,
        "named_in_requests_s": xplane._overlap(
            xplane._union(named), requests
        ) / 1e9,
        "by_span": by_span,
    }


def table(r: dict) -> str:
    rows = [f"window {r['window_s']:.3f} s, idle {r['idle_s']:.3f} s "
            f"({100 * r['idle_s'] / r['window_s']:.1f} %), inside requests "
            f"{r['in_requests_s']:.3f} s, of that under a program span "
            f"{r['named_in_requests_s']:.3f} s "
            f"({100 * r['named_in_requests_s'] / max(r['in_requests_s'], 1e-12):.1f} %)"]
    for name, secs in xplane.top(r["by_span"], 30):
        rows.append(f"  {name:24s} {secs:9.4f} s  "
                    f"{100 * secs / max(r['idle_s'], 1e-12):5.1f} %")
    return "\n".join(rows)


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(__doc__.split("\n\n")[1], file=sys.stderr)
        return 2
    r = reduce(load(argv[0]))
    print(table(r))
    print(json.dumps(r))
    return 0


if __name__ == "__main__":
    sys.exit(main())
