"""From the profiler's ``.xplane.pb`` to the numbers the benchmark
reports: device busy seconds, the window, seconds by device operation,
and idle gaps by what the client was doing.

Two steps, so that each can be checked alone on the recorded trace in
``testdata/``: ``load`` reads the planes into plain lists, ``reduce``
does the arithmetic on them. Times are nanoseconds on the profiler's
one clock; host annotations and device operations share it.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: Annotations the benchmark's own driver writes (``harness.mark``).
WINDOW_MARK = "bench:traced_window"
LABEL_PREFIXES = ("request:", "check", "think")


def newest_trace(trace_dir: str) -> str:
    files = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"
    ))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def load(path: str) -> dict:
    """{"devices": {chip: {"ops": [[name, start, dur]], "modules":
    [...]}}, "host": [[name, start, dur]]}: the device planes' two
    lines, and the benchmark's own annotations from the host plane."""
    from jax.profiler import ProfileData

    out = {"devices": {}, "host": []}
    for plane in ProfileData.from_file(path).planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key:
                    dev[key] = [[e.name, float(e.start_ns),
                                 float(e.duration_ns)] for e in line.events]
            out["devices"][int(m.group(1))] = dev
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW_MARK or e.name.startswith(
                        LABEL_PREFIXES
                    ):
                        out["host"].append([e.name, float(e.start_ns),
                                            float(e.duration_ns)])
    return out


def _union(intervals):
    """Sorted, merged [start, end) intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def _overlap(a, b):
    """Total length of the intersection of two merged interval lists."""
    total, j = 0.0, 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            total += min(e, b[k][1]) - max(s, b[k][0])
            k += 1
    return total


def op_name(module: str, hlo: str) -> str:
    """``jit_update_all(123)`` and ``%fusion.45 = ... kind=kCustom ...``
    give ``jit_update_all/fusion:kCustom``: the numbering changes from
    compile to compile, the names do not. A custom call carries its
    target (``custom-call:tpu_custom_call`` is a Pallas kernel)."""
    module = re.sub(r"\(\d+\)$", "", module)
    m = re.match(r"%?([\w\-]+(?:\.[A-Za-z_][\w\-]*)*)(?:\.\d+)* = ", hlo)
    op = m.group(1) if m else re.sub(r"(\.\d+)+$", "", hlo.lstrip("%"))
    detail = re.search(r'custom_call_target="([^"]+)"', hlo) or re.search(
        r"kind=(k\w+)", hlo
    )
    if detail:
        op = f"{op}:{detail.group(1)}"
    return f"{module}/{op}" if module else op


def _self_times(ops):
    """(name, start, end, self ns): an operation that holds others (a
    ``while`` and its body) keeps only the time none of them covers."""
    out, stack = [], []
    for name, s, d in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and s >= stack[-1][2]:
            out.append(stack.pop())
        if stack:
            stack[-1][3] -= min(d, stack[-1][2] - s)
        stack.append([name, s, s + d, d])
    return out + stack


def _with_modules(dev: dict):
    """Each op, named with the module whose interval holds its start."""
    mods = sorted(dev["modules"], key=lambda m: m[1])
    starts = [m[1] for m in mods]
    for hlo, s, e, own in _self_times(dev["ops"]):
        i = bisect.bisect_right(starts, s) - 1
        module = ""
        if i >= 0 and s < mods[i][1] + mods[i][2]:
            module = mods[i][0]
        yield op_name(module, hlo), s, e, own


def reduce(events: dict, chips: int) -> dict:
    """busy_s / window_s (seconds, busy averaged over the ``chips``
    devices that ran anything), ``ops`` {name: seconds of its own,
    averaged the same way}, ``gaps`` {label: idle seconds on the
    busiest chip}."""
    marks = [h for h in events["host"] if h[0] == WINDOW_MARK]
    if not marks:
        raise ValueError(f"no {WINDOW_MARK} annotation in the trace")
    lo = min(m[1] for m in marks)
    hi = max(m[1] + m[2] for m in marks)
    used = {c: d for c, d in events["devices"].items() if d["ops"]}
    if len(used) != chips:
        raise ValueError(
            f"operations ran on {len(used)} chips, the cell has {chips}"
        )
    ops: dict = {}
    busy = {}
    for chip, dev in used.items():
        spans = []
        for name, s, e, own in _with_modules(dev):
            for cs, ce in _clip([(s, e)], lo, hi):
                # An op cut by the window's edge keeps that share of
                # its own time.
                ops[name] = ops.get(name, 0.0) + own * (ce - cs) / (e - s)
                spans.append((cs, ce))
        busy[chip] = _union(spans)
    busy_s = sum(e - s for u in busy.values() for s, e in u) / chips / 1e9
    # Idle gaps of the busiest chip, by the client's annotation.
    chip = max(busy, key=lambda c: sum(e - s for s, e in busy[c]))
    gaps, cur = [], lo
    for s, e in busy[chip]:
        if s > cur:
            gaps.append([cur, s])
        cur = max(cur, e)
    if hi > cur:
        gaps.append([cur, hi])
    labelled: dict = {}
    covered = []
    for name in sorted({h[0] for h in events["host"]} - {WINDOW_MARK}):
        spans = _union(_clip(
            [(h[1], h[1] + h[2]) for h in events["host"] if h[0] == name],
            lo, hi,
        ))
        labelled[name] = _overlap(gaps, spans) / 1e9
        covered += spans
    idle = sum(e - s for s, e in gaps) / 1e9
    labelled["between_refreshes"] = idle - _overlap(gaps, _union(covered)) / 1e9
    return {
        "busy_s": busy_s,
        "window_s": (hi - lo) / 1e9,
        "ops": {k: v / chips / 1e9 for k, v in ops.items()},
        "gaps": labelled,
    }


def top(table: dict, n: int = 10) -> list:
    return [[k, v] for k, v in sorted(
        table.items(), key=lambda kv: -kv[1]
    )[:n]]
