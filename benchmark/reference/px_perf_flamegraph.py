"""px/perf_flamegraph in plain numpy, independent of the program: the
rows in range, exact INT64 sums of ``count`` by (pod, stack_trace_id)
with the group's folded stack, exact sums by pod, and each stack's
share of its pod's samples, ``100.0 * count / total`` in float64.

``data`` is the builder's: ``pod`` and ``stack_trace`` as codes into
``data["names"]``. Every row of a (pod, stack_trace_id) group carries
the same stack (the id is handed out a distinct pair), so ``px.any`` of
it has one right answer; ``answer`` raises on data where it has not.
"""

from __future__ import annotations

import numpy as np

#: ``percent`` is the one number that is not exact. The program's
#: FLOAT64 planes are f32 on the device (``types/dtypes.py``): ``count``
#: and the pod's total reach f32 exactly (every pod's total is under
#: 2^24 here), ``100.0 * count`` is rounded once and the division once
#: more, each by at most 2^-24 = 5.96e-8, so a sound answer reads at most
#: 1.2e-7 and the sound runs' largest is 1.13e-7-1.17e-7 (PERF.md
#: section 2). The control one precision below (the percent rounded
#: into a half-precision plane, ``control_perf_flamegraph.py``) reads
#: 4.9e-4. The limit is 2^-21: four times the sound bound, a thousandth
#: of the control. An f32 division of exact integers is what the program
#: does and reads as a sound run; f32 SUMS are exact here (no group or
#: pod passes 2^24 samples in five minutes) and read 0.
LIMITS = {
    "perf_flamegraph.keys_differ": 0,
    "perf_flamegraph.count_differ": 0,
    "perf_flamegraph.stack_differ": 0,
    "perf_flamegraph.percent_relerr": 2.0 ** -21,
}


def _sum(values, starts, dtype):
    """Sums of the runs of ``values`` that start at ``starts``."""
    if dtype == np.int64:
        return np.add.reduceat(values, starts) if len(starts) else (
            np.zeros(0, np.int64))
    ends = list(starts[1:]) + [len(values)]
    f = values.astype(dtype)
    return np.asarray([f[a:b].sum(dtype=dtype) for a, b in zip(starts, ends)],
                      np.float64)


def answer(data: dict, lo_ns: int | None, sums: str = "exact",
           percent: str = "float64") -> dict:
    """The script's rows, ascending by (pod code, stack_trace_id):
    ``key`` an int64 [n, 2] of them, ``stack_trace``, ``count`` and
    ``percent``; ``pods`` names the pod codes. ``sums`` and
    ``percent`` are the controls' (``control_perf_flamegraph.py``)."""
    keep = np.ones(len(data["time_"]), bool) if lo_ns is None else (
        data["time_"] >= lo_ns
    )
    pod = data["pod"][keep].astype(np.int64)
    sid = data["stack_trace_id"][keep].astype(np.int64)
    stack = data["stack_trace"][keep]
    count = data["count"][keep].astype(np.int64)
    order = np.lexsort((sid, pod))
    pod, sid, stack, count = pod[order], sid[order], stack[order], count[order]
    new_pod = np.ones(len(pod), bool)
    new_pod[1:] = pod[1:] != pod[:-1]
    first = new_pod.copy()
    first[1:] |= sid[1:] != sid[:-1]
    starts = np.nonzero(first)[0]
    if np.any(stack != stack[starts][np.cumsum(first) - 1]):
        raise ValueError("a (pod, stack_trace_id) group holds two stacks")
    acc = {"exact": np.int64, "f32": np.float32}[sums]
    by_group = _sum(count, starts, acc)
    # A group's pod is the (cumulative count of pod starts - 1)-th pod.
    total = _sum(count, np.nonzero(new_pod)[0], acc)[
        np.cumsum(new_pod)[starts] - 1]
    share = 100.0 * by_group.astype(np.float64) / total.astype(np.float64)
    if percent != "float64":
        share = share.astype(percent).astype(np.float64)
    names = data["names"]["stack_trace"]
    return {
        "key": np.stack([pod[starts], sid[starts]], axis=1),
        "pods": data["names"]["pod"],
        "stack_trace": np.asarray(
            [names[c] for c in stack[starts].tolist()], object),
        "count": by_group,
        "percent": share,
    }


def rows(table: dict) -> dict:
    """The program's decoded rows as they came; ``numbers`` orders them
    (it needs the reference's names to turn a pod into its code)."""
    return {
        "pod": np.asarray(table["pod"], object),
        "stack_trace_id": np.asarray(table["stack_trace_id"], np.int64),
        "stack_trace": np.asarray(table["stack_trace"], object),
        "count": np.asarray(table["count"]),
        "percent": np.asarray(table["percent"], np.float64),
    }


def numbers(got: dict, ref: dict) -> dict:
    """Each number compared, by the name ``LIMITS`` has. Answers with
    other keys than the reference's cannot be compared row by row:
    every row then counts as differing."""
    code = {s: i for i, s in enumerate(ref["pods"])}
    pod = np.fromiter((code.get(s, -1) for s in got["pod"].tolist()),
                      np.int64, len(got["pod"]))
    order = np.lexsort((got["stack_trace_id"], pod))
    key = np.stack([pod[order], got["stack_trace_id"][order]], axis=1)
    if key.shape != ref["key"].shape or np.any(key != ref["key"]):
        a = set(map(tuple, key.tolist()))
        b = set(map(tuple, ref["key"].tolist()))
        return {
            "perf_flamegraph.keys_differ": len(a ^ b) or 1,
            "perf_flamegraph.count_differ": len(ref["key"]),
            "perf_flamegraph.stack_differ": len(ref["key"]),
            "perf_flamegraph.percent_relerr": float("inf"),
        }
    return {
        "perf_flamegraph.keys_differ": 0,
        "perf_flamegraph.count_differ": int(np.sum(
            got["count"][order] != ref["count"])),
        "perf_flamegraph.stack_differ": int(np.sum(
            got["stack_trace"][order] != ref["stack_trace"])),
        "perf_flamegraph.percent_relerr": float(np.max(
            np.abs(got["percent"][order] - ref["percent"]) / ref["percent"],
            initial=0.0,
        )),
    }
