"""px/sql_stats in plain numpy and Python: count and mean of latency_ns
per (query shape, one-second window) over the rows inside the range.

A query's shape is what ``NormalizeMySQLUDF``'s rule makes of its text
(upstream ``src/carnot/funcs/builtins/sql_ops.cc``): every string
literal, every numeric literal and every IN-list of placeholders
becomes ``?``, white space collapses. ``shape`` below is that rule as a
character scanner, written against the rule and not against the
program's regular expressions; it runs once a distinct statement in
the range. The window is the row's time with the part under a second
taken off, by integer arithmetic.

``sums="f32"`` is the control of "How correct is decided": the same
answer with each group's latency sum taken in 32-bit floats, the step
below the exact INT64 sum the configuration states, by numpy's pairwise
summation (the most accurate plain f32 sum). ``lat_mean`` is compared
twice, as ``px_http_stats`` compares it: the program rounds the exact
mean once into its f32 result plane, so its answer IS the f32 nearest
the exact mean and ``lat_mean_misrounded_share`` is the share of groups
where it is not (what catches a lower precision); ``lat_mean_relerr``,
the widest relative gap, is held against gross faults.
"""

from __future__ import annotations

import numpy as np

SECOND_NS = 1_000_000_000

#: name -> limit. An exact comparison has the limit 0; ``lat_mean``'s
#: two were set from readings at full size (PERF.md section 2).
LIMITS = {
    "sql_stats.keys_differ": 0,
    "sql_stats.n_differ": 0,
    "sql_stats.lat_mean_relerr": 1.8e-7,
    "sql_stats.lat_mean_misrounded_share": 0.05,
}


def _word(ch: str) -> bool:
    return ch.isalnum() or ch == "_"


def _literals(q: str) -> str:
    """``q`` with every quoted string and every number that stands as a
    word of its own replaced by ``?``. A backslash inside quotes takes
    the next character with it; a quote that never closes is no string.
    ``12ab`` and ``sbtest7`` are names, ``1.5`` is one number."""
    out = []
    i, n = 0, len(q)
    prev = ""  # the character before i in the text as it stands now
    while i < n:
        ch = q[i]
        if ch in "'\"":
            j = i + 1
            while j < n and q[j] != ch:
                j += 2 if q[j] == "\\" else 1
            if j < n:  # closed
                out.append("?")
                prev = "?"
                i = j + 1
                continue
        elif ch.isdigit() and ch.isascii() and not _word(prev):
            j = i + 1
            while j < n and q[j].isdigit() and q[j].isascii():
                j += 1
            if j == n or not _word(q[j]):
                if j + 1 < n and q[j] == "." and q[j + 1].isdigit():
                    k = j + 2
                    while k < n and q[k].isdigit() and q[k].isascii():
                        k += 1
                    if k == n or not _word(q[k]):
                        j = k
                out.append("?")
                prev = "?"
                i = j
                continue
            # digits that run into a name: the whole run is copied
            out.append(q[i:j])
            prev = q[j - 1]
            i = j
            continue
        out.append(ch)
        prev = ch
        i += 1
    return "".join(out)


def _in_lists(q: str) -> str:
    """``IN (?, ?, ?)`` -> ``IN (?)``, whatever the case of IN and the
    white space inside."""
    out = []
    i, n = 0, len(q)
    while i < n:
        if (q[i] in "iI" and i + 1 < n and q[i + 1] in "nN"
                and (i == 0 or not _word(q[i - 1]))):
            j = i + 2
            while j < n and q[j].isspace():
                j += 1
            if j < n and q[j] == "(":
                head_end = j + 1
                k, marks = head_end, 0
                while True:
                    while k < n and q[k].isspace():
                        k += 1
                    if k < n and q[k] == "?":
                        marks += 1
                        k += 1
                    else:
                        marks = 0
                        break
                    while k < n and q[k].isspace():
                        k += 1
                    if k < n and q[k] == ",":
                        k += 1
                        continue
                    break
                if marks and k < n and q[k] == ")":
                    out.append(q[i:head_end] + "?)")
                    i = k + 1
                    continue
        out.append(q[i])
        i += 1
    return "".join(out)


def shape(q: str) -> str:
    """The shape of one statement."""
    return " ".join(_in_lists(_literals(q)).split())


def answer(data: dict, lo_ns: int | None, sums: str = "exact") -> dict:
    keep = np.ones(len(data["time_"]), bool) if lo_ns is None else (
        data["time_"] >= lo_ns
    )
    codes = data["query_str"][keep]
    lat = data["latency_ns"][keep].astype(np.int64)
    window = data["time_"][keep] // SECOND_NS * SECOND_NS
    # The shape of each distinct statement in range, once.
    used, code_ix = np.unique(codes, return_inverse=True)
    names = data["names"]["query_str"]
    shape_of_used = [shape(names[c]) for c in used.tolist()]
    shapes = sorted(set(shape_of_used))
    shape_no = {s: i for i, s in enumerate(shapes)}
    shape_ix = np.asarray([shape_no[s] for s in shape_of_used],
                          np.int64)[code_ix]
    w0 = int(window.min(initial=0))
    seconds = (window - w0) // SECOND_NS
    n_sec = int(seconds.max(initial=0)) + 1
    key = shape_ix * n_sec + seconds
    order = np.argsort(key, kind="stable")
    key_sorted = key[order]
    live, starts, cnt = np.unique(key_sorted, return_index=True,
                                  return_counts=True)
    by_group = lat[order]
    if sums == "exact":
        total = np.add.reduceat(by_group, starts) if len(starts) else (
            np.zeros(0, np.int64))
        mean = np.asarray([int(t) / int(c) for t, c in zip(total, cnt)],
                          np.float64)
    elif sums == "f32":
        f = by_group.astype(np.float32)
        total = np.asarray([
            f[s:s + c].sum(dtype=np.float32) for s, c in zip(starts, cnt)
        ], np.float64)
        # ... into an f32 result plane, as the program's is
        mean = (total / cnt).astype(np.float32).astype(np.float64)
    else:
        raise ValueError(f"sums={sums!r}")
    # ``live`` ascends by (shape, second) and ``shapes`` is sorted: the
    # keys come out in the order ``rows`` sorts them.
    return {
        "key": [(shapes[k // n_sec], w0 + (k % n_sec) * SECOND_NS)
                for k in live.tolist()],
        "n": cnt.astype(np.int64),
        "lat_mean": mean,
    }


def rows(table: dict) -> dict:
    """The program's decoded rows, ordered by the reference's key."""
    key = list(zip(table["query_norm"],
                   np.asarray(table["window"], np.int64).tolist()))
    order = sorted(range(len(key)), key=key.__getitem__)
    return {
        "key": [key[i] for i in order],
        "n": np.asarray(table["n"])[order],
        "lat_mean": np.asarray(table["lat_mean"], np.float64)[order],
    }


def numbers(got: dict, ref: dict) -> dict:
    """Each number compared, by the name ``LIMITS`` has. Answers with
    other keys than the reference's cannot be compared row by row:
    every row then counts as differing."""
    if got["key"] != ref["key"]:
        return {
            "sql_stats.keys_differ": len(
                set(got["key"]) ^ set(ref["key"])
            ) or 1,
            "sql_stats.n_differ": len(ref["key"]),
            "sql_stats.lat_mean_relerr": float("inf"),
            "sql_stats.lat_mean_misrounded_share": 1.0,
        }
    return {
        "sql_stats.keys_differ": 0,
        "sql_stats.n_differ": int(np.sum(got["n"] != ref["n"])),
        "sql_stats.lat_mean_relerr": float(np.max(
            np.abs(got["lat_mean"] - ref["lat_mean"]) / ref["lat_mean"],
            initial=0.0,
        )),
        "sql_stats.lat_mean_misrounded_share": float(np.mean(
            got["lat_mean"] != ref["lat_mean"].astype(np.float32)
        )) if len(ref["key"]) else 0.0,
    }
