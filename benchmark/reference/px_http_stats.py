"""px/http_stats in plain numpy: count, mean and max of latency_ns per
(service, req_path) over rows with resp_status < 400, inside the range.

``sums="f32"`` is the control of "How correct is decided": the same
answer with each group's latency sum taken in 32-bit floats, the step
below the exact i64 sum the configuration states, by numpy's pairwise
summation: the most accurate plain f32 sum, so whatever a later PR's
f32 segment or tree sum errs by, it is no less.

``lat_mean`` is compared twice. The program rounds the exact mean once
into its f32 result plane, so its answer IS the f32 nearest the exact
mean: ``lat_mean_misrounded_share`` is the share of groups where it is
not (sound 0; f32 sums 0.4, and 0.25 even when only the finished sum is
rounded to f32), which is what catches a lower precision.
``lat_mean_relerr``, the widest relative gap, is at most 2^-24 for a
sound run and only 1.3e-7 for pairwise f32 sums, too close to separate
the two: it is held against gross faults (a row-by-row f32 sum, an i32
sum that overflows) at three times the sound largest.
"""

from __future__ import annotations

import numpy as np

#: name -> limit. An exact comparison has the limit 0; ``lat_mean``'s
#: two were set from chip readings (PERF.md section 2).
LIMITS = {
    "http_stats.keys_differ": 0,
    "http_stats.n_differ": 0,
    "http_stats.lat_max_differ": 0,
    "http_stats.lat_mean_relerr": 1.8e-7,
    "http_stats.lat_mean_misrounded_share": 0.05,
}


def answer(data: dict, lo_ns: int | None, sums: str = "exact") -> dict:
    n_paths = len(data["names"]["req_path"])
    groups = len(data["names"]["service"]) * n_paths
    keep = data["resp_status"] < 400
    if lo_ns is not None:
        keep &= data["time_"] >= lo_ns
    key = (data["service"][keep].astype(np.int64) * n_paths
           + data["req_path"][keep])
    lat = data["latency_ns"][keep]
    cnt = np.bincount(key, minlength=groups)
    if sums == "exact":
        # f64 accumulation of integers is exact while every partial sum
        # stays under 2^53.
        if int(cnt.max(initial=0)) * int(lat.max(initial=0)) >= 1 << 53:
            raise ValueError("group sums pass 2^53: not exact in float64")
        total = np.bincount(key, weights=lat, minlength=groups)
    elif sums == "f32":
        by_group = lat[np.argsort(key, kind="stable")].astype(np.float32)
        ends = np.cumsum(cnt)
        total = np.asarray([
            by_group[e - n:e].sum(dtype=np.float32)
            for e, n in zip(ends, cnt)
        ], np.float64)
    else:
        raise ValueError(f"sums={sums!r}")
    mx = np.full(groups, np.iinfo(np.int64).min, np.int64)
    np.maximum.at(mx, key, lat)
    names = data["names"]
    key = {g: (names["service"][g // n_paths], names["req_path"][g % n_paths])
           for g in np.flatnonzero(cnt)}
    live = sorted(key, key=key.__getitem__)  # by name, as ``rows`` orders
    mean = total[live] / cnt[live]
    if sums == "f32":  # ... into an f32 result plane, as the program's is
        mean = mean.astype(np.float32).astype(np.float64)
    return {
        "key": [key[g] for g in live],
        "n": cnt[live],
        "lat_mean": mean,
        "lat_max": mx[live].astype(np.float64),
    }


def rows(table: dict) -> dict:
    """The program's decoded rows, ordered by the reference's key."""
    key = list(zip(table["service"], table["req_path"]))
    order = sorted(range(len(key)), key=key.__getitem__)
    return {
        "key": [key[i] for i in order],
        "n": np.asarray(table["n"])[order],
        "lat_mean": np.asarray(table["lat_mean"], np.float64)[order],
        "lat_max": np.asarray(table["lat_max"], np.float64)[order],
    }


def numbers(got: dict, ref: dict) -> dict:
    """Each number compared, by the name ``LIMITS`` has. Answers with
    other keys than the reference's cannot be compared row by row:
    every row then counts as differing."""
    if got["key"] != ref["key"]:
        worst = float("inf")
        return {
            "http_stats.keys_differ": len(
                set(got["key"]) ^ set(ref["key"])
            ) or 1,
            "http_stats.n_differ": len(ref["key"]),
            "http_stats.lat_max_differ": len(ref["key"]),
            "http_stats.lat_mean_relerr": worst,
            "http_stats.lat_mean_misrounded_share": 1.0,
        }
    return {
        "http_stats.keys_differ": 0,
        "http_stats.n_differ": int(np.sum(got["n"] != ref["n"])),
        "http_stats.lat_max_differ": int(
            np.sum(got["lat_max"] != ref["lat_max"])
        ),
        "http_stats.lat_mean_relerr": float(np.max(
            np.abs(got["lat_mean"] - ref["lat_mean"]) / ref["lat_mean"],
            initial=0.0,
        )),
        "http_stats.lat_mean_misrounded_share": float(np.mean(
            got["lat_mean"] != ref["lat_mean"].astype(np.float32)
        )),
    }
