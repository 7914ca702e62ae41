"""px/service_stats in plain numpy: per service the exact p50 and p99 of
latency_ns, the share of rows with resp_status >= 400, and the row
count, inside the range.

The program's quantiles come from a t-digest: they are held to the
digest's error, not to exactness. ``sums="f32"`` (the control) takes
the error share as a 32-bit float mean; it hardly moves these numbers,
and is caught by px_http_stats' ``lat_mean``.
"""

from __future__ import annotations

import numpy as np

#: name -> limit (PERF.md section 2, "Limits").
LIMITS = {
    "service_stats.keys_differ": 0,
    "service_stats.throughput_differ": 0,
    "service_stats.error_rate_relerr": 1.5e-7,
    "service_stats.p50_relerr": 0.012,
    "service_stats.p99_relerr": 0.075,
}


def answer(data: dict, lo_ns: int | None, sums: str = "exact") -> dict:
    svc, lat, status = data["service"], data["latency_ns"], data["resp_status"]
    if lo_ns is not None:
        keep = data["time_"] >= lo_ns
        svc, lat, status = svc[keep], lat[keep], status[keep]
    n_svc = len(data["names"]["service"])
    cnt = np.bincount(svc, minlength=n_svc)
    failed = np.bincount(svc, weights=status >= 400, minlength=n_svc)
    order = np.argsort(svc, kind="stable")
    ends = np.cumsum(cnt)
    lat_by_svc = lat[order]
    names = data["names"]["service"]
    live = sorted(np.flatnonzero(cnt), key=names.__getitem__)
    p50, p99 = [], []
    for s in live:
        q = np.quantile(lat_by_svc[ends[s] - cnt[s]:ends[s]], [0.5, 0.99])
        p50.append(q[0])
        p99.append(q[1])
    rate = failed[live] / cnt[live]
    if sums == "f32":
        rate = (failed[live].astype(np.float32)
                / cnt[live].astype(np.float32)).astype(np.float64)
    elif sums != "exact":
        raise ValueError(f"sums={sums!r}")
    return {
        "key": [names[s] for s in live],
        "p50": np.asarray(p50), "p99": np.asarray(p99),
        "error_rate": rate, "throughput": cnt[live],
    }


def rows(table: dict) -> dict:
    key = list(table["service"])
    order = sorted(range(len(key)), key=key.__getitem__)
    out = {"key": [key[i] for i in order]}
    for col in ("p50", "p99", "error_rate"):
        out[col] = np.asarray(table[col], np.float64)[order]
    out["throughput"] = np.asarray(table["throughput"])[order]
    return out


def numbers(got: dict, ref: dict) -> dict:
    if got["key"] != ref["key"]:
        return {
            "service_stats.keys_differ": len(
                set(got["key"]) ^ set(ref["key"])
            ) or 1,
            "service_stats.throughput_differ": len(ref["key"]),
            "service_stats.error_rate_relerr": float("inf"),
            "service_stats.p50_relerr": float("inf"),
            "service_stats.p99_relerr": float("inf"),
        }

    def relerr(col):
        return float(np.max(
            np.abs(got[col] - ref[col]) / np.abs(ref[col]), initial=0.0
        ))

    return {
        "service_stats.keys_differ": 0,
        "service_stats.throughput_differ": int(
            np.sum(got["throughput"] != ref["throughput"])
        ),
        "service_stats.error_rate_relerr": relerr("error_rate"),
        "service_stats.p50_relerr": relerr("p50"),
        "service_stats.p99_relerr": relerr("p99"),
    }
