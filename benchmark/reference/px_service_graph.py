"""The cluster view's service graph in plain numpy: inside the range, by
(remote_addr, pod, service): the exact p50 / p90 / p99 of latency_ns,
the share of rows with resp_status >= 400, the row count and the sum of
resp_body_size.

One ``lexsort`` of the range's rows by the three keys' strings and the
latency: an edge is a run, its quantiles are read off the sorted run
(numpy's linear interpolation), its count and INT64 sum are exact.

The program's quantiles come from a t-digest: they are held to the
digest's error on EVERY edge, not to exactness, and by two numbers a
quantile, because most edges are a handful of rows:

- ``pXX_rank_err``, over every edge: how far q lies outside the share
  of the edge's rows under and at-or-under the estimate, beyond one
  row's slack (1 / n: between two neighbouring rows every estimate is
  as good as another, and a digest of single rows interpolates by
  another convention than numpy's). The sketch's own guarantee.
- ``pXX_relerr``, over the edges of ``VALUE_EDGE_ROWS`` rows or more:
  the estimate against the exact quantile, as ``px_service_stats`` has
  it. Neighbouring rows at the 99th percentile lie 45 / n apart in the
  value's logarithm (lognormal(15, 1.2): 1.1 % at 4,096 rows, 0.55 % at
  8,192), so below that size a value error measures the edge, not the
  digest: at 4,096 rows sound runs read 0.038-0.067 by the seed and a
  numpy digest of the edge's exact order, with nothing of the program
  in it, 0.030-0.057 on the same seeds (``control_service_graph.py
  --bins 0 --value-rows 4096``); at 8,192 (28 edges a seed) 0.017-0.033
  and 0.020-0.033 (PERF.md section 2). Under that size an edge is held
  by its rank error alone.

``sums="f32"`` (the control) takes the byte sums and the error share as
32-bit floats, the step below the exact INT64 sum and count ratio.
"""

from __future__ import annotations

import numpy as np

#: Edges of at least this many rows are also held to a value error.
VALUE_EDGE_ROWS = 8192
QUANTILES = (("p50", 0.50), ("p90", 0.90), ("p99", 0.99))
KEY_COLUMNS = ("remote_addr", "pod", "service")

#: name -> limit (PERF.md section 2 gives each limit's two readings).
LIMITS = {
    "service_graph.keys_differ": 0,
    "service_graph.throughput_differ": 0,
    "service_graph.bytes_differ": 0,
    "service_graph.error_rate_relerr": 1.5e-7,
    "service_graph.p50_rank_err": 0.02,
    "service_graph.p90_rank_err": 0.02,
    "service_graph.p99_rank_err": 0.01,
    "service_graph.p50_relerr": 0.02,
    "service_graph.p90_relerr": 0.03,
    "service_graph.p99_relerr": 0.07,
}


def _string_rank(names: list) -> np.ndarray:
    """code -> the rank of its string among the column's strings."""
    rank = np.empty(len(names), np.int64)
    rank[np.argsort(np.asarray(names))] = np.arange(len(names))
    return rank


def answer(data: dict, lo_ns: int | None, sums: str = "exact") -> dict:
    if sums not in ("exact", "f32"):
        raise ValueError(f"sums={sums!r}")
    names = data["names"]
    keep = slice(None) if lo_ns is None else data["time_"] >= lo_ns
    codes = [data[c][keep] for c in KEY_COLUMNS]
    ranks = [_string_rank(names[c])[k] for c, k in zip(KEY_COLUMNS, codes)]
    lat = data["latency_ns"][keep]
    order = np.lexsort((lat, ranks[2], ranks[1], ranks[0]))
    ranks = [r[order] for r in ranks]
    lat = lat[order]
    new = np.ones(len(lat), bool)
    if len(lat):
        new[1:] = False
        for r in ranks:
            new[1:] |= r[1:] != r[:-1]
    start = np.flatnonzero(new)
    n = np.diff(np.r_[start, len(lat)])
    failed = np.add.reduceat(
        (data["resp_status"][keep][order] >= 400).astype(np.int64), start
    ) if len(start) else np.zeros(0, np.int64)
    size = data["resp_body_size"][keep][order]
    if sums == "exact":
        total = (np.add.reduceat(size, start) if len(start)
                 else np.zeros(0, np.int64))
        rate = failed / n
    else:
        total = np.asarray([
            size[s:s + k].astype(np.float32).sum(dtype=np.float32)
            for s, k in zip(start, n)
        ], np.float64).astype(np.int64)
        rate = (failed.astype(np.float32)
                / n.astype(np.float32)).astype(np.float64)
    heads = [k[order][start].tolist() for k in codes]
    edge = np.arange(len(start), dtype=np.int64) << 40
    out = {
        "key": [tuple(names[c][i] for c, i in zip(KEY_COLUMNS, row))
                for row in zip(*heads)],
        "error_rate": rate, "throughput": n.astype(np.int64),
        "bytes": total.astype(np.int64),
        # The sorted runs themselves, for the rank errors: an edge's
        # rows are a run of ``rows_key`` (latencies are under 2^40 ns).
        "lat": lat, "start": start, "edge": edge,
        "rows_key": np.repeat(edge, n) + lat,
    }
    for name, q in QUANTILES:
        pos = q * (n - 1)
        lo = np.floor(pos).astype(np.int64)
        hi = np.minimum(lo + 1, n - 1)
        frac = pos - lo
        out[name] = lat[start + lo] * (1 - frac) + lat[start + hi] * frac
    return out


def rows(table: dict) -> dict:
    """The program's decoded rows, ordered by the reference's key."""
    key = list(zip(*(table[c] for c in KEY_COLUMNS)))
    order = np.asarray(sorted(range(len(key)), key=key.__getitem__), np.int64)
    out = {"key": [key[i] for i in order]}
    for col, src in (("p50", "latency_p50"), ("p90", "latency_p90"),
                     ("p99", "latency_p99"), ("error_rate", "error_rate")):
        out[col] = np.asarray(table[src], np.float64)[order]
    out["throughput"] = np.asarray(table["throughput_total"], np.int64)[order]
    out["bytes"] = np.asarray(table["outbound_bytes_total"], np.int64)[order]
    return out


def rank_err(ref: dict, estimate: np.ndarray, q: float) -> np.ndarray:
    """An edge's rank error of ``estimate`` for quantile q: the distance
    of q from [share of its rows under the estimate, share at or under
    it], less one row's slack; inf where the estimate is no number."""
    start, n = ref["start"], ref["throughput"]
    edge, rows_key = ref["edge"], ref["rows_key"]
    ok = np.isfinite(estimate)
    v = np.clip(np.where(ok, estimate, 0.0), 0.0, float((1 << 40) - 1))
    under = np.searchsorted(
        rows_key, edge + np.ceil(v).astype(np.int64), "left") - start
    at_or_under = np.searchsorted(
        rows_key, edge + np.floor(v).astype(np.int64), "right") - start
    err = np.maximum(under / n - q, q - at_or_under / n) - 1.0 / n
    return np.where(ok, np.maximum(err, 0.0), np.inf)


def numbers(got: dict, ref: dict, value_rows: int = VALUE_EDGE_ROWS) -> dict:
    """Each number compared, by the name ``LIMITS`` has. Answers with
    other keys than the reference's cannot be compared row by row: every
    row then counts as differing. ``value_rows`` is for the controls'
    readings at another threshold; a run's comparison leaves it."""
    if got["key"] != ref["key"]:
        out = dict.fromkeys(LIMITS, float("inf"))
        out.update({
            "service_graph.keys_differ": len(
                set(got["key"]) ^ set(ref["key"])) or 1,
            "service_graph.throughput_differ": len(ref["key"]),
            "service_graph.bytes_differ": len(ref["key"]),
        })
        return out

    def worst(err):
        return float(np.max(np.where(np.isnan(err), np.inf, err), initial=0.0))

    # An edge with no failed row reads 0: held to that, not to a ratio.
    rate = np.abs(got["error_rate"] - ref["error_rate"]) / np.where(
        ref["error_rate"] > 0, ref["error_rate"], 1.0)
    out = {
        "service_graph.keys_differ": 0,
        "service_graph.throughput_differ": int(
            np.sum(got["throughput"] != ref["throughput"])),
        "service_graph.bytes_differ": int(np.sum(got["bytes"] != ref["bytes"])),
        "service_graph.error_rate_relerr": worst(rate),
    }
    large = ref["throughput"] >= value_rows
    for name, q in QUANTILES:
        out[f"service_graph.{name}_rank_err"] = worst(
            rank_err(ref, got[name], q))
        out[f"service_graph.{name}_relerr"] = worst(
            (np.abs(got[name] - ref[name]) / ref[name])[large])
    return out
