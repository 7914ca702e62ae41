"""The controls of ``px_service_graph``'s comparison, put in the
program's place at a cell's own size and compared as a run's answers
are. Each has to come out NOT correct:

- the plain reference with its sums one precision down (32-bit floats
  for the exact INT64 byte sums and the count ratio the configuration
  states: ``resp_body_size`` passes 2^24 at 16 rows);
- the exact answer cut at the broker's default 10,000 rows a table;
- the quantiles of a t-digest built at the width ``ops/tdigest.py``
  ``_hist_bins`` gives 2^17 groups, **256 bins** (the top 8 bits of an
  f32: bins a factor of four wide in value), which is what the parent
  program would have answered with: a row's latency is binned, a bin is a
  centroid of its rows' mean, the centroids are re-binned by the k1
  scale to K = 128 and read by the digest's own interpolation.
  ``--bins 0`` keeps every distinct value a centroid of its own (a
  digest built from the exact order: what the limits leave room for;
  with ``--value-rows 4096`` the witness that an edge of 4-8 k rows
  reads its own spacing, whatever builds the digest: PERF.md section 2).

Needs no chip and is no part of a benchmark run:

    python3 benchmark/control_service_graph.py \\
        --workload http_edges_1chip.graph_recent --seeds 1,2,3
"""

from __future__ import annotations

import argparse
import os
import sys

import numpy as np

#: ``QueryBroker.execute_script``'s default ``max_output_rows``.
BROKER_DEFAULT_CUT = 10_000
#: ``ops/tdigest.py`` ``_hist_bins(1 << 17)``, and the digest's K.
PARENT_BINS = 256
K = 128


def binned_digest_quantiles(ref: dict, bins: int, k: int = K) -> dict:
    """{name: estimate an edge} of ``px_service_graph.QUANTILES`` from a
    t-digest an edge whose rows were binned by the top log2(bins) bits
    of their f32 pattern (0: not binned), in plain numpy."""
    from benchmark.reference.px_service_graph import QUANTILES

    lat, start, n = ref["lat"], ref["start"], ref["throughput"]
    edge = np.repeat(np.arange(len(start)), n)
    value = lat.astype(np.float32)
    if bins:
        pattern = value.view(np.uint32) | np.uint32(0x80000000)
        cell = (pattern >> np.uint32(33 - bins.bit_length())).astype(np.int64)
    else:
        cell = value.view(np.uint32).astype(np.int64)

    def runs(key, weight, mean):
        """Neighbouring centroids of one key as one: (first index of a
        run, weight, mean)."""
        first = np.flatnonzero(np.r_[True, key[1:] != key[:-1]])
        w = np.add.reduceat(weight, first)
        return first, w, np.add.reduceat(weight * mean, first) / w

    # A bin of an edge's rows is a centroid (the rows are value-ordered).
    first, w, mean = runs((edge << 32) | cell, np.ones(len(lat)),
                          value.astype(np.float64))
    edge = edge[first]

    def midpoints(edge, w):
        """(cumulative weight at each centroid's middle, its edge's
        total) inside each edge."""
        cum = np.cumsum(w)
        head = np.flatnonzero(np.r_[True, edge[1:] != edge[:-1]])
        before = np.repeat(np.r_[0.0, cum[head[1:] - 1]],
                           np.diff(np.r_[head, len(edge)]))
        total = np.repeat(np.add.reduceat(w, head),
                          np.diff(np.r_[head, len(edge)]))
        return cum - before - w * 0.5, total

    # The k1 scale's re-binning to k centroids (``_compress``).
    mid, total = midpoints(edge, w)
    knorm = np.arcsin(2.0 * np.clip(mid / total, 0, 1) - 1.0) / np.pi + 0.5
    cbin = np.clip(np.floor(knorm * k).astype(np.int64), 0, k - 1)
    first, w, mean = runs((edge << 8) | cbin, w, mean)
    edge = edge[first]
    mid, total = midpoints(edge, w)

    # ``digest_quantile``: means over the cumulative midpoints, clamped.
    axis = edge.astype(np.float64) * (1 << 26) + mid
    head = np.flatnonzero(np.r_[True, edge[1:] != edge[:-1]])
    tail = np.r_[head[1:], len(edge)] - 1
    out = {}
    for name, q in QUANTILES:
        x = q * n
        at = np.searchsorted(axis, np.arange(len(n)) * float(1 << 26) + x,
                             "right")
        hi = np.clip(at, head, tail)
        lo = np.clip(at - 1, head, tail)
        span = mid[hi] - mid[lo]
        frac = np.where(span > 0, (x - mid[lo]) / np.where(span > 0, span, 1),
                        0.0)
        out[name] = mean[lo] + np.clip(frac, 0.0, 1.0) * (mean[hi] - mean[lo])
    return out


def control_numbers(workload: str, seed: int, rows: int | None = None,
                    cut: int = BROKER_DEFAULT_CUT,
                    bins: int = PARENT_BINS,
                    value_rows: int | None = None) -> tuple:
    """({control: numbers}, limits) of each control against the exact
    reference, on the data of ``seed`` (``rows``: a rehearsal's size;
    ``value_rows``: the value errors over the edges of that many rows or
    more, where the reference holds them from ``VALUE_EDGE_ROWS``)."""
    from benchmark import harness

    spec = harness.load_cell(workload)
    cfg, traffic = spec["config"], spec["traffic"]
    data = harness.module("builders", cfg["builder"]).make_data(
        {**cfg, "requires": {}}, seed, cfg["rows"] if rows is None else rows
    )
    lo_ns, _now = harness.range_lo_ns(cfg, traffic)
    (script,) = traffic["scripts"]
    ref = harness.module("reference", script["reference"])
    exact = ref.answer(data, lo_ns)
    held = {} if value_rows is None else {"value_rows": value_rows}
    # The cut as the broker makes it: the first ``cut`` rows of a table.
    kept = {k: v[:cut] for k, v in exact.items()}
    return {
        "f32 sums": ref.numbers(
            ref.answer(data, lo_ns, sums="f32"), exact, **held),
        f"cut at {cut} rows": ref.numbers(kept, exact, **held),
        f"digest at {bins} bins": ref.numbers(
            {**exact, **binned_digest_quantiles(exact, bins)}, exact, **held),
    }, dict(ref.LIMITS)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--rehearse-rows", type=int, default=None)
    ap.add_argument("--cut", type=int, default=BROKER_DEFAULT_CUT)
    ap.add_argument("--bins", type=int, default=PARENT_BINS)
    ap.add_argument("--value-rows", type=int, default=None,
                    help="hold the value errors from this many rows an edge")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    )))
    from benchmark import harness

    caught = True
    for seed in (int(s) for s in args.seeds.split(",")):
        controls, limits = control_numbers(
            args.workload, seed, args.rehearse_rows, args.cut, args.bins,
            args.value_rows
        )
        for control, numbers in controls.items():
            over = sorted(k for k in limits if numbers[k] > limits[k])
            harness.say(workload=args.workload, seed=seed, control=control,
                        numbers={k: [numbers[k], limits[k]] for k in limits},
                        over_limit=over, correct=not over)
            caught = caught and bool(over)
    return 0 if caught else 1


if __name__ == "__main__":
    sys.exit(main())
