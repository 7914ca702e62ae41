"""The served stack of ``served_http_skew`` over an ``http_events`` table
whose ``remote_addr`` is a CLIENT's address: the table, the skew and the
other nine columns are ``http_full_1chip``'s row for row in size and
law, and the cluster is ``conn_flow_1chip``'s (``services`` x ``pods``,
one address a pod, as many addresses outside the cluster). Every pod
has ``peers`` clients drawn from the seed (some pods, some outside), and
a row's ``remote_addr`` is one of its pod's clients by rank with p(r)
proportional to 1 / r^c (YCSB's core zipfian generator,
``values.skew.constant``). So (``remote_addr``, ``pod``, ``service``),
the key of the cluster view's service graph, has no dense domain (8,193
x 4,097 x 33 codes) though at most pods x peers of its combinations are
live, and most of those hold a handful of rows in five minutes.

Every request passes the configuration's ``max_output_rows`` (the answer
has a row a live edge); ``build`` tells glibc's malloc to keep its heap
and ``execute`` hands the harness copies of an answer's number columns,
as ``served_conn`` does and for its reasons: the harness keeps every
refresh's decoded rows (some 6 MB here) until its window has closed.

``python3 -m benchmark.builders.served_http_edges --seeds 1,2,3``
counts what the configuration's file states: the live edges of the
traffic's range, the rows an edge and the answer's rows.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from . import served_conn, served_http_skew
from .served_http_skew import CHUNK_ROWS, _ranks, _zipf_cdf

COLUMNS = served_http_skew.COLUMNS
batches = served_http_skew.batches


def _folds_keyed_quantiles_in_the_sort() -> bool:
    """The keyed fold that carries a digest beside its integer
    aggregates AND the ordered digest it merges and reads without a
    row-wise sort: the one arrives with the other."""
    from pixie_tpu.exec import fold_plan
    from pixie_tpu.ops import tdigest

    return "digests" in getattr(
        fold_plan.FoldPlan, "__dataclass_fields__", {}
    ) and hasattr(tdigest, "merge_ordered")


#: What this configuration's ``requires`` may name, and how it is looked
#: for: ``served_http_skew``'s, and the keyed fold that carries a
#: ``quantiles`` aggregate beside its integer ones as an ordered digest.
CAPABILITIES = {
    **served_http_skew.CAPABILITIES,
    "keyed_digest_fold": _folds_keyed_quantiles_in_the_sort,
}


def require_capabilities(cfg: dict) -> None:
    """Exit at once, with the configuration's own reason, on a program
    that lacks something ``cfg["requires"]`` names (as
    ``served_http_skew.require_capabilities``, over this module's
    ``CAPABILITIES``): before a row is made."""
    for name, why in cfg.get("requires", {}).items():
        if not CAPABILITIES[name]():
            raise SystemExit(f"{cfg['name']}: the program lacks {name}: {why}")


def client_table(cfg: dict, rng) -> np.ndarray:
    """clients[p, r]: the ``remote_addr`` code of pod p's client of rank
    r, drawn as ``served_conn.make_data`` draws a pod's peers. The
    column's dictionary holds the outside addresses first, so a pod's
    own address is code ``outside_addrs`` + pod."""
    dist = cfg["values"]
    n_pods, n_out = dist["services"] * dist["pods"], dist["outside_addrs"]
    k_pod, k_out = dist["peers"]["pods"], dist["peers"]["outside"]
    clients = np.concatenate([
        n_out + rng.permuted(
            np.tile(np.arange(n_pods, dtype=np.int32), (n_pods, 1)), axis=1
        )[:, :k_pod],
        rng.permuted(
            np.tile(np.arange(n_out, dtype=np.int32), (n_pods, 1)), axis=1
        )[:, :k_out],
    ], axis=1)
    return rng.permuted(clients, axis=1)


def make_data(cfg: dict, seed: int, rows: int) -> dict:
    """``served_http_skew.make_data``'s rows (children 0 .. n of
    ``SeedSequence(seed)``, chunk by chunk) with ``remote_addr`` drawn
    anew: child n + 1 draws every pod's clients, child n + 2 + k the
    client ranks of chunk k."""
    require_capabilities(cfg)
    data = served_http_skew.make_data({**cfg, "requires": {}}, seed, rows)
    dist = cfg["values"]
    offsets = range(0, rows, CHUNK_ROWS)
    n = len(offsets)
    head, *streams = np.random.SeedSequence(seed).spawn(2 * n + 2)[n + 1:]
    clients = client_table(cfg, np.random.default_rng(head))
    rank_cdf = _zipf_cdf(clients.shape[1], dist["skew"]["constant"])
    addr = np.empty(rows, np.int32)

    def draw(off: int, stream) -> None:
        s = slice(off, min(off + CHUNK_ROWS, rows))
        rng = np.random.default_rng(stream)
        addr[s] = clients[data["pod"][s],
                          _ranks(rng, rank_cdf, s.stop - s.start)]

    with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
        list(pool.map(draw, offsets, streams))
    n_pods, n_out = dist["services"] * dist["pods"], dist["outside_addrs"]
    data["remote_addr"] = addr
    data["names"] = {
        **data["names"],
        "remote_addr": [served_conn._outside_addr(k) for k in range(n_out)]
                       + [served_conn._pod_addr(p) for p in range(n_pods)],
    }
    return data


class EdgeStack(served_conn.ConnStack):
    """``SkewStack`` (every request asks for all of its rows) over
    ``http_events``, with ``ConnStack.execute``'s copies of the answer's
    number columns; the ingest is ``served_http``'s."""

    ingest = served_http_skew.SkewStack.ingest


def build(cfg: dict, window_rows: int) -> EdgeStack:
    served_conn.keep_the_heap()
    return EdgeStack(cfg, window_rows)


def count_edges(cfg: dict, traffic: dict, seed: int) -> dict:
    """What the configuration's file states of one seed's data: the live
    (client, pod) edges of the traffic's range and the rows an edge."""
    data = make_data({**cfg, "requires": {}}, seed, cfg["rows"])
    lo_ns = cfg[traffic["now"]] - traffic["range_s"] * 1_000_000_000
    keep = data["time_"] >= lo_ns
    code = (data["remote_addr"][keep].astype(np.int64) << 32) | data["pod"][keep]
    _edges, n = np.unique(code, return_counts=True)
    return {
        "seed": seed, "rows_in_range": int(keep.sum()),
        "live_edges": len(n), "answer_rows": len(n),
        "median_rows": float(np.median(n)),
        "share_under_8_rows": float(np.mean(n < 8)),
        "edges_of_1000_rows_or_more": int(np.sum(n >= 1000)),
        "largest": int(n.max()),
    }


def main(argv=None) -> int:
    import argparse
    import json

    ap = argparse.ArgumentParser(description=count_edges.__doc__)
    ap.add_argument("--workload", default="http_edges_1chip.graph_recent")
    ap.add_argument("--seeds", default="1,2,3")
    args = ap.parse_args(argv)
    from benchmark import harness

    spec = harness.load_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(count_edges(spec["config"], spec["traffic"], seed)),
              flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
