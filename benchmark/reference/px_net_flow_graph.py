"""px/net_flow_graph in plain numpy: inside the range, the sums of
bytes_sent and bytes_recv by (src_pod, remote_addr); the set of
(src_addr, src_pod); the inner join of the two on the address *strings*
(the two columns have dictionaries of their own: a code means nothing
across them); and the sums again by (src_pod, src_pod_dst). A flow to an
address that is no pod's ``src_addr`` in the range is not an edge.

Everything is exact: the configuration states INT64 sums, so all three
limits are 0. ``sums="f32"`` is the control of "How correct is
decided": the same answer with each sum taken in 32-bit floats (numpy's
pairwise summation, the most accurate plain f32 sum), the step below
the exact INT64 sum. Samples are up to 2^20 B, so any pair with more
than a few dozen rows passes 2^24 and its f32 sum is not the integer.
"""

from __future__ import annotations

import numpy as np

#: name -> limit; every comparison is exact.
LIMITS = {
    "net_flow_graph.keys_differ": 0,
    "net_flow_graph.bytes_sent_differ": 0,
    "net_flow_graph.bytes_recv_differ": 0,
}


def _sum_by(key: np.ndarray, values: tuple, sums: str):
    """(distinct keys ascending, each value's sum by key)."""
    if sums not in ("exact", "f32"):
        raise ValueError(f"sums={sums!r}")
    if len(key) == 0:
        return key, [v[:0] for v in values]
    order = np.argsort(key, kind="stable")
    sorted_key = key[order]
    starts = np.flatnonzero(np.r_[True, sorted_key[1:] != sorted_key[:-1]])
    out = []
    for v in values:
        v = v[order]
        if sums == "exact":
            total = np.add.reduceat(v, starts)
        else:
            ends = np.r_[starts[1:], len(v)]
            total = np.asarray([
                v[s:e].astype(np.float32).sum(dtype=np.float32)
                for s, e in zip(starts, ends)
            ], np.float64).astype(np.int64)
        out.append(total)
    return sorted_key[starts], out


def answer(data: dict, lo_ns: int | None, sums: str = "exact") -> dict:
    names = data["names"]
    keep = slice(None) if lo_ns is None else data["time_"] >= lo_ns
    pod = data["src_pod"][keep].astype(np.int64)
    remote = data["remote_addr"][keep].astype(np.int64)
    src = data["src_addr"][keep].astype(np.int64)
    n_pods, n_remote = len(names["src_pod"]), len(names["remote_addr"])

    # flows: sums by (src_pod, remote_addr).
    flow_key, (sent, recv) = _sum_by(
        pod * n_remote + remote,
        (data["bytes_sent"][keep], data["bytes_recv"][keep]), sums,
    )
    flow_pod, flow_remote = flow_key // n_remote, flow_key % n_remote

    # addrs: the (src_addr, src_pod) pairs in range.
    pair = np.unique(src * n_pods + pod)
    addr_src, addr_pod = pair // n_pods, pair % n_pods

    # The join, on strings: both columns' codes into one id space.
    ids: dict = {}
    remote_id = np.asarray(
        [ids.setdefault(s, len(ids)) for s in names["remote_addr"]], np.int64
    )
    src_id = np.asarray(
        [ids.setdefault(s, len(ids)) for s in names["src_addr"]], np.int64
    )
    by_addr = np.argsort(src_id[addr_src], kind="stable")
    build_id, build_pod = src_id[addr_src][by_addr], addr_pod[by_addr]
    probe_id = remote_id[flow_remote]
    first = np.searchsorted(build_id, probe_id, side="left")
    count = np.searchsorted(build_id, probe_id, side="right") - first
    flow = np.repeat(np.arange(len(probe_id)), count)
    match = np.arange(len(flow)) - np.repeat(np.cumsum(count) - count, count)
    dst = build_pod[np.repeat(first, count) + match]

    # out: sums again by (src_pod, src_pod_dst).
    edge_key, (sent, recv) = _sum_by(
        flow_pod[flow] * n_pods + dst, (sent[flow], recv[flow]), sums,
    )
    key = [(names["src_pod"][k // n_pods], names["src_pod"][k % n_pods])
           for k in edge_key.tolist()]
    order = sorted(range(len(key)), key=key.__getitem__)  # as ``rows`` orders
    return {
        "key": [key[i] for i in order],
        "bytes_sent": sent[order].astype(np.int64),
        "bytes_recv": recv[order].astype(np.int64),
    }


def rows(table: dict) -> dict:
    """The program's decoded rows, ordered by the reference's key."""
    key = list(zip(table["src_pod"], table["src_pod_dst"]))
    order = sorted(range(len(key)), key=key.__getitem__)
    return {
        "key": [key[i] for i in order],
        "bytes_sent": np.asarray(table["bytes_sent"], np.int64)[order],
        "bytes_recv": np.asarray(table["bytes_recv"], np.int64)[order],
    }


def numbers(got: dict, ref: dict) -> dict:
    """Each number compared, by the name ``LIMITS`` has. Answers with
    other keys than the reference's cannot be compared row by row:
    every row then counts as differing."""
    if got["key"] != ref["key"]:
        return {
            "net_flow_graph.keys_differ": len(
                set(got["key"]) ^ set(ref["key"])
            ) or 1,
            "net_flow_graph.bytes_sent_differ": len(ref["key"]),
            "net_flow_graph.bytes_recv_differ": len(ref["key"]),
        }
    return {
        "net_flow_graph.keys_differ": 0,
        "net_flow_graph.bytes_sent_differ": int(
            np.sum(got["bytes_sent"] != ref["bytes_sent"])
        ),
        "net_flow_graph.bytes_recv_differ": int(
            np.sum(got["bytes_recv"] != ref["bytes_recv"])
        ),
    }
