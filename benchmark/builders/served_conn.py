"""The served stack of ``served_http`` over a PEM's ``conn_stats``
table: the program's own fifteen columns (``ingest/schemas.py``
``CONN_STATS_RELATION``, upstream's ``kConnStatsElements``), made from
the seed and appended through the PEM's ingest path with device
residency on. ``px/net_flow_graph`` reads it.

The cluster is ``http_full_1chip``'s (``services`` x ``pods``, one
address a pod). ``remote_addr`` takes the pods' addresses and as many
outside the cluster; every pod talks to ``peers`` of them, drawn from
the seed. A row draws its pod by rank with p(r) proportional to
1 / r^c over all pods and its peer by the same law over the pod's
peers (YCSB's core zipfian generator, ``values.skew.constant``), so
``src_pod`` x ``remote_addr`` has no dense domain though few of its
combinations are live. The two address columns have dictionaries of
their own, in different orders: a pod's address has another code as a
``remote_addr`` than as a ``src_addr``, and only the strings join.

Every request passes the configuration's ``max_output_rows``, as
``served_http_skew``'s do: the broker's default cut is below the
answer's edges.

``build`` also tells glibc's malloc to keep what it has (``MALLOC_KEEP``):
the harness keeps every refresh's decoded rows until the window has
closed, so the heap grows by ~1.4 MB a refresh all through it, and with
the default policy the main arena's top is trimmed and grown again and
the client's decode of a 45 k-row answer flips between 1.4 and 2.5 ms in
phases tens of refreshes long, which the run's median follows (PERF.md
section 6, PR 32). A server would state the same in its environment
(``MALLOC_TRIM_THRESHOLD_`` and the like); a builder can only say it
once the process is up.
"""

from __future__ import annotations

import os
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from . import served_http_skew
from .served_http_skew import CHUNK_ROWS, SkewStack, _ranks, _zipf_cdf

#: ``conn_stats`` as the program's own schema has it: 109 B a row.
COLUMNS = (
    ("time_", "TIME64NS"), ("upid", "UINT128"), ("remote_addr", "STRING"),
    ("remote_port", "INT64"), ("trace_role", "INT64"),
    ("addr_family", "INT64"), ("protocol", "INT64"), ("ssl", "BOOLEAN"),
    ("conn_open", "INT64"), ("conn_close", "INT64"),
    ("conn_active", "INT64"), ("bytes_sent", "INT64"),
    ("bytes_recv", "INT64"), ("src_addr", "STRING"), ("src_pod", "STRING"),
)
#: The columns every row fills with one value (``values.<column>``).
CONSTANT = ("remote_port", "trace_role", "addr_family", "protocol", "ssl",
            "conn_open", "conn_close", "conn_active")


#: glibc ``mallopt`` parameters (malloc.h) and the values ``build`` sets:
#: no block under 32 MiB is a mapping of its own, the heap's top is not
#: given back, and it grows 256 MiB at a time.
MALLOC_KEEP = (
    ("M_MMAP_THRESHOLD", -3, 32 << 20),
    ("M_TRIM_THRESHOLD", -1, (1 << 31) - 1),
    ("M_TOP_PAD", -2, 256 << 20),
)


def keep_the_heap() -> dict:
    """Set ``MALLOC_KEEP``; {name: accepted}. Empty where the C library
    has no ``mallopt`` (not glibc): the run goes on as it is."""
    import ctypes

    try:
        mallopt = ctypes.CDLL(None).mallopt
    except (OSError, AttributeError):
        return {}
    return {name: bool(mallopt(param, value))
            for name, param, value in MALLOC_KEEP}


def _sizes_a_join_tail_from_the_rows_in_hand() -> bool:
    from pixie_tpu.exec import joins, stream

    return (hasattr(joins, "_in_hand_build_stats")
            and hasattr(stream, "_rows_in_hand"))


#: What this configuration's ``requires`` may name, and how it is looked
#: for: ``served_http_skew``'s, and the sizing of a join of merged
#: aggregates and of the aggregate over its rows.
CAPABILITIES = {
    **served_http_skew.CAPABILITIES,
    "join_tail_sizing": _sizes_a_join_tail_from_the_rows_in_hand,
}


def require_capabilities(cfg: dict) -> None:
    """Exit at once, with the configuration's own reason, on a program
    that lacks something ``cfg["requires"]`` names (as
    ``served_http_skew.require_capabilities``, over this module's
    ``CAPABILITIES``): before a row is made."""
    for name, why in cfg.get("requires", {}).items():
        if not CAPABILITIES[name]():
            raise SystemExit(f"{cfg['name']}: the program lacks {name}: {why}")


def _pod_addr(p: int) -> str:
    return f"10.{p >> 16}.{p >> 8 & 255}.{p & 255}"


def _outside_addr(k: int) -> str:
    return f"198.{18 + (k >> 16)}.{k >> 8 & 255}.{k & 255}"


def make_data(cfg: dict, seed: int, rows: int) -> dict:
    """``rows`` samples at all fifteen columns, every value from
    ``seed``. Times are evenly spaced over ``span_s`` and end at
    ``t_end_ns``, so a range of the last r seconds holds the same rows
    whatever the seed. Chunks of ``CHUNK_ROWS`` are drawn side by side,
    chunk k from child k + 1 of ``SeedSequence(seed)`` (child 0 draws
    the permutation and the peers), as ``served_http_skew`` draws."""
    require_capabilities(cfg)
    dist = cfg["values"]
    if dist["skew"]["distribution"] != "zipfian":
        raise ValueError(f"skew {dist['skew']!r}")
    c = dist["skew"]["constant"]
    step = cfg["span_s"] * 1_000_000_000 // rows
    n_svc, per_svc = dist["services"], dist["pods"]
    n_pods, n_out = n_svc * per_svc, dist["outside_addrs"]
    k_pod, k_out = dist["peers"]["pods"], dist["peers"]["outside"]
    lo, hi = dist["bytes"]
    offsets = range(0, rows, CHUNK_ROWS)
    head, *streams = np.random.SeedSequence(seed).spawn(len(offsets) + 1)
    rng = np.random.default_rng(head)
    pod_of_rank = rng.permutation(n_pods).astype(np.int32)
    # peers[p, r]: the ``remote_addr`` code of pod p's peer of rank r.
    # The column's dictionary holds the outside addresses first, so a
    # pod's address is code n_out + pod there and code pod in src_addr's.
    peers = np.concatenate([
        n_out + rng.permuted(
            np.tile(np.arange(n_pods, dtype=np.int32), (n_pods, 1)), axis=1
        )[:, :k_pod],
        rng.permuted(
            np.tile(np.arange(n_out, dtype=np.int32), (n_pods, 1)), axis=1
        )[:, :k_out],
    ], axis=1)
    peers = rng.permuted(peers, axis=1)
    pod_cdf, peer_cdf = _zipf_cdf(n_pods, c), _zipf_cdf(k_pod + k_out, c)
    out = {
        "upid": (np.ones(rows, np.uint64), np.empty(rows, np.uint64)),
        "remote_addr": np.empty(rows, np.int32),
        "bytes_sent": np.empty(rows, np.int64),
        "bytes_recv": np.empty(rows, np.int64),
        "src_pod": np.empty(rows, np.int32),
    }

    def draw(off: int, stream) -> None:
        s = slice(off, min(off + CHUNK_ROWS, rows))
        n = s.stop - s.start
        rng = np.random.default_rng(stream)
        pod = pod_of_rank[_ranks(rng, pod_cdf, n)]
        out["src_pod"][s] = pod
        out["upid"][1][s] = pod
        out["remote_addr"][s] = peers[pod, _ranks(rng, peer_cdf, n)]
        out["bytes_sent"][s] = rng.integers(lo, hi, n)
        out["bytes_recv"][s] = rng.integers(lo, hi, n)

    with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
        list(pool.map(draw, offsets, streams))
    constant = {
        col: np.full(rows, dist[col], bool if col == "ssl" else np.int64)
        for col in CONSTANT
    }
    return {
        "time_": cfg["t_end_ns"] - step * np.arange(rows - 1, -1, -1,
                                                    dtype=np.int64),
        **out, **constant,
        "src_addr": out["src_pod"],  # one address a pod
        "names": {
            "remote_addr": [_outside_addr(k) for k in range(n_out)]
                           + [_pod_addr(p) for p in range(n_pods)],
            "src_addr": [_pod_addr(p) for p in range(n_pods)],
            "src_pod": [f"svc-{i}/pod-{j}" for i in range(n_svc)
                        for j in range(per_svc)],
        },
    }


def batches(data: dict, window_rows: int, lo: int = 0, hi: int | None = None):
    """``data``'s rows [lo, hi) as the ingest path takes them: one
    ``HostBatch`` a window, every batch over the same dictionaries."""
    from pixie_tpu.types.batch import HostBatch
    from pixie_tpu.types.dtypes import DataType
    from pixie_tpu.types.relation import Relation
    from pixie_tpu.types.strings import StringDictionary

    rel = Relation([(c, DataType[t]) for c, t in COLUMNS])
    dicts = {c: StringDictionary(v) for c, v in data["names"].items()}
    hi = len(data["time_"]) if hi is None else hi
    for off in range(lo, hi, window_rows):
        s = slice(off, min(off + window_rows, hi))
        yield HostBatch(
            relation=rel, length=s.stop - s.start, dicts=dicts,
            cols={c: tuple(p[s] for p in (
                data[c] if isinstance(data[c], tuple) else (data[c],)
            )) for c in rel.column_names},
        )


class ConnStack(SkewStack):
    """``SkewStack`` (every request asks for all of its rows) whose
    table is ``conn_stats``."""

    def ingest(self, data: dict) -> None:
        """Append every row, a window at a time, and wait until the
        tracker has the table's schema: the broker plans against it."""
        t0 = time.perf_counter()
        for batch in batches(data, self.window_rows):
            self.pem.append_data(self.table, batch)
        self.ingest_s = time.perf_counter() - t0
        self.rows = len(data["time_"])
        self.pem._register()  # the tracker learns the post-ingest schema
        deadline = time.monotonic() + 30
        while self.table not in self.tracker.schemas():
            if time.monotonic() > deadline:
                raise RuntimeError("the PEM's schema never reached the tracker")
            time.sleep(0.01)

    def execute(self, pxl: str, timeout_s: float, now_ns: int) -> dict:
        """``Stack.execute``, the number columns as the client's own
        copies: ``to_pydict`` hands back the planes the Kelvin's thread
        allocated, and the harness keeps every refresh's rows until the
        window has closed, so without the copy that thread's arena grows
        by 0.7 MB a refresh and the big blocks of its next requests
        (the merged rows, the staged window) flip between recycled and
        fresh memory in phases (PERF.md section 6, PR 32). A client
        across a network owns its rows anyway."""
        res = super().execute(pxl, timeout_s, now_ns)
        res["rows"] = {c: v if v.dtype == object else v.copy()
                       for c, v in res["rows"].items()}
        return res


def build(cfg: dict, window_rows: int) -> ConnStack:
    keep_the_heap()
    return ConnStack(cfg, window_rows)
