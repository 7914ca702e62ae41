"""The served stack of ``served_http`` over an ``http_events`` table
whose keys are skewed and whose request paths carry ids: a row draws
its service, its path and its pod by rank with p(r) proportional to
1 / r^c (YCSB's core zipfian generator; ``values.skew.constant``), and
a path belongs to one service (``paths_per_service`` each), so
``service`` x ``req_path`` has no dense domain though few of its
combinations are live.

The stack, the columns and the ingest path are ``served_http``'s; the
data differs, and the client asks for complete answers: the broker's
default cut of 10,000 rows a table (upstream's, for its UI) is below
this deployment's 65 k groups, so every request passes the
configuration's ``max_output_rows``.
"""

from __future__ import annotations

import functools
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from .served_http import COLUMNS, Stack


def _zipf_cdf(n: int, constant: float) -> np.ndarray:
    """Cumulative p(r) over the finite ranks 1..n, p(r) ~ 1 / r^c."""
    p = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** constant
    cdf = np.cumsum(p)
    return cdf / cdf[-1]


def _ranks(rng, cdf: np.ndarray, rows: int) -> np.ndarray:
    """``rows`` ranks (0-based) by inverse-CDF sampling."""
    r = np.searchsorted(cdf, rng.random(rows), side="right")
    return np.minimum(r, len(cdf) - 1).astype(np.int32)


#: Rows a stream of the seed: chunk k of the table is drawn from child
#: k + 1 of ``SeedSequence(seed)`` (child 0 draws the permutations), so
#: the data is the seed's whatever the number of threads that draw it.
CHUNK_ROWS = 1 << 22


def _sizes_keyed_aggregates_from_the_joint_key() -> bool:
    from pixie_tpu.exec.engine import Engine

    return bool(getattr(Engine, "probe_group_keys", False))


#: What a configuration's ``requires`` may name, and how it is looked for.
CAPABILITIES = {"joint_key_sizing": _sizes_keyed_aggregates_from_the_joint_key}


def require_capabilities(cfg: dict) -> None:
    """Exit at once, with the configuration's own reason, on a program
    that lacks something ``cfg["requires"]`` names. This deployment
    names ``joint_key_sizing``: a program without it folds ``service`` x
    ``req_path`` at the planner's bound, the product of the columns'
    NDVs (2^22 slots for 65 k live groups, seconds a window), and its run
    does not end within the time one run is given. It fails here, before
    a row is made."""
    for name, why in cfg.get("requires", {}).items():
        if not CAPABILITIES[name]():
            raise SystemExit(f"{cfg['name']}: the program lacks {name}: {why}")


def make_data(cfg: dict, seed: int, rows: int) -> dict:
    """``rows`` events at all ten columns, every value from ``seed``.
    Methods, statuses, ``latency_ns``, ``resp_body_size`` and ``upid``
    are drawn as ``served_http.make_data`` draws them; times are evenly
    spaced over ``span_s`` and end at ``t_end_ns``, so a range of the
    last r seconds holds rows * r / span_s rows whatever the seed.
    Chunks of ``CHUNK_ROWS`` are drawn side by side, each from a stream
    of its own."""
    require_capabilities(cfg)
    dist = cfg["values"]
    if dist["skew"]["distribution"] != "zipfian":
        raise ValueError(f"skew {dist['skew']!r}")
    c = dist["skew"]["constant"]
    step = cfg["span_s"] * 1_000_000_000 // rows
    n_svc, n_pods = dist["services"], dist["pods"]
    per_svc = dist["paths_per_service"]
    if n_svc * per_svc != dist["paths"]:
        raise ValueError("paths != services * paths_per_service")
    methods = sorted(set(dist["methods"]))
    method_code = np.asarray([methods.index(m) for m in dist["methods"]],
                             np.int32)
    statuses = np.repeat(
        np.asarray([s for s, _n in dist["statuses"]], np.int64),
        [n for _s, n in dist["statuses"]],
    )
    mu, sigma = dist["latency_ns_lognormal"]
    lo, hi = dist["resp_body_size"]
    offsets = range(0, rows, CHUNK_ROWS)
    head, *streams = np.random.SeedSequence(seed).spawn(len(offsets) + 1)
    # Rank -> code, from the seed: hot keys are not neighbouring codes.
    rng = np.random.default_rng(head)
    svc_of_rank = rng.permutation(n_svc).astype(np.int32)
    path_of_rank = rng.permuted(
        np.tile(np.arange(per_svc, dtype=np.int32), (n_svc, 1)), axis=1
    )
    pod_of_rank = rng.permuted(
        np.tile(np.arange(n_pods, dtype=np.int32), (n_svc, 1)), axis=1
    )
    svc_cdf, path_cdf, pod_cdf = (
        _zipf_cdf(n, c) for n in (n_svc, per_svc, n_pods)
    )
    out = {
        "upid": (np.empty(rows, np.uint64), np.empty(rows, np.uint64)),
        "req_method": np.empty(rows, np.int32),
        "req_path": np.empty(rows, np.int32),
        "resp_status": np.empty(rows, np.int64),
        "resp_body_size": np.empty(rows, np.int64),
        "latency_ns": np.empty(rows, np.int64),
        "service": np.empty(rows, np.int32),
        "pod": np.empty(rows, np.int32),
    }

    def draw(off: int, stream) -> None:
        s = slice(off, min(off + CHUNK_ROWS, rows))
        n = s.stop - s.start
        rng = np.random.default_rng(stream)
        svc = svc_of_rank[_ranks(rng, svc_cdf, n)]
        out["service"][s] = svc
        out["req_path"][s] = svc * np.int32(per_svc) + path_of_rank[
            svc, _ranks(rng, path_cdf, n)
        ]
        out["pod"][s] = svc * np.int32(n_pods) + pod_of_rank[
            svc, _ranks(rng, pod_cdf, n)
        ]
        out["upid"][0][s] = rng.integers(1, 1 << 30, n)
        out["upid"][1][s] = rng.integers(1, 1 << 62, n)
        out["req_method"][s] = method_code[
            rng.integers(0, len(method_code), n)
        ]
        out["resp_status"][s] = statuses[rng.integers(0, len(statuses), n)]
        out["resp_body_size"][s] = rng.integers(lo, hi, n)
        out["latency_ns"][s] = np.exp(rng.normal(mu, sigma, n))

    with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
        list(pool.map(draw, offsets, streams))
    return {
        "time_": cfg["t_end_ns"] - step * np.arange(rows - 1, -1, -1,
                                                    dtype=np.int64),
        **out,
        "remote_addr": out["service"],  # one address a service, as the replay
        "names": {
            "remote_addr": [f"10.0.{i % 256}.{i % 251}" for i in range(n_svc)],
            "req_method": methods,
            "req_path": [f"/api/v1/svc-{i}/items/{j}" for i in range(n_svc)
                         for j in range(per_svc)],
            "service": [f"svc-{i}" for i in range(n_svc)],
            "pod": [f"svc-{i}/pod-{j}" for i in range(n_svc)
                    for j in range(n_pods)],
        },
    }


def batches(data: dict, window_rows: int, lo: int = 0, hi: int | None = None):
    """``data``'s rows [lo, hi) as the ingest path takes them: one
    ``HostBatch`` a window, every batch over the same dictionaries (for
    callers that feed an engine without the served stack: the chip smoke
    and the tests)."""
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


class SkewStack(Stack):
    """``Stack`` whose every request asks for all of its groups."""

    def __init__(self, cfg: dict, window_rows: int):
        super().__init__(cfg, window_rows)
        self._execute = functools.partial(
            self._execute, max_output_rows=cfg["max_output_rows"]
        )


def build(cfg: dict, window_rows: int) -> SkewStack:
    return SkewStack(cfg, window_rows)
