"""The served stack of ``served_http`` over a PEM's ``stack_traces.beta``
table: the program's own six columns (``ingest/schemas.py``
``STACK_TRACES_RELATION``, upstream's ``kStackTraceTable`` with the
``pod`` context column materialised at ingest), made from the seed and
appended through the PEM's ingest path with device residency on.
``px/perf_flamegraph`` reads it.

The cluster is ``conn_flow_1chip``'s (``services`` x ``pods``, one
process a pod). A service is one binary: its pods share its
``stacks_per_binary`` folded stacks (the same symbols), so the table's
``stack_trace`` dictionary holds at most services x stacks_per_binary
strings, each hundreds of bytes long, in the order the rows first use
them. Upstream's profiler samples every ``sample_period_ms`` and pushes
every ``push_period_s``: a push is one row a distinct (upid, stack) of
its interval, every row at the push's instant, ``count`` the samples
that hit the pair. Here a push is its first N distinct (pod, stack)
pairs of draws, pod by rank with p(r) proportional to 1 / r^c over all
pods and stack by the same law over the binary's stacks (YCSB's core
zipfian generator, ``values.skew.constant``); ``stack_trace_id`` is
handed out by one counter on a pair's first sight, so (pod,
stack_trace_id) has no dense domain and over half of a range's rows are
groups of their own.

Every request passes the configuration's ``max_output_rows`` (the answer
has a row a live pair) and ``build`` tells malloc to keep its heap, as
``served_conn`` does. What the harness keeps is steadied further here:
it holds every refresh's decoded rows until its window has closed, 25.7
MB a refresh at this answer's size (0.64 M rows x five 8-byte columns),
so a refresh's five fresh arrays are never given back, the next
refresh's come from memory the process has not touched, and the first
touch of a page is dear on the chip's host: with the harness keeping
them a request's client side read 55 ms for six refreshes and 75-90
after, without 53-62 throughout (my chip run, PR 39; faulting the heap
in beforehand only moved the step). ``execute`` therefore copies an
answer's columns into ``Kept``, one block the stack owns and has touched
during set-up, and hands the harness views of it; the arrays the decode
made are freed at once and malloc recycles them refresh after refresh,
as for a client that does not hoard its answers.
"""

from __future__ import annotations

import os
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from . import served_conn, served_http_skew
from .served_http_skew import _ranks, _zipf_cdf

#: ``stack_traces.beta`` as the program's own schema has it: 48 B a row.
COLUMNS = (
    ("time_", "TIME64NS"), ("upid", "UINT128"), ("stack_trace_id", "INT64"),
    ("stack_trace", "STRING"), ("count", "INT64"), ("pod", "STRING"),
)

#: Most draws a block while a push looks for its distinct pairs (a small
#: push draws twice its rows a block).
DRAW_BLOCK = 1 << 18
#: Characters of a symbol (C++ / Go names: letters, digits, ``_``, ``:``,
#: ``.``) and how many kernel symbols every binary's stacks end in.
SYMBOL_CHARS = np.frombuffer(
    b"abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789_:.",
    dtype=np.uint8,
)
KERNEL_MARK = "[k] "
#: Bytes of ``Kept`` at the configuration's full size (scaled down with a
#: rehearsal's rows): the answers of some 160 refreshes.
HARNESS_KEEPS_BYTES = 4 << 30


def _folds_any_in_the_sort() -> bool:
    from pixie_tpu.exec import fold_plan

    return hasattr(fold_plan, "_sort_max")


#: What this configuration's ``requires`` may name, and how it is looked
#: for: ``served_conn``'s, and ``px.any`` riding the keyed fold's sort.
CAPABILITIES = {
    **served_conn.CAPABILITIES,
    "sorted_fold_any": _folds_any_in_the_sort,
}


def require_capabilities(cfg: dict) -> None:
    """Exit at once, with the configuration's own reason, on a program
    that lacks something ``cfg["requires"]`` names: before a row is
    made."""
    for name, why in cfg.get("requires", {}).items():
        if not CAPABILITIES[name]():
            raise SystemExit(f"{cfg['name']}: the program lacks {name}: {why}")


def push_rows(cfg: dict, rows: int) -> np.ndarray:
    """Rows of each push: ``rows`` split over the pushes as evenly as
    integers allow, the earlier pushes taking the odd rows."""
    pushes = cfg["span_s"] // cfg["values"]["push_period_s"]
    return np.asarray(
        [rows // pushes + (k < rows % pushes) for k in range(pushes)],
        np.int64)


def _symbols(rng, n: int, lo: int, hi: int) -> list:
    """``n`` symbol names of ``lo``..``hi`` characters."""
    lens = rng.integers(lo, hi + 1, n)
    text = SYMBOL_CHARS[rng.integers(0, len(SYMBOL_CHARS), int(lens.sum()))]
    text = text.tobytes().decode("ascii")
    ends = np.cumsum(lens).tolist()
    return [text[a:b] for a, b in zip([0] + ends[:-1], ends)]


def folded_stacks(cfg: dict, rng, service: int) -> list:
    """The binary's folded stacks (upstream's format: frames joined by
    ``;``, root first, kernel frames marked ``[k] ``): ``frames`` frames
    each, drawn from the binary's own symbols; one stack in
    ``kernel_share`` ends in up to ``kernel_frames`` kernel frames."""
    dist = cfg["values"]
    n = dist["stacks_per_binary"]
    lo, hi = dist["symbol_chars"]
    user = _symbols(rng, dist["symbols_per_binary"], lo, hi)
    kernel = [KERNEL_MARK + s
              for s in _symbols(rng, dist["kernel_symbols"], lo, hi)]
    f_lo, f_hi = dist["frames"]
    depth = rng.integers(f_lo, f_hi + 1, n)
    k_frames = np.where(
        rng.random(n) < dist["kernel_share"],
        rng.integers(1, dist["kernel_frames"] + 1, n), 0,
    )
    k_frames = np.minimum(k_frames, depth - 1)
    picks = rng.integers(0, len(user), int(depth.sum())).tolist()
    k_picks = rng.integers(0, len(kernel), int(k_frames.sum())).tolist()
    out, at, k_at = [], 0, 0
    root = f"svc-{service}::main"
    for d, k in zip(depth.tolist(), k_frames.tolist()):
        frames = [root] + [user[i] for i in picks[at:at + d - k - 1]]
        frames += [kernel[i] for i in k_picks[k_at:k_at + k]]
        out.append(";".join(frames))
        at += d
        k_at += k
    return out


def make_data(cfg: dict, seed: int, rows: int) -> dict:
    """``rows`` rows at all six columns, every value from ``seed``. Push
    k (``push_rows``) is drawn from child k + 1 of ``SeedSequence(seed)``
    (child 0 draws the permutations, children past the pushes the
    binaries' text), so the data is the seed's whatever the number of
    threads that draw it. Pushes are ``push_period_s`` apart and end at
    ``t_end_ns``, so a range of the last r seconds holds the same rows
    whatever the seed.

    ``stack_trace`` comes as codes into ``names["stack_trace"]``, the
    distinct stacks in the order the rows first use them."""
    require_capabilities(cfg)
    dist = cfg["values"]
    if dist["skew"]["distribution"] != "zipfian":
        raise ValueError(f"skew {dist['skew']!r}")
    n_svc, per_svc = dist["services"], dist["pods"]
    n_pods, n_stacks = n_svc * per_svc, dist["stacks_per_binary"]
    per_push = push_rows(cfg, rows)
    pushes = len(per_push)
    offsets = np.concatenate([[0], np.cumsum(per_push)])
    head, *streams = np.random.SeedSequence(seed).spawn(1 + pushes + n_svc)
    rng0 = np.random.default_rng(head)
    pod_of_rank = rng0.permutation(n_pods).astype(np.int64)
    stack_of_rank = np.stack(
        [rng0.permutation(n_stacks) for _ in range(n_svc)]
    ).astype(np.int64)
    pod_cdf = _zipf_cdf(n_pods, dist["skew"]["constant"])
    stack_cdf = _zipf_cdf(n_stacks, dist["skew"]["constant"])

    pair = np.empty(rows, np.int64)  # pod * n_stacks + the binary's stack
    count = np.empty(rows, np.int64)

    def draw(k: int) -> None:
        """A push: draws until ``per_push[k]`` distinct pairs have been
        seen; its rows are those pairs in the order they were first
        drawn, each with the draws that hit it up to the draw that
        brought the last pair."""
        want = int(per_push[k])
        rng = np.random.default_rng(streams[k])
        drawn = np.empty(0, np.int64)
        block = min(DRAW_BLOCK, max(1024, 2 * want))
        while True:
            pod = pod_of_rank[_ranks(rng, pod_cdf, block)]
            stack = stack_of_rank[pod // per_svc,
                                  _ranks(rng, stack_cdf, block)]
            drawn = np.concatenate([drawn, pod * n_stacks + stack])
            uniq, first = np.unique(drawn, return_index=True)
            if len(uniq) >= want:
                break
        cut = np.sort(first)[want - 1] + 1  # draws until the last pair
        uniq, first, hits = np.unique(drawn[:cut], return_index=True,
                                      return_counts=True)
        order = np.argsort(first, kind="stable")
        s = slice(int(offsets[k]), int(offsets[k + 1]))
        pair[s] = uniq[order]
        count[s] = hits[order]

    with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
        list(pool.map(draw, range(pushes)))

    # One counter hands out ``stack_trace_id`` on a pair's first sight.
    uniq, first, inverse = np.unique(pair, return_index=True,
                                     return_inverse=True)
    arrival = np.argsort(first, kind="stable")
    id_of_uniq = np.empty(len(uniq), np.int64)
    id_of_uniq[arrival] = np.arange(len(uniq), dtype=np.int64)
    stack_trace_id = id_of_uniq[inverse]

    # The dictionary in arrival order: a stack is (service, its number).
    pod = pair // n_stacks
    stack = (pod // per_svc) * n_stacks + pair % n_stacks
    used, first, inverse = np.unique(stack, return_index=True,
                                     return_inverse=True)
    arrival = np.argsort(first, kind="stable")
    code_of_used = np.empty(len(used), np.int32)
    code_of_used[arrival] = np.arange(len(used), dtype=np.int32)
    text = [None] * n_svc

    def write(i: int) -> None:
        text[i] = folded_stacks(
            cfg, np.random.default_rng(streams[pushes + i]), i)

    with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
        list(pool.map(write, range(n_svc)))
    names = [text[u // n_stacks][u % n_stacks]
             for u in used[arrival].tolist()]
    if len(set(names)) != len(names):
        raise RuntimeError("two stacks of the seed fold to one string")

    period = dist["push_period_s"] * 1_000_000_000
    push_time = cfg["t_end_ns"] - period * np.arange(
        pushes - 1, -1, -1, dtype=np.int64)
    return {
        "time_": np.repeat(push_time, per_push),
        "upid": (np.ones(rows, np.uint64), pod.astype(np.uint64)),
        "stack_trace_id": stack_trace_id,
        "stack_trace": code_of_used[inverse],
        "count": count,
        "pod": pod.astype(np.int32),
        "names": {
            "stack_trace": names,
            "pod": [f"svc-{i}/pod-{j}" for i in range(n_svc)
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


class Kept:
    """``nbytes`` of the stack's own memory, written once here (``np.full``
    touches every page), for the rows the harness keeps: ``keep(v)`` is
    ``v``'s values in the next free stretch, as an array like ``v``. Two
    of an answer's five columns are strings (object references), so two
    fifths of the bytes are an object array. A column that no longer
    fits is copied (numbers) or handed on (strings), as ``ConnStack``
    does."""

    def __init__(self, nbytes: int):
        self.objects = np.full(nbytes * 2 // 5 // 8, None, object)
        self.numbers = np.full(nbytes - self.objects.nbytes, 1, np.uint8)
        self.objects_at = self.numbers_at = 0

    def keep(self, v: np.ndarray) -> np.ndarray:
        if v.dtype == object:
            end = self.objects_at + len(v)
            if end > len(self.objects):
                return v
            out = self.objects[self.objects_at:end]
            self.objects_at = end
        else:
            end = self.numbers_at + v.nbytes
            if end > len(self.numbers):
                return v.copy()
            out = self.numbers[self.numbers_at:end].view(v.dtype)
            self.numbers_at = -(-end // 64) * 64
        out[:] = v
        return out


class StackTraceStack(served_conn.ConnStack):
    """``ConnStack`` (every request asks for all of its rows; the ingest
    waits for the schema) whose table is ``stack_traces.beta`` and whose
    answers are handed to the harness as views of ``Kept``."""

    def ingest(self, data: dict) -> None:
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
        self.kept = Kept(HARNESS_KEEPS_BYTES * self.rows // self.cfg["rows"])

    def execute(self, pxl: str, timeout_s: float, now_ns: int) -> dict:
        """``SkewStack.execute`` (every group asked for), the answer's
        columns as views of ``Kept``."""
        res = super(served_conn.ConnStack, self).execute(
            pxl, timeout_s, now_ns)
        res["rows"] = {c: self.kept.keep(v) for c, v in res["rows"].items()}
        return res


def build(cfg: dict, window_rows: int) -> StackTraceStack:
    served_conn.keep_the_heap()
    return StackTraceStack(cfg, window_rows)
