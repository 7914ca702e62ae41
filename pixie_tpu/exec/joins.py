"""Join routing + union: host N:1 / vectorized N:M / device kernel /
fused in-fragment lookup joins.

Reference parity: ``src/carnot/exec/equijoin_node.cc`` (build+probe hash
join) and ``union_node.cc`` (k-way ordered merge). The TPU redesign
routes by shape, backend and *ingest sketches* instead of always
hash-joining (see docs/JOINS.md for the full strategy matrix):

- inner/left joins whose build side is unique on ONE dense key (a
  dictionary's codes, or integers in a narrow range: the post-agg
  common case) are a table lookup on the host at any size
  (``host_table``); small unique multi-column keys run a host dict join,
- large N:M joins run a device kernel — single-shot sort-based, or the
  windowed drivers (sorted-probe / radix-partitioned) that stage the
  build side once and stream probe windows through the prefetch
  pipeline — or a native/numpy hash join on the CPU backend (where XLA
  sorts are the wrong tool),
- N:1 joins against a dense-domain build side fuse INTO the probe
  stream's fragment as device gathers (``try_fused_join``) so output
  rows never materialize host-side.

Sketch-guided routing (``choose_join_strategy``): the table store's
ingest sketches (``table_store/sketches.py`` — row counts, HLL NDV,
zone maps) pick the build side, estimate the join's output cardinality
to size the initial output capacity (instead of climbing the
overflow-doubling ladder, one jit compile per rung), choose single-shot
vs windowed vs radix, and skip probe windows whose key range cannot
intersect the build side. Final capacities persist per plan hash on
the engine (``Engine._join_capacity_cache``) so repeated queries start
at the right rung; ``pixie_join_capacity_retries_total`` counts the
residual retries.
"""

from __future__ import annotations

import functools
import threading
from dataclasses import dataclass

import numpy as np

from ..types.batch import HostBatch, bucket_capacity
from ..types.dtypes import DataType
from ..types.strings import NULL_ID, StringDictionary
from .fragment import compile_fragment_cached as compile_fragment
from .plan import AggOp, JoinOp, LimitOp, LookupJoinOp, MapOp
from .stream import (
    _NO_STATS,
    QueryError,
    _chain_out_relation,
    _col,
    _fetch_tree,
    _Stream,
    _stream_col_stats,
)
from .trace import current_trace


def _key_tuples(hb: HostBatch, on, remaps):
    keys = []
    for c in on:
        ids = hb.cols[c][0]
        if c in remaps:
            # Null string ids (-1) must stay null, not wrap to the last entry.
            ids = np.where(
                ids >= 0, remaps[c][np.clip(ids, 0, None)], NULL_ID
            ).astype(ids.dtype)
        keys.append(ids)
    extra = [hb.cols[c][1] for c in on if len(hb.cols[c]) > 1]
    return list(zip(*(list(k) for k in (keys + extra)))) if keys else []


# Inputs smaller than this run the host dict join (when N:1 applies and
# the build side is no table: ``_join_host_table`` has no row limit);
# larger inputs and right/outer/N:M joins go to the device kernel.
DEVICE_JOIN_MIN_ROWS = 1 << 15

# The windowed driver prefers the radix-partitioned probe over the full
# searchsorted once the build side clears this many rows (below it the
# partition bookkeeping costs more than the shorter binary search saves).
RADIX_MIN_BUILD_ROWS = 1 << 16

# Radix bits of the partitioned probe: build keys are splitmix64-hashed
# and partitioned by the top bits, so each probe row binary-searches ONE
# of 2^bits partitions instead of the whole build side.
JOIN_RADIX_BITS = 8


# -- sketch-backed side statistics -------------------------------------------
@dataclass
class JoinSideStats:
    """What routing knows about one join input without touching data.

    ``lo``/``hi``/``ndv`` describe the SINGLE key column when the join
    key is one single-plane INT64/STRING column (the packed-id fast
    path); multi-key joins carry rows only. All fields are conservative
    estimates: rows is an upper bound when the stream filters, NDV is
    HLL (~3% error), zone bounds never shrink under expiry.
    """

    rows: int
    lo: int | None = None
    hi: int | None = None
    ndv: int | None = None
    origin: str = "none"  # 'sketch' | 'scan' | 'none'


def _chain_key_sources(chain, on_cols):
    """Trace join key columns back through a stream's op chain to source
    table columns, or None when any op rewrites/aggregates them (then
    ingest sketches no longer describe the key values).

    The chain is in APPLICATION order; tracing an output name back to
    its source walks it in reverse (the last Map renamed it most
    recently)."""
    from .plan import FilterOp, LimitOp, MapOp, trace_map_renames

    mapping = {c: c for c in on_cols}
    for op in reversed(chain):
        if isinstance(op, (FilterOp, LimitOp)):
            continue  # values survive, rows only shrink
        if isinstance(op, MapOp):
            mapping = trace_map_renames(op, mapping)
            if mapping is None:
                return None
        else:
            return None
    return mapping


def stream_join_stats(res, on_cols) -> JoinSideStats | None:
    """Ingest-sketch stats for one join input (``results[nid]`` BEFORE
    materialization), or None when the input is not a table-backed
    stream with the key columns passing through unmodified."""
    if not isinstance(res, _Stream) or not isinstance(res.source, list):
        return None
    mapping = _chain_key_sources(res.chain, on_cols)
    if mapping is None:
        return None
    tablets = [t for t in res.source if getattr(t, "sketches", None)]
    if not tablets or len(tablets) != len(res.source):
        return None
    rows = sum(t.sketches.rows for t in tablets)
    stats = JoinSideStats(rows=rows, origin="sketch")
    if len(on_cols) == 1:
        src = mapping[on_cols[0]]
        sks = [t.sketches.col(src) for t in tablets]
        if all(s is not None and s.rows for s in sks):
            stats.lo = min(s.lo for s in sks)
            stats.hi = max(s.hi for s in sks)
            if len(sks) == 1:
                stats.ndv = sks[0].ndv
            else:
                # Cross-tablet NDV: HLL registers merge exactly
                # (elementwise max) — never sum per-tablet estimates.
                from ..ops.hll import hll_estimate_np

                reg = sks[0].registers.copy()
                for s in sks[1:]:
                    np.maximum(reg, s.registers, out=reg)
                stats.ndv = max(1, min(hll_estimate_np(reg), rows))
    return stats


def _scan_side_stats(keys: np.ndarray) -> JoinSideStats:
    """Fallback stats computed from packed key ids: exact zone bounds
    (one vectorized pass) + HLL NDV (one hash pass). Used when ingest
    sketches don't cover an input; ~10ms per 4M keys, amortized against
    the device join it steers."""
    from ..ops.hll import hll_estimate_np, hll_init_np, hll_update_np

    n = len(keys)
    if n == 0:
        return JoinSideStats(rows=0, origin="scan")
    reg = hll_init_np()
    hll_update_np(reg, keys)
    return JoinSideStats(
        rows=n, lo=int(keys.min()), hi=int(keys.max()),
        ndv=max(1, min(hll_estimate_np(reg), n)), origin="scan",
    )


def _in_hand_build_stats(stats: JoinSideStats | None,
                         build_planes: list) -> JoinSideStats | None:
    """The single-shot join's stats of its build side: the ingest
    sketch's where one covers it; else, where the side is one integer
    key plane (a merged aggregate's rows, string codes aligned to one
    dictionary), a count of the keys in hand. Without it the output is
    sized from the plan's BOUNDS (a keyed aggregate's 2^22 slots for
    57 k live groups: 4 Mi output slots, a kernel and six device-to-host
    copies of that length); the plan's capacity stays the ceiling."""
    if stats is not None and stats.ndv:
        return stats
    if len(build_planes) != 1 or build_planes[0].dtype.kind not in "iu":
        return stats
    return _scan_side_stats(build_planes[0])


# -- learned capacity + retry accounting -------------------------------------
# (mode, plan-hash, node) -> final (post-overflow) output capacity of a
# join node, stored on ``Engine._join_capacity_cache``: a repeated query
# starts at the rung its last run finished on instead of re-climbing the
# doubling ladder (one jit compile per rung, paid MID-query in the
# synchronous dispatch regime). Engine-scoped because the plan
# fingerprint hashes operators, not data — two engines running the same
# script over different tables must not seed each other's rungs.
# Engine-less driver calls pass cap_key=None and learn nothing.
#
# Eviction is LRU (python dicts are insertion-ordered; a hit re-inserts
# its key at the back) with a hard size cap: pxbound's plan-time
# pre-sizing makes retention past the cap pure memory loss — under many
# distinct plan hashes (dashboard fleets, ephemeral test engines) an
# unbounded dict is a slow leak. Evictions are counted
# (pixie_join_capacity_evictions_total): a hot cache churning entries
# means the cap is too small for the plan population, worth seeing.
_CAPACITY_LOCK = threading.Lock()
_CAPACITY_CACHE_MAX = 4096


def _eviction_counter():
    from ..services.observability import default_counter

    return default_counter(
        "pixie_join_capacity_evictions_total",
        "Learned join-capacity entries evicted by the per-engine LRU "
        "size cap",
    )


def learned_capacity(engine, cap_key) -> int | None:
    cache = getattr(engine, "_join_capacity_cache", None)
    if cap_key is None or cache is None:
        return None
    with _CAPACITY_LOCK:
        cap = cache.get(cap_key)
        if cap is not None:
            # Refresh recency: re-insert at the back of the order.
            del cache[cap_key]
            cache[cap_key] = cap
        return cap


def remember_capacity(engine, cap_key, capacity: int) -> None:
    cache = getattr(engine, "_join_capacity_cache", None)
    if cap_key is None or cache is None:
        return
    evicted = 0
    with _CAPACITY_LOCK:
        cache.pop(cap_key, None)
        while len(cache) >= _CAPACITY_CACHE_MAX:
            cache.pop(next(iter(cache)))  # LRU: oldest-inserted first
            evicted += 1
        cache[cap_key] = capacity
    if evicted:
        _eviction_counter().inc(evicted)


def _retry_counter(engine):
    """pixie_join_capacity_retries_total: overflow-retry kernel re-runs
    (each costs a fresh jit compile mid-query). The sketch estimate
    plus the learned-capacity cache should make retries exceptional."""
    tracer = getattr(engine, "tracer", None)
    if tracer is not None:
        reg = tracer.registry
    else:  # engine stand-ins (tests) and direct driver calls
        from ..services.observability import default_registry as reg
    return reg.counter(
        "pixie_join_capacity_retries_total",
        "Device-join output-capacity overflow retries (kernel re-runs "
        "at a doubled capacity, one fresh jit compile each)",
    )


def estimate_join_capacity(probe_rows: int, build: JoinSideStats | None,
                           probe: JoinSideStats | None, how: str,
                           overlap: float | None = None) -> int:
    """Estimated output rows for ``probe_rows`` probe rows against the
    build side: fan-out = build_rows / NDV(build), scaled by the zone
    overlap fraction of the probe range (keys outside the build side's
    [lo, hi] cannot match). ``overlap`` overrides the fraction when the
    caller knows it more precisely (the windowed driver passes the WORST
    surviving window's overlap — the whole-probe fraction would shrink
    the per-window estimate for clustered probes whose surviving windows
    each overlap fully). Conservative where sketches are missing."""
    from ..config import get_flag

    safety = float(get_flag("join_capacity_safety"))
    if build is None or not build.ndv:
        # No build stats: the historical default (2x probe rows).
        return bucket_capacity(max(2 * probe_rows, 1))
    fanout = build.rows / max(build.ndv, 1)
    if overlap is None:
        overlap = 1.0
        if (
            probe is not None and probe.lo is not None
            and probe.hi is not None
            and build.lo is not None and build.hi is not None
            and probe.hi > probe.lo
        ):
            inter = min(probe.hi, build.hi) - max(probe.lo, build.lo) + 1
            overlap = max(0.0, min(1.0, inter / (probe.hi - probe.lo + 1)))
    est = probe_rows * fanout * overlap
    if how in ("left", "outer"):
        est = max(est, probe_rows)  # unmatched rows still emit
    return bucket_capacity(max(int(est * safety) + 1, 1 << 10))


# -- strategy choice ---------------------------------------------------------
@dataclass
class JoinDecision:
    """Routing outcome, recorded on ``engine.last_join_decision`` so
    tests and the ``join`` span can see which strategy served a query."""

    # degenerate|host_table|host_dict|host_hash|single|sorted|radix
    strategy: str
    swap: bool = False  # probe the RIGHT side (inner only)
    capacity: int | None = None  # initial output capacity (per window)
    window_rows: int = 0  # probe rows per dispatch (windowed paths)
    retries: int = 0  # overflow retries actually paid
    skipped_windows: int = 0
    domain: int = 0  # the lookup table's length (host_table)
    reason: str = ""


def choose_join_strategy(left: HostBatch, right: HostBatch, op: JoinOp,
                         engine=None, left_stats=None, right_stats=None,
                         device_only: bool = False) -> JoinDecision:
    """Pick the N:M execution strategy from shape, backend and sketches.

    The host-dict (small unique-key) and degenerate (empty-side) routes
    are resolved by the dispatcher before this is called; this chooses
    among the bulk N:M paths. ``device_only`` skips the CPU-backend
    host-hash route — direct ``_join_device`` callers (tests, forced
    device runs) always get a device kernel. See docs/JOINS.md for the
    matrix.
    """
    from ..config import get_flag
    from ..ops import routes

    forced = str(get_flag("join_strategy"))
    window_rows = int(get_flag("join_probe_window_rows"))
    tpu = routes.routes_platform() == "tpu"

    if not device_only and op.how in ("inner", "left") and (
        forced == "host" or (forced == "auto" and not tpu)
    ):
        # XLA CPU sorts make the device kernels a regression there; the
        # native build+probe hash join is the CPU-backend fast path.
        return JoinDecision(
            strategy="host_hash",
            reason="cpu backend" if forced == "auto" else "forced",
        )

    # Build-side swap (inner only: left/right/outer pin the null side).
    # Cost model: the build side is sorted/partitioned and resident for
    # the whole query, the probe side streams — so build = the side with
    # the LOWER rows x log2(NDV) sort cost, with hysteresis (4x) so
    # near-balanced inputs keep the stable left-probe order.
    swap = False
    if op.how == "inner" and right.length > 4 * left.length:
        import math

        def score(n_rows, st):
            ndv = st.ndv if st is not None and st.ndv else max(n_rows, 2)
            return n_rows * math.log2(max(ndv, 2))

        swap = score(left.length, left_stats) < score(right.length,
                                                      right_stats) / 4
    probe_rows = right.length if swap else left.length

    windowable = (
        op.how in ("inner", "left") and window_rows > 0
        and probe_rows > window_rows
    )
    if forced in ("sorted", "radix", "single"):
        strategy = forced
        if forced != "single" and op.how not in ("inner", "left"):
            strategy = "single"  # right/outer need the global kernel
    elif not windowable:
        strategy = "single"
    else:
        build_rows = left.length if swap else right.length
        strategy = (
            "radix" if build_rows >= RADIX_MIN_BUILD_ROWS else "sorted"
        )
    return JoinDecision(
        strategy=strategy, swap=swap and strategy != "single",
        window_rows=window_rows,
        reason="forced" if forced != "auto" else "auto",
    )


#: Strategies whose build and probe run in a device program (a ``join``
#: span's ``where``); the others run on the host.
DEVICE_STRATEGIES = frozenset({"fused", "single", "sorted", "radix"})


def traced_join_dispatch(left: HostBatch, right: HostBatch, op: JoinOp,
                         engine, **kw) -> HostBatch:
    """``_join_dispatch`` inside a ``join`` span on the query's trace:
    the dictionaries' alignment, the strategy's build and probe and the
    output rows' assembly, with what the ``JoinDecision`` chose
    (``strategy``, ``where``: ``host`` / ``device``), ``how``, the
    rows of both sides and of the output and, of a ``host_table``
    lookup, the table's length (``domain``). ``QueryTrace._finalize_usage``
    counts them into ``usage.join_rows_in`` / ``join_rows_out``."""
    qstats = getattr(engine, "_query_stats", None)
    if qstats is None:
        return _join_dispatch(left, right, op, engine, **kw)
    with qstats.trace.span("join", how=op.how) as sp:
        out = _join_dispatch(left, right, op, engine, **kw)
        decision = engine.last_join_decision
        build, probe = (left, right) if decision.swap else (right, left)
        sp.attributes.update(
            strategy=decision.strategy,
            where="device" if decision.strategy in DEVICE_STRATEGIES
            else "host",
            build_rows=build.length, probe_rows=probe.length,
            rows_out=out.length,
        )
        if decision.strategy == "host_table":
            sp.attributes["domain"] = decision.domain
    return out


def _join_dispatch(left: HostBatch, right: HostBatch, op: JoinOp,
                   engine=None, left_stats=None, right_stats=None,
                   cap_key=None, planned_capacity=None) -> HostBatch:
    """Route a join: host N:1 table lookup or dict, native host hash,
    or a device kernel strategy chosen by ``choose_join_strategy``.

    Reference: ``equijoin_node.cc`` always hash-joins; here an inner/left
    join against a build side that is unique on one dense key (the
    post-agg common case) is a lookup on the host whatever its size, a
    small unique multi-column key a dict join there, and everything else
    routes by shape/backend/sketches. ``engine`` (when
    the call comes from a query) carries the pipeline depth and the
    per-query cancel handle into the windowed device drivers;
    ``left_stats``/``right_stats`` are ingest-sketch
    :class:`JoinSideStats`; ``cap_key`` keys the learned-capacity cache.
    """
    if len(op.left_on) != len(op.right_on):
        raise QueryError("join key arity mismatch")
    from ..config import get_flag

    if op.how in ("inner", "left") and get_flag("join_strategy") == "auto":
        looked_up = _join_host_table(left, right, op)
        if looked_up is not None:
            out, domain = looked_up
            if engine is not None:
                engine.last_join_decision = JoinDecision(
                    strategy="host_table", domain=domain,
                    reason="unique dense build",
                )
            return out
    small = left.length + right.length < DEVICE_JOIN_MIN_ROWS
    if op.how in ("inner", "left") and small:
        try:
            out = _join_host(left, right, op)
            if engine is not None:
                engine.last_join_decision = JoinDecision(
                    strategy="host_dict", reason="small unique-key build"
                )
            return out
        except _BuildNotUnique:
            pass  # N:M fan-out -> bulk strategies
    if left.length == 0 or right.length == 0:
        if engine is not None:
            engine.last_join_decision = JoinDecision(
                strategy="degenerate", reason="empty side"
            )
        return _join_degenerate(left, right, op)

    decision = choose_join_strategy(
        left, right, op, engine, left_stats, right_stats
    )
    if engine is not None:
        engine.last_join_decision = decision
    if decision.strategy == "host_hash":
        return _join_host_nm(left, right, op, right_stats)
    return _join_device(left, right, op, engine, decision,
                        left_stats, right_stats, cap_key,
                        planned_capacity=planned_capacity)


class _BuildNotUnique(Exception):
    pass


def _join_child(name: str, **attrs):
    """A child span of the open ``join`` span of the query this thread
    runs (``traced_join_dispatch``'s); no-op, and ``as`` binds None,
    outside one."""
    trace = current_trace()
    join = trace.open_span("join") if trace is not None else None
    if join is None:
        return _NO_STATS
    return trace.span(name, parent=join, **attrs)


def _align_join_dicts(left, right, op):
    """String-dictionary id remaps so key ids compare across sides.

    Returns (l_remap, r_remap, key_dicts): key_dicts maps a left key
    column to the merged dictionary (union preserves left ids, so pair
    rows stay valid and coalesced build-side ids land past them).
    A traced join's ``join.align`` span: ``strings`` hashed into the
    unions; ``memo`` is ``miss`` where a union was built and ``none``
    where the sides share their dictionaries (nothing remembers a union
    yet, so never ``hit``).
    """
    l_remap: dict = {}
    r_remap: dict = {}
    key_dicts: dict = {}
    with _join_child("join.align") as sp:
        strings = 0
        for lc, rc in zip(op.left_on, op.right_on):
            ld, rd = left.dicts.get(lc), right.dicts.get(rc)
            if ld is not None and rd is not None and ld is not rd:
                merged, rl, rr = ld.union(rd)
                l_remap[lc], r_remap[rc] = rl, rr
                key_dicts[lc] = merged
                strings += len(rd)
        if sp is not None:
            sp.attributes.update(
                strings=strings, memo="miss" if key_dicts else "none"
            )
    return l_remap, r_remap, key_dicts


def _join_out_schema(left, right, op):
    """(out_rel, ordered (side, src_col) pairs) for join output columns."""
    out_rel = left.relation.merge(
        right.relation.select(
            [c for c in right.relation.column_names if c not in op.right_on]
        ),
        suffix=op.suffix,
    )
    src = [("l", c) for c in left.relation.column_names] + [
        ("r", c) for c in right.relation.column_names if c not in op.right_on
    ]
    return out_rel, src


def _join_degenerate(left, right, op: JoinOp) -> HostBatch:
    """Joins where one side is empty (device kernel needs real rows)."""
    out_rel, src = _join_out_schema(left, right, op)
    if op.how == "inner" or (op.how == "left" and left.length == 0) or (
        op.how == "right" and right.length == 0
    ):
        keep_l = keep_r = np.zeros(0, dtype=np.int64)
    elif op.how in ("left", "outer") and right.length == 0:
        keep_l, keep_r = np.arange(left.length), np.full(left.length, -1)
    elif op.how in ("right", "outer") and left.length == 0:
        keep_l, keep_r = np.full(right.length, -1), np.arange(right.length)
    else:  # outer with one side non-empty handled above; both empty:
        keep_l = keep_r = np.zeros(0, dtype=np.int64)
    _, r_remap, key_dicts = _align_join_dicts(left, right, op)
    return _assemble_join(
        left, right, op, out_rel, src,
        keep_l, keep_l >= 0, keep_r, keep_r >= 0,
        r_remap=r_remap, key_dicts=key_dicts,
    )


def _assemble_join(left, right, op, out_rel, src, l_idx, l_take, r_idx, r_take,
                   r_remap=None, key_dicts=None):
    """Gather output columns from per-row indices + take masks.

    Join key columns coalesce (SQL USING semantics): a right/outer extra
    row — whose probe side is null — takes its key from the build side,
    remapped into the merged dictionary for strings.
    """
    with _join_child("join.assemble", rows_out=len(l_idx)):
        return _gather_join(left, right, op, out_rel, src, l_idx, l_take,
                            r_idx, r_take, r_remap or {}, key_dicts or {})


def _gather_join(left, right, op, out_rel, src, l_idx, l_take, r_idx, r_take,
                 r_remap, key_dicts):
    key_map = dict(zip(op.left_on, op.right_on))
    out_cols: dict = {}
    out_dicts: dict = {}
    names = iter(out_rel.column_names)
    for side, c in src:
        n = next(names)
        hb = left if side == "l" else right
        idx = l_idx if side == "l" else r_idx
        take = l_take if side == "l" else r_take
        rc = key_map.get(c) if side == "l" else None
        nullv = NULL_ID if hb.relation.col_type(c) == DataType.STRING else 0
        planes = []
        for pi, p in enumerate(hb.cols[c]):
            if len(p) == 0:
                taken = np.full(len(idx), nullv, dtype=p.dtype)
            else:
                taken = p[np.clip(idx, 0, len(p) - 1)]
            if not take.all():
                if rc is not None:
                    q = right.cols[rc][pi]
                    if pi == 0 and rc in r_remap:
                        q = np.where(
                            q >= 0, r_remap[rc][np.clip(q, 0, None)], NULL_ID
                        ).astype(q.dtype)
                    alt = (
                        np.full(len(r_idx), nullv, dtype=p.dtype)
                        if len(q) == 0
                        else q[np.clip(r_idx, 0, len(q) - 1)]
                    )
                    taken = np.where(
                        take, taken, np.where(r_take, alt, nullv)
                    ).astype(p.dtype)
                else:
                    taken = np.where(take, taken, nullv).astype(p.dtype)
            planes.append(taken)
        out_cols[n] = tuple(planes)
        if c in hb.dicts:
            out_dicts[n] = (
                key_dicts.get(c, hb.dicts[c]) if side == "l" else hb.dicts[c]
            )
    return HostBatch(
        relation=out_rel, cols=out_cols, length=len(l_idx), dicts=out_dicts
    )


def _join_key_planes(hb, cols, remaps):
    planes = []
    for c in cols:
        for i, p in enumerate(hb.cols[c]):
            if i == 0 and c in remaps:
                p = np.where(
                    p >= 0, remaps[c][np.clip(p, 0, None)], NULL_ID
                ).astype(p.dtype)
            planes.append(p)
    return planes


@functools.lru_cache(maxsize=64)
def _device_join_cache(n_build, n_probe, dtypes, capacity, how, platform):
    """One jitted kernel per (bucketed shapes, key dtypes, capacity, how,
    the platform whose routes run: ``device_join`` takes its key ids by
    the sort or by the table from it, ``ops/routes.py``).
    Tracked in the program registry (exec/programs.py): the lru key
    params fully determine the traced program, so they ARE the program
    key — compile wall-time, XLA cost/memory analysis and hit counts
    land in /debug/programz and ``__programs__``."""
    import jax

    from ..ops.join import device_join
    from .programs import default_program_registry

    fn = jax.jit(
        lambda bk, bv, pk, pv: device_join(bk, bv, pk, pv, capacity, how)
    )
    return default_program_registry().wrap(
        fn, "join_single_shot",
        ("join", "single", n_build, n_probe, dtypes, capacity, how, platform),
        f"single nb={n_build} np={n_probe} cap={capacity} {how}",
    )


@functools.lru_cache(maxsize=64)
def _probe_sorted_cache(n_build_cap, n_probe_cap, capacity, how):
    """One jitted presorted-probe kernel per (bucketed shapes, capacity,
    how); the sorted build side and its row count are runtime args, so
    every probe window of a query (and across queries of the same
    shapes) reuses one program. Registry-tracked (see
    ``_device_join_cache``)."""
    import jax

    from ..ops.join import probe_sorted_join
    from .programs import default_program_registry

    fn = jax.jit(
        lambda sbk, rb, pk, pv: probe_sorted_join(sbk, rb, pk, pv, capacity, how)
    )
    return default_program_registry().wrap(
        fn, "join_probe_sorted",
        ("join", "sorted", n_build_cap, n_probe_cap, capacity, how),
        f"sorted nb={n_build_cap} w={n_probe_cap} cap={capacity} {how}",
    )


@functools.lru_cache(maxsize=64)
def _radix_probe_cache(n_build_cap, n_probe_cap, capacity, how, radix_bits,
                       steps):
    """One jitted radix-partitioned probe kernel per (bucketed shapes,
    capacity, how, partition count, search depth); the partitioned build
    keys and offsets are runtime args — see ``_probe_sorted_cache``.
    Registry-tracked (see ``_device_join_cache``)."""
    import jax

    from ..ops.join import radix_probe_join
    from .programs import default_program_registry

    fn = jax.jit(
        lambda sbk, starts, pk, pv: radix_probe_join(
            sbk, starts, pk, pv, capacity, how, radix_bits, steps
        )
    )
    return default_program_registry().wrap(
        fn, "join_probe_radix",
        ("join", "radix", n_build_cap, n_probe_cap, capacity, how,
         radix_bits, steps),
        f"radix nb={n_build_cap} w={n_probe_cap} cap={capacity} {how} "
        f"bits={radix_bits}",
    )


def _window_zones(keys: np.ndarray, window_rows: int):
    """(lo[W], hi[W]) per probe window — one vectorized pass (the
    windowed drivers' exact zone maps; ingest sketches only gate whether
    this pass is worth running)."""
    n = len(keys)
    offs = np.arange(0, n, window_rows)
    return (
        np.minimum.reduceat(keys, offs),
        np.maximum.reduceat(keys, offs),
    )


def _join_device_windowed(left: HostBatch, right: HostBatch, op: JoinOp,
                          window_rows: int, engine=None, decision=None,
                          left_stats=None, right_stats=None,
                          cap_key=None) -> HostBatch:
    """Multi-window device join driver (inner/left N:M).

    The build side is packed to comparable int64 key ids, then sorted
    (``strategy="sorted"``) or radix-partitioned by splitmix64 hash
    (``strategy="radix"``) and staged on device ONCE per query (the
    fused-join ``__side__`` discipline: a query-constant table rides as
    a reused runtime arg, never re-``device_put`` per window). Probe
    windows then stream through the window-prefetch pipeline, so staging
    window N+1 overlaps the join kernel on window N. Without a build-
    side swap, output rows are bit-identical to the single-shot
    kernel's: windows emit in probe order, and matches within a probe
    row follow build order on every path (both partitionings are stable
    on equal keys). A swap emits the same row multiset in build-major
    order instead — joins carry no row-order contract.

    Sketch guidance: the initial output capacity comes from the learned
    per-plan cache, else the NDV-based cardinality estimate — NOT a
    fixed guess climbing the overflow-doubling ladder; windows whose key
    zone cannot intersect the build side are never staged (inner skips
    them outright, left emits their null rows host-side); ``decision``
    may swap probe/build for inner joins.
    """
    from ..config import get_flag
    from . import placement
    from .pipeline import WindowPipeline
    from .stream import _block_if, _device_wait, _dispatch, _timed

    if decision is None:
        decision = JoinDecision(strategy="sorted", window_rows=window_rows)
    # Where the build side and the probe windows go: the engine's device
    # (the prefetch thread that stages the windows is outside its scope).
    put = getattr(engine, "_put", placement.put)
    # Under analyze, the join gets its own stage breakdown (stage /
    # compute / stall) like every other window consumer.
    qstats = getattr(engine, "_query_stats", None) if engine is not None \
        else None
    stats = qstats.new_fragment([op]) if qstats is not None else None

    l_remap, r_remap, key_dicts = _align_join_dicts(left, right, op)
    lkeys, rkeys = _packed_key_ids(left, op.left_on, l_remap,
                                   right, op.right_on, r_remap)
    swap = bool(decision.swap)
    if swap and op.how != "inner":
        raise QueryError("join build-side swap is inner-only")
    pkeys, bkeys = (rkeys, lkeys) if swap else (lkeys, rkeys)
    n_probe = len(pkeys)
    bstats = left_stats if swap else right_stats
    if (
        bstats is None or not bstats.ndv or len(op.left_on) > 1
        or l_remap or r_remap
    ):
        # Multi-plane keys were re-packed into dense ids, and divergent
        # string dictionaries were remapped into a merged id space —
        # table sketches describe RAW values, so their zone bounds no
        # longer apply; rescan the packed ids (rows/NDV survive either
        # transform, bounds do not).
        bstats = _scan_side_stats(bkeys)
    elif bstats.rows > len(bkeys):
        import dataclasses

        # Sketch rows are table-lifetime counts (expiry/filters shrink
        # the materialized batch); fan-out comes from live rows.
        bstats = dataclasses.replace(bstats, rows=len(bkeys))

    rb = len(bkeys)
    nb = bucket_capacity(rb)
    sentinel = np.iinfo(np.int64).max  # sorts past every real key
    sbk = np.full(nb, sentinel, dtype=np.int64)
    if decision.strategy == "radix":
        from ..ops.join import radix_partition_build

        order, part_starts, steps = radix_partition_build(
            bkeys, JOIN_RADIX_BITS
        )
        sbk[:rb] = bkeys[order]
        sbk_dev = put(sbk)  # staged once; reused by every window
        starts_dev = put(part_starts)

        def probe_fn(cap):
            fn = _radix_probe_cache(
                nb, wcap, cap, op.how, JOIN_RADIX_BITS, steps
            )
            return lambda pk_dev, pv_dev: fn(
                sbk_dev, starts_dev, pk_dev, pv_dev
            )
    else:
        order = np.argsort(bkeys, kind="stable")
        sbk[:rb] = bkeys[order]
        sbk_dev = put(sbk)
        rb_s = np.int32(rb)

        def probe_fn(cap):
            fn = _probe_sorted_cache(nb, wcap, cap, op.how)
            return lambda pk_dev, pv_dev: fn(sbk_dev, rb_s, pk_dev, pv_dev)

    wcap = bucket_capacity(min(window_rows, n_probe))
    n_windows = (n_probe + window_rows - 1) // window_rows

    # Zone-map window skipping: a probe window whose [min, max] cannot
    # intersect the build side's key range joins nothing — inner skips
    # it outright (the prefetch thread never stages it), left emits its
    # null rows host-side with zero device work.
    skip = np.zeros(n_windows, dtype=bool)
    build_lo = int(bkeys.min()) if rb else 0
    build_hi = int(bkeys.max()) if rb else 0
    window_overlap = None  # worst surviving window's zone overlap
    if n_windows > 1:
        # Per-window zones feed BOTH decisions (one cheap pass): which
        # windows to skip, and the capacity estimate's overlap fraction
        # — which must cover the worst WINDOW, not the probe-wide
        # average (for clustered probes most windows miss the build
        # range entirely while the live ones overlap it almost fully;
        # the whole-probe fraction would understate them).
        wlo, whi = _window_zones(pkeys, window_rows)
        skip = (whi < build_lo) | (wlo > build_hi)
        decision.skipped_windows = int(skip.sum())
        live = ~skip
        if live.any():
            span = np.maximum(whi[live] - wlo[live] + 1, 1)
            inter = (
                np.minimum(whi[live], build_hi)
                - np.maximum(wlo[live], build_lo) + 1
            )
            window_overlap = float(
                np.clip(inter / span, 0.0, 1.0).max()
            )

    def stage_window(off):
        m = min(window_rows, n_probe - off)
        pk = np.full(wcap, sentinel, dtype=np.int64)
        pk[:m] = pkeys[off:off + m]
        pv = np.zeros(wcap, dtype=bool)
        pv[:m] = True
        return m, put(pk), put(pv)

    def staged_probe_windows():
        for w in range(n_windows):
            if skip[w]:
                continue
            off = w * window_rows
            m = min(window_rows, n_probe - off)
            with _timed(stats, "stage", rows=m):
                _, pk_dev, pv_dev = stage_window(off)
                _block_if(stats, (pk_dev, pv_dev))
            if stats is not None:
                stats.rows_in += m
            yield off, pk_dev, pv_dev

    # Initial capacity: learned (this plan overflowed before — start at
    # the rung it settled on), else the sketch estimate. Each overflow
    # retry costs a fresh jit compile MID-query, so getting this right
    # is worth more than the capacity estimate's few percent of error.
    probe_side = JoinSideStats(
        rows=n_probe,
        lo=int(pkeys.min()) if n_probe else None,
        hi=int(pkeys.max()) if n_probe else None,
        origin="scan",
    )
    # Namespace the learned rung by execution mode + window size: a
    # windowed rung is PER WINDOW, a single-shot rung covers the whole
    # output — cross-seeding them either overallocates every window or
    # guarantees a re-climb when the same plan flips paths.
    cap_key = None if cap_key is None else ("windowed", window_rows, cap_key)
    capacity = learned_capacity(engine, cap_key)
    if capacity is None:
        # Clamp the ESTIMATE (a skew blowup would allocate absurd
        # expansion buffers); a learned value is never clamped — it was
        # reached by real doublings and re-clamping would re-climb.
        capacity = min(
            estimate_join_capacity(
                min(window_rows, n_probe), bstats, probe_side, op.how,
                overlap=window_overlap,
            ),
            bucket_capacity(max(2 * window_rows, 1) * 64),
        )

    parts: dict = {}  # off -> (probe_idx, probe_take, build_row, build_take)
    depth = (
        engine.pipeline_depth if engine is not None
        else get_flag("pipeline_depth")
    )
    pipe = WindowPipeline(
        staged_probe_windows(), depth,
        cancel=getattr(engine, "_cancel", None), stats=stats,
    )

    def compact(off, p_idx, p_take, b_idx, b_take, out_valid):
        sel = np.nonzero(out_valid)[0]
        parts[off] = (
            p_idx[sel].astype(np.int64) + off,
            p_take[sel],
            order[np.clip(b_idx[sel], 0, max(rb - 1, 0))],
            b_take[sel],
        )

    counter = _retry_counter(engine)
    try:
        run = probe_fn(capacity)
        for off, pk_dev, pv_dev in pipe:
            while True:
                with _dispatch(stats, run):
                    out = run(pk_dev, pv_dev)
                # The per-window readback is the driver's consume
                # step: compacting each window host-side bounds
                # memory to one window's capacity, and the overflow
                # flag rides in the same batch (no extra sync, no
                # per-window bool(overflow) readback).
                with _device_wait(stats):
                    p_idx, p_take, b_idx, b_take, out_valid, overflow = (
                        _fetch_tree(out)  # pxlint: disable=host-sync-hot-path
                    )
                if not bool(overflow):
                    break
                # Estimate/learned rung was wrong: double, recompile
                # (counted in pixie_join_capacity_retries_total), and
                # keep the larger capacity for every later window.
                capacity *= 2
                counter.inc()
                decision.retries += 1
                run = probe_fn(capacity)
            if stats is not None:
                stats.windows += 1
            compact(off, p_idx, p_take, b_idx, b_take, out_valid)
    finally:
        pipe.close()
        if engine is not None:
            engine._note_pipeline(pipe)

    if op.how == "left":
        # Zone-skipped windows of a left join still emit one null-right
        # row per probe row — assembled host-side, no device dispatch.
        for w in np.nonzero(skip)[0]:
            off = int(w) * window_rows
            m = min(window_rows, n_probe - off)
            parts[off] = (
                np.arange(off, off + m, dtype=np.int64),
                np.ones(m, dtype=bool),
                np.zeros(m, dtype=np.int64),
                np.zeros(m, dtype=bool),
            )
    remember_capacity(engine, cap_key, capacity)

    ordered = [parts[off] for off in sorted(parts)]

    def cat(i, dtype):
        if not ordered:
            return np.zeros(0, dtype=dtype)
        return np.concatenate([p[i] for p in ordered]).astype(
            dtype, copy=False
        )

    p_all = (cat(0, np.int64), cat(1, bool))
    b_all = (cat(2, np.int64), cat(3, bool))
    l_idx, l_take = (b_all if swap else p_all)
    r_idx, r_take = (p_all if swap else b_all)
    out_rel, src = _join_out_schema(left, right, op)
    out = _assemble_join(
        left, right, op, out_rel, src,
        l_idx, l_take, r_idx, r_take,
        r_remap=r_remap, key_dicts=key_dicts,
    )
    if stats is not None:
        stats.rows_out = out.length
    return out


def _join_device(left: HostBatch, right: HostBatch, op: JoinOp,
                 engine=None, decision=None, left_stats=None,
                 right_stats=None, cap_key=None,
                 planned_capacity=None) -> HostBatch:
    """N:M device join: pad to bucketed capacities, run the sort-based
    kernel at the sketch-estimated (or learned) capacity, re-run doubled
    on overflow (counted), gather columns host-side. Large windowable
    probes route to the windowed drivers instead."""
    from ..config import get_flag
    from ..ops import routes

    if decision is None or decision.strategy == "host_hash":
        decision = choose_join_strategy(
            left, right, op, engine, left_stats, right_stats,
            device_only=True,
        )
        if engine is not None:
            engine.last_join_decision = decision
    probe_window = get_flag("join_probe_window_rows")
    probe_rows = right.length if decision.swap else left.length
    if (
        decision.strategy in ("sorted", "radix")
        and op.how in ("inner", "left")
        and probe_window > 0
        and probe_rows > probe_window
        and left.length > 0
        and right.length > 0
    ):
        # Same key-dtype guard as the single-shot path below — the
        # packed-id densify would otherwise paper over a mismatch via
        # numpy promotion (int64 vs float64 collides above 2^53).
        for lc, rc in zip(op.left_on, op.right_on):
            for lp_, rp_ in zip(left.cols[lc], right.cols[rc]):
                if lp_.dtype != rp_.dtype:
                    raise QueryError(
                        f"join key dtype mismatch: {rp_.dtype} vs {lp_.dtype}"
                    )
        # Windowable joins with a big probe side: sorted/partitioned
        # build staged once, probe windows pipelined (one dispatch per
        # window).
        return _join_device_windowed(
            left, right, op, probe_window, engine, decision,
            left_stats, right_stats, cap_key,
        )
    l_remap, r_remap, key_dicts = _align_join_dicts(left, right, op)
    probe_planes = _join_key_planes(left, op.left_on, l_remap)
    build_planes = _join_key_planes(right, op.right_on, r_remap)
    for bp, pp in zip(build_planes, probe_planes):
        if bp.dtype != pp.dtype:
            raise QueryError(
                f"join key dtype mismatch: {bp.dtype} vs {pp.dtype}"
            )

    nb, np_ = bucket_capacity(right.length), bucket_capacity(left.length)

    def pad(p, cap):
        out = np.zeros(cap, dtype=p.dtype)
        out[: len(p)] = p
        return out

    bk = [pad(p, nb) for p in build_planes]
    pk = [pad(p, np_) for p in probe_planes]
    bv = np.zeros(nb, dtype=bool)
    bv[: right.length] = True
    pv = np.zeros(np_, dtype=bool)
    pv[: left.length] = True

    # Initial capacity: learned rung, else the NDV-based estimate, else
    # the historical probe+build default. right/outer append one extra
    # row per unmatched build row past the pair region. The rung is
    # namespaced: single-shot capacities cover the WHOLE output, never
    # interchangeable with the windowed drivers' per-window rungs.
    cap_key = None if cap_key is None else ("single", cap_key)
    capacity = learned_capacity(engine, cap_key)
    if capacity is None:
        right_stats = _in_hand_build_stats(right_stats, build_planes)
        if right_stats is not None and right_stats.ndv:
            import dataclasses

            # Sketch rows are table-LIFETIME counts (expiry never
            # decrements; filters shrink the batch further) — fan-out
            # must come from the rows actually materialized, or a
            # churned streaming table inflates the estimate without
            # bound. Divergent string dictionaries were remapped into a
            # merged id space, so the sketches' zone bounds are
            # raw-space and only the NDV/rows half applies there.
            remapped = bool(l_remap or r_remap)
            capacity = estimate_join_capacity(
                left.length,
                dataclasses.replace(
                    right_stats, rows=min(right_stats.rows, right.length)
                ),
                left_stats, op.how,
                overlap=1.0 if remapped else None,
            )
            if op.how in ("right", "outer"):
                capacity = bucket_capacity(capacity + right.length)
            # Clamp to the theoretical maximum output (every probe row
            # matching every build row) — stale stats must never drive
            # an allocation past what the data could produce.
            capacity = min(
                capacity, bucket_capacity(max(left.length, 1) * right.length)
            )
            if right_stats.origin == "scan" and planned_capacity:
                capacity = min(
                    capacity, bucket_capacity(max(int(planned_capacity), 1))
                )
        elif planned_capacity:
            # pxbound's plan-time estimate (analysis/bounds.py): sized
            # from bounds run-time sketches cannot see — a post-
            # aggregate build side's group-count bound. Clamped to the
            # theoretical max like the run-time estimate.
            capacity = min(
                bucket_capacity(max(int(planned_capacity), 1)),
                bucket_capacity(max(left.length, 1) * right.length),
            )
        else:
            capacity = bucket_capacity(max(left.length + right.length, 1))
    counter = _retry_counter(engine)
    while True:
        fn = _device_join_cache(
            nb, np_, tuple(str(p.dtype) for p in bk), capacity, op.how,
            routes.routes_platform(),
        )
        # The kernel's six outputs by one batched get, the overflow flag
        # among them: every copy queued behind the program at once, the
        # get's first wait the path's sync.
        p_idx, p_take, b_idx, b_take, out_valid, overflow = _fetch_tree(
            fn(bk, bv, pk, pv)
        )
        if not bool(overflow):
            break
        capacity *= 2
        counter.inc()
        decision.retries += 1
    remember_capacity(engine, cap_key, capacity)

    sel = np.nonzero(out_valid)[0]
    out_rel, src = _join_out_schema(left, right, op)
    return _assemble_join(
        left, right, op, out_rel, src,
        p_idx[sel], p_take[sel], b_idx[sel], b_take[sel],
        r_remap=r_remap, key_dicts=key_dicts,
    )


def _unique_dense_build(kb: np.ndarray, dtype: DataType,
                        strings: int | None, nulls_join: bool):
    """``(lo, dom, row_of)`` where a build side's ONE key plane ``kb``
    makes it a table, else None: the one statement of the rule the host
    lookup (``_join_host_table``) and the fused lookup
    (``_host_table_build``) share.

    *Dense*: STRING codes of a dictionary of ``strings`` entries (domain
    ``strings`` + 1), or INT64 / TIME64NS whose range ``hi - lo + 1`` is
    within ``int_dense_domain_limit``; any other type, or a wider range,
    is not. *Unique*, exactly: ``row_of`` (int32, ``dom`` long) holds at
    code ``k - lo`` the build row whose key is ``k`` and -1 where none
    is; two rows on one code (N:M) leave fewer codes set than rows, and
    that is not a table. O(rows + dom), one vectorized pass.

    ``nulls_join``: a null string id (-1) is a key like any other (as
    the dict, hash and device routes compare ids: ``lo`` = -1), or a row
    that can match nothing and is left out of the table (the fused
    lookup's: ``lo`` = 0)."""
    from ..config import get_flag

    rows = None  # every row is keyed
    if dtype == DataType.STRING:
        lo, dom = (-1 if nulls_join else 0), strings + 1
        if not nulls_join:
            rows = np.nonzero(kb >= 0)[0]
            kb = kb[rows]
    elif dtype in (DataType.INT64, DataType.TIME64NS) and len(kb):
        lo = int(kb.min())
        dom = int(kb.max()) - lo + 1
        if dom > get_flag("int_dense_domain_limit"):
            return None
    else:
        return None
    if len(kb) > dom:
        return None  # more rows than codes: some code is two rows'
    row_of = np.full(dom, -1, dtype=np.int32)
    row_of[kb - lo] = np.arange(len(kb)) if rows is None else rows
    if np.count_nonzero(row_of >= 0) != len(kb):
        return None
    return lo, dom, row_of


def _join_host_table(left: HostBatch, right: HostBatch, op: JoinOp):
    """Inner / left N:1 equijoin as a table lookup on the host, at any
    size: ``(rows, the table's length)``, or None where the build side
    is not a table (``_unique_dense_build``: two key columns or planes,
    a type with no dense codes, a range too wide, a duplicate key) or a
    side is empty, and the caller's routing goes on as it was.

    The probe is one gather at the PROBE's own length from a table that
    fits in cache; rows come in probe order. Both sides are on the host
    when a join starts (merged aggregates, materialized), so a device
    kernel would round-trip every byte: docs/JOINS.md."""
    if len(op.left_on) != 1 or not left.length or not right.length:
        return None
    lc, rc = op.left_on[0], op.right_on[0]
    dtype = left.relation.col_type(lc)
    if (
        right.relation.col_type(rc) != dtype
        or len(left.cols[lc]) != 1 or len(right.cols[rc]) != 1
    ):
        return None
    strings = None
    if dtype == DataType.STRING:
        if lc not in left.dicts or rc not in right.dicts:
            return None
        strings = len(right.dicts[rc])
    # A remap is one to one, so a build side unique in its own codes is
    # unique in a union's: asked before one is built.
    built = _unique_dense_build(right.cols[rc][0], dtype, strings, True)
    if built is None:
        return None
    _, r_remap, key_dicts = _align_join_dicts(left, right, op)
    if r_remap:
        # The build's keys in the union's codes, which are the probe's
        # own (a union keeps the left side's ids).
        built = _unique_dense_build(
            _join_key_planes(right, op.right_on, r_remap)[0], dtype,
            len(key_dicts[lc]), True,
        )
    lo, dom, row_of = built
    hi = lo + dom - 1
    kp = left.cols[lc][0]
    if int(kp.min()) >= lo and int(kp.max()) <= hi:
        match = row_of.take(kp - lo)
    else:  # probe keys past the build's range match nothing
        match = np.full(left.length, -1, dtype=np.int32)
        inside = np.nonzero((kp >= lo) & (kp <= hi))[0]
        match[inside] = row_of.take(kp[inside] - lo)
    l_idx = None  # every probe row, in order: its planes pass through
    if op.how == "inner" and int(match.min()) < 0:
        l_idx = np.nonzero(match >= 0)[0]
        match = match[l_idx]
    return _assemble_join_host(left, right, op, l_idx, match), dom


def _join_host(left: HostBatch, right: HostBatch, op: JoinOp) -> HostBatch:
    """N:1 equijoin on host (post-agg inputs are small).

    Reference: ``src/carnot/exec/equijoin_node.cc`` build+probe — here the
    build side must be unique on the key (raises _BuildNotUnique for the
    dispatcher to fall through to the device kernel).
    """
    l_remap, r_remap, _ = _align_join_dicts(left, right, op)

    lk = _key_tuples(left, op.left_on, l_remap)
    rk = _key_tuples(right, op.right_on, r_remap)
    lookup: dict = {}
    for i, k in enumerate(rk):
        if k in lookup:
            raise _BuildNotUnique(op.right_on, k)
        lookup[k] = i

    match = np.fromiter((lookup.get(k, -1) for k in lk), dtype=np.int64, count=len(lk))
    if op.how == "inner":
        l_idx = np.nonzero(match >= 0)[0]
    elif op.how == "left":
        l_idx = np.arange(left.length)
    else:
        raise QueryError(f"unsupported join how={op.how!r}")
    r_idx = match[l_idx]
    return _assemble_join_host(left, right, op, l_idx, r_idx)


def _join_host_nm(left: HostBatch, right: HostBatch, op: JoinOp,
                  right_stats=None) -> HostBatch:
    """N:M inner/left equijoin on host — the CPU-backend analog of the
    device kernel (XLA CPU sorts are too slow to route big joins through
    the device path there). The native O(n) build+probe hash join
    (native/hash_join.cc) carries the bulk; the vectorized numpy
    sort/searchsorted form is the no-toolchain fallback.

    Zone pre-filter (the host analog of the windowed drivers' window
    skipping): rows whose key lies outside the other side's [min, max]
    cannot join — inner drops them from the probe, both hows drop them
    from the build, so a selective join hashes only the overlap."""
    l_remap, r_remap, _ = _align_join_dicts(left, right, op)
    lk = _packed_key_ids(left, op.left_on, l_remap,
                         right, op.right_on, r_remap)
    lkeys, rkeys = lk

    sel_l = sel_r = None  # compressed-row -> original-row maps
    if len(lkeys) and len(rkeys):
        llo, lhi = int(lkeys.min()), int(lkeys.max())
        rlo, rhi = int(rkeys.min()), int(rkeys.max())
        if op.how == "inner" and (llo < rlo or lhi > rhi):
            keep = (lkeys >= rlo) & (lkeys <= rhi)
            if int(keep.sum()) < int(0.9 * len(lkeys)):
                sel_l = np.nonzero(keep)[0]
                lkeys = lkeys[sel_l]
        if rlo < llo or rhi > lhi:
            keep = (rkeys >= llo) & (rkeys <= lhi)
            if int(keep.sum()) < int(0.9 * len(rkeys)):
                sel_r = np.nonzero(keep)[0]
                rkeys = rkeys[sel_r]

    def _emit(l_idx, r_idx):
        if sel_l is not None:
            l_idx = sel_l[l_idx]
        if sel_r is not None and len(sel_r):
            r_idx = np.where(r_idx >= 0, sel_r[np.clip(r_idx, 0, None)], -1)
        return _assemble_join_host(left, right, op, l_idx, r_idx)

    if op.how == "left" and len(lkeys) and not len(rkeys):
        # Pre-filter emptied the build side: every probe row is
        # unmatched (the generic path below assumes a non-empty build).
        return _emit(
            np.arange(left.length, dtype=np.int64),
            np.full(left.length, -1, dtype=np.int64),
        )
    if op.how == "inner" and (not len(lkeys) or not len(rkeys)):
        return _emit(
            np.zeros(0, dtype=np.int64), np.zeros(0, dtype=np.int64)
        )

    from ..native import hash_join_call

    if len(rkeys) and len(lkeys):
        native = hash_join_call(rkeys, lkeys, left_outer=(op.how == "left"))
        if native is not None:
            l_idx, r_idx = native
            return _emit(l_idx.astype(np.int64), r_idx.astype(np.int64))
    order = np.argsort(rkeys, kind="stable")
    span = 0
    if len(rkeys) and len(lkeys):
        kmin = min(int(rkeys.min()), int(lkeys.min()))
        kmax = max(int(rkeys.max()), int(lkeys.max()))
        span = kmax - kmin + 1
    if 0 < span <= 4 * (len(lkeys) + len(rkeys)):
        # Dense key range: bincount + cumsum offsets replace the two
        # binary searches (random-access searchsorted over millions of
        # probes is the profile's hot spot).
        kcounts = np.bincount(rkeys - kmin, minlength=span)
        key_starts = np.zeros(span + 1, dtype=np.int64)
        np.cumsum(kcounts, out=key_starts[1:])
        lo = key_starts[lkeys - kmin]
        counts = kcounts[lkeys - kmin]
        hi = lo + counts
    else:
        srk = rkeys[order]
        lo = np.searchsorted(srk, lkeys, side="left")
        hi = np.searchsorted(srk, lkeys, side="right")
        counts = hi - lo
    if op.how == "left":
        counts = np.maximum(counts, 1)  # unmatched keep one null row
        unmatched = (hi - lo) == 0
    total = int(counts.sum())
    starts = np.zeros(len(counts) + 1, dtype=np.int64)
    np.cumsum(counts, out=starts[1:])
    l_idx = np.repeat(np.arange(len(lkeys), dtype=np.int64), counts)
    within = np.arange(total, dtype=np.int64) - np.repeat(starts[:-1], counts)
    if len(rkeys):
        r_idx = order[
            np.clip(np.repeat(lo, counts) + within, 0, len(rkeys) - 1)
        ]
    else:
        r_idx = np.full(total, -1, dtype=np.int64)
    if op.how == "left" and len(rkeys):
        r_idx = np.where(np.repeat(unmatched, counts), -1, r_idx)
    return _emit(l_idx, r_idx)


def _packed_key_ids(left, left_on, l_remap, right, right_on, r_remap):
    """Dense i64 key ids comparable across both sides (np.unique over the
    stacked key planes of the concatenated inputs)."""
    def planes(b, cols, remap):
        out = []
        for c in cols:
            for i, p in enumerate(b.cols[c]):
                q = p
                if i == 0 and c in remap:
                    q = remap[c][np.clip(p, 0, None)]
                    q = np.where(p >= 0, q, NULL_ID)
                out.append(np.asarray(q))
        return out
    lp = planes(left, left_on, l_remap)
    rp = planes(right, right_on, r_remap)
    if (
        len(lp) == 1
        and np.issubdtype(lp[0].dtype, np.integer)
        and np.issubdtype(rp[0].dtype, np.integer)
    ):
        # Single-plane INTEGER keys compare directly — no densification
        # pass (the int64 cast is equality-preserving, wrapping uints
        # bijectively). Floats must densify: casting would truncate
        # 1.2 and 1.7 onto the same key.
        return (lp[0].astype(np.int64, copy=False),
                rp[0].astype(np.int64, copy=False))
    # Exact densify: per-plane np.unique codes (lossless for ANY dtype —
    # a blanket int64 cast would truncate float keys), then one unique
    # over the code tuples for multi-plane keys.
    codes = []
    for a, b in zip(lp, rp):
        _, inv = np.unique(np.concatenate([a, b]), return_inverse=True)
        codes.append(inv.astype(np.int64).reshape(-1))
    if len(codes) == 1:
        inv = codes[0]
    else:
        _, inv = np.unique(
            np.stack(codes, axis=1), axis=0, return_inverse=True
        )
        inv = inv.astype(np.int64).reshape(-1)
    return inv[: left.length], inv[left.length:]


def _assemble_join_host(left, right, op, l_idx, r_idx) -> HostBatch:
    """Row assembly for the host N:1 / N:M paths (r_idx=-1 -> null;
    l_idx=None -> every left row in order, its planes as they are)."""
    with _join_child("join.assemble", rows_out=len(r_idx)):
        return _gather_join_host(left, right, op, l_idx, r_idx)


def _gather_join_host(left, right, op, l_idx, r_idx) -> HostBatch:
    out_rel = left.relation.merge(
        right.relation.select(
            [c for c in right.relation.column_names if c not in op.right_on]
        ),
        suffix=op.suffix,
    )
    out_cols: dict = {}
    out_dicts: dict = {}
    names = iter(out_rel.column_names)
    for c in left.relation.column_names:
        n = next(names)
        out_cols[n] = (
            left.cols[c] if l_idx is None
            else tuple(p[l_idx] for p in left.cols[c])
        )
        if c in left.dicts:
            out_dicts[n] = left.dicts[c]
    for c in right.relation.column_names:
        if c in op.right_on:
            continue
        n = next(names)
        planes = []
        nullv = NULL_ID if right.relation.col_type(c) == DataType.STRING else 0
        for p in right.cols[c]:
            if len(p) == 0:  # empty build side: all-null fill
                taken = np.full(len(r_idx), nullv, dtype=p.dtype)
            else:
                taken = p[np.clip(r_idx, 0, None)]
                if op.how == "left":
                    taken = np.where(r_idx >= 0, taken, nullv).astype(p.dtype)
            planes.append(taken)
        out_cols[n] = tuple(planes)
        if c in right.dicts:
            out_dicts[n] = right.dicts[c]
    return HostBatch(
        relation=out_rel, cols=out_cols, length=len(r_idx), dicts=out_dicts
    )


def _union_host(mats) -> HostBatch:
    """Schema-aligned union with dictionary re-encoding.

    When the schema carries a ``time_`` column the result is merged in
    time order — the reference UnionNode's k-way ordered merge of
    cross-PEM streams (``src/carnot/exec/union_node.cc``); a stable sort
    over the concatenation is equivalent given each input is itself
    time-ordered, and stays a single vectorized pass.
    """
    first = mats[0]
    for m in mats[1:]:
        if tuple(m.relation.column_names) != tuple(first.relation.column_names):
            raise QueryError("union inputs must share a schema")
    out_cols: dict = {}
    out_dicts: dict = {}
    for c, dt in first.relation.items():
        if dt == DataType.STRING:
            merged = StringDictionary()
            planes = []
            for m in mats:
                d = m.dicts.get(c, StringDictionary())
                # union preserves existing ids (append-only), so earlier
                # planes stay valid as merged grows.
                merged, _, remap = merged.union(d)
                ids = m.cols[c][0]
                planes.append(
                    np.where(ids >= 0, remap[np.clip(ids, 0, None)], NULL_ID).astype(
                        np.int32
                    )
                )
            out_cols[c] = (np.concatenate(planes),)
            out_dicts[c] = merged
        else:
            out_cols[c] = tuple(
                np.concatenate([m.cols[c][i] for m in mats])
                for i in range(len(first.cols[c]))
            )
    if first.relation.has_column("time_"):
        order = np.argsort(out_cols["time_"][0], kind="stable")
        if not np.array_equal(order, np.arange(len(order))):
            out_cols = {
                c: tuple(p[order] for p in ps) for c, ps in out_cols.items()
            }
    return HostBatch(
        relation=first.relation,
        cols=out_cols,
        length=sum(m.length for m in mats),
        dicts=out_dicts,
    )


# -- fused lookup join --------------------------------------------------------
def try_fused_join(engine, nid, node, results, consumers):
    """N:1 join as an in-fragment device lookup, or None to fall back.

    Reference contrast: ``equijoin_node.cc`` materializes output rows
    through a host hash map; here, when the build side resolves to a
    dense-domain table, the probe stream keeps flowing — each window
    gathers the build columns on device and the downstream
    Map/Filter/Agg fuse into the same XLA program (VERDICT r03 ask
    #2: output-row assembly never leaves the device).
    """
    from ..types.dtypes import device_dtypes

    op = node.op
    if not engine.fused_lookup_join:
        return None
    if op.how not in ("inner", "left") or len(op.left_on) != 1:
        return None
    left_id, right_id = node.inputs
    left_res = results[left_id]
    if not isinstance(left_res, _Stream) or consumers.get(left_id, 0) > 1:
        return None
    if any(isinstance(o, (AggOp, LimitOp)) for o in left_res.chain):
        return None
    lc, rc = op.left_on[0], op.right_on[0]
    bound = _chain_out_relation(left_res, engine.registry)
    if bound is None:
        return None
    left_rel, left_dicts = bound
    if not left_rel.has_column(lc):
        return None
    l_dt = left_rel.col_type(lc)
    if len(device_dtypes(l_dt)) != 1:
        return None

    right_res = results[right_id]
    if (
        isinstance(right_res, _Stream)
        and consumers.get(right_id, 0) <= 1
        and any(isinstance(o, AggOp) for o in right_res.chain)
    ):
        built = _dense_agg_build(engine, right_res, op, l_dt, left_dicts, lc, rc)
        if isinstance(built, tuple) and built[0] == "fallback":
            # The aggregate already executed; keep its rows for the
            # generic join path rather than re-folding the stream.
            results[right_id] = built[1]
            built = _host_table_build(
                built[1], op, l_dt, left_dicts, lc, rc
            )
    else:
        if not isinstance(right_res, HostBatch):
            return None
        built = _host_table_build(right_res, op, l_dt, left_dicts, lc, rc)
    if built is None:
        return None
    lo, dom, found, value_tables, right_rel = built

    # Output naming: all left columns keep their names; right value
    # columns (minus the key) merge with the join suffix — the same
    # schema ``_join_out_schema`` produces for the host paths.
    try:
        out_rel = left_rel.merge(
            right_rel.select(
                [c for c in right_rel.column_names if c not in op.right_on]
            ),
            suffix=op.suffix,
        )
    except Exception:
        return None
    value_srcs = [c for c in right_rel.column_names if c not in op.right_on]
    out_names = out_rel.column_names[len(left_rel.column_names):]

    out_cols = []
    side: dict = {}
    prefix = f"__lj{nid}"
    for src, out_name in zip(value_srcs, out_names):
        dt = right_rel.col_type(src)
        if dt == DataType.STRING:
            return None  # string values need mid-chain dict plumbing
        planes = value_tables[src]
        out_cols.append((out_name, dt, len(planes)))
        for j, p in enumerate(planes):
            side[f"{prefix}:{out_name}:{j}"] = p
    side[f"{prefix}:found"] = found

    lj = LookupJoinOp(
        key_col=lc, how=op.how, prefix=prefix, lo=int(lo), dom=int(dom),
        out_cols=tuple(out_cols),
    )
    st = left_res.extend(lj)
    st.side.update(side)
    return st


def _dense_agg_build(engine, right_stream, op, l_dt, left_dicts, lc, rc):
    """Build lookup tables straight from a dense aggregate's device
    state: the slot-aligned finalize output IS the table (slot =
    key - lo), so the build side never visits the host."""
    if any(isinstance(o, LimitOp) for o in right_stream.chain):
        return None
    frag_probe = compile_fragment(
        right_stream.chain, right_stream.relation, right_stream.dicts,
        engine.registry, col_stats=_stream_col_stats(right_stream),
    )
    if (
        not frag_probe.is_agg
        or len(frag_probe.dense_domains) != 1
        or frag_probe.dense_strides not in ((), (1,))
        or frag_probe.limit is not None
    ):
        # (strided domains step-index their slots; the LookupJoinOp
        # gather arithmetic assumes stride 1.)
        return None
    # The dense slot space must be the probe key's own code space.
    agg_i = next(
        i for i, o in enumerate(right_stream.chain)
        if isinstance(o, AggOp)
    )
    agg = right_stream.chain[agg_i]
    if tuple(agg.group_cols) != (rc,):
        return None
    # Post-agg ops must leave the key column untouched — the slot
    # arithmetic pairs probe keys with SLOT indices, so a post map
    # that rewrites the key would silently mispair every row.
    for o in right_stream.chain[agg_i + 1:]:
        if isinstance(o, MapOp):
            key_expr = dict(o.exprs).get(rc)
            if key_expr != _col(rc):
                return None
    out_rel = frag_probe.relation
    if rc not in out_rel.column_names:
        return None
    if out_rel.col_type(rc) != l_dt:
        return None
    if l_dt == DataType.STRING:
        meta = next(m for m in frag_probe.out_meta if m.name == rc)
        if left_dicts.get(lc) is not meta.dict:
            return None
    if any(m.struct_fields for m in frag_probe.out_meta):
        return None
    # Execute the PROBE's fragment, not a recompile: an append racing
    # between two compiles (stats crossing the stats quantization
    # grain) would give the run a different dense domain/offset than
    # the lo/dom captured below, silently mispairing every lookup.
    # With the same fragment, a racing append past the captured
    # domain surfaces as dr._overflow and takes the reject path.
    dr = engine._run_fragment(right_stream, frag=frag_probe)
    reject = bool(np.asarray(dr._overflow))  # stats raced an append
    value_tables = {
        n: tuple(dr._cols[n])
        for n in out_rel.column_names
        if n != rc and n in dr._cols
    }
    if set(value_tables) != {c for c in out_rel.column_names if c != rc}:
        reject = True
    if reject:
        # Don't discard the executed aggregate: hand the (rebucketed
        # if needed) rows back so the generic join path reuses them
        # instead of re-folding the whole right stream.
        return ("fallback", dr.to_host())
    return (
        frag_probe.dense_offsets[0], frag_probe.dense_domains[0],
        dr._valid, value_tables, out_rel,
    )


def _host_table_build(right_hb, op, l_dt, left_dicts, lc, rc):
    """Build dense lookup tables from a materialized unique-key host
    batch (the post-agg N:1 case arriving as rows)."""
    if not right_hb.relation.has_column(rc):
        return None
    if right_hb.relation.col_type(rc) != l_dt:
        return None
    if right_hb.length == 0:
        return None
    kb = np.asarray(right_hb.cols[rc][0])
    strings = None
    if l_dt == DataType.STRING:
        ld = left_dicts.get(lc)
        rd = right_hb.dicts.get(rc)
        if ld is None or rd is None:
            return None
        if rd is not ld:
            # Re-express build keys in the probe's id space without
            # growing it: unseen keys can never match a probe row.
            remap = np.fromiter(
                (ld.lookup(s) for s in rd.strings),
                dtype=np.int64, count=len(rd),
            )
            kb = np.where(kb >= 0, remap[np.clip(kb, 0, None)], -1)
        strings = len(ld)
    # Dense and unique, or N:M / too wide and not this path.
    built = _unique_dense_build(kb, l_dt, strings, nulls_join=False)
    if built is None:
        return None
    lo, dom, row_of = built
    found = row_of >= 0
    slots = np.nonzero(found)[0]
    rows = row_of[slots]
    from ..types.dtypes import device_dtypes

    value_tables = {}
    for c in right_hb.relation.column_names:
        if c == rc:
            continue
        ddts = device_dtypes(right_hb.relation.col_type(c))
        planes = []
        for p, ddt in zip(right_hb.cols[c], ddts):
            # Device dtype, not host: FLOAT64 host planes are f64 but
            # the device-plane invariant is f32 — an f64 side table
            # would re-admit f64 into fused device code.
            p = np.asarray(p)
            t = np.zeros(dom, dtype=ddt)
            if len(p):
                t[slots] = p[rows]
            planes.append(t)
        value_tables[c] = tuple(planes)
    return lo, dom, found, value_tables, right_hb.relation
