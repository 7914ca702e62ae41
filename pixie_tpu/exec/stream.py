"""Stream & result primitives shared by the engine's execution modules.

A ``_Stream`` is the engine's unit of deferred work: a source (tables or
a host batch) plus the chain of fragment-fusable ops accumulated so far.
This module also owns the host-batch assembly helpers every executor
path (engine, joins, bridge merge, streaming) shares.

Reference parity: the exec-side RowBatch/Table plumbing around Carnot's
ExecNode chain (``src/carnot/exec/exec_node.h``) — here a chain becomes
one fused XLA fragment instead of a node-per-op push loop.
"""

from __future__ import annotations

import contextlib
import json
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from ..types.batch import HostBatch
from ..types.dtypes import DataType, host_dtypes
from ..types.relation import Relation
from ..types.strings import StringDictionary
from .plan import AggOp, MemorySourceOp


class QueryError(Exception):
    pass


class QueryCancelled(QueryError):
    """Raised mid-stream when a query's cancel event fires (the
    ExecState::keep_running / exec_graph abort path,
    ``src/carnot/exec/exec_state.h``)."""


@dataclass
class _Stream:
    relation: Relation
    dicts: dict
    chain: list
    source: object  # list[Table] | Table | HostBatch
    source_op: Optional[MemorySourceOp] = None
    # Query-constant side-input arrays (numpy, keyed by reserved names)
    # passed to the fragment program alongside each window — the build
    # tables of fused lookup joins ride here, staged once per query.
    side: dict = field(default_factory=dict)

    def extend(self, op):
        return _Stream(
            self.relation, self.dicts, self.chain + [op], self.source,
            self.source_op, dict(self.side),
        )


def _chain_out_relation(stream: "_Stream", registry):
    """(relation, dicts) after a stream's pre-stage chain, or None if the
    chain does not bind (the caller falls back to the generic path)."""
    from .fragment import _bind_pre_stage

    try:
        _, rel, dicts = _bind_pre_stage(
            list(stream.chain), stream.relation, dict(stream.dicts), registry
        )
    except Exception:
        return None
    return rel, dicts


def _stream_col_stats(stream: "_Stream"):
    """Merged per-column (min, max) bounds across a stream's source
    tablets (None when the source is not table-backed or any tablet
    lacks stats for a column)."""
    src = stream.source
    if not isinstance(src, list) or not src:
        return None
    merged: dict | None = None
    for t in src:
        ts = getattr(t, "col_stats", None)
        if ts is None:
            return None
        if not ts:
            continue  # empty tablet (or no int columns): contributes no rows
        if merged is None:
            merged = dict(ts)
        else:
            merged = {
                c: (min(merged[c][0], ts[c][0]), max(merged[c][1], ts[c][1]))
                for c in merged.keys() & ts.keys()
            }
    return merged or None


def _col(name):
    from .plan import ColumnRef

    return ColumnRef(name)


def _double_agg_groups(stream: "_Stream") -> "_Stream":
    """Return the stream with its AggOp's max_groups doubled (rebucket)."""
    from ..config import get_flag

    g = next(
        (op.max_groups for op in stream.chain if isinstance(op, AggOp)), None
    )
    if g is None:
        raise AssertionError("no AggOp in overflowing chain")
    limit = get_flag("max_groups_limit")
    if g * 2 > limit:
        raise QueryError(
            f"group-by overflow at max_groups={g}; "
            f"rebucketing past the {limit} cap refused "
            "(PIXIE_TPU_MAX_GROUPS_LIMIT)"
        )
    return _stream_with_groups(stream, g * 2)


# -- the capacity of a keyed aggregate ---------------------------------------
# A keyed (non-dense) aggregate is compiled at a capacity g. The planner
# sizes g from the product of the group columns' NDVs, which is a bound,
# not an estimate (a request path belongs to one service: 65,536 live
# groups under a product of 2^21), and without sketches it is AggOp's
# default, climbed from by doubling with one whole re-fold and one
# compile a rung. At 2^22 slots a window's fold takes the chip 5 s where
# 2^17 take 0.46 s (PERF.md section 6). What the engine can observe and
# a plan cannot:
#
# - before the first fold of a chain, the JOINT key's distinct count:
#   one HyperLogLog row over the windows in range
#   (``CompiledFragment.group_sketch``, ``Engine._sized_agg_fragment``),
#   a pass that neither sorts nor keeps a keyed state. Asked for only
#   where the plan's capacity is large enough for a wrong one to hurt,
#   and taken only where it is far enough under the plan's to change
#   what a fold costs: at a quarter of the plan's or less (an eighth
#   until px/perf_flamegraph, whose 0.64 M live groups under a plan
#   clamped at 2^22 slots stayed there and folded 2^21-row windows into
#   four times the slots they need: PERF.md section 6, PR 39);
#   likewise where the plan's capacity is small but the rows are in hand
#   (a join's output) and outnumber it: without sketches the plan's is
#   AggOp's default, and 45 k edges climb from 4,096 slots by four
#   compiles of the sort route;
#   and where the chain COMPUTES a group key (a time bin, a UDF's image
#   of a string column: ``_computed_group_keys``) over tables that hold
#   more rows than the plan has slots: the planner has no sketch of a
#   computed column, the plan's capacity is again AggOp's default, and
#   a script's 75 k (query shape, second) groups climbed from 4,096 slots by
#   six rebuckets, 33 s of compiling each, past the request's timeout
#   (my chip run, PR 34: PERF.md section 6);
# - after a fold that overflowed, the rung its climb ended on.
#
# Either is remembered per (chain, source tables) on the engine, beside
# the learned join capacities and under their LRU
# (``joins.remember_capacity``), and the next compile of the same chain
# starts there: the sketch is read once, the ladder is climbed once. A
# remembered capacity never shrinks (two time ranges of one script share
# a chain, and flapping between their sizes would compile each time); a
# fold that overflows it climbs on and remembers the rung it settles on.
# A fold that fits is no observation: what it returns has been through
# the script's filters. Dense folds ignore g and are never recorded.
#
# The four numbers below come from one chip comparison (2^22 slots
# against 2^17, above); where between 8 Ki and 4 Mi slots a probe pass
# and a compile pay for themselves has not been swept
# (docs/EXECUTOR.md).

#: Head-room over the distinct keys a probe estimated.
_CAPACITY_SLACK = 1.25
#: Floor of a probed capacity: small states cost nothing to keep.
_CAPACITY_FLOOR = 1024
#: The planner's capacity is given up for a probed one from this ratio
#: on.
_CAPACITY_SHRINK = 4
#: A plan's capacity under this many slots is neither probed nor given
#: up for a smaller one: small states cost nothing to keep.
_PROBE_MIN_SLOTS = _CAPACITY_FLOOR * 8


def _agg_capacity_key(chain, source, where: str):
    """Key of a chain's remembered capacity, or None when the chain does
    not hash: its ops with the AggOp's capacity taken out (frozen
    dataclasses: equal structure, equal key; a few microseconds, where
    the fragment cache's canonical form costs sixty), the tables it
    folds (a plan does not tell two tables apart) and the tier (``pem``:
    rows folded; ``kelvin``: states merged). Two chains that differ only
    in literals Python holds equal (1, 1.0, True) share a key: they
    share a hint, never an answer."""
    key = (
        "agg", where,
        tuple(_with_agg_groups(chain, 0)),
        tuple(getattr(t, "name", "") for t in
              (source if isinstance(source, list) else [source])),
    )
    try:
        hash(key)
    except TypeError:
        return None
    return key


def _with_agg_groups(chain, g: int) -> list:
    import dataclasses

    return [
        dataclasses.replace(op, max_groups=g) if isinstance(op, AggOp) else op
        for op in chain
    ]


def _stream_with_groups(stream: "_Stream", g: int) -> "_Stream":
    return _Stream(
        stream.relation, stream.dicts, _with_agg_groups(stream.chain, g),
        stream.source, stream.source_op,
        dict(stream.side),  # keep lookup-join side tables
    )


def _probed_capacity(estimate: int, planned: int) -> int:
    """The capacity to fold at where a probe counted ``estimate``
    distinct keys under a plan of ``planned`` slots: the next power of
    two over them with head-room where that is far enough under the
    plan's to be worth a program, or over the plan's (which would
    overflow and climb there a rung and a compile at a time); the
    plan's otherwise."""
    want = int(estimate * _CAPACITY_SLACK) + 1
    cap = 1 << (max(want, _CAPACITY_FLOOR) - 1).bit_length()
    if want > planned:
        return cap
    shrink = planned >= _PROBE_MIN_SLOTS and cap * _CAPACITY_SHRINK <= planned
    return cap if shrink else planned


def _rows_in_hand(stream: "_Stream") -> int:
    """The rows of a stream whose source is a materialized batch (a
    join's output, a merged aggregate): known before any fold, where a
    table's rows in range are not."""
    return stream.source.length if isinstance(stream.source, HostBatch) else 0


def _computed_group_keys(chain) -> bool:
    """Whether the chain's aggregate groups by a column its own Maps
    COMPUTE (``px.bin(time_)``, a UDF's image of a string column) and do
    not merely select or rename: the planner sizes an aggregate from its
    group columns' sketches at the source, so such a key leaves the plan
    with AggOp's default capacity, which is no estimate."""
    from .plan import ColumnRef, MapOp

    agg_at = next(
        (i for i, op in enumerate(chain) if isinstance(op, AggOp)), None
    )
    if agg_at is None:
        return False
    cols = set(chain[agg_at].group_cols)
    for op in reversed(chain[:agg_at]):
        if not isinstance(op, MapOp):
            continue  # a filter neither makes nor renames a column
        exprs = dict(op.exprs)
        if any(not isinstance(exprs.get(c), ColumnRef) for c in cols):
            return True
        cols = {exprs[c].name for c in cols}
    return False


def _rows_at_source(stream: "_Stream") -> int:
    """The rows a stream's tables hold (all their retention: a bound on
    the rows in range, known before any window is staged); 0 for any
    other source."""
    src = stream.source
    if not isinstance(src, list):
        return 0
    return sum(int(getattr(t, "num_rows", 0) or 0) for t in src)


def _remember_climb(engine, chain, source, where: str, frag) -> None:
    """Record the rung a keyed fold's climb settled on."""
    from .joins import learned_capacity, remember_capacity

    key = _agg_capacity_key(chain, source, where)
    if key is not None and frag.slots > (learned_capacity(engine, key) or 0):
        remember_capacity(engine, key, frag.slots)


def _rebucket(stats, frm: int, to: int, where: str):
    """Around one re-fold after a capacity overflow: a ``rebucket`` span
    on a traced fragment (no-op without stats)."""
    return _subspan(stats, "rebucket", **{"from": frm, "to": to, "where": where})


def _window_shapes(cols) -> tuple:
    """Shape/dtype signature of a staged window (scan batching requires
    identical signatures so the stacked treedef stays one program).
    Side inputs are query-constant and never affect batchability."""
    return tuple(
        (c, tuple((p.shape, str(p.dtype)) for p in planes))
        for c, planes in sorted(cols.items())
        if c != "__side__"
    )


def _fold_rows(capacity: int, rows: int) -> int:
    """The length a fold program is handed for ``rows`` rows in range of
    a resident window padded to ``capacity``: the least of a quarter, a
    half and the whole that holds them. At most three lengths (three
    traces) a program whatever the ranges asked for, the whole being the
    program of a full window; a sort, a gather or a kernel over the
    window then pays for at most twice the rows in range, not for the
    padding (PERF.md section 6, PR 44)."""
    for length in (capacity // 4, capacity // 2):
        if rows <= length:
            return length
    return capacity


#: What the stage helpers return without stats (reusable, reentrant).
_NO_STATS = contextlib.nullcontext()


def _timed(stats, stage: str, rows: int = 0, nbytes: int = 0):
    """Stage timer context (no-op without stats) — keeps the analyze and
    plain execution paths one code path."""
    if stats is None:
        return _NO_STATS
    return stats.timed(stage, rows, nbytes)


def _dispatch(stats, fn, stage: str = "compute", windows: int = 1):
    """Around ONE program's enqueue call: the stage timer plus, on a
    traced fragment, a ``device.dispatch`` span named for the program as
    ``ProgramRegistry`` names its kind (no-op without stats)."""
    if stats is None:
        return _NO_STATS
    program = getattr(fn, "kind", None) or getattr(fn, "__name__", "program")
    return stats.dispatch(program, stage, windows)


def _subspan(stats, name: str, **attrs):
    """A named child span of a traced fragment (no-op without stats;
    ``as`` then binds None)."""
    if stats is None:
        return _NO_STATS
    return stats.subspan(name, **attrs)


def _query_trace(engine):
    """The trace of the query ``engine`` is running on this thread, or
    None outside one."""
    return getattr(getattr(engine, "_query_stats", None), "trace", None)


def _root_span(engine, name: str, **attrs):
    """A named child of the root of the trace of the query ``engine``
    is running on this thread (no-op outside one; ``as`` then binds
    None): host work between a trace's fragments."""
    trace = _query_trace(engine)
    if trace is None:
        return _NO_STATS
    return trace.span(name, **attrs)


def _window_selected(stats, n: int, start_ns: int, skipped: int) -> None:
    """The ``n``-th window a table scan handed over, asked for at
    ``start_ns`` (the range and pruner on the first, the zone-map skip,
    the resident window found or staged): a ``window.select`` span on a
    traced fragment, sampled as the per-window stage spans are (no-op
    without stats)."""
    if stats is not None and stats.keeps_interval(n):
        stats.stamped("window.select", start_ns, skipped=int(skipped))


def _device_wait(stats):
    """Around the sync the path has anyway — the host asks for a result
    until its bytes are on the host: a ``device.wait`` span on a traced
    fragment (no-op without stats; ``as`` binds the span, or None).
    Never adds a sync of its own."""
    return _subspan(stats, "device.wait")


def _device_fetch(stats, wait):
    """Inside a ``device.wait``, from the instant the path's own sync
    has returned to the last leaf on the host: a ``device.fetch`` span,
    child of that wait (``wait`` is what ``_device_wait`` bound). The
    program has run by then; what is left of the wait is its outputs'
    copies, started at the dispatch (``_start_fetch``) and waited for
    together (``_fetch_tree``). ``_note_fetched`` gives it its
    ``leaves`` and ``bytes``."""
    if stats is None or wait is None:
        return _NO_STATS
    return stats.subspan("device.fetch", parent=wait)


def _start_fetch(tree) -> None:
    """Start the device-to-host copy of every device leaf of ``tree``
    and return at once: no sync, the copies queue behind the program
    that makes the leaves. Called where that program is enqueued, so
    the bytes cross while the host waits for the path's sync and
    ``_fetch_tree`` finds them in flight or on the host already. Host
    leaves and Python scalars have nothing to start. A tree that is
    dropped unread (a fold that overflowed) drops its copies with it."""
    import jax

    for leaf in jax.tree_util.tree_leaves(tree):
        start = getattr(leaf, "copy_to_host_async", None)
        if start is not None:
            start()


def _fetch_tree(tree):
    """``tree`` on the host by ONE batched ``jax.device_get``: every
    leaf's copy in flight together (those ``_start_fetch`` started are
    found, the rest start here), then one wait a leaf in order; never a
    blocking copy a leaf. The one way anything a program made leaves
    the device in this layer. Device leaves come back as the numpy
    arrays ``np.asarray`` would give, bit for bit; host leaves and
    Python scalars pass through."""
    import jax

    return jax.device_get(tree)


def _note_fetched(span, leaves) -> None:
    """``leaves`` and ``bytes`` of the host arrays a fetch left in hand,
    onto its span: a ``device.fetch``, or the ``device.wait`` itself
    where the path's one batched get is also its sync (no-op
    without a span). ``QueryTrace._finalize_usage`` counts the bytes
    into ``usage.bytes_fetched``."""
    if span is not None:
        span.attributes.update(
            leaves=len(leaves),
            bytes=int(sum(getattr(a, "nbytes", 0) for a in leaves)),
        )


def _block_if(stats, x) -> None:
    """block_until_ready under analyze only (attribution needs sync).

    The always-on trace spine passes stats with ``sync=False``: stage
    timestamps still land, but the device is never fenced — pipeline
    overlap survives (the ISSUE-3 no-forced-sync contract)."""
    if stats is not None and getattr(stats, "sync", True):
        import jax

        jax.block_until_ready(x)


# -- host-batch assembly ------------------------------------------------------
def _result_tree(meta_list, cols, valid) -> tuple:
    """(validity, [a column's planes, ...]): the planes
    ``_to_host_batch`` reads, in the order it reads them (a struct
    column's one plane; a plane a host dtype otherwise): what
    ``_start_fetch`` / ``_fetch_result`` bring to the host."""
    return valid, [
        tuple(cols[m.name][
            :1 if m.struct_fields is not None else len(host_dtypes(m.dtype))
        ])
        for m in meta_list
    ]


def _start_result_fetch(meta_list, cols, valid, overflow) -> None:
    """``_start_fetch`` of what a ``finalize`` program's caller will
    read: its overflow flag and ``_result_tree``. Where the program is
    dispatched; ``_fetch_result(..., synced=True)`` collects them."""
    _start_fetch((overflow, _result_tree(meta_list, cols, valid)))


def _fetch_result(meta_list, cols, valid, stats=None, wait=None,
                  synced: bool = False):
    """A result's validity and planes to the host — what a
    ``device.wait`` span is put around: ``_result_tree`` by one
    ``_fetch_tree``. Returns (host cols, host valid).

    The validity's read is the path's sync, with every plane's copy
    started before it; what follows is the wait's ``device.fetch``
    (``stats`` and ``wait``: the fragment and its ``device.wait``
    span). ``synced``: the caller has read a flag of the same program
    already (and started the copies where it dispatched the program),
    and the validity is part of the fetch."""
    tree = _result_tree(meta_list, cols, valid)
    if not synced:
        _start_fetch(tree)
        np.asarray(valid)  # the path's sync
    with _device_fetch(stats, wait) as fetch:
        valid, planes = _fetch_tree(tree)
        _note_fetched(fetch, [valid, *(p for ps in planes for p in ps)])
    return {m.name: ps for m, ps in zip(meta_list, planes)}, valid


def _to_host_batch(meta_list, cols, valid) -> HostBatch:
    idx = np.nonzero(valid)[0]
    out_cols: dict = {}
    dicts: dict = {}
    rel_items = []
    for m in meta_list:
        if m.struct_fields is not None:
            planes = np.asarray(cols[m.name][0])[idx]  # [rows, k] floats
            d = StringDictionary()
            ids = np.fromiter(
                (
                    d.get_or_add(
                        json.dumps(
                            {f: round(float(v), 6) for f, v in zip(m.struct_fields, row)}
                        )
                    )
                    for row in planes
                ),
                dtype=np.int32,
                count=len(planes),
            )
            out_cols[m.name] = (ids,)
            dicts[m.name] = d
            rel_items.append((m.name, DataType.STRING))
            continue
        hdts = host_dtypes(m.dtype)
        out_cols[m.name] = tuple(
            np.asarray(p)[idx].astype(h) for p, h in zip(cols[m.name], hdts)
        )
        if m.dict is not None:
            dicts[m.name] = m.dict
        rel_items.append((m.name, m.dtype))
    return HostBatch(
        relation=Relation(rel_items), cols=out_cols, length=len(idx), dicts=dicts
    )


def _empty_host_batch(relation, dicts=None) -> HostBatch:
    cols = {
        n: tuple(np.empty(0, dtype=h) for h in host_dtypes(t))
        for n, t in relation.items()
    }
    return HostBatch(relation=relation, cols=cols, length=0, dicts=dict(dicts or {}))


def _concat_host(pieces, relation) -> HostBatch:
    nonempty = [p for p in pieces if p.length > 0]
    if not nonempty:
        dicts = pieces[0].dicts if pieces else {}
        return _empty_host_batch(relation, dicts)
    pieces = nonempty
    first = pieces[0]
    if len(pieces) == 1:
        return first
    cols = {
        n: tuple(
            np.concatenate([p.cols[n][i] for p in pieces])
            for i in range(len(first.cols[n]))
        )
        for n in first.relation.column_names
    }
    return HostBatch(
        relation=first.relation,
        cols=cols,
        length=sum(p.length for p in pieces),
        dicts=first.dicts,
    )


def _apply_limit(hb: HostBatch, limit) -> HostBatch:
    if limit is None or hb.length <= limit:
        return hb
    return HostBatch(
        relation=hb.relation,
        cols={n: tuple(p[:limit] for p in ps) for n, ps in hb.cols.items()},
        length=limit,
        dicts=hb.dicts,
    )
