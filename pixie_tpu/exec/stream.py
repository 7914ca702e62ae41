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
    import dataclasses

    from ..config import get_flag

    limit = get_flag("max_groups_limit")
    chain = []
    doubled = False
    for op in stream.chain:
        if isinstance(op, AggOp) and not doubled:
            g2 = op.max_groups * 2
            if g2 > limit:
                raise QueryError(
                    f"group-by overflow at max_groups={op.max_groups}; "
                    f"rebucketing past the {limit} cap refused "
                    "(PIXIE_TPU_MAX_GROUPS_LIMIT)"
                )
            chain.append(dataclasses.replace(op, max_groups=g2))
            doubled = True
        else:
            chain.append(op)
    if not doubled:
        raise AssertionError("no AggOp in overflowing chain")
    return _Stream(
        stream.relation, stream.dicts, chain, stream.source,
        stream.source_op, dict(stream.side),  # keep lookup-join side tables
    )


def _window_shapes(cols) -> tuple:
    """Shape/dtype signature of a staged window (scan batching requires
    identical signatures so the stacked treedef stays one program).
    Side inputs are query-constant and never affect batchability."""
    return tuple(
        (c, tuple((p.shape, str(p.dtype)) for p in planes))
        for c, planes in sorted(cols.items())
        if c != "__side__"
    )


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


def _device_wait(stats):
    """Around the sync the path has anyway — the host asks for a result
    until its bytes are on the host: a ``device.wait`` span on a traced
    fragment (no-op without stats). Never adds a sync of its own."""
    if stats is None:
        return _NO_STATS
    return stats.subspan("device.wait")


def _block_if(stats, x) -> None:
    """block_until_ready under analyze only (attribution needs sync).

    The always-on trace spine passes stats with ``sync=False``: stage
    timestamps still land, but the device is never fenced — pipeline
    overlap survives (the ISSUE-3 no-forced-sync contract)."""
    if stats is not None and getattr(stats, "sync", True):
        import jax

        jax.block_until_ready(x)


# -- host-batch assembly ------------------------------------------------------
def _fetch_result(meta_list, cols, valid):
    """A result's validity and planes to the host — what a
    ``device.wait`` span is put around. One copy a plane, the planes
    ``_to_host_batch`` reads and in its order, exactly the copies the
    path made when they were interleaved with the assembly (one batched
    ``jax.device_get`` is fewer round trips: PERF.md, PR 25, left to a
    ``perf_opt`` issue). Returns (host cols, host valid)."""
    valid = np.asarray(valid)
    host: dict = {}
    for m in meta_list:
        n = 1 if m.struct_fields is not None else len(host_dtypes(m.dtype))
        host[m.name] = tuple(np.asarray(p) for p in cols[m.name][:n])
    return host, valid


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
