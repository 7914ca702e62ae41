"""Live (streaming) query execution: infinite sources + incremental
results.

Reference parity: ``src/carnot/exec/memory_source_node.cc`` — a memory
source with no stop time streams forever, emitting row batches as the
table grows — and ``query_result_forwarder.go:470`` (StreamResults),
which relays incremental batches to the subscribed client until cancel.

TPU-first redesign: instead of a long-lived push graph, a **streaming
cursor** holds a per-tablet row watermark and, each round, folds only
the windows appended since the last round through the chain's compiled
fragment:

- Non-blocking chains (Map/Filter/Limit) emit each new batch once
  (``mode="append"``) — the infinite-MemorySource behavior.
- Blocking aggregates keep their group state ACROSS rounds: new windows
  fold into the persistent state and the re-finalized aggregate is
  emitted each round (``mode="replace"``) — incremental view
  maintenance, which Carnot does not do (it recomputes live views from
  scratch on every UI poll).

The distributed form (PEM partial states re-shipped per round, Kelvin
re-merging latest states) lives in ``services.agent``.
"""

from __future__ import annotations

import time
from dataclasses import dataclass

import numpy as np

from ..table_store.coldstore import take_decode_meter
from ..table_store.device_cache import take_restage_meter
from .engine import (
    Engine,
    QueryCancelled,
    QueryError,
    _device_wait,
    _dispatch,
    _double_agg_groups,
    _fetch_result,
    _stream_col_stats,
    _Stream,
    _timed,
    _to_host_batch,
)
from .fragment import compile_fragment_cached as compile_fragment
from .plan import (
    AggOp,
    FilterOp,
    LimitOp,
    MapOp,
    MemorySourceOp,
    Plan,
    ResultSinkOp,
    TableSinkOp,
)
from .stream import _fetch_tree, _start_fetch, _start_result_fetch


@dataclass
class StreamUpdate:
    """One incremental result delivery."""

    table: object  # sink name (None for bridge updates)
    batch: object  # HostBatch | AggStatePayload | RowsPayload
    seq: int
    # "append": batch holds only NEW rows; "replace": batch is the full
    # current aggregate (supersedes every earlier update); "state": a
    # partial-agg state snapshot for the merge tier (supersedes this
    # agent's earlier snapshots); "rows": a new-rows bridge payload.
    mode: str
    bridge_id: object = None


@dataclass
class _StreamChain:
    """A linear Source -> ops -> sink slice of a streamable plan."""

    source: MemorySourceOp
    ops: list
    sink_name: str
    is_agg: bool
    bridge_id: object = None  # set when the terminal is a BridgeSinkOp


def _linearize(plan: Plan) -> _StreamChain:
    """Validate + flatten a streamable plan.

    Streamable = exactly one MemorySource feeding a linear
    Map/Filter/Agg run into one result sink (or a BridgeSink — the
    distributed form's per-agent half). Joins/unions/UDTFs stay one-shot
    (QueryError) — the service layer can still poll those.
    """
    from .plan import BridgeSinkOp

    sources = [
        n for n in plan.nodes.values() if isinstance(n.op, MemorySourceOp)
    ]
    if len(sources) != 1:
        raise QueryError(
            f"streaming needs exactly one memory source, plan has "
            f"{len(sources)}"
        )
    node = sources[0]
    src = node.op
    if src.stop_time is not None:
        raise QueryError("a time-bounded source cannot stream (stop_time set)")
    consumers = {
        nid: [m.id for m in plan.nodes.values() if nid in m.inputs]
        for nid in plan.nodes
    }
    ops: list = []
    sink = None
    bridge_id = None
    cur = node.id
    while True:
        outs = consumers[cur]
        if len(outs) != 1:
            raise QueryError("streaming plans must be linear (fan-out found)")
        nxt = plan.nodes[outs[0]]
        if isinstance(nxt.op, (MapOp, FilterOp, AggOp, LimitOp)):
            ops.append(nxt.op)
            cur = nxt.id
        elif isinstance(nxt.op, (ResultSinkOp, TableSinkOp)):
            sink = nxt.op
            break
        elif isinstance(nxt.op, BridgeSinkOp):
            bridge_id = nxt.op.bridge_id
            break
        else:
            raise QueryError(
                f"operator {type(nxt.op).__name__} is not streamable"
            )
    # A LimitOp caps total rows; meaningful for append streams only.
    n_aggs = sum(isinstance(o, AggOp) for o in ops)
    if n_aggs > 1:
        raise QueryError("streaming supports at most one aggregate")
    if bridge_id is not None:
        name = None
    else:
        name = sink.name if isinstance(sink, ResultSinkOp) else sink.table
    return _StreamChain(
        source=src, ops=ops, sink_name=name, is_agg=n_aggs == 1,
        bridge_id=bridge_id,
    )


class StreamingQuery:
    """A live cursor over one plan: ``poll()`` folds everything appended
    since the last poll and emits 0..n StreamUpdates; ``run()`` loops
    until cancelled (the service-loop form)."""

    def __init__(self, engine: Engine, plan: Plan, emit, cancel=None,
                 script: str = ""):
        self.engine = engine
        self.emit = emit
        self.cancel = cancel
        self.chain = _linearize(plan)
        src = self.chain.source
        tablets = engine.table_store.tablets(src.table)
        if not tablets:
            raise QueryError(f"no table named {src.table!r}")
        self.tablets = tablets
        base = next((t for t in tablets if len(t.relation)), tablets[0])
        self.relation = base.relation
        self.dicts = dict(base.dicts)
        pre = []
        if src.columns is not None:
            from .engine import _col

            pre.append(MapOp(exprs=tuple((c, _col(c)) for c in src.columns)))
        self.ops = pre + list(self.chain.ops)
        self.seq = 0
        self.rows_emitted = 0
        self._wm: dict = {}  # id(tablet) -> row watermark
        for t in tablets:
            be = getattr(t, "_backend", None)
            start = src.start_time
            if be is None:
                self._wm[id(t)] = 0
            elif start is not None:
                self._wm[id(t)] = t.row_id_for_time(int(start), False)
            else:
                self._wm[id(t)] = t.first_row_id()
        # Where the CURRENT agg state's fold started, per tablet: ring
        # expiry crossing this mark means folded rows are gone and the
        # persistent state must refold from the live rows (otherwise a
        # replace-mode aggregate keeps counting expired rows a one-shot
        # rescan would not see).
        self._fold_lo: dict = dict(self._wm)
        self._state = None
        self._frag = None
        self._pruners: dict = {}  # id(tablet) -> zone-skip pruner | None
        # One lifecycle trace per stream (exec/trace.py): the stream
        # shows in /debug/queryz as in-flight until close()/run() ends
        # it; per-poll window work lands in its fragment stats. Begun
        # last so earlier __init__ raises can't leak an in-flight trace.
        from .trace import plan_script

        engine._name_device()
        self.trace = engine.tracer.begin_query(
            script=script or plan_script(plan), kind="stream"
        )
        self._tstats = None  # current compile's fragment stats
        try:
            self._compile()
        except BaseException as e:
            self.close(status="error", error=f"{type(e).__name__}: {e}")
            raise

    def _compile(self):
        stream = _Stream(self.relation, self.dicts, list(self.ops), self.tablets)
        self._frag = compile_fragment(
            self.ops, self.relation, self.dicts, self.engine.registry,
            col_stats=_stream_col_stats(stream),
        )
        if self.trace is not None:
            # A fresh fragment per (re)compile: rebuckets show as their
            # own fragment rows, the engine one-shot convention.
            self._tstats = self.trace.stats.new_fragment(self.ops)
        if self.chain.is_agg and self._state is not None:
            # Rebucket path: state restarts from scratch at the new size.
            self._state = None
        self._pruners = {}  # fragment stats changed; rebuild lazily

    def _pruner_for(self, t):
        """Zone-map window pruner for one tablet (None = no skipping).
        Built once per compile; skips are charged to the stream's
        current fragment stats."""
        key = id(t)
        if key not in self._pruners:
            from .zoneskip import chain_pruner

            self._pruners[key] = chain_pruner(
                t, self.ops, self.dicts, stats=self._tstats
            )
        return self._pruners[key]

    def close(self, status: str = "ok", error: str = "") -> None:
        """End the stream's lifecycle trace (idempotent). ``run()`` calls
        this on exit; callers driving ``poll()`` directly should close
        explicitly so /debug/queryz stops listing the stream as
        in-flight."""
        tr, self.trace = self.trace, None
        if tr is not None:
            self.engine.tracer.end_query(tr, status=status, error=error)

    def _new_windows(self):
        """(cols, valid, (tablet_key, row_hi)) device windows appended
        since the last poll. ``cols is None`` marks a zone-map-pruned
        tail: no window survives past ``row_hi``'s predecessor, and the
        consumer should commit the watermark without folding.

        Watermarks are NOT advanced here: with the prefetch pipeline this
        generator runs up to ``pipeline_depth`` windows ahead of the
        consumer, and advancing eagerly would mark windows consumed that
        an error/cancel then drops forever. The consumer commits
        ``self._wm[tablet_key] = row_hi`` only AFTER folding/emitting a
        window (at-least-once, matching the serial executor)."""
        for t in self.tablets:
            be = getattr(t, "_backend", None)
            if be is None:
                continue
            wm = self._wm[id(t)]
            end = t.end_row_id()
            # TRUE expiry may have dropped rows under the watermark
            # (tier-merged first: demotion does NOT advance it, so
            # demoted-but-never-folded rows are still visited).
            wm = max(wm, t.first_row_id())
            self._wm[id(t)] = wm
            if end <= wm:
                continue
            last_hi = wm
            for win, lo, hi in t.device_scan(
                window_rows=self.engine.window_rows,
                start_row=wm, stop_row=end,
                prune=self._pruner_for(t),
            ):
                # Cold decode ran on this (producer) thread inside the
                # staging call — charge it via the locked fragment stats.
                dsec, dbytes = take_decode_meter()
                if self._tstats is not None and (dsec or dbytes):
                    self._tstats.add("decode", dsec, nbytes=dbytes)
                rsec, rbytes = take_restage_meter()
                if self._tstats is not None and rbytes:
                    self._tstats.add("restage", rsec, nbytes=rbytes)
                last_hi = hi
                yield win.cols, (
                    np.int32(lo - win.row0), np.int32(hi - win.row0)
                ), (id(t), hi)
            if last_hi < end:
                # Zone maps pruned the tail windows. Pruned windows in
                # the MIDDLE of a scan are covered by the next surviving
                # window's commit (the watermark is a scalar), but a
                # pruned tail would otherwise leave the watermark short:
                # the poll would emit nothing and every later poll would
                # rescan (and re-prune) the same windows. Yield a
                # column-less marker so the consumer commits ``end`` and
                # still counts the poll as progress — the pruner proved
                # the predicate matches no row in those windows, so
                # skipping the fold is exact.
                yield None, None, (id(t), end)

    def _check_cancel(self):
        if self.cancel is not None and self.cancel.is_set():
            raise QueryCancelled("stream cancelled")

    def _pipelined_windows(self):
        """``_new_windows`` behind the engine's window-prefetch pipeline:
        the next appended window stages on a background thread while the
        current one folds/emits. Callers wrap iteration in try/finally
        close() (no leaked prefetch threads on cancel/StopStream).

        Empty polls (nothing appended since the watermark) run serial —
        a 0.25s-interval idle stream must not churn a thread per poll."""
        from .pipeline import WindowPipeline

        depth = getattr(self.engine, "pipeline_depth", 1)
        if depth > 1 and not self._has_new_rows():
            depth = 1
        return WindowPipeline(
            self._new_windows(), depth, cancel=self.cancel,
            stats=self._tstats,
        )

    def _has_new_rows(self) -> bool:
        # Mirrors _new_windows' watermark arithmetic (clamp to
        # first_row_id for ring expiry, compare against end_row_id);
        # keep the two in lockstep. Disagreement is only a perf wobble
        # (thread churn or a serial poll), never a correctness issue —
        # _new_windows alone decides what is yielded.
        for t in self.tablets:
            be = getattr(t, "_backend", None)
            if be is None:
                continue
            wm = max(self._wm[id(t)], t.first_row_id())
            if t.end_row_id() > wm:
                return True
        return False

    def _fold_new(self, frag):
        """Shared agg half: fold newly appended windows into the
        persistent group state. Returns (rows, folded)."""
        rows = 0
        if self._state is not None:
            for t in self.tablets:
                be = getattr(t, "_backend", None)
                if be is not None and (
                    t.first_row_id() > self._fold_lo.get(id(t), 0)
                ):
                    # TRUE expiry dropped rows ALREADY folded into the
                    # persistent state — refold from the live rows so
                    # the replace-mode aggregate matches what a
                    # one-shot rescan would compute (materialized-view
                    # bit-identity across expiry churn). Demotion alone
                    # never triggers this: the tier-merged first row id
                    # only moves on cold eviction.
                    self._state = None
                    break
        if self._state is None:
            self._state = frag.init_program()
            # Restart folds everything from the source's start.
            for t in self.tablets:
                be = getattr(t, "_backend", None)
                if be is not None:
                    start = self.chain.source.start_time
                    pos = (
                        t.row_id_for_time(int(start), False)
                        if start is not None
                        else t.first_row_id()
                    )
                    self._wm[id(t)] = pos
                    # The effective fold start: expiry may already sit
                    # past a time-derived position.
                    self._fold_lo[id(t)] = max(pos, t.first_row_id())
        folded = False
        st = self._tstats
        if st is not None:
            st.fold, st.group, st.slots = frag.fold, frag.group, frag.slots
        pipe = self._pipelined_windows()
        try:
            for cols, valid, (wm_key, wm_hi) in pipe:
                self._check_cancel()
                if cols is not None:
                    with _dispatch(st, frag.update):
                        self._state = frag.update(self._state, cols, valid)
                    w_rows = int(valid[1] - valid[0])
                    rows += w_rows
                    if st is not None:
                        st.windows += 1
                        st.rows_in += w_rows
                # A column-less marker (zone-map-pruned tail) folds
                # nothing but still counts as progress: rows WERE
                # consumed, so the poll must emit (matching the serial
                # executor, which emits the unchanged aggregate).
                folded = True
                self._wm[wm_key] = wm_hi  # commit AFTER the fold
        finally:
            pipe.close()
            self.engine._note_pipeline(pipe)
        return rows, folded

    def _rebucket(self):
        """Group overflow: double capacity (recompiling against fresh
        stats) and refold history."""
        new_ops = _double_agg_groups(
            _Stream(self.relation, self.dicts, list(self.ops), self.tablets)
        ).chain
        self.ops = list(new_ops)
        self._state = None
        self._compile()

    def _note_freshness(self) -> None:
        """Stamp this poll's staleness (now minus the source table's max
        event-time watermark) on the stream's trace: the usage field
        keeps the worst round — a live view that fell behind its ingest
        shows its backlog in __queries__ like any one-shot query.
        Exactly ONE watermark sweep per poll round: the overflow-
        rebucket retry re-enters ``_poll_inner``, not ``poll``, so it
        cannot re-sweep (shared helper + call structure; regression
        test in tests/test_result_cache.py)."""
        if self.trace is None:
            return
        from ..table_store import table as _table_mod

        wm = _table_mod.max_watermark_ns(self.tablets)
        if wm is not None:
            self.trace.note_freshness_lag(
                self.chain.source.table, (time.time_ns() - wm) / 1e6
            )

    def poll(self) -> int:
        """Fold new rows; emit updates. Returns rows consumed."""
        self._note_freshness()
        with self.engine._on_device():  # the engine's device: placement.py
            return self._poll_inner()

    def _poll_inner(self) -> int:
        frag = self._frag
        rows = 0
        if self.chain.bridge_id is not None:
            return self._poll_bridge(frag)
        if self.chain.is_agg:
            rows, folded = self._fold_new(frag)
            if not folded and self.seq > 0:
                return 0
            cols, valid, overflow = frag.finalize(self._state)
            _start_result_fetch(frag.out_meta, cols, valid, overflow)
            if bool(np.asarray(overflow)):
                self._rebucket()
                return self._poll_inner()
            cols, valid = _fetch_result(
                frag.out_meta, cols, valid, synced=True
            )
            hb = _to_host_batch(frag.out_meta, cols, valid)
            if frag.limit is not None and hb.length > frag.limit:
                hb = _head(hb, frag.limit)
            self.emit(StreamUpdate(
                table=self.chain.sink_name, batch=hb, seq=self.seq,
                mode="replace",
            ))
            self.seq += 1
            return rows
        # Non-blocking: each new window emits once.
        st = self._tstats
        pipe = self._pipelined_windows()
        try:
            for cols, valid, (wm_key, wm_hi) in pipe:
                self._check_cancel()
                if cols is None:
                    # Zone-map-pruned tail: no row can match, so there
                    # is nothing to emit — just advance the watermark.
                    self._wm[wm_key] = wm_hi
                    continue
                with _dispatch(st, frag.update):
                    out_cols, out_valid = frag.update(cols, valid)
                with _device_wait(st) as wait:
                    out_cols, out_valid = _fetch_result(
                        frag.out_meta, out_cols, out_valid, st, wait
                    )
                with _timed(st, "materialize"):
                    hb = _to_host_batch(frag.out_meta, out_cols, out_valid)
                if st is not None:
                    st.windows += 1
                    st.rows_in += int(valid[1] - valid[0])
                    st.rows_out += hb.length
                if hb.length == 0:
                    rows += int(valid[1] - valid[0])
                    self._wm[wm_key] = wm_hi
                    continue
                if frag.limit is not None:
                    left = frag.limit - self.rows_emitted
                    if left <= 0:
                        raise StopStream()
                    if hb.length > left:
                        hb = _head(hb, left)
                self.emit(StreamUpdate(
                    table=self.chain.sink_name, batch=hb, seq=self.seq,
                    mode="append",
                ))
                self.seq += 1
                self.rows_emitted += hb.length
                rows += int(valid[1] - valid[0])
                self._wm[wm_key] = wm_hi  # commit AFTER the emit
                if frag.limit is not None and self.rows_emitted >= frag.limit:
                    raise StopStream()
        finally:
            pipe.close()
            self.engine._note_pipeline(pipe)
        return rows

    def _poll_bridge(self, frag) -> int:
        """Per-agent half of a distributed live query: fold new windows,
        ship the current partial state (agg bridges) or the new rows
        (row-gather bridges) to the merge tier."""
        from .engine import AggStatePayload, RowsPayload

        rows = 0
        if self.chain.is_agg:
            rows, folded = self._fold_new(frag)
            # The first round ships even an empty (neutral) state: the
            # merge tier gates on hearing from EVERY data agent, and an
            # idle agent must not blank the whole live view.
            if not folded and self.seq > 0:
                return 0
            # The state stays on the device for the next poll's fold;
            # what ships is its image on the host, by one batched get
            # whose copies start before the flag's read.
            _start_fetch(self._state)
            if bool(np.asarray(self._state["overflow"])):
                self._rebucket()
                return self._poll_bridge(self._frag)
            payload = AggStatePayload(
                chain=tuple(self.ops),
                input_relation=self.relation,
                input_dicts=dict(self.dicts),
                state=_fetch_tree(self._state),
                dense_domains=frag.dense_domains,
                dense_offsets=frag.dense_offsets,
                dense_strides=frag.dense_strides,
            )
            self.emit(StreamUpdate(
                table=None, batch=payload, seq=self.seq, mode="state",
                bridge_id=self.chain.bridge_id,
            ))
            self.seq += 1
            return rows
        st = self._tstats
        pipe = self._pipelined_windows()
        try:
            for cols, valid, (wm_key, wm_hi) in pipe:
                self._check_cancel()
                if cols is None:
                    # Zone-map-pruned tail (see _new_windows): commit
                    # the watermark; no rows survive to ship.
                    self._wm[wm_key] = wm_hi
                    continue
                with _dispatch(st, frag.update):
                    out_cols, out_valid = frag.update(cols, valid)
                with _device_wait(st) as wait:
                    out_cols, out_valid = _fetch_result(
                        frag.out_meta, out_cols, out_valid, st, wait
                    )
                with _timed(st, "materialize"):
                    hb = _to_host_batch(frag.out_meta, out_cols, out_valid)
                rows += int(valid[1] - valid[0])
                if st is not None:
                    st.windows += 1
                    st.rows_in += int(valid[1] - valid[0])
                    st.rows_out += hb.length
                if hb.length != 0:
                    self.emit(StreamUpdate(
                        table=None, batch=RowsPayload(batch=hb),
                        seq=self.seq, mode="rows",
                        bridge_id=self.chain.bridge_id,
                    ))
                    self.seq += 1
                self._wm[wm_key] = wm_hi  # commit AFTER the emit
        finally:
            pipe.close()
            self.engine._note_pipeline(pipe)
        return rows

    def run(self, poll_interval_s: float = 0.25, max_rounds=None) -> int:
        """Poll until cancelled (or the row limit / max_rounds hits).
        Returns the number of updates emitted."""
        rounds = 0
        status, error = "ok", ""
        try:
            while True:
                self._check_cancel()
                self.poll()
                rounds += 1
                if max_rounds is not None and rounds >= max_rounds:
                    break
                if self.cancel is not None:
                    if self.cancel.wait(poll_interval_s):
                        status = "cancelled"
                        break
                else:
                    time.sleep(poll_interval_s)
        except StopStream:
            pass  # row limit satisfied: a normal end
        except QueryCancelled as e:
            status, error = "cancelled", str(e)
        except BaseException as e:
            self.close(status="error", error=f"{type(e).__name__}: {e}")
            raise
        finally:
            self.close(status=status, error=error)
        return self.seq


class StopStream(Exception):
    """Row limit satisfied: the stream ends itself (LimitNode's abort
    signal to upstream sources)."""


def _head(hb, n: int):
    from ..types.batch import HostBatch

    return HostBatch(
        relation=hb.relation,
        cols={c: tuple(p[:n] for p in planes) for c, planes in hb.cols.items()},
        length=n,
        dicts=dict(hb.dicts),
    )


def stream_query(
    engine: Engine, query: str, emit, cancel=None, now_ns: int = 0,
    max_output_rows: int | None = None,
) -> StreamingQuery:
    """Compile a PxL script into a live StreamingQuery on ``engine``.

    ``max_output_rows=None`` (the default) disables the result-sink row
    cap: a live stream is unbounded by design; pass a value to cap the
    append stream like the reference's 10k default does for one-shots.
    """
    from ..planner import CompilerState, compile_pxl

    state = CompilerState(
        schemas={
            name: t.relation
            for name, t in engine.tables.items()
            if t is not None and len(t.relation)
        },
        registry=engine.registry,
        now_ns=now_ns,
        max_output_rows=max_output_rows or (1 << 62),
        table_stats=engine._compile_table_stats(),
    )
    compiled = compile_pxl(query, state)
    return StreamingQuery(engine, compiled.plan, emit, cancel=cancel,
                          script=query)
