"""Query engine: plan DAG -> streamed, jit-compiled execution.

Reference parity: the Carnot facade (``src/carnot/carnot.h:39-95``
Carnot::ExecutePlan) + ExecutionGraph (``exec/exec_graph.cc:295``). The
TPU execution model:

- Each maximal linear chain of Map/Filter/Agg/Limit over one input
  compiles to a single fragment program (see fragment.py).
- Tables stream through in fixed-capacity windows (static shapes -> one
  compile, reused every window; the Table::Cursor batch loop analog).
- DAG joints (Join/Union) materialize their small (post-agg) inputs and
  continue; joins run host-side on dense ids (N:1, right-unique) or
  fuse into the probe fragment (see joins.py).
- Aggregation group state survives across windows via the regroup
  machinery, so a billion-row table aggregates in O(windows) device
  dispatches with O(G) memory.

Module layout (split r5): stream.py (stream/result primitives),
joins.py (join routing + union + fused lookup build), bridge.py
(agent-mode payloads + merge). This module keeps the Engine facade and
the window-staging/fold execution core, and re-exports the split names
for compatibility.
"""

from __future__ import annotations

import collections
import itertools
import threading
import time

import numpy as np

from ..types.batch import HostBatch, bucket_capacity
from ..types.relation import Relation
from ..udf.registry import Registry, default_registry
from .bridge import (  # noqa: F401  (re-exported)
    AggStatePayload,
    RowsPayload,
    _PendingAggBridge,
    bind_bridge,
    bridge_payload,
    merge_agg_bridge,
)
from . import placement, threadmap
from .fragment import compile_fragment_cached as compile_fragment
from .fragment import RowSlice, window_rows
from .pipeline import WindowPipeline
from .trace import Span, Tracer, clock_ns, plan_script
from .joins import (  # noqa: F401  (re-exported)
    _join_dispatch,
    _union_host,
    traced_join_dispatch,
    try_fused_join,
)
# NOTE: DEVICE_JOIN_MIN_ROWS deliberately NOT re-exported — patching a
# re-exported copy would be a silent no-op; joins.py is the patch point.
from .plan import (
    AggOp,
    TableSinkOp,
    BridgeSinkOp,
    BridgeSourceOp,
    EmptySourceOp,
    FilterOp,
    JoinOp,
    LimitOp,
    MapOp,
    MemorySourceOp,
    OTelExportSinkOp,
    Plan,
    ResultSinkOp,
    UDTFSourceOp,
    UnionOp,
)
from .stream import (  # noqa: F401  (re-exported)
    QueryCancelled,
    QueryError,
    _apply_limit,
    _block_if,
    _chain_out_relation,
    _col,
    _concat_host,
    _device_wait,
    _dispatch,
    _agg_capacity_key,
    _double_agg_groups,
    _rebucket,
    _root_span,
    _subspan,
    _window_selected,
    _probed_capacity,
    _PROBE_MIN_SLOTS,
    _computed_group_keys,
    _rows_at_source,
    _rows_in_hand,
    _remember_climb,
    _stream_with_groups,
    _empty_host_batch,
    _fetch_result,
    _note_fetched,
    _start_result_fetch,
    _Stream,
    _stream_col_stats,
    _timed,
    _to_host_batch,
    _fold_rows,
    _window_shapes,
)


def _dispatch_finalize(frag, state, stats):
    """Enqueue ``frag.finalize`` over a fold's state: (cols, valid,
    overflow) on the device, with what ``DeviceResult.to_host`` reads of
    them started for the host behind the program (no sync: the result
    stays the caller's to read, or to drop)."""
    with _dispatch(stats, frag.finalize, "finalize"):
        out = cols, valid, overflow = frag.finalize(state)
        _start_result_fetch(frag.out_meta, cols, valid, overflow)
        _block_if(stats, out)
    return out


class DeviceResult:
    """Device-resident aggregate query output.

    Holds the finalized [G] column planes + validity on device, so a
    query's fold and finalize dispatch without a host sync in between.
    Callers compile/warm with ``materialize=False`` and control when the
    single readback — ``to_host()``, which also resolves group-overflow
    rebucketing — happens. ``block_until_ready()`` fences without
    reading back (it does NOT flush the journal).

    Reference contrast: Carnot's MemorySink always lands rows host-side
    (``src/carnot/exec/memory_sink_node.cc``); on TPU the result's natural
    home is HBM until a client asks for bytes.
    """

    def __init__(self, engine, stream, frag, cols, valid, overflow,
                 stats=None, qstats=None):
        self._engine = engine
        self._stream = stream
        self._frag = frag
        self._cols = cols
        self._valid = valid
        self._overflow = overflow
        self._stats = stats
        self._qstats = qstats  # the CREATING query's stats (analyze mode)
        self._host: HostBatch | None = None

    @property
    def relation(self):
        return self._frag.relation

    def block_until_ready(self) -> "DeviceResult":
        import jax

        jax.block_until_ready((self._cols, self._valid, self._overflow))
        return self

    def to_host(self) -> HostBatch:
        if self._host is not None:
            return self._host
        eng, stream, frag = self._engine, self._stream, self._frag
        cols, valid, overflow = self._cols, self._valid, self._overflow
        stats = self._stats
        climbed = False
        while True:
            # The aggregate's first sync: the finalize program has run
            # when its overflow flag is on the host.
            with _device_wait(stats):
                overflowed = bool(overflow)
            if not overflowed:
                break
            # NOTE: the rebucket re-folds the source table AS IT IS NOW —
            # rows appended between execute and to_host are included,
            # unlike the no-overflow snapshot. Callers needing snapshot
            # semantics materialize before further ingest (the service
            # shell serializes queries against appends anyway).
            # Rebucket: double max_groups and re-run the stream (the same
            # recovery the device join uses on output overflow; Carnot's
            # hash map grows instead, ``agg_node.cc``).
            stream = _double_agg_groups(stream)
            climbed = True
            with _rebucket(stats, frag.slots, frag.slots * 2, "pem"):
                frag = compile_fragment(
                    stream.chain, stream.relation, stream.dicts, eng.registry,
                    col_stats=_stream_col_stats(stream),
                )
                if self._qstats is not None:
                    # Fresh per-attempt stats: rows/windows stay
                    # per-attempt and the attempt is marked (analyze
                    # fidelity).
                    stats = self._qstats.new_fragment(stream.chain)
                    stats.ops = stats.ops + ("rebucket",)
                state = eng._fold_agg_state(stream, frag, stats)
                cols, valid, overflow = _dispatch_finalize(frag, state, stats)
        # The overflow flag above was this program's sync: all of this
        # wait is the fetch (of copies started where ``finalize`` was
        # dispatched).
        with _device_wait(stats) as wait:
            cols, valid = _fetch_result(
                frag.out_meta, cols, valid, stats, wait, synced=True
            )
        if climbed:
            _remember_climb(eng, stream.chain, stream.source, "pem", frag)
        with _timed(stats, "materialize"):
            out = _to_host_batch(frag.out_meta, cols, valid)
        if stats is not None:
            stats.rows_out = out.length
        self._host = _apply_limit(out, frag.limit)
        self._cols = self._valid = self._overflow = None  # release HBM
        return self._host

    def to_pydict(self, **kw):
        return self.to_host().to_pydict(**kw)


def _agg_tail(plan: Plan, nid: int) -> tuple:
    """(node ids, last id) of the Map / Filter ops and the closing Limit
    that follow node ``nid`` and nothing else: the rest of its fragment,
    up to the next blocking op or sink (a limit terminates a fragment;
    a node read twice, or one that reads two, ends it)."""
    tail, last = [], nid
    while True:
        readers = [n for n in plan.nodes.values() if last in n.inputs]
        if len(readers) != 1 or not isinstance(
            readers[0].op, (MapOp, FilterOp, LimitOp)
        ):
            return tail, last
        last = readers[0].id
        tail.append(last)
        if isinstance(readers[0].op, LimitOp):
            return tail, last


def _counting(prune, skipped: list):
    """``prune`` (``zoneskip.chain_pruner``'s, or None) counting the
    windows it skips into ``skipped[0]``."""
    if prune is None:
        return None

    def counted(lo, hi):
        skip = prune(lo, hi)
        skipped[0] += bool(skip)
        return skip

    return counted


class _PlanWalk:
    """The ``plan.walk`` span of ``_execute_plan_inner``: open while the
    loop walks the plan (sources found, chains extended), closed around
    every op that runs work (``with walk.paused():``), so the stretches
    of host time between a trace's fragments, joins and merges carry a
    name. One span a stretch; it opens again at the next node's
    ``step``, so a plan's last sink leaves no empty stretch behind."""

    def __init__(self, trace):
        self.trace = trace
        self._ctx = None
        self._pauses = 0  # ops that run work nest (a join materializes)

    def step(self) -> None:
        """At a node of the plan: the walk is on (again)."""
        if self._ctx is None and not self._pauses:
            self._ctx = self.trace.span("plan.walk")
            self._ctx.__enter__()

    def end(self) -> None:
        if self._ctx is not None:
            self._ctx.__exit__(None, None, None)
            self._ctx = None

    def paused(self) -> "_PlanWalk":
        return self

    def __enter__(self) -> None:
        self._pauses += 1
        self.end()

    def __exit__(self, *exc) -> None:
        self._pauses -= 1


class _QueryScratch:
    """Per-query execution state, one instance per in-flight
    ``execute_plan`` (thread-local on the engine). This is what used to
    live as engine attributes under ``_exec_guard``'s one-query-at-a-
    time serialization — moving it here is what lets independent
    queries overlap on one engine (certified by pxlock: the lock-order/
    request-from-handler rules repo-green + lockdep-clean concurrency
    suites; see docs/ANALYSIS.md "pxlock")."""

    __slots__ = (
        "cancel", "stats", "pipeline", "join_decision",
        "resource_report", "table_sinks",
    )

    def __init__(self, cancel=None, stats=None):
        self.cancel = cancel  # per-query cancel event (execute_plan arg)
        self.stats = stats  # the trace's stats spine (QueryStats)
        self.pipeline: dict | None = None
        self.join_decision = None
        self.resource_report = None
        self.table_sinks: dict = {}


class Engine:
    """Owns tables + registry; executes plans. (EngineState analog,
    ``src/carnot/engine_state.h``.)"""

    def __init__(self, registry: Registry | None = None,
                 window_rows: int | None = None,
                 pipeline_depth: int | None = None,
                 device=None):
        from ..config import get_flag
        from ..table_store import TableStore

        self.registry = registry or default_registry()
        # The one device this engine lives on (``exec/placement.py``): a
        # PEM a node on a chip of its own beside other engines of the
        # process. Its tables stage there, its programs run there, its
        # results are fetched from there. None: wherever JAX puts
        # things, as an engine always did.
        self.device = device
        self._stage_sharding = None
        if device is not None:
            import jax

            self._stage_sharding = jax.sharding.SingleDeviceSharding(device)
        self.table_store = TableStore()
        self.window_rows = window_rows or get_flag("window_rows")
        # Window-executor prefetch depth (pipeline.py): staging of window
        # N+1 overlaps compute of window N; 1 = serial.
        self.pipeline_depth = int(pipeline_depth or get_flag("pipeline_depth"))
        # Per-query execution scratch (thread-local: each concurrent
        # execute_plan runs on its own caller thread). The ``last_*``
        # attributes below are engine-level LAST-FINISHED-QUERY
        # snapshots for tests/observability — under concurrency
        # they are last-writer-wins by design; anything correctness-
        # bearing reads the scratch, never these.
        self._tls = threading.local()
        # Guards the last-* snapshots, pipeline totals and the
        # in-flight counters (tiny critical sections, no blocking calls
        # inside — lock-order leaf).
        self._state_lock = threading.Lock()
        self._inflight = 0
        self.max_inflight = 0  # high-water concurrent queries (tests/obs)
        # Pipeline accounting: per-query snapshot + engine-lifetime totals
        # (exported by services.observability.engine_collector).
        self._last_pipeline: dict | None = None
        self.pipeline_totals = {
            "windows": 0, "stage_secs": 0.0, "stall_secs": 0.0,
        }
        self.last_stats = None
        # Always-on query-lifecycle tracing (exec/trace.py): every
        # execute_plan gets a trace (spans + stats spine, ring-buffered,
        # /debug/queryz). Cheap: timestamps only, no device sync.
        self.tracer = Tracer()
        # Its spans name the engine's device; one given none learns
        # where JAX puts its work at its first request (``_device_id``).
        self.tracer.device_id = None if device is None else device.id
        # Engine-STATE mutation guard. Queries no longer serialize on it
        # (per-query state lives on ``_QueryScratch``); it remains for
        # subclasses that mutate engine-scoped execution state around
        # super().execute_plan() (DistributedEngine's replan swaps the
        # mesh) and as the "engine not stuck" probe the fault tests
        # acquire. Reentrant so such a subclass can nest.
        self._exec_guard = threading.RLock()
        self._last_table_sinks: dict = {}  # {table: rows} from TableSinkOps
        # Routing outcome of the most recent materialized JoinOp
        # (joins.JoinDecision): strategy, build-side swap, capacity,
        # overflow retries, zone-skipped windows. Tests and the ``join``
        # span read it; None until a query joins.
        self._last_join_decision = None
        self._last_resource_report = None
        # OTel egress collection (export_otel): init here, not lazily —
        # a hasattr-then-assign under concurrent queries could lose an
        # export.
        self.otel_exports: list = []
        # Learned join-output capacities, keyed by (mode, plan hash,
        # node): a repeated query starts at the rung its last run
        # settled on. Engine-scoped — plan hashes don't capture table
        # identity, so a shared cache would cross-seed engines running
        # the same script over different data.
        self._join_capacity_cache: dict = {}
        # Prepared merges (exec/bridge.py ``_PreparedMerge``): what the
        # Kelvin's merge of a chain's payloads needs beyond the values
        # in them, remembered by content under a bounded LRU. Merges of
        # different queries run concurrently on one Kelvin: the lock
        # guards lookup and insert, never a build.
        self._prepared_merges: collections.OrderedDict = (
            collections.OrderedDict()
        )
        self._prepared_merges_lock = threading.Lock()
        # Self-telemetry (services/telemetry.py TelemetryCollector):
        # when attached, finished traces fold into __queries__/__spans__
        # tables and observed per-script cardinalities feed back into
        # _compile_table_stats. None = off (the default for bare
        # engines; agents/deploy roles wire it).
        self.telemetry = None
        # Device-tier observability (exec/programs.py): the shared
        # device-memory monitor brackets every execute_plan so the
        # query's high-water device bytes land in
        # QueryResourceUsage.device_peak_bytes (memory_stats() is None
        # on CPU — the bracket then costs two no-op samples).
        from .programs import default_device_monitor

        self.device_memory = default_device_monitor()
        self.device_memory.start()  # no-op while DEVICE_MEMORY_POLL_S is 0
        # Local result cache (exec/result_cache.py; result_cache_mb
        # flag, 0 = off): broker-less deployments cache merged results
        # at execute_query exactly like the broker's execute path.
        from .result_cache import ResultCache

        self.result_cache = ResultCache()
        # Incremental materialized views (exec/views.py): lazily
        # constructed on first use — ViewRegistry imports streaming,
        # which imports this module.
        self._views = None

    # -- per-query scratch plumbing ------------------------------------------
    # The underscore accessors keep the long-standing call sites in
    # joins.py / bridge.py (`getattr(engine, "_query_stats", None)`,
    # `engine.last_join_decision = ...`) working unchanged while the
    # state behind them became per-query.
    @property
    def _scratch(self) -> "_QueryScratch | None":
        return getattr(self._tls, "scratch", None)

    @property
    def _query_stats(self):
        s = self._scratch
        return s.stats if s is not None else None

    @property
    def _cancel(self):
        s = self._scratch
        return s.cancel if s is not None else None

    @property
    def last_join_decision(self):
        s = self._scratch
        if s is not None and s.join_decision is not None:
            return s.join_decision
        return self._last_join_decision

    @last_join_decision.setter
    def last_join_decision(self, jd) -> None:
        s = self._scratch
        if s is not None:
            s.join_decision = jd
        self._last_join_decision = jd

    @property
    def last_resource_report(self):
        s = self._scratch
        if s is not None:
            return s.resource_report
        return self._last_resource_report

    @property
    def last_pipeline(self) -> dict | None:
        s = self._scratch
        if s is not None and s.pipeline is not None:
            return s.pipeline
        return self._last_pipeline

    @property
    def last_table_sinks(self) -> dict:
        s = self._scratch
        if s is not None:
            return s.table_sinks
        return self._last_table_sinks

    @property
    def tables(self) -> dict:
        """{name: default-tablet (or first) Table} view over the store."""
        out = {}
        for n in self.table_store.table_names():
            t = self.table_store.get_table(n)
            if t is None:
                tablets = self.table_store.tablets(n)
                t = tablets[0] if tablets else None
            out[n] = t
        return out

    # -- table management ----------------------------------------------------
    def create_table(self, name: str, relation: Relation | None = None,
                     max_bytes: int = -1):
        t = self.table_store.add_table(name, relation, max_bytes=max_bytes)
        # Tables created through an engine stage device windows at the
        # engine's streaming size from the first append on, on the
        # engine's device.
        t.device_window_rows = self.window_rows
        if self._stage_sharding is not None:
            t.stage_sharding = self._stage_sharding
        return t

    def append_data(self, name: str, data, time_cols=("time_",)):
        """Push path (Stirling's RegisterDataPushCallback analog)."""
        # Atomic get-or-create at THIS engine's streaming window size so
        # first appends stage device windows correctly (and concurrent
        # first appends never replace each other's table).
        t = self.table_store.ensure_table(
            name, device_window_rows=self.window_rows
        )
        if self._stage_sharding is not None:
            t.stage_sharding = self._stage_sharding
        return self.table_store.append_data(name, data, time_cols=time_cols)

    # -- execution -----------------------------------------------------------
    def execute_query(self, query: str, now_ns: int = 0,
                      max_output_rows: int = 10_000,
                      analyze: bool = False,
                      materialize: bool = True) -> dict:
        """Compile a PxL script and execute it (Carnot::ExecuteQuery parity,
        ``src/carnot/carnot.cc:122-134``). Returns {output name: HostBatch}.
        ``analyze`` records per-fragment stats on ``self.last_stats``.
        ``materialize=False`` leaves aggregate outputs device-resident
        (returns DeviceResult — call ``.to_host()`` for bytes)."""
        from ..planner import CompilerState, compile_pxl
        from . import result_cache as rc

        # The query's lifecycle trace starts HERE so the parse/compile/
        # plan phase gets its own span; execute_plan ends the trace.
        trace = self.tracer.begin_query(script=query, analyze=analyze)
        # Local result cache / materialized views (the broker-less
        # repeat fast path): only for fully-materialized, non-analyze
        # runs — an analyze run's point is the execution stats, and a
        # DeviceResult must not be shared between callers.
        servable = materialize and not analyze and "pxtrace" not in query
        cache_status = ""
        if servable and self.result_cache.enabled():
            status, entry, lag_ms = self.result_cache.lookup(
                query, now_ns, max_output_rows, self._table_watermark_ns
            )
            if status == rc.HIT:
                trace.cache = rc.HIT
                trace.usage.freshness_lag_ms = lag_ms
                self.tracer.end_query(trace, status="ok")
                return dict(entry.result)
            cache_status = status
        if servable:
            view_res = self._try_view_answer(
                query, now_ns, max_output_rows, trace
            )
            if view_res is not None:
                trace.cache = rc.VIEW
                self.tracer.end_query(trace, status="ok")
                return view_res
        trace.cache = cache_status
        try:
            with trace.span("compile"):
                state = CompilerState(
                    schemas={n: t.relation for n, t in self.tables.items()},
                    registry=self.registry,
                    now_ns=now_ns,
                    max_output_rows=max_output_rows,
                    table_stats=self._compile_table_stats(),
                )
                compiled = compile_pxl(query, state)
        except BaseException as e:
            self.tracer.end_query(
                trace, status="error", error=f"{type(e).__name__}: {e}"
            )
            raise
        # Watermark snapshot BEFORE execution (conservative: ingest
        # landing mid-scan makes the stored watermark older than
        # reality, so the next lookup re-validates rather than
        # over-trusting), and the cache disposition resolved before the
        # trace ends so __queries__ rows carry it.
        store_wms: dict | None = None
        if servable and self.result_cache.enabled():
            tables, _ = rc.scan_info(compiled.plan)
            wms = {t: self._table_watermark_ns(t) for t in tables}
            if tables and all(w is not None for w in wms.values()):
                store_wms = wms
            else:
                trace.cache = rc.BYPASS
        try:
            result = self.execute_plan(
                compiled.plan, analyze=analyze, materialize=materialize,
                trace=trace,
            )
        except BaseException as e:
            # Safety net for execute_plan overrides that can raise before
            # reaching the base implementation (e.g. DistributedEngine's
            # replan): end_query is idempotent, so the normal path —
            # where execute_plan already ended the trace — is a no-op.
            self.tracer.end_query(
                trace,
                status=(
                    "cancelled" if isinstance(e, QueryCancelled) else "error"
                ),
                error=f"{type(e).__name__}: {e}",
            )
            raise
        if store_wms is not None and isinstance(result, dict):
            self.result_cache.store(
                query, state.now_ns, max_output_rows, compiled.plan,
                result, store_wms.get,
            )
        return result

    def _table_watermark_ns(self, table: str):
        """Current max event-time watermark across ``table``'s tablets
        (None = unknown table / no time index) — the local engine's
        half of the result cache's validity predicate."""
        from ..table_store import table as _table_mod

        tablets = self.table_store.tablets(table)
        if not tablets:
            return None
        return _table_mod.max_watermark_ns(tablets)

    @property
    def views(self):
        """Lazily built ViewRegistry (exec/views.py) — deferred because
        views ride StreamingQuery, whose module imports this one."""
        if self._views is None:
            from .views import ViewRegistry

            self._views = ViewRegistry(self)
        return self._views

    def _try_view_answer(self, query: str, now_ns: int,
                         max_output_rows: int, trace):
        """Materialized-view fast path: count the run, auto/manifest-
        register when warranted, and answer finalize-over-state when a
        registered view covers this query. None = execute normally.
        Never raises — a view failure falls back to full execution."""
        from .views import view_candidates_enabled

        if not view_candidates_enabled(query):
            return None
        try:
            return self.views.serve(
                query, now_ns=now_ns, max_output_rows=max_output_rows,
                trace=trace,
            )
        except Exception:
            import logging

            logging.getLogger("pixie_tpu.views").warning(
                "materialized-view answer failed; executing normally",
                exc_info=True,
            )
            return None

    def _compile_table_stats(self) -> dict:
        """Ingest-sketch stats snapshot for the optimizer
        (``CompilerState.table_stats``): per-table row counts + per-key-
        column HLL NDV estimates. A few microseconds per column — the
        sketches were maintained at append time."""
        out: dict = {}
        # Snapshots: the agent's heartbeat thread builds this while a
        # query/ingest thread appends tables and sketched columns —
        # iterating the live dicts intermittently dies with "dictionary
        # changed size during iteration" (observed as a heartbeat-
        # thread flake that silently killed the heartbeat loop).
        for n, t in list(self.tables.items()):
            sk = getattr(t, "sketches", None)
            if not sk:
                continue
            cols = list(sk.cols.items())
            out[n] = {
                "rows": sk.rows,
                "ndv": {
                    c: s.ndv for c, s in cols if s.rows
                },
                # Global zone maps per sketched column — pxbound's join
                # overlap term (analysis/bounds.py) reads them.
                "zones": {
                    c: (s.lo, s.hi)
                    for c, s in cols
                    if s.rows and s.lo is not None
                },
            }
            # Storage-tier seeding (docs/STORAGE.md): pxbound reads the
            # OBSERVED per-tier bytes/row off the freshness envelope to
            # seed staged-bytes and cold-decode-bytes bounds — resident
            # widths the schema walk cannot see (compression, dict
            # codes vs raw strings).
            if getattr(t, "_tier", None) is not None:
                f = t.freshness()
                hr, cr = int(f["hot_rows"]), int(f["cold_rows"])
                out[n]["tier"] = {
                    "hot_rows": hr,
                    "cold_rows": cr,
                    "hot_row_bytes": f["hot_bytes"] / hr if hr else None,
                    "cold_row_bytes": f["cold_bytes"] / cr if cr else None,
                    "raw_row_bytes": (
                        (f["hot_bytes"] + f["cold_raw_bytes"]) / (hr + cr)
                        if hr + cr else None
                    ),
                }
        # Telemetry feedback (arXiv:2102.02440): OBSERVED per-script
        # output cardinalities from past runs, keyed by script hash
        # under a dunder key no table name can collide with. compile_pxl
        # resolves the entry for the script being compiled so optimizer
        # rules (push_agg_through_join sizing) can floor their capacity
        # estimates at reality instead of trusting a drifted sketch.
        if self.telemetry is not None:
            obs = self.telemetry.observed()
            if obs:
                out["__observed__"] = obs
        return out

    def set_metadata_state(self, state) -> None:
        """Attach k8s metadata; rebinds the metadata UDFs to a snapshot of
        ``state`` (reference: per-query AgentMetadataState), preserving all
        other registrations on this engine's registry."""
        from ..metadata.funcs import METADATA_FUNC_NAMES, register_metadata_funcs

        self.metadata_state = state
        reg = self.registry.clone("engine", exclude=METADATA_FUNC_NAMES)
        register_metadata_funcs(reg, state)
        self.registry = reg

    def execute_plan(
        self, plan: Plan, bridge_inputs: dict | None = None,
        analyze: bool = False, materialize: bool = True,
        cancel=None, trace=None,
    ) -> dict:
        """Execute a plan. Whole plans return {sink name: HostBatch}.

        Split-fragment plans (from the distributed splitter, agent mode):
        a plan ending in BridgeSinkOps additionally returns
        {("bridge", id): payload}; a merge plan starting from
        BridgeSourceOps reads ``bridge_inputs`` = {bridge id: [payloads]}.

        ``analyze`` records per-fragment, per-stage execution stats
        (exec_node.h:40 ExecNodeStats analog) on ``self.last_stats``.

        Concurrent queries overlap on one Engine: every per-query
        execution state (cancel handle, stats spine, pipeline snapshot,
        join decision, resource report, table sinks) lives on a
        thread-local :class:`_QueryScratch`, so the Agent's bus
        dispatcher threads (execute/merge/bridge work) and broker-side
        worker threads run independent queries side by side. Shared
        engine state is individually thread-safe: TableStore/Tracer/
        ProgramRegistry/DeviceMemoryMonitor carry their own locks, the
        learned join-capacity cache locks in joins.py, and the
        ``last_*`` snapshots are last-finished-query observability
        (``_state_lock``). Subclasses that mutate engine-SCOPED
        execution state around super() (DistributedEngine's mesh
        replan) still serialize on ``_exec_guard``.

        ``trace`` is the query's in-progress QueryTrace when the caller
        (execute_query) already began one; otherwise a fresh trace is
        started here. Either way this call ends it — after execution,
        so the trace sinks (slow-query log, OTLP push to a possibly-
        slow collector) never run inside the scratch scope.
        """
        if trace is None:
            trace = self.tracer.begin_query(
                script=plan_script(plan), analyze=analyze
            )
        self._name_device()
        status, error = "ok", ""
        try:
            with trace.annotation(), self._on_device():
                return self._execute_plan_scoped(
                    plan, bridge_inputs, analyze, materialize, cancel, trace
                )
        except QueryCancelled as e:
            status, error = "cancelled", str(e)
            raise
        except BaseException as e:
            status, error = "error", f"{type(e).__name__}: {e}"
            raise
        finally:
            self.tracer.end_query(trace, status=status, error=error)

    def _execute_plan_scoped(
        self, plan, bridge_inputs, analyze, materialize, cancel, trace
    ) -> dict:
        # The trace's stats spine IS the per-fragment stats object —
        # analyze just runs it with sync=True (see analyze.py).
        scratch = _QueryScratch(cancel=cancel, stats=trace.stats)
        # pxbound's plan-time resource envelope (analysis/bounds.py),
        # attached by compile_pxl: join-buffer pre-sizing reads it, and
        # the soundness gate compares it against the trace's observed
        # QueryResourceUsage.
        scratch.resource_report = getattr(plan, "resource_report", None)
        # Predicted-vs-observed calibration (__queries__ feedback loop):
        # stamp the plan's predicted cost on the trace so the telemetry
        # fold records it NEXT TO the observed usage — px/bound_accuracy
        # computes the per-script calibration ratio from the pair. The
        # broker path stamps its merged (logical + wire) cost instead.
        if trace.predicted is None and scratch.resource_report is not None:
            from ..analysis.bounds import merged_cost

            trace.predicted = merged_cost(scratch.resource_report, None)
        mem_token = (
            self.device_memory.query_begin()
            if self.device_memory is not None else None
        )
        prev = self._scratch  # defensive: a nested call restores it
        self._tls.scratch = scratch
        with self._state_lock:
            self._inflight += 1
            if self._inflight > self.max_inflight:
                self.max_inflight = self._inflight
        # Profiler attribution: CPU samples taken on this thread while
        # the plan runs carry the query's qid/tenant/script hash
        # (exec/threadmap.py; phase refined by pipeline/program hooks).
        tm_token = threadmap.bind(trace=trace, phase="host")
        try:
            return self._execute_plan_inner(plan, bridge_inputs, materialize)
        finally:
            threadmap.unbind(tm_token)
            self._tls.scratch = prev
            if analyze:
                self.last_stats = trace.stats
            trace.pipeline = (
                dict(scratch.pipeline) if scratch.pipeline else None
            )
            jd = scratch.join_decision
            if jd is not None:
                trace.usage.retries += int(getattr(jd, "retries", 0))
                trace.usage.skipped_windows += int(
                    getattr(jd, "skipped_windows", 0)
                )
            if mem_token is not None:
                trace.usage.device_peak_bytes = (
                    self.device_memory.query_end(mem_token)
                )
            # Publish the last-finished-query snapshots (observability/
            # test seams; last-writer-wins under concurrency).
            with self._state_lock:
                self._inflight -= 1
                self._last_pipeline = scratch.pipeline
                self._last_table_sinks = scratch.table_sinks
                # jd may be None: a finished non-join query clears the
                # snapshot (callers must not re-account a previous
                # query's decision).
                self._last_join_decision = jd
                self._last_resource_report = scratch.resource_report

    @staticmethod
    def _plan_fingerprint(plan: Plan) -> int:
        """Structural plan hash (cached on the plan object): keys the
        joins' learned-capacity cache so a repeated script starts at the
        output-capacity rung its last run settled on."""
        fp = getattr(plan, "_fingerprint", None)
        if fp is None:
            fp = hash(tuple(
                (nid, type(n.op).__name__, repr(n.op), tuple(n.inputs))
                for nid, n in sorted(plan.nodes.items())
            ))
            plan._fingerprint = fp
        return fp

    def _execute_plan_inner(
        self, plan: Plan, bridge_inputs: dict | None = None,
        materialize: bool = True,
    ) -> dict:
        walk = _PlanWalk(self._query_stats.trace)
        try:
            return self._walk_plan(plan, bridge_inputs, materialize, walk)
        finally:
            walk.end()

    def _walk_plan(self, plan: Plan, bridge_inputs, materialize: bool,
                   walk: _PlanWalk) -> dict:
        trace = walk.trace
        results: dict[int, object] = {}
        outputs: dict = {}
        consumers: dict[int, int] = {}
        for n in plan.nodes.values():
            for i in n.inputs:
                consumers[i] = consumers.get(i, 0) + 1

        def materialized(res):
            with walk.paused():
                return self._materialize(res)

        def mat_input(nid):
            """Materialize a node's result once; cache for fan-out."""
            r = results[nid]
            if not isinstance(r, HostBatch):
                r = results[nid] = materialized(r)
            return r

        def as_stream(res):
            """``res`` as the source of the next fragment: a batch in
            hand (a join's rows, a materialized aggregate) under a
            ``restream`` span."""
            if isinstance(res, _Stream):
                return res
            with walk.paused(), trace.span("restream", rows=res.length):
                return self._as_stream(res)

        absorbed: set = set()  # nodes a merge's program has run already
        for nid in plan.topo_order():
            if nid in absorbed:
                continue
            walk.step()
            node = plan.nodes[nid]
            op = node.op
            if isinstance(op, MemorySourceOp):
                tablets = self.table_store.tablets(op.table)
                if not tablets:
                    raise QueryError(f"no table named {op.table!r}")
                # Tablets share relation + string dictionaries (enforced by
                # TableStore); a query scans all of them.
                self._note_scan_freshness(op, tablets)
                base = next((t for t in tablets if len(t.relation)), tablets[0])
                chain = []
                if op.columns is not None:
                    chain.append(
                        MapOp(exprs=tuple((c, _col(c)) for c in op.columns))
                    )
                results[nid] = _Stream(
                    base.relation, dict(base.dicts), chain, tablets, op
                )
            elif isinstance(op, UDTFSourceOp):
                results[nid] = self._run_udtf(op)
            elif isinstance(op, EmptySourceOp):
                results[nid] = _empty_host_batch(
                    Relation(list(op.relation_items))
                )
            elif isinstance(op, (MapOp, FilterOp, AggOp, LimitOp)):
                upstream = results[node.inputs[0]]
                if isinstance(upstream, _PendingAggBridge):
                    # The finalize half of a split aggregate: merge the
                    # shipped partial states and finalize — the agent-mode
                    # form of the bridge collective.
                    if not (isinstance(op, AggOp) and op.mode == "finalize"):
                        raise QueryError(
                            "agg bridge must feed its finalize AggOp"
                        )
                    # ... and with it the plan's ops up to the next
                    # blocking op or sink: they run in the merge's one
                    # program, not as a fragment over its rows.
                    tail, last = _agg_tail(plan, nid)
                    absorbed.update(tail)
                    with walk.paused():
                        results[last] = merge_agg_bridge(
                            self, upstream, [plan.nodes[t].op for t in tail]
                        )
                    continue
                st = as_stream(upstream)
                if st.chain and isinstance(st.chain[-1], LimitOp):
                    # A limit terminates its fragment: apply the cap at its
                    # plan position, then keep chaining on the result.
                    st = as_stream(materialized(st))
                if isinstance(op, AggOp) and any(
                    isinstance(o, AggOp) for o in st.chain
                ):
                    # Two blocking aggs never share a fragment: the first
                    # materializes (its output is small), the second re-
                    # aggregates it (the splitter's cut-at-blocking-op rule,
                    # planner/distributed/splitter/splitter.h:75).
                    st = as_stream(materialized(st))
                results[nid] = st.extend(op)
            elif isinstance(op, JoinOp):
                with walk.paused():
                    t_join = clock_ns()
                    fused = try_fused_join(self, nid, node, results, consumers)
                    if fused is not None:
                        from .joins import JoinDecision

                        self.last_join_decision = JoinDecision(
                            strategy="fused",
                            reason="dense-domain N:1 in-fragment lookup",
                        )
                        results[nid] = fused
                        qstats = self._query_stats
                        if qstats is not None:
                            # The build alone: the probe rows flow through
                            # the fragment the lookup fused into, uncounted.
                            qstats.trace.add_span(
                                "join", t_join, clock_ns(), strategy="fused",
                                where="device", how=op.how,
                                build_rows=int(fused.chain[-1].dom),
                                probe_rows=0, rows_out=0,
                            )
                    else:
                        from .joins import stream_join_stats

                        # Ingest-sketch stats must be read BEFORE
                        # materialization (the table provenance dies with
                        # the stream); they steer build-side choice,
                        # capacity estimation and zone skipping.
                        lstats = stream_join_stats(
                            results[node.inputs[0]], op.left_on
                        )
                        rstats = stream_join_stats(
                            results[node.inputs[1]], op.right_on
                        )
                        left = mat_input(node.inputs[0])
                        right = mat_input(node.inputs[1])
                        # Join-buffer pre-sizing (pxbound): the plan-time
                        # capacity estimate covers inputs run-time sketches
                        # cannot see (post-aggregate build sides) — used as
                        # the fallback rung before the historical default.
                        report = self.last_resource_report
                        planned = (
                            report.join_capacity.get(nid)
                            if report is not None else None
                        )
                        results[nid] = traced_join_dispatch(
                            left, right, op, self,
                            left_stats=lstats, right_stats=rstats,
                            cap_key=(self._plan_fingerprint(plan), nid),
                            planned_capacity=planned,
                        )
            elif isinstance(op, UnionOp):
                mats = [mat_input(i) for i in node.inputs]
                with walk.paused():
                    results[nid] = _union_host(mats)
            elif isinstance(op, ResultSinkOp):
                src_id = node.inputs[0]
                r = results[src_id]
                if (
                    not materialize
                    and isinstance(r, _Stream)
                    and consumers.get(src_id, 0) <= 1
                ):
                    # Device-resident result: the readback (and any
                    # overflow rebucket) happens in DeviceResult.to_host.
                    with walk.paused():
                        outputs[op.name] = self._run_fragment(r)
                else:
                    outputs[op.name] = mat_input(src_id)
                    # The answer leaves the engine as ids and their
                    # dictionaries; what a client's decode will hold.
                    with walk.paused(), _root_span(
                        self, "payload", kind="result"
                    ) as sp:
                        if sp is not None:
                            out = outputs[op.name]
                            sp.attributes.update(
                                rows=out.length,
                                string_bytes=out.string_nbytes(),
                            )
            elif isinstance(op, TableSinkOp):
                hb = mat_input(node.inputs[0])
                with walk.paused():
                    self.append_data(op.table, hb)
                # Not a client output (clients iterate result tables);
                # recorded on the engine for callers/tests.
                self.last_table_sinks[op.table] = hb.length
            elif isinstance(op, OTelExportSinkOp):
                from .otel import batch_to_otlp

                hb = mat_input(node.inputs[0])
                with walk.paused():
                    self.export_otel(
                        batch_to_otlp(hb, op.spec), op.spec.endpoint
                    )
            elif isinstance(op, BridgeSinkOp):
                # (the payload's wire bytes are counted where it is
                # built: its ``payload`` span)
                with walk.paused():
                    outputs[("bridge", op.bridge_id)] = bridge_payload(
                        self, results[node.inputs[0]]
                    )
            elif isinstance(op, BridgeSourceOp):
                if not bridge_inputs or op.bridge_id not in bridge_inputs:
                    raise QueryError(f"no input for bridge {op.bridge_id}")
                results[nid] = bind_bridge(bridge_inputs[op.bridge_id])
            else:
                raise QueryError(f"unsupported operator {op}")
            # Fan-out of a stream: materialize once, share the batch —
            # EXCEPT pure table scans (empty/column-select chains over
            # table sources): their windows are device-cache-resident,
            # so each consumer re-scanning them is free, while a
            # materialize would round-trip the whole table through host
            # memory. (Consumers then fold against the table as it is
            # when THEY run — the same snapshot caveat DeviceResult
            # documents for rebuckets.)
            if consumers.get(nid, 0) > 1 and isinstance(results[nid], _Stream):
                st = results[nid]
                from .fragment import _pure_select_map

                pure_scan = (
                    isinstance(st.source, list)
                    and not st.side
                    and _pure_select_map(st.chain) is not None
                )
                if not pure_scan:
                    results[nid] = materialized(st)
        return outputs

    def _note_scan_freshness(self, op, tablets) -> None:
        """Stamp result staleness for one table scan onto the query's
        trace: the scan's stop-time (or now, for unbounded scans) minus
        the max event-time watermark across the table's tablets. Host
        attribute reads only — no backend lock, no device work."""
        qstats = self._query_stats
        trace = getattr(qstats, "trace", None) if qstats is not None else None
        if trace is None:
            return
        from ..table_store import table as _table_mod

        wm = _table_mod.max_watermark_ns(tablets)
        if wm is None:
            return  # no time index / nothing appended: no signal
        ref = op.stop_time if op.stop_time is not None else time.time_ns()
        trace.note_freshness_lag(op.table, (int(ref) - wm) / 1e6)

    def export_otel(self, payload: dict, endpoint) -> None:
        """OTel egress. Default: collect in-memory (``otel_exports``,
        initialized in ``__init__``; list.append is atomic under
        concurrent queries); deployments override/replace with an OTLP
        pusher (the reference ships over OTLP gRPC — grpc is gated in
        this environment)."""
        self.otel_exports.append({"endpoint": endpoint, "payload": payload})

    def _run_udtf(self, op: UDTFSourceOp) -> HostBatch:
        """Execute a UDTF source (``udtf_source_node.h`` analog): call its
        fn with this engine as context and shape the rows to the declared
        relation."""
        udtf = self.registry.get_udtf(op.name)
        args = dict(op.args)
        for entry in udtf.init_args:  # declared defaults (3-tuples)
            if len(entry) == 3 and entry[0] not in args:
                args[entry[0]] = entry[2]
        data = udtf.fn(self, **args)
        rel = Relation(list(udtf.relation))
        hb = HostBatch.from_pydict(data, relation=rel, time_cols=())
        return hb

    # -- window fold core -----------------------------------------------------
    def _fold_agg_state(self, stream: "_Stream", frag, stats=None):
        """Stream the source through the fragment's window fold, returning
        the accumulated (unfinalized) group state.

        Equal-capacity device-resident window runs fold through
        ``update_all`` — ONE scan program per chunk of windows instead of
        one dispatch per window."""
        import jax

        from ..config import get_flag

        init_state, agg_step, _ = self._compile_steps(frag)
        # Native fold, scan program or per-window loop: from the platform
        # the fragment's routes were decided for (``FoldPlan.platform``).
        if (
            self.cpu_parallel_fold
            and frag.plan.platform == "cpu"
            and frag.native_fold is not None
            and get_flag("cpu_fold_threads") != 1
        ):
            # CPU backend: XLA executes scatters single-threaded, capping
            # bincount-class aggregations at one core. Route the scatter
            # passes through the native multi-core kernel instead (XLA
            # still runs the elementwise pre-stage + slot packing).
            state = self._fold_agg_state_native(stream, frag, stats)
            if state is not None:
                return state
        # The fold's empty state: ONE program of no argument, made with
        # the fragment and enqueued here (``frag.init_program``; the mesh's
        # replicates its output), before the first window's program has
        # work. Made anew every request and never kept: a state is as
        # large as 134 MB, and the mesh step donates it.
        with _subspan(stats, "state.init", programs=1) as span:
            state = init_state()
            if isinstance(span, Span):
                span.attributes["leaves"] = len(
                    jax.tree_util.tree_leaves(state)
                )
        if stats is not None:
            # Onto its device.dispatch spans.
            stats.fold, stats.group, stats.slots = (
                frag.fold, frag.group, frag.slots
            )
            stats.remap_entries = frag.remap_entries
            stats.digests, stats.digest_slots, stats.digest_bins = (
                frag.plan.digests, frag.plan.digest_slots,
                frag.plan.digest_bins,
            )
            stats.digest_outputs = len(frag.plan.digest_owners)
        # Scan-folding trades W dispatches for one; on the CPU backend
        # dispatches are cheap and the jnp.stack of window planes is a
        # pure memory-bandwidth loss.
        # (DistributedEngine turns it off: update_all is a single-logical-
        # device jit and would bypass the shard_map distributed steps.)
        chunk_w = (
            get_flag("fold_scan_windows")
            if frag.update_all and self.scan_fold
            and frag.plan.platform == "tpu"
            else 0
        )
        pend_cols, pend_lo, pend_hi = [], [], []

        def note_ride(span, rows):
            # How a keyed sorted fold carries these rows' sums, and the
            # words of the maxima it sorts by (an ``any``'s among them),
            # on a traced fragment's ``device.dispatch``.
            if frag.ride is not None and isinstance(span, Span):
                way = frag.ride(rows)
                if way:
                    span.attributes["ride"] = way
                if frag.plan.max_words:
                    span.attributes["max_words"] = frag.plan.max_words

        def fold_resident(state, windows, los, his):
            """ONE program over a run of resident windows of one shape
            (``update`` of one, ``update_all`` of several), each with
            rows [lo, hi) in range. The program is handed ``rows`` rows
            of each window, a ``RowSlice`` from ``lo`` (or as far back
            as the window's end demands): the length ``_fold_rows``
            gives the run's longest range, so a range short against its
            window does not pay for the padding; whole windows (a full
            one in the run, the mesh step) go as they always went."""
            one = len(windows) == 1
            program = agg_step if one else frag.update_all
            capacity = window_rows(windows[0])
            rows = max(
                _fold_rows(capacity, hi - lo) for lo, hi in zip(los, his)
            ) if self.slice_windows else capacity
            starts = [min(lo, capacity - rows) for lo in los]
            # Host ints, not device buffers: no sync.
            starts, los, his = (
                np.asarray(v, dtype=np.int32)  # pxlint: disable=host-sync-hot-path
                for v in (starts, np.subtract(los, starts),
                          np.subtract(his, starts))
            )
            with _dispatch(stats, program, windows=len(windows)) as span:
                if isinstance(span, Span):
                    span.attributes["rows"] = rows * len(windows)
                    span.attributes["range_rows"] = int(his.sum() - los.sum())
                note_ride(span, rows)
                if one:
                    args = (windows[0], (los[0], his[0]))
                    starts = starts[0]
                else:
                    args = (tuple(windows), los, his)
                if rows < capacity:
                    args += (RowSlice(starts, rows),)
                state = program(state, *args)
                _block_if(stats, state)
            return state

        def flush_pending(state):
            if pend_cols:
                state = fold_resident(state, pend_cols, pend_lo, pend_hi)
            pend_cols.clear()
            pend_lo.clear()
            pend_hi.clear()
            return state

        pipe = self._window_pipeline(stream, stats)
        try:
            for cols, valid in pipe:
                resident = isinstance(valid, tuple)
                batchable = (
                    chunk_w > 1
                    and resident
                    and (
                        not pend_cols
                        or _window_shapes(cols) == _window_shapes(pend_cols[0])
                    )
                )
                # A resident window joins the pending run (no program
                # runs for it yet); each enqueue is one device.dispatch.
                if batchable:
                    pend_cols.append(cols)
                    pend_lo.append(int(valid[0]))
                    pend_hi.append(int(valid[1]))
                    if len(pend_cols) >= chunk_w:
                        state = flush_pending(state)
                elif resident:
                    state = fold_resident(
                        flush_pending(state), [cols],
                        [int(valid[0])], [int(valid[1])],
                    )
                else:
                    state = flush_pending(state)
                    with _dispatch(stats, agg_step) as span:
                        note_ride(span, window_rows(cols))
                        state = agg_step(state, cols, valid)
                        _block_if(stats, state)
                if stats is not None:
                    stats.windows += 1
        finally:
            pipe.close()
            self._note_pipeline(pipe)
        return flush_pending(state)

    def _fold_agg_state_native(self, stream: "_Stream", frag, stats=None):
        """Fold via the native multi-core segmented-fold kernel.

        Per window, XLA produces (slot ids, per-agg value columns) —
        elementwise work it handles well — and ``native/seg_fold.cc``
        does the scatter passes with one table per core. Output tables
        accumulate across windows IN PLACE (the carries are associative),
        so there is no per-window state or merge at all. Returns None to
        fall back when the kernel is unavailable or a dtype is exotic.
        """
        import jax

        import jax.numpy as jnp

        from ..native import seg_fold_call

        plan = frag.native_fold["plan"]
        inputs_jit = frag.native_fold["inputs_jit"]
        g = len(np.asarray(frag.init_state()["valid"]))
        # One output table per flattened carry leaf, (g+1) rows (slot g
        # is the masked-row trash), pre-filled with the UDA's neutral
        # (init carries are uniform fills by construction).
        _OP = {"count": 0, "sum": 1, "min": 2, "max": 3}
        specs = []  # (op, dtype, arg_index | None) per leaf
        outs = []
        treedefs = []  # (out_name, treedef, n_leaves) for scalar aggs
        digests = []  # (out_name, init, arg_index, w, mw) for sketches
        hist_shift = None
        for j, (out_name, uda_name, init) in enumerate(plan):
            if uda_name == "quantiles" or uda_name.startswith("_quantile_"):
                # Sketch aggs: the kernel accumulates the GLOBAL dual
                # histogram across every window; ONE compress at the end
                # replaces the XLA path's per-window compress+merge
                # (histogram addition is exact — strictly less work,
                # no added error).
                from ..ops.routes import digest_hist_bins

                b = digest_hist_bins(g)
                if not b or g * b > (1 << 22):  # host-table budget: XLA instead
                    return None
                hist_shift = 32 - b.bit_length() + 1
                digests.append((
                    out_name, init, j,
                    np.zeros(g * b, dtype=np.float32),
                    np.zeros(g * b, dtype=np.float32),
                ))
                continue
            leaves, treedef = jax.tree_util.tree_flatten(init(1))
            treedefs.append((out_name, treedef, len(leaves)))
            for li, leaf in enumerate(leaves):
                leaf = np.asarray(leaf)
                if uda_name == "mean":
                    # (sum, count) carry: leaf 0 sums the arg, leaf 1
                    # counts rows.
                    op, arg_i = (1, j) if li == 0 else (0, None)
                elif uda_name == "count":
                    op, arg_i = 0, None
                else:
                    op, arg_i = _OP[uda_name], j
                specs.append((op, leaf.dtype, arg_i))
                outs.append(np.full(g + 1, leaf.reshape(-1)[0], dtype=leaf.dtype))
        if not any(op == 0 for op, _dt, _a in specs):
            # Validity needs a row count; add a hidden one.
            specs.append((0, np.dtype(np.int64), None))
            outs.append(np.zeros(g + 1, dtype=np.int64))

        from ..native import np_view, seg_fold_raw_call, tdigest_hist_call

        raw = frag.native_fold.get("raw")
        if digests:
            # Sketch bins derive from the value planes the jit form
            # produces; the raw fast path handles scalar ops only.
            raw = None
        oob_any = False
        oob_acc = None  # ONE device scalar, read back ONCE post-loop
        xla_fallback = False  # aborted mid-stream: XLA re-runs the fold
        pipe = self._window_pipeline(stream, stats)
        try:
            for cols, valid in pipe:
                with _timed(stats, "compute"):
                    if raw is not None and isinstance(valid, tuple):
                        # Zero-device-work path: the kernel reads the
                        # staged planes directly (keys packed in-kernel;
                        # np_view shares the buffers, no copies).
                        planes = [
                            np_view(cols[c][0]) for c in raw["key_cols"]
                        ]
                        vals = [
                            None if a is None
                            else np_view(cols[raw["arg_cols"][a]][0])
                            for _op, _dt, a in specs
                        ]
                        oob_n = seg_fold_raw_call(
                            planes, raw["key_specs"], int(valid[0]),
                            int(valid[1]), g, specs, vals, outs,
                        )
                        if oob_n is not None:
                            oob_any = oob_any or oob_n > 0
                            if stats is not None:
                                stats.windows += 1
                            continue
                        # Unsupported dtype combo: fall through to the
                        # jit form for this (and subsequent) windows.
                    # NOTE: keep gids_dev/args referenced while the kernel
                    # reads their zero-copy views (np_view aliases
                    # buffers).
                    gids_dev, args, oob = inputs_jit(cols, valid)
                    gids = np_view(gids_dev)
                    vals = [
                        None if a is None else np_view(args[a])
                        for _op, _dt, a in specs
                    ]
                    if specs and not seg_fold_call(gids, g, specs, vals, outs):
                        xla_fallback = True
                        return None  # exotic dtype combo: XLA fallback
                    for _name, _init, j, w, mw in digests:
                        v = np_view(args[j])
                        if str(v.dtype) != "float32":
                            xla_fallback = True
                            return None
                        if not tdigest_hist_call(gids, v, g, hist_shift, w, mw):
                            xla_fallback = True
                            return None
                    # Deferred: a bool() here would force a device sync
                    # EVERY window, serializing the prefetch pipeline —
                    # accumulate on device (one scalar, O(1) memory)
                    # and read back once after the loop.
                    oob_acc = (
                        oob if oob_acc is None
                        else jnp.logical_or(oob_acc, oob)
                    )
                if stats is not None:
                    stats.windows += 1
        finally:
            pipe.close()
            if not xla_fallback:
                # A fallback's windows re-run through the XLA fold's own
                # pipeline — noting the aborted one would double-count.
                self._note_pipeline(pipe)
        if oob_acc is not None:
            # The one readback for the whole fold (materialization
            # boundary). # pxlint: disable=host-sync-hot-path
            oob_any = oob_any or bool(np.asarray(oob_acc))
        carries = {}
        k = 0
        for out_name, treedef, n_leaves in treedefs:
            leaves = [self._put(outs[k + i][:g]) for i in range(n_leaves)]
            carries[out_name] = jax.tree_util.tree_unflatten(treedef, leaves)
            k += n_leaves
        for out_name, init, _j, w, mw in digests:
            # ONE compression of the global histogram into the [G, K]
            # digest carry (batch_to_digest's ordered compress).
            from ..ops.tdigest import _compress

            kk = int(np.asarray(init(1)[0]).shape[1])
            b = len(w) // g
            w2 = w.reshape(g, b)
            means = np.where(w2 > 0, mw.reshape(g, b) / np.maximum(w2, 1e-30),
                             0.0).astype(np.float32)
            carries[out_name] = _compress(
                self._put(means), self._put(w2), kk
            )
        count_out = next(
            o for (op, _dt, _a), o in zip(specs, outs) if op == 0
        )
        return {
            "keys": (),
            "valid": self._put(count_out[:g] > 0),
            "carries": carries,
            "overflow": self._put(np.bool_(oob_any)),
        }

    # -- internals -----------------------------------------------------------
    def _as_stream(self, res) -> _Stream:
        if isinstance(res, _Stream):
            return res
        hb: HostBatch = res
        return _Stream(hb.relation, dict(hb.dicts), [], hb)

    def _windows(self, stream: _Stream, stats=None):
        """Slice source batches into <= window_rows chunks."""
        if isinstance(stream.source, HostBatch):
            batches = [stream.source]
        else:
            from .zoneskip import chain_pruner

            sop = stream.source_op
            tables = (
                stream.source if isinstance(stream.source, list) else [stream.source]
            )
            batches = itertools.chain.from_iterable(
                t.scan(
                    sop.start_time if sop else None,
                    sop.stop_time if sop else None,
                    prune=chain_pruner(
                        t, stream.chain, getattr(t, "dicts", stream.dicts),
                        stats=stats,
                    ),
                )
                for t in tables
            )
        for b in batches:
            for off in range(0, max(b.length, 1), self.window_rows):
                if b.length == 0:
                    yield b
                    break
                idx = slice(off, min(off + self.window_rows, b.length))
                if idx.start == 0 and idx.stop == b.length:
                    yield b
                else:
                    yield HostBatch(
                        relation=b.relation,
                        cols={
                            n: tuple(p[idx] for p in ps) for n, ps in b.cols.items()
                        },
                        length=idx.stop - idx.start,
                        dicts=b.dicts,
                    )

    # -- execution seams (overridden by DistributedEngine) -------------------
    # Whether this engine may consume device-resident table windows (HBM
    # cold store). DistributedEngine stages row-sharded instead.
    device_residency = True
    # Whether N:1 joins may fuse into probe fragments as device lookups
    # (joins.try_fused_join); DistributedEngine gates this on mesh
    # side-table replication.
    fused_lookup_join = True
    # CPU-backend thread-parallel window folding; DistributedEngine turns
    # it off (its fold steps run inside shard_map over the mesh).
    cpu_parallel_fold = True
    # TPU scan-fold window batching (update_all); DistributedEngine turns
    # it off for the same reason — update_all is not a distributed step.
    scan_fold = True
    # Whether a fold program is handed the rows in range of a resident
    # window (``fragment.RowSlice``) and not its padded capacity;
    # DistributedEngine turns it off: its windows are row-sharded over
    # the mesh, and a slice at a dynamic offset would reshard them.
    slice_windows = True
    # The joint-key sketch before a keyed aggregate's first fold
    # (_sized_agg_fragment): a plain jit a window, which DistributedEngine
    # turns off too (its windows are row-sharded over the mesh).
    probe_group_keys = True

    def _window_capacity(self, length: int) -> int:
        return max(bucket_capacity(self.window_rows), bucket_capacity(length))

    def _stage(self, hb: HostBatch, capacity: int):
        """Pad a host window to capacity and place it on device."""
        db = hb.to_device(capacity, sharding=self._stage_sharding)
        return db.cols, db.valid

    def _check_cancel(self) -> None:
        c = getattr(self, "_cancel", None)
        if c is not None and c.is_set():
            raise QueryCancelled("query cancelled")

    def _staged_windows(self, stream: "_Stream", stats=None):
        """Yield (cols, valid) device-staged windows for a stream.

        Table sources use the device-resident window cache (zero
        host->device transfer once staged — SURVEY.md §7 stage 1 "HBM as
        cold"); host batches and distributed engines stage per window.
        Streams with side inputs (fused lookup-join build tables) carry
        them in every window's cols under ``__side__`` — device_put once
        per query, then reused as runtime args (never closure constants).
        """
        if stream.side:
            yield from self._staged_windows_with_side(stream, stats)
            return
        yield from self._staged_windows_inner(stream, stats)

    def _window_pipeline(self, stream: "_Stream", stats=None) -> WindowPipeline:
        """Pipelined view of ``_staged_windows``: staging for window N+1
        runs on a prefetch thread while the caller computes window N
        (``pipeline_depth`` windows in flight; 1 = serial, no thread).
        Callers MUST wrap iteration in try/finally close() — that is the
        no-leaked-threads / no-use-after-cancel contract.

        A batch in hand that fits one window (a join's rows on their way
        to a re-aggregation) is staged serially: there is no window N+1
        to stage ahead of, and the thread's start and hand-over cost its
        one ``window.stage`` 2.8 or 6.0 ms on the chip, in phases seconds
        long, where the serial 2.2 never moves (PERF.md section 6, PR 32)."""
        one_window = 0 < _rows_in_hand(stream) <= self.window_rows
        return WindowPipeline(
            self._staged_windows(stream, stats),
            1 if one_window else self.pipeline_depth,
            cancel=getattr(self, "_cancel", None), stats=stats,
        )

    def _note_pipeline(self, pipe: WindowPipeline) -> None:
        """Fold a finished pipeline's counters into the per-query snapshot
        (``scratch.pipeline``, which the query's trace snapshots at end;
        falls back to the engine-level snapshot for callers outside an
        execute_plan scope — the streaming cursor, DeviceResult
        rebuckets) and the engine-lifetime totals (state-locked: the
        totals are read-modify-write shared across concurrent queries).
        """
        c = pipe.counters()
        s = self._scratch
        with self._state_lock:
            if s is not None:
                lp = s.pipeline
                if lp is None:
                    lp = s.pipeline = {
                        "depth": c["depth"], "windows": 0,
                        "stage_secs": 0.0, "stall_secs": 0.0,
                    }
            else:
                lp = self._last_pipeline
                if lp is None:
                    lp = self._last_pipeline = {
                        "depth": c["depth"], "windows": 0,
                        "stage_secs": 0.0, "stall_secs": 0.0,
                    }
            lp["depth"] = c["depth"]
            tot = self.pipeline_totals
            for d in (lp, tot):
                d["windows"] += c["windows"]
                d["stage_secs"] += c["stage_secs"]
                d["stall_secs"] += c["stall_secs"]

    def _on_device(self):
        """The scope a request of this engine runs in: the thread's
        uncommitted values and programs go to the engine's device
        (``exec/placement.py``); no-op on an engine given none."""
        return placement.scope(self.device)

    def _device_id(self) -> int:
        """The id of the device this engine's work runs on: its own, or
        where JAX puts the work of an engine given none."""
        import jax

        return (self.device or placement.current() or jax.devices()[0]).id

    def _name_device(self) -> None:
        """Before a request's trace begins its work: the tracer learns
        the device its ``device.*`` spans name, once."""
        if self.tracer.device_id is None:
            self.tracer.device_id = self._device_id()

    def _put(self, v):
        """THE way a host value (an array or a tree of them) this engine
        makes on a request's path goes to a device: its own, committed
        (a fused join's side table, a merge's remaps and shipped states,
        the native fold's carries). DistributedEngine replicates over
        its mesh instead."""
        return placement.put(v, self.device)

    def _staged_windows_with_side(self, stream: "_Stream", stats=None):
        side = {k: self._put(v) for k, v in stream.side.items()}
        for cols, valid in self._staged_windows_inner(stream, stats):
            yield {**cols, "__side__": side}, valid

    def _staged_windows_inner(self, stream: "_Stream", stats=None):
        from ..config import get_flag
        from ..table_store.coldstore import take_decode_meter
        from ..table_store.device_cache import take_restage_meter
        from .zoneskip import chain_pruner

        use_cache = (
            self.device_residency
            and get_flag("device_residency")
            and not isinstance(stream.source, HostBatch)
        )
        if use_cache:
            sop = stream.source_op
            start = sop.start_time if sop else None
            stop = sop.stop_time if sop else None
            tables = (
                stream.source
                if isinstance(stream.source, list)
                else [stream.source]
            )
            for t in tables:
                if getattr(t, "_backend", None) is None:
                    continue
                scan, skipped, n = None, [0], 0
                while True:
                    # A ``window.select`` a window the scan hands over,
                    # on whichever thread stages (the prefetch producer's
                    # when the pipeline is deep): the table's range and
                    # pruner on the first, then the zone-map skip and
                    # the resident window found (or staged).
                    t_select, before = clock_ns(), skipped[0]
                    if scan is None:
                        scan = t.device_scan(
                            start, stop, window_rows=self.window_rows,
                            prune=_counting(chain_pruner(
                                t, stream.chain,
                                getattr(t, "dicts", stream.dicts),
                                stats=stats,
                            ), skipped),
                        )
                    item = next(scan, None)
                    if item is None:
                        break
                    _window_selected(stats, n, t_select, skipped[0] - before)
                    n += 1
                    win, lo, hi = item
                    self._check_cancel()
                    # Cold-tier decode ran inside device_scan's staging
                    # (on THIS thread — the pipeline producer when
                    # prefetching): charge it to the query via the
                    # locked fragment stats, the only query-scoped
                    # object reachable from the producer thread.
                    dsec, dbytes = take_decode_meter()
                    # ... and so did the staging of a window the device
                    # cache missed.
                    rsec, rbytes = take_restage_meter()
                    if stats is not None:
                        if dsec or dbytes:
                            stats.add("decode", dsec, nbytes=dbytes)
                        if rbytes:
                            stats.add("restage", rsec, nbytes=rbytes)
                        stats.rows_in += hi - lo
                    # (lo, hi) scalar pair, not a mask: the fragment
                    # builds the iota mask INSIDE its program — no
                    # separate mask dispatch per window. np scalars
                    # stay dynamic (no retrace per offset).
                    yield win.cols, (
                        np.int32(lo - win.row0), np.int32(hi - win.row0)
                    )
            return
        for hb in self._windows(stream, stats=stats):
            self._check_cancel()
            dsec, dbytes = take_decode_meter()
            if stats is not None and (dsec or dbytes):
                stats.add("decode", dsec, nbytes=dbytes)
            with _timed(stats, "stage", rows=hb.length, nbytes=hb.nbytes):
                cols, valid = self._stage(hb, self._window_capacity(hb.length))
                _block_if(stats, cols)
            if stats is not None:
                stats.rows_in += hb.length
            yield cols, valid

    def _compile_steps(self, frag):
        """(init_state program, agg_step, rows_step) for a compiled
        fragment."""
        if frag.is_agg:
            return frag.init_program, frag.update, None
        return None, None, frag.update

    def _materialize(self, res) -> HostBatch:
        if isinstance(res, HostBatch):
            return res
        if isinstance(res, DeviceResult):
            return res.to_host()
        dr = self._run_fragment(res)
        if isinstance(dr, DeviceResult):
            return dr.to_host()
        return dr

    def _sized_agg_fragment(self, stream: "_Stream"):
        """(stream, fragment) of ``stream`` compiled at the capacity to
        fold it at (``exec/stream.py``, "the capacity of a keyed
        aggregate"): the one remembered for this chain and these tables;
        else, for a keyed aggregate whose plan asks for many slots, or
        for fewer than the rows in hand it is about to fold, or for
        fewer than its tables hold where a group key is computed (the
        plan's capacity is then a default, not an estimate), the one a
        sketch of the joint key over the windows in range gives, which
        is then remembered; else the plan's."""
        with _root_span(self, "fragment.bind") as sp:
            lookups: list = []  # "hit" / "miss" a compile_fragment call
            bound = self._bind_sized(stream, lookups.append)
            if sp is not None:
                sp.attributes["cached"] = (
                    "miss" if "miss" in lookups else "hit"
                )
            return bound

    def _bind_sized(self, stream: "_Stream", note):
        from .joins import learned_capacity, remember_capacity

        def compiled(st):
            return compile_fragment(
                st.chain, st.relation, st.dicts, self.registry,
                col_stats=_stream_col_stats(st), note=note,
            )

        frag = compiled(stream)
        if not frag.is_agg or frag.group == "dense":
            return stream, frag  # no capacity to choose
        key = _agg_capacity_key(stream.chain, stream.source, "pem")
        known = learned_capacity(self, key)
        if known is not None and known != frag.slots:
            stream = _stream_with_groups(stream, known)
            frag = compiled(stream)
        elif (
            known is None and key is not None and self.probe_group_keys
            and frag.group_sketch is not None
            and (frag.slots >= _PROBE_MIN_SLOTS
                 or _rows_in_hand(stream) > frag.slots
                 or (_computed_group_keys(stream.chain)
                     and _rows_at_source(stream) > frag.slots))
        ):
            cap = _probed_capacity(
                self._sketch_agg_groups(stream, frag), frag.slots
            )
            remember_capacity(self, key, cap)
            if cap != frag.slots:
                stream = _stream_with_groups(stream, cap)
                frag = compiled(stream)
        return stream, frag

    def _sketch_agg_groups(self, stream: "_Stream", frag) -> int:
        """The estimated distinct count of ``stream``'s joint group key:
        its windows through ``frag.group_sketch``, one program a window,
        one register row read back. A fragment of its own on the trace
        (``group_probe``), with a span of that name around the pass."""
        import jax

        from ..ops.hll import hll_estimate_np

        qstats = getattr(self, "_query_stats", None)
        stats = None
        if qstats is not None:
            stats = qstats.new_fragment(stream.chain)
            stats.ops = stats.ops + ("group_probe",)
        with _subspan(stats, "group_probe", slots=frag.slots) as sp:
            registers = frag.init_sketch()
            pipe = self._window_pipeline(stream, stats)
            try:
                for cols, valid in pipe:
                    with _dispatch(stats, frag.group_sketch):
                        registers = frag.group_sketch(registers, cols, valid)
                    if stats is not None:
                        stats.windows += 1
            finally:
                pipe.close()
                self._note_pipeline(pipe)
            with _device_wait(stats) as wait:
                registers = jax.device_get(registers)
                _note_fetched(wait, jax.tree_util.tree_leaves(registers))
            estimate = hll_estimate_np(registers[0])
            if sp is not None:
                sp.attributes["estimate"] = estimate
        return estimate

    def _run_fragment(self, stream: "_Stream", frag=None):
        """Run a stream's fragment; agg chains return a DeviceResult
        (device-resident, no host readback — callers decide when the
        one device-to-host sync happens), non-agg chains a HostBatch. Callers that captured
        domain metadata from a probe compile pass that fragment in so
        the run cannot recompile against racing stats."""
        if frag is None:
            stream, frag = self._sized_agg_fragment(stream)
        qstats = getattr(self, "_query_stats", None)
        stats = qstats.new_fragment(stream.chain) if qstats is not None else None

        if frag.is_agg:
            state = self._fold_agg_state(stream, frag, stats)
            cols, valid, overflow = _dispatch_finalize(frag, state, stats)
            return DeviceResult(
                self, stream, frag, cols, valid, overflow, stats,
                qstats=getattr(self, "_query_stats", None),
            )

        # Non-agg: stream windows, stop early once a limit is satisfied.
        _, _, rows_step = self._compile_steps(frag)
        pieces, total = [], 0
        pipe = self._window_pipeline(stream, stats)
        try:
            for cols, valid in pipe:
                with _dispatch(stats, rows_step):
                    out_cols, out_valid = rows_step(cols, valid)
                    _block_if(stats, (out_cols, out_valid))
                if stats is not None:
                    stats.windows += 1
                with _device_wait(stats) as wait:
                    out_cols, out_valid = _fetch_result(
                        frag.out_meta, out_cols, out_valid, stats, wait
                    )
                with _timed(stats, "materialize"):
                    piece = _to_host_batch(frag.out_meta, out_cols, out_valid)
                pieces.append(piece)
                total += piece.length
                if frag.limit is not None and total >= frag.limit:
                    break
        finally:
            pipe.close()
            self._note_pipeline(pipe)
        out = _concat_host(pieces, frag.relation)
        if stats is not None:
            stats.rows_out = out.length
        return _apply_limit(out, frag.limit)
