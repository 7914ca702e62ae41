"""Uniform config/flag system with environment-variable fallback.

Reference parity: the gflags + ``StringFromEnv`` idiom used throughout the
reference (``src/carnot/carnot_executable.cc:40-50``,
``src/vizier/services/agent/pem/pem_manager.cc:26-33``) and the Go
pflag/viper layer (``src/shared/services/service_flags.go``). One registry:
every tunable declares a name, type, default and doc here; the value
resolves from (in order) an explicit ``set_flag`` override, the
``PIXIE_TPU_<NAME>`` environment variable, then the default.
"""

from __future__ import annotations

import contextlib
import os
import threading
from dataclasses import dataclass
from typing import Callable


@dataclass
class Flag:
    name: str
    default: object
    parse: Callable
    doc: str
    # Computed once at definition: flag reads sit on per-compile hot
    # paths (verify/bounds memo keys), where rebuilding the env-var
    # string per get_flag call measurably added up.
    env_var: str = ""

    def __post_init__(self):
        if not self.env_var:
            self.env_var = "PIXIE_TPU_" + self.name.upper()


_REGISTRY: dict[str, Flag] = {}
_OVERRIDES: dict[str, object] = {}
_LOCK = threading.Lock()


def _parse_bool(s) -> bool:
    if isinstance(s, bool):
        return s
    return str(s).strip().lower() in ("1", "true", "yes", "on")


def define_flag(name: str, default, doc: str, parse: Callable | None = None) -> None:
    if parse is None:
        if isinstance(default, bool):
            parse = _parse_bool
        elif isinstance(default, int):
            parse = int
        elif isinstance(default, float):
            parse = float
        else:
            parse = str
    with _LOCK:
        _REGISTRY[name] = Flag(name=name, default=default, parse=parse, doc=doc)


def get_flag(name: str):
    f = _REGISTRY[name]
    with _LOCK:
        if name in _OVERRIDES:
            return _OVERRIDES[name]
    env = os.environ.get(f.env_var)
    if env is not None:
        return f.parse(env)
    return f.default


_MISSING = object()


def get_flags(*names) -> tuple:
    """Batch ``get_flag``: one lock acquisition for N flags. For hot
    paths that snapshot several flags per call (the analysis passes'
    memo keys read five per compile)."""
    flags = [_REGISTRY[n] for n in names]
    environ = os.environ
    with _LOCK:
        ov = [_OVERRIDES.get(n, _MISSING) for n in names]
    out = []
    for f, o in zip(flags, ov):
        if o is not _MISSING:
            out.append(o)
            continue
        env = environ.get(f.env_var)
        out.append(f.parse(env) if env is not None else f.default)
    return tuple(out)


def set_flag(name: str, value) -> None:
    """Programmatic override (the runtime ConfigUpdateMessage analog)."""
    f = _REGISTRY[name]
    with _LOCK:
        _OVERRIDES[name] = f.parse(value) if not isinstance(value, type(f.default)) else value


def clear_flag(name: str) -> None:
    with _LOCK:
        _OVERRIDES.pop(name, None)


@contextlib.contextmanager
def override_flag(name: str, value):
    """Scoped ``set_flag`` that restores any PRE-EXISTING programmatic
    override on exit (a bare set/clear pair would delete a caller's own
    override, silently flipping later runs back to the default)."""
    with _LOCK:
        had = name in _OVERRIDES
        prev = _OVERRIDES.get(name)
    set_flag(name, value)
    try:
        yield
    finally:
        if had:
            set_flag(name, prev)
        else:
            clear_flag(name)


def all_flags() -> dict:
    """{name: (value, doc)} snapshot — the --helpfull / statusz listing."""
    return {n: (get_flag(n), f.doc) for n, f in sorted(_REGISTRY.items())}


# -- engine/table tunables ---------------------------------------------------
define_flag("window_rows", 1 << 17,
            "Rows per streamed device window (engine + device residency).")
define_flag("max_groups_limit", 1 << 22,
            "Hard cap for group-by rebucketing growth.")
define_flag("dense_domain_limit", 1 << 20,
            "Group-bys whose key columns all have statically-known domains "
            "(dictionary-encoded strings, booleans) with product <= this "
            "use the packed key AS the group id: no sort, no hash, and "
            "slot-aligned (regroup-free) state merges.")
define_flag("int_dense_domain_limit", 1 << 23,
            "Dense-domain budget for group-bys whose keys include integer "
            "columns bounded by table min/max stats (Table.col_stats). "
            "Separate from dense_domain_limit because a single int key "
            "can't suffer the multi-key packing blowup; the agg carry is "
            "one slot per domain value.")
define_flag("fold_scan_windows", 16,
            "Fold up to this many equal-shape device-resident windows per "
            "aggregate dispatch via one lax.scan program (1 disables); "
            "one dispatch then replaces that many.")
define_flag("pipeline_depth", 2,
            "Window-executor prefetch depth: host slicing/packing/"
            "device_put of window N+1 runs on a background thread while "
            "window N computes, with at most this many windows in "
            "flight. 1 = serial (no prefetch thread, today's behavior).")
define_flag("join_probe_window_rows", 1 << 20,
            "Probe rows per device-join dispatch for inner/left N:M "
            "joins: the build side is sorted and staged on device ONCE "
            "per query and probe windows stream through the prefetch "
            "pipeline. 0 = single-shot kernel over the whole probe side.")
define_flag("ingest_sketches", True,
            "Maintain per-tablet ingest sketches (row count, HLL NDV, "
            "zone maps on key columns) on the append path; join routing "
            "and the planner's eager-aggregation sizing consult them.")
define_flag("join_strategy", "auto",
            "N:M join strategy: 'auto' (a unique dense build side is a "
            "host table lookup; else sketch-guided routing picks "
            "host-dict / host-hash / single-shot / windowed sorted-probe "
            "/ windowed radix by shape, backend and sketches), or force "
            "'host', 'single', 'sorted', 'radix' (tests do).")
define_flag("join_capacity_safety", 2.0,
            "Multiplier on the sketch-estimated join output cardinality "
            "when sizing the initial device-join output capacity (then "
            "rounded to a power-of-two bucket). Headroom over the "
            "NDV-based mean fan-out absorbs moderate key skew; an "
            "overflow retry costs a fresh jit compile mid-query, so "
            "over-sizing is the cheaper error.")
define_flag("device_residency", True,
            "Stage full table windows into device memory (HBM) at append "
            "time so steady-state queries run without host transfers.")
define_flag("device_cache_bytes", 6 << 30,
            "Byte budget for device-resident table windows (LRU-evicted).")
define_flag(
    "cpu_fold_threads", 0,
    "CPU-backend parallel window fold: thread count (0 = auto from cores, "
    "1 = disable and fold sequentially).",
)
define_flag(
    "table_store_data_limit_mb", 1024 + 256,
    "Byte budget across ALL canonical ingest tables (reference "
    "PL_TABLE_STORE_DATA_LIMIT_MB, default 1.25GB); <= 0 = unbounded.",
)
define_flag(
    "table_store_http_events_percent", 40,
    "Percent of the table-store budget devoted to http_events "
    "(reference PL_TABLE_STORE_HTTP_EVENTS_PERCENT).",
)
define_flag(
    "cold_tier_mb", 0,
    "Encoded cold-tier byte budget per table (table_store/coldstore.py). "
    "> 0 enables tiering for byte-bounded tables: the oldest hot-ring "
    "windows demote into dictionary/delta/run-length encoded cold "
    "windows instead of expiring, and only cold evictions count as "
    "expiry. 0 = cold tier off (hot ring expires directly, the "
    "pre-tier behavior).",
)
define_flag(
    "scan_zone_skip", True,
    "Skip scan windows whose per-column zone maps cannot satisfy a "
    "query's FilterOp predicate (exec/zoneskip.py) — checked BEFORE "
    "stage/decode, so selective scans over cold data never decode "
    "dead windows. The join drivers' key-range window skipping, "
    "generalized to plain table scans.",
)
define_flag(
    "bus_secret", "",
    "Shared secret for netbus/broker bearer tokens; empty disables auth "
    "(single-trust-domain deployments).",
)

# -- fault tolerance (services/query_broker.py) ------------------------------
define_flag(
    "dispatch_retries", 3,
    "Re-publishes of an un-acked fragment dispatch before the broker "
    "declares the agent lost (0 = a single un-acked attempt is lost).",
)
define_flag(
    "dispatch_backoff_ms", 50.0,
    "Initial ack-wait/backoff for fragment dispatch retries; doubles "
    "per attempt (capped at 2s) with +0..25% jitter.",
)
define_flag(
    "require_complete", False,
    "Fail a distributed query as soon as a participating data agent is "
    "lost, instead of completing with partial results from the "
    "survivors (the pre-fault-tolerance fail-closed behavior).",
)

# -- broker HA (services/broker_ha.py; docs/RESILIENCE.md "Broker HA") -------
define_flag(
    "broker_reconcile_wait_s", 0.5,
    "How long a freshly elected leader collects agents' answers to the "
    "broker.reconcile probe before resolving the deposed leader's "
    "in-flight queries (re-attach vs partial/broker_failover).",
)
define_flag(
    "broker_reattach_timeout_s", 15.0,
    "Forwarder wait budget for a re-attached failover query on the new "
    "leader; the inactivity watchdog inside the wait bounds a truly "
    "dead query well before this.",
)
define_flag(
    "client_request_retries", 3,
    "api.Client retries of IDEMPOTENT control-plane requests (agents, "
    "schemas, debug_queries, ...) on BusTimeout. execute_script is "
    "never blind-retried (non-idempotent).",
)
define_flag(
    "client_retry_backoff_ms", 50.0,
    "Initial backoff for api.Client idempotent-request retries; "
    "doubles per attempt (capped at 2s) with +0..25% jitter.",
)

# -- query-lifecycle tracing (exec/trace.py) ---------------------------------
define_flag(
    "trace_window_sample", 64,
    "window.stage / window.stall spans: every interval of a stage up "
    "to N, then every Nth (1 = every window, 0 = no window spans; the "
    "device.dispatch / device.wait spans are never sampled). "
    "Timestamps only — never forces device sync.",
)
define_flag(
    "trace_export_url", "",
    "OTLP/HTTP base URL (e.g. http://collector:4318) to push finished "
    "query traces to via exec.otel.OTLPHttpExporter; empty keeps traces "
    "in-memory only (ring buffer).",
)
define_flag(
    "slow_query_threshold_ms", 0.0,
    "Queries slower than this (wall-clock ms) dump their full trace to "
    "the 'pixie_tpu.slow_query' logger; 0 disables the slow-query log.",
)

# -- resource bounds + admission control (analysis/bounds.py) ----------------
define_flag(
    "bounds_presize", True,
    "Grow AggOp.max_groups at compile time to the sketch-NDV group "
    "bound (pxbound) so first-run aggregates start at the predicted "
    "capacity instead of climbing the overflow-doubling ladder (one "
    "whole-table re-fold per rung). Growth only — results identical.",
)
define_flag(
    "bounds_query_budget_mb", 0.0,
    "Per-query budget on pxbound's predicted staged bytes; a plan "
    "predicted over budget fails AT COMPILE with a structured "
    "resource-bound Diagnostic instead of OOMing mid-query. 0 "
    "disables. Sketch-less (unbounded) predictions are never rejected.",
)
define_flag(
    "bounds_device_budget_mb", 0.0,
    "Per-node budget on pxbound's predicted device allocation (staged "
    "window planes, aggregate group state, join build+output buffers); "
    "enforced at compile like bounds_query_budget_mb. 0 disables.",
)
define_flag(
    "admission_bytes_budget_mb", 0.0,
    "Broker admission control: budget on the SUM of in-flight queries' "
    "predicted staged bytes (pxbound predicted_cost). A single query "
    "predicted over the whole budget is rejected with its diagnostic; "
    "a query that merely doesn't fit NOW queues up to "
    "admission_queue_s. 0 disables (every query admitted). Queries "
    "with unknown (sketch-less) predictions are admitted and accounted "
    "at zero.",
)
define_flag(
    "broker_execute_threads", 16,
    "PER-TENANT worker-thread cap for the served broker.execute topic "
    "(serve()). Each in-flight remote request holds one daemon worker "
    "for its whole execution (including admission queueing); a "
    "tenant's requests past its cap wait in that tenant's own FIFO "
    "backlog, so one tenant's parked requests can never starve "
    "another tenant's at the front door, and total threads stay "
    "bounded by cap x the registered tenant set even with admission "
    "control disabled.",
)
define_flag(
    "admission_queue_s", 5.0,
    "How long an admission-controlled query may wait for in-flight "
    "predicted bytes to drain before it is rejected (queue timeout). "
    "0 rejects immediately when the budget is full.",
)
define_flag(
    "admission_tenant_weights", "",
    "Registered tenant set with fair-share weights for broker "
    "admission control, as comma-separated name:weight entries "
    "(e.g. 'dash:4,batch:1'). Each tenant's slice of "
    "admission_bytes_budget_mb is budget * weight / sum(weights); the "
    "default tenant 'shared' is always registered (weight 1 unless "
    "listed) and absorbs queries with no/unknown tenant. Empty = "
    "single shared tenant (the whole budget, pre-tenancy behavior). "
    "Tenant names label metrics, so they MUST come from this set — "
    "services/tenancy.py resolve_tenant() folds anything else into "
    "'shared' (bounded label cardinality).",
)
define_flag(
    "admission_priority_holddown_ms", 0.0,
    "Non-work-conserving grace window for strict-priority admission: "
    "after a priority-p query releases, strictly-lower-priority "
    "waiters stay queued for this many milliseconds. An admitted "
    "query's compute cannot be preempted (queries now overlap on an "
    "engine — pxlock, docs/ANALYSIS.md — but still contend for the "
    "same cores/devices), so without the hold-down a back-to-back "
    "high-priority stream is interleaved with unpreemptible "
    "low-priority work admitted in its ~ms inter-arrival gaps — "
    "head-of-line blocking that moves the high class's p99 however "
    "fair the byte shares are. 0 (default) disables: admission is "
    "work-conserving and purely share/priority ordered.",
)

# -- concurrency verification (analysis/lockdep.py) --------------------------
define_flag(
    "lockdep", False,
    "Runtime lock-order validation (Linux-lockdep style): wraps "
    "threading.Lock/RLock/Condition creation, maintains per-thread "
    "held-stacks and a process-wide observed acquisition-order graph, "
    "and raises (with both stack pairs) at the first acquisition that "
    "would close a cycle. Test/deploy instrumentation — off by "
    "default, zero overhead when off (the raw C lock types are "
    "untouched). run_tests.sh --locks runs the concurrency suites "
    "under it; deploy roles honor it at process start.",
)

# -- observed-cost feedback into admission (services/query_broker.py) --------
define_flag(
    "admission_observed_floor",
    True,
    "Broker admission control floors predicted_cost at the observed "
    "per-script-hash bytes_staged history from finished query traces "
    "(the __queries__ feedback loop): a sketch-less UNKNOWN prediction "
    "with history is admitted against the observed bytes instead of "
    "zero, and a known prediction below observed reality is raised to "
    "it. Only matters while admission_bytes_budget_mb > 0.",
)

# -- result cache + materialized views (exec/result_cache.py, exec/views.py) -
define_flag(
    "result_cache_mb", 0,
    "Byte budget (MB) for the watermark-validated merged-result cache "
    "(broker execute_script + local engine.execute_query). A repeat of "
    "a script whose scanned tables' cluster watermarks have not "
    "advanced past the per-script staleness budget is served from the "
    "cache with zero compile/admission/dispatch cost. 0 disables "
    "(every query executes; the pre-cache behavior). Validity is "
    "purely watermark comparison — never wall-clock TTL.",
)
define_flag(
    "result_cache_staleness_ms", 0.0,
    "Default per-script staleness budget (ms) for result-cache hits "
    "when the script manifest carries no staleness_budget_ms field: a "
    "cached result whose stored watermarks trail the current ones by "
    "at most this much still serves (freshness_lag_ms re-stamped "
    "against the CURRENT watermark). 0 = exact-watermark hits only.",
)
define_flag(
    "view_auto_min_runs", 0,
    "Observed-frequency heuristic for incremental materialized views: "
    "a script executed at least this many times (ObservedCostIndex "
    "runs + live counts) is auto-registered as a continuously "
    "maintained view, answered as finalize-over-state instead of a "
    "full rescan. 0 disables auto-registration (manifest "
    "'materialize: true' opt-in still registers).",
)
define_flag(
    "pushdown_union_agg", True,
    "Distributed planner: place PEM-safe UnionOps (all inputs "
    "PEM-resident and non-blocking, sole consumer chain ending at a "
    "full AggOp) on the data agents so the downstream aggregate splits "
    "into partial-on-PEM + AGG_STATE_MERGE, shipping sketch-sized "
    "merge state (HLL registers, t-digest centroids) instead of "
    "pre-agg rows over the union's ROW_GATHER bridges.",
)

# -- self-observability (services/telemetry.py) ------------------------------
define_flag(
    "telemetry_table_mb", 8,
    "Per-table byte budget (MB) for the self-telemetry tables; each "
    "table's ring expires its own oldest rows at the budget.",
)
define_flag(
    "self_profiling", True,
    "Deploy roles run the self-sampling perf profiler "
    "(ingest/profiler.py): agents AND the broker fold their own "
    "Python stacks — attributed with {qid, script_hash, tenant, "
    "phase} from the thread attribution registry — into the "
    "__stacks__ telemetry ring (px/query_cpu / px/tenant_cpu) plus "
    "the anonymous stack_traces.beta aggregate "
    "(px/perf_flamegraph), and serve merged flames via "
    "/debug/pprof + /debug/flamez. Off = no sampling thread work "
    "at all.",
)
define_flag(
    "bus_telemetry", True,
    "Buses (MessageBus/RemoteBus) stamp the transport tier "
    "(services/busstats.py): per-topic-class publish/deliver/byte "
    "counters, dispatcher-lag + handler service-time histograms, "
    "queue-depth high-water gauges, wire frame/byte/RTT accounting — "
    "folded into the __bus__ telemetry ring on the heartbeat cadence "
    "and served at /debug/busz. Off = buses carry no stats object "
    "(the A/B overhead baseline).",
)
define_flag(
    "slow_handler_threshold_ms", 0.0,
    "Bus handlers slower than this (service time, ms) log topic, "
    "class, service/lag times to the 'pixie_tpu.slow_handler' logger "
    "and count in pixie_bus_slow_handlers_total; 0 disables the "
    "slow-handler log. The transport-tier twin of "
    "slow_query_threshold_ms.",
)
