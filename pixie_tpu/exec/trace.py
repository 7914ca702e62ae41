"""Always-on query-lifecycle tracing: spans, ring buffer, OTLP export.

Reference parity: Carnot ships per-operator ``OperatorExecutionStats``
with every query result (``src/carnot/carnot.cc:389-423``) and the
services expose statusz/metrics — but that telemetry is per-request and
the engine's own ``analyze`` mode forces device sync (killing the PR-1
pipeline overlap). This module is the cheap, always-on third way: every
query gets a **trace** — a tree of spans stamped at existing host-side
boundaries, never ``block_until_ready`` — kept in a bounded ring buffer
and optionally pushed over the engine's own OTLP path (dogfooding
``exec/otel.py``'s span dicts through ``OTLPHttpExporter``).

**One clock.** Every span, on every thread of the process, takes both
its ends from ``time.perf_counter_ns()`` (``clock_ns``), where the work
runs; nothing is back-dated from a duration. ``start_unix_nano`` /
``end_unix_nano`` (OTLP, ``/debug/queryz``) are reckoned from one
wall-clock anchor taken once a process. Entering a span also enters a
``jax.profiler.TraceAnnotation`` of the same name (``qid`` attached),
so a profiler session's ``.xplane.pb`` holds the program's spans on the
host threads' lines, on the device operations' own clock; with no
session on that is a flag test.

Span hierarchy (one trace per ``Engine.execute_plan`` /
``StreamingQuery`` lifetime; the broker's and the agents' stamps are in
``services/query_broker.py`` and ``services/agent.py``):

- ``query``               root; status/script-hash/row-count attributes
- ``compile``             parse + PxL compile + plan (execute_query path)
- ``fragment``            one per compiled fragment actually executed
  (Map/Filter/Agg chain, join driver, rebucket attempt); attributes
  carry windows, rows in/out and the per-stage second totals
- ``device.dispatch``     one per program enqueued: the host's enqueue
  call (attributes ``program`` as ``ProgramRegistry`` names its kind,
  ``windows``, ``device``: the id of the device the engine lives on
  (``Engine(device=...)``, ``exec/placement.py``; an engine given none
  names the device JAX puts its work on, a mesh engine its mesh's
  first), and on a window-fold program ``fold``: ``pallas_int``,
  ``pallas_f32``, ``sorted_digest``, ``keyed_digest``, ``xla``,
  ``mixed:...`` or ``sorted_int``, as ``CompiledFragment.fold``
  decided at compile time, with ``group``: ``dense`` / ``sorted`` /
  ``hashed`` and ``slots``: the capacity g it was compiled at; under
  ``sorted_int`` also ``ride``: ``payload`` / ``index``, how this
  window's sum planes reach group order, ``ops/routes.py``
  ``sorted_fold_ride``, absent where none rides, and ``max_words``: the
  u32 words of the maxima its sorts carry as keys, two an INT64 max /
  min / ``any``, one an ``any`` of a string, absent at 0; a keyed fold
  that also holds ``quantiles`` reads ``mixed:sorted_int=<n>,
  keyed_digest=<m>``; with a ``quantiles`` aggregate on any layout
  ``digests``: the digest carries the program holds (one an argument:
  the plucked quantiles of one column share theirs,
  ``exec/fold_plan.py`` ``digest_owners``), ``digest_outputs``: the
  aggregates that read them, ``digest_slots``:
  groups x centroids of one, and ``digest_bins``: the width B its
  windows' rows are binned at, 2^32 where a sort orders the values
  themselves, ``ops/routes.py`` ``digest_bins``; where its windows came
  resident with a row range, ``rows``: the rows of them the program
  folded, a power-of-two slice around the range or the whole capacity,
  summed over a run's windows, ``exec/stream.py`` ``_fold_rows``, and
  ``range_rows``: the rows in range among them; on the Kelvin's
  ``merge_finalize`` ``prepared``: ``hit`` / ``miss``, ``slots``, and
  what k agents cost it: ``payloads`` (k), ``merges`` (k - 1),
  ``remap_entries`` (the padded entries of the key-id remaps it reads,
  absent where the agents' dictionaries are equal) and ``upload_bytes`` (the
  compacted states put on its device), counted in
  ``usage.merge_payloads`` / ``merge_remap_entries`` /
  ``merge_upload_bytes``; the k - 1 states beyond the first are folded
  in by ONE k-way fold at the states' own sizes where the merge's fold
  is the keyed sort fold's, ``exec/fragment.py`` ``merge_many``, by
  k - 1 pairwise merges at the union's capacity otherwise); child of
  its fragment
- ``rebucket``            one per re-fold after a group-capacity overflow
  (attributes ``from``, ``to`` slots, ``where``: ``pem`` the fold of
  rows, ``kelvin`` the merge of states): the compile at twice the slots
  and the whole fold again; child of the attempt that overflowed
- ``group_probe``         the joint-key sketch before a keyed aggregate's
  first fold of a chain (attributes ``slots``: the plan's capacity,
  ``estimate``: distinct joint keys), in a fragment of its own
- ``join``                one per ``JoinOp``, child of the root: the
  dictionaries' alignment, build and probe, the output rows' assembly
  (attributes ``strategy``: the ``JoinDecision``'s, ``where``: ``host``
  / ``device``, ``how``, ``build_rows``, ``probe_rows``, ``rows_out``,
  and ``domain``, the table's length, under ``host_table``; a fused
  lookup join's span covers its build alone)
- ``device.wait``         the host asks for a result until the bytes are
  on the host, at the sync the path has anyway (where the path's one
  batched ``jax.device_get`` is also its sync the span carries
  ``leaves`` and ``bytes`` itself; the wait of a ``merge_finalize``
  that folded k >= 2 keyed states in one k-way fold also says what the
  fold saw, two scalars beside its answer: ``contended_slots``, the
  merged slots that two or more states filled, and ``rebins``, the
  ``merge_ordered`` runs its digests took: k - 1 a digest carry where a
  slot was contended, 0 where every slot's digest moved as it was
  shipped, and for a chain that holds none; ``rebins`` is counted in
  ``usage.merge_rebins``)
- ``device.fetch``        child of its ``device.wait``: from the instant
  the path's own sync has returned (an overflow scalar, a validity
  plane) to the last leaf on the host: one batched get
  (``stream._fetch_tree``) of copies started where the program was
  enqueued (``stream._start_fetch``), so only what is still in flight
  is waited for here (attributes ``leaves``, ``bytes`` and ``device``,
  as on ``device.dispatch``). Counted in
  ``usage.bytes_fetched`` / ``usage.fetches``
- ``plan.walk``           child of the root: the plan's loop between the
  ops that run work (sources found, chains extended), one span a stretch
- ``fragment.bind``       child of the root: a chain's fragment found or
  compiled at the capacity to fold it at (content keys, the fragment
  cache's lookup, the remembered capacity; on the Kelvin the prepared
  merge's lookup); ``cached``: ``hit`` / ``miss``
- ``pipeline.start``      child of its fragment: the window pipeline's
  prefetch thread made and started
- ``window.select``       child of its fragment: one a window a table
  scan hands over (the range and pruner on the first, the zone-map
  skip, the resident window found or staged), stamped on the thread
  that stages; ``skipped``: windows the zone maps pruned on the way
- ``state.init``          child of its fragment: a fold's empty group
  state made, before the first window's program: ONE program of no
  argument enqueued (``fragment_init_state``, the fragment's
  ``init_program``; the mesh's replicates its output), outside
  ``device.dispatch`` and made anew every request; ``programs``: 1,
  ``leaves``: the state's
- ``merge.compact``       child of the root, on the Kelvin: the shipped
  states' live slots found and taken (``payloads``, ``slots``: the
  bucket they are merged at)
- ``payload``             child of the root: the fragment's last
  ``device.wait`` end to the bridge payload built and its wire bytes
  counted (``kind``: ``agg_state`` / ``rows``; an ``agg_state`` that
  holds ``quantiles`` digests says ``digest_bytes``, counted in
  ``usage.digest_bytes``); and a result sink's
  answer in hand (``kind``: ``result``, ``rows``, ``string_bytes``: the
  UTF-8 bytes its STRING columns' ids stand for; counted in
  ``usage.answer_rows`` / ``usage.string_bytes_out``)
- ``join.align`` / ``join.assemble``  children of ``join``: the key
  dictionaries' union and remaps (``strings`` hashed; ``memo``: ``miss``
  where a union was built, ``none`` where the sides share dictionaries:
  no memo exists yet, so never ``hit``) and the output rows gathered on
  the host (``rows_out``)
- ``restream``            child of the root: a batch in hand (a join's
  rows, a materialized aggregate) made the source of the next fragment
  (``rows``)
- ``window.stage`` / ``window.stall`` / ``materialize``  windows that
  are staged, the consumer blocked on the prefetch pipe, host batch
  assembly after the wait. Every interval of a stage up to
  ``trace_window_sample``, then every that-many-th.
- ``bus.deliver``         one a bus message that starts or feeds a
  trace: its enqueue on the subscription's queue to the handler's entry
  on the dispatcher thread (``services/msgbus.py`` stamps both on this
  clock, and ``busstats``' dispatcher lag is the same pair; ``topic``:
  the topic class, ``bytes``: the bus's estimate).
  Before the root (``outside_root`` = ``before``) on the PEM's
  ``fragment`` trace (the execute message) and the Kelvin's ``merge``
  trace (each bridge payload); child of ``await.results`` on the
  broker's
- ``trace.sinks``         after the root (``outside_root`` = ``after``):
  ``Tracer.end_query`` from the root's end to the last listener's
  return (usage, ring, metrics, slow-query check, export, listeners:
  the telemetry fold); ``listeners``: how many ran

The agents' own (``services/agent.py``): ``merge.wait`` (before the
Kelvin's root: merge installed until the last bridge payload is in) and
``publish`` (after an agent's root: its payloads, stats and eos on the
bus). The binder's: ``dict_udf`` (``dict_udf_span`` below). The
broker's stages on its ``distributed`` trace
(``services/query_broker.py``): ``snapshot``, ``compile``, ``plan``,
``admit``, ``register``, ``dispatch`` (``agents``: how many agents the
request fans out to; ``dispatch.retry`` a re-publish),
``await`` > ``await.results`` / ``await.stats``, ``finish``, and
``failover`` where an agent was lost.

``SPAN_NAMES`` below is every name the program stamps; a test holds the
served scripts and ``docs/OBSERVABILITY.md`` to it.

The stats spine is shared with ``analyze`` (``analyze.py``): a trace
owns a ``QueryStats`` whose fragments the engine fills exactly as
before; ``analyze=True`` just flips ``sync=True`` on that object, so
analyze is a *detail level* of the same trace, not a separate path
(a ``device.dispatch`` then lasts until the program has run).

**Background work** is not a query trace: each turn of a periodic task
(heartbeats, sweeps, telemetry folds, the memory poller, collections of
the garbage collector) is one entry of the process-wide ``background``
ring, on the same clock, so it can be laid over a late request. It
reaches no listener, no ``__queries__`` row and no query counter.

Consumers:

- ``Tracer.recent()`` / ``in_flight()`` and ``background.entries()`` —
  served by ``ObservabilityServer`` as ``/debug/queryz``
- Prometheus histograms on the shared ``MetricsRegistry``
  (``pixie_query_duration_seconds``, ``pixie_window_stage_seconds``)
  — ``/metrics``
- the slow-query log (``slow_query_threshold_ms`` flag): offending
  queries dump their full trace to the ``pixie_tpu.slow_query`` logger
- OTLP/HTTP push of finished traces when ``trace_export_url`` is set
  (in-memory otherwise); export failures count in
  ``pixie_trace_export_errors_total`` and never fail the query
"""

from __future__ import annotations

import contextlib
import gc
import hashlib
import itertools
import json
import logging
import os
import threading
import time
from collections import deque
from dataclasses import asdict, dataclass, field

from ..config import get_flag
from . import threadmap, tracectx
from .analyze import FragmentStats, QueryStats, StageStat, _Timer

logger = logging.getLogger("pixie_tpu.slow_query")

#: Hard cap on spans kept per trace (sampling bounds the rate, this
#: bounds the worst case — a million-window query must not hold a
#: million span dicts).
MAX_SPANS_PER_TRACE = 512

#: Finished query traces a ``Tracer`` keeps in its ring (served by
#: /debug/queryz; oldest evicted first).
TRACE_RING_SIZE = 128

#: Sub-second buckets for per-window stage timings (a window stage is
#: typically 0.1ms..1s; the prometheus defaults top out too coarse).
STAGE_BUCKETS = (
    0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025,
    0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
)

#: Byte-volume buckets (staged / wire bytes per query): one window is
#: KBs..MBs, a 16M-row scan is GBs.
BYTES_BUCKETS = (
    1 << 10, 1 << 14, 1 << 18, 1 << 22, 1 << 26, 1 << 30, 1 << 34,
)

#: Millisecond buckets (per-query device time).
MS_BUCKETS = (0.1, 1.0, 10.0, 100.0, 1_000.0, 10_000.0, 100_000.0)

#: Stages whose timed intervals become spans, and the span's name.
#: ``compute`` and ``finalize`` are not here: a program's enqueue is a
#: ``device.dispatch`` span, stamped by ``TracedFragment.dispatch``.
STAGE_SPANS = {
    "stage": "window.stage", "stall": "window.stall",
    "materialize": "materialize",
}

#: Every span name the program stamps (engine, agents, broker), so that
#: a reader of the spans can tell the program's from anyone else's
#: (``tests/test_host_path_spans.py`` holds the served scripts to it).
SPAN_NAMES = frozenset({
    "query", "compile", "fragment", "device.dispatch", "device.wait",
    "device.fetch", "rebucket", "group_probe", "join", "join.align",
    "join.assemble", "dict_udf", "plan.walk", "fragment.bind",
    "pipeline.start", "window.select", "state.init",
    "merge.compact", "payload", "restream",
    "bus.deliver", "merge.wait", "publish", "trace.sinks",
    # the broker's stages (services/query_broker.py)
    "snapshot", "plan", "admit", "register", "dispatch", "dispatch.retry",
    "await", "await.results", "await.stats", "finish", "failover",
    *STAGE_SPANS.values(),
})

#: THE clock: both ends of every span and of every background entry,
#: on every thread. It is the clock of ``time.perf_counter()``, which
#: callers (the benchmark's driver) time requests with.
clock_ns = time.perf_counter_ns

#: Wall-clock anchor, taken once a process: unix ns = anchor + clock.
_UNIX_ANCHOR_NS = time.time_ns() - clock_ns()


def unix_ns(ns: int) -> int:
    """A ``clock_ns`` reading as unix nanoseconds (0 stays 0: unset)."""
    return _UNIX_ANCHOR_NS + ns if ns else 0


def _new_id(nbytes: int) -> str:
    return os.urandom(nbytes).hex()


def _seed_span_ids() -> None:
    global _span_ids
    _span_ids = itertools.count(int.from_bytes(os.urandom(8), "big"))


def _new_span_id() -> str:
    """A span's 64-bit id: a counter from a start drawn once a process
    (and again in a forked child), so a span does not read the entropy
    pool. A ``getrandom`` a span was half of what a span costs on the
    chip's host (5 of 10 us: PERF.md section 6, PR 37), and a request
    leaves some fifty spans."""
    return f"{next(_span_ids) & 0xFFFFFFFFFFFFFFFF:016x}"


_seed_span_ids()
os.register_at_fork(after_in_child=_seed_span_ids)


_TraceAnnotation = None
#: What ``_annotation`` hands out while no profiler session is on.
_NO_ANNOTATION = contextlib.nullcontext()


def _annotation(name: str, qid: str = ""):
    """A ``jax.profiler.TraceAnnotation`` (a context manager): the
    benchmark's ``harness.mark`` for the program's own spans. With no
    profiler session on it is a flag test (``is_enabled``) and one
    shared no-op: an annotation object a span was a fifth of a span's
    cost."""
    global _TraceAnnotation
    if _TraceAnnotation is None:
        from jax.profiler import TraceAnnotation

        _TraceAnnotation = TraceAnnotation
    if not _TraceAnnotation.is_enabled():
        return _NO_ANNOTATION
    return _TraceAnnotation(name, qid=qid) if qid else _TraceAnnotation(name)


@dataclass
class QueryResourceUsage:
    """What one query actually COST, accumulated at existing host
    boundaries (never a device sync): the observed counterpart of the
    sketch-guided planner's predictions (arXiv:2102.02440 feedback loop)
    and the load signal multi-tenant admission control schedules on.

    - ``bytes_staged``  host->device transfer bytes during execution
      (0 for device-cache-resident windows — those were staged at
      append time; the gap between rows_in and bytes_staged IS the
      cache-hit signal)
    - ``bytes_restaged`` device bytes of table windows the device cache
      did not hold (LRU evicted them, or the tail grew) and the scan
      staged again (``Table.device_scan``; padded planes, every column).
      A counter of its own: admission's observed floor and pxbound's
      check read ``bytes_staged`` alone
    - ``bytes_fetched`` / ``fetches`` device->host bytes the query's
      results and shipped states crossed by, and the fetches they took:
      the ``device.fetch`` spans' ``bytes`` (a fragment's one batched
      get after its sync) and the ``device.wait`` spans' that carry
      ``bytes`` themselves (the get is the sync); ``nbytes`` of the
      host arrays in hand
    - ``device_ms``     the time the query had work on the device or
      was waiting for it: each fragment's first ``device.dispatch``
      start to its last ``device.wait`` end, summed over fragments
    - ``compile_ms``    the compile span (parse + PxL + plan + verify)
    - ``stall_ms``      query-thread time blocked on the prefetch pipe
    - ``wire_bytes``    bridge payload bytes this query SHIPPED
      (BridgeSinkOp egress — data-agent attribution; the merge's
      ingress is the sum over its producers)
    - ``retries``       dispatch retries (broker) + join-capacity
      overflow retries (engine)
    - ``rebuckets``     re-folds of an aggregate after a group-capacity
      overflow (``rebucket`` spans: the PEM's fold or the Kelvin's merge
      compiled again at twice the slots)
    - ``merge_prepared_hits`` / ``merge_prepared_misses`` the Kelvin's
      merges that found what they need beyond the payloads' values
      (canonical dictionaries, remaps, fragment, program) remembered by
      content, or built it (``exec/bridge.py`` ``_PreparedMerge``; the
      ``prepared`` attr of a ``merge_finalize`` dispatch). A warm script
      reads one hit a request
    - ``merge_payloads`` / ``merge_remap_entries`` / ``merge_upload_bytes``
      what k agents cost the Kelvin's merges beyond one: the partial-agg
      states they folded (k a merge: a fold of k - 1 merges), the padded
      entries of the key-id remaps their programs read (one a payload
      and string key column whose dictionary is not the canonical one's
      prefix; 0 where the agents' dictionaries are equal), and the bytes
      of the compacted states uploaded to the Kelvin's device: the
      ``payloads``, ``remap_entries`` and ``upload_bytes`` of the
      ``merge_finalize`` dispatches (a re-fold after an overflow counts
      again: it uploads again)
    - ``merge_rebins`` the ``merge_ordered`` runs of the Kelvin's k-way
      folds of k >= 2 keyed states (0 where the agents' groups are
      disjoint: a digest nothing joins moves to its slot as it was
      shipped): the ``rebins`` of the merges' ``device.wait`` spans
      (absent, and 0 here, for one payload and for a pairwise fold)
    - ``join_rows_in`` / ``join_rows_out`` rows the query's joins took
      in (build + probe) and gave out: the ``join`` spans' ``build_rows``
      + ``probe_rows`` and ``rows_out`` (a span around every ``JoinOp``)
    - ``dict_udf_strings`` strings the query's dictionary-side UDFs were
      run on: the ``dict_udf`` spans' ``strings`` (a span around every
      bind of a UDF that maps a string column's dictionary to strings;
      0 once the images are remembered: ``StringDictionary.image``)
    - ``answer_rows`` / ``string_bytes_out`` rows of the result tables
      the query handed back and the UTF-8 bytes their STRING columns' ids
      stand for: the ``rows`` and ``string_bytes`` of the ``payload``
      spans of kind ``result`` (one a ``ResultSinkOp``, on the engine
      that runs it; ``rows_out`` is every fragment's output, a merge's
      and a join's among them, not the answer's)
    - ``digest_bytes`` bytes of the ``quantiles`` aggregates' [slots, K]
      digest planes in the partial-agg states the query shipped: the
      ``digest_bytes`` of its ``payload`` spans of kind ``agg_state``
      (part of ``wire_bytes``; 2 x slots x 128 x 4 B a digest, one an
      argument however many outputs read it)
    - ``skipped_windows`` probe/scan windows never staged (zone maps)
    - ``device_peak_bytes`` high-water device ``bytes_in_use`` observed
      while the query ran (``exec/programs.py`` DeviceMemoryMonitor;
      TPU-real, 0 on backends whose ``memory_stats()`` is None).
      Merges by MAX across agents — it is a watermark, not a volume.
    - ``freshness_lag_ms`` result staleness: query stop-time minus the
      max event-time watermark of each scanned table at execute time,
      worst table kept (0 = fresh or no time-indexed scan). Merges by
      MAX across agents — the answer is only as fresh as the most
      behind shard. The validity predicate a result cache keyed on
      (script hash, table watermark) would check.
    """

    rows_in: int = 0
    rows_out: int = 0
    windows: int = 0
    bytes_staged: int = 0
    bytes_restaged: int = 0
    bytes_fetched: int = 0
    fetches: int = 0
    device_ms: float = 0.0
    compile_ms: float = 0.0
    stall_ms: float = 0.0
    wire_bytes: int = 0
    retries: int = 0
    rebuckets: int = 0
    merge_prepared_hits: int = 0
    merge_prepared_misses: int = 0
    merge_payloads: int = 0
    merge_remap_entries: int = 0
    merge_upload_bytes: int = 0
    merge_rebins: int = 0
    join_rows_in: int = 0
    join_rows_out: int = 0
    dict_udf_strings: int = 0
    answer_rows: int = 0
    string_bytes_out: int = 0
    digest_bytes: int = 0
    skipped_windows: int = 0
    device_peak_bytes: int = 0
    freshness_lag_ms: float = 0.0
    # Cold-tier decode wall time charged to this query (decoding runs on
    # the prefetch producer thread — decode-on-stage — so this overlaps
    # device compute rather than adding to it; compare against stall_ms
    # to see whether decode ever became the bottleneck).
    decode_ms: float = 0.0

    def to_dict(self) -> dict:
        d = asdict(self)
        for k in ("device_ms", "compile_ms", "stall_ms",
                  "freshness_lag_ms", "decode_ms"):
            d[k] = round(d[k], 3)
        return d

    def merge(self, other: "QueryResourceUsage | dict") -> None:
        """Fold another usage record in (broker-side per-agent
        aggregation; accepts the dict form that crossed the bus)."""
        d = other if isinstance(other, dict) else asdict(other)
        for k in (
            "rows_in", "rows_out", "windows", "bytes_staged",
            "bytes_restaged", "bytes_fetched", "fetches", "wire_bytes",
            "retries", "rebuckets",
            "merge_prepared_hits", "merge_prepared_misses",
            "merge_payloads", "merge_remap_entries", "merge_upload_bytes",
            "merge_rebins",
            "join_rows_in", "join_rows_out", "dict_udf_strings",
            "answer_rows", "string_bytes_out", "digest_bytes",
            "skipped_windows",
        ):
            setattr(self, k, getattr(self, k) + int(d.get(k, 0)))
        for k in ("device_ms", "compile_ms", "stall_ms", "decode_ms"):
            setattr(self, k, getattr(self, k) + float(d.get(k, 0.0)))
        # A watermark, not a volume: agents sharing a device would
        # double-count under addition.
        self.device_peak_bytes = max(
            self.device_peak_bytes, int(d.get("device_peak_bytes", 0))
        )
        # Staleness too: the merged answer is only as fresh as the most
        # behind agent's shard.
        self.freshness_lag_ms = max(
            self.freshness_lag_ms, float(d.get("freshness_lag_ms", 0.0))
        )


@dataclass
class Span:
    """One timed interval. ``to_otlp`` emits the OTLP-JSON span shape
    ``exec/otel.py`` ships (plus trace/span ids, which the batch path
    leaves to the collector)."""

    name: str
    trace_id: str
    span_id: str = field(default_factory=_new_span_id)
    parent_id: str = ""
    start_ns: int = 0  # both on ``clock_ns``; 0 = not stamped yet
    end_ns: int = 0
    attributes: dict = field(default_factory=dict)

    @property
    def start_unix_nano(self) -> int:
        return unix_ns(self.start_ns)

    @property
    def end_unix_nano(self) -> int:
        return unix_ns(self.end_ns)

    def to_otlp(self) -> dict:
        from .otel import _attr_kvs

        d = {
            "name": self.name,
            "traceId": self.trace_id,
            "spanId": self.span_id,
            "startTimeUnixNano": int(self.start_unix_nano),
            "endTimeUnixNano": int(self.end_unix_nano),
            "attributes": _attr_kvs(sorted(self.attributes.items())),
        }
        if self.parent_id:
            d["parentSpanId"] = self.parent_id
        return d


class _SpanCtx:
    """Context manager stamping a span's start/end around a block (and
    the profiler's annotation of the same name)."""

    def __init__(self, trace: "QueryTrace", name: str, parent: Span | None,
                 attrs: dict | None = None):
        self.trace = trace
        self.span = trace._new_span(name, parent)
        if attrs:
            self.span.attributes.update(attrs)

    def __enter__(self) -> Span:
        self._ann = _annotation(self.span.name, self.trace.qid)
        self._ann.__enter__()
        self.span.start_ns = clock_ns()
        return self.span

    def __exit__(self, exc_type, exc, tb):
        self.span.end_ns = clock_ns()
        self._ann.__exit__(exc_type, exc, tb)
        if exc is not None:
            self.span.attributes["error"] = f"{type(exc).__name__}: {exc}"


class _FragmentSpanCtx(_SpanCtx):
    """A span under a fragment's span. ``device.*`` spans move the
    fragment's device interval; one with a ``stage`` also feeds that
    stage's timer (a program's enqueue is both)."""

    def __init__(self, frag: "TracedFragment", name: str,
                 attrs: dict | None = None, stage: str | None = None,
                 parent: Span | None = None):
        super().__init__(frag.trace, name, parent or frag.span, attrs)
        self.frag = frag
        self.stage = stage

    def __exit__(self, exc_type, exc, tb):
        super().__exit__(exc_type, exc, tb)
        sp = self.span
        if self.stage is not None:
            self.frag.add(self.stage, (sp.end_ns - sp.start_ns) / 1e9,
                          start_ns=sp.start_ns, end_ns=sp.end_ns)
        if sp.name.startswith("device."):
            self.frag._note_device(sp.start_ns, sp.end_ns)


class _AnnotatedTimer(_Timer):
    def __init__(self, stats, stage, rows, nbytes, name):
        super().__init__(stats, stage, rows, nbytes)
        self.name = name

    def __enter__(self):
        self._ann = _annotation(self.name, self.stats.trace.qid)
        self._ann.__enter__()
        return super().__enter__()

    def __exit__(self, *exc):
        super().__exit__(*exc)
        self._ann.__exit__(*exc)


class TracedFragment(FragmentStats):
    """FragmentStats that additionally owns a ``fragment`` span, the
    spans under it, and the stage histograms. ``add`` runs on both the
    query thread (compute/stall) and the prefetch thread (stage) — the
    inherited lock covers both."""

    def __init__(self, ops: tuple, trace: "QueryTrace", sync: bool):
        super().__init__(ops=ops, sync=sync)
        self.trace = trace
        self.span = trace._new_span("fragment", trace.root)
        self.span.start_ns = clock_ns()
        self.span.attributes["ops"] = ",".join(ops) or "(join)"
        self.last_activity_ns = self.span.start_ns
        # [first device.dispatch start, last device.* end]: the time
        # this fragment had work on the device or waited for it.
        self.device_first_ns = 0
        self.device_last_ns = 0

    def add(self, stage: str, seconds: float, rows: int = 0,
            nbytes: int = 0, start_ns: int = 0, end_ns: int = 0) -> None:
        now_ns = end_ns or clock_ns()
        with self._lock:
            s = self.stages.setdefault(stage, StageStat())
            s.seconds += seconds
            s.rows += int(rows)
            s.count += 1
            s.nbytes += int(nbytes)
            count = s.count
            self.last_activity_ns = max(self.last_activity_ns, now_ns)
        tracer = self.trace.tracer
        if tracer is not None:
            tracer._observe_stage(stage, seconds)
        name = STAGE_SPANS.get(stage)
        # A span needs its two stamps: a bare duration (the cold tier's
        # decode meter) is a stage total only.
        if (name and start_ns and end_ns
                and self.keeps_interval(count - 1)):
            attrs = {"interval": count - 1}
            if rows:
                attrs["rows"] = int(rows)
            self.trace._add_span(Span(
                name=name,
                trace_id=self.trace.trace_id,
                parent_id=self.span.span_id,
                start_ns=start_ns,
                end_ns=end_ns,
                attributes=attrs,
            ))

    def keeps_interval(self, n: int) -> bool:
        """Whether the ``n``-th (from 0) interval of a per-window stage
        becomes a span: every one up to ``trace_window_sample``, then
        every that-many-th."""
        k = self.trace.window_sample
        return bool(k) and (n < k or n % k == 0)

    def stamped(self, name: str, start_ns: int, **attrs) -> None:
        """A span under the fragment's from ``start_ns`` (``clock_ns``,
        read where it began) to now: for work on a thread that is not
        the query's and lives for one fragment (no profiler annotation
        there)."""
        self.trace.add_span(name, start_ns, clock_ns(), parent=self.span,
                            **attrs)

    def timed(self, stage: str, rows: int = 0, nbytes: int = 0):
        """The stage timer; a stage that becomes a span is also the
        profiler's annotation of that name while it runs."""
        name = STAGE_SPANS.get(stage)
        if name is None:
            return super().timed(stage, rows, nbytes)
        return _AnnotatedTimer(self, stage, rows, nbytes, name)

    def subspan(self, name: str, parent: Span | None = None,
                **attrs) -> _FragmentSpanCtx:
        """A named span under the fragment's, or under ``parent``: a
        span of this fragment (a ``device.fetch`` inside its
        ``device.wait``)."""
        if name == "device.fetch":
            self._name_device(attrs)
        return _FragmentSpanCtx(self, name, attrs, parent=parent)

    def _name_device(self, attrs: dict) -> None:
        """``device``: the id of the device the engine lives on."""
        device_id = getattr(self.trace.tracer, "device_id", None)
        if device_id is not None:
            attrs["device"] = device_id

    def dispatch(self, program: str, stage: str = "compute",
                 windows: int = 1) -> _FragmentSpanCtx:
        attrs = {"program": program, "windows": int(windows)}
        self._name_device(attrs)
        if self.fold and stage == "compute":  # a window-fold program
            attrs["fold"] = self.fold
            if self.group:
                attrs["group"], attrs["slots"] = self.group, int(self.slots)
            if self.remap_entries:
                attrs["remap_entries"] = int(self.remap_entries)
            if self.digests:
                attrs["digests"] = int(self.digests)
                attrs["digest_outputs"] = int(self.digest_outputs)
                attrs["digest_slots"] = int(self.digest_slots)
                attrs["digest_bins"] = int(self.digest_bins)
        return _FragmentSpanCtx(self, "device.dispatch", attrs, stage=stage)

    def _note_device(self, start_ns: int, end_ns: int) -> None:
        with self._lock:
            if not self.device_first_ns or start_ns < self.device_first_ns:
                self.device_first_ns = start_ns
            self.device_last_ns = max(self.device_last_ns, end_ns)
            self.last_activity_ns = max(self.last_activity_ns, end_ns)

    def device_ms(self) -> float:
        with self._lock:
            return (self.device_last_ns - self.device_first_ns) / 1e6

    def finish(self, end_ns: int) -> None:
        """Seal the fragment span (trace end): end timestamp = last
        host-side activity, attributes = the final counters."""
        with self._lock:
            self.span.end_ns = min(
                max(self.last_activity_ns, self.span.start_ns), end_ns
            ) or end_ns
            self.span.attributes.update({
                "windows": self.windows,
                "rows_in": self.rows_in,
                "rows_out": self.rows_out,
            })
            for k, v in self.stages.items():
                self.span.attributes[f"{k}_seconds"] = round(v.seconds, 6)


class TraceStats(QueryStats):
    """The trace's stats spine — what the engine sees as
    ``_query_stats``. ``sync`` False = always-on tracing (no device
    fence); True = analyze detail level."""

    def __init__(self, trace: "QueryTrace", sync: bool = False):
        super().__init__(sync=sync)
        self.trace = trace

    def new_fragment(self, ops) -> TracedFragment:
        fs = TracedFragment(
            tuple(type(o).__name__ for o in ops), self.trace, self.sync
        )
        self.fragments.append(fs)
        return fs


class QueryTrace:
    """One query's lifecycle: ids, status, span tree, stats spine."""

    def __init__(self, tracer: "Tracer | None", script: str = "",
                 analyze: bool = False, kind: str = "query",
                 parent_ctx: dict | None = None):
        self.start_ns = clock_ns()
        self.end_ns = 0
        self.tracer = tracer
        # A valid parent context (a broker dispatch span, carried in the
        # bus envelope — see tracectx.py) makes this trace PART of the
        # distributed trace: same trace id, root parented under the
        # dispatch span. Otherwise this query is its own trace root.
        self.parent_ctx = (
            dict(parent_ctx) if tracectx.valid(parent_ctx) else None
        )
        self.trace_id = (
            self.parent_ctx["trace_id"] if self.parent_ctx else _new_id(16)
        )
        self.script = script or ""
        self.script_hash = hashlib.sha256(
            self.script.encode()
        ).hexdigest()[:12]
        self.kind = kind  # "query" | "stream" | "fragment" | "merge" | ...
        self.qid = ""  # distributed query id (agents/broker stamp it)
        self.agent_id = ""  # executing agent (agents stamp it)
        # Tenant the query was admitted under (services/tenancy.py):
        # the broker stamps its resolved tenant, agents copy it from
        # the dispatch envelope so per-agent __queries__ rows carry the
        # same attribution. "" = not a tenant-scoped query (bare local
        # engines).
        self.tenant = ""
        # Result-cache disposition (exec/result_cache.py): "hit" /
        # "miss" / "stale" / "bypass" / "view"; "" = cache not in play
        # (disabled, or a path the cache never sees). Flows to
        # __queries__ and `px debug queries`.
        self.cache = ""
        self.status = "running"
        self.error = ""
        self.duration_s = 0.0
        self.window_sample = int(get_flag("trace_window_sample"))
        self.pipeline: dict | None = None  # engine.last_pipeline snapshot
        self.usage = QueryResourceUsage()
        self.agent_usage: dict = {}  # broker: {agent_id: usage dict}
        # pxbound predicted_cost (analysis/bounds.py): what the query
        # was PREDICTED to stage/ship at plan time. The broker stamps
        # it; `px debug queries` renders predicted vs observed.
        self.predicted: dict | None = None
        # Per-scanned-table staleness detail ({table: lag_ms} at scan
        # setup; usage.freshness_lag_ms keeps the worst) — queryz rows.
        self.freshness: dict = {}
        self.exported = False  # OTLP push succeeded (ring-drop counting)
        self.dropped_spans = 0
        self._lock = threading.Lock()
        self.root = Span(
            "query", self.trace_id, start_ns=self.start_ns,
            parent_id=self.parent_ctx["span_id"] if self.parent_ctx else "",
        )
        self.spans: list[Span] = [self.root]
        self.stats = TraceStats(self, sync=analyze)

    @property
    def start_unix_nano(self) -> int:
        return unix_ns(self.start_ns)

    @property
    def end_unix_nano(self) -> int:
        return unix_ns(self.end_ns)

    def annotation(self):
        """The profiler's annotation of the trace's root while it runs
        on the calling thread: ``query:<kind>``."""
        return _annotation(f"query:{self.kind}", self.qid)

    def ctx(self, span: "Span | None" = None) -> dict:
        """The propagation envelope for children of ``span`` (default:
        the root) — what the broker stamps onto dispatch messages."""
        return tracectx.make(
            self.trace_id, (span or self.root).span_id
        )

    def add_wire_bytes(self, n: int) -> None:
        """Account bridge egress bytes (BridgeSinkOp payloads)."""
        with self._lock:
            self.usage.wire_bytes += int(n)

    def note_freshness_lag(self, table: str, lag_ms: float) -> None:
        """Record one scanned table's staleness (query stop-time minus
        its max event-time watermark at scan setup): the usage field
        keeps the WORST table/round, ``self.freshness`` the per-table
        detail (/debug/queryz). Bounded: one key per scanned table."""
        lag_ms = max(0.0, float(lag_ms))
        with self._lock:
            self.usage.freshness_lag_ms = max(
                self.usage.freshness_lag_ms, lag_ms
            )
            self.freshness[table] = max(
                self.freshness.get(table, 0.0), lag_ms
            )

    # -- span plumbing -------------------------------------------------------
    def _new_span(self, name: str, parent: Span | None) -> Span:
        s = Span(
            name, self.trace_id,
            parent_id=parent.span_id if parent is not None else "",
        )
        self._add_span(s)
        return s

    def _add_span(self, span: Span) -> None:
        with self._lock:
            if len(self.spans) >= MAX_SPANS_PER_TRACE:
                self.dropped_spans += 1
                return
            self.spans.append(span)

    def span(self, name: str, parent: Span | None = None,
             **attrs) -> _SpanCtx:
        """``with trace.span("compile"): ...`` — stamps start/end."""
        return _SpanCtx(
            self, name, parent if parent is not None else self.root, attrs
        )

    def add_span(self, name: str, start_ns: int, end_ns: int,
                 parent: Span | None = None, **attrs) -> Span:
        """A span whose two ends were stamped (``clock_ns``) where they
        happened but not around one block: a wait that begins in one
        handler and ends in another."""
        sp = Span(
            name, self.trace_id,
            parent_id=(parent if parent is not None else self.root).span_id,
            start_ns=start_ns, end_ns=end_ns, attributes=attrs,
        )
        self._add_span(sp)
        return sp

    def open_span(self, name: str) -> Span | None:
        """The newest span of that name that has not ended (a ``join``
        whose pieces want to be its children), or None."""
        with self._lock:
            for s in reversed(self.spans):
                if s.name == name and not s.end_ns:
                    return s
        return None

    # -- derived views -------------------------------------------------------
    @property
    def rows_in(self) -> int:
        return sum(f.rows_in for f in self.stats.fragments)

    @property
    def rows_out(self) -> int:
        return sum(f.rows_out for f in self.stats.fragments)

    @property
    def windows(self) -> int:
        return sum(f.windows for f in self.stats.fragments)

    def _finalize(self, status: str, error: str) -> None:
        self.status = status
        self.error = error
        self.end_ns = clock_ns()
        self.duration_s = (self.end_ns - self.start_ns) / 1e9
        self.stats.total_seconds = self.duration_s
        self.root.end_ns = self.end_ns
        self._finalize_usage()
        self.root.attributes.update({
            "status": status,
            "script_hash": self.script_hash,
            "kind": self.kind,
            "rows_in": self.rows_in,
            "rows_out": self.rows_out,
            "bytes_staged": self.usage.bytes_staged,
            "device_ms": round(self.usage.device_ms, 3),
            "wire_bytes": self.usage.wire_bytes,
        })
        if self.qid:
            self.root.attributes["qid"] = self.qid
        if self.agent_id:
            self.root.attributes["agent_id"] = self.agent_id
        if error:
            self.root.attributes["error"] = error
        for f in self.stats.fragments:
            if isinstance(f, TracedFragment):
                f.finish(self.end_ns)

    def _finalize_usage(self) -> None:
        """Derive the resource record from the stats spine + spans.
        Purely host-side arithmetic over already-collected counters."""
        u = self.usage
        # Additive: a broker trace pre-merged its agents' usage (its own
        # stats spine is empty); an engine trace starts from zeros.
        u.rows_in += self.rows_in
        u.rows_out += self.rows_out
        u.windows += self.windows
        for f in self.stats.fragments:
            with f._lock:
                stages = {k: (v.seconds, v.nbytes, v.count)
                          for k, v in f.stages.items()}
            u.bytes_staged += stages.get("stage", (0.0, 0, 0))[1]
            u.bytes_restaged += stages.get("restage", (0.0, 0, 0))[1]
            if isinstance(f, TracedFragment):
                u.device_ms += f.device_ms()
            u.stall_ms += stages.get("stall", (0.0, 0, 0))[0] * 1e3
            # Cold-tier stage adds: "decode" seconds ride the stage
            # timeline (producer thread); "skip" counts windows a zone
            # map pruned before stage/decode (one add() per window).
            u.decode_ms += stages.get("decode", (0.0, 0, 0))[0] * 1e3
            u.skipped_windows += stages.get("skip", (0.0, 0, 0))[2]
        for s in self.spans:
            if s.name == "rebucket":
                u.rebuckets += 1
            elif s.name == "join":
                a = s.attributes
                u.join_rows_in += (
                    a.get("build_rows", 0) + a.get("probe_rows", 0)
                )
                u.join_rows_out += a.get("rows_out", 0)
            elif s.name == "dict_udf":
                u.dict_udf_strings += s.attributes.get("strings", 0)
            elif s.name == "payload" and s.attributes.get("kind") == "result":
                u.answer_rows += s.attributes.get("rows", 0)
                u.string_bytes_out += s.attributes.get("string_bytes", 0)
            elif s.name == "payload":
                u.digest_bytes += s.attributes.get("digest_bytes", 0)
            elif (s.name in ("device.fetch", "device.wait")
                  and "bytes" in s.attributes):
                # One batched get after the path's sync (the child) or
                # as the sync (the wait itself carries the bytes): never
                # both.
                u.bytes_fetched += s.attributes["bytes"]
                u.fetches += 1
                # (A k-way merge's wait also says what its fold joined.)
                u.merge_rebins += s.attributes.get("rebins", 0)
            elif "prepared" in s.attributes:  # a ``merge_finalize``
                a = s.attributes
                if a["prepared"] == "hit":
                    u.merge_prepared_hits += 1
                elif a["prepared"] == "miss":
                    u.merge_prepared_misses += 1
                u.merge_payloads += a.get("payloads", 0)
                u.merge_remap_entries += a.get("remap_entries", 0)
                u.merge_upload_bytes += a.get("upload_bytes", 0)
        compile_span = next(
            (s for s in self.spans if s.name == "compile"), None
        )
        if compile_span is not None and compile_span.end_ns:
            u.compile_ms += (
                compile_span.end_ns - compile_span.start_ns
            ) / 1e6

    def to_dict(self) -> dict:
        """The /debug/queryz row (and slow-query log body)."""
        d = {
            "id": self.trace_id,
            "kind": self.kind,
            "script_hash": self.script_hash,
            "query": self.script[:200],
            "status": self.status,
            "start_unix_nano": self.start_unix_nano,
            "duration_ms": round(
                (self.duration_s if self.end_ns
                 else (clock_ns() - self.start_ns) / 1e9) * 1e3, 3
            ),
            "rows_in": self.rows_in,
            "rows_out": self.rows_out,
            "windows": self.windows,
            "spans": len(self.spans),
            "usage": self.usage.to_dict(),
            "fragments": [f.to_dict() for f in self.stats.fragments],
        }
        if self.qid:
            d["qid"] = self.qid
        if self.agent_id:
            d["agent_id"] = self.agent_id
        if self.tenant:
            d["tenant"] = self.tenant
        if self.cache:
            d["cache"] = self.cache
        if self.agent_usage:
            d["agent_usage"] = dict(self.agent_usage)
        if self.predicted:
            d["predicted"] = dict(self.predicted)
        if self.freshness:
            # dict() snapshot first: queryz renders in-flight traces
            # while the query thread may still note scans.
            d["freshness"] = {
                t: round(v, 3) for t, v in dict(self.freshness).items()
            }
        if self.parent_ctx:
            d["parent"] = dict(self.parent_ctx)
        if self.error:
            d["error"] = self.error
        if self.pipeline:
            d["pipeline"] = {
                k: (round(v, 6) if isinstance(v, float) else v)
                for k, v in self.pipeline.items()
            }
        if self.dropped_spans:
            d["dropped_spans"] = self.dropped_spans
        return d

    def to_otlp(self) -> dict:
        """OTLP-JSON ResourceSpans payload — the exact shape
        ``OTLPHttpExporter`` POSTs to ``/v1/traces``."""
        from .otel import _attr_kvs

        res = [
            ("service.name", "pixie-tpu-engine"),
            ("query.script_hash", self.script_hash),
        ]
        if self.agent_id:
            res.append(("service.instance.id", self.agent_id))
        return {
            "resourceSpans": [{
                "resource": {
                    "attributes": _attr_kvs(res)
                },
                "scopeSpans": [{
                    "scope": {"name": "pixie_tpu.exec.trace"},
                    "spans": [s.to_otlp() for s in self.spans],
                }],
            }]
        }


def current_trace():
    """The trace of the query the calling thread is executing
    (``Engine._execute_plan_scoped`` binds it through ``threadmap``), or
    None outside one: for code the engine reaches but does not hand its
    stats spine to (the expression binder)."""
    entry = threadmap.current_entry()
    t = entry.get("trace") if entry else None
    return t if isinstance(t, QueryTrace) else None


@contextlib.contextmanager
def dict_udf_span(udf: str, entries: int):
    """Around one bind of a dictionary-side UDF with a string result
    (``exec/expr.py`` ``_bind_host_dict``): a ``dict_udf`` span under the
    root of the query's trace, on whichever engine binds it, with ``udf``
    and ``entries`` (the source dictionary's size). Yields ``note``,
    which the binder calls with ``strings`` (how many the UDF was run
    on) and ``memo`` (``hit`` / ``extend`` / ``miss``);
    ``_finalize_usage`` counts ``strings`` into
    ``usage.dict_udf_strings``. No span, and a ``note`` that does
    nothing, outside a query."""
    t = current_trace()
    if t is None:
        yield lambda **attrs: None
        return
    with t.span("dict_udf", udf=udf, entries=int(entries)) as sp:
        yield sp.attributes.update


class Tracer:
    """Per-engine trace sink: bounded ring of finished traces, the
    in-flight set, histogram/counter recording, slow-query log, and the
    optional OTLP push. All methods are thread-safe."""

    def __init__(self, registry=None, ring_size: int = TRACE_RING_SIZE):
        self._registry = registry  # lazy: services import at first use
        self._ring: deque = deque(maxlen=int(ring_size))
        self._inflight: dict[str, QueryTrace] = {}
        self._lock = threading.Lock()
        self._metrics: dict | None = None
        self._stage_hist: dict = {}  # stage -> bound Histogram
        self._exporter = None
        self._exporter_url = None
        # Finished-trace listeners (the TelemetryCollector hook): called
        # AFTER metrics/export, exceptions contained — telemetry folding
        # must never fail or slow the query that produced the trace.
        self._listeners: list = []
        self._closed = False
        # The id of the device the tracer's engine lives on (the engine
        # sets it; None on a tracer of no engine, the broker's): the
        # ``device`` of its traces' ``device.dispatch`` / ``device.fetch``.
        self.device_id = None

    def add_listener(self, fn) -> None:
        """Register ``fn(trace)`` to run on every finished trace."""
        self._listeners.append(fn)

    def shutdown(self) -> None:
        """Stop exporting/notifying: traces finished after shutdown
        still finalize into the ring (queryz keeps working) but no OTLP
        push or listener runs — the teardown contract for processes
        whose collector endpoint is already gone."""
        self._closed = True

    # -- metrics -------------------------------------------------------------
    @property
    def registry(self):
        if self._registry is None:
            from ..services.observability import default_registry

            self._registry = default_registry
        return self._registry

    def _m(self) -> dict:
        if self._metrics is None:
            reg = self.registry
            self._metrics = {
                "queries": reg.counter(
                    "pixie_queries_total",
                    "Queries finished, by terminal status",
                ),
                "duration": reg.histogram(
                    "pixie_query_duration_seconds",
                    "End-to-end query wall time (compile + execute)",
                ),
                "stage": reg.histogram(
                    "pixie_window_stage_seconds",
                    "Per-window host-side stage intervals (stage/compute/"
                    "stall/finalize/materialize; timestamps, not device "
                    "sync)",
                    buckets=STAGE_BUCKETS,
                ),
                "slow": reg.counter(
                    "pixie_slow_queries_total",
                    "Queries over slow_query_threshold_ms",
                ),
                "export_errors": reg.counter(
                    "pixie_trace_export_errors_total",
                    "Failed OTLP trace pushes (trace_export_url)",
                ),
                "dropped": reg.counter(
                    "pixie_trace_dropped_total",
                    "Finished traces evicted from the ring buffer "
                    "without having been OTLP-exported",
                ),
                "bytes_staged": reg.histogram(
                    "pixie_query_bytes_staged",
                    "Per-query host->device staging bytes (0 = fully "
                    "device-cache-resident)",
                    buckets=BYTES_BUCKETS,
                ),
                "device_ms": reg.histogram(
                    "pixie_query_device_ms",
                    "Per-query milliseconds with work on the device or "
                    "waiting for it (first device.dispatch start to last "
                    "device.wait end, summed over fragments)",
                    buckets=MS_BUCKETS,
                ),
                "wire_bytes": reg.histogram(
                    "pixie_query_wire_bytes",
                    "Per-query bridge payload egress bytes (agent "
                    "fragments shipping partial states/rows)",
                    buckets=BYTES_BUCKETS,
                ),
            }
        return self._metrics

    def _observe_stage(self, stage: str, seconds: float) -> None:
        h = self._stage_hist.get(stage)
        if h is None:
            h = self._stage_hist[stage] = self._m()["stage"].labels(
                stage=stage
            )
        h.observe(seconds)

    # -- lifecycle -----------------------------------------------------------
    def begin_query(self, script: str = "", analyze: bool = False,
                    kind: str = "query",
                    parent_ctx: dict | None = None) -> QueryTrace:
        """Start a trace. ``parent_ctx`` defaults to the AMBIENT
        distributed context (tracectx.current(), bound by the bus
        dispatcher that delivered the triggering message) — so a
        fragment executed inside an agent handler automatically joins
        the broker's trace without explicit plumbing."""
        if parent_ctx is None:
            parent_ctx = tracectx.current()
        tr = QueryTrace(
            self, script=script, analyze=analyze, kind=kind,
            parent_ctx=parent_ctx,
        )
        with self._lock:
            # Keyed by root span id, not trace id: N fragments of one
            # distributed query SHARE a trace id but are distinct
            # in-flight entries.
            self._inflight[tr.root.span_id] = tr
        return tr

    def end_query(self, trace: QueryTrace, status: str = "ok",
                  error: str = "") -> None:
        """Finalize a trace: seal spans, move it to the ring, record
        metrics, run the slow-query log and the OTLP export. Idempotent
        (a second end is a no-op) so both StreamingQuery.run's finally
        and an explicit close() can call it."""
        with self._lock:
            if self._inflight.pop(trace.root.span_id, None) is None:
                return  # already ended (or foreign trace)
        # ``trace.sinks`` lies AFTER the trace's root, from the root's
        # end to the last listener's return. It joins the trace's spans
        # once the sinks have run: what they export and fold is the
        # trace as its root's end left it.
        with _annotation("trace.sinks", trace.qid):
            trace._finalize(status, error)
            listeners = self._run_sinks(trace, status)
            sinks_end_ns = clock_ns()
        trace.add_span("trace.sinks", trace.end_ns, sinks_end_ns,
                       outside_root="after", listeners=listeners)

    def _run_sinks(self, trace: QueryTrace, status: str) -> int:
        """The finished trace into the ring, the metrics, the slow-query
        log, the OTLP push and the listeners; how many listeners ran."""
        m = self._m()
        with self._lock:
            # Ring-drop accounting (satellite): an evicted trace that
            # never made it out over OTLP is telemetry LOST — count it
            # so operators can see the ring is short / wire an exporter.
            if (
                self._ring.maxlen is not None
                and len(self._ring) == self._ring.maxlen
                and self._ring
                and not self._ring[0].exported
            ):
                m["dropped"].inc()
            self._ring.append(trace)
        m["queries"].labels(status=status).inc()
        m["duration"].labels(status=status).observe(trace.duration_s)
        u = trace.usage
        m["bytes_staged"].observe(u.bytes_staged)
        m["device_ms"].observe(u.device_ms)
        m["wire_bytes"].observe(u.wire_bytes)
        self._slow_query_check(trace, m)
        self._export(trace, m)
        return self._notify(trace)

    def _notify(self, trace: QueryTrace) -> int:
        if self._closed:
            return 0
        listeners = list(self._listeners)
        for fn in listeners:
            try:
                fn(trace)
            except Exception:
                # A broken telemetry consumer must never fail queries.
                logging.getLogger("pixie_tpu.trace").warning(
                    "trace listener %r failed", fn, exc_info=True
                )
        return len(listeners)

    def _slow_query_check(self, trace: QueryTrace, m: dict) -> None:
        thresh_ms = float(get_flag("slow_query_threshold_ms"))
        if thresh_ms <= 0 or trace.duration_s * 1e3 < thresh_ms:
            return
        m["slow"].inc()
        logger.warning(
            "slow query (%.1fms > %.1fms): %s",
            trace.duration_s * 1e3, thresh_ms,
            json.dumps(trace.to_dict(), default=str),
        )

    def _export(self, trace: QueryTrace, m: dict) -> None:
        url = str(get_flag("trace_export_url"))
        if not url or self._closed:
            return
        if self._exporter is None or self._exporter_url != url:
            from .otel import OTLPHttpExporter

            self._exporter = OTLPHttpExporter(url)
            self._exporter_url = url
        try:
            self._exporter(trace.to_otlp())
            trace.exported = True
        except Exception:
            # Telemetry must never fail the query; the counter is the
            # operator's signal that the collector is down. A shutdown
            # racing a slow in-flight push lands here too (socket torn
            # down mid-POST) — counted, not raised.
            m["export_errors"].inc()

    # -- accessors (the /debug/queryz surface) -------------------------------
    def in_flight(self) -> list:
        with self._lock:
            traces = sorted(
                self._inflight.values(), key=lambda t: t.start_ns
            )
        return [t.to_dict() for t in traces]

    def recent(self) -> list:
        with self._lock:
            traces = list(self._ring)
        return [t.to_dict() for t in reversed(traces)]

    def get(self, trace_id: str) -> QueryTrace | None:
        with self._lock:
            for t in self._inflight.values():
                if t.trace_id == trace_id:
                    return t
            for t in self._ring:
                if t.trace_id == trace_id:
                    return t
        return None

    def last(self) -> QueryTrace | None:
        """Most recently finished trace (None if the ring is empty)."""
        with self._lock:
            return self._ring[-1] if self._ring else None


class BackgroundRing:
    """What the process did besides queries: one entry a turn of each
    periodic task, ``(name, thread, start_ns, end_ns)`` on ``clock_ns``,
    newest last, bounded. Not query traces: no listener, no
    ``__queries__`` row, no counter sees them."""

    def __init__(self, size: int = 8192):
        self._ring: deque = deque(maxlen=size)
        self._gc_t0 = 0
        self._gc_watched = False

    def record(self, name: str, start_ns: int, end_ns: int) -> None:
        # deque.append is atomic: no lock on any task's path.
        self._ring.append(
            (name, threading.current_thread().name, start_ns, end_ns)
        )

    def turn(self, name: str) -> "_Turn":
        """``with background.turn("heartbeat"): ...``"""
        return _Turn(self, name)

    def entries(self, since_ns: int = 0) -> list:
        """Entries that ended at or after ``since_ns``, as dicts."""
        return [
            {"name": n, "thread": t, "start_ns": a, "end_ns": b}
            for n, t, a, b in list(self._ring) if b >= since_ns
        ]

    def watch_gc(self, min_ms: float = 1.0) -> None:
        """Record every garbage collection of ``min_ms`` or more
        (``gc.callbacks``). Idempotent; the served stack's agents and
        broker call it when they start."""
        if self._gc_watched:
            return
        self._gc_watched = True
        min_ns = int(min_ms * 1e6)

        def on_gc(phase, info):
            if phase == "start":
                self._gc_t0 = clock_ns()
            elif self._gc_t0:
                t1 = clock_ns()
                if t1 - self._gc_t0 >= min_ns:
                    self.record(
                        f"gc.gen{info.get('generation')}", self._gc_t0, t1
                    )
                self._gc_t0 = 0

        self._gc_callback = on_gc  # so a test can take it off again
        gc.callbacks.append(on_gc)


class _Turn:
    def __init__(self, ring: BackgroundRing, name: str):
        self.ring, self.name = ring, name

    def __enter__(self):
        self._ann = _annotation(self.name)
        self._ann.__enter__()
        self.t0 = clock_ns()
        return self

    def __exit__(self, *exc):
        self.ring.record(self.name, self.t0, clock_ns())
        self._ann.__exit__(*exc)


#: The process-wide ring (``/debug/queryz`` serves it beside the traces).
background = BackgroundRing()


def plan_script(plan) -> str:
    """Stable pseudo-script for direct ``execute_plan`` calls (no PxL
    source): the op-type chain in topo order, so equal plans share a
    script hash in /debug/queryz."""
    try:
        ops = [type(plan.nodes[nid].op).__name__ for nid in plan.topo_order()]
    except Exception:
        return "<plan>"
    return "plan:" + ">".join(ops)
