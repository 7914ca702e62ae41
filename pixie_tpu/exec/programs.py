"""Device-tier observability: compiled-program registry + device memory.

Everything above the JAX boundary is already observable (trace spans,
``QueryResourceUsage``, telemetry tables); below it the engine was
blind — nothing recorded what XLA programs exist, what each one cost to
compile, what it reads/allocates, or whether a repeated query actually
reused an executable. This module closes that gap with two pieces:

**ProgramRegistry** — the process-wide registry of tracked XLA
programs. The fragment compiler (``exec/fragment.py``) and the join
drivers (``exec/joins.py``) wrap their jit entry points in
:class:`TrackedProgram` proxies; each distinct (program key, input
shape signature) pair becomes one :class:`ProgramRecord` holding

- the executable itself, built through the AOT ``lower().compile()``
  path so the compile wall-time is measured exactly (the jit dispatch
  path hides it inside the first call),
- XLA ``cost_analysis()`` (FLOPs, bytes accessed) and
  ``memory_analysis()`` (argument/output/temp bytes) — both guarded:
  CPU/older jaxlib may return nothing or raise, in which case the
  record degrades to timing-only with ``None`` analysis fields,
- hit/compile counters (a *hit* is one tracked invocation served by a
  cached executable; windows hit once per dispatch).

Because the registry OWNS the executables (this jax version does not
share the AOT and jit dispatch caches), it is literally the
compiled-program cache the ROADMAP's concurrent-serving item wants to
promote: a fragment-cache eviction no longer implies an XLA recompile
as long as the registry still holds the record. Any failure anywhere in
the AOT path falls back to the plain jit call — tracking can degrade,
execution cannot.

Surfaces: ``pixie_program_cache_{hits,misses,evictions}_total``
counters + the ``pixie_compile_seconds`` histogram on the default
metrics registry, the ``/debug/programz`` endpoint
(``services/observability.py``), and the ``__programs__`` telemetry
table (``services/telemetry.py`` drains :meth:`ProgramRegistry.rows`
per finished trace).

**DeviceMemoryMonitor** — periodic ``device.memory_stats()`` snapshots
exported as ``pixie_device_memory_bytes{device,kind}`` gauges (real on
TPU; ``memory_stats()`` returns None on CPU and the gauges simply don't
appear), plus per-query high-water attribution: the engine brackets
every ``execute_plan`` with :meth:`query_begin`/:meth:`query_end` and
stamps the observed peak ``bytes_in_use`` into
``QueryResourceUsage.device_peak_bytes`` (0 on stat-less backends).
"""

from __future__ import annotations

import threading
import time

from . import placement, threadmap

#: Tracked (program, shape-signature) records a registry keeps, each
#: holding its XLA executable, compile wall-time and cost/memory
#: analysis, in an LRU; the oldest is evicted (and recompiled on its
#: next use).
PROGRAM_REGISTRY_SIZE = 512

#: Period of the device-memory poll thread (seconds). 0: no thread, and
#: a query's peak comes from its boundary samples alone; the gauges
#: refresh at every /metrics scrape regardless.
DEVICE_MEMORY_POLL_S = 0.0

#: ``pixie_compile_seconds`` buckets: a CPU fragment compiles in
#: ~10-100ms, a big window-fold program on the TPU in a minute or two.
COMPILE_BUCKETS = (
    0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0,
    60.0, 300.0,
)

#: ``memory_stats()`` keys exported as gauges / tracked for peaks.
_MEM_KINDS = (
    "bytes_in_use", "peak_bytes_in_use", "bytes_limit",
    "largest_free_block_bytes",
)


def shape_signature(args) -> tuple:
    """Hashable signature of a call's input pytree: treedef + per-leaf
    (shape, dtype-or-type, sharding), and the device of the scope the
    call runs in (``exec/placement.py``; None outside one). Exactly the
    distinctions XLA compiles separate programs for — two calls with
    equal signatures may share one executable. A leaf committed to a
    device says so in its sharding; a call whose leaves are host arrays
    and scalars runs where its scope says, and an executable compiled
    for one engine's device must not serve another's. ~7µs per call
    (hot-path budget: one per tracked dispatch, i.e. per window)."""
    from jax import tree_util

    leaves, treedef = tree_util.tree_flatten(args)
    return (treedef, tuple(
        (
            getattr(leaf, "shape", ()),
            getattr(leaf, "dtype", None) or type(leaf),
            getattr(leaf, "sharding", None),
        )
        for leaf in leaves
    ), placement.current())


class ProgramRecord:
    """One tracked XLA program: a (program key, shape signature) pair
    and everything observed about it."""

    __slots__ = (
        "program_id", "kind", "label", "sig_repr", "plan_hash",
        "compiled", "fn_id", "compiles", "hits", "compile_s_total",
        "compile_s_last", "flops", "bytes_accessed", "argument_bytes",
        "output_bytes", "temp_bytes", "peak_bytes", "created_ns",
        "last_used_ns", "seq", "pins", "aot_disabled", "jit_warm",
        "fn_ref",
    )

    def __init__(self, program_id: str, kind: str, label: str,
                 sig_repr: str, plan_hash: str = ""):
        self.program_id = program_id
        self.kind = kind
        self.label = label
        self.sig_repr = sig_repr
        self.plan_hash = plan_hash
        self.compiled = None  # AOT executable (None = timing-only)
        self.fn_id = 0  # id() of the jit fn the executable came from
        self.compiles = 0
        self.hits = 0
        self.compile_s_total = 0.0
        self.compile_s_last = 0.0
        # XLA analyses; None until a compile produced them (CPU/older
        # jax may never fill them — consumers must tolerate None).
        self.flops = None
        self.bytes_accessed = None
        self.argument_bytes = None
        self.output_bytes = None
        self.temp_bytes = None
        self.peak_bytes = None
        self.created_ns = time.time_ns()
        self.last_used_ns = self.created_ns
        self.seq = 0  # registry change sequence (telemetry drain)
        # Objects whose id() participates in the program key (string
        # dictionaries, the UDF registry — the fragment cache key is
        # id-based): pinning them here keeps a key match valid even
        # after the fragment cache evicts its own pinning entry, so a
        # registry hit can NEVER serve an executable compiled against a
        # recycled address.
        self.pins = None
        # AOT gave up for this program (lower/compile raised, or a
        # compiled executable failed at dispatch): stop re-attempting
        # and run through the plain jit call instead.
        self.aot_disabled = False
        # The jit fn's own dispatch cache has compiled this signature
        # (we timed that call). False routes the next call through the
        # miss path so a silent jit recompile — e.g. right after a
        # degrade, when every prior call went through the AOT
        # executable — is COUNTED, never mislabeled as a free hit.
        self.jit_warm = False
        # The jit fn a timing-only record's jit_warm refers to: held so
        # the fn_id comparison can never match a RECYCLED address of a
        # collected fn (same discipline as ``pins``). None while an AOT
        # executable exists (the hit path doesn't consult fn_id then).
        self.fn_ref = None

    def to_dict(self) -> dict:
        """The /debug/programz row."""
        return {
            "program_id": self.program_id,
            "kind": self.kind,
            "label": self.label,
            "shape": self.sig_repr,
            "plan_hash": self.plan_hash,
            "cached": self.compiled is not None,
            "compiles": self.compiles,
            "hits": self.hits,
            "compile_ms": round(self.compile_s_total * 1e3, 3),
            "compile_ms_last": round(self.compile_s_last * 1e3, 3),
            "flops": self.flops,
            "bytes_accessed": self.bytes_accessed,
            "argument_bytes": self.argument_bytes,
            "output_bytes": self.output_bytes,
            "temp_bytes": self.temp_bytes,
            "peak_bytes": self.peak_bytes,
            "created_ns": self.created_ns,
            "last_used_ns": self.last_used_ns,
        }


def _analyses(compiled):
    """(flops, bytes_accessed, argument, output, temp, peak) from an AOT
    Compiled — every field independently guarded to None (the CPU
    backend fills cost analysis but e.g. no generated-code sizes; other
    backends may raise on either call)."""
    flops = bytes_accessed = None
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        if isinstance(ca, dict):
            v = ca.get("flops")
            flops = float(v) if v is not None else None
            v = ca.get("bytes accessed")
            bytes_accessed = float(v) if v is not None else None
    except Exception:
        pass
    arg_b = out_b = temp_b = peak = None
    try:
        ma = compiled.memory_analysis()
    except Exception:
        ma = None
    if ma is not None:
        def _field(attr):
            # Per-field guard: a backend missing ONE size attribute
            # must not discard the sizes it did report.
            try:
                v = getattr(ma, attr, None)
                return int(v) if v is not None else None
            except Exception:
                return None

        arg_b = _field("argument_size_in_bytes")
        out_b = _field("output_size_in_bytes")
        temp_b = _field("temp_size_in_bytes")
        if arg_b is not None and out_b is not None and temp_b is not None:
            # Static allocation high-water approximation: XLA does not
            # expose a true peak on every backend, but args + outputs +
            # temps bounds what the program pins while running.
            peak = arg_b + out_b + temp_b
    return flops, bytes_accessed, arg_b, out_b, temp_b, peak


class TrackedProgram:
    """Callable proxy over one jitted entry point: every invocation is
    keyed by input shape signature against the registry. Misses compile
    via the AOT path (exact timing + analyses) and cache the
    executable; hits dispatch the cached executable directly (same
    per-call cost as the jit fast path — measured ~32µs vs ~31µs on
    CPU). Any AOT failure falls back to the plain jit call."""

    __slots__ = ("fn", "_registry", "_key", "_kind", "_label", "_pins")

    def __init__(self, fn, registry: "ProgramRegistry", key, kind: str,
                 label: str, pins=None):
        self.fn = fn
        self._registry = registry
        self._key = key
        self._kind = kind
        self._label = label
        self._pins = pins

    @property
    def kind(self) -> str:
        """The program's name in the registry (``fragment_update``,
        ``join_probe_sorted``...): what a ``device.dispatch`` span
        carries as ``program``."""
        return self._kind

    def __call__(self, *args):
        reg = self._registry
        # Profiler phase bracket: samples landing while the program
        # dispatches/runs are device work (or the wait for it), not
        # host execution — set_phase is a no-op on unattributed
        # threads, so the per-window cost is one dict get.
        tm = threadmap.set_phase("device_dispatch")
        try:
            try:
                sig = shape_signature(args)
                hash(sig)
            except Exception:
                return self.fn(*args)  # unhashable input: untracked call
            rec = reg._lookup(self._key, sig, id(self.fn))
            if rec is not None:
                if rec.compiled is not None:
                    try:
                        return rec.compiled(*args)
                    except Exception:
                        # Executable/input mismatch the signature missed
                        # (e.g. an exotic sharding): drop the executable
                        # for this record and re-raise nothing — the jit
                        # path below recomputes identically (programs
                        # are pure).
                        reg._degrade(rec)
                return self.fn(*args)  # timing-only record: plain jit path
            return reg._compile_and_run(self, sig, args)
        finally:
            threadmap.restore(tm)


class ProgramRegistry:
    """Bounded LRU of :class:`ProgramRecord`. Thread-safe; compilation
    runs outside the lock (a miss must not serialize unrelated
    programs behind a multi-second XLA compile)."""

    def __init__(self, metrics_registry=None,
                 size: int = PROGRAM_REGISTRY_SIZE):
        self._metrics_registry = metrics_registry
        self._size = int(size)  # <= 0: nothing is tracked
        self._lock = threading.Lock()
        self._records: dict = {}  # (key, sig) -> ProgramRecord
        self._seq = 0
        self._metrics: dict | None = None
        # Hit increments batch registry-side and flush to the shared
        # prometheus counter every _HIT_FLUSH hits (and at every
        # surface read): one global-lock round trip per window across
        # all engines was the hot path's contention point.
        self._pending_hits = 0
        # In-flight compile dedup: (key, sig) -> threading.Event. The
        # first thread to miss compiles; concurrent missers wait on the
        # event and re-lookup — a multi-second XLA compile must not run
        # twice for the same program.
        self._inflight: dict = {}
        # LRU-evicted records, keyed by program_id (executable/pins
        # dropped, counters kept). Serves two contracts: (a) rows()
        # still drains an evicted record's FINAL state (its seq is
        # bumped at eviction), so undrained hit increments are never
        # lost to __programs__; (b) a re-created record RESUMES these
        # counters, keeping the per-program_id stream monotonic.
        # Bounded FIFO at 4x the registry size — churn beyond that can
        # reset a long-gone program's counters, a documented memory
        # bound.
        self._evicted: dict = {}

    # -- wrapping ------------------------------------------------------------
    def wrap(self, fn, kind: str, key, label: str = "", pins=None):
        """Wrap a jitted entry point; returns ``fn`` unchanged when the
        registry is disabled (``size`` <= 0) or ``fn``
        is not trackable. ``pins`` are objects whose id() participates
        in ``key`` — held by the record so a key match stays valid."""
        if fn is None or self._size <= 0:
            return fn
        if isinstance(fn, TrackedProgram):
            return fn
        if not hasattr(fn, "lower"):
            return fn  # not a jit stage: nothing to AOT-compile
        return TrackedProgram(fn, self, key, kind, label, pins=pins)

    # -- metrics -------------------------------------------------------------
    def _m(self) -> dict:
        if self._metrics is not None:
            return self._metrics
        with self._lock:
            if self._metrics is not None:
                return self._metrics
            if self._metrics_registry is None:
                from ..services.observability import default_registry

                self._metrics_registry = default_registry
            reg = self._metrics_registry
            # Flush batched hit increments at every /metrics render so
            # a scrape never under-reports by the batch remainder.
            # Registered under the lock: two racing first callers must
            # not install the collector twice.
            reg.register_collector(self._flush_hits_collector)
            self._metrics = {
                "hits": reg.counter(
                    "pixie_program_cache_hits_total",
                    "Tracked program invocations served by a cached "
                    "XLA executable (one per dispatch, i.e. per window)",
                ),
                "misses": reg.counter(
                    "pixie_program_cache_misses_total",
                    "Tracked program invocations that compiled a new "
                    "XLA executable (first shape, eviction, or rebuild)",
                ),
                "evictions": reg.counter(
                    "pixie_program_cache_evictions_total",
                    "Program records LRU-evicted from the registry "
                    "(their executables recompile on next use)",
                ),
                "compile": reg.histogram(
                    "pixie_compile_seconds",
                    "XLA compile wall time per tracked program "
                    "(the AOT lower().compile() span)",
                    buckets=COMPILE_BUCKETS,
                ),
            }
        return self._metrics

    #: Batched hit increments flush to the prometheus counter at this
    #: granularity (also flushed by every surface read).
    _HIT_FLUSH = 64

    # -- the dispatch paths (TrackedProgram.__call__) ------------------------
    def _lookup(self, key, sig, fn_id: int):
        """Hit path: return the record for (key, sig) and count the hit,
        or None when this call must go through the miss path. A record
        without an executable only counts hits while the jit fn's own
        dispatch cache is provably warm FOR THIS fn — after a degrade
        or a fragment rebuild the jit call would silently recompile,
        which must be counted, never mislabeled as a free hit."""
        flush = 0
        with self._lock:
            rec = self._records.get((key, sig))
            if rec is None:
                return None
            if rec.compiled is None and not (
                rec.jit_warm and rec.fn_id == fn_id
            ):
                return None
            rec.hits += 1
            rec.last_used_ns = time.time_ns()
            self._seq += 1
            rec.seq = self._seq
            self._pending_hits += 1
            if self._pending_hits >= self._HIT_FLUSH:
                flush, self._pending_hits = self._pending_hits, 0
        if flush:
            self._m()["hits"].inc(flush)
        return rec

    def _flush_hits_locked(self) -> int:
        """Caller holds self._lock; returns the count to inc OUTSIDE."""
        flush, self._pending_hits = self._pending_hits, 0
        return flush

    def _flush_hits_collector(self, _reg) -> None:
        """Metrics-render collector: drain the batched hit count."""
        m = self._metrics
        if m is None:
            return  # render raced _m()'s registration; nothing pending
        with self._lock:
            flush = self._flush_hits_locked()
        if flush:
            m["hits"].inc(flush)

    def _degrade(self, rec: ProgramRecord) -> None:
        """The cached executable failed at dispatch: drop it, stop
        re-attempting AOT for this program, and route the NEXT call
        through the miss path so the jit recompile it will trigger is
        timed and counted."""
        with self._lock:
            rec.compiled = None
            rec.aot_disabled = True
            rec.jit_warm = False

    def _compile_and_run(self, prog: TrackedProgram, sig, args):
        """Miss path: AOT-compile (timed, analyzed), record, execute.
        Every step guarded — a failure anywhere degrades the record to
        timing-only and executes through the plain jit call. Concurrent
        missers of the SAME (key, sig) wait for the first compiler and
        re-lookup instead of duplicating a multi-second XLA compile;
        different programs never serialize on each other."""
        fn = prog.fn
        key = (prog._key, sig)
        with self._lock:
            rec = self._records.get(key)
            attempt_aot = not (rec is not None and rec.aot_disabled)
            ev = self._inflight.get(key)
            if ev is None:
                self._inflight[key] = threading.Event()
        if ev is not None:
            # Another thread is compiling this exact program: wait for
            # its record, then retry the hit path (falling back to the
            # plain jit call if it degraded meanwhile). No timeout
            # fallthrough — the owner's finally ALWAYS sets the event,
            # and duplicating a genuinely wedged multi-minute compile
            # would only multiply the stall by the waiter count.
            ev.wait()
            rec = self._lookup(prog._key, sig, id(fn))
            if rec is not None and rec.compiled is not None:
                try:
                    return rec.compiled(*args)
                except Exception:
                    self._degrade(rec)
            return fn(*args)
        try:
            t0 = time.perf_counter()
            compiled = None
            analyses = (None,) * 6
            if attempt_aot:
                try:
                    compiled = fn.lower(*args).compile()
                    compile_s = time.perf_counter() - t0
                    analyses = _analyses(compiled)
                except Exception:
                    compiled = None
            out = None
            ran = False
            if compiled is not None:
                try:
                    out = compiled(*args)
                    ran = True
                except Exception:
                    compiled = None
            if not ran:
                # jit fallback: this call includes jit's own compile, so
                # the timing still approximates compile cost
                # (timing-only mode; jit_warm marks the cache hot).
                out = fn(*args)
                compile_s = time.perf_counter() - t0
            self._record_compile(
                prog, sig, compiled, compile_s, analyses,
                aot_failed=attempt_aot and compiled is None,
            )
            return out
        finally:
            with self._lock:
                done = self._inflight.pop(key, None)
            if done is not None:
                done.set()

    def _record_compile(self, prog: TrackedProgram, sig, compiled,
                        compile_s: float, analyses,
                        aot_failed: bool = False) -> None:
        key = prog._key
        with self._lock:
            rec = self._records.get((key, sig))
            if rec is None:
                # (A treedef's hash leaves out its nodes' static data,
                # the length of a fold's ``RowSlice``; its text holds it.)
                pid = f"{hash((key, sig, str(sig[0]))) & (2**64 - 1):016x}"
                rec = ProgramRecord(
                    pid, prog._kind, prog._label, _sig_repr(sig),
                )
                base = self._evicted.pop(pid, None)
                if base is not None:
                    # Resume the evicted incarnation's counters so the
                    # telemetry stream stays monotonic per program_id.
                    rec.compiles = base.compiles
                    rec.hits = base.hits
                    rec.compile_s_total = base.compile_s_total
            rec.compiled = compiled
            rec.fn_id = id(prog.fn)
            rec.pins = prog._pins
            if aot_failed:
                rec.aot_disabled = True
            rec.jit_warm = compiled is None  # the jit path just ran
            # Pin the fn for timing-only records: jit_warm is only
            # meaningful for THIS fn object, and an unpinned id() could
            # be recycled by a rebuilt fragment's fn.
            rec.fn_ref = prog.fn if compiled is None else None
            rec.compiles += 1
            rec.compile_s_last = compile_s
            rec.compile_s_total += compile_s
            flops, bytes_acc, arg_b, out_b, temp_b, peak = analyses
            # Per-field: a backend reporting only SOME sizes keeps them.
            if flops is not None:
                rec.flops = flops
            if bytes_acc is not None:
                rec.bytes_accessed = bytes_acc
            if arg_b is not None:
                rec.argument_bytes = arg_b
            if out_b is not None:
                rec.output_bytes = out_b
            if temp_b is not None:
                rec.temp_bytes = temp_b
            if peak is not None:
                rec.peak_bytes = peak
            rec.last_used_ns = time.time_ns()
            self._seq += 1
            rec.seq = self._seq
            self._records[(key, sig)] = rec
            evicted = 0
            while len(self._records) > max(self._size, 1):
                # Evict least-recently-used by timestamp (insertion
                # order no longer tracks recency — hits deliberately
                # skip the pop/reinsert dict churn).
                lru = min(
                    self._records, key=lambda k: self._records[k].last_used_ns
                )
                gone = self._records.pop(lru)
                # Free the heavy state, keep the counters, and bump the
                # seq so the next drain emits the FINAL row.
                gone.compiled = None
                gone.pins = None
                gone.fn_ref = None
                gone.jit_warm = False
                self._seq += 1
                gone.seq = self._seq
                self._evicted[gone.program_id] = gone
                evicted += 1
            while len(self._evicted) > 4 * max(self._size, 1):
                self._evicted.pop(next(iter(self._evicted)))
        m = self._m()
        m["misses"].inc()
        m["compile"].observe(compile_s)
        if evicted:
            m["evictions"].inc(evicted)

    # -- surfaces ------------------------------------------------------------
    def records(self) -> list:
        """Snapshot of the live :class:`ProgramRecord` objects (their
        ``compiled`` executables included — ``as_text()`` shows what XLA
        built, e.g. whether a Pallas ``tpu_custom_call`` is in it)."""
        with self._lock:
            return list(self._records.values())

    def programz(self) -> dict:
        """The /debug/programz body: every record, most recent first."""
        with self._lock:
            recs = [r.to_dict() for r in self._records.values()]
            flush = self._flush_hits_locked()
        if flush:
            self._m()["hits"].inc(flush)
        recs.sort(key=lambda r: r["last_used_ns"], reverse=True)
        hits = sum(r["hits"] for r in recs)
        compiles = sum(r["compiles"] for r in recs)
        return {
            "programs": recs,
            "count": len(recs),
            "hits": hits,
            "compiles": compiles,
            "compile_ms": round(
                sum(r["compile_ms"] for r in recs), 3
            ),
        }

    def rows(self, since_seq: int) -> tuple:
        """(new_cursor, rows) — one ``__programs__`` row per record that
        changed since ``since_seq`` (cumulative counters; the LATEST row
        per program_id is its current state). Each telemetry collector
        keeps its own cursor, so N agents in one process each fold the
        full program history into their own table."""
        import itertools

        rows = []
        with self._lock:
            flush = self._flush_hits_locked()
            cursor = self._seq
            # Evicted records drain too (their seq was bumped at
            # eviction): the final counter state always reaches the
            # table even when the program never runs again.
            for rec in itertools.chain(
                self._records.values(), self._evicted.values()
            ):
                if rec.seq > since_seq:
                    rows.append({
                        "program_id": rec.program_id,
                        "kind": rec.kind,
                        "label": rec.label,
                        "compiles": rec.compiles,
                        "hits": rec.hits,
                        "compile_ms": rec.compile_s_total * 1e3,
                        "flops": (
                            float(rec.flops) if rec.flops is not None
                            else 0.0
                        ),
                        "bytes_accessed": (
                            float(rec.bytes_accessed)
                            if rec.bytes_accessed is not None else 0.0
                        ),
                        "argument_bytes": int(rec.argument_bytes or 0),
                        "temp_bytes": int(rec.temp_bytes or 0),
                        "peak_bytes": int(rec.peak_bytes or 0),
                        "last_used_ns": rec.last_used_ns,
                    })
        if flush:
            self._m()["hits"].inc(flush)
        return cursor, rows

    def stats(self) -> dict:
        with self._lock:
            return {
                "programs": len(self._records),
                "hits": sum(r.hits for r in self._records.values()),
                "compiles": sum(
                    r.compiles for r in self._records.values()
                ),
            }

    def clear(self) -> None:
        with self._lock:
            self._records.clear()


def _sig_repr(sig) -> str:
    """Compact human form of a shape signature for programz/telemetry:
    the distinct leaf shapes with multiplicities, e.g.
    '3x[131072]float32,[scalar]int32'."""
    _treedef, leaves = sig[:2]
    counts: dict = {}
    for shape, dtype, _sharding in leaves:
        name = getattr(dtype, "name", None) or getattr(
            dtype, "__name__", None
        ) or str(dtype)
        k = (tuple(shape), name)
        counts[k] = counts.get(k, 0) + 1
    parts = []
    for (shape, dtype), n in list(counts.items())[:8]:
        s = "x".join(str(d) for d in shape) or "scalar"
        parts.append(f"{n}x[{s}]{dtype}" if n > 1 else f"[{s}]{dtype}")
    if len(counts) > 8:
        parts.append("...")
    return ",".join(parts)


class DeviceMemoryMonitor:
    """``device.memory_stats()`` snapshots: gauges + per-query peaks.

    CPU devices return None from ``memory_stats()`` — every consumer of
    this class sees zeros/absent gauges there, never an error (the
    None-guard contract the telemetry tests pin). A poll thread
    (``start(poll_s)`` with a period > 0) tightens per-query peak resolution;
    without it peaks come from the query-boundary samples alone.
    """

    def __init__(self, metrics_registry=None):
        self._metrics_registry = metrics_registry
        self._lock = threading.Lock()
        self._open: list[dict] = []  # live per-query peak trackers
        self._thread: threading.Thread | None = None
        self._stop = threading.Event()
        self._collector_installed = False

    # -- snapshots -----------------------------------------------------------
    @staticmethod
    def snapshot() -> dict:
        """{device label: {kind: bytes}} for devices that report stats
        (TPU); stat-less devices (CPU) are simply absent."""
        import jax

        out: dict = {}
        for d in jax.local_devices():
            try:
                stats = d.memory_stats()
            except Exception:
                stats = None
            if not stats:
                continue
            label = f"{d.platform}:{d.id}"
            out[label] = {
                k: int(v) for k, v in stats.items()
                if isinstance(v, (int, float))
            }
        return out

    def _in_use(self) -> int:
        """Max ``bytes_in_use`` across devices (0 when unreported)."""
        snap = self.snapshot()
        return max(
            (s.get("bytes_in_use", 0) for s in snap.values()), default=0
        )

    # -- per-query peak attribution (engine execute_plan brackets) -----------
    def query_begin(self) -> dict:
        token = {"peak": self._in_use()}
        with self._lock:
            self._open.append(token)
        return token

    def query_end(self, token: dict) -> int:
        """High-water device bytes_in_use observed while the query ran
        (begin sample, any poll samples, end sample). 0 on backends
        without memory stats."""
        end = self._in_use()
        with self._lock:
            # Remove by IDENTITY: two overlapping queries whose begin
            # samples were equal hold ==-equal token dicts, and
            # list.remove would drop the OTHER query's token, cutting
            # it off from further poll updates.
            self._open = [t for t in self._open if t is not token]
            return max(token["peak"], end)

    # -- gauges + poll loop --------------------------------------------------
    def install_collector(self) -> None:
        """Refresh ``pixie_device_memory_bytes`` at every /metrics
        render (idempotent)."""
        if self._collector_installed:
            return
        if self._metrics_registry is None:
            from ..services.observability import default_registry

            self._metrics_registry = default_registry
        self._metrics_registry.register_collector(self._collect)
        self._collector_installed = True

    def _collect(self, reg) -> None:
        g = reg.gauge(
            "pixie_device_memory_bytes",
            "device.memory_stats() snapshot per local device "
            "(TPU-real; CPU devices report no stats and emit nothing)",
        )
        for dev, stats in self.snapshot().items():
            for kind in _MEM_KINDS:
                if kind in stats:
                    g.labels(device=dev, kind=kind).set(stats[kind])

    def start(self, poll_s: float = DEVICE_MEMORY_POLL_S) -> None:
        """Start the background poller (no-op when the period is <= 0
        or it is already running)."""
        period = float(poll_s)
        if period <= 0 or self._thread is not None:
            return
        self._stop.clear()

        def run():
            from .trace import background

            while not self._stop.wait(period):
                with background.turn("device_memory.poll"):
                    peak = self._in_use()
                    with self._lock:
                        for token in self._open:
                            if peak > token["peak"]:
                                token["peak"] = peak

        self._thread = threading.Thread(
            target=run, name="device-memory-poll", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        t, self._thread = self._thread, None
        if t is not None:
            t.join(timeout=2.0)


_DEFAULT_REGISTRY: ProgramRegistry | None = None
_DEFAULT_MONITOR: DeviceMemoryMonitor | None = None
_DEFAULT_LOCK = threading.Lock()


def default_program_registry() -> ProgramRegistry:
    """The process-wide program registry (fragments are shared process-
    wide through the fragment cache, so their programs are too)."""
    global _DEFAULT_REGISTRY
    with _DEFAULT_LOCK:
        if _DEFAULT_REGISTRY is None:
            _DEFAULT_REGISTRY = ProgramRegistry()
        return _DEFAULT_REGISTRY


def default_device_monitor() -> DeviceMemoryMonitor:
    """The process-wide device-memory monitor (one /metrics collector,
    shared per-query peak tracking across engines)."""
    global _DEFAULT_MONITOR
    with _DEFAULT_LOCK:
        if _DEFAULT_MONITOR is None:
            _DEFAULT_MONITOR = DeviceMemoryMonitor()
            _DEFAULT_MONITOR.install_collector()
        return _DEFAULT_MONITOR
