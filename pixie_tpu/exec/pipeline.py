"""Pipelined window executor: overlap host staging with device compute.

The serial window loop stages (host slice -> pack -> ``device_put``)
window N and only then dispatches compute for it, so the host idles
during compute and the device idles during staging. This module runs the
staging generator on a background *prefetch thread* while the consumer
computes, with a bounded number of windows in flight — the standard
near-data-execution overlap lever: the difference between a stalled and
a saturated device.

Design:

- The producer thread pulls from the underlying staged-window generator
  (which performs all the staging work — for device-cache-resident
  windows that work is ~zero and the prefetcher degenerates to a cheap
  hand-off) and enqueues items.
- A semaphore with ``depth`` permits bounds in-flight windows: the
  producer acquires a permit *before* staging the next window; the
  consumer releases it only after it finishes computing that window.
  ``depth=1`` disables the thread entirely (bit-for-bit the serial
  executor).
- Errors raised during background staging are captured and re-raised in
  the consumer with the original traceback — a staging failure is the
  query's failure, never a hang or a secondary ``queue.Empty``.
- ``close()`` is idempotent and *always* joins the prefetch thread and
  drains staged-but-unconsumed device buffers; every consumer wraps its
  loop in try/finally so cancellation, limits, and compute errors can
  never leak a thread or touch a buffer after cancel.

Instrumentation: the pipeline tracks ``windows``, ``stage_secs``
(producer time spent staging), and ``stall_secs`` (consumer time blocked
waiting for a window). The per-window stall intervals also land in the
query's fragment stats (stage ``"stall"``) — always on since the trace
spine (``trace.py``) passes stats for every query, feeding the
``pixie_window_stage_seconds{stage="stall"}`` histogram and
``window.stall`` spans (both ends stamped here, where it stalls); engines accumulate per-query and lifetime
totals (``Engine.last_pipeline``, ``pipeline_totals``) for the
observability gauges.
"""

from __future__ import annotations

import contextlib
import queue
import threading
import time

from . import threadmap

#: Poll period for interruptible blocking waits (slot acquire / queue
#: get). Bounds how long cancellation/teardown can lag, not throughput —
#: steady-state hand-offs never hit the timeout.
_POLL_S = 0.05

#: Hot regions of the per-window execution path, registered for the
#: ``host-sync-hot-path`` lint (pixie_tpu/analysis/lint.py): a host
#: sync inside any of these runs once PER WINDOW, serializing the
#: prefetch overlap this module exists to provide. Entries are
#: "path-suffix:qualname-glob"; the lint engine reads this assignment
#: statically.
PXLINT_HOT_REGIONS = (
    "exec/pipeline.py:WindowPipeline*",
    "exec/engine.py:Engine._fold_agg_state",
    "exec/engine.py:Engine._fold_agg_state_native",
    "exec/engine.py:Engine._staged_windows*",
    "exec/engine.py:Engine._windows",
    "exec/engine.py:Engine._stage",
    # Windowed device-join drivers: their per-window loops ride the same
    # prefetch pipeline; an unjustified host sync there serializes the
    # probe stream exactly like one in the fold loops.
    "exec/joins.py:_join_device_windowed*",
    # Telemetry fold (services/telemetry.py): runs in Tracer.end_query
    # on the query thread right after the exec guard releases — a host
    # sync there would serialize the NEXT query behind telemetry
    # bookkeeping, so the fold must stay pure host-list arithmetic.
    "services/telemetry.py:TelemetryCollector*",
    "services/telemetry.py:ClusterTraceView*",
    # Storage-tier fold (__tables__): runs per finished trace on the
    # query thread and per heartbeat — host-counter arithmetic only.
    "services/telemetry.py:TableStatsCollector*",
    # Resource accounting on the trace spine: _finalize_usage and the
    # per-window stage/add paths run per query/window with the same
    # no-sync contract.
    "exec/trace.py:QueryTrace._finalize_usage",
    "exec/trace.py:TracedFragment.add",
    # Program registry (exec/programs.py): TrackedProgram.__call__ runs
    # once per tracked dispatch (i.e. per window) and the registry's
    # lookup/record/drain paths run under its lock — a host sync in any
    # of them would serialize every fold loop in the process. The
    # device-memory query brackets run per query with the same
    # contract (memory_stats() is a host call, not a device fence).
    "exec/programs.py:TrackedProgram*",
    "exec/programs.py:ProgramRegistry*",
    "exec/programs.py:DeviceMemoryMonitor*",
    # Profiling tier: the 100Hz sampler and the thread attribution
    # registry it reads. A host sync (or any blocking call) inside the
    # sample/fold path stalls EVERY thread's profile and turns the
    # profiler into a periodic global pause; the attribution reads are
    # GIL-atomic dict gets by design — keep them that way.
    "ingest/profiler.py:PerfProfilerConnector*",
    "ingest/profiler.py:_fold_stack",
    "exec/threadmap.py:*",
    # Transport tier: publish/deliver stamping runs on EVERY bus
    # message (dispatch, acks, partials, heartbeats) on the
    # publisher's and dispatcher's threads, and the __bus__ fold runs
    # per heartbeat — host-counter arithmetic only; a host sync here
    # would serialize the whole message path.
    "services/msgbus.py:Subscription._deliver",
    "services/msgbus.py:Subscription._run",
    "services/msgbus.py:MessageBus.publish",
    "services/msgbus.py:MessageBus._fanout",
    "services/busstats.py:BusStats*",
    "services/telemetry.py:BusStatsCollector*",
    # Storage tier (ISSUE 20): cold-window decode runs on the prefetch
    # thread once per staged window, and the zone-map pruner + the
    # tier-merged read path run per window on the scan spine — pure
    # numpy/host arithmetic; a host sync in any of them stalls the
    # decode-on-stage overlap exactly like one in WindowPipeline.
    "table_store/coldstore.py:EncodedPlane.decode",
    "table_store/coldstore.py:ColdStore._decode_window",
    "table_store/coldstore.py:ColdStore.read",
    "table_store/table.py:Table.read_rows",
    "exec/zoneskip.py:make_pruner*",
    "exec/zoneskip.py:chain_pruner*",
)


class DeadlineEvent:
    """Event-like cancel handle that also trips at an absolute
    wall-clock deadline (``time.time()`` seconds).

    The cooperative-cancellation seam polls ``cancel.is_set()`` at
    every window boundary (``Engine._check_cancel`` and
    :meth:`WindowPipeline._check_cancel`, which also polls every
    ``_POLL_S`` while blocked), so wrapping a query's cancel event in
    one of these makes an expired deadline abort the query between
    windows — dead work is dropped within one window boundary instead
    of computed to completion. Wall-clock (not monotonic) because the
    deadline is stamped by the BROKER and rides the dispatch message
    across processes; agents and broker are assumed loosely
    clock-synced (the same assumption the tracker's heartbeat expiry
    already makes).
    """

    __slots__ = ("_event", "deadline_unix_s")

    def __init__(self, event, deadline_unix_s: float):
        self._event = event
        self.deadline_unix_s = float(deadline_unix_s)

    def set(self) -> None:
        self._event.set()

    def is_set(self) -> bool:
        return self._event.is_set() or self.deadline_exceeded()

    def deadline_exceeded(self) -> bool:
        return time.time() >= self.deadline_unix_s


class WindowPipeline:
    """Bounded-depth prefetch over a staged-window generator.

    Iterate it exactly once; call :meth:`close` when done (iteration
    wrapped in try/finally — see module docstring). ``cancel`` is an
    optional ``threading.Event``-like object polled on both sides;
    when set, iteration raises ``QueryCancelled``.
    """

    def __init__(self, gen, depth: int, cancel=None, stats=None):
        self._gen = gen
        self.depth = max(1, int(depth))
        self._cancel = cancel
        self._stats = stats
        self.windows = 0
        self.stage_secs = 0.0
        self.stall_secs = 0.0
        self._slots = threading.Semaphore(self.depth)
        self._q: queue.Queue = queue.Queue()
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self._iterated = False
        # Profiler attribution: the prefetch thread does the creating
        # query's staging work, so it inherits the creator's entry
        # (rebound with phase "stage" in _produce) — otherwise its CPU
        # samples would show up unattributed.
        self._owner_entry = threadmap.current_entry()

    # -- consumer side -------------------------------------------------------
    def __iter__(self):
        if self._iterated:
            raise RuntimeError("WindowPipeline is single-use")
        self._iterated = True
        if self.depth <= 1:
            # Serial mode: no thread, no queue — today's loop, but the
            # cancel handle is still polled per window so generators
            # without their own check (e.g. the windowed join driver)
            # keep the both-sides cancellation contract.
            for item in self._gen:
                self._check_cancel()
                self.windows += 1
                yield item
            return
        # The prefetch thread's creation and start: half a millisecond
        # of the fragment's head on the chip's host (``pipeline.start``
        # on a traced fragment).
        started = (
            self._stats.subspan("pipeline.start")
            if self._stats is not None else contextlib.nullcontext()
        )
        with started:
            self._thread = threading.Thread(
                target=self._produce, name="pixie-window-prefetch",
                daemon=True,
            )
            self._thread.start()
        try:
            while True:
                self._check_cancel()
                t0 = time.perf_counter_ns()
                # Samples landing while we block on the producer are
                # wait-for-staging, not compute: flag them "stall" so
                # the flame separates starvation from real host work.
                tm = threadmap.set_phase("stall")
                try:
                    kind, val = self._get()
                finally:
                    threadmap.restore(tm)
                t1 = time.perf_counter_ns()
                dt = (t1 - t0) / 1e9
                self.stall_secs += dt
                if self._stats is not None:
                    self._stats.add("stall", dt, start_ns=t0, end_ns=t1)
                if kind == "done":
                    return
                if kind == "error":
                    # Surface the background staging failure as the
                    # query's own error, original traceback intact.
                    raise val
                self._check_cancel()
                self.windows += 1
                yield val
                val = None  # drop the device refs before freeing the slot
                self._slots.release()
        finally:
            self.close()

    def _get(self):
        while True:
            try:
                return self._q.get(timeout=_POLL_S)
            except queue.Empty:
                self._check_cancel()
                t = self._thread
                if (t is None or not t.is_alive()) and self._q.empty():
                    # Defensive: the producer always enqueues a terminal
                    # sentinel, so this is unreachable unless the thread
                    # was killed externally. Fail loudly, don't hang.
                    raise RuntimeError("window prefetch thread died")

    def _check_cancel(self):
        if self._cancel is not None and self._cancel.is_set():
            from .stream import QueryCancelled

            raise QueryCancelled("query cancelled")

    def counters(self) -> dict:
        """Counter snapshot ({depth, windows, stage_secs, stall_secs}) —
        what ``Engine._note_pipeline`` folds into the per-query trace
        and the engine-lifetime totals."""
        return {
            "depth": self.depth,
            "windows": self.windows,
            "stage_secs": self.stage_secs,
            "stall_secs": self.stall_secs,
        }

    def close(self) -> None:
        """Stop the producer, join its thread, drop staged buffers.

        Idempotent; safe on partially-consumed, cancelled, and errored
        pipelines. After close() returns no prefetch thread is alive and
        no staged window remains referenced by the pipeline.
        """
        self._stop.set()
        t, self._thread = self._thread, None
        if t is not None:
            t.join()
        while True:
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        gen, self._gen = self._gen, iter(())
        try:
            gen.close()
        except AttributeError:
            pass

    # -- producer side -------------------------------------------------------
    def _produce(self):
        tm = (
            threadmap.bind(base=self._owner_entry, phase="stage")
            if self._owner_entry is not None else None
        )
        try:
            while True:
                if not self._acquire_slot():
                    return  # consumer closed the pipeline
                t0 = time.perf_counter()
                try:
                    item = next(self._gen)
                except StopIteration:
                    self._put(("done", None))
                    return
                self.stage_secs += time.perf_counter() - t0
                if not self._put(("item", item)):
                    return
        except BaseException as e:  # noqa: BLE001 — relayed, not swallowed
            self._put(("error", e))
        finally:
            threadmap.unbind(tm)

    def _acquire_slot(self) -> bool:
        while not self._stop.is_set():
            if self._slots.acquire(timeout=_POLL_S):
                return True
        return False

    def _put(self, item) -> bool:
        # The queue is unbounded (the slot semaphore bounds in-flight
        # windows), so put never blocks; stop just discards late items.
        if self._stop.is_set():
            return False
        self._q.put(item)
        return True
