"""Per-query execution statistics (the ``analyze`` flag).

Reference parity: every Carnot ExecNode tracks ``ExecNodeStats``
(bytes/rows/batches, self vs child timers — ``src/carnot/exec/
exec_node.h:40-127``) and ``ExecutePlan`` ships per-operator
``queryresultspb.OperatorExecutionStats`` (``carnot.cc:389-423``). Here
the unit of execution is a compiled *fragment* (a whole Map/Filter/Agg
chain), so stats attach per fragment with a per-stage wall-time
breakdown of the TPU streaming pipeline:

- ``stage``    host -> device transfer + padding (zero when the window
               was already device-resident)
- ``compute``  device program (update/fold): to completion under
               ``analyze``; without it, what enqueueing costs the host
- ``finalize`` agg finalize program (likewise)
- ``materialize`` host batch assembly, once the bytes are on the host
- ``stall``    consumer time blocked waiting on the window-prefetch
               pipeline (pipeline_depth > 1); high stall with low stage
               time means the device, not staging, is the bottleneck

Since the query-lifecycle tracing subsystem (``trace.py``) landed,
these stats are one detail level of the always-on trace spine: every
query gets a ``QueryStats`` (attached to its ``QueryTrace``) with
``sync=False`` — stage timers stamp host-side wall-clock boundaries and
overlap survives. Enabling ``analyze`` sets ``sync=True``, which forces
synchronization after each stage (``block_until_ready``) so stage times
attribute real device work — overlap is sacrificed for attribution; run
benchmarks with it off. With the pipelined window executor the ``stage``
timer runs on the prefetch thread while ``compute`` runs on the query
thread, so FragmentStats.add is lock-protected.
"""

from __future__ import annotations

import contextlib
import threading
import time
from dataclasses import dataclass, field


@dataclass
class StageStat:
    seconds: float = 0.0
    rows: int = 0
    count: int = 0
    # Bytes moved by this stage (today: host->device transfer bytes on
    # "stage" intervals; zero for device-cache-resident windows). Feeds
    # QueryResourceUsage.bytes_staged (trace.py).
    nbytes: int = 0


@dataclass
class FragmentStats:
    """Stats for one materialized fragment."""

    ops: tuple = ()  # operator type names in chain order
    windows: int = 0
    rows_in: int = 0
    rows_out: int = 0
    # True = analyze mode: _block_if syncs the device after each stage so
    # timings attribute device work. False = always-on tracing: stamp
    # wall-clock boundaries only, never force a sync.
    sync: bool = True
    stages: dict = field(default_factory=dict)  # {stage: StageStat}
    # How an aggregating fragment's window fold runs
    # (``CompiledFragment.fold``, set by the fold loop); "" for a fragment
    # that folds nothing. On a traced fragment every ``compute`` dispatch
    # carries it.
    fold: str = ""
    # How its rows find their group (``CompiledFragment.group``: ``dense``
    # / ``sorted`` / ``hashed``) and the capacity the fold was compiled at
    # (``slots``); beside ``fold`` wherever that goes.
    group: str = ""
    slots: int = 0
    # The largest operand table its fold programs read (a dictionary-side
    # UDF's remap, padded to its bucket: ``CompiledFragment.
    # remap_entries``); 0 without one. Beside ``fold`` on a traced
    # fragment's ``compute`` dispatches.
    remap_entries: int = 0
    # The fold's ``quantiles`` digests (``FoldPlan.digests``: the carries,
    # one an argument / ``digest_slots`` / ``digest_bins``, and
    # ``digest_outputs``: the aggregates that read them); 0 without one.
    # Beside ``fold``.
    digests: int = 0
    digest_slots: int = 0
    digest_bins: int = 0
    digest_outputs: int = 0
    # Staging runs on the prefetch thread concurrently with compute on
    # the query thread (pipeline.py), so stage accumulation is locked.
    _lock: threading.Lock = field(
        default_factory=threading.Lock, repr=False, compare=False
    )

    def add(self, stage: str, seconds: float, rows: int = 0,
            nbytes: int = 0, start_ns: int = 0, end_ns: int = 0) -> None:
        """``start_ns``/``end_ns`` are the interval's two ends on
        ``time.perf_counter_ns()``, stamped where it ran; a traced
        fragment (``trace.py``) makes a span of them."""
        with self._lock:
            s = self.stages.setdefault(stage, StageStat())
            s.seconds += seconds
            s.rows += int(rows)
            s.count += 1
            s.nbytes += int(nbytes)

    def timed(self, stage: str, rows: int = 0, nbytes: int = 0):
        return _Timer(self, stage, rows, nbytes)

    def keeps_interval(self, n: int) -> bool:
        """Whether a per-window interval becomes a span: never without
        a trace."""
        return False

    def stamped(self, name: str, start_ns: int, **attrs) -> None:
        """A span from ``start_ns`` to now; nothing without a trace."""

    def subspan(self, name: str, parent=None, **attrs):
        """A named span under this fragment (or under ``parent``, a span
        of it); nothing without a trace."""
        return contextlib.nullcontext()

    def dispatch(self, program: str, stage: str = "compute",
                 windows: int = 1):
        """Around ONE program's enqueue: the ``stage`` timer, and on a
        traced fragment a ``device.dispatch`` span as well."""
        return self.timed(stage)

    def to_dict(self) -> dict:
        # Snapshot under the lock: /debug/queryz renders IN-FLIGHT
        # queries, so add() on the query/prefetch threads can be
        # inserting stage keys while a scrape iterates.
        with self._lock:
            stages = {
                k: (v.seconds, v.rows, v.count, v.nbytes)
                for k, v in self.stages.items()
            }
        out = {
            "ops": list(self.ops),
            "windows": self.windows,
            "rows_in": self.rows_in,
            "rows_out": self.rows_out,
            "stages": {
                k: {"seconds": round(s, 6), "rows": r, "count": c,
                    "bytes": b}
                for k, (s, r, c, b) in stages.items()
            },
        }
        if self.fold:
            out["fold"] = self.fold
        if self.group:
            out["group"], out["slots"] = self.group, self.slots
        return out


class _Timer:
    def __init__(self, stats: FragmentStats, stage: str, rows: int,
                 nbytes: int = 0):
        self.stats, self.stage, self.rows = stats, stage, rows
        self.nbytes = nbytes

    def __enter__(self):
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        self.stats.add(
            self.stage, (t1 - self.t0) / 1e9, self.rows, self.nbytes,
            start_ns=self.t0, end_ns=t1,
        )


@dataclass
class QueryStats:
    """All fragment stats for one plan execution."""

    fragments: list = field(default_factory=list)  # list[FragmentStats]
    total_seconds: float = 0.0
    sync: bool = True  # propagated to fragments; see FragmentStats.sync

    def new_fragment(self, ops) -> FragmentStats:
        fs = FragmentStats(
            ops=tuple(type(o).__name__ for o in ops), sync=self.sync
        )
        self.fragments.append(fs)
        return fs

    def to_dict(self) -> dict:
        # Per-fragment to_dict snapshots under each fragment's lock;
        # totals come from those snapshots (never raw racing dicts).
        frags = [f.to_dict() for f in self.fragments]
        totals: dict = {}
        for fd in frags:
            for k, v in fd["stages"].items():
                totals[k] = totals.get(k, 0.0) + v["seconds"]
        return {
            "total_seconds": round(self.total_seconds, 6),
            "stage_totals": {k: round(v, 6) for k, v in sorted(totals.items())},
            "fragments": frags,
        }
