"""Hot/cold Table with time-indexed cursors and byte-budget expiry.

Reference parity: ``src/table_store/table/table.h:104`` — writes land in a
hot store, a compaction pass merges them into large cold slabs, reads go
through a ``Cursor`` keyed by *unique row ids* so no row is returned twice
even when compaction/expiry runs mid-query, and the oldest batches expire
when the byte budget is exceeded.

TPU-first redesign: both stores hold flat fixed-width column slabs (no
Arrow framing) sized so cursor reads hand back contiguous windows that
stage straight into fixed-capacity device buffers. Strings are dictionary
ids by the time they reach the table (``pixie_tpu.types.strings``); the
dictionaries live on the Python Table wrapper and are append-only, so
shared references stay valid as the table grows.

The slab store itself is native C++ (``pixie_tpu/native/table_ring.cc``,
ctypes-bound) with a pure-numpy fallback mirroring the same ABI.
"""

from __future__ import annotations

import ctypes
import threading
import time
from dataclasses import dataclass
from typing import Iterable, Optional, Sequence

import numpy as np

from ..native import load as load_native
from ..types.batch import HostBatch
from ..types.dtypes import DataType, host_dtypes
from ..types.relation import Relation
from ..types.strings import StringDictionary

TIME_COLUMN = "time_"
DEFAULT_COMPACTED_ROWS = 64 * 1024

#: EWMA smoothing factor for the per-append ingest rate: ~the last five
#: appends dominate, so the rate reflects the current push cadence
#: rather than table-lifetime throughput.
INGEST_EWMA_ALPHA = 0.2


@dataclass
class TableStats:
    bytes: int
    hot_bytes: int
    cold_bytes: int
    num_batches: int
    batches_added: int
    batches_expired: int
    bytes_added: int
    compacted_batches: int
    min_time: int
    num_rows: int
    # -- freshness surface (storage-tier observability) ----------------------
    # Derived/maintained OUTSIDE the backend stats buffer (the native ABI
    # stays 10 slots): monotonic append/expiry counters come from the
    # backend's existing row-id space (row ids are never reused, so
    # end_row_id == rows ever appended and first_row_id == rows expired),
    # the watermark from the col_stats bounds the append path already
    # maintains, and the wall-clock/EWMA fields from two attribute writes
    # per append. Defaults let bare positional constructions keep working.
    rows_added: int = 0  # rows ever appended (monotonic)
    rows_expired: int = 0  # rows dropped by TRUE expiry (monotonic)
    bytes_expired: int = 0  # raw bytes lost to true expiry (monotonic)
    watermark: int = -1  # max event-time ns ever appended (never regresses)
    last_append_unix_ns: int = 0  # wall time of the latest append
    ingest_rows_per_s: float = 0.0  # per-append EWMA ingest rate
    device_bytes: int = 0  # device-resident (HBM) staged window bytes
    # -- storage tier surface (table_store/tier.py; zeros when untiered).
    # For a tiered table hot_bytes/cold_bytes above are repurposed as the
    # per-TIER split (whole ring = hot, encoded store = cold) rather
    # than the ring's internal hot/compacted split.
    hot_rows: int = 0  # live rows in the hot ring
    cold_rows: int = 0  # live rows in the encoded cold store
    cold_raw_bytes: int = 0  # decoded size of the cold rows (ratio base)
    cold_windows: int = 0
    demotions: int = 0  # windows ever demoted hot -> cold (monotonic)
    evictions: int = 0  # cold windows ever evicted = expired (monotonic)
    decode_seconds: float = 0.0  # lifetime cold decode wall time


@dataclass(frozen=True)
class StartSpec:
    """Where a cursor begins: at a time, or the current start of table."""

    start_time: Optional[int] = None

    @classmethod
    def at_time(cls, t: int) -> "StartSpec":
        return cls(start_time=t)


@dataclass(frozen=True)
class StopSpec:
    """When a cursor is exhausted: at a time, at the current end of the
    table, or never (infinite streaming — the live-query mode)."""

    stop_time: Optional[int] = None
    infinite: bool = False

    @classmethod
    def at_time(cls, t: int) -> "StopSpec":
        return cls(stop_time=t)

    @classmethod
    def current_end(cls) -> "StopSpec":
        return cls()

    @classmethod
    def never(cls) -> "StopSpec":
        return cls(infinite=True)


class _PyBackend:
    """Pure-numpy mirror of the native slab store ABI (fallback path)."""

    def __init__(self, elem_dtypes, has_time, compacted_rows, max_bytes):
        self.elem_dtypes = elem_dtypes
        self.row_bytes = sum(np.dtype(d).itemsize for d in elem_dtypes)
        self.has_time = has_time
        self.compacted_rows = compacted_rows
        self.max_bytes = max_bytes
        self.lock = threading.Lock()
        self.hot: list = []  # [first_row_id, planes, min_t, max_t]
        self.cold: list = []
        self.next_row_id = 0
        self.counters = dict(
            batches_added=0, batches_expired=0, bytes_added=0, compacted=0
        )

    def _bytes(self, q) -> int:
        return sum(len(b[1][0]) * self.row_bytes for b in q)

    def _first_row_id(self) -> int:
        if self.cold:
            return self.cold[0][0]
        if self.hot:
            return self.hot[0][0]
        return self.next_row_id

    def append(self, planes: Sequence[np.ndarray], times) -> int:
        n = len(planes[0])
        if n == 0:
            return -1
        mn, mx = (int(times.min()), int(times.max())) if self.has_time else (0, 0)
        with self.lock:
            if self.max_bytes >= 0:
                while (
                    self._bytes(self.hot) + self._bytes(self.cold) + n * self.row_bytes
                    > self.max_bytes
                ):
                    q = self.cold if self.cold else self.hot
                    if not q:
                        break
                    q.pop(0)
                    self.counters["batches_expired"] += 1
            rid = self.next_row_id
            self.next_row_id += n
            self.hot.append([rid, [p.copy() for p in planes], mn, mx])
            self.counters["batches_added"] += 1
            self.counters["bytes_added"] += n * self.row_bytes
            return rid

    def compact(self) -> int:
        with self.lock:
            created = 0
            while self.hot:
                rows, take = 0, 0
                while take < len(self.hot) and rows < self.compacted_rows:
                    rows += len(self.hot[take][1][0])
                    take += 1
                group = self.hot[:take]
                del self.hot[:take]
                planes = [
                    np.concatenate([g[1][i] for g in group])
                    for i in range(len(self.elem_dtypes))
                ]
                self.cold.append(
                    [
                        group[0][0],
                        planes,
                        min(g[2] for g in group),
                        max(g[3] for g in group),
                    ]
                )
                self.counters["compacted"] += 1
                created += 1
            return created

    def first_row_id(self) -> int:
        with self.lock:
            return self._first_row_id()

    def end_row_id(self) -> int:
        with self.lock:
            return self.next_row_id

    def row_id_for_time(self, t: int, strictly_greater: bool) -> int:
        with self.lock:
            if not self.has_time:
                return self._first_row_id()
            for q in (self.cold, self.hot):
                for rid, planes, _, mx in q:
                    if (mx > t) if strictly_greater else (mx >= t):
                        times = planes[0]
                        hits = np.nonzero(times > t if strictly_greater else times >= t)[0]
                        if len(hits):
                            return rid + int(hits[0])
            return self.next_row_id

    def read(self, start_row_id: int, max_rows: int):
        with self.lock:
            row_id = max(start_row_id, self._first_row_id())
            pieces = [[] for _ in self.elem_dtypes]
            copied = 0
            for q in (self.cold, self.hot):
                for rid, planes, _, _ in q:
                    n = len(planes[0])
                    if rid + n <= row_id:
                        continue
                    start = max(0, row_id + copied - rid)
                    take = min(n - start, max_rows - copied)
                    if take <= 0:
                        continue
                    for i, p in enumerate(planes):
                        pieces[i].append(p[start : start + take])
                    copied += take
                    if copied >= max_rows:
                        break
                if copied >= max_rows:
                    break
            out = [
                np.concatenate(ps) if ps else np.empty(0, dtype=d)
                for ps, d in zip(pieces, self.elem_dtypes)
            ]
            return out, row_id, copied

    def drop_before(self, row_id: int) -> int:
        """Drop rows with id < row_id (cold-tier demotion handoff — NOT
        expiry: batches_expired does not move). Row-granular: a batch
        straddling row_id is split and its tail kept."""
        with self.lock:
            for q in (self.cold, self.hot):
                while q:
                    rid, planes, mn, mx = q[0]
                    n = len(planes[0])
                    if rid + n <= row_id:
                        q.pop(0)
                        continue
                    if rid < row_id:
                        drop = row_id - rid
                        tail = [p[drop:].copy() for p in planes]
                        if self.has_time:
                            mn = int(tail[0].min())
                            mx = int(tail[0].max())
                        q[0] = [row_id, tail, mn, mx]
                    return self._first_row_id()
            return self._first_row_id()

    def stats(self) -> list:
        with self.lock:
            hot_b, cold_b = self._bytes(self.hot), self._bytes(self.cold)
            min_t = (
                self.cold[0][2] if self.cold else (self.hot[0][2] if self.hot else -1)
            )
            return [
                hot_b + cold_b,
                hot_b,
                cold_b,
                len(self.hot) + len(self.cold),
                self.counters["batches_added"],
                self.counters["batches_expired"],
                self.counters["bytes_added"],
                self.counters["compacted"],
                min_t,
                self.next_row_id - self._first_row_id(),
            ]


class _NativeBackend:
    """ctypes binding for pixie_tpu/native/table_ring.cc."""

    _configured = False

    def __init__(self, lib, elem_dtypes, has_time, compacted_rows, max_bytes):
        self.lib = lib
        self.elem_dtypes = [np.dtype(d) for d in elem_dtypes]
        self.has_time = has_time
        self._configure(lib)
        sizes = (ctypes.c_int32 * len(self.elem_dtypes))(
            *[d.itemsize for d in self.elem_dtypes]
        )
        self.handle = lib.pxt_table_create(
            len(self.elem_dtypes), sizes, int(has_time), compacted_rows, max_bytes
        )

    @classmethod
    def _configure(cls, lib):
        if getattr(lib, "_pxt_configured", False):
            return
        lib.pxt_table_create.restype = ctypes.c_void_p
        lib.pxt_table_create.argtypes = [
            ctypes.c_int32,
            ctypes.POINTER(ctypes.c_int32),
            ctypes.c_int32,
            ctypes.c_int64,
            ctypes.c_int64,
        ]
        lib.pxt_table_destroy.argtypes = [ctypes.c_void_p]
        lib.pxt_table_append.restype = ctypes.c_int64
        lib.pxt_table_append.argtypes = [
            ctypes.c_void_p,
            ctypes.c_int64,
            ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_int64),
        ]
        for fn in ("pxt_table_compact", "pxt_table_first_row_id", "pxt_table_end_row_id"):
            f = getattr(lib, fn)
            f.restype = ctypes.c_int64
            f.argtypes = [ctypes.c_void_p]
        lib.pxt_table_row_id_for_time.restype = ctypes.c_int64
        lib.pxt_table_row_id_for_time.argtypes = [
            ctypes.c_void_p,
            ctypes.c_int64,
            ctypes.c_int32,
        ]
        lib.pxt_table_read.restype = ctypes.c_int64
        lib.pxt_table_read.argtypes = [
            ctypes.c_void_p,
            ctypes.c_int64,
            ctypes.c_int64,
            ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_int64),
        ]
        lib.pxt_table_stats.argtypes = [
            ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_int64),
        ]
        lib.pxt_table_drop_before.restype = ctypes.c_int64
        lib.pxt_table_drop_before.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib._pxt_configured = True

    def __del__(self):
        if getattr(self, "handle", None):
            self.lib.pxt_table_destroy(self.handle)
            self.handle = None

    def append(self, planes: Sequence[np.ndarray], times) -> int:
        planes = [np.ascontiguousarray(p) for p in planes]
        n = len(planes[0])
        ptrs = (ctypes.c_void_p * len(planes))(*[p.ctypes.data for p in planes])
        tptr = None
        if self.has_time:
            times = np.ascontiguousarray(times, dtype=np.int64)
            tptr = times.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))
        return self.lib.pxt_table_append(self.handle, n, ptrs, tptr)

    def compact(self) -> int:
        return self.lib.pxt_table_compact(self.handle)

    def first_row_id(self) -> int:
        return self.lib.pxt_table_first_row_id(self.handle)

    def end_row_id(self) -> int:
        return self.lib.pxt_table_end_row_id(self.handle)

    def row_id_for_time(self, t: int, strictly_greater: bool) -> int:
        return self.lib.pxt_table_row_id_for_time(self.handle, t, int(strictly_greater))

    def read(self, start_row_id: int, max_rows: int):
        out = [np.empty(max_rows, dtype=d) for d in self.elem_dtypes]
        ptrs = (ctypes.c_void_p * len(out))(*[a.ctypes.data for a in out])
        first = ctypes.c_int64(0)
        n = self.lib.pxt_table_read(
            self.handle, start_row_id, max_rows, ptrs, ctypes.byref(first)
        )
        return [a[:n] for a in out], first.value, n

    def drop_before(self, row_id: int) -> int:
        return self.lib.pxt_table_drop_before(self.handle, row_id)

    def stats(self) -> list:
        buf = (ctypes.c_int64 * 10)()
        self.lib.pxt_table_stats(self.handle, buf)
        return list(buf)


class Cursor:
    """Iterates a Table without ever returning a row twice.

    Reference: ``table.h`` Table::Cursor — position is the unique id of the
    next unread row, so compaction (which moves rows between stores) and
    expiry (which drops them) never desynchronize the read position.
    """

    def __init__(self, table: "Table", start: StartSpec, stop: StopSpec):
        self._table = table
        if start.start_time is not None:
            self._next_row_id = table.row_id_for_time(start.start_time, False)
        else:
            self._next_row_id = table.first_row_id()
        self.update_stop_spec(stop)

    def update_stop_spec(self, stop: StopSpec) -> None:
        t = self._table
        if stop.infinite:
            self._stop_row_id = None
        elif stop.stop_time is not None:
            # Stop at the time or the current end, whichever is first
            # (reference StopAtTime semantics).
            self._stop_row_id = min(
                t.row_id_for_time(stop.stop_time, True), t.end_row_id()
            )
        else:
            self._stop_row_id = t.end_row_id()

    def done(self) -> bool:
        if self._stop_row_id is None:
            return False
        return self._next_row_id >= self._stop_row_id

    def next_batch_ready(self) -> bool:
        if self._stop_row_id is not None:
            return not self.done()
        return self._next_row_id < self._table.end_row_id()

    def skip_to(self, row_id: int) -> None:
        """Advance past rows a zone-map check proved irrelevant (the
        scan-skip fast-forward; never moves backwards)."""
        self._next_row_id = max(self._next_row_id, int(row_id))

    def next_batch(self, max_rows: int, cols: Optional[Sequence[str]] = None):
        """Read up to max_rows as a HostBatch, or None when exhausted/dry."""
        if self.done():
            return None
        if self._stop_row_id is not None:
            max_rows = min(max_rows, self._stop_row_id - self._next_row_id)
        planes, first, n = self._table.read_rows(self._next_row_id, max_rows)
        if self._stop_row_id is not None:
            # Expiry may have skipped the read past the stop snapshot.
            n = min(n, max(0, self._stop_row_id - first))
            planes = [p[:n] for p in planes]
        if n == 0:
            self._next_row_id = max(self._next_row_id, first)
            return None
        self._next_row_id = first + n
        return self._table._batch_from_planes(planes, cols)


class Table:
    """Engine-facing table: relation + dictionaries over the slab store."""

    def __init__(
        self,
        name: str,
        relation: Relation | None = None,
        max_bytes: int = -1,
        compacted_rows: int = DEFAULT_COMPACTED_ROWS,
        dicts: dict[str, StringDictionary] | None = None,
    ):
        self.name = name
        self.relation = relation or Relation()
        # ``dicts`` may be shared across tablets of one logical table so
        # every tablet encodes strings into the same id space.
        self.dicts: dict[str, StringDictionary] = dicts if dicts is not None else {}
        self.max_bytes = max_bytes
        self.compacted_rows = compacted_rows
        self._backend = None
        # A table made without a relation adopts its first batch's: the
        # lock makes that one step (see append).
        self._adopt_lock = threading.Lock()
        self._plane_layout: list[tuple[str, int]] = []  # native order
        # Device residency (HBM as cold store): staged windows + watermark
        # of rows already staged at append time (device_cache.py). The
        # staging window size is a per-table fact: it defaults to the
        # window_rows flag and is ADOPTED from the first consumer that
        # scans at a different size, so append-time staging and query
        # windows converge without env-var choreography.
        from ..config import get_flag as _get_flag

        self._device_cache = None
        self._staged_through = 0
        self.device_window_rows = int(_get_flag("window_rows"))
        # Mesh residency: when a DistributedEngine owns the table, staged
        # windows device_put row-sharded over its mesh (None = single
        # device), padded to a shard-count multiple.
        self.stage_sharding = None
        self.stage_capacity_multiple = 1
        # Per-column (min, max) over every row ever appended, for
        # single-plane integer columns. Conservative bounds (ring expiry
        # never widens them), maintained on the push path so the query
        # compiler can pick dense-domain group-bys for integer keys the
        # way it does for dictionary codes. The reference has no analog
        # (its agg hash map is domain-oblivious, agg_node.h).
        self.col_stats: dict[str, tuple[int, int]] = {}
        # Ingest sketches (sketches.py): per-key-column HLL NDV + zone
        # maps + row count, consulted by join routing and the planner's
        # eager-aggregation sizing (PAPERS.md 2102.02440). Gated by the
        # ingest_sketches flag; None until the first sketched append.
        self.sketches = None
        # Freshness bookkeeping (storage-tier observability): wall time
        # of the latest append + a per-append ingest-rate EWMA. Plain
        # attribute writes on the push path — same unlocked-wrapper
        # convention as col_stats/sketches above (the backend holds the
        # only append-path lock); readers snapshot via stats().
        self._last_append_unix_ns = 0
        self._last_append_mono = None
        self._last_append_rows = 0
        self._ingest_ewma = 0.0
        # Cold storage tier (tier.py): set by _init_backend when the
        # cold_tier_mb flag is on AND the table is byte-bounded. A
        # tiered table's backend ring is created UNBOUNDED — the tier
        # manager owns both budgets (demote past max_bytes, evict past
        # cold_tier_mb), so ring self-expiry never races the demotion
        # handoff.
        self._tier = None
        if len(self.relation):
            self._init_backend()

    # -- backend wiring ------------------------------------------------------
    def _init_backend(self) -> None:
        has_time = (
            self.relation.has_column(TIME_COLUMN)
            and self.relation.col_type(TIME_COLUMN) == DataType.TIME64NS
        )
        # Native layout: the time plane first (the native time index reads
        # column 0), then every remaining plane in relation order.
        layout: list[tuple[str, int]] = []
        if has_time:
            layout.append((TIME_COLUMN, 0))
        for cname, dt in self.relation.items():
            for i in range(len(host_dtypes(dt))):
                if (cname, i) != (TIME_COLUMN, 0) or not has_time:
                    layout.append((cname, i))
        self._plane_layout = layout
        dts = [
            np.dtype(host_dtypes(self.relation.col_type(c))[i]) for c, i in layout
        ]
        from ..config import get_flag as _get_flag

        cold_mb = int(_get_flag("cold_tier_mb"))
        tiered = cold_mb > 0 and self.max_bytes >= 0
        lib = load_native("table_ring")
        ring_max = -1 if tiered else self.max_bytes
        args = (dts, has_time, self.compacted_rows, ring_max)
        backend = (
            _NativeBackend(lib, *args) if lib is not None else _PyBackend(*args)
        )
        if tiered:
            from .tier import MB, TierManager

            self._tier = TierManager(self, self.max_bytes, cold_mb * MB)
        for cname, dt in self.relation.items():
            if dt == DataType.STRING:
                self.dicts.setdefault(cname, StringDictionary())
        # Last: a set backend is what tells append the table is ready.
        self._backend = backend

    # -- write path ----------------------------------------------------------
    def append(self, data, time_cols: Iterable[str] = (TIME_COLUMN,)) -> HostBatch:
        """Push path: Stirling's TransferRecordBatch analog (table.h:268)."""
        hb = (
            data
            if isinstance(data, HostBatch)
            else HostBatch.from_pydict(
                data,
                relation=self.relation if len(self.relation) else None,
                time_cols=tuple(time_cols),
                dicts=self.dicts,
            )
        )
        if self._backend is None:
            # Two first appends at once (a collector's loop thread and a
            # caller's flush) must not find the relation set before the
            # backend is.
            with self._adopt_lock:
                if self._backend is None:
                    self.relation = hb.relation
                    self._init_backend()
        if hb.length == 0:
            return hb
        cols = dict(hb.cols)  # never mutate the caller's batch
        for col, d in hb.dicts.items():
            if col not in self.dicts:
                self.dicts[col] = d
            elif self.dicts[col] is not d:
                # Re-encode foreign ids into this table's dictionary,
                # extending it in place (append-only: ids already handed
                # out in earlier batches stay valid).
                mine = self.dicts[col]
                remap = np.fromiter(
                    (mine.get_or_add(s) for s in d.strings),
                    dtype=np.int32,
                    count=len(d),
                )
                ids = cols[col][0]
                cols[col] = (
                    np.where(ids >= 0, remap[np.clip(ids, 0, None)], -1).astype(
                        np.int32
                    ),
                )
        planes = [np.ascontiguousarray(cols[c][i]) for c, i in self._plane_layout]
        for (c, _i), p in zip(self._plane_layout, planes):
            if p.ndim != 1 or len(p) != hb.length:
                # A mis-shaped plane would silently corrupt the flat slab.
                raise ValueError(
                    f"column {c!r} plane has shape {p.shape}; expected "
                    f"1-D of length {hb.length}"
                )
        for (c, i), p in zip(self._plane_layout, planes):
            if (
                i == 0
                and len(p)
                and self.relation.col_type(c)
                in (DataType.INT64, DataType.TIME64NS)
            ):
                lo, hi = int(p.min()), int(p.max())
                cur = self.col_stats.get(c)
                self.col_stats[c] = (
                    (lo, hi)
                    if cur is None
                    else (min(cur[0], lo), max(cur[1], hi))
                )
        times = cols[TIME_COLUMN][0] if (TIME_COLUMN, 0) == self._plane_layout[0] else None
        if self._tier is not None:
            # Make room BEFORE the append lands: oldest windows demote
            # (encode-then-drop handoff, not expiry) so the unbounded
            # ring never holds more than max_bytes after this append.
            self._tier.demote_for(sum(p.nbytes for p in planes))
        rid = self._backend.append(planes, times)
        if rid >= 0:
            self._note_append_freshness(hb.length)
        from ..config import get_flag

        if (
            get_flag("ingest_sketches") and rid >= 0
            and not self.name.startswith("__")
        ):
            # Per-column NDV/zone-map sketches for join routing: the
            # single-plane INT64 columns col_stats already bounds, plus
            # dictionary string code planes (their ids ARE the join key
            # space). time_ is skipped — the time index supersedes it.
            # Dunder telemetry tables are excluded: they are never join
            # build sides, their bounds path is the documented
            # sketch-less fallback, and sketching a dozen INT64 columns
            # per __tables__/__queries__ fold row taxed every finished
            # trace AND bloated the bounds-memo stats key.
            if self.sketches is None:
                from .sketches import TableSketches

                self.sketches = TableSketches()
            self.sketches.rows += hb.length
            for (c, i), p in zip(self._plane_layout, planes):
                if i != 0 or c == TIME_COLUMN or len(p) == 0:
                    continue
                if self.relation.col_type(c) in (
                    DataType.INT64, DataType.STRING
                ) and len(host_dtypes(self.relation.col_type(c))) == 1:
                    self.sketches.update(c, p, rid)

        if get_flag("device_residency"):
            # Ship any newly completed windows to device now (the
            # device_put is async) so queries find them resident.
            self.stage_resident()
        return hb

    def _note_append_freshness(self, n: int) -> None:
        """Freshness bookkeeping per appended batch: two clock reads +
        EWMA arithmetic (the watermark itself is the ``time_`` col_stats
        bound append already maintains — no extra min/max pass). A
        separate method so the append-overhead A/B test can strip
        exactly this addition."""
        self._last_append_unix_ns = time.time_ns()
        self._last_append_rows = n
        mono = time.monotonic()
        prev, self._last_append_mono = self._last_append_mono, mono
        if prev is not None and mono > prev:
            rate = n / (mono - prev)
            self._ingest_ewma += (
                INGEST_EWMA_ALPHA * (rate - self._ingest_ewma)
            )

    def compact(self) -> int:
        """CompactHotToCold analog; call periodically (service loop)."""
        return self._backend.compact()

    # -- tier-merged row-id space --------------------------------------------
    # One unique monotone row-id space spans both tiers: demotion moves a
    # row from the ring into the cold store WITHOUT changing its id, so
    # cursors/watermarks keyed by row id never re-read or skip across a
    # demotion. These helpers are the read-path entry points; everything
    # below the Cursor goes through them instead of the bare backend.

    def first_row_id(self) -> int:
        """Oldest LIVE row id across both tiers. Advances only on true
        expiry (cold eviction for tiered tables, ring expiry otherwise)."""
        if self._tier is not None:
            f = self._tier.store.first_row_id()
            if f is not None:
                return f
        return self._backend.first_row_id()

    def end_row_id(self) -> int:
        return self._backend.end_row_id()

    def row_id_for_time(self, t: int, strictly_greater: bool) -> int:
        if self._tier is not None:
            store = self._tier.store
            if not store.has_time:
                return self.first_row_id()
            r = store.row_id_for_time(t, strictly_greater)
            if r is not None:
                return r
        return self._backend.row_id_for_time(t, strictly_greater)

    def read_rows(self, start_row_id: int, max_rows: int):
        """Tier-merged mirror of the backend ``read`` ABI: (planes,
        first_row_id, rows). Ordering is the demotion-race guard: the
        ring is read FIRST, then the gap below the ring's answer is
        filled from cold. Demotion encodes into cold BEFORE dropping
        from the ring, so any row the ring no longer has is either in
        the cold store or truly evicted — never in flight."""
        be = self._backend
        h_planes, h_first, h_n = be.read(start_row_id, max_rows)
        if self._tier is None or h_first <= start_row_id:
            return h_planes, h_first, h_n
        want = min(h_first - start_row_id, max_rows)
        c_planes, c_first, c_n = self._tier.store.read(start_row_id, want)
        if c_n == 0:
            return h_planes, h_first, h_n
        if c_first + c_n == h_first and h_n > 0 and c_n < max_rows:
            take_h = min(h_n, max_rows - c_n)
            planes = [
                np.concatenate([cp, hp[:take_h]])
                for cp, hp in zip(c_planes, h_planes)
            ]
            return planes, c_first, c_n + take_h
        return c_planes, c_first, c_n

    # -- read path -----------------------------------------------------------
    def cursor(
        self, start: StartSpec | None = None, stop: StopSpec | None = None
    ) -> Cursor:
        return Cursor(self, start or StartSpec(), stop or StopSpec())

    def scan(self, start_time=None, stop_time=None, window_rows: int = 1 << 17,
             prune=None):
        """Yield HostBatch windows, time-bounded (engine source interface).

        ``prune(row_lo, row_hi) -> bool`` (exec/zoneskip.py) is consulted
        per window BEFORE the read: True fast-forwards the cursor past
        [row_lo, row_hi) without touching either tier — for cold windows
        that means no decode at all.
        """
        if self._backend is None:
            return
        start = StartSpec.at_time(int(start_time)) if start_time is not None else StartSpec()
        stop = StopSpec.at_time(int(stop_time) - 1) if stop_time is not None else StopSpec()
        cur = self.cursor(start, stop)
        while not cur.done():
            if prune is not None:
                lo = cur._next_row_id
                hi = lo + window_rows
                if cur._stop_row_id is not None:
                    hi = min(hi, cur._stop_row_id)
                if hi > lo and prune(lo, hi):
                    cur.skip_to(hi)
                    continue
            hb = cur.next_batch(window_rows)
            if hb is None:
                break
            yield hb

    def stage_resident(self, window_rows: int | None = None) -> None:
        """Stage all complete windows onto the device (HBM cold store)."""
        from .device_cache import DeviceWindowCache, stage_window

        if self._backend is None:
            return
        w = int(window_rows or self.device_window_rows)
        if self._device_cache is None:
            self._device_cache = DeviceWindowCache()
        # Evict by the tier-merged first LIVE row: demoted-but-live rows
        # keep their staged device windows (repeat scans stay resident
        # and never re-decode), only true expiry reclaims them.
        self._device_cache.evict_before(self.first_row_id())
        end = self.end_row_id()
        self._staged_through = max(
            self._staged_through, (self.first_row_id() // w) * w
        )
        while self._staged_through + w <= end:
            k = self._staged_through // w
            first = max(k * w, self.first_row_id())
            n = min((k + 1) * w, end) - first
            if n > 0 and self._device_cache.get((w, k, first, n)) is None:
                win = stage_window(self, k, w)
                if win is not None:
                    self._device_cache.put((w, k, win.row0, win.n), win)
            self._staged_through = (k + 1) * w

    def device_scan(self, start_time=None, stop_time=None,
                    window_rows: int | None = None, start_row=None,
                    stop_row=None, prune=None):
        """Yield (DeviceWindow, lo_row, hi_row) covering the time range.

        Windows come from the device-resident cache when staged (zero
        transfer); misses — typically the partial tail window — stage on
        demand and are cached keyed by their length, so a grown tail
        re-stages while full windows stay immutable. ``start_row`` /
        ``stop_row`` clamp by absolute row id — the streaming
        (live-query) cursor's watermark interface. ``prune(lo, hi)``
        (exec/zoneskip.py) runs BEFORE the cache probe/stage, so a
        skipped window is never decoded or transferred.
        """
        from .device_cache import (
            DeviceWindowCache, note_restage, stage_window,
        )

        if self._backend is None:
            return
        w = int(window_rows or self.device_window_rows)
        if self._device_cache is None:
            self._device_cache = DeviceWindowCache()
        self._device_cache.evict_before(self.first_row_id())
        if w != self.device_window_rows:
            # Adopt the consumer's window size: future appends stage at w
            # (last consumer wins; differently-sized stagings are dead
            # weight for this consumer and are reclaimed now).
            self.device_window_rows = w
            self._staged_through = 0
        self._device_cache.evict_other_window_sizes(w)
        if start_time is not None:
            row0 = self.row_id_for_time(int(start_time), False)
        else:
            row0 = self.first_row_id()
        if start_row is not None:
            row0 = max(row0, int(start_row))
        start_row = row0
        if stop_time is not None:
            row1 = min(
                self.row_id_for_time(int(stop_time) - 1, True),
                self.end_row_id(),
            )
        else:
            row1 = self.end_row_id()
        if stop_row is not None:
            row1 = min(row1, int(stop_row))
        stop_row = row1
        if stop_row <= start_row:
            return
        for k in range(start_row // w, (stop_row + w - 1) // w):
            first = max(k * w, self.first_row_id())
            n = min((k + 1) * w, self.end_row_id()) - first
            if n <= 0:
                continue
            if prune is not None:
                plo = max(start_row, first)
                phi = min(stop_row, first + n)
                if phi > plo and prune(plo, phi):
                    continue
            win = self._device_cache.get((w, k, first, n))
            if win is None:
                t0 = time.perf_counter()
                win = stage_window(self, k, w)
                if win is None:
                    continue
                note_restage(time.perf_counter() - t0, win.nbytes)
                self._device_cache.put((w, k, win.row0, win.n), win)
            lo, hi = max(start_row, win.row0), min(stop_row, win.row0 + win.n)
            if hi > lo:
                yield win, lo, hi

    def _batch_from_planes(self, planes, cols=None) -> HostBatch:
        by_key = {k: p for k, p in zip(self._plane_layout, planes)}
        names = list(cols) if cols is not None else self.relation.column_names
        rel = self.relation.select(names)
        out_cols = {
            c: tuple(by_key[(c, i)] for i in range(len(host_dtypes(rel.col_type(c)))))
            for c in names
        }
        n = len(planes[0]) if planes else 0
        return HostBatch(
            relation=rel,
            cols=out_cols,
            length=n,
            dicts={c: d for c, d in self.dicts.items() if c in set(names)},
        )

    def read_all(self) -> HostBatch:
        """Materialize the whole table as one HostBatch (test/debug path)."""
        if self._backend is None:
            from ..exec.engine import _empty_host_batch

            return _empty_host_batch(self.relation, self.dicts)
        n = max(1, self.num_rows)
        planes, _, got = self.read_rows(self.first_row_id(), n)
        return self._batch_from_planes([p[:got] for p in planes])

    # -- introspection -------------------------------------------------------
    @property
    def num_rows(self) -> int:
        return self.stats().num_rows if self._backend is not None else 0

    @property
    def watermark_ns(self):
        """Max event-time ns ever appended (None without a time index).
        Monotonic by construction — ring expiry never regresses it."""
        st = self.col_stats.get(TIME_COLUMN)
        return st[1] if st is not None else None

    def stats(self) -> TableStats:
        """Snapshot of the backend counters + the freshness surface.
        The backend half is one locked stats() read; the row-id counters
        are two more locked reads (row ids are never reused, so
        end_row_id == rows ever appended and first_row_id == rows
        expired) — under concurrent appends the trio can straddle a
        batch, so exact cross-field reconciliation holds at quiesce."""
        if self._backend is None:
            return TableStats(0, 0, 0, 0, 0, 0, 0, 0, -1, 0)
        be = self._backend
        st = TableStats(*be.stats())
        st.rows_added = be.end_row_id()
        if self._tier is not None:
            # Tiered view: the whole ring is the hot tier, the encoded
            # store is the cold tier. Only cold EVICTION is expiry —
            # demotion moved rows, it didn't lose them — so the expiry
            # counters come from the cold store's eviction ledger (at
            # raw row widths, matching the ring's accounting).
            cs = self._tier.store
            st.hot_bytes = st.bytes
            st.cold_bytes = cs.nbytes
            st.bytes = st.hot_bytes + cs.nbytes
            st.hot_rows = st.num_rows
            st.cold_rows = cs.num_rows()
            st.num_rows = be.end_row_id() - self.first_row_id()
            st.num_batches += len(cs.windows)
            cold_min_t = cs.min_time()
            if cold_min_t is not None:
                st.min_time = cold_min_t
            st.rows_expired = cs.rows_evicted
            st.bytes_expired = cs.bytes_evicted_raw
            st.cold_raw_bytes = cs.raw_nbytes
            st.cold_windows = len(cs.windows)
            st.demotions = cs.demotions
            st.evictions = cs.evictions
            st.decode_seconds = cs.decode_seconds
        else:
            st.hot_rows = st.num_rows
            st.rows_expired = be.first_row_id()
            st.bytes_expired = st.bytes_added - st.bytes
        wm = self.watermark_ns
        st.watermark = wm if wm is not None else -1
        st.last_append_unix_ns = self._last_append_unix_ns
        st.ingest_rows_per_s = self._current_ingest_rate()
        dc = self._device_cache
        st.device_bytes = dc.nbytes if dc is not None else 0
        return st

    def _current_ingest_rate(self) -> float:
        """The EWMA, decayed at READ time: the EWMA itself only moves on
        appends, so a STOPPED ingest would report its last healthy rate
        forever. Capping at last-batch-rows / silence-elapsed decays the
        reported rate toward 0 as the silence grows, while an actively
        appending table (elapsed <= its inter-append interval) reports
        the EWMA unchanged."""
        last = self._last_append_mono
        if last is None:
            return 0.0
        elapsed = time.monotonic() - last
        if elapsed <= 0:
            return self._ingest_ewma
        return min(self._ingest_ewma, self._last_append_rows / elapsed)

    def freshness(self) -> dict:
        """Wire form of the freshness surface (agent heartbeat envelope
        + ``__tables__`` telemetry fold): live sizes, monotonic append/
        expiry counters, the event-time watermark pair, wall time of the
        last append and the ingest-rate EWMA."""
        st = self.stats()
        return {
            "rows": st.num_rows,
            "bytes": st.bytes,
            "hot_bytes": st.hot_bytes,
            "cold_bytes": st.cold_bytes,
            "device_bytes": st.device_bytes,
            "rows_total": st.rows_added,
            "bytes_total": st.bytes_added,
            "expired_rows_total": st.rows_expired,
            "expired_bytes_total": st.bytes_expired,
            "watermark": st.watermark,
            "min_time": st.min_time,
            "last_append": st.last_append_unix_ns,
            "ingest_rows_per_s": round(st.ingest_rows_per_s, 3),
            # storage-tier split (zeros for untiered tables)
            "hot_rows": st.hot_rows,
            "cold_rows": st.cold_rows,
            "cold_raw_bytes": st.cold_raw_bytes,
            "cold_demotions_total": st.demotions,
            "cold_evictions_total": st.evictions,
            "cold_decode_seconds_total": round(st.decode_seconds, 6),
        }


def max_watermark_ns(tablets):
    """Max event-time watermark across ``tablets`` (None = no time
    index / nothing appended anywhere). THE freshness sweep: the
    engine's per-scan staleness stamp, the streaming cursor's per-poll
    note and the result cache's validity reads all go through this one
    helper — one sweep per poll/scan round, never one per consumer
    (the same dedup PR 14 applied to the heartbeat path). Callers
    resolve it through the module (``table.max_watermark_ns``) so the
    regression test can count sweeps."""
    wm = -1
    for t in tablets:
        w = getattr(t, "watermark_ns", None)
        if w is not None and w > wm:
            wm = int(w)
    return None if wm < 0 else wm
