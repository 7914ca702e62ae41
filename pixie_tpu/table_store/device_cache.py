"""Device-resident table windows: HBM as the cold store.

Reference contrast: Carnot's Table keeps hot ColumnWrapper batches and
cold Arrow slabs in host RAM (``src/table_store/table/table.h:104``), and
every query re-reads them. On TPU the equivalent of "cold" is **HBM**:
a full window of rows is staged onto the device once — at append time,
asynchronously — and every subsequent query consumes the already-resident
buffers, so steady-state queries perform zero host->device transfers of
table data (SURVEY.md §7 stage 1, §5 long-context).

Windows are aligned to absolute row-id multiples of ``window_rows`` (row
ids are monotone and never reused — ``table.h`` unique-row-id cursors), so
a window's content is immutable once full. Partial tail windows are cached
keyed by their current length and re-staged as they grow; expired windows
are evicted. An LRU byte budget (``device_cache_bytes``) bounds HBM use.
"""

from __future__ import annotations

import itertools
import threading
import weakref
from collections import OrderedDict
from dataclasses import dataclass

import numpy as np

from ..config import get_flag
from ..types.dtypes import device_dtypes, pad_values

# Global LRU accounting: the device_cache_bytes budget bounds the SUM of
# resident windows across every table's cache (one HBM, many tables), so
# eviction picks the globally least-recently-used window. The registry
# is process-global while engines are per-agent: one agent's staging
# loop iterates it while another agent's table creation add()s, so
# every traversal goes through a locked snapshot ("Set changed size
# during iteration" otherwise — observed as a cluster-test flake).
_CACHES: "weakref.WeakSet[DeviceWindowCache]" = weakref.WeakSet()
_CACHES_LOCK = threading.Lock()
_TICK = itertools.count()


def _caches() -> list:
    with _CACHES_LOCK:
        return list(_CACHES)


def total_resident_bytes() -> int:
    return sum(c._bytes for c in _caches())


def _enforce_global_budget(newest: tuple) -> None:
    """Evict globally-LRU windows until under budget; the just-inserted
    window (``newest`` = (cache, key)) always survives."""
    budget = get_flag("device_cache_bytes")
    while total_resident_bytes() > budget:
        victim = None  # (tick, cache, key)
        for c in _caches():
            # Snapshot: another engine's concurrent get()/put() moves
            # its own cache's ticks. Eviction choice is best-effort
            # under that race; the traversal must not crash.
            for k, t in list(c._ticks.items()):
                if (c, k) == newest:
                    continue
                if victim is None or t < victim[0]:
                    victim = (t, c, k)
        if victim is None:
            break
        victim[1]._evict(victim[2])


# -- thread-local restage meter ------------------------------------------------
# A window a scan found missing (LRU evicted it, or the tail grew) is
# staged again inside ``Table.device_scan``, on the scanning thread (the
# pipeline's producer when prefetching). The staging generators take the
# meter after each window and charge it to the query's ``restage`` stage
# (``QueryResourceUsage.bytes_restaged``), as the cold tier's decode
# meter is. It is a counter of its own: ``bytes_staged`` feeds
# admission's observed floor and pxbound's observed-against-predicted
# check, which predict unpadded rows of the scan's columns.

_RESTAGED = threading.local()


def take_restage_meter() -> tuple[float, int]:
    """Return and reset this thread's (seconds, device bytes) of windows
    ``Table.device_scan`` staged on a cache miss since the last take."""
    out = (getattr(_RESTAGED, "secs", 0.0), getattr(_RESTAGED, "nbytes", 0))
    _RESTAGED.secs = 0.0
    _RESTAGED.nbytes = 0
    return out


def note_restage(secs: float, nbytes: int) -> None:
    _RESTAGED.secs = getattr(_RESTAGED, "secs", 0.0) + secs
    _RESTAGED.nbytes = getattr(_RESTAGED, "nbytes", 0) + nbytes


@dataclass
class DeviceWindow:
    """One staged window: device column planes + occupancy info.

    ``cols`` maps column name -> tuple of jnp planes, each of length
    ``capacity`` (== the window size, a power of two). Rows
    [row0, row0 + n) are live; the validity mask for a query's row range
    is computed on device by the engine (cheap iota compares).
    """

    row0: int  # absolute row id of slot 0
    n: int  # live rows staged
    capacity: int
    cols: dict  # {name: tuple(jnp arrays)}
    nbytes: int


class DeviceWindowCache:
    """Cache of staged windows for one Table; budget enforced globally."""

    def __init__(self):
        self._entries: OrderedDict[tuple, DeviceWindow] = OrderedDict()
        self._ticks: dict[tuple, int] = {}
        self._bytes = 0
        with _CACHES_LOCK:
            _CACHES.add(self)

    def __len__(self) -> int:
        return len(self._entries)

    @property
    def nbytes(self) -> int:
        return self._bytes

    def get(self, key: tuple) -> DeviceWindow | None:
        win = self._entries.get(key)
        if win is not None:
            self._entries.move_to_end(key)
            self._ticks[key] = next(_TICK)
        return win

    def put(self, key: tuple, win: DeviceWindow) -> None:
        old = self._entries.pop(key, None)
        if old is not None:
            self._bytes -= old.nbytes
        self._entries[key] = win
        self._ticks[key] = next(_TICK)
        self._bytes += win.nbytes
        # Evict partial-window predecessors of the same (window_rows,
        # window_index) — key = (W, k, row0, n): a grown window supersedes
        # its stale shorter stagings.
        stale = [
            k for k in self._entries if k[:2] == key[:2] and k != key
        ]
        for k in stale:
            self._evict(k)
        _enforce_global_budget(newest=(self, key))

    def _evict(self, key: tuple) -> None:
        win = self._entries.pop(key, None)
        self._ticks.pop(key, None)
        if win is not None:
            self._bytes -= win.nbytes

    def evict_other_window_sizes(self, window_rows: int) -> None:
        """Drop entries staged at a different window size.

        A consumer scanning at W can never hit a (W', ...) entry; leaving
        them resident would double HBM use when append-time staging
        (keyed by the ``window_rows`` flag) disagrees with an engine's
        explicit ``window_rows`` override.
        """
        stale = [k for k in self._entries if k[0] != window_rows]
        for k in stale:
            self._evict(k)

    def evict_before(self, first_row_id: int) -> None:
        """Drop windows fully expired from the table."""
        stale = [
            k
            for k, w in self._entries.items()
            if w.row0 + w.n <= first_row_id
        ]
        for k in stale:
            self._evict(k)

    def clear(self) -> None:
        self._entries.clear()
        self._ticks.clear()
        self._bytes = 0


def stage_window(table, window_index: int, window_rows: int) -> DeviceWindow | None:
    """Read window ``window_index`` (rows [k*W, (k+1)*W)) and place it on
    device. Returns None for an empty window. The device_put is
    asynchronous — callers at append time pay only the host read/pad."""
    import jax.numpy as jnp

    from ..types.batch import bucket_capacity

    # Tier-merged read (Table.read_rows): a window straddling the
    # demotion boundary assembles from decoded cold rows + hot ring rows
    # transparently — the decode runs on THIS thread, which under the
    # WindowPipeline is the prefetch producer (decode-on-stage overlap).
    lo = window_index * window_rows
    planes, first, n = table.read_rows(
        max(lo, table.first_row_id()), window_rows
    )
    hi_cap = (window_index + 1) * window_rows
    if n > 0 and first + n > hi_cap:  # clip reads that ran past the window
        n = max(0, hi_cap - first)
        planes = [p[:n] for p in planes]
    if n <= 0:
        return None
    cap = bucket_capacity(window_rows)
    mult = getattr(table, "stage_capacity_multiple", 1)
    if mult > 1:
        from ..parallel.mesh import pad_to_multiple

        cap = pad_to_multiple(cap, mult)
    sharding = getattr(table, "stage_sharding", None)
    cols: dict = {}
    nbytes = 0
    for (cname, plane_i), p in zip(table._plane_layout, planes):
        dt = table.relation.col_type(cname)
        ddt = np.dtype(device_dtypes(dt)[plane_i])  # f64 -> f32 etc.
        padded = np.full(cap, pad_values(dt)[plane_i], dtype=ddt)
        padded[:n] = p
        if sharding is not None:
            # Mesh residency: the window lives row-sharded across the
            # engine's mesh — each virtual PEM holds its shard in HBM.
            import jax

            arr = jax.device_put(padded, sharding)
        else:
            arr = jnp.asarray(padded)
        cols.setdefault(cname, {})[plane_i] = arr
        nbytes += cap * ddt.itemsize
    cols = {
        c: tuple(v[i] for i in sorted(v)) for c, v in cols.items()
    }
    return DeviceWindow(
        row0=first, n=n, capacity=cap, cols=cols, nbytes=nbytes
    )
