"""Device-side N:M equijoin with static shapes.

Reference parity: ``src/carnot/exec/equijoin_node.{h,cc}`` — build+probe
hash join supporting inner/left/right/outer with N:M fan-out and chunked
output. Hash maps are hostile to XLA, so the TPU design is sort-based,
reusing the group-by machinery (``pixie_tpu.ops.groupby``):

1. Both sides' key planes are mapped to one exact dense key-id space by
   ``dense_group_ids`` over the concatenated rows (multi-key sort — no
   hash collisions, static shapes).
2. The build side is sorted by key id; a probe row's contiguous match
   range [lo, lo + m) is read at its id from the build rows' counts by
   id and their exclusive prefix (the ids are dense, so nothing is
   searched; the windowed drivers below still ``searchsorted`` a build
   sorted once against each probe window).
3. Match ranges expand into a fixed-capacity output via exclusive prefix
   sums + a scatter/cummax ownership scan; rows beyond ``capacity`` are
   dropped and flagged (``overflow=True``) so the caller can re-run with
   a doubled capacity — the static-shape analog of Carnot's growing
   output chunks.

The kernel returns gather indices + take-masks, not materialized columns:
(probe_idx, probe_take, build_idx, build_take, out_valid, overflow).
Unmatched sides emit take=False, which callers turn into nulls. Where a
take-mask is False the paired index is arbitrary but always in-bounds,
so unconditional gathers stay safe.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .groupby import dense_group_ids, dense_group_ids_hash
from .hashtable import _mix64, _mix64_j
from . import routes
from .scan import blocked_cummax, blocked_cumsum


def _exclusive_cumsum(x):
    """(exclusive cumsum, total) for an int32 vector. Blocked so probe-
    length scans compile on TPU (flat cumsum overflows scoped vmem at
    multi-million rows — see ops/scan.py)."""
    c = blocked_cumsum(x)
    return jnp.concatenate([jnp.zeros(1, x.dtype), c[:-1]]), c[-1]


#: Longest ownership scan taken as ONE ``lax.associative_scan``; above it
#: the two-level blocked scan (``ops/scan.py``). The flat scan's compile
#: seconds grow with its length: 93 s at 2^20 output slots against 1.1 s
#: blocked (a described v5e, PR 39: px/perf_flamegraph's join of 0.64 M
#: rows, whose first request did not end inside its timeout); the limit
#: is the one size a cell runs it flat at (px/net_flow_graph's 2^17
#: slots, where it compiles in seconds).
_FLAT_CUMMAX_MAX = 1 << 17


def _cummax(x):
    """Inclusive cumulative max (associative scan -> O(log n) on device)."""
    if x.shape[0] > _FLAT_CUMMAX_MAX:
        return blocked_cummax(x, force=True)
    return jax.lax.associative_scan(jnp.maximum, x)


def _owners(slot_of, emitting, count, capacity):
    """Per-output-slot owner row (1-based; 0 = no owner yet).

    Scatter (row+1) at each emitting row's start slot, then cummax: every
    slot inherits the nearest preceding start's row. Emitting rows have
    strictly increasing starts, so scatters never collide.
    """
    marker = (
        jnp.zeros(capacity + 1, dtype=jnp.int32)
        .at[slot_of]
        .max(jnp.arange(1, count + 1, dtype=jnp.int32) * emitting)[:capacity]
    )
    return _cummax(marker)


def probe_sorted_join(
    sorted_build_keys,
    n_build,
    probe_keys,
    probe_valid,
    capacity: int,
    how: str = "inner",
):
    """Probe one window against a PRE-SORTED device-resident build side.

    The multi-window join driver (``exec/joins.py``) packs both sides'
    keys into one comparable int64 id space on host, sorts the build ids
    ONCE, and stages them on device once per query; each probe window
    then runs only the searchsorted + expansion half of ``device_join``
    — no per-window dense-id pass, no per-window build sort, and no
    per-window build transfer.

    Args:
      sorted_build_keys: int64[B]; entries [0, n_build) ascending, the
        rest padded with int64 max (never matched — ranges clamp to
        ``n_build``).
      n_build: traced int32 count of real build rows.
      probe_keys / probe_valid: int64[N] ids + bool[N] mask for this
        probe window.
      capacity: static output row capacity C.
      how: 'inner' | 'left' (windowable joins only: each probe row's
        output is independent of every other window's; right/outer need
        global unmatched-build knowledge and stay single-shot).

    Returns the same (probe_idx, probe_take, build_idx, build_take,
    out_valid, overflow) contract as ``device_join``, with ``build_idx``
    indexing the SORTED build order (the driver maps back through its
    host-side sort permutation).
    """
    if how not in ("inner", "left"):
        raise ValueError(f"probe_sorted_join supports inner/left, not {how!r}")
    nb = jnp.asarray(n_build, dtype=jnp.int32)
    lo = jnp.minimum(
        jnp.searchsorted(sorted_build_keys, probe_keys, side="left"), nb
    ).astype(jnp.int32)
    hi = jnp.minimum(
        jnp.searchsorted(sorted_build_keys, probe_keys, side="right"), nb
    ).astype(jnp.int32)
    return _expand_ranges(
        lo, hi, probe_valid, capacity, how, sorted_build_keys.shape[0]
    )


def _expand_ranges(lo, hi, probe_valid, capacity: int, how: str, b: int):
    """Expand per-probe match ranges [lo, hi) into the fixed-capacity
    (probe_idx, probe_take, build_idx, build_take, out_valid, overflow)
    output — the shared back half of every probe-side kernel."""
    n = probe_valid.shape[0]
    c = capacity
    m = jnp.where(probe_valid, hi - lo, 0).astype(jnp.int32)

    e = jnp.maximum(m, 1) if how == "left" else m
    e = jnp.where(probe_valid, e, 0).astype(jnp.int32)
    start, _ = _exclusive_cumsum(e)
    # Overflow detection in 64-bit: a window with > 2^31 total pairs
    # wraps the int32 prefix sums, which would otherwise read as "fits"
    # and silently drop the window. The int32 slot math stays exact in
    # every non-overflow case (total <= capacity << 2^31); on overflow
    # the caller discards this output and retries doubled anyway.
    total_pairs = jnp.sum(e.astype(jnp.int64))

    slot_of = jnp.where((e > 0) & (start < c), start, c)
    owner1 = _owners(slot_of, (e > 0).astype(jnp.int32), n, c)

    # A slot past the pairs has no owner to read; it reads the row of its
    # own number. Left to the scan it inherits the LAST owner, so every
    # idle slot of the output (more than half of it at the estimate's
    # head-room) gathered one address: 46 ms or 62 for the same 2^21-slot
    # gather, by where the seed's last row fell (PERF.md section 6, PR 39).
    j = jnp.arange(c, dtype=jnp.int32)
    pair_valid = (j < total_pairs) & (owner1 > 0)
    probe_idx = jnp.where(pair_valid, owner1 - 1, j % n)
    t = j - start[probe_idx]
    is_match = t < m[probe_idx]
    build_idx = jnp.clip(
        lo[probe_idx] + jnp.minimum(t, m[probe_idx] - 1), 0, b - 1
    )
    return (
        probe_idx, pair_valid, build_idx, pair_valid & is_match,
        pair_valid, total_pairs > c,
    )


# -- radix-partitioned probe -------------------------------------------------
def radix_partition_build(keys: np.ndarray, radix_bits: int):
    """Host-side build partitioning for ``radix_probe_join``.

    Hashes the packed int64 build keys with the splitmix64 mixer
    (``ops/hashtable._mix64``) and sorts them by (top ``radix_bits`` of
    the hash, key). Within a partition keys are ascending, so a probe
    row binary-searches ONE partition instead of the whole build side —
    log2(B/P) memory touches per probe instead of log2(B), against a
    partition-sized working set.

    Returns (order, part_starts, search_steps):
      order        int64[B] — build-row permutation (sorted position ->
                   original row), the analog of the sorted driver's
                   ``np.argsort``.
      part_starts  int32[P+1] — partition offsets into the sorted keys
                   (real rows only; padding stays outside every range).
      search_steps static trip count for the kernel's bounded binary
                   search: enough for the LARGEST partition, bucketed up
                   so one compiled program serves similar builds.
    """
    p = 1 << radix_bits
    keys = np.ascontiguousarray(keys, dtype=np.int64)
    h = _mix64(keys.view(np.uint64))
    part = (h >> np.uint64(64 - radix_bits)).astype(np.int64)
    order = np.lexsort((keys, part)).astype(np.int64)
    counts = np.bincount(part, minlength=p)
    part_starts = np.zeros(p + 1, dtype=np.int64)
    np.cumsum(counts, out=part_starts[1:])
    # +1 step of slack over ceil(log2(max+1)): the branchless search
    # no-ops once converged, so slack costs one gather, never wrongness.
    steps = max(4, int(np.ceil(np.log2(int(counts.max()) + 2))) + 1)
    return order, part_starts.astype(np.int32), steps


def _bounded_searchsorted(a, keys, lo0, hi0, steps: int, side: str):
    """Per-row binary search of ``keys`` into ``a`` restricted to
    [lo0, hi0), with a STATIC trip count (extra steps no-op once
    lo == hi — static shapes, no data-dependent control flow)."""
    lo, hi = lo0, hi0
    top = a.shape[0] - 1
    for _ in range(steps):
        mid = (lo + hi) >> 1
        v = a[jnp.clip(mid, 0, top)]
        go = (v < keys) if side == "left" else (v <= keys)
        upd = lo < hi
        lo = jnp.where(upd & go, mid + 1, lo)
        hi = jnp.where(upd & ~go, mid, hi)
    return lo


def radix_probe_join(
    sorted_build_keys,
    part_starts,
    probe_keys,
    probe_valid,
    capacity: int,
    how: str = "inner",
    radix_bits: int = 8,
    search_steps: int = 24,
):
    """Probe one window against a radix-partitioned device build side.

    The driver partitions the build side ONCE per query with
    ``radix_partition_build`` and stages ``sorted_build_keys`` (int64[B],
    padding = int64 max past the real rows) + ``part_starts`` (int32[P+1])
    on device; each probe window hashes its keys with the same splitmix64
    mixer, reads its partition's [start, end) range, and binary-searches
    only that partition. Same output contract and ``how`` support
    (inner/left) as ``probe_sorted_join``.
    """
    if how not in ("inner", "left"):
        raise ValueError(f"radix_probe_join supports inner/left, not {how!r}")
    h = _mix64_j(probe_keys.astype(jnp.uint64))
    p = (h >> jnp.uint64(64 - radix_bits)).astype(jnp.int32)
    lo0 = part_starts[p]
    hi0 = part_starts[p + 1]
    lo = _bounded_searchsorted(
        sorted_build_keys, probe_keys, lo0, hi0, search_steps, "left"
    )
    hi = _bounded_searchsorted(
        sorted_build_keys, probe_keys, lo0, hi0, search_steps, "right"
    )
    return _expand_ranges(
        lo, hi, probe_valid, capacity, how, sorted_build_keys.shape[0]
    )


def device_join(
    build_keys,
    build_valid,
    probe_keys,
    probe_valid,
    capacity: int,
    how: str = "inner",
):
    """Join probe (left) rows against build (right) rows on equal keys.

    Args:
      build_keys / probe_keys: lists of [B] / [N] key planes (same plane
        count and dtypes per position; a UINT128 key contributes two).
        Both sides must be non-empty arrays (mask rows invalid instead).
      build_valid / probe_valid: bool masks.
      capacity: static output row capacity C.
      how: 'inner' | 'left' | 'right' | 'outer'.

    Returns:
      probe_idx int32[C], probe_take bool[C]  — left-side gather/null
      build_idx int32[C], build_take bool[C]  — right-side gather/null
      out_valid bool[C], overflow bool[]      — occupancy + truncation
    """
    if how not in ("inner", "left", "right", "outer"):
        raise ValueError(f"unsupported join how={how!r}")
    b = build_valid.shape[0]
    n = probe_valid.shape[0]
    c = capacity
    if b == 0 or n == 0:
        raise ValueError("device_join sides must be non-empty (mask instead)")

    # 1. Shared exact key-id space. Invalid rows get id b+n from the
    # group machinery; split that trash id per side so invalid build and
    # invalid probe rows can never match each other. The bounded-probe
    # hash table is O(rounds * (b+n)) elementwise vs the multi-plane
    # stable sort's O((b+n) log(b+n)) — at 10M-row joins that sort was
    # the kernel's hot spot. Distinct keys <= b+n by construction, so a
    # reported overflow can only mean probe exhaustion (pathological
    # clustering); lax.cond falls back to the exact sort path then.
    # On the TPU's routes, up to ``JOIN_SORT_IDS_MAX_ROWS``, the sort
    # alone: the table's rounds end when the data lets them, so the
    # kernel's time moved from seed to seed (``ops/routes.py``).
    cat_keys = [jnp.concatenate([bk, pk]) for bk, pk in zip(build_keys, probe_keys)]
    cat_valid = jnp.concatenate([build_valid, probe_valid])
    if (routes.routes_platform() == "tpu"
            and b + n <= routes.JOIN_SORT_IDS_MAX_ROWS):
        ids = dense_group_ids(cat_keys, cat_valid, b + n)[0]
    else:
        ids_h, _, _, ng_h = dense_group_ids_hash(cat_keys, cat_valid, b + n)
        ids = jax.lax.cond(
            ng_h > b + n,
            lambda: dense_group_ids(cat_keys, cat_valid, b + n)[0],
            lambda: ids_h,
        )
    kb = jnp.where(build_valid, ids[:b], b + n)
    kp = jnp.where(probe_valid, ids[b:], b + n + 1)

    # 2. Sort build by key id; per-probe match ranges. The ids are dense
    # (0 .. b+n+1), so a probe row's range in the sorted build needs no
    # search: the build rows of each id are counted (a scatter of b
    # rows), the rows before an id are the counts' exclusive prefix, and
    # a probe row reads both at its id. (Two binary searches were 2 x
    # log2(b) full-length gathers at addresses that follow the data:
    # most of the kernel at 2^20 probe rows, and its time moved with the
    # seed; PERF.md section 6, PR 39.)
    perm = jnp.argsort(kb, stable=True).astype(jnp.int32)  # invalid last
    per_id = jnp.zeros(b + n + 2, dtype=jnp.int32).at[kb].add(1)
    before, _ = _exclusive_cumsum(per_id)
    lo = before[kp]
    m = per_id[kp]  # matches per probe row (0 for invalid probe rows)

    # 3. Expansion: emitted rows per probe row.
    pad_unmatched = how in ("left", "outer")
    e = jnp.maximum(m, 1) if pad_unmatched else m
    e = jnp.where(probe_valid, e, 0).astype(jnp.int32)
    start, total_pairs = _exclusive_cumsum(e)

    slot_of = jnp.where((e > 0) & (start < c), start, c)
    owner1 = _owners(slot_of, (e > 0).astype(jnp.int32), n, c)

    # A slot past the pairs has no owner to read; it reads the row of its
    # own number. Left to the scan it inherits the LAST owner, so every
    # idle slot of the output (more than half of it at the estimate's
    # head-room) gathered one address: 46 ms or 62 for the same 2^21-slot
    # gather, by where the seed's last row fell (PERF.md section 6, PR 39).
    j = jnp.arange(c, dtype=jnp.int32)
    pair_valid = (j < total_pairs) & (owner1 > 0)
    probe_idx = jnp.where(pair_valid, owner1 - 1, j % n)
    t = j - start[probe_idx]
    is_match = t < m[probe_idx]
    build_idx = perm[
        jnp.clip(lo[probe_idx] + jnp.minimum(t, m[probe_idx] - 1), 0, b - 1)
    ]

    probe_take = pair_valid
    build_take = pair_valid & is_match
    out_valid = pair_valid
    overflow = total_pairs > c

    if how in ("right", "outer"):
        # Build rows whose key matches no probe row emit once with a null
        # left side, appended after the pair region.
        skp = jnp.sort(kp)
        lo_b = jnp.searchsorted(skp, kb, side="left")
        hi_b = jnp.searchsorted(skp, kb, side="right")
        unmatched = build_valid & ((hi_b - lo_b) == 0)
        su, n_extra = _exclusive_cumsum(unmatched.astype(jnp.int32))
        extra_slot = jnp.where(
            unmatched & (total_pairs + su < c), total_pairs + su, c
        )
        extra_owner = _owners(extra_slot, unmatched.astype(jnp.int32), b, c)
        # The extras region starts at total_pairs; inside it the pair
        # machinery's owner is stale, so extras override.
        in_extras = (j >= total_pairs) & (extra_owner > 0)
        build_idx = jnp.where(in_extras, jnp.maximum(extra_owner - 1, 0), build_idx)
        build_take = jnp.where(in_extras, True, build_take)
        probe_take = probe_take & ~in_extras
        out_valid = out_valid | (in_extras & (j < total_pairs + n_extra))
        overflow = overflow | (total_pairs + n_extra > c)

    return probe_idx, probe_take, build_idx, build_take, out_valid, overflow
