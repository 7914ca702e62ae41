"""Batched t-digest quantile sketch over [num_groups, K] centroid arrays.

Reference parity: ``src/carnot/funcs/builtins/math_sketches.h:34``
(QuantilesUDA wrapping the sequential-insertion tdigest library).

TPU-first redesign: sequential insertion is hostile to XLA. Each batch is
instead **binned by value**: the f32 value's IEEE-754 bit pattern is made
order-monotone (standard sign-flip transform) and its top bits index one
of B log-spaced bins per group, no data-dependent control flow. Bins are
value-ordered, so re-binning them through the t-digest k1 scale function
k(q) = asin(2q-1) down to K centroids needs only each bin's weight and
the weight before it. How a row reaches its bin is the platform's route
(``ops/routes.py`` ``digest_route``), the fold's own rule, "rows sort on
the TPU, hash and scatter on the CPU":

- on the TPU the rows ride ONE payload-carrying sort by (group, bin);
  a bin is a run of equal keys whose weight and place are read from row
  positions, every row computes its centroid elementwise, and the digest
  is a reduction of sorted ids into G x K slots (a Pallas kernel,
  ``pallas_tdigest.sorted_centroid_fold``). The [G, B] histogram is never
  built. On the v5e a two-operand 2^21-row ``lax.sort`` is 3.55 ms where
  a row scatter is 10.5 (PERF.md section 6, PR 29 and PR 33);
- on the CPU (XLA's CPU sort is ~90x slower than its scatter), and above
  the sorted route's slot limit, the rows scatter-add into the [G, B]
  histogram and ``_compress`` re-bins it with cumsum +
  segment-sum.

Same B, same bin of a value, same ``qmid``, same K: the routes agree up
to f32 summation order. A KEYED group-by on the TPU's routes (no dense
slot, ``exec/fragment.py`` ``_sorted_fold``) bins nothing: its rows sort
by (packed key, value) beside the integer fold's own sort, a group's
rows come out in exact value order and ``qmid`` is read from a row's
rank (``ordered_batch_to_digest``), whatever the number of groups.

**A digest is ordered**: its centroids of weight ascend in mean with
their slot (a centroid's slot is its k1 bin), empty slots lie anywhere
between. Every producer keeps that (``_compress`` fills slots by
cumulative position), so neither the merge of two digests (the
partial-agg path across windows and devices) nor the read-out sorts
anything: ``merge_ordered`` places each side's centroids in the other's
cumulative weight by comparing, [G, K, K] compares reduced in place, and
``digest_quantile`` reads the slots as they lie. (Until PR 41 both
sorted [G, 2K] and [G, K] row by row: 162 ms a merge at G = 8,192, my
chip run, PR 33, linear in G.) Everything is static-shape.

The carry is (means f32[G,K], weights f32[G,K]) — a pytree, trivially
shippable through shard_map/psum-style collectives.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import routes
from .groupby import sorted_lead_runs
from .scan import _CHUNK, blocked_cummax, blocked_cumsum

DEFAULT_K = routes.DIGEST_K
_BIG = jnp.inf


def _knorm(q):
    """t-digest k1 scale normalized to [0, 1): concentrates bins at tails."""
    q = jnp.clip(q, 0.0, 1.0)
    return (jnp.arcsin(2.0 * q - 1.0) / jnp.pi) + 0.5


def digest_init(num_groups: int, k: int = DEFAULT_K):
    return (
        jnp.zeros((num_groups, k), dtype=jnp.float32),
        jnp.zeros((num_groups, k), dtype=jnp.float32),
    )


def _compress(means, weights, k: int):
    """Re-bin [G, M] VALUE-ORDERED centroids to [G, k] by cumulative-weight
    position (a histogram's bins are ordered by construction; empty
    (w==0) slots may be interleaved: they carry no weight, land in the
    trash segment and don't perturb ``cumw``). The result is ordered."""
    g, m = means.shape
    total = jnp.sum(weights, axis=-1, keepdims=True)
    cumw = jnp.cumsum(weights, axis=-1)
    qmid = jnp.where(total > 0, (cumw - weights * 0.5) / total, 0.0)
    bins = jnp.clip(jnp.floor(_knorm(qmid) * k).astype(jnp.int32), 0, k - 1)

    gid = jnp.broadcast_to(jnp.arange(g, dtype=jnp.int32)[:, None], (g, m))
    flat = jnp.where(weights > 0, gid * k + bins, g * k).reshape(-1)
    w_flat = weights.reshape(-1)
    mw_flat = (means * weights).reshape(-1)

    new_w = jax.ops.segment_sum(w_flat, flat, num_segments=g * k + 1)[:-1]
    new_mw = jax.ops.segment_sum(mw_flat, flat, num_segments=g * k + 1)[:-1]
    new_w = new_w.reshape(g, k)
    new_means = jnp.where(new_w > 0, new_mw.reshape(g, k) / jnp.maximum(new_w, 1e-30), 0.0)
    return new_means, new_w


def _placed_weight(m_at, m_of, w_of, strict: bool):
    """[G, K]: for each centroid mean ``m_at[g, i]`` the weight of the
    centroids of ``(m_of, w_of)`` that lie under it (``strict``) or at
    and under it. A [G, K, K] compare reduced over the other side's
    slots, which sit on the second-minor axis so that the reduction adds
    whole vector registers; nothing of that size is stored."""
    if strict:
        under = m_of[:, :, None] < m_at[:, None, :]
    else:
        under = m_of[:, :, None] <= m_at[:, None, :]
    return jnp.sum(jnp.where(under, w_of[:, :, None], 0.0), axis=1)


def _rebinned(cbin, w, mw, k: int):
    """[G, k] sums of ``w`` and ``mw`` by k1 bin ``cbin`` ([G, M] each):
    a one-hot compare reduced over the M source slots."""
    hit = cbin[:, :, None] == jnp.arange(k, dtype=jnp.int32)[None, None, :]
    return (jnp.sum(jnp.where(hit, w[:, :, None], 0.0), axis=1),
            jnp.sum(jnp.where(hit, mw[:, :, None], 0.0), axis=1))


def merge_ordered(a, b):
    """``_compress`` of the union of two ORDERED [G, K] digests with no
    sort: a centroid's cumulative position in the union is its own
    side's weight before it plus the other side's weight under it (ties
    go to ``a``, as the stable sort of the concatenation had them), its
    k1 bin follows elementwise, and a bin's weight and mean are one-hot
    sums over both sides. The result is ordered."""
    (ma, wa), (mb, wb) = a, b
    k = ma.shape[-1]
    total = jnp.sum(wa, axis=-1, keepdims=True) + jnp.sum(
        wb, axis=-1, keepdims=True)

    def cbin(before, w):
        qmid = jnp.where(total > 0, (before + w * 0.5) / total, 0.0)
        return jnp.clip(jnp.floor(_knorm(qmid) * k).astype(jnp.int32), 0, k - 1)

    bin_a = cbin(jnp.cumsum(wa, axis=-1) - wa
                 + _placed_weight(ma, mb, wb, strict=True), wa)
    bin_b = cbin(jnp.cumsum(wb, axis=-1) - wb
                 + _placed_weight(mb, ma, wa, strict=False), wb)
    w_a, mw_a = _rebinned(bin_a, wa, ma * wa, k)
    w_b, mw_b = _rebinned(bin_b, wb, mb * wb, k)
    new_w = w_a + w_b
    new_means = jnp.where(
        new_w > 0, (mw_a + mw_b) / jnp.maximum(new_w, 1e-30), 0.0)
    return new_means, new_w


def digest_merge(a, b):
    """Associative merge of two [G, K] digests (windows, devices, agents).

    Where one side holds no weight at all (a fold's first window meets
    the empty state) the other passes through as it is: compressed
    already, and one of a fold's merges in two or three."""
    return jax.lax.cond(
        jnp.any(a[1] > 0) & jnp.any(b[1] > 0),
        merge_ordered,
        lambda a, b: (a[0] + b[0], a[1] + b[1]),
        a, b,
    )


def _row_bins(values, group_ids, mask, num_groups: int):
    """(f32 values, finite-row mask, group ids, histogram bins, B) of a
    batch's rows: a value's bin is the top log2(B) bits of its order-monotone f32
    bit pattern (standard sign-flip transform), B log-spaced bins a group."""
    values = values.astype(jnp.float32)
    # The sketch is defined over FINITE values on every route: a NaN
    # would poison a sum, and +-inf has no meaningful quantile position.
    mask = mask & jnp.isfinite(values)
    gids = jnp.where(mask, group_ids.astype(jnp.int32), num_groups)
    b = routes.digest_hist_bins(num_groups)
    shift = jnp.uint32(32 - b.bit_length() + 1)  # top log2(B) bits
    vb = jax.lax.bitcast_convert_type(values, jnp.uint32)
    vb = jnp.where(values < 0, ~vb, vb | jnp.uint32(0x80000000))
    return values, mask, gids, (vb >> shift).astype(jnp.int32), b


def batch_to_digest(values, group_ids, mask, num_groups: int, k: int = DEFAULT_K):
    """Build a [G, K] digest from one batch of (value, group) rows.

    The platform's route (``routes.digest_route``): on the TPU the rows
    sort by (group, bin) and reach their centroids from their positions
    (``_sorted_batch_to_digest``); on the CPU, and above the sorted
    route's slot limit, they scatter-add into the [G, B] histogram, whose
    value-ordered bins are k1-rebinned to K centroids with cumsum +
    segment-sum (``_compress``). Same bins, same ``qmid``, same
    centroids: the two agree up to f32 summation order. Past the group
    counts a histogram is built at (``routes.digest_hist_bins``) the
    rows sort by (group, value) on every platform and nothing is binned
    (``ordered_batch_to_digest``).
    """
    if not routes.digest_hist_bins(num_groups):
        # Too many groups for a histogram of a width worth having.
        live = mask & (group_ids >= 0) & (group_ids < num_groups)
        lead = jnp.where(live, group_ids.astype(jnp.uint32),
                         jnp.uint32(0xFFFFFFFF))
        return ordered_batch_to_digest([lead], True, values, num_groups, k,
                                       ranked=False)
    values, mask, gids, bins, b = _row_bins(values, group_ids, mask, num_groups)
    route = routes.digest_route(routes.routes_platform(), num_groups * k)
    if route == "sorted_digest":
        return _sorted_batch_to_digest(values, gids, bins, mask, num_groups, b, k)
    n_slots = num_groups * b
    flat = jnp.where(mask & (gids < num_groups), gids * b + bins, n_slots)
    w = jax.ops.segment_sum(
        mask.astype(jnp.float32), flat, num_segments=n_slots + 1
    )[:-1].reshape(num_groups, b)
    mw = jax.ops.segment_sum(
        jnp.where(mask, values, 0.0), flat, num_segments=n_slots + 1
    )[:-1].reshape(num_groups, b)
    means = jnp.where(w > 0, mw / jnp.maximum(w, 1e-30), 0.0)
    return _compress(means, w, k)


def digest_update(carry, group_ids, mask, values, *, num_groups: int | None = None):
    """UDA update: fold a batch into the digest carry."""
    g, k = carry[0].shape
    fresh = batch_to_digest(values, group_ids, mask, g if num_groups is None else num_groups, k)
    return digest_merge(carry, fresh)


def digest_quantile(carry, qs):
    """Estimate quantiles per group: [G, len(qs)] (NaN for empty groups).

    Linear interpolation of centroid means over cumulative-weight midpoints
    (the standard t-digest estimator), read off the ORDERED slots as they
    lie: an empty slot stands for the centroid before it (the first
    centroid, for those ahead of it), which leaves every interpolation
    between two neighbouring centroids what it was.
    """
    means, weights = carry
    k = means.shape[-1]
    live = weights > 0
    total = jnp.sum(weights, axis=-1)
    cumw = jnp.cumsum(weights, axis=-1)
    cmid = cumw - weights * 0.5
    least = jnp.min(jnp.where(live, means, _BIG), axis=-1, keepdims=True)
    xp = jnp.maximum(
        jax.lax.cummax(jnp.where(live, cmid, -_BIG), axis=1), 0.0)
    fp = jnp.maximum(
        jax.lax.cummax(jnp.where(live, means, -_BIG), axis=1), least)
    slot = jnp.arange(k, dtype=jnp.int32)[None, :]

    def pick(plane, at):
        return jnp.sum(jnp.where(slot == at, plane, 0.0), axis=-1)

    out = []
    for q in qs:
        # ``jnp.interp(q * total, xp, fp)`` a group, by counting.
        x = (jnp.float32(q) * total)[:, None]
        hi = jnp.clip(jnp.sum((xp <= x).astype(jnp.int32), axis=-1,
                              keepdims=True), 1, k - 1)
        x0, x1 = pick(xp, hi - 1), pick(xp, hi)
        f0, f1 = pick(fp, hi - 1), pick(fp, hi)
        dx = x1 - x0
        x = x[:, 0]
        f = jnp.where(dx == 0, f1,
                      f0 + (x - x0) / jnp.where(dx == 0, 1.0, dx) * (f1 - f0))
        f = jnp.where(x < xp[:, 0], fp[:, 0], f)
        out.append(jnp.where(x > xp[:, -1], fp[:, -1], f))
    out = jnp.stack(out, axis=-1)
    return jnp.where(total[:, None] > 0, out, jnp.nan)


def _span_bounds(differs, iota):
    """(first, last) row index of the span each row lies in, where
    ``differs[i]`` says row i + 1 opens a new span: a forward and a
    backward running maximum of flagged positions."""
    n = iota.shape[0]
    one = jnp.ones(1, dtype=jnp.bool_)
    opens = jnp.concatenate([one, differs])
    closes = jnp.concatenate([differs, one])
    # Always the blocked scan past one chunk: at 2^21 rows the flat i32
    # reduce-window takes the TPU's compiler 34 s and the chip 1.42 ms,
    # the blocked one 0.4 s and 0.89 ms (my chip run, PR 33).
    force = n > _CHUNK
    first = blocked_cummax(jnp.where(opens, iota, 0), force=force)
    # The last row of a span, from the far end: the same scan on the
    # reversed rows (position n - 1 - i counted from the back).
    back = blocked_cummax(jnp.where(closes[::-1], iota, 0), force=force)
    return first, (n - 1) - back[::-1]


def _sorted_batch_to_digest(values, gids, bins, mask, num_groups: int, b: int,
                            k: int):
    """``batch_to_digest`` on the TPU's routes: the rows reach their
    centroids by ONE payload-carrying sort, and the [G, B] histogram is
    never built.

    Sorted by ``slot = gid * B + bin`` (the value rides as payload), a
    bin is a run of equal keys: its weight is the run's length and the
    weight before it is the run's first index less its group's, both
    read from POSITIONS. So every row knows its bin's ``qmid`` and
    centroid, elementwise and by ``_compress``'s own arithmetic, and the
    centroid ids are sorted: what is left is a reduction of sorted ids
    into G x K slots (``pallas_tdigest.sorted_centroid_fold``). On the
    v5e a two-operand sort of 2^21 rows is a sixth of the two row
    scatters it replaces (``tools/fold_sweep.py --digest``).
    """
    # Imported only here: pulling in Pallas costs seconds, which a
    # process that never runs the kernel must not pay mid-query.
    from .pallas_tdigest import sorted_centroid_fold

    u32 = jnp.uint32
    live = mask & (gids < num_groups)
    # G * B <= 2^25 (``routes.digest_hist_bins``): the sentinel sorts after every slot.
    key = jnp.where(live, (gids * b + bins).astype(u32), u32(0xFFFFFFFF))
    payload = jax.lax.bitcast_convert_type(jnp.where(live, values, 0.0), u32)
    key, payload = jax.lax.sort((key, payload), num_keys=1, is_stable=False)

    iota = jnp.arange(key.shape[0], dtype=jnp.int32)
    gid = (key >> u32(b.bit_length() - 1)).astype(jnp.int32)  # B is 2^m
    first, last = _span_bounds(key[1:] != key[:-1], iota)
    g_first, g_last = _span_bounds(gid[1:] != gid[:-1], iota)
    # ``_compress`` a row: cumw, w and total are integers.
    w = (last - first + 1).astype(jnp.float32)
    cumw = (last + 1 - g_first).astype(jnp.float32)
    total = (g_last - g_first + 1).astype(jnp.float32)
    qmid = (cumw - w * 0.5) / total
    cbin = jnp.clip(jnp.floor(_knorm(qmid) * k).astype(jnp.int32), 0, k - 1)
    n_slots = num_groups * k
    # The sentinel's rows sorted last: the reduction drops an id >= n_slots.
    ids = jnp.where(key != u32(0xFFFFFFFF), gid * k + cbin, n_slots)
    new_w, new_mw = sorted_centroid_fold(
        ids, jax.lax.bitcast_convert_type(payload, jnp.float32), n_slots,
        interpret=routes.kernels_interpreted(),
    )
    new_w = new_w.reshape(num_groups, k)
    new_means = jnp.where(
        new_w > 0, new_mw.reshape(num_groups, k) / jnp.maximum(new_w, 1e-30), 0.0
    )
    return new_means, new_w


def ordered_batch_to_digest(lead, folded_flag: bool, values, num_groups: int,
                            k: int = DEFAULT_K, ranked: bool = True):
    """The [G, K] digest of one batch of rows whose group is told by the
    u32 words ``lead`` (``groupby.lead_words``: not-valid folded into the
    first as 0xFFFFFFFF where ``folded_flag``, else a flag word ahead of
    them). ``ranked``: a KEYED group-by's, group for group as
    ``ops/groupby.py`` ``sorted_group_fold`` numbers them: slot g is the
    g-th distinct key of the valid rows in the words' order. Not ranked:
    the one word IS the slot (group ids in hand).

    ONE ``lax.sort`` by (``lead``..., the value's order-monotone 32-bit
    pattern): a group's rows come out in exact value order, so nothing
    is binned and no width depends on G. A row's rank in its group and
    the group's size are read from positions, its k1 bin follows
    elementwise, and the centroids are sums over sorted ids. A row whose
    value is not finite keeps its group's place in the numbering and
    adds nothing (it sorts behind its group's finite rows). Groups past
    ``num_groups`` are dropped: the fold's overflow says so.
    """
    u32 = jnp.uint32
    values = values.astype(jnp.float32)
    n = values.shape[0]
    vb = jax.lax.bitcast_convert_type(values, u32)
    vb = jnp.where(values < 0, ~vb, vb | u32(0x80000000))
    # No finite value reads 0xFFFFFFFF (a NaN's pattern).
    vb = jnp.where(jnp.isfinite(values), vb, u32(0xFFFFFFFF))
    out = jax.lax.sort(list(lead) + [vb], dimension=0, is_stable=False,
                       num_keys=len(lead) + 1)
    s_lead, s_vb = out[:-1], out[-1]
    s_valid, differs = sorted_lead_runs(s_lead, folded_flag)
    iota = jnp.arange(n, dtype=jnp.int32)
    starts = jnp.concatenate([jnp.ones(1, jnp.bool_), differs]) & s_valid
    if ranked:
        gid = blocked_cumsum(starts.astype(jnp.int32), force=n > _CHUNK) - 1
    else:
        gid = jnp.where(s_valid, s_lead[0], u32(num_groups)).astype(jnp.int32)
    finite = s_valid & (s_vb != u32(0xFFFFFFFF))
    # A group's finite rows are a run of their own, ahead of the others.
    first, last = _span_bounds(
        differs | (finite[1:] != finite[:-1]), iota)
    size = (last - first + 1).astype(jnp.float32)
    qmid = ((iota - first).astype(jnp.float32) + 0.5) / size
    cbin = jnp.clip(jnp.floor(_knorm(qmid) * k).astype(jnp.int32), 0, k - 1)
    n_slots = num_groups * k
    ids = jnp.where(finite & (gid < num_groups), gid * k + cbin, n_slots)
    bits = jnp.where(s_vb >= u32(0x80000000), s_vb ^ u32(0x80000000), ~s_vb)
    v = jnp.where(finite, jax.lax.bitcast_convert_type(bits, jnp.float32), 0.0)
    # (The ids ascend but for the rows that are no number, which sit
    # between their group's and the next one's: no sorted promise.)
    new_w = jax.ops.segment_sum(
        finite.astype(jnp.float32), ids, num_segments=n_slots + 1
    )[:-1].reshape(num_groups, k)
    new_mw = jax.ops.segment_sum(
        v, ids, num_segments=n_slots + 1
    )[:-1].reshape(num_groups, k)
    new_means = jnp.where(new_w > 0, new_mw / jnp.maximum(new_w, 1e-30), 0.0)
    return new_means, new_w
