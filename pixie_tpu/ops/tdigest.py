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
  histogram and ``_compress(ordered=True)`` re-bins it with cumsum +
  segment-sum.

Same B, same bin of a value, same ``qmid``, same K: the routes agree up
to f32 summation order. Merging two digests (the partial-agg path across
windows and devices) concatenates centroid sets and re-compresses with
one tiny [G, 2K] sort. Everything is static-shape.

The carry is (means f32[G,K], weights f32[G,K]) — a pytree, trivially
shippable through shard_map/psum-style collectives.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import routes
from .scan import _CHUNK, blocked_cummax

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


def _compress(means, weights, k: int, ordered: bool = False):
    """Re-bin [G, M] centroids to [G, k] by cumulative-weight position.

    ``ordered=True`` asserts the centroids are already ascending by mean
    within each group (histogram bins are, by construction) and skips the
    sort — empty (w==0) slots may then be interleaved; they carry no
    weight, land in the trash segment, and don't perturb ``cumw``.
    """
    g, m = means.shape
    if ordered:
        means_s, weights_s = means, weights
    else:
        # Sort centroids by mean within each group; empty slots last.
        sort_key = jnp.where(weights > 0, means, _BIG)
        order = jnp.argsort(sort_key, axis=-1, stable=True)
        means_s = jnp.take_along_axis(means, order, axis=-1)
        weights_s = jnp.take_along_axis(weights, order, axis=-1)

    total = jnp.sum(weights_s, axis=-1, keepdims=True)
    cumw = jnp.cumsum(weights_s, axis=-1)
    qmid = jnp.where(total > 0, (cumw - weights_s * 0.5) / total, 0.0)
    bins = jnp.clip(jnp.floor(_knorm(qmid) * k).astype(jnp.int32), 0, k - 1)

    gid = jnp.broadcast_to(jnp.arange(g, dtype=jnp.int32)[:, None], (g, m))
    flat = jnp.where(weights_s > 0, gid * k + bins, g * k).reshape(-1)
    w_flat = weights_s.reshape(-1)
    mw_flat = (means_s * weights_s).reshape(-1)

    new_w = jax.ops.segment_sum(w_flat, flat, num_segments=g * k + 1)[:-1]
    new_mw = jax.ops.segment_sum(mw_flat, flat, num_segments=g * k + 1)[:-1]
    new_w = new_w.reshape(g, k)
    new_means = jnp.where(new_w > 0, new_mw.reshape(g, k) / jnp.maximum(new_w, 1e-30), 0.0)
    return new_means, new_w


def digest_merge(a, b):
    """Associative merge of two [G, K] digests (cross-device finalize path)."""
    means = jnp.concatenate([a[0], b[0]], axis=-1)
    weights = jnp.concatenate([a[1], b[1]], axis=-1)
    return _compress(means, weights, a[0].shape[-1])


def _hist_bins(num_groups: int) -> int:
    """Histogram width B: as fine as a [G, B] f32 scratch budget allows.

    B=8192 gives positive values 4 mantissa bits of resolution (bins are
    ~4.4% wide in value; the within-bin weighted mean recovers most of
    that). Large-G aggregates shrink B toward a floor of K=128 so G*B
    stays near 2^25 slots — past G=2^18 the scratch tracks the [G, K]
    digest carry's own footprint (2 arrays of the same shape), which is
    the dominant allocation at that scale with or without the histogram.
    """
    b = 8192
    while b > DEFAULT_K and num_groups * b > (1 << 25):
        b //= 2
    return b


def _row_bins(values, group_ids, mask, num_groups: int):
    """(f32 values, finite-row mask, group ids, histogram bins, B) of a
    batch's rows: a value's bin is the top log2(B) bits of its order-monotone f32
    bit pattern (standard sign-flip transform), B log-spaced bins a group."""
    values = values.astype(jnp.float32)
    # The sketch is defined over FINITE values on every route: a NaN
    # would poison a sum, and +-inf has no meaningful quantile position.
    mask = mask & jnp.isfinite(values)
    gids = jnp.where(mask, group_ids.astype(jnp.int32), num_groups)
    b = _hist_bins(num_groups)
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
    segment-sum (``_compress(ordered=True)``). Same bins, same ``qmid``,
    same centroids: the two agree up to f32 summation order.
    """
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
    return _compress(means, w, k, ordered=True)


def digest_update(carry, group_ids, mask, values, *, num_groups: int | None = None):
    """UDA update: fold a batch into the digest carry."""
    g, k = carry[0].shape
    fresh = batch_to_digest(values, group_ids, mask, g if num_groups is None else num_groups, k)
    return digest_merge(carry, fresh)


def digest_quantile(carry, qs):
    """Estimate quantiles per group: [G, len(qs)] (NaN for empty groups).

    Linear interpolation of centroid means over cumulative-weight midpoints
    (the standard t-digest estimator).
    """
    means, weights = carry
    qs_arr = jnp.asarray(qs, dtype=jnp.float32)

    sort_key = jnp.where(weights > 0, means, _BIG)
    order = jnp.argsort(sort_key, axis=-1, stable=True)
    means_s = jnp.take_along_axis(means, order, axis=-1)
    weights_s = jnp.take_along_axis(weights, order, axis=-1)

    total = jnp.sum(weights_s, axis=-1)
    cumw = jnp.cumsum(weights_s, axis=-1)
    cmid = cumw - weights_s * 0.5

    # Fill empty (w==0, sorted to the end) slots so interp clamps to the
    # last real centroid instead of walking into garbage.
    filled_mean = jax.lax.cummax(jnp.where(weights_s > 0, means_s, -_BIG), axis=1)
    filled_cmid = jnp.where(weights_s > 0, cmid, total[:, None])

    def one_group(m, c, t):
        return jnp.interp(qs_arr * t, c, m)

    out = jax.vmap(one_group)(filled_mean, filled_cmid, total)
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
    # G * B <= 2^25 (``_hist_bins``): the sentinel sorts after every slot.
    key = jnp.where(live, (gids * b + bins).astype(u32), u32(0xFFFFFFFF))
    payload = jax.lax.bitcast_convert_type(jnp.where(live, values, 0.0), u32)
    key, payload = jax.lax.sort((key, payload), num_keys=1, is_stable=False)

    iota = jnp.arange(key.shape[0], dtype=jnp.int32)
    gid = (key >> u32(b.bit_length() - 1)).astype(jnp.int32)  # B is 2^m
    first, last = _span_bounds(key[1:] != key[:-1], iota)
    g_first, g_last = _span_bounds(gid[1:] != gid[:-1], iota)
    # ``_compress(ordered=True)`` a row: cumw, w and total are integers.
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
