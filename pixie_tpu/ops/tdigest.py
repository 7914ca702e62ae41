"""Batched t-digest quantile sketch over [num_groups, K] centroid arrays.

Reference parity: ``src/carnot/funcs/builtins/math_sketches.h:34``
(QuantilesUDA wrapping the sequential-insertion tdigest library).

TPU-first redesign: sequential insertion is hostile to XLA, and even
whole-batch sorting is the wrong primitive on both XLA backends (TPU sort
programs compile slowly and run sort-bound; XLA CPU sort is ~90x slower
than its scatter). Each batch is instead **histogram-binned by value**:
the f32 value's IEEE-754 bit pattern is made order-monotone (standard
sign-flip transform) and its top bits index one of B log-spaced bins per
group — a pure scatter-add, no sort, no data-dependent control flow. Bin
(weight, weighted-mean) pairs are already value-ordered, so re-binning the
histogram through the t-digest k1 scale function k(q) = asin(2q-1) down to
K centroids is cumsum + segment-sum only. Merging two digests (the
partial-agg path across devices) concatenates centroid sets and
re-compresses with one tiny [G, 2K] sort. Everything is static-shape.

The carry is (means f32[G,K], weights f32[G,K]) — a pytree, trivially
shippable through shard_map/psum-style collectives.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import routes

DEFAULT_K = 128
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


def batch_to_digest(values, group_ids, mask, num_groups: int, k: int = DEFAULT_K):
    """Build a [G, K] digest from one batch of (value, group) rows.

    Sort-free: values land in B log-spaced histogram bins per group via
    their order-monotone f32 bit pattern (one scatter-add), and the
    value-ordered histogram is k1-rebinned to K centroids with
    cumsum + segment-sum (``_compress(ordered=True)``).
    """
    values = values.astype(jnp.float32)
    # The sketch is defined over FINITE values on both fold paths: a NaN
    # would poison the Pallas contraction across all bins, and ±inf has
    # no meaningful quantile position either way.
    mask = mask & jnp.isfinite(values)
    gids = jnp.where(mask, group_ids.astype(jnp.int32), num_groups)
    b = _hist_bins(num_groups)
    shift = jnp.uint32(32 - b.bit_length() + 1)  # top log2(B) bits

    vb = jax.lax.bitcast_convert_type(values, jnp.uint32)
    vb = jnp.where(values < 0, ~vb, vb | jnp.uint32(0x80000000))
    bins = (vb >> shift).astype(jnp.int32)

    n_slots = num_groups * b
    n = values.shape[0]
    chunk = None
    if (
        routes.routes_platform() == "tpu"
        and n_slots <= routes.HIST_FOLD_MAX_SLOTS
        and n >= 128
    ):
        # Imported only here: pulling in Pallas costs seconds, which a
        # process that never runs the kernel must not pay mid-query.
        from .pallas_groupby import row_chunk

        chunk = row_chunk(n, 2048)  # None: no block the tiling accepts
    if chunk is not None:
        # Pallas kernel: both histograms in one VMEM-resident sweep
        # (pallas_tdigest.py); trash rows get an id past the kernel's
        # padded slot range so they match no tile column.
        from .pallas_tdigest import hist_fold, _TILE

        pad = -(-n_slots // _TILE) * _TILE
        flat = jnp.where(mask & (gids < num_groups), gids * b + bins, pad)
        w_f, mw_f = hist_fold(
            flat, jnp.where(mask, values, 0.0), n_slots, chunk=chunk,
            interpret=routes.kernels_interpreted(),
        )
        w = w_f.reshape(num_groups, b)
        mw = mw_f.reshape(num_groups, b)
    else:
        flat = jnp.where(
            mask & (gids < num_groups), gids * b + bins, n_slots
        )
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
