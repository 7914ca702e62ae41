"""ML exec primitives: kmeans, mergeable uniform samples (coresets).

Reference parity: ``src/carnot/exec/ml/`` — Eigen kmeans (``kmeans.h:32``)
with kmeans++ init, streaming coresets (``coreset.h``), sampling
(``sampling.h``), consumed by ``funcs/builtins/ml_ops.h`` (KMeansUDA
:88, ReservoirSampleUDA :145).

TPU-first redesign: the reference's coreset tree is a pointer-chasing
stream structure; here the mergeable uniform sample is a **bottom-k
priority sketch** — every row draws a deterministic pseudo-random
priority (a hash of its value bits and window position) and each group
keeps the k lowest-priority rows. Bottom-k unions are associative, so
the same sketch serves window folds, cross-device ``psum``-style merges,
and agent-mode bridge payloads. K-means then runs on the per-group
sample entirely on device (vectorized Lloyd over [G, C] samples).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .scan import blocked_cummax

# numpy, not jnp: no concrete jax scalar as a jit constant.
_EMPTY = np.float32(np.inf)  # priority of an empty reservoir slot


def row_priorities(values, salt: int = 0x9E3779B9):
    """Deterministic pseudo-random priority per row in [0, 1).

    splitmix-style integer hash of the value bits xor'd with the row's
    window position. Rows at the same position with the same value in
    different windows collide; for sampling telemetry streams the bias
    is negligible (documented, matches the determinism constraint of
    compiled code — no RNG state threading).
    """
    values = jnp.asarray(values)
    if jnp.issubdtype(values.dtype, jnp.integer):
        u = values.astype(jnp.uint64)
        bits = (u ^ (u >> 32)).astype(jnp.uint32)
    else:
        bits = jax.lax.bitcast_convert_type(
            values.astype(jnp.float32), jnp.uint32
        )
    idx = jnp.arange(bits.shape[-1], dtype=jnp.uint32)
    x = bits ^ (idx * jnp.uint32(0x85EBCA6B)) ^ jnp.uint32(salt)
    x = (x ^ (x >> 16)) * jnp.uint32(0x7FEB352D)
    x = (x ^ (x >> 15)) * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    return x.astype(jnp.float32) / jnp.float32(2**32)


# -- bottom-k reservoir (per-group, mergeable) -------------------------------
def reservoir_init(num_groups: int, capacity: int, dtype=jnp.float32):
    """``dtype`` is the sampled values' dtype — int64 samples stay int64
    (a sample must be an element of the data, bit-exactly)."""
    return (
        jnp.zeros((num_groups, capacity), dtype=dtype),  # values
        jnp.full((num_groups, capacity), _EMPTY),  # priorities
        jnp.zeros((num_groups,), dtype=jnp.float32),  # row counts
    )


def _batch_to_reservoir(values, prio, group_ids, mask, num_groups, capacity, dtype):
    """Scatter a window's rows into a fresh [G, C] bottom-k reservoir."""
    n = values.shape[-1]
    g, c = num_groups, capacity
    gid = jnp.where(mask, group_ids, g)
    # Lexsort (gid, prio): stable argsort of gid after argsort of prio.
    order1 = jnp.argsort(jnp.where(mask, prio, _EMPTY), stable=True)
    order2 = jnp.argsort(gid[order1], stable=True)
    order = order1[order2]
    gs = gid[order]
    vs = jnp.asarray(values, dtype)[order]
    ps = jnp.where(mask, prio, _EMPTY)[order]
    pos = jnp.arange(n)
    is_first = jnp.concatenate([jnp.ones(1, bool), gs[1:] != gs[:-1]])
    seg_start = blocked_cummax(jnp.where(is_first, pos, 0))
    rank = pos - seg_start
    slot = jnp.where((gs < g) & (rank < c), gs * c + rank, g * c)
    out_v = jnp.zeros(g * c + 1, dtype).at[slot].set(vs, mode="drop")
    out_p = jnp.full(g * c + 1, _EMPTY).at[slot].set(ps, mode="drop")
    counts = jax.ops.segment_sum(
        jnp.where(mask, 1.0, 0.0), gid, num_segments=g + 1
    )[:-1]
    return (
        out_v[:-1].reshape(g, c),
        out_p[:-1].reshape(g, c),
        counts.astype(jnp.float32),
    )


def reservoir_merge(a, b):
    """Associative bottom-k union of two reservoirs."""
    va, pa, ca = a
    vb, pb, cb = b
    v = jnp.concatenate([va, vb], axis=-1)
    p = jnp.concatenate([pa, pb], axis=-1)
    c = va.shape[-1]
    neg_top, idx = jax.lax.top_k(-p, c)  # lowest priorities win
    return (
        jnp.take_along_axis(v, idx, axis=-1),
        -neg_top,
        ca + cb,
    )


def reservoir_update(carry, group_ids, mask, values):
    g, c = carry[0].shape
    fresh = _batch_to_reservoir(
        values, row_priorities(values), group_ids, mask, g, c, carry[0].dtype
    )
    return reservoir_merge(carry, fresh)


# -- 1-D weighted k-means over per-group samples -----------------------------
def kmeans_groups(samples, sample_mask, k_max: int, k, iters: int = 16):
    """Lloyd iterations per group on [G, C] sample values.

    ``k`` is a [G] (or scalar) runtime cluster count <= k_max; centroids
    beyond k come back NaN. Init = evenly-spaced sample quantiles (the
    1-D stand-in for kmeans++: spread over the value range).
    """
    g, c = samples.shape
    k_arr = jnp.broadcast_to(jnp.asarray(k, jnp.int32), (g,))
    big = jnp.float32(3.4e38)
    s_sorted = jnp.sort(jnp.where(sample_mask, samples, big), axis=-1)
    n_valid = jnp.sum(sample_mask, axis=-1)
    # Initial centroids: quantile positions j/(k) over the valid prefix.
    j = jnp.arange(k_max, dtype=jnp.float32)
    pos = jnp.clip(
        ((j[None, :] + 0.5) / jnp.maximum(k_arr[:, None], 1))
        * jnp.maximum(n_valid[:, None] - 1, 0),
        0,
        c - 1,
    ).astype(jnp.int32)
    cent = jnp.take_along_axis(s_sorted, pos, axis=-1)  # [G, k_max]
    kmask = j[None, :] < k_arr[:, None]

    def body(_, cent):
        d = jnp.abs(samples[:, :, None] - cent[:, None, :])  # [G, C, K]
        d = jnp.where(kmask[:, None, :], d, big)
        assign = jnp.argmin(d, axis=-1)  # [G, C]
        onehot = (
            jax.nn.one_hot(assign, k_max, dtype=jnp.float32)
            * sample_mask[:, :, None]
        )
        wsum = jnp.sum(onehot, axis=1)  # [G, K]
        vsum = jnp.sum(onehot * samples[:, :, None], axis=1)
        return jnp.where(wsum > 0, vsum / jnp.maximum(wsum, 1e-30), cent)

    cent = jax.lax.fori_loop(0, iters, body, cent)
    cent = jnp.sort(jnp.where(kmask, cent, jnp.nan), axis=-1)
    return jnp.where(kmask & (n_valid[:, None] > 0), cent, jnp.nan)


# -- standalone multi-dim kmeans (library API, kmeans.h parity) --------------
@partial(jax.jit, static_argnames=("k", "iters"))
def kmeans_fit(points, k: int, iters: int = 32, weights=None):
    """Weighted Lloyd k-means on [N, D] points; returns [k, D] centroids.

    kmeans++-style init: greedy farthest-point seeding from the weighted
    mean (deterministic — compiled code can't thread RNG state).
    """
    n, d = points.shape
    w = jnp.ones(n) if weights is None else jnp.asarray(weights, jnp.float32)

    def seed_body(i, cent):
        d2 = jnp.min(
            jnp.sum((points[:, None, :] - cent[None, :, :]) ** 2, axis=-1)
            + jnp.where(jnp.arange(k)[None, :] < i, 0.0, 3.4e38),
            axis=-1,
        )
        nxt = points[jnp.argmax(d2 * w)]
        return cent.at[i].set(nxt)

    mean0 = jnp.sum(points * w[:, None], axis=0) / jnp.sum(w)
    cent = jnp.zeros((k, d)).at[0].set(mean0)
    cent = jax.lax.fori_loop(1, k, seed_body, cent)

    def lloyd(_, cent):
        d2 = jnp.sum((points[:, None, :] - cent[None, :, :]) ** 2, axis=-1)
        assign = jnp.argmin(d2, axis=-1)
        onehot = jax.nn.one_hot(assign, k, dtype=jnp.float32) * w[:, None]
        wsum = jnp.sum(onehot, axis=0)
        vsum = onehot.T @ points
        return jnp.where(wsum[:, None] > 0, vsum / jnp.maximum(wsum[:, None], 1e-30), cent)

    return jax.lax.fori_loop(0, iters, lloyd, cent)
