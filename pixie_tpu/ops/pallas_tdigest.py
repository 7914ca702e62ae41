"""Pallas TPU kernel: the sorted t-digest route's reduction.

On the TPU's routes a window's rows reach their t-digest centroids by one
payload-carrying sort (``ops/tdigest.py`` ``_sorted_batch_to_digest``,
reference ``src/carnot/funcs/builtins/math_sketches.h:34`` QuantilesUDA):
every row knows its centroid slot and the slots come out sorted. What is
left is per-slot row counts and value sums over G x K slots, a reduction
of SORTED ids into a small domain. XLA's ``segment_sum`` of 2^21 sorted
rows is still a row scatter (37.9 ms on the v5e, my chip run, PR 33);
this kernel walks the row chunks once with the [slots] accumulators
VMEM-resident and, because the ids are sorted, compares each chunk only
against the 128-slot tiles between its smallest and largest id: at most
chunks + tiles tile steps for a window, whatever the slot count (1,024 +
33 for ``px/service_stats``' 2^21-row window), where a dense one-hot
sweep of every tile would be chunks x tiles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: Slots a tile of the centroid accumulators holds (one row of lanes).
CENTROID_TILE = 128
#: Rows a grid step of ``sorted_centroid_fold`` takes.
CENTROID_CHUNK = 2048


def _centroid_kernel(first_ref, last_ref, c_ref, v_ref, w_ref, mw_ref):
    """Grid (row chunks): fold one chunk of SORTED centroid ids into the
    VMEM-resident [tiles, 128] accumulators, visiting only the tiles
    between the chunk's smallest and largest id (scalar-prefetched), so
    the whole pass makes at most chunks + tiles tile steps whatever the
    slot count."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        w_ref[:] = jnp.zeros_like(w_ref)
        mw_ref[:] = jnp.zeros_like(mw_ref)

    rows = c_ref.shape[0]
    tile_rows = (CENTROID_TILE, rows)
    # The rows stay along the lanes, as they arrive (no relayout of the
    # chunk); a tile's slots run down the sublanes.
    c = c_ref[:][None, :]
    v = v_ref[:][None, :]
    slot = jax.lax.broadcasted_iota(jnp.int32, tile_rows, 0)
    square = (CENTROID_TILE, CENTROID_TILE)
    eye = (jax.lax.broadcasted_iota(jnp.int32, square, 0)
           == jax.lax.broadcasted_iota(jnp.int32, square, 1))

    def along_lanes(col):
        # [128, 1] sums, one a sublane, as the accumulators' [1, 128] row:
        # spread along the lanes, kept on the diagonal, summed down.
        return jnp.sum(
            jnp.where(eye, jnp.broadcast_to(col, square), 0.0),
            axis=0, keepdims=True,
        )

    def tile(t, carry):
        # A select, not a product: exact f32 adds on the VPU, and a row of
        # another tile (or a dropped row, or a slot past ``n_slots`` that
        # the caller never reads) adds nothing that is kept.
        hit = c == slot + t * jnp.int32(CENTROID_TILE)
        at = pl.ds(t, 1)
        w_ref[at, :] += along_lanes(
            jnp.sum(hit.astype(jnp.float32), axis=1, keepdims=True)
        )
        mw_ref[at, :] += along_lanes(
            jnp.sum(jnp.where(hit, v, 0.0), axis=1, keepdims=True)
        )
        return carry

    jax.lax.fori_loop(first_ref[i], last_ref[i] + 1, tile, jnp.int32(0))


@functools.partial(jax.jit, static_argnames=("n_slots", "interpret"))
def sorted_centroid_fold(ids, values, n_slots: int, interpret: bool = False):
    """(weights, value sums) f32[n_slots] of rows whose slot ``ids`` are
    SORTED (non-decreasing within a 128-slot tile's reach: a chunk's
    smallest and largest id bound the tiles it touches).

    ``ids`` i32[n] in [0, n_slots) for live rows, ``n_slots`` or more for
    rows to drop (they sort last); ``values`` f32[n], finite. Any n: the
    rows are padded with dropped ones to whole 1,024-row blocks, which
    the chip's tiling of a 1-D 32-bit operand asks for. A weight is a
    count of rows: exact in f32 below 2^24 rows a slot.
    """
    ids = ids.astype(jnp.int32)
    values = values.astype(jnp.float32)
    pad = -ids.shape[0] % 1024
    if pad:
        ids = jnp.pad(ids, (0, pad), constant_values=n_slots)
        values = jnp.pad(values, (0, pad))
    n = ids.shape[0]
    chunk = CENTROID_CHUNK if n % CENTROID_CHUNK == 0 else 1024
    tiles = -(-n_slots // CENTROID_TILE)
    by_chunk = ids.reshape(n // chunk, chunk)
    # A chunk of dropped rows alone loops over nothing (first = tiles).
    first = jnp.minimum(by_chunk.min(axis=1) // CENTROID_TILE, tiles)
    last = jnp.minimum(by_chunk.max(axis=1) // CENTROID_TILE, tiles - 1)
    acc = jax.ShapeDtypeStruct((tiles, CENTROID_TILE), jnp.float32)
    whole = pl.BlockSpec(
        (tiles, CENTROID_TILE),
        lambda i, first, last: (jnp.int32(0), jnp.int32(0)),
    )
    w, mw = pl.pallas_call(
        _centroid_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n // chunk,),
            in_specs=[
                pl.BlockSpec((chunk,), lambda i, first, last: (i,)),
                pl.BlockSpec((chunk,), lambda i, first, last: (i,)),
            ],
            out_specs=[whole, whole],
        ),
        out_shape=[acc, acc],
        interpret=interpret,
        name="sorted_centroid_fold",
    )(first.astype(jnp.int32), last.astype(jnp.int32), ids, values)
    return w.reshape(-1)[:n_slots], mw.reshape(-1)[:n_slots]
