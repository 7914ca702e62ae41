"""Pallas TPU kernel: t-digest histogram binning (dual scatter-add).

The sketch pipeline's hot loop (``ops/tdigest.py`` batch_to_digest,
reference ``src/carnot/funcs/builtins/math_sketches.h:34`` QuantilesUDA)
is two segment-sums over the same flat bin ids: per-bin weight and
weighted-value totals across ``G * B`` slots. XLA lowers those to two
HBM scatter passes; this kernel computes BOTH in one sweep of the rows
with the accumulators VMEM-resident, tiling the slot axis and using the
same one-hot MXU contraction trick as ``pallas_groupby`` — a [C, T]
one-hot against the row chunk yields the weight row-sum and the
weighted-mean contraction per tile.

FLOP note: the dense sweep costs n * S MACs (S = G*B slots). It wins
when S is small enough for the MXU to beat two scatter passes —
the caller gates on ``S <= 1 << 15`` (~2 GFLOP per 2M-row window, sub-ms
on the MXU) and falls back to the XLA scatters beyond that.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

#: Slot-axis tile width (lanes).
_TILE = 2048


def _hist_kernel(bin_ref, val_ref, w_ref, mw_ref, *, tile: int):
    """Grid (slot_tiles, row_chunks): fold one row chunk into one tile."""
    t = pl.program_id(0)
    step = pl.program_id(1)

    @pl.when(step == 0)
    def _init():
        w_ref[:] = jnp.zeros_like(w_ref)
        mw_ref[:] = jnp.zeros_like(mw_ref)

    bins = bin_ref[:]  # [C] i32 flat slot ids (trash >= n_slots_pad)
    vals = val_ref[:]  # [C] f32
    base = t * tile
    onehot = (
        (bins[:, None] - base)
        == jax.lax.broadcasted_iota(jnp.int32, (bins.shape[0], tile), 1)
    ).astype(jnp.float32)
    w_ref[:] += jnp.sum(onehot, axis=0)
    # [1, C] lhs: Mosaic's dot lowering needs a non-contracting lhs dim.
    mw_ref[:] += (vals[None, :] @ onehot)[0]


@functools.partial(jax.jit, static_argnames=("n_slots", "chunk", "interpret"))
def hist_fold(bins, values, n_slots: int, chunk: int = 2048,
              interpret: bool = False):
    """(weights, weighted_sums) f32[n_slots] over flat bin ids.

    ``bins`` i32[n] in [0, n_slots) for live rows, >= padded slot count
    for masked rows; ``values`` f32[n]. n must be a chunk multiple;
    n_slots pads internally to the tile width.
    """
    n = bins.shape[0]
    pad = -(-n_slots // _TILE) * _TILE
    grid = (pad // _TILE, n // chunk)
    w, mw = pl.pallas_call(
        functools.partial(_hist_kernel, tile=_TILE),
        grid=grid,
        in_specs=[
            pl.BlockSpec((chunk,), lambda t, i: (i,)),
            pl.BlockSpec((chunk,), lambda t, i: (i,)),
        ],
        out_specs=[
            pl.BlockSpec((_TILE,), lambda t, i: (t,)),
            pl.BlockSpec((_TILE,), lambda t, i: (t,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((pad,), jnp.float32),
            jax.ShapeDtypeStruct((pad,), jnp.float32),
        ],
        interpret=interpret,
        name="hist_fold",
    )(bins.astype(jnp.int32), values.astype(jnp.float32))
    return w[:n_slots], mw[:n_slots]
