"""Pallas TPU kernel: fused dense-domain group-by fold (count/sum/max).

The XLA path aggregates via scatters/sorts per UDA (``ops/groupby.py``,
``udf/builtins/math_ops.py``). This kernel is the hand-scheduled
alternative for the dense-domain case (slot ids already packed, G slots
known statically): a grid over row chunks keeps the [G] accumulators
resident in VMEM for the whole pass and turns the per-chunk reduction
into MXU work — a [C, G] one-hot contraction computes count and sum in
two matmuls, and a masked VPU reduce folds max — instead of HBM
scatter traffic per aggregate.

Reference contrast: Carnot's AggNode walks a hash map row-by-row
(``src/carnot/exec/agg_node.h:66``); there is no reference analog of a
fused systolic-array group-by — this is the TPU-first design the MXU
makes natural.

Numeric contract: f32 throughout (count is exact below 2^24 per group;
sums carry f32 rounding) — the engine's exact i64 paths stay on the XLA
pipeline; this kernel serves FLOAT64-typed aggregations whose planes
are f32 on device anyway (``types/dtypes.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def row_chunk(n: int, cap: int) -> int | None:
    """Row block (<= cap) Mosaic accepts for a 1-D 32-bit [n] operand.

    XLA tiles such an operand T(1024) (the whole array below that), and
    the kernel's block has to agree with it: a multiple of 1024 that
    divides n, or all n rows as one block. None when there is neither —
    the caller keeps that window on its XLA path.
    """
    for c in range(cap - cap % 1024, 0, -1024):
        if n % c == 0:
            return c
    return n if n <= cap else None


def fold_row_chunk(n: int, g: int) -> int | None:
    """``dense_group_fold``'s row block for [n] rows and g (padded) groups.

    The [chunk, g] one-hot and masked max/min temporaries stay at 4 MiB
    each, but for the 1024-row floor the tiling sets: 8 MiB at g = 2048,
    which still compiles under the kernel's 16 MiB scoped-vmem limit.
    """
    return row_chunk(n, 2048 if g <= 512 else 1024)


def _fold_kernel(slot_ref, val_ref, cnt_ref, sum_ref, max_ref, aux_ref,
                 *, g: int, want_min: bool):
    """One grid step: fold a [C]-row chunk into the [G] accumulators.

    ``aux_ref`` is the per-group MIN when ``want_min`` (full VPU masked
    reduce) and otherwise a per-group count of -inf values (one extra
    MXU contraction) — the cheap evidence the sum-restore logic needs,
    since zeroed non-finite rows must resurface in their own group.
    """
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        cnt_ref[:] = jnp.zeros_like(cnt_ref)
        sum_ref[:] = jnp.zeros_like(sum_ref)
        max_ref[:] = jnp.full_like(max_ref, -jnp.inf)
        aux_ref[:] = jnp.full_like(aux_ref, jnp.inf if want_min else 0.0)

    slots = slot_ref[:]  # [C] i32; trash rows carry an id >= g
    vals = val_ref[:]  # [C] f32
    # [C, G] one-hot via broadcast compare: rows with slot >= g match no
    # column, so invalid rows vanish without a separate mask pass.
    onehot = (
        slots[:, None]
        == jax.lax.broadcasted_iota(jnp.int32, (slots.shape[0], g), 1)
    ).astype(jnp.float32)
    # MXU: [1, C] @ [C, G] contractions. NON-FINITE values must be zeroed
    # for the contraction (NaN * 0 = NaN and inf * 0 = NaN would poison
    # EVERY group's sum, not just the row's own group); the masked
    # max/min reductions below see the raw values, so a group containing
    # NaN/+inf/-inf surfaces there and the caller restores the correct
    # non-finite sum into that group alone. The masked fills are ±inf —
    # they feed only VPU reductions, never the matmul, so a group whose
    # values are all +inf (f32 overflow of a huge f64) still reports the
    # true extremum the XLA scatter path would.
    # The lhs is written [1, C], never a bare [C] vector: Mosaic's dot
    # lowering needs a non-contracting lhs dimension.
    cnt_ref[:] += jnp.sum(onehot, axis=0)
    sum_ref[:] += (jnp.where(jnp.isfinite(vals), vals, 0.0)[None, :] @ onehot)[0]
    masked_hi = jnp.where(onehot > 0, vals[:, None], -jnp.inf)  # [C, G] VPU
    max_ref[:] = jnp.maximum(max_ref[:], jnp.max(masked_hi, axis=0))
    if want_min:
        masked_lo = jnp.where(onehot > 0, vals[:, None], jnp.inf)
        aux_ref[:] = jnp.minimum(aux_ref[:], jnp.min(masked_lo, axis=0))
    else:
        aux_ref[:] += ((vals == -jnp.inf).astype(jnp.float32)[None, :] @ onehot)[0]


@functools.partial(
    jax.jit, static_argnames=("g", "chunk", "interpret", "want_min")
)
def dense_group_fold(slots, values, g: int, chunk: int = 2048,
                     interpret: bool = False, want_min: bool = False):
    """(count, sum, max, min | None) f32[g] over packed slot ids.

    ``slots`` i32[n] in [0, g) for live rows, >= g for masked rows;
    ``values`` f32[n]. ``chunk`` comes from ``fold_row_chunk`` (a multiple
    of 1024 dividing n, or n itself); g should be a multiple of 128 for
    lane alignment (pad and slice at the caller).
    ``want_min=False`` skips the min reduce (the 4th return is None) —
    queries without a min aggregate don't pay its VPU pass.
    """
    n = slots.shape[0]
    grid = (n // chunk,)
    out = pl.pallas_call(
        functools.partial(_fold_kernel, g=g, want_min=want_min),
        grid=grid,
        in_specs=[
            pl.BlockSpec((chunk,), lambda i: (i,)),
            pl.BlockSpec((chunk,), lambda i: (i,)),
        ],
        # Accumulators: every grid step maps to the SAME [g] block, so
        # they live in VMEM across the whole pass (init at step 0). The
        # block index is an explicit int32: the package runs with x64 on,
        # where a Python 0 traces as i64 and Mosaic refuses the index map.
        out_specs=[pl.BlockSpec((g,), lambda i: (jnp.int32(0),))] * 4,
        out_shape=[
            jax.ShapeDtypeStruct((g,), jnp.float32),
            jax.ShapeDtypeStruct((g,), jnp.float32),
            jax.ShapeDtypeStruct((g,), jnp.float32),
            jax.ShapeDtypeStruct((g,), jnp.float32),
        ],
        interpret=interpret,
        name="dense_group_fold",
    )(slots.astype(jnp.int32), values.astype(jnp.float32))
    cnt, s, m, aux = out
    # Restore per-group non-finite sums from the max/aux evidence (the
    # contraction zeroed them so they could not leak across groups):
    # NaN anywhere -> NaN; +inf and -inf together -> NaN; else +/-inf.
    mn = aux if want_min else None
    has_nan = jnp.isnan(m) | (jnp.isnan(aux) if want_min else False)
    has_pos = m == jnp.inf
    has_neg = (aux == -jnp.inf) if want_min else (aux > 0)
    s = jnp.where(
        has_nan | (has_pos & has_neg), jnp.nan,
        jnp.where(has_pos, jnp.inf, jnp.where(has_neg, -jnp.inf, s)),
    )
    live = cnt > 0
    return (
        cnt,
        jnp.where(live, s, 0.0),
        jnp.where(live, m, jnp.nan),
        jnp.where(live, mn, jnp.nan) if want_min else None,
    )
