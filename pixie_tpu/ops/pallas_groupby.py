"""Pallas TPU kernels: fused dense-domain group-by folds.

The XLA path aggregates via scatters/sorts per UDA (``ops/groupby.py``,
``udf/builtins/math_ops.py``). These kernels are the hand-scheduled
alternative for the dense-domain case (slot ids already packed, G slots
known statically): a grid over row chunks keeps the [G] accumulators
resident in VMEM for the whole pass and turns the per-chunk reduction
into MXU work — a [C, G] one-hot contraction computes count and sums,
and a masked VPU reduce folds max/min — instead of a sort, window-long
gathers or HBM scatter traffic per aggregate.

Reference contrast: Carnot's AggNode walks a hash map row-by-row
(``src/carnot/exec/agg_node.h:66``); there is no reference analog of a
fused systolic-array group-by — this is the TPU-first design the MXU
makes natural.

Two kernels, two numeric contracts (``exec/fragment.py`` routes each
aggregate by its argument's type):

- ``dense_group_fold``: f32 throughout (count is exact below 2^24 per
  group; sums carry f32 rounding). It serves FLOAT64-typed aggregations,
  whose planes are f32 on device anyway (``types/dtypes.py``).
- ``dense_group_fold_int``: EXACT. INT64 / BOOLEAN / TIME64NS arguments
  are split by XLA into 8-bit limb planes (Pallas on this chip takes no
  i64); sums and the count are ``limbs[L, C] @ onehot[C, G]`` in bf16
  with every partial inside f32's integers, accumulated in i32 and
  recombined in wrapping i64 on [G]; max/min are a lexicographic (high
  i32, low u32) masked reduce on the same tile. Equal to numpy's int64
  sum, count and extremes bit for bit. This is the fold the shipped
  scripts (``px/http_stats``, ``px/service_stats``) run on the chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .routes import (  # noqa: F401  (the kernels' limits, kept by routes.py)
    INT_FOLD_GROUP_BLOCK,
    INT_FOLD_MAX_GROUPS,
    int_fold_groups,
)


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


# -- exact integer fold -------------------------------------------------------
#: Rows one call may fold: a limb's column sum (255 a row) has to stay
#: inside the i32 accumulator, 255 * 2^23 < 2^31.
INT_FOLD_MAX_ROWS = 1 << 23

_LIMB_BITS = 8
_I32_MIN = -(1 << 31)
_I32_MAX = (1 << 31) - 1


def int_fold_blocks(n: int, g_pad: int) -> tuple[int, int] | None:
    """(row block, group block) of ``dense_group_fold_int`` for [n] rows
    and ``g_pad`` (``int_fold_groups``) groups, or None when the call
    stays on XLA: no row block the chip's tiling accepts, more rows than
    the i32 limb accumulators hold, or groups above the cross-over."""
    if g_pad > INT_FOLD_MAX_GROUPS or n > INT_FOLD_MAX_ROWS:
        return None
    chunk = row_chunk(n, 2048)
    if chunk is None:
        return None
    return chunk, min(g_pad, INT_FOLD_GROUP_BLOCK)


def _int_fold_kernel(slot_ref, limb_ref, *refs, gb: int, ext_max: tuple):
    """One grid step (group block j, row chunk i): fold [C] rows into the
    accumulators of block j. ``refs`` = (hi, lo) i32[C] per extreme
    argument, then the outputs: the [L, gb] i32 limb sums and the
    [E, gb] i32 extremes, rows (2e, 2e + 1) = the e-th argument's
    (hi, lo)."""
    n_ext = len(ext_max)
    ext_in = refs[: 2 * n_ext]
    acc_ref = refs[2 * n_ext]
    ext_ref = refs[2 * n_ext + 1] if n_ext else None
    j = pl.program_id(0)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        for e, is_max in enumerate(ext_max):
            # (I32_MIN, biased 0) is INT64_MIN and (I32_MAX, biased
            # 2^32-1) is INT64_MAX: the fills ARE the UDAs' neutrals.
            ext_ref[2 * e:2 * e + 2, :] = jnp.full(
                (2, gb), _I32_MIN if is_max else _I32_MAX, jnp.int32
            )

    # [C, gb] one-hot of this group block, built once and used by every
    # statistic of every argument: rows of other blocks, and trash rows
    # (id >= the padded G), match no column.
    local = slot_ref[:] - j * jnp.int32(gb)
    hit = local[:, None] == jax.lax.broadcasted_iota(
        jnp.int32, (local.shape[0], gb), 1
    )
    # MXU: [L, C] @ [C, gb]. Operands are 8-bit limbs and 0/1 in bf16,
    # both exact; a chunk's partial is at most 255 * C <= 255 * 2048
    # < 2^24, exact in the f32 the MXU accumulates in, so the cast to
    # i32 loses nothing. The i32 accumulator then holds at most
    # 255 * n <= 255 * 2^23 < 2^31 (INT_FOLD_MAX_ROWS).
    onehot = hit.astype(jnp.float32).astype(jnp.bfloat16)
    acc_ref[:] += jnp.dot(
        limb_ref[:], onehot, preferred_element_type=jnp.float32
    ).astype(jnp.int32)
    for e, is_max in enumerate(ext_max):
        # Lexicographic (high i32, low u32 biased to i32) extreme on the
        # same tile: the chunk's per-group high extreme, the low extreme
        # among the rows whose high equals it, then the pair against the
        # accumulator pair. Every step is a compare or a select of i32:
        # exact, no bound to state.
        fill = jnp.int32(_I32_MIN if is_max else _I32_MAX)
        red = jnp.max if is_max else jnp.min
        pick = jnp.maximum if is_max else jnp.minimum
        hi = ext_in[2 * e][:]
        lo = ext_in[2 * e + 1][:]
        c_hi = red(jnp.where(hit, hi[:, None], fill), axis=0, keepdims=True)
        top = hit & (hi[:, None] == c_hi)
        c_lo = red(jnp.where(top, lo[:, None], fill), axis=0, keepdims=True)
        a_hi = ext_ref[2 * e:2 * e + 1, :]
        a_lo = ext_ref[2 * e + 1:2 * e + 2, :]
        better = (c_hi > a_hi) if is_max else (c_hi < a_hi)
        ext_ref[2 * e:2 * e + 1, :] = pick(a_hi, c_hi)
        ext_ref[2 * e + 1:2 * e + 2, :] = jnp.where(
            better, c_lo, jnp.where(c_hi == a_hi, pick(a_lo, c_lo), a_lo)
        )


def _limb_rows(v) -> list:
    """An integer argument as bf16 planes of 8-bit limbs, low limb first
    (a BOOLEAN is one limb). Elementwise shifts and masks of the u32
    halves the TPU keeps an i64 as; XLA fuses them into one pass."""
    if v.dtype == jnp.bool_:
        return [v.astype(jnp.bfloat16)]
    halves = (v.astype(jnp.uint32), (v >> 32).astype(jnp.uint32))
    return [
        ((h >> s) & 0xFF).astype(jnp.bfloat16)
        for h in halves
        for s in range(0, 32, _LIMB_BITS)
    ]


@functools.partial(
    jax.jit,
    static_argnames=("g", "chunk", "g_block", "ext_max", "interpret"),
)
def dense_group_fold_int(slots, sum_args, ext_args, g: int, chunk: int,
                         g_block: int, ext_max: tuple = (),
                         interpret: bool = False):
    """Exact (count, sums, extremes) i64[g] of a dense group-by.

    ``slots`` i32[n] in [0, g) for live rows, >= g for masked rows (g a
    multiple of 128 and of ``g_block``; blocks from ``int_fold_blocks``).
    ``sum_args``: tuple of i64[n] / bool[n] planes, each summed per group
    modulo 2^64 as numpy's int64 sum is; ``ext_args``: tuple of i64[n]
    planes, the e-th reduced by max when ``ext_max[e]`` and by min
    otherwise, an empty group reading INT64_MIN / INT64_MAX (the UDAs'
    neutral fills). No sort, no gather, no 64-bit scatter: XLA splits
    the planes into limbs (Pallas on this chip takes no i64), the kernel
    contracts them with the [chunk, g_block] one-hot, XLA recombines on
    [g].
    """
    n = slots.shape[0]
    rows = [jnp.ones(n, jnp.bfloat16)]  # the count's row of ones
    spans = []
    for v in sum_args:
        limbs = _limb_rows(v)
        spans.append((len(rows), len(limbs)))
        rows += limbs
    # bf16 packs 16 sublanes a tile: pad the limb planes to a multiple.
    n_rows = -(-len(rows) // 16) * 16
    rows += [jnp.zeros(n, jnp.bfloat16)] * (n_rows - len(rows))
    ext_in = []
    for v in ext_args:
        ext_in.append((v >> 32).astype(jnp.int32))
        ext_in.append(jax.lax.bitcast_convert_type(
            v.astype(jnp.uint32) ^ jnp.uint32(1 << 31), jnp.int32
        ))
    row_spec = pl.BlockSpec((chunk,), lambda j, i: (i,))
    # Outputs: the limb sums, then (when there are any) the extremes'
    # (hi, lo) rows, padded to the i32 tile's 8 sublanes.
    out_rows = [n_rows] + ([-(-len(ext_in) // 8) * 8] if ext_in else [])
    out = pl.pallas_call(
        functools.partial(_int_fold_kernel, gb=g_block, ext_max=ext_max),
        grid=(g // g_block, n // chunk),
        in_specs=[row_spec,
                  pl.BlockSpec((n_rows, chunk), lambda j, i: (jnp.int32(0), i))]
        + [row_spec] * len(ext_in),
        # Accumulators: every row chunk of a group block maps to the
        # SAME output block, so they stay in VMEM across the block's
        # pass (init at its first chunk). The block index is an explicit
        # int32: see ``dense_group_fold``.
        out_specs=[
            pl.BlockSpec((r, g_block), lambda j, i: (jnp.int32(0), j))
            for r in out_rows
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r, g), jnp.int32) for r in out_rows
        ],
        interpret=interpret,
        name="dense_group_fold_int",
    )(slots.astype(jnp.int32), jnp.stack(rows), *ext_in)
    acc = out[0].astype(jnp.int64)
    sums = tuple(
        # Limb sums recombined in wrapping i64 arithmetic: what the top
        # limb's sum loses above bit 63 is what numpy's sum loses.
        functools.reduce(
            jnp.add,
            [acc[r0 + k] << (_LIMB_BITS * k) for k in range(n_limbs)],
        )
        for r0, n_limbs in spans
    )
    exts = tuple(
        (out[1][2 * e].astype(jnp.int64) << 32)
        | (jax.lax.bitcast_convert_type(
            out[1][2 * e + 1], jnp.uint32
        ).astype(jnp.int64) ^ jnp.int64(1 << 31))
        for e in range(len(ext_args))
    )
    return acc[0], sums, exts
