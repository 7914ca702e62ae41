"""Math scalar UDFs and numeric UDAs.

Reference parity: ``src/carnot/funcs/builtins/math_ops.h:34-744`` — binary
arith (add/subtract/multiply/divide/modulo), comparisons
(equal/notEqual/lessThan/greaterThan/...), logical ops, unary
(abs/ceil/floor/round/sqrt/exp/ln/log2/log10/negate/invert), ``bin``, time
conversions, and the UDAs MeanUDA(:584)/SumUDA(:630)/MaxUDA(:661)/
MinUDA(:703)/CountUDA(:744).

TPU-first: scalars are whole-column jnp expressions XLA fuses; UDAs are
segment reductions into [G] carries with associative merges.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ...ops import routes
from ...ops.scan import blocked_cumsum
from ..udf import BOOLEAN, FLOAT64, INT64, STRING, TIME64NS


def _num(t):  # numeric overload families
    return [(INT64, jnp.int64), (FLOAT64, jnp.float64)][t]


_I64_MAX = jnp.iinfo(jnp.int64).max
_I64_MIN = jnp.iinfo(jnp.int64).min


def register(reg):
    # -- binary arithmetic ---------------------------------------------------
    for dt in (INT64, FLOAT64):
        reg.scalar("add", (dt, dt), dt, lambda a, b: a + b)
        reg.scalar("subtract", (dt, dt), dt, lambda a, b: a - b)
        reg.scalar("multiply", (dt, dt), dt, lambda a, b: a * b)
    # Time arithmetic keeps TIME64NS (duration treated as INT64 input).
    reg.scalar("add", (TIME64NS, TIME64NS), TIME64NS, lambda a, b: a + b)
    reg.scalar("subtract", (TIME64NS, TIME64NS), TIME64NS, lambda a, b: a - b)
    # divide always yields float (Carnot: DivideUDF -> FLOAT64).
    reg.scalar(
        "divide",
        (FLOAT64, FLOAT64),
        FLOAT64,
        lambda a, b: a / b,
        doc="Arithmetic division; inf/nan on zero divisors.",
    )
    reg.scalar("modulo", (INT64, INT64), INT64, lambda a, b: jnp.where(b != 0, a % jnp.where(b == 0, 1, b), 0))
    reg.scalar("pow", (FLOAT64, FLOAT64), FLOAT64, lambda a, b: jnp.power(a, b))

    # -- comparisons ---------------------------------------------------------
    for dt in (INT64, FLOAT64, TIME64NS, BOOLEAN, STRING):
        reg.scalar("equal", (dt, dt), BOOLEAN, lambda a, b: a == b)
        reg.scalar("notEqual", (dt, dt), BOOLEAN, lambda a, b: a != b)
    for dt in (INT64, FLOAT64, TIME64NS):
        reg.scalar("lessThan", (dt, dt), BOOLEAN, lambda a, b: a < b)
        reg.scalar("lessThanEqual", (dt, dt), BOOLEAN, lambda a, b: a <= b)
        reg.scalar("greaterThan", (dt, dt), BOOLEAN, lambda a, b: a > b)
        reg.scalar("greaterThanEqual", (dt, dt), BOOLEAN, lambda a, b: a >= b)
    # Tolerance sized for f32 planes (one ULP at magnitude 1 is ~1.2e-7).
    reg.scalar("approxEqual", (FLOAT64, FLOAT64), BOOLEAN, lambda a, b: jnp.abs(a - b) < 1e-4)

    # -- logical -------------------------------------------------------------
    reg.scalar("logicalAnd", (BOOLEAN, BOOLEAN), BOOLEAN, lambda a, b: a & b)
    reg.scalar("logicalOr", (BOOLEAN, BOOLEAN), BOOLEAN, lambda a, b: a | b)
    reg.scalar("logicalNot", (BOOLEAN,), BOOLEAN, lambda a: ~a)
    reg.scalar("invert", (BOOLEAN,), BOOLEAN, lambda a: ~a)

    # -- unary math ----------------------------------------------------------
    for dt in (INT64, FLOAT64):
        reg.scalar("abs", (dt,), dt, jnp.abs)
        reg.scalar("negate", (dt,), dt, jnp.negative)
    reg.scalar("ceil", (FLOAT64,), FLOAT64, jnp.ceil)
    reg.scalar("floor", (FLOAT64,), FLOAT64, jnp.floor)
    reg.scalar("round", (FLOAT64,), FLOAT64, jnp.round)
    reg.scalar("sqrt", (FLOAT64,), FLOAT64, jnp.sqrt)
    reg.scalar("exp", (FLOAT64,), FLOAT64, jnp.exp)
    reg.scalar("ln", (FLOAT64,), FLOAT64, jnp.log)
    reg.scalar("log2", (FLOAT64,), FLOAT64, jnp.log2)
    reg.scalar("log10", (FLOAT64,), FLOAT64, jnp.log10)
    reg.scalar("log", (FLOAT64, FLOAT64), FLOAT64, lambda b, x: jnp.log(x) / jnp.log(b))

    # -- bin + time conversions ----------------------------------------------
    reg.scalar(
        "bin",
        (INT64, INT64),
        INT64,
        lambda v, s: v - v % jnp.where(s == 0, 1, s),
        doc="Round v down to the nearest multiple of s (px.bin).",
    )
    reg.scalar("bin", (TIME64NS, INT64), TIME64NS, lambda v, s: v - v % jnp.where(s == 0, 1, s))
    reg.scalar("time_to_int64", (TIME64NS,), INT64, lambda t: t)
    reg.scalar("int64_to_time", (INT64,), TIME64NS, lambda t: t)

    # -- UDAs ----------------------------------------------------------------
    # Float carries are f64 even though column planes are f32: [G]-sized,
    # sort-free accumulators keep billions-row sums exact without tripping
    # the f64-sort compile blowup (see types/dtypes.py).
    # 64-bit INTEGER segment reductions avoid XLA's 64-bit scatter on the
    # TPU: they take the sort-based form (argsort group ids once, cumsum,
    # boundary gathers), whose shared argsort/searchsorted CSE away
    # across the aggs of one fused window program. On the v5e that form
    # costs 134-158 ms a 2^21-row window for count + mean + max of one
    # INT64 column, about the same whatever the group count
    # (tools/fold_sweep.py; PERF.md section 6, my chip run, PR 26): the
    # window-long gathers behind the argsort, 19-34 ms each, are what it
    # pays for (PR 29). Neither of the engine's own integer folds comes
    # here any more: on a dense key domain of up to INT_FOLD_MAX_GROUPS
    # slots exec/fragment.py routes count / sum / mean / max / min to the
    # one-hot limb kernel (ops/pallas_groupby.py dense_group_fold_int),
    # and on a key with NO dense domain, when every aggregate of the
    # AggOp is such an integer statistic, to the payload-carrying sort
    # (ops/groupby.py sorted_group_fold), which gives no row a group id.
    # What is LEFT for these functions' 64-bit integer branch: an AggOp
    # that mixes integer aggregates with one that needs group ids in row
    # order (a ``quantiles``, a FLOAT64 sum) by a non-dense key, dense
    # domains above the kernel's cross-over, and windows with no row
    # block the kernel's tiling accepts. 32-bit-and-smaller dtypes keep
    # the plain scatter (cheaper than a sort), and so do floats
    # (prefix-difference sums cancel).

    def _sorted_segments() -> bool:
        """TPU only: XLA's TPU sort is fast (the sorts are 5-6 % of the
        sort-based fold's device time, ledger PR 25; the window-long
        gathers behind them are the rest) while a 64-bit scatter was
        measured no better; on CPU the trade inverts hard (argsort 2M
        ~660ms vs scatter-add ~8ms). Trace-time check — executables are
        per-backend."""
        return routes.routes_platform() == "tpu"

    def _seg_order(gids, mask, g):
        """(order, sorted_gids, ends): rows sorted by group id, invalid
        rows last (slot g); ends[k] = one past segment k's last row.
        Pure function of (gids, mask) — duplicated calls CSE under jit."""
        gi = jnp.where(mask, gids, g).astype(jnp.int32)
        order = jnp.argsort(gi).astype(jnp.int32)
        sg = gi[order]
        ends = jnp.searchsorted(
            sg, jnp.arange(g, dtype=jnp.int32), side="right"
        ).astype(jnp.int32)
        return order, sg, ends

    def _seg_sum(carry, gids, mask, v):
        g = carry.shape[0]
        v = v.astype(carry.dtype)
        # Floats keep the scatter: the cumsum-diff trick subtracts window-
        # wide prefixes, which catastrophically cancels when a huge-sum
        # group precedes a tiny one. Int64 is safe (wraparound differences
        # are exact).
        if (
            np.dtype(carry.dtype).itemsize <= 4
            or not jnp.issubdtype(carry.dtype, jnp.integer)
            or not _sorted_segments()
        ):
            contrib = jnp.where(mask, v, jnp.zeros((), v.dtype))
            return carry + jax.ops.segment_sum(
                contrib, jnp.where(mask, gids, g), num_segments=g + 1
            )[:-1]
        order, _sg, ends = _seg_order(gids, mask, g)
        contrib = jnp.where(mask, v, jnp.zeros((), v.dtype))[order]
        # blocked_cumsum: XLA:TPU cannot compile a flat multi-million-row
        # i64 cumsum (scoped-vmem overflow in the u32-pair reduce-window
        # lowering); the two-level blocked scan is bit-identical.
        cs0 = jnp.concatenate(
            [jnp.zeros(1, contrib.dtype), blocked_cumsum(contrib)]
        )
        tot = cs0[ends]  # cumulative sum up to each segment's end
        return carry + tot - jnp.concatenate(
            [jnp.zeros(1, tot.dtype), tot[:-1]]
        )

    def _seg_count(carry, gids, mask):
        """Row count per group: boundary diffs on the shared sorted ids
        (TPU), or an i32 scatter (CPU — sorts are slow there). Window
        counts always fit i32 (window size < 2^31)."""
        g = carry.shape[0]
        if not _sorted_segments():
            cnt = jax.ops.segment_sum(
                mask.astype(jnp.int32), jnp.where(mask, gids, g),
                num_segments=g + 1,
            )[:-1]
            return carry + cnt.astype(carry.dtype)
        _order, _sg, ends = _seg_order(gids, mask, g)
        cnt = ends - jnp.concatenate([jnp.zeros(1, ends.dtype), ends[:-1]])
        return carry + cnt.astype(carry.dtype)

    for dt, zdtype in ((INT64, jnp.int64), (FLOAT64, jnp.float64)):
        reg.uda(
            "sum",
            (dt,),
            dt,
            init=lambda g, _z=zdtype: jnp.zeros(g, dtype=_z),
            update=lambda c, gids, mask, v: _seg_sum(c, gids, mask, v),
            merge=lambda a, b: a + b,
            finalize=lambda c: c,
            doc="Sum of the group.",
        )
    reg.uda(
        "sum",
        (BOOLEAN,),
        INT64,
        init=lambda g: jnp.zeros(g, dtype=jnp.int64),
        update=lambda c, gids, mask, v: _seg_sum(c, gids, mask, v.astype(jnp.int64)),
        merge=lambda a, b: a + b,
        finalize=lambda c: c,
    )

    reg.uda(
        "count",
        (FLOAT64,),
        INT64,
        init=lambda g: jnp.zeros(g, dtype=jnp.int64),
        update=lambda c, gids, mask, v: _seg_count(c, gids, mask),
        merge=lambda a, b: a + b,
        finalize=lambda c: c,
        doc="Number of rows in the group.",
    )

    reg.uda(
        "mean",
        (FLOAT64,),
        FLOAT64,
        init=lambda g: (jnp.zeros(g, dtype=jnp.float64), jnp.zeros(g, dtype=jnp.float64)),
        update=lambda c, gids, mask, v: (
            _seg_sum(c[0], gids, mask, v),
            _seg_count(c[1], gids, mask),
        ),
        merge=lambda a, b: (a[0] + b[0], a[1] + b[1]),
        finalize=lambda c: jnp.where(c[1] > 0, c[0] / jnp.maximum(c[1], 1.0), jnp.nan),
        doc="Arithmetic mean of the group (sum/count carry; merges exactly).",
    )
    # Direct integer/bool overloads: EXACT i64 sums (the FLOAT64 path
    # rides f32 device planes) via the shared sort-based reduction here,
    # or the limb kernel on a dense domain (see above) — never f32.
    reg.uda(
        "mean",
        (INT64,),
        FLOAT64,
        init=lambda g: (jnp.zeros(g, dtype=jnp.int64), jnp.zeros(g, dtype=jnp.int64)),
        update=lambda c, gids, mask, v: (
            _seg_sum(c[0], gids, mask, v),
            _seg_count(c[1], gids, mask),
        ),
        merge=lambda a, b: (a[0] + b[0], a[1] + b[1]),
        finalize=lambda c: jnp.where(
            c[1] > 0,
            c[0].astype(jnp.float64) / jnp.maximum(c[1], 1).astype(jnp.float64),
            jnp.nan,
        ),
        doc="Arithmetic mean (exact int64 sum/count carry).",
    )
    reg.uda(
        "mean",
        (BOOLEAN,),
        FLOAT64,
        init=lambda g: (jnp.zeros(g, dtype=jnp.int64), jnp.zeros(g, dtype=jnp.int64)),
        update=lambda c, gids, mask, v: (
            _seg_sum(c[0], gids, mask, v.astype(jnp.int64)),
            _seg_count(c[1], gids, mask),
        ),
        merge=lambda a, b: (a[0] + b[0], a[1] + b[1]),
        finalize=lambda c: jnp.where(
            c[1] > 0,
            c[0].astype(jnp.float64) / jnp.maximum(c[1], 1).astype(jnp.float64),
            jnp.nan,
        ),
        doc="Fraction of true rows (exact integer carry).",
    )

    def _seg_extreme64(carry, gids, mask, v, neutral, is_max):
        """64-bit int min/max without a 64-bit scatter: two-key sort
        (group id primary, value secondary) makes each segment's extreme
        its first/last element; the group-id sort CSEs with the other
        aggs' _seg_order."""
        g = carry.shape[0]
        n = v.shape[0]
        gi = jnp.where(mask, gids, g).astype(jnp.int32)
        ov = jnp.argsort(v, stable=True).astype(jnp.int32)
        order = ov[jnp.argsort(gi[ov], stable=True).astype(jnp.int32)]
        sv = v[order]
        ends = jnp.searchsorted(
            gi[order], jnp.arange(g, dtype=jnp.int32), side="right"
        ).astype(jnp.int32)
        starts = jnp.concatenate([jnp.zeros(1, ends.dtype), ends[:-1]])
        if is_max:
            val = sv[jnp.clip(ends - 1, 0, max(n - 1, 0))]
        else:
            val = sv[jnp.clip(starts, 0, max(n - 1, 0))]
        upd = jnp.where(ends > starts, val, jnp.full((), neutral, v.dtype))
        return jnp.maximum(carry, upd) if is_max else jnp.minimum(carry, upd)

    def _seg_min(carry, gids, mask, v, neutral):
        g = carry.shape[0]
        if (np.dtype(v.dtype).itemsize > 4
                and jnp.issubdtype(v.dtype, jnp.integer)
                and _sorted_segments()):
            return _seg_extreme64(carry, gids, mask, v, neutral, is_max=False)
        contrib = jnp.where(mask, v, jnp.full((), neutral, v.dtype))
        upd = jax.ops.segment_min(contrib, jnp.where(mask, gids, g), num_segments=g + 1)[:-1]
        return jnp.minimum(carry, upd)

    def _seg_max(carry, gids, mask, v, neutral):
        g = carry.shape[0]
        if (np.dtype(v.dtype).itemsize > 4
                and jnp.issubdtype(v.dtype, jnp.integer)
                and _sorted_segments()):
            return _seg_extreme64(carry, gids, mask, v, neutral, is_max=True)
        contrib = jnp.where(mask, v, jnp.full((), neutral, v.dtype))
        upd = jax.ops.segment_max(contrib, jnp.where(mask, gids, g), num_segments=g + 1)[:-1]
        return jnp.maximum(carry, upd)

    for dt, zd, lo, hi in (
        (INT64, jnp.int64, _I64_MIN, _I64_MAX),
        (FLOAT64, jnp.float64, -jnp.inf, jnp.inf),
        (TIME64NS, jnp.int64, _I64_MIN, _I64_MAX),
    ):
        reg.uda(
            "min",
            (dt,),
            dt,
            init=lambda g, _z=zd, _hi=hi: jnp.full(g, _hi, dtype=_z),
            update=lambda c, gids, mask, v, _hi=hi: _seg_min(c, gids, mask, v, _hi),
            merge=jnp.minimum,
            finalize=lambda c: c,
        )
        reg.uda(
            "max",
            (dt,),
            dt,
            init=lambda g, _z=zd, _lo=lo: jnp.full(g, _lo, dtype=_z),
            update=lambda c, gids, mask, v, _lo=lo: _seg_max(c, gids, mask, v, _lo),
            merge=jnp.maximum,
            finalize=lambda c: c,
        )
