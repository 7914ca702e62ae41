"""Sketch UDAs: t-digest quantiles (and HLL count-distinct).

Reference parity: ``src/carnot/funcs/builtins/math_sketches.h:34``
(QuantilesUDA over tdigest; finalize emits JSON {p01,p10,p25,p50,p75,p90,p99}).
Here the digest is the batched sorted-binning implementation in
``pixie_tpu.ops.tdigest``; finalize yields [G, 7] floats that the host
materializes to JSON (or the planner plucks directly).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ...ops import tdigest
from ...ops.hll import hll_estimate, hll_init, hll_update
from ..udf import FLOAT64, INT64, STRING

QUANTILE_FIELDS = ("p01", "p10", "p25", "p50", "p75", "p90", "p99")
QUANTILE_POINTS = (0.01, 0.10, 0.25, 0.50, 0.75, 0.90, 0.99)


def quantile_points(uda_name: str) -> tuple:
    """The points a digest aggregate's read-out asks of its digest:
    ``quantiles``' seven, a ``_quantile_pXX``'s one. The digest itself is
    the argument's, whichever asks (``exec/fold_plan.py``
    ``digest_owners``)."""
    if uda_name == "quantiles":
        return QUANTILE_POINTS
    return (QUANTILE_POINTS[
        QUANTILE_FIELDS.index(uda_name.removeprefix("_quantile_"))],)


def register(reg):
    reg.uda(
        "quantiles",
        (FLOAT64,),
        STRING,
        init=lambda g: tdigest.digest_init(g),
        update=lambda c, gids, mask, v: tdigest.digest_update(c, gids, mask, v),
        merge=tdigest.digest_merge,
        finalize=lambda c: tdigest.digest_quantile(c, QUANTILE_POINTS),
        struct_fields=QUANTILE_FIELDS,
        doc="Approximate quantiles of the group via a mergeable t-digest.",
        semantic_type=1000,  # SemanticType.ST_QUANTILES (types.proto:84)
    )

    # Direct single-quantile UDAs (not in the reference's registry, but the
    # planner fuses pluck_float64(quantiles(x), 'p99') into these so the
    # hot path never materializes JSON).
    for field, point in zip(QUANTILE_FIELDS, QUANTILE_POINTS):
        reg.uda(
            f"_quantile_{field}",
            (FLOAT64,),
            FLOAT64,
            init=lambda g: tdigest.digest_init(g),
            update=lambda c, gids, mask, v: tdigest.digest_update(c, gids, mask, v),
            merge=tdigest.digest_merge,
            finalize=lambda c, _p=point: tdigest.digest_quantile(c, (_p,))[:, 0],
            doc=f"Approximate {field} of the group via t-digest.",
        )

    for dt in (INT64, STRING):
        reg.uda(
            "count_distinct",
            (dt,),
            INT64,
            init=lambda g: hll_init(g),
            update=lambda c, gids, mask, v: hll_update(c, gids, mask, v),
            merge=jnp.maximum,
            finalize=hll_estimate,
            doc="Approximate distinct count via a mergeable HyperLogLog sketch.",
        )
