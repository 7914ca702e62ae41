"""Which fold a group-by takes: decided once, here, from what the code
observes about one AggOp.

``plan_fold`` is a pure function: it traces nothing, compiles nothing and
imports no kernel, so a test asks it "which route?" in microseconds.
``exec/fragment.py`` ``_compile_agg`` calls it once and builds the one
fold the record names; ``CompiledFragment.fold`` / ``.group`` / ``.slots``
and the engine's choice between the native fold, the scan program and the
per-window loop (``exec/engine.py`` ``_fold_agg_state``) read the same
record. There is no flag that picks a kernel: the platform comes from
``ops/routes.py`` ``routes_platform`` (the fragment cache keys on it), the
sizes from the key columns' static domains, the rest from the aggregates.

Three folds, told apart by ``layout`` and ``payload_sort``:

- ``dense``: every group column has a static domain (dictionary ids,
  booleans, stats-bounded integers) whose product fits the limit; the
  packed key code IS the slot. Each aggregate takes its own route: the
  exact integer Pallas kernel, the f32 one, or XLA (``uda.update``).
- keyed, ``payload_sort``: no dense domain, every aggregate an exact
  integer statistic, an ``any`` the sort carries as a maximum
  (``_sort_max``) or a ``quantiles`` (PR 41), on the TPU: rows ride one
  sort with their keys and values (``ops/groupby.py``
  ``sorted_group_fold``), windows and merges alike, and a ``quantiles``
  argument's rows sort once more by (the same key words, the value) into
  the digest of the same slots (route ``keyed_digest``: ``ops/tdigest.py``
  ``ordered_batch_to_digest``; a merge moves a digest to its group's new
  slot and merges it there). ``layout`` reads ``sorted``.
- keyed, group ids in row order (a FLOAT64 sum needs them, and a
  ``quantiles`` on the CPU): ids by sort on the TPU (``layout``
  ``sorted``), by the bounded hash table on the CPU (``hashed``), then
  ``uda.update``; states merge by regroup + scatter.
"""

from __future__ import annotations

import math
from collections import Counter
from dataclasses import dataclass
from typing import Optional

from ..ops.routes import (
    DIGEST_K,
    F32_FOLD_MAX_GROUPS,
    INT_FOLD_MAX_GROUPS,
    digest_bins,
    digest_route,
    int_fold_groups,
)
from ..types.dtypes import DataType

# Integer-typed key columns that qualify for stats-derived dense domains.
INT_KEY_TYPES = (DataType.INT64, DataType.TIME64NS)

_STATS = ("sum", "mean", "max", "min")


@dataclass(frozen=True)
class FoldPlan:
    #: Whose routes these are (``ops/routes.py`` ``routes_platform``).
    platform: str
    #: How rows find their group: ``dense`` / ``sorted`` / ``hashed``
    #: (``CompiledFragment.group``, the ``group`` span attribute).
    layout: str
    #: The capacity g the programs are compiled at: the domains' product
    #: on a dense layout, else the AggOp's ``max_groups``.
    slots: int
    #: ((out_name, route), ...) in the AggOp's order; a route is
    #: ``pallas_int`` / ``pallas_f32`` / ``xla`` on a dense layout or
    #: under group ids, ``sorted_digest`` for a ``quantiles`` aggregate
    #: whose window digest is built by sorting the rows
    #: (``ops/tdigest.py``), ``sorted_int`` under the payload-carrying
    #: sort and ``keyed_digest`` for a ``quantiles`` beside it.
    routes: tuple
    #: The kernel a dense window's per-slot row count rides (a ``count``
    #: aggregate's route, and where the state's ``valid`` comes from
    #: even without one): the integer kernel wherever it runs, else the
    #: f32 kernel if some aggregate runs it, else ``xla``.
    count_route: str
    #: The label on the fold programs' ``device.dispatch`` spans and the
    #: /debug/queryz entry: the one route, or ``mixed:<route>=<n>,...``.
    fold: str
    #: Dense layout only: per-group-column domain size, value offset and
    #: value stride (``_static_key_domains``).
    domains: tuple = ()
    offsets: tuple = ()
    strides: tuple = ()
    #: The keyed fold is the payload-carrying sort.
    payload_sort: bool = False
    #: Under it, the key planes pack into ONE u32 sort word where the
    #: columns' domains show they fit (per-column sizes), else None.
    pack_doms: Optional[tuple] = None
    #: Unpacked, a leading dictionary id still spares the flag operand:
    #: ids are >= NULL_ID (-1), so id + 1 never reads 0xFFFFFFFF.
    lead_id: bool = False
    #: Under it, the u32 words of the maxima the sorts carry as keys
    #: (``_sort_max`` over the aggregates): the ``max_words`` attribute
    #: of the fold programs' ``device.dispatch`` spans.
    max_words: int = 0
    #: The ``quantiles`` aggregates' digests (``digests``, ``digest_slots``
    #: and ``digest_bins`` on the fold programs' ``device.dispatch``
    #: spans): how many [slots, K] carries the state holds (one an
    #: argument: the digest aggregates of one argument share theirs,
    #: ``digest_owners``), one's groups x centroids, and the width B a
    #: window's rows are binned at (``routes.digest_bins``: a histogram's
    #: or a (slot, bin) sort's width, ``KEYED_DIGEST_BINS`` where a sort
    #: orders the values themselves). 0 without one.
    digests: int = 0
    digest_slots: int = 0
    digest_bins: int = 0
    #: The digest aggregates, each with the output whose name its carry
    #: is held under in ``state["carries"]``: ((out_name, owner), ...) in
    #: the AggOp's order (``digest_owners``). An owner is its own; the
    #: others hold no carry and read the owner's. Their count is the
    #: spans' ``digest_outputs``.
    digest_owners: tuple = ()


def is_digest(uda_name: str) -> bool:
    """A t-digest aggregate (``udf/builtins/math_sketches.py``):
    ``quantiles`` or one of the planner's ``_quantile_pXX``."""
    return uda_name == "quantiles" or uda_name.startswith("_quantile_")


def digest_owners(aggs) -> tuple:
    """((out_name, owner), ...) of an AggOp's digest aggregates: those of
    one argument (the fourth field of an ``aggs`` entry: the argument
    expression's structure, equal where the expressions are) under one
    cast share ONE carry, held under the first one's name. A digest is
    its argument's and nobody's point (a ``_quantile_pXX`` differs from
    ``quantiles`` only in what its read-out asks of it), so the shared
    state is each of theirs value for value. An entry with no argument
    field is not known to share and keeps a carry of its own."""
    first, owners = {}, []
    for out, uda, types, *arg in aggs:
        if is_digest(uda):
            key = (arg[0], types) if arg else (out,)
            owners.append((out, first.setdefault(key, out)))
    return tuple(owners)


def _int_stat(uda_name: str, arg_types: tuple) -> bool:
    """An exact integer statistic: sum / mean / max / min of one INT64 /
    TIME64NS argument, sum / mean of a BOOLEAN one (the set both integer
    folds take: the dense kernel and the keyed sort)."""
    if uda_name in _STATS and len(arg_types) == 1:
        want = arg_types[0]
        return want in INT_KEY_TYPES or (
            want == DataType.BOOLEAN and uda_name in ("sum", "mean")
        )
    return False


def _sort_max(uda_name: str, arg_types: tuple) -> int:
    """The u32 words of the maximum the keyed sort carries for this
    aggregate, 0 where it carries none: ``max`` / ``min`` of an INT64 /
    TIME64NS (two), and ``any`` (``udf/builtins/collections.py``: a
    segment maximum) of one (two) or of a STRING (one: the maximum of
    its int32 dictionary ids)."""
    if len(arg_types) != 1:
        return 0
    if uda_name in ("max", "min"):
        return 2 if arg_types[0] in INT_KEY_TYPES else 0
    if uda_name == "any":
        return (1 if arg_types[0] == DataType.STRING
                else 2 if arg_types[0] in INT_KEY_TYPES else 0)
    return 0


def plan_fold(group_cols, domains, aggs, *, max_groups: int,
              allow_dense: bool, dense_limit: int, int_dense_limit: int,
              platform: str) -> FoldPlan:
    """The fold of one AggOp.

    ``group_cols``: ((name, DataType), ...). ``domains``: the columns'
    (size, offset, stride) triples (``_static_key_domains``) or None when
    any is not known at compile time. ``aggs``: ((out_name, uda_name,
    cast argument types[, the argument expression's structure]), ...);
    the fourth field is what tells two digests of one argument
    (``digest_owners``). ``allow_dense`` False is the Kelvin's
    fragment: it merges ids remapped into a dictionary it was not
    compiled against, so it trusts no domain (no dense slot, no packed
    sort word)."""
    if not (allow_dense and group_cols):
        domains = None
    g = max_groups
    dense = False
    if domains is not None:
        total = math.prod(d for d, _off, _st in domains)
        has_int = any(
            off or dt in INT_KEY_TYPES
            for (_d, off, _st), (_c, dt) in zip(domains, group_cols)
        )
        # The larger int budget is justified only for a SINGLE int key
        # (no multi-key packing blowup); mixed/multi-key domains stay
        # under the base limit.
        limit = (
            int_dense_limit if has_int and len(group_cols) == 1
            else dense_limit
        )
        if total <= limit:
            dense, g = True, total

    tpu = platform == "tpu"
    # Above the measured cross-over the one-hot (rows x G) loses to the
    # sort: those domains keep the XLA fold.
    int_ok = dense and tpu and int_fold_groups(g) <= INT_FOLD_MAX_GROUPS
    f32_ok = dense and tpu and g <= F32_FOLD_MAX_GROUPS

    def route(uda_name, arg_types):
        if _int_stat(uda_name, arg_types):
            return "pallas_int" if int_ok else "xla"
        if (uda_name in _STATS and len(arg_types) == 1
                and arg_types[0] == DataType.FLOAT64):
            return "pallas_f32" if f32_ok else "xla"
        if is_digest(uda_name):
            # Dense or under group ids alike: ``uda.update`` takes the ids
            # and asks the same function (``ops/tdigest.py``).
            return digest_route(platform, g * DIGEST_K)
        return "xla"

    owners = digest_owners(aggs)
    aggs = tuple(a[:3] for a in aggs)
    routes = {
        out: route(uda, types) for out, uda, types in aggs if uda != "count"
    }
    # A count reads no argument: it rides the kernel that runs anyway
    # (the integer one's count is i32-exact, so it is preferred).
    kernels = set(routes.values()) - {"xla", "sorted_digest"}
    count_route = (
        "pallas_int" if int_ok and kernels != {"pallas_f32"}
        else "pallas_f32" if "pallas_f32" in kernels
        else "xla"
    )
    for out, uda, _types in aggs:
        if uda == "count":
            routes[out] = count_route

    # Keyed integer fold: chosen where the key has no dense domain, every
    # aggregate is exact-integer, an ``any`` that is a maximum of
    # integers (dictionary ids are) or a ``quantiles`` (its digest is
    # built by a sort of its own under the same key words), and the
    # platform sorts. Anything else keeps group ids in row order, which a
    # FLOAT64 sum needs.
    payload_sort = (
        not dense and bool(group_cols) and tpu
        and all(uda == "count" or _int_stat(uda, types)
                or _sort_max(uda, types) or is_digest(uda)
                for _out, uda, types in aggs)
    )
    pack_doms = None
    lead_id = False
    if payload_sort:
        routes = {
            out: "keyed_digest" if is_digest(uda) else "sorted_int"
            for out, uda, _types in aggs
        }
        # Dictionary ids and booleans pack exactly, as the dense route
        # trusts them (33 x 65,537 codes are 22 bits); the top bit is
        # left for "not valid".
        if (
            domains is not None
            and all(dt not in INT_KEY_TYPES for _c, dt in group_cols)
            and math.prod(d for d, _off, _st in domains) < (1 << 31) - 1
        ):
            pack_doms = tuple(d for d, _off, _st in domains)
        else:
            lead_id = group_cols[0][1] == DataType.STRING

    tally = Counter(routes.values())
    fold = (
        next(iter(tally), "sorted_int" if payload_sort else "xla")
        if len(tally) <= 1
        else "mixed:" + ",".join(
            f"{r}={tally[r]}"
            for r in ("pallas_int", "pallas_f32", "sorted_int",
                      "sorted_digest", "keyed_digest", "xla")
            if r in tally
        )
    )
    digests = len({owner for _out, owner in owners})
    return FoldPlan(
        platform=platform,
        layout="dense" if dense else "sorted" if tpu else "hashed",
        slots=g,
        routes=tuple((out, routes[out]) for out, _uda, _types in aggs),
        count_route=count_route,
        fold=fold,
        domains=tuple(d for d, _off, _st in domains) if dense else (),
        offsets=tuple(off for _d, off, _st in domains) if dense else (),
        strides=tuple(st for _d, _off, st in domains) if dense else (),
        payload_sort=payload_sort,
        pack_doms=pack_doms,
        lead_id=lead_id,
        max_words=sum(
            _sort_max(uda, types) for _out, uda, types in aggs
        ) if payload_sort else 0,
        digests=digests,
        digest_slots=g * DIGEST_K if digests else 0,
        digest_bins=digest_bins(g, payload_sort) if digests else 0,
        digest_owners=owners,
    )
