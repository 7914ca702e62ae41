"""The group-by's one decision (``exec/fold_plan.py`` ``plan_fold``): a
table of AggOps asked "which fold?" on both platforms, with nothing traced
or compiled, and the fragment cache keyed on the platform asked."""

import subprocess
import sys

import pytest

from conftest import routes_of
from pixie_tpu.exec.fold_plan import FoldPlan, digest_owners, plan_fold
from pixie_tpu.ops.routes import (
    DIGEST_K, F32_FOLD_MAX_GROUPS, INT_FOLD_MAX_GROUPS,
    SORTED_DIGEST_MAX_SLOTS, int_fold_groups,
)
from pixie_tpu.types.dtypes import DataType as D

I64, F64, BOOL, T64 = (D.INT64,), (D.FLOAT64,), (D.BOOLEAN,), (D.TIME64NS,)
SVC, PATH = ("service", D.STRING), ("req_path", D.STRING)
# An aggregate's fourth field: its argument expression's structure (what
# ``exec/fragment.py`` ``_struct_key`` gives; any hashable does here).
LAT, SIZE = ("col", "latency_ns"), ("col", "resp_body_size")
# px/http_stats' and px/service_stats' aggregates.
HTTP = (("n", "count", I64), ("lat_mean", "mean", I64), ("lat_max", "max", I64))
SERVICE = (("err", "mean", BOOL), ("n", "count", I64),
           ("p50", "_quantile_p50", F64, LAT),
           ("p99", "_quantile_p99", F64, LAT))
DENSE_2145 = [(33, 0, 1), (65, 0, 1)]  # the dense cells' dictionaries
KEYED = [(33, 0, 1), (65_537, 0, 1)]  # http_full_1chip's: over the limit
# http_edges_1chip's service graph (benchmark/traffic/graph_recent).
EDGE_KEYS = (("remote_addr", D.STRING), ("pod", D.STRING), SVC)
EDGE_DOMAINS = [(8_193, 0, 1), (4_097, 0, 1), (33, 0, 1)]
EDGES = (("latency_p50", "_quantile_p50", F64, LAT),
         ("latency_p90", "_quantile_p90", F64, LAT),
         ("latency_p99", "_quantile_p99", F64, LAT),
         ("error_rate", "mean", BOOL), ("throughput_total", "count", I64),
         ("outbound_bytes_total", "sum", I64))


def _plan(group_cols, domains, aggs, platform, max_groups=4096,
          allow_dense=True) -> FoldPlan:
    return plan_fold(
        tuple(group_cols), domains, tuple(aggs), max_groups=max_groups,
        allow_dense=allow_dense, dense_limit=1 << 20,
        int_dense_limit=1 << 23, platform=platform,
    )


def _routes(plan):
    return tuple(r for _out, r in plan.routes)


# (id, group cols, domains, aggregates,
#  tpu: (layout, slots, routes, count_route, fold),
#  cpu: (layout, routes, fold)): on the CPU nothing takes a kernel or the
#  payload sort, whatever the AggOp.
DENSE_CASES = [
    ("http_stats_2145", (SVC, PATH), DENSE_2145, HTTP,
     ("dense", 2145, ("pallas_int",) * 3, "pallas_int", "pallas_int")),
    ("service_stats_33", (SVC,), [(33, 0, 1)], SERVICE,
     ("dense", 33,
      ("pallas_int", "pallas_int", "sorted_digest", "sorted_digest"),
      "pallas_int", "mixed:pallas_int=2,sorted_digest=2")),
    ("at_the_cross_over", (PATH,), [(INT_FOLD_MAX_GROUPS, 0, 1)], HTTP,
     ("dense", INT_FOLD_MAX_GROUPS, ("pallas_int",) * 3, "pallas_int",
      "pallas_int")),
    ("over_the_cross_over", (PATH,), [(INT_FOLD_MAX_GROUPS + 1, 0, 1)], HTTP,
     ("dense", INT_FOLD_MAX_GROUPS + 1, ("xla",) * 3, "xla", "xla")),
    ("integer_alone", (SVC,), [(33, 0, 1)], (("s", "sum", I64),),
     ("dense", 33, ("pallas_int",), "pallas_int", "pallas_int")),
    ("time_extremes", (SVC,), [(33, 0, 1)],
     (("first", "min", T64), ("last", "max", T64)),
     ("dense", 33, ("pallas_int",) * 2, "pallas_int", "pallas_int")),
    ("boolean_sum_not_max", (SVC,), [(33, 0, 1)],
     (("e", "sum", BOOL), ("any", "max", BOOL)),
     ("dense", 33, ("pallas_int", "xla"), "pallas_int",
      "mixed:pallas_int=1,xla=1")),
    # A FLOAT64 argument alone: the count rides the f32 kernel with it.
    ("float_alone", (SVC,), [(33, 0, 1)],
     (("s", "sum", F64), ("n", "count", F64)),
     ("dense", 33, ("pallas_f32",) * 2, "pallas_f32", "pallas_f32")),
    ("float_at_2048", (PATH,), [(F32_FOLD_MAX_GROUPS, 0, 1)],
     (("mx", "max", F64),),
     ("dense", F32_FOLD_MAX_GROUPS, ("pallas_f32",), "pallas_f32",
      "pallas_f32")),
    # Above the f32 kernel's limit the sum stays on XLA; the count still
    # rides the integer kernel, which runs for it alone.
    ("float_over_2048", (SVC, PATH), DENSE_2145,
     (("s", "sum", F64), ("n", "count", F64)),
     ("dense", 2145, ("xla", "pallas_int"), "pallas_int",
      "mixed:pallas_int=1,xla=1")),
    ("integer_and_float", (SVC,), [(33, 0, 1)],
     (("si", "sum", I64), ("sf", "mean", F64), ("n", "count", I64)),
     ("dense", 33, ("pallas_int", "pallas_f32", "pallas_int"), "pallas_int",
      "mixed:pallas_int=2,pallas_f32=1")),
    # A ``quantiles`` alone vetoes nothing: the slots' row count (the
    # state's ``valid``) still comes from the integer kernel. Its window
    # digest is built by sorting the rows (``ops/tdigest.py``) while the
    # groups' centroids fit the reduction's accumulators, by scatters
    # above that.
    ("quantiles_alone", (SVC,), [(33, 0, 1)], (("q", "quantiles", F64),),
     ("dense", 33, ("sorted_digest",), "pallas_int", "sorted_digest")),
    ("quantiles_at_the_accumulators_limit", (PATH,),
     [(SORTED_DIGEST_MAX_SLOTS // DIGEST_K, 0, 1)],
     (("q", "_quantile_p99", F64),),
     ("dense", SORTED_DIGEST_MAX_SLOTS // DIGEST_K, ("sorted_digest",),
      "pallas_int", "sorted_digest")),
    ("quantiles_over_the_accumulators_limit", (PATH,),
     [(SORTED_DIGEST_MAX_SLOTS // DIGEST_K + 1, 0, 1)],
     (("q", "_quantile_p99", F64),),
     ("dense", SORTED_DIGEST_MAX_SLOTS // DIGEST_K + 1, ("xla",),
      "pallas_int", "xla")),
    ("two_arguments_stay_on_xla", (SVC,), [(33, 0, 1)],
     (("c", "sum", (D.INT64, D.INT64)),),
     ("dense", 33, ("xla",), "pallas_int", "xla")),
    # One integer key has the larger budget; beside a second key it has not.
    ("single_int_key", (("shard", D.INT64),), [(1 << 22, -7, 1)], HTTP,
     ("dense", 1 << 22, ("xla",) * 3, "xla", "xla")),
]


@pytest.mark.parametrize(
    "group_cols,domains,aggs,tpu", [c[1:] for c in DENSE_CASES],
    ids=[c[0] for c in DENSE_CASES])
def test_a_dense_domain(group_cols, domains, aggs, tpu):
    layout, slots, routes, count_route, fold = tpu
    plan = _plan(group_cols, domains, aggs, "tpu")
    assert (plan.layout, plan.slots, _routes(plan), plan.count_route,
            plan.fold) == (layout, slots, routes, count_route, fold)
    assert plan.domains == tuple(d for d, _o, _s in domains)
    assert not plan.payload_sort and plan.pack_doms is None
    cpu = _plan(group_cols, domains, aggs, "cpu")
    assert (cpu.layout, cpu.slots, cpu.count_route, cpu.fold) == (
        "dense", slots, "xla", "xla")
    assert set(_routes(cpu)) == {"xla"}
    assert (cpu.domains, cpu.offsets, cpu.strides) == (
        plan.domains, plan.offsets, plan.strides)
    assert [out for out, _r in plan.routes] == [a[0] for a in aggs]


def test_a_dense_domains_offsets_and_strides_pass_through():
    plan = _plan((("minute", T64),), [(60, 1_000, 60_000_000_000)], HTTP,
                 "tpu")
    assert (plan.domains, plan.offsets, plan.strides) == (
        (60,), (1_000,), (60_000_000_000,))


def test_the_integer_kernels_padding_decides_the_cross_over():
    """The gate is on the PADDED group count: one group over a block
    boundary costs a block, and the last block under the limit counts."""
    g = INT_FOLD_MAX_GROUPS - 1_023  # pads to the limit itself
    assert int_fold_groups(g) == INT_FOLD_MAX_GROUPS
    assert _plan((PATH,), [(g, 0, 1)], HTTP, "tpu").fold == "pallas_int"


# (id, group cols, domains, aggregates, extra arguments,
#  tpu: (payload_sort, pack_doms, lead_id, fold), cpu's fold is ``xla``).
KEYED_CASES = [
    ("http_stats_packed", (SVC, PATH), KEYED, HTTP, {"max_groups": 1 << 17},
     (True, (33, 65_537), False, "sorted_int")),
    # The Kelvin's fragment trusts no domain: planes as they are, the
    # leading dictionary id sparing the flag operand.
    ("kelvin_unpacked_lead_id", (SVC, PATH), KEYED, HTTP,
     {"allow_dense": False}, (True, None, True, "sorted_int")),
    ("kelvin_small_domains_stay_keyed", (SVC, PATH), DENSE_2145, HTTP,
     {"allow_dense": False}, (True, None, True, "sorted_int")),
    ("unknown_domain", (SVC, PATH), None, HTTP, {},
     (True, None, True, "sorted_int")),
    ("an_integer_key_does_not_pack", (SVC, ("shard", D.INT64)),
     [(33, 0, 1), (1 << 21, 0, 1)], HTTP, {},
     (True, None, True, "sorted_int")),
    ("integer_key_first_no_lead_id", (("shard", D.INT64), SVC), None, HTTP,
     {}, (True, None, False, "sorted_int")),
    ("too_wide_for_one_word", (PATH, ("peer", D.STRING)),
     [(65_537, 0, 1), (65_537, 0, 1)], HTTP, {},
     (True, None, True, "sorted_int")),
    ("two_keys_have_the_base_budget", (("shard", D.INT64), ("ok", D.BOOLEAN)),
     [(1 << 20, 0, 1), (2, 0, 1)], HTTP, {},
     (True, None, False, "sorted_int")),
    # px/sql_stats' two COMPUTED keys: the ids of a dictionary a UDF made
    # at bind time (290 shapes) and a one-second bin of ``time_`` whose
    # domain the table's stats give (4,096 steps): known domains, over the
    # base budget together, and an integer key does not pack, so the
    # planes sort as they are behind the leading id, on the PEM and, with
    # no domain trusted, on the Kelvin.
    ("two_computed_keys", (("query_norm", D.STRING), ("window", D.TIME64NS)),
     [(291, 0, 1), (4_096, 1_699_996_000_000_000_000, 1_000_000_000)],
     (("n", "count", I64), ("lat_mean", "mean", I64)),
     {"max_groups": 1 << 17}, (True, None, True, "sorted_int")),
    ("two_computed_keys_on_the_kelvin",
     (("query_norm", D.STRING), ("window", D.TIME64NS)),
     [(291, 0, 1), (4_096, 1_699_996_000_000_000_000, 1_000_000_000)],
     (("n", "count", I64), ("lat_mean", "mean", I64)),
     {"max_groups": 1 << 17, "allow_dense": False},
     (True, None, True, "sorted_int")),
    ("count_alone", (SVC, PATH), KEYED, (("n", "count", I64),), {},
     (True, (33, 65_537), False, "sorted_int")),
    # px/perf_flamegraph's: ``any`` is a segment maximum, of a STRING's
    # int32 dictionary ids or of an INT64, so it rides the sort as a
    # maximum does, on the PEM (2^20 slots) and on the Kelvin; of a
    # FLOAT64 or a BOOLEAN it keeps the id form.
    ("any_of_a_string_beside_a_sum",
     (("pod", D.STRING), ("stack_trace_id", D.INT64)), None,
     (("stack_trace", "any", (D.STRING,)), ("count", "sum", I64)),
     {"max_groups": 1 << 20}, (True, None, True, "sorted_int")),
    ("any_of_a_string_on_the_kelvin",
     (("pod", D.STRING), ("stack_trace_id", D.INT64)), None,
     (("stack_trace", "any", (D.STRING,)), ("count", "sum", I64)),
     {"max_groups": 1 << 20, "allow_dense": False},
     (True, None, True, "sorted_int")),
    ("any_of_an_int64_beside_a_sum", (SVC, PATH), KEYED,
     (("first", "any", I64), ("seen", "any", T64), ("s", "sum", I64)), {},
     (True, (33, 65_537), False, "sorted_int")),
    ("any_of_a_float", (SVC, PATH), KEYED,
     (("x", "any", F64), ("s", "sum", I64)), {}, (False, None, False, "xla")),
    ("any_of_a_boolean", (SVC, PATH), KEYED, (("x", "any", BOOL),), {},
     (False, None, False, "xla")),
    # A ``quantiles`` rides nothing and needs no row's group id either
    # (PR 41): the integer aggregates keep the payload sort and its
    # digest is built by a sort of its own under the same key words,
    # whatever the slots.
    ("with_a_quantiles", (SVC, PATH), KEYED,
     HTTP + (("q", "quantiles", F64),), {},
     (True, (33, 65_537), False, "mixed:sorted_int=3,keyed_digest=1")),
    ("with_a_quantiles_at_the_cells_slots", (SVC, PATH), KEYED,
     HTTP + (("q", "quantiles", F64),), {"max_groups": 1 << 17},
     (True, (33, 65_537), False, "mixed:sorted_int=3,keyed_digest=1")),
    # http_edges_1chip's AggOp: three plucked quantiles of one column, a
    # BOOLEAN mean, a count and an INT64 sum on three dictionary keys
    # that pack into one word (8,193 x 4,097 x 33 codes), on the PEM; on
    # the Kelvin, which trusts no domain, behind the leading id.
    ("the_service_graph", EDGE_KEYS, EDGE_DOMAINS, EDGES,
     {"max_groups": 1 << 17},
     (True, (8_193, 4_097, 33), False, "mixed:sorted_int=3,keyed_digest=3")),
    ("the_service_graph_on_the_kelvin", EDGE_KEYS, EDGE_DOMAINS, EDGES,
     {"max_groups": 1 << 17, "allow_dense": False},
     (True, None, True, "mixed:sorted_int=3,keyed_digest=3")),
    ("quantiles_alone_by_a_key", (SVC, PATH), KEYED,
     (("q", "_quantile_p99", F64),), {},
     (True, (33, 65_537), False, "keyed_digest")),
    # An aggregate that needs a row's group id keeps the id form.
    ("with_a_float_sum", (SVC, PATH), KEYED, HTTP + (("s", "sum", F64),), {},
     (False, None, False, "xla")),
    ("with_a_boolean_max", (SVC, PATH), KEYED, (("any", "max", BOOL),), {},
     (False, None, False, "xla")),
    ("no_key_at_all", (), None, HTTP, {}, (False, None, False, "xla")),
]


@pytest.mark.parametrize(
    "group_cols,domains,aggs,kw,tpu", [c[1:] for c in KEYED_CASES],
    ids=[c[0] for c in KEYED_CASES])
def test_a_keyed_state(group_cols, domains, aggs, kw, tpu):
    payload_sort, pack_doms, lead_id, fold = tpu
    plan = _plan(group_cols, domains, aggs, "tpu", **kw)
    assert (plan.layout, plan.slots) == ("sorted", kw.get("max_groups", 4096))
    assert (plan.payload_sort, plan.pack_doms, plan.lead_id, plan.fold) == tpu
    assert set(_routes(plan)) - {"sorted_digest", "keyed_digest"} <= {
        "sorted_int" if payload_sort else "xla"}
    assert ("keyed_digest" in _routes(plan)) == bool(
        payload_sort and plan.digests)
    assert plan.count_route == "xla" and plan.domains == ()
    cpu = _plan(group_cols, domains, aggs, "cpu", **kw)
    assert (cpu.layout, cpu.slots, cpu.fold) == ("hashed", plan.slots, "xla")
    assert not cpu.payload_sort and cpu.pack_doms is None and not cpu.lead_id
    assert set(_routes(cpu)) == {"xla"}


@pytest.mark.parametrize("aggs,words", [
    (HTTP, 2),  # px/http_stats' one INT64 maximum
    ((("n", "count", I64), ("m", "mean", I64)), 0),
    ((("stack_trace", "any", (D.STRING,)), ("count", "sum", I64)), 1),
    ((("a", "any", I64), ("b", "min", T64), ("c", "any", (D.STRING,))), 5),
])
def test_the_words_of_the_maxima_the_sort_carries(aggs, words):
    """``max_words`` (the span's attribute): two an INT64 maximum,
    minimum or ``any``, one an ``any`` of dictionary ids; 0 off the
    sorted fold."""
    assert _plan((SVC, PATH), KEYED, aggs, "tpu").max_words == words
    assert _plan((SVC, PATH), KEYED, aggs, "cpu").max_words == 0
    assert _plan((SVC,), [(33, 0, 1)], aggs, "tpu").max_words == 0


@pytest.mark.parametrize("platform,groups,allow_dense,want", [
    # The cell's fold: ONE carry of 2^17 x 128 slots for the three plucked
    # quantiles of one column, rows ordered by their values (no
    # histogram), on the PEM and on the Kelvin.
    ("tpu", 1 << 17, True, (1, 1 << 24, 1 << 32)),
    ("tpu", 1 << 17, False, (1, 1 << 24, 1 << 32)),
    # The CPU's id form at that size bins nothing either: a histogram
    # would be 256 bins wide.
    ("cpu", 1 << 17, True, (1, 1 << 24, 1 << 32)),
    # Small enough for a histogram: 8,192 bins to 4,096 groups, 4,096 at
    # 8,192.
    ("cpu", 4096, True, (1, 4096 * 128, 8192)),
    ("cpu", 8192, True, (1, 8192 * 128, 4096)),
])
def test_the_digests_of_a_keyed_fold(platform, groups, allow_dense, want):
    """``digests`` (the carries: one an argument) / ``digest_slots`` /
    ``digest_bins`` (the dispatch span's attributes), the outputs that
    read them, and which aggregates ride the sort."""
    plan = _plan(EDGE_KEYS, EDGE_DOMAINS, EDGES, platform,
                 max_groups=groups, allow_dense=allow_dense)
    assert (plan.digests, plan.digest_slots, plan.digest_bins) == want
    assert plan.digest_owners == tuple(
        (out, "latency_p50")
        for out in ("latency_p50", "latency_p90", "latency_p99"))
    if platform == "tpu":
        assert _routes(plan) == ("keyed_digest",) * 3 + ("sorted_int",) * 3
    else:
        assert set(_routes(plan)) == {"xla"}


def test_a_dense_fold_says_its_digests_too():
    plan = _plan((SVC,), [(33, 0, 1)], SERVICE, "tpu")
    assert (plan.digests, plan.digest_slots, plan.digest_bins) == (
        1, 33 * DIGEST_K, 8192)
    assert plan.digest_owners == (("p50", "p50"), ("p99", "p50"))
    none = _plan((SVC, PATH), KEYED, HTTP, "tpu")
    assert (none.digests, none.digest_owners) == (0, ())


def _pluck(point, arg, types=F64):
    return (point, f"_quantile_{point}", types, arg)


# (id, the digest aggregates, the carries: {owner: its readers}).
OWNER_CASES = [
    ("one_pluck", (_pluck("p50", LAT),), {"p50": ("p50",)}),
    ("two_plucks", (_pluck("p50", LAT), _pluck("p99", LAT)),
     {"p50": ("p50", "p99")}),
    ("three_plucks",
     (_pluck("p50", LAT), _pluck("p90", LAT), _pluck("p99", LAT)),
     {"p50": ("p50", "p90", "p99")}),
    # The first of an argument owns, whichever kind it is.
    ("unplucked_beside_plucks",
     (_pluck("p50", LAT), ("q", "quantiles", F64, LAT), _pluck("p99", LAT)),
     {"p50": ("p50", "q", "p99")}),
    ("unplucked_first",
     (("q", "quantiles", F64, LAT), _pluck("p99", LAT)),
     {"q": ("q", "p99")}),
    ("two_arguments",
     (_pluck("p50", LAT), ("size_p50", "_quantile_p50", F64, SIZE),
      _pluck("p99", LAT), ("size_p99", "_quantile_p99", F64, SIZE)),
     {"p50": ("p50", "p99"), "size_p50": ("size_p50", "size_p99")}),
    ("one_argument_two_casts",
     (_pluck("p50", LAT), _pluck("p99", LAT, I64)),
     {"p50": ("p50",), "p99": ("p99",)}),
    # No argument field: not known to share, a carry each.
    ("arguments_not_told",
     (("p50", "_quantile_p50", F64), ("p99", "_quantile_p99", F64)),
     {"p50": ("p50",), "p99": ("p99",)}),
]


@pytest.mark.parametrize("platform", ["tpu", "cpu"])
@pytest.mark.parametrize(
    "digests,carries", [c[1:] for c in OWNER_CASES],
    ids=[c[0] for c in OWNER_CASES])
def test_the_digests_of_one_argument_share_a_carry(digests, carries, platform):
    """The owner record (``digest_owners``, decided beside ``plan_fold``
    from the AggOp alone): the digests of one argument under one cast
    hold ONE carry under the first one's name; ``digests`` counts the
    carries; the routes stay one an output."""
    aggs = (("n", "count", I64),) + digests + (("s", "sum", I64),)
    want = tuple((out, own) for own, outs in carries.items() for out in outs)
    assert sorted(digest_owners(aggs)) == sorted(want)
    for keys, domains in (((SVC,), [(33, 0, 1)]), ((SVC, PATH), KEYED)):
        plan = _plan(keys, domains, aggs, platform)
        assert sorted(plan.digest_owners) == sorted(want)
        assert [o for o, _own in plan.digest_owners] == [
            a[0] for a in digests]  # the AggOp's order
        assert plan.digests == len(carries)
        assert plan.digest_slots == plan.slots * DIGEST_K
        assert len(plan.routes) == len(aggs)
        reads = {out for out, own in plan.digest_owners if out != own}
        assert {a[0] for a in aggs} - reads == {"n", "s"} | set(carries)
        n = len(digests)
        if platform == "tpu" and len(keys) == 2:
            assert plan.fold == f"mixed:sorted_int=2,keyed_digest={n}"


def _compiled(aggs, keys=("service",), platform="tpu", **kw):
    import pixie_tpu  # noqa: F401
    from pixie_tpu.exec.fragment import compile_fragment
    from pixie_tpu.exec.plan import AggExpr, AggOp
    from pixie_tpu.types.relation import Relation
    from pixie_tpu.types.strings import StringDictionary
    from pixie_tpu.udf.registry import default_registry

    rel = Relation([("latency_ns", D.INT64), ("resp_body_size", D.INT64),
                    ("ratio", D.FLOAT64), ("service", D.STRING),
                    ("req_path", D.STRING)])
    dicts = {"service": StringDictionary(f"s{i}" for i in range(32)),
             "req_path": StringDictionary(f"p{i}" for i in range(70_000))}
    with routes_of(platform):
        return compile_fragment(
            [AggOp(tuple(keys), tuple(AggExpr(*a) for a in aggs),
                   max_groups=256)],
            rel, dicts, default_registry(), **kw)


def _exprs():
    from pixie_tpu.exec.plan import ColumnRef, FuncCall, Literal

    lat, size = ColumnRef("latency_ns"), ColumnRef("resp_body_size")

    def ms(col):
        return FuncCall("divide", (col, Literal(1_000_000, D.INT64)))

    return lat, size, ms


# (id, aggregates as (out, uda, argument), the state's digest carries).
FRAGMENT_CASES = [
    ("three_plucks_of_a_column",
     lambda lat, size, ms: (("p50", "_quantile_p50", (lat,)),
                            ("n", "count", (lat,)),
                            ("p90", "_quantile_p90", (lat,)),
                            ("p99", "_quantile_p99", (lat,))),
     {"p50"}),
    ("an_expression_spelled_twice",
     lambda lat, size, ms: (("p50", "_quantile_p50", (ms(lat),)),
                            ("p99", "_quantile_p99", (ms(lat),)),
                            ("raw", "_quantile_p99", (lat,))),
     {"p50", "raw"}),
    ("two_columns",
     lambda lat, size, ms: (("p50", "_quantile_p50", (lat,)),
                            ("s50", "_quantile_p50", (size,)),
                            ("p99", "_quantile_p99", (lat,)),
                            ("q", "quantiles", (size,))),
     {"p50", "s50"}),
]


@pytest.mark.parametrize("layout", ["dense", "sorted", "kelvin", "hashed"])
@pytest.mark.parametrize(
    "build,owners", [c[1:] for c in FRAGMENT_CASES],
    ids=[c[0] for c in FRAGMENT_CASES])
def test_a_fragments_state_holds_one_digest_an_argument(build, owners, layout):
    """Through ``compile_fragment``: the argument's structure is read
    off the AggOp's expressions, the state holds the owners' carries
    and no reader's, and the PEM's and the Kelvin's fragment of one
    chain derive the same owners."""
    aggs = build(*_exprs())
    keys, platform, kw = {
        "dense": (("service",), "tpu", {}),
        "sorted": (("service", "req_path"), "tpu", {}),
        "kelvin": (("service", "req_path"), "tpu", {"allow_dense": False}),
        "hashed": (("service", "req_path"), "cpu", {}),
    }[layout]
    frag = _compiled(aggs, keys, platform, **kw)
    plan = frag.plan
    assert plan.layout == layout.replace("kelvin", "sorted")
    digests = [a[0] for a in aggs if a[1] != "count"]
    assert [o for o, _own in plan.digest_owners] == digests
    assert {own for _o, own in plan.digest_owners} == owners
    assert plan.digests == len(owners)
    carries = frag.init_state()["carries"]
    assert set(carries) == owners | {a[0] for a in aggs if a[1] == "count"}
    for own in owners:
        means, weights = carries[own]
        assert means.shape == weights.shape == (plan.slots, DIGEST_K)


def test_the_record_is_frozen_and_names_its_platform():
    plan = _plan((SVC,), [(33, 0, 1)], HTTP, "tpu")
    assert plan.platform == "tpu"
    with pytest.raises(AttributeError):
        plan.layout = "hashed"


def test_the_fragment_cache_key_separates_the_platforms():
    """One chain compiled under each platform's routes: two fragments,
    each stamped with its platform; asked again, each is found."""
    import pixie_tpu  # noqa: F401
    from pixie_tpu.exec.fragment import compile_fragment_cached
    from pixie_tpu.exec.plan import AggExpr, AggOp, ColumnRef
    from pixie_tpu.types.relation import Relation
    from pixie_tpu.types.strings import StringDictionary
    from pixie_tpu.udf.registry import default_registry

    rel = Relation([("latency_ns", D.INT64), ("service", D.STRING)])
    dicts = {"service": StringDictionary(f"s{i}" for i in range(32))}
    lat = (ColumnRef("latency_ns"),)
    ops = [AggOp(("service",), (AggExpr("n", "count", lat),
                                AggExpr("m", "mean", lat)))]

    def compiled(platform):
        with routes_of(platform):
            return compile_fragment_cached(ops, rel, dicts, default_registry())

    cpu, tpu = compiled("cpu"), compiled("tpu")
    assert cpu is not tpu
    assert (cpu.plan.platform, cpu.fold) == ("cpu", "xla")
    assert (tpu.plan.platform, tpu.fold) == ("tpu", "pallas_int")
    assert (tpu.group, tpu.slots) == (tpu.plan.layout, tpu.plan.slots) == (
        "dense", 33)
    assert compiled("cpu") is cpu and compiled("tpu") is tpu


def test_asking_imports_no_kernel():
    code = (
        "import sys; import pixie_tpu.exec.fold_plan as fp;"
        "from pixie_tpu.types.dtypes import DataType as D;"
        "p = fp.plan_fold((('s', D.STRING),), [(33, 0, 1)],"
        " (('n', 'count', (D.INT64,)),), max_groups=64, allow_dense=True,"
        " dense_limit=1 << 20, int_dense_limit=1 << 23, platform='tpu');"
        "assert p.fold == 'pallas_int', p;"
        "bad = [m for m in sys.modules if 'pallas' in m]; assert not bad, bad"
    )
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)


# (cell, rows in range of its fold's window, slots g, key words, sum
#  planes, the window's ride, whether the window folds WITH the state).
SLICED_CELLS = [
    ("flow_recent_pairs", 614_366, 1 << 17, 1, 2, "payload", False),
    ("flow_recent_addrs", 614_366, 1 << 13, 1, 0, "", False),
    ("sql_recent", 790_568, 1 << 17, 3, 1, "payload", False),
    ("flame_recent_first", 432_374, 1 << 20, 4, 1, "index", True),
    ("flame_recent_last", 582_178, 1 << 20, 4, 1, "index", True),
    ("graph_recent", 1_428_589, 1 << 17, 1, 2, "payload", False),
]


@pytest.mark.parametrize("cell,rows,g,key_words,planes,ride,absorbs",
                         SLICED_CELLS, ids=[c[0] for c in SLICED_CELLS])
def test_a_sliced_window_takes_the_whole_windows_routes_at_the_cells_sizes(
        cell, rows, g, key_words, planes, ride, absorbs):
    """A fold handed a power-of-two slice around its range (PR 44) sees a
    shorter n, as under a smaller ``window_rows``: at the cells' sizes the
    routes that read n (``sorted_fold_ride``, ``_front`` / ``absorb`` at
    n >= 4 g) choose as they chose for the 2^21-row window."""
    from pixie_tpu.exec.stream import _fold_rows
    from pixie_tpu.ops.routes import sorted_fold_ride

    window = 1 << 21
    n = _fold_rows(window, rows)
    if cell.startswith("flame"):
        n = 1 << 20  # one length a run of windows: its longest
    assert n == (window if rows > 1 << 20 else 1 << 20)
    for length in (n, window):
        assert sorted_fold_ride(length, g, key_words, planes) == ride
        assert (length < 4 * g) == absorbs
