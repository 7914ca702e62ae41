"""A dictionary-side UDF with a string result (``exec/expr.py``
``_bind_host_dict``): its image of the column's dictionary is remembered
(``StringDictionary.image``), its remap reaches a fragment's programs as
an operand and not as a literal of their text, and a served request
shows both on its trace (a ``dict_udf`` span, ``remap_entries`` on the
fold's dispatches). Small sizes, on the CPU."""

import json
import os

import numpy as np
import pytest

from conftest import routes_of
from pixie_tpu.types.strings import NULL_ID, StringDictionary

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _shape(s: str) -> str:
    return s.rstrip("0123456789")


def _counted():
    calls = []

    def fn(s):
        calls.append(s)
        return _shape(s)

    return fn, calls


def _strings(n, start=0):
    return [f"q{i % 7}-{i}" for i in range(start, start + n)]


# -- the memo -----------------------------------------------------------------


def test_a_second_bind_runs_the_udf_on_no_string():
    d = StringDictionary(_strings(50))
    fn, calls = _counted()
    img, memo, ran = d.image(fn, "k")
    assert (memo, ran, len(calls)) == ("miss", 50, 50)
    again, memo, ran = d.image(fn, "k")
    assert again is img and (memo, ran, len(calls)) == ("hit", 0, 50)
    new, remap = d.transform(_shape)  # the unremembered form agrees
    assert new.strings == img.dict.strings
    assert np.array_equal(remap, img.remap) and img.n == 50


def test_a_dictionary_grown_by_k_pays_for_k():
    d = StringDictionary(_strings(50))
    fn, calls = _counted()
    first, _memo, _ran = d.image(fn, "grown")
    for s in _strings(9, start=50) + ["other-1"]:
        d.get_or_add(s)
    grown, memo, ran = d.image(fn, "grown")
    assert (memo, ran) == ("extend", 10) and calls[50:] == d.strings[50:]
    assert grown.n == 60 and np.array_equal(grown.remap[:50], first.remap)
    # The results' dictionary grew in place: ids handed out stay good.
    assert grown.dict is first.dict and "other-" in grown.dict.strings
    assert [grown.dict.strings[i] for i in grown.remap] == [
        _shape(s) for s in d.strings]
    assert d.image(fn, "grown")[1:] == ("hit", 0)


def test_equal_content_from_a_fresh_object_hits():
    fn, calls = _counted()
    img, _memo, _ran = StringDictionary(_strings(40)).image(fn, "fresh")
    twin = StringDictionary(_strings(40))
    got, memo, ran = twin.image(fn, "fresh")
    assert got is img and (memo, ran, len(calls)) == ("hit", 0, 40)
    # Other content, or another function's key, misses.
    assert StringDictionary(_strings(41)).image(fn, "fresh")[1] == "miss"
    assert twin.image(fn, "another")[1] == "miss"


def test_binds_that_race_run_the_udf_once():
    import threading

    d = StringDictionary(_strings(2000))
    fn, calls = _counted()
    out = []
    threads = [threading.Thread(target=lambda: out.append(d.image(fn, "r")))
               for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(calls) == 2000
    assert sorted(m for _i, m, _r in out) == ["hit", "hit", "hit", "miss"]
    assert len({id(i) for i, _m, _r in out}) == 1


# -- the binder ---------------------------------------------------------------


def _bind(dicts, registry=None, literal=None):
    from pixie_tpu.exec.expr import bind_expr
    from pixie_tpu.exec.plan import ColumnRef, FuncCall, Literal
    from pixie_tpu.types.dtypes import DataType
    from pixie_tpu.types.relation import Relation
    from pixie_tpu.udf.registry import default_registry

    rel = Relation([("q", DataType.STRING)])
    if literal is None:
        expr = FuncCall("normalize_mysql", (ColumnRef("q"),))
    else:
        expr = FuncCall("strip_prefix", (Literal(literal, DataType.STRING),
                                         ColumnRef("q")))
    return bind_expr(expr, rel, dicts, registry or default_registry())


def test_the_binder_remembers_by_udf_literals_and_content(monkeypatch):
    from pixie_tpu.udf.builtins import sql_ops

    d = StringDictionary([f"SELECT {i} FROM t{i % 3}" for i in range(30)])
    a, b = _bind({"q": d}), _bind({"q": d})
    assert a.dict is b.dict and len(a.dict) == 3
    ids = np.arange(-1, 30, dtype=np.int32)
    got = np.asarray(a.fn({"q": (ids,)}))
    assert got[0] == NULL_ID
    assert [a.dict.strings[i] for i in got[1:]] == [
        sql_ops.normalize_sql(s) for s in d.strings]
    # Another UDF, or the same UDF with other literals, is another image.
    x, y = _bind({"q": d}, literal="SELECT "), _bind({"q": d}, literal="SEL")
    assert x.dict is not y.dict and x.dict is not a.dict
    assert x.dict.strings[0] == "0 FROM t0" and y.dict.strings[0][:3] == "ECT"
    assert _bind({"q": d}, literal="SEL").dict is y.dict


def _fragment(dicts, slots=1 << 10):
    from pixie_tpu.exec.fragment import compile_fragment
    from pixie_tpu.exec.plan import (
        AggExpr, AggOp, ColumnRef, FuncCall, Literal, MapOp,
    )
    from pixie_tpu.types.dtypes import DataType
    from pixie_tpu.types.relation import Relation
    from pixie_tpu.udf.registry import default_registry

    rel = Relation([("time_", DataType.TIME64NS), ("q", DataType.STRING),
                    ("latency_ns", DataType.INT64)])
    lat = (ColumnRef("latency_ns"),)
    ops = [
        MapOp((("query_norm", FuncCall("normalize_mysql", (ColumnRef("q"),))),
               ("window", FuncCall("bin", (ColumnRef("time_"),
                                           Literal(10**9, DataType.INT64)))),
               ("latency_ns", ColumnRef("latency_ns")))),
        AggOp(("query_norm", "window"),
              (AggExpr("n", "count", lat), AggExpr("lat_mean", "mean", lat)),
              max_groups=slots),
    ]
    return compile_fragment(ops, rel, dicts, default_registry()), rel


def _lowered(frag, rel, rows=1 << 12):
    import jax
    import jax.numpy as jnp

    from pixie_tpu.types.dtypes import device_dtypes

    state = jax.eval_shape(frag.init_state)
    cols = {c: tuple(jax.ShapeDtypeStruct((rows,), dt)
                     for dt in device_dtypes(t)) for c, t in rel.items()}
    scalar = jax.ShapeDtypeStruct((), jnp.int32)
    return frag.update.lower(state, cols, (scalar, scalar)).as_text()


@pytest.mark.parametrize("platform", ["cpu", "tpu"])
def test_two_seeds_one_lowered_text(platform):
    """Two dictionaries of other strings in another order, one bucket:
    the fold program's lowered text is the same, because the remap is
    its operand. The same tables as literals make two texts."""
    from pixie_tpu.exec.fragment import OperandProgram

    one = StringDictionary([f"SELECT {i} FROM t{i % 5}" for i in range(1500)])
    two = StringDictionary(
        [f"UPDATE t{i % 4} SET k={i}" for i in range(1900)] + ["BEGIN"])
    with routes_of(platform):
        (fa, rel), (fb, _rel) = _fragment({"q": one}), _fragment({"q": two})
        assert isinstance(fa.update, OperandProgram)
        assert fa.remap_entries == fb.remap_entries == 2048
        assert list(fa.operands) == list(fb.operands) == ["dict_udf:0"]
        ta, tb = _lowered(fa, rel), _lowered(fb, rel)
    assert ta == tb and "2048xi32" in ta
    # Three programs of one fragment, one host table, one device copy.
    (op,) = fa.operands.values()
    assert fa.update_all.operands is fa.update.operands is fa.operands
    assert op.device() is op.device()
    assert _fragment({"q": one}, slots=1 << 11)[0].operands[
        "dict_udf:0"] is op


def test_without_a_collecting_fragment_the_remap_is_a_literal():
    import jax

    d = StringDictionary([f"SELECT {i} FROM t{i % 5}" for i in range(1500)])
    bound = _bind({"q": d})
    text = jax.jit(lambda ids: bound.fn({"q": (ids,)})).lower(
        jax.ShapeDtypeStruct((64,), np.int32)).as_text()
    assert "1500xi32" in text and "2048xi32" not in text


def test_a_fragments_answer_is_the_literals(monkeypatch):
    """The operand path and the literal path give one answer (the
    fragment's ``window_state`` traced outside its programs reads the
    literal), ids past the image read null."""
    import jax
    import jax.numpy as jnp

    d = StringDictionary([f"SELECT {i} FROM t{i % 5}" for i in range(300)])
    frag, _rel = _fragment({"q": d})
    rng = np.random.default_rng(5)
    n = 1 << 10
    cols = {
        "time_": (jnp.asarray(rng.integers(0, 5 * 10**9, n)),),
        "q": (jnp.asarray(rng.integers(-1, 300, n).astype(np.int32)),),
        "latency_ns": (jnp.asarray(rng.integers(1, 10**8, n)),),
    }
    valid = jnp.ones(n, bool)
    with_operand = frag.finalize(frag.update(frag.init_state(), cols, valid))
    literal = jax.jit(lambda c, v: frag.finalize_state(
        frag.merge_states(frag.init_state(), frag.window_state(c, v))))(
            cols, valid)
    for a, b in zip(jax.tree_util.tree_leaves(with_operand),
                    jax.tree_util.tree_leaves(literal)):
        assert np.array_equal(np.asarray(a), np.asarray(b), equal_nan=True)


# -- a served request ---------------------------------------------------------


def _served(requests: int):
    """``px/sql_stats`` through a broker, a PEM and a Kelvin over the
    benchmark builder's statements; the engines' traces a request."""
    from benchmark.builders import served_sql
    from pixie_tpu.config import override_flag
    from pixie_tpu.exec.engine import Engine
    from pixie_tpu.scripts import load_script
    from pixie_tpu.services import (
        AgentTracker, KelvinAgent, MessageBus, PEMAgent, QueryBroker,
    )
    from conftest import wait_until

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "sql_stats_1chip.json")) as f:
        cfg = json.load(f)
    data = served_sql.make_data(cfg, 3_400_000_019, 1 << 14)
    bus = MessageBus()
    tracker = AgentTracker(bus, expiry_s=60.0, check_interval_s=60.0)
    pem = PEMAgent(bus, "pem-0", heartbeat_interval_s=0.05,
                   engine=Engine(window_rows=1 << 12)).start()
    kelvin = KelvinAgent(bus, "kelvin-0", heartbeat_interval_s=0.05).start()
    traces = {"pem": [], "kelvin": []}
    pem.engine.tracer.add_listener(traces["pem"].append)
    kelvin.engine.tracer.add_listener(traces["kelvin"].append)
    out = []
    try:
        with override_flag("cpu_fold_threads", 1):
            for hb in served_sql.batches(data, 1 << 12):
                pem.append_data("mysql_events", hb)
            pem._register()
            wait_until(
                lambda: tracker.distributed_state().pems_with_table(
                    "mysql_events"), "the PEM's schema at the tracker")
            broker = QueryBroker(bus, tracker)
            for _ in range(requests):
                res = broker.execute_script(load_script("px/sql_stats").pxl,
                                            timeout_s=120,
                                            max_output_rows=1 << 17)
                assert not res.get("partial")
                out.append(res["tables"]["output"].to_pydict())
                wait_until(lambda: min(len(v) for v in traces.values())
                           >= len(out), "the engines' traces")
    finally:
        pem.stop()
        kelvin.stop()
        tracker.close()
        bus.close()
    return data, out, traces


def test_served_request_span_shape():
    """The first request binds ``px.normalize_mysql`` over the whole
    dictionary once (``miss``) and every later bind of it hits, on the
    PEM and on the Kelvin; its fold's dispatches carry the remap's
    bucket; the second request binds nothing at all."""
    from pixie_tpu.types.batch import bucket_capacity

    data, out, traces = _served(2)
    entries = len(data["names"]["query_str"])
    assert out[0]["n"].sum() == out[1]["n"].sum() == 1 << 14
    first = [s for tracer in ("pem", "kelvin") for s in traces[tracer][0].spans
             if s.name == "dict_udf"]
    first.sort(key=lambda s: s.start_ns)
    assert len(first) >= 2
    assert [s.attributes["memo"] for s in first] == (
        ["miss"] + ["hit"] * (len(first) - 1))
    assert [s.attributes["strings"] for s in first] == (
        [entries] + [0] * (len(first) - 1))
    assert {(s.attributes["udf"], s.attributes["entries"])
            for s in first} == {("normalize_mysql", entries)}
    assert sum(t.usage.dict_udf_strings
               for t in (traces["pem"][0], traces["kelvin"][0])) == entries
    # Both keys are computed, so the plan's 4,096 slots are a default:
    # the first request reads the joint-key sketch once and climbs no
    # ladder (some 12 k groups here); the second reads nothing.
    (probe,) = [s for s in traces["pem"][0].spans if s.name == "group_probe"]
    groups = len(out[0]["n"])
    assert probe.attributes["slots"] == 4096 < groups
    assert abs(probe.attributes["estimate"] - groups) < 0.1 * groups
    assert not any(s.name == "group_probe" for s in traces["pem"][1].spans)
    assert [t.usage.rebuckets for t in traces["pem"]] == [0, 0]
    for tracer in ("pem", "kelvin"):
        later = traces[tracer][1]
        assert not [s for s in later.spans if s.name == "dict_udf"]
        assert later.usage.dict_udf_strings == 0
    folds = [s for s in traces["pem"][1].spans
             if s.name == "device.dispatch" and "fold" in s.attributes]
    assert folds and {s.attributes["remap_entries"] for s in folds} == {
        bucket_capacity(entries + 1)}
    merges = [s for s in traces["kelvin"][1].spans
              if s.name == "device.dispatch"]
    assert merges and not any("remap_entries" in s.attributes for s in merges)


def test_a_dictionary_that_grows_between_requests_is_extended():
    """Rows appended between two requests bring new strings: the next
    request's bind runs the UDF on those alone, and its answer counts
    the new rows under their shapes."""
    from benchmark.builders import served_sql
    from pixie_tpu.exec.engine import Engine
    from pixie_tpu.scripts import load_script

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "sql_stats_1chip.json")) as f:
        cfg = json.load(f)
    data = served_sql.make_data(cfg, 3_400_000_019, 1 << 13)
    eng = Engine(window_rows=1 << 12)
    traces = []
    eng.tracer.add_listener(traces.append)
    names = data["names"]

    def rows(lo, hi):
        """Rows [lo, hi) with their strings, as a collector hands them
        over: the table's dictionary meets them in arrival order."""
        return {
            "time_": data["time_"][lo:hi],
            "upid": np.stack([p[lo:hi] for p in data["upid"]], axis=1),
            "req_cmd": data["req_cmd"][lo:hi],
            "query_str": [names["query_str"][c]
                          for c in data["query_str"][lo:hi]],
            "resp_status": data["resp_status"][lo:hi],
            "latency_ns": data["latency_ns"][lo:hi],
            "service": [names["service"][c] for c in data["service"][lo:hi]],
        }

    eng.append_data("mysql_events", rows(0, 1 << 12))
    pxl = load_script("px/sql_stats").pxl
    a = eng.execute_query(pxl)["output"].to_pydict()
    known = len(eng.tables["mysql_events"].dicts["query_str"])
    eng.append_data("mysql_events", rows(1 << 12, 1 << 13))
    grown = len(eng.tables["mysql_events"].dicts["query_str"])
    b = eng.execute_query(pxl)["output"].to_pydict()
    assert (a["n"].sum(), b["n"].sum()) == (1 << 12, 1 << 13)
    spans = [[s for s in t.spans if s.name == "dict_udf"] for t in traces]
    assert spans[0][0].attributes["memo"] == "miss"
    assert spans[0][0].attributes["strings"] == known
    assert spans[1][0].attributes["memo"] == "extend"
    assert spans[1][0].attributes["strings"] == grown - known > 0
    assert traces[1].usage.dict_udf_strings == grown - known


# -- the capacity of an aggregate on computed keys ----------------------------


def _chain(*ops):
    from pixie_tpu.exec.plan import (
        AggExpr, AggOp, ColumnRef, FilterOp, FuncCall, Literal, MapOp,
    )
    from pixie_tpu.types.dtypes import DataType

    col = ColumnRef
    made = {
        "select": MapOp((("service", col("service")), ("t", col("time_")),
                         ("latency_ns", col("latency_ns")))),
        "rename": MapOp((("svc", col("service")),
                         ("latency_ns", col("latency_ns")))),
        "bin": MapOp((("service", col("service")),
                      ("t", FuncCall("bin", (col("time_"), Literal(
                          10**9, DataType.INT64)))),
                      ("latency_ns", col("latency_ns")))),
        "keep_t": MapOp((("t", col("t")), ("latency_ns", col("latency_ns")))),
        "filter": FilterOp(FuncCall("greaterThan", (col("latency_ns"),
                                                    Literal(5, DataType.INT64)))),
    }
    lat = (col("latency_ns"),)

    def agg(*by):
        return AggOp(tuple(by), (AggExpr("n", "count", lat),))

    return [made[o] if isinstance(o, str) else agg(*o) for o in ops]


@pytest.mark.parametrize("ops,computed", [
    ((("service",),), False),
    (("select", ("service", "t")), False),
    (("select", "filter", ("service",)), False),
    (("select", "rename", ("svc",)), False),
    (("bin", ("service", "t")), True),
    (("bin", ("service",)), False),  # the bin is made, not grouped by
    (("bin", "filter", "rename", ("svc",)), False),
    (("bin", "keep_t", ("t",)), True),  # selected since, computed before
    (("select", "filter"), False),  # no aggregate at all
], ids=["bare", "select", "filter", "rename", "bin", "bin_unused",
        "bin_then_rename", "bin_then_select", "no_agg"])
def test_a_chain_that_computes_a_group_key_is_told_apart(ops, computed):
    from pixie_tpu.exec.stream import _computed_group_keys

    assert _computed_group_keys(_chain(*ops)) is computed
