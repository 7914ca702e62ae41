"""End-to-end exec engine tests (Carnot carnot_test.cc analog)."""

import numpy as np
import pytest

from pixie_tpu.exec import (
    AggExpr,
    AggOp,
    ColumnRef,
    Engine,
    FilterOp,
    FuncCall,
    JoinOp,
    LimitOp,
    Literal,
    MapOp,
    MemorySourceOp,
    Plan,
    QueryError,
    ResultSinkOp,
    UnionOp,
)
from pixie_tpu.types import DataType

C = ColumnRef


def lit(v, dt=DataType.INT64):
    return Literal(v, dt)


def f(name, *args):
    return FuncCall(name, tuple(args))


@pytest.fixture()
def engine():
    e = Engine(window_rows=1 << 12)
    rng = np.random.default_rng(0)
    n = 10_000
    e.append_data(
        "http_events",
        {
            "time_": np.arange(n, dtype=np.int64) * 1_000_000,
            "latency_ns": rng.integers(10**5, 10**9, n).astype(np.int64),
            "resp_status": rng.choice([200, 200, 200, 404, 500], n).astype(np.int64),
            "service": [f"svc-{i % 7}" for i in range(n)],
            "req_path": [f"/api/v{i % 3}/x" for i in range(n)],
        },
    )
    return e


def run(engine, plan):
    return engine.execute_plan(plan)["output"]


def chain(plan, ops, inputs=None):
    nid = None
    for i, op in enumerate(ops):
        nid = plan.add(op, [nid] if nid is not None else (inputs or []))
    return nid


class TestMapFilter:
    def test_filter_only(self, engine):
        p = Plan()
        src = p.add(MemorySourceOp(table="http_events"))
        flt = p.add(FilterOp(f("greaterThanEqual", C("resp_status"), lit(400))), [src])
        p.add(ResultSinkOp("output"), [flt])
        out = run(engine, p).to_pydict()
        table = engine.tables["http_events"].read_all()
        expected = int((table.cols["resp_status"][0] >= 400).sum())
        assert len(out["resp_status"]) == expected
        assert set(np.unique(out["resp_status"])) <= {404, 500}

    def test_map_projection(self, engine):
        p = Plan()
        src = p.add(MemorySourceOp(table="http_events"))
        m = p.add(
            MapOp(
                exprs=(
                    ("service", C("service")),
                    ("latency_ms", f("divide", C("latency_ns"), lit(1e6, DataType.FLOAT64))),
                )
            ),
            [src],
        )
        p.add(ResultSinkOp("output"), [m])
        out = run(engine, p)
        assert out.relation.column_names == ("service", "latency_ms")
        table = engine.tables["http_events"].read_all()
        np.testing.assert_allclose(
            out.cols["latency_ms"][0][:100],
            table.cols["latency_ns"][0][:100] / 1e6,
            rtol=1e-5,
        )
        assert out.to_pydict()["service"][0] == "svc-0"

    def test_string_filter_literal(self, engine):
        p = Plan()
        src = p.add(MemorySourceOp(table="http_events"))
        flt = p.add(FilterOp(f("equal", C("service"), Literal("svc-3", DataType.STRING))), [src])
        p.add(ResultSinkOp("output"), [flt])
        out = run(engine, p).to_pydict()
        assert len(out["service"]) == 10_000 // 7 + (1 if 3 < 10_000 % 7 else 0)
        assert set(out["service"]) == {"svc-3"}

    def test_filter_unseen_literal_empty(self, engine):
        p = Plan()
        src = p.add(MemorySourceOp(table="http_events"))
        flt = p.add(FilterOp(f("equal", C("service"), Literal("nope", DataType.STRING))), [src])
        p.add(ResultSinkOp("output"), [flt])
        assert run(engine, p).length == 0

    def test_limit_stops_stream(self, engine):
        p = Plan()
        src = p.add(MemorySourceOp(table="http_events"))
        lim = p.add(LimitOp(17), [src])
        p.add(ResultSinkOp("output"), [lim])
        assert run(engine, p).length == 17

    def test_host_dict_udf_contains(self, engine):
        p = Plan()
        src = p.add(MemorySourceOp(table="http_events"))
        flt = p.add(
            FilterOp(f("contains", C("req_path"), Literal("/v1/", DataType.STRING))),
            [src],
        )
        p.add(ResultSinkOp("output"), [flt])
        out = run(engine, p).to_pydict()
        assert len(out["req_path"]) > 0
        assert all("/v1/" in s for s in out["req_path"])

    def test_time_range_source(self, engine):
        p = Plan()
        src = p.add(
            MemorySourceOp(
                table="http_events", start_time=1_000_000 * 100, stop_time=1_000_000 * 200
            )
        )
        p.add(ResultSinkOp("output"), [src])
        out = run(engine, p)
        assert out.length == 100


class TestAgg:
    def _truth(self, engine):
        t = engine.tables["http_events"].read_all()
        svc = t.dicts["service"].decode(t.cols["service"][0])
        lat = t.cols["latency_ns"][0]
        status = t.cols["resp_status"][0]
        return svc, lat, status

    def test_groupby_mean_count(self, engine):
        p = Plan()
        src = p.add(MemorySourceOp(table="http_events"))
        agg = p.add(
            AggOp(
                group_cols=("service",),
                aggs=(
                    AggExpr("mean_lat", "mean", (C("latency_ns"),)),
                    AggExpr("n", "count", (C("latency_ns"),)),
                ),
            ),
            [src],
        )
        p.add(ResultSinkOp("output"), [agg])
        out = run(engine, p).to_pydict()
        svc, lat, _ = self._truth(engine)
        got = dict(zip(out["service"], zip(out["mean_lat"], out["n"])))
        assert len(got) == 7
        for s in sorted(set(svc)):
            mask = svc == s
            np.testing.assert_allclose(got[s][0], lat[mask].mean(), rtol=1e-6)
            assert got[s][1] == mask.sum()

    def test_multiwindow_agg_matches_single(self, engine):
        """Cross-window regroup: tiny windows must agree with one window."""
        small = Engine(window_rows=256)
        big = Engine(window_rows=1 << 15)
        t = engine.tables["http_events"].read_all()
        for e in (small, big):
            e.append_data("http_events", t.to_pydict())

        def q(e):
            p = Plan()
            src = p.add(MemorySourceOp(table="http_events"))
            agg = p.add(
                AggOp(
                    group_cols=("service", "resp_status"),
                    aggs=(AggExpr("total", "sum", (C("latency_ns"),)),),
                ),
                [src],
            )
            p.add(ResultSinkOp("output"), [agg])
            d = e.execute_plan(p)["output"].to_pydict()
            return {
                (s, int(st)): int(v)
                for s, st, v in zip(d["service"], d["resp_status"], d["total"])
            }

        assert q(small) == q(big)

    def test_filter_groupby_http_stats_shape(self, engine):
        """The px/http_stats benchmark shape: filter + groupby-agg."""
        p = Plan()
        src = p.add(MemorySourceOp(table="http_events"))
        flt = p.add(FilterOp(f("greaterThanEqual", C("resp_status"), lit(400))), [src])
        agg = p.add(
            AggOp(
                group_cols=("service",),
                aggs=(AggExpr("errors", "count", (C("resp_status"),)),),
            ),
            [flt],
        )
        p.add(ResultSinkOp("output"), [agg])
        out = run(engine, p).to_pydict()
        svc, _, status = self._truth(engine)
        for s, n in zip(out["service"], out["errors"]):
            assert n == ((svc == s) & (status >= 400)).sum()

    def test_quantiles_struct_output(self, engine):
        import json

        p = Plan()
        src = p.add(MemorySourceOp(table="http_events"))
        agg = p.add(
            AggOp(
                group_cols=("service",),
                aggs=(AggExpr("latency_dist", "quantiles", (C("latency_ns"),)),),
            ),
            [src],
        )
        p.add(ResultSinkOp("output"), [agg])
        out = run(engine, p).to_pydict()
        svc, lat, _ = self._truth(engine)
        row = json.loads(out["latency_dist"][list(out["service"]).index("svc-0")])
        truth = np.percentile(lat[svc == "svc-0"], 50)
        assert abs(row["p50"] - truth) / truth < 0.05
        assert set(row) == {"p01", "p10", "p25", "p50", "p75", "p90", "p99"}

    def test_agg_overflow_rebuckets(self, engine):
        """Overflow no longer fails: the engine doubles max_groups and
        re-runs (Carnot's growing hash map, ``agg_node.cc``)."""
        p = Plan()
        src = p.add(MemorySourceOp(table="http_events"))
        agg = p.add(
            AggOp(
                group_cols=("latency_ns",),  # ~all distinct
                aggs=(AggExpr("n", "count", (C("latency_ns"),)),),
                max_groups=64,
            ),
            [src],
        )
        p.add(ResultSinkOp("output"), [agg])
        out = run(engine, p).to_pydict()
        table = engine.tables["http_events"].read_all()
        lat = table.cols["latency_ns"][0]
        assert len(out["latency_ns"]) == len(np.unique(lat))
        assert out["n"].sum() == len(lat)

    def test_agg_overflow_cap_raises(self, engine, monkeypatch):
        from pixie_tpu import config

        monkeypatch.setenv("PIXIE_TPU_MAX_GROUPS_LIMIT", "128")
        p = Plan()
        src = p.add(MemorySourceOp(table="http_events"))
        agg = p.add(
            AggOp(
                group_cols=("latency_ns",),
                aggs=(AggExpr("n", "count", (C("latency_ns"),)),),
                max_groups=64,
            ),
            [src],
        )
        p.add(ResultSinkOp("output"), [agg])
        with pytest.raises(QueryError, match="overflow"):
            run(engine, p)
        assert config.get_flag("max_groups_limit") == 128

    def test_post_agg_map_filter(self, engine):
        p = Plan()
        src = p.add(MemorySourceOp(table="http_events"))
        agg = p.add(
            AggOp(
                group_cols=("service",),
                aggs=(AggExpr("n", "count", (C("latency_ns"),)),),
            ),
            [src],
        )
        m = p.add(
            MapOp(
                exprs=(
                    ("service", C("service")),
                    ("double_n", f("multiply", C("n"), lit(2))),
                )
            ),
            [agg],
        )
        flt = p.add(FilterOp(f("greaterThan", C("double_n"), lit(0))), [m])
        p.add(ResultSinkOp("output"), [flt])
        out = run(engine, p).to_pydict()
        assert len(out["service"]) == 7
        assert all(v > 0 and v % 2 == 0 for v in out["double_n"])


class TestJoinUnion:
    def test_self_join_flow_graph_shape(self, engine):
        """px/net_flow_graph shape: two aggs joined on the group key."""
        p = Plan()
        src1 = p.add(MemorySourceOp(table="http_events"))
        agg1 = p.add(
            AggOp(group_cols=("service",), aggs=(AggExpr("n", "count", (C("latency_ns"),)),)),
            [src1],
        )
        src2 = p.add(MemorySourceOp(table="http_events"))
        agg2 = p.add(
            AggOp(group_cols=("service",), aggs=(AggExpr("total", "sum", (C("latency_ns"),)),)),
            [src2],
        )
        j = p.add(JoinOp(left_on=("service",), right_on=("service",)), [agg1, agg2])
        p.add(ResultSinkOp("output"), [j])
        out = run(engine, p).to_pydict()
        assert len(out["service"]) == 7
        assert set(out) == {"service", "n", "total"}
        svc = engine.tables["http_events"].read_all()
        dec = svc.dicts["service"].decode(svc.cols["service"][0])
        lat = svc.cols["latency_ns"][0]
        got = dict(zip(out["service"], out["total"]))
        for s in set(dec):
            assert got[s] == lat[dec == s].sum()

    def test_left_join_missing(self, engine):
        left = Engine()
        left.append_data("a", {"k": np.array([1, 2, 3], dtype=np.int64)}, time_cols=())
        left.append_data("b", {"k": np.array([2], dtype=np.int64), "v": np.array([9], dtype=np.int64)}, time_cols=())
        p = Plan()
        s1 = p.add(MemorySourceOp(table="a"))
        s2 = p.add(MemorySourceOp(table="b"))
        j = p.add(JoinOp(left_on=("k",), right_on=("k",), how="left"), [s1, s2])
        p.add(ResultSinkOp("output"), [j])
        out = left.execute_plan(p)["output"].to_pydict()
        assert list(out["k"]) == [1, 2, 3]
        assert list(out["v"]) == [0, 9, 0]

    def test_join_dup_build_side_fans_out(self, engine):
        """A non-unique build side falls through to the device N:M join
        (reference equijoin_node.cc supports full fan-out)."""
        e = Engine()
        e.append_data("a", {"k": np.array([1, 2], dtype=np.int64)}, time_cols=())
        e.append_data(
            "b",
            {"k": np.array([2, 2, 3], dtype=np.int64),
             "v": np.array([7, 8, 9], dtype=np.int64)},
            time_cols=(),
        )
        p = Plan()
        s1 = p.add(MemorySourceOp(table="a"))
        s2 = p.add(MemorySourceOp(table="b"))
        j = p.add(JoinOp(left_on=("k",), right_on=("k",)), [s1, s2])
        p.add(ResultSinkOp("output"), [j])
        out = e.execute_plan(p)["output"].to_pydict()
        assert list(out["k"]) == [2, 2]
        assert sorted(out["v"]) == [7, 8]

    def test_union(self, engine):
        e = Engine()
        e.append_data("a", {"s": ["x", "y"]}, time_cols=())
        e.append_data("b", {"s": ["y", "z"]}, time_cols=())
        p = Plan()
        s1 = p.add(MemorySourceOp(table="a"))
        s2 = p.add(MemorySourceOp(table="b"))
        u = p.add(UnionOp(), [s1, s2])
        p.add(ResultSinkOp("output"), [u])
        out = e.execute_plan(p)["output"].to_pydict()
        assert list(out["s"]) == ["x", "y", "y", "z"]


class TestSqlStatsShape:
    def test_normalize_and_windowed_agg(self, engine):
        """px/sql_stats shape: normalize query strings + windowed agg."""
        e = Engine()
        n = 1000
        queries = [
            f"SELECT * FROM t WHERE id = {i % 50} AND name = 'u{i % 11}'" for i in range(n)
        ]
        e.append_data(
            "mysql_events",
            {
                "time_": np.arange(n, dtype=np.int64) * 1_000_000_000,
                "req_body": queries,
                "latency_ns": np.full(n, 10**6, dtype=np.int64),
            },
        )
        p = Plan()
        src = p.add(MemorySourceOp(table="mysql_events"))
        m = p.add(
            MapOp(
                exprs=(
                    ("q", f("normalize_mysql", C("req_body"))),
                    ("window", f("bin", C("time_"), lit(100 * 1_000_000_000))),
                    ("latency_ns", C("latency_ns")),
                )
            ),
            [src],
        )
        agg = p.add(
            AggOp(
                group_cols=("q", "window"),
                aggs=(AggExpr("n", "count", (C("latency_ns"),)),),
            ),
            [m],
        )
        p.add(ResultSinkOp("output"), [agg])
        out = e.execute_plan(p)["output"].to_pydict()
        assert set(out["q"]) == {"SELECT * FROM t WHERE id = ? AND name = ?"}
        assert len(out["window"]) == 10  # 1000s of data in 100s windows
        assert sum(out["n"]) == n


class TestReviewRegressions:
    def test_limit_position_semantics(self, engine):
        """Limit before agg caps input rows, not output groups."""
        p = Plan()
        src = p.add(MemorySourceOp(table="http_events"))
        lim = p.add(LimitOp(5), [src])
        agg = p.add(
            AggOp(group_cols=("service",), aggs=(AggExpr("n", "count", (C("latency_ns"),)),)),
            [lim],
        )
        p.add(ResultSinkOp("output"), [agg])
        out = run(engine, p).to_pydict()
        assert sum(out["n"]) == 5  # aggregated only the first 5 rows

    def test_cross_dict_string_compare(self, engine):
        """Two string columns with different dictionaries compare by value."""
        e = Engine()
        e.append_data("t", {"a": ["x", "y", "z"], "b": ["x", "q", "z"]}, time_cols=())
        p = Plan()
        src = p.add(MemorySourceOp(table="t"))
        flt = p.add(FilterOp(f("equal", C("a"), C("b"))), [src])
        p.add(ResultSinkOp("output"), [flt])
        out = e.execute_plan(p)["output"].to_pydict()
        assert list(out["a"]) == ["x", "z"]

    def test_empty_table_query(self, engine):
        e = Engine()
        e.create_table("empty")
        t = e.tables["empty"]
        from pixie_tpu.types import Relation as R

        t.relation = R({"x": DataType.INT64})
        p = Plan()
        src = p.add(MemorySourceOp(table="empty"))
        p.add(ResultSinkOp("output"), [src])
        out = e.execute_plan(p)["output"]
        assert out.length == 0
        assert list(out.to_pydict()["x"]) == []

    def test_left_join_empty_build_side(self, engine):
        e = Engine()
        e.append_data("a", {"k": np.array([1, 2], dtype=np.int64)}, time_cols=())
        e.append_data(
            "b",
            {"k": np.array([9], dtype=np.int64), "v": np.array([1], dtype=np.int64)},
            time_cols=(),
        )
        p = Plan()
        s1 = p.add(MemorySourceOp(table="a"))
        s2 = p.add(MemorySourceOp(table="b"))
        flt = p.add(FilterOp(f("equal", C("k"), lit(1000))), [s2])  # empties b
        j = p.add(JoinOp(left_on=("k",), right_on=("k",), how="left"), [s1, flt])
        p.add(ResultSinkOp("output"), [j])
        out = e.execute_plan(p)["output"].to_pydict()
        assert list(out["k"]) == [1, 2]
        assert list(out["v"]) == [0, 0]

    def test_fanout_shared_agg(self, engine):
        """One agg feeding both join sides executes once and stays correct."""
        p = Plan()
        src = p.add(MemorySourceOp(table="http_events"))
        agg = p.add(
            AggOp(group_cols=("service",), aggs=(AggExpr("n", "count", (C("latency_ns"),)),)),
            [src],
        )
        j = p.add(JoinOp(left_on=("service",), right_on=("service",)), [agg, agg])
        p.add(ResultSinkOp("output"), [j])
        out = run(engine, p).to_pydict()
        assert len(out["service"]) == 7
        np.testing.assert_array_equal(out["n"], out["n_y"])


class TestDenseDomain:
    """Dense-domain group-by (packed dict codes as slot ids) must agree
    with the generic sort-space path bit for bit, including deferred
    (DeviceResult) execution."""

    QUERY = """
import px
df = px.DataFrame(table='http_events')
df = df[df.resp_status < 400]
df = df.groupby(['service', 'req_path']).agg(
    n=('latency_ns', px.count),
    lat_mean=('latency_ns', px.mean),
    lat_max=('latency_ns', px.max),
)
px.display(df)
"""

    def _rows(self, out):
        d = out["output"].to_pydict()
        keys = sorted(
            (d["service"][i], d["req_path"][i]) for i in range(len(d["n"]))
        )
        order = np.lexsort((d["req_path"], d["service"]))
        return keys, d["n"][order], d["lat_mean"][order], d["lat_max"][order]

    def test_matches_sort_path(self, engine):
        from pixie_tpu import config
        from pixie_tpu.exec.fragment import _FRAGMENT_CACHE

        dense = self._rows(engine.execute_query(self.QUERY))
        config.set_flag("dense_domain_limit", 0)  # force generic path
        _FRAGMENT_CACHE.clear()
        try:
            generic = self._rows(engine.execute_query(self.QUERY))
        finally:
            config.clear_flag("dense_domain_limit")
            _FRAGMENT_CACHE.clear()
        assert dense[0] == generic[0]
        np.testing.assert_array_equal(dense[1], generic[1])
        np.testing.assert_allclose(dense[2], generic[2], rtol=1e-6)
        np.testing.assert_array_equal(dense[3], generic[3])

    def test_dense_fragment_selected(self, engine):
        from pixie_tpu.exec.fragment import _FRAGMENT_CACHE

        engine.execute_query(self.QUERY)
        frags = [hit[0] for hit in _FRAGMENT_CACHE.values()]
        dense = [fr for fr in frags if fr.is_agg and fr.dense_domains]
        assert dense, "expected the agg fragment to compile dense"
        # (the cache is the process's: other files' fragments may be in it)
        assert (8, 4) in [fr.dense_domains for fr in dense]  # 7 svcs, 3 paths (+NULL)

    def test_deferred_device_result(self, engine):
        from pixie_tpu.exec.engine import DeviceResult

        out = engine.execute_query(self.QUERY, materialize=False)
        r = out["output"]
        assert isinstance(r, DeviceResult)
        r.block_until_ready()
        d = r.to_host().to_pydict()
        assert len(d["n"]) == 21  # 7 services x 3 paths
        # Second to_host returns the cached batch.
        assert r.to_host() is r.to_host()
