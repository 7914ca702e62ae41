"""Pipelined window executor tests (exec/pipeline.py).

Covers the ISSUE 1 acceptance surface: pipelined-vs-serial bit-identical
equivalence across six of the small-replay shapes at pipeline_depth 1/2/4,
mid-pipeline cancellation, prefetch-thread exception propagation (the
original traceback, not a hang), a concurrent-queries stress test
asserting no thread leaks, the windowed device-join driver, and the
stats/observability plumbing.
"""

from __future__ import annotations

import threading
import time
import traceback

import numpy as np
import pytest

from pixie_tpu import config
from pixie_tpu.exec.engine import Engine, QueryCancelled
from pixie_tpu.exec.stream import QueryError  # noqa: F401 (doc import)

W = 1 << 10  # small windows -> many windows -> real pipelining


def _prefetch_threads():
    return [
        t for t in threading.enumerate()
        if t.name.startswith("pixie-window-prefetch") and t.is_alive()
    ]


def _assert_no_prefetch_threads(timeout=5.0):
    deadline = time.time() + timeout
    while time.time() < deadline and _prefetch_threads():
        time.sleep(0.01)
    assert _prefetch_threads() == []


def _mk_engine(n=10 * W + 57, depth=2, **kw):
    eng = Engine(window_rows=W, pipeline_depth=depth, **kw)
    rng = np.random.default_rng(5)
    eng.append_data("t", {
        "time_": np.arange(n, dtype=np.int64),
        "k": rng.integers(0, 41, n),
        "v": rng.integers(0, 1000, n),
    })
    return eng

AGG_Q = (
    "import px\ndf = px.DataFrame(table='t')\n"
    "df = df[df.v > 100]\n"
    "df = df.groupby('k').agg(n=('v', px.count), s=('v', px.sum),"
    " m=('v', px.mean))\npx.display(df)"
)
ROWS_Q = (
    "import px\ndf = px.DataFrame(table='t')\n"
    "df.w = df.v * 2\ndf = df[df.w > 900]\npx.display(df)"
)


class TestBitIdenticalEquivalence:
    @pytest.mark.parametrize("query", [AGG_Q, ROWS_Q], ids=["agg", "rows"])
    @pytest.mark.parametrize("residency", [True, False],
                             ids=["resident", "host-staged"])
    def test_depths_bit_identical(self, query, residency):
        """Depth 1/2/4 produce byte-equal outputs on both the device-
        cache-resident and the host-staged window paths."""
        config.set_flag("device_residency", residency)
        try:
            outs = []
            for depth in (1, 2, 4):
                eng = _mk_engine(depth=depth)
                out = eng.execute_query(query, max_output_rows=1 << 20)
                outs.append(out["output"].to_pydict(decode_strings=False))
            for other in outs[1:]:
                assert set(other) == set(outs[0])
                for c in outs[0]:
                    np.testing.assert_array_equal(outs[0][c], other[c])
        finally:
            config.clear_flag("device_residency")
        _assert_no_prefetch_threads()


def _groups(*keys):
    """{key tuple: row indices} over equal-length host columns."""
    groups = {}
    for i, k in enumerate(zip(*keys)):
        groups.setdefault(k, []).append(i)
    return {k: np.array(v) for k, v in groups.items()}


def _assert_rows(got, key_cols, want):
    """``got`` (an output's columns) holds exactly the keys of ``want``
    ({key tuple: {column: value}}), once each, with its values: counts
    and integer sums exactly, means and shares to an f32's precision."""
    keys = list(zip(*(got[c] for c in key_cols)))
    assert len(keys) == len(set(keys)) and set(keys) == set(want)
    for i, k in enumerate(keys):
        for c, v in want[k].items():
            rtol = 0 if isinstance(v, (int, np.integer)) else 1e-6
            np.testing.assert_allclose(
                got[c][i], v, rtol=rtol, err_msg=f"{k} {c}")


def _oracle_http_stats(got, host):
    h = host["http_events"]
    ok = h["resp_status"] < 400
    lat = h["latency_ns"][ok]
    _assert_rows(got, ("service", "req_path"), {
        k: {"n": len(i), "lat_mean": lat[i].mean(), "lat_max": lat[i].max()}
        for k, i in _groups(h["service"][ok], h["req_path"][ok]).items()
    })


def _oracle_service_stats(got, host):
    h = host["http_events"]
    lat, failed = h["latency_ns"], h["resp_status"] >= 400
    groups = _groups(h["service"])
    _assert_rows(got, ("service",), {
        k: {"error_rate": failed[i].mean(), "throughput": len(i)}
        for k, i in groups.items()
    })
    # The quantiles are a t-digest's: held to the sample's within 15 %.
    for s, p50, p99 in zip(got["service"], got["p50"], got["p99"]):
        r50, r99 = np.quantile(lat[groups[(s,)]], [0.5, 0.99])
        assert abs(p50 - r50) / r50 < 0.15 and abs(p99 - r99) / r99 < 0.15


def _oracle_net_flow_graph(got, host):
    h = host["conn_stats"]
    pods_of = {}  # the addrs side: the distinct (src_addr, src_pod)
    for addr, pod in _groups(h["src_addr"], h["src_pod"]):
        pods_of.setdefault(addr, []).append(pod)
    want = {}
    for (pod, remote), i in _groups(h["src_pod"], h["remote_addr"]).items():
        for dst in pods_of.get(remote, ()):
            row = want.setdefault(
                (pod, dst), {"bytes_sent": 0, "bytes_recv": 0})
            row["bytes_sent"] += h["bytes_sent"][i].sum()
            row["bytes_recv"] += h["bytes_recv"][i].sum()
    assert want  # the replay's addresses join
    _assert_rows(got, ("src_pod", "src_pod_dst"), want)


def _oracle_sql_stats(got, host):
    from pixie_tpu.udf.builtins.sql_ops import normalize_sql

    h = host["mysql_events"]
    norm = [normalize_sql(q) for q in h["query_str"]]
    window = h["time_"] // 1_000_000_000 * 1_000_000_000
    lat = h["latency_ns"]
    _assert_rows(got, ("query_norm", "window"), {
        k: {"n": len(i), "lat_mean": lat[i].mean()}
        for k, i in _groups(norm, window).items()
    })


def _oracle_perf_flamegraph(got, host):
    h = host["stack_traces.beta"]
    count = h["count"]
    of_pod = {k: count[i].sum() for (k,), i in _groups(h["pod"]).items()}
    groups = _groups(h["pod"], h["stack_trace_id"])
    _assert_rows(got, ("pod", "stack_trace_id"), {
        k: {"count": count[i].sum(),
            "percent": 100.0 * count[i].sum() / of_pod[k[0]]}
        for k, i in groups.items()
    })
    for pod, sid, st in zip(got["pod"], got["stack_trace_id"],
                            got["stack_trace"]):
        assert st in h["stack_trace"][groups[(pod, sid)]]  # px.any


def _oracle_device_join(got, host):
    l, r = host["conn_l"], host["conn_r"]
    right = {k: r["v"][i] for (k,), i in _groups(r["k"]).items()}
    want = {}
    for k, b in zip(l["k"], l["b"]):
        if k in right:
            row = want.setdefault((b,), {"n": 0, "s": 0})
            row["n"] += len(right[k])
            row["s"] += right[k].sum()
    _assert_rows(got, ("b",), want)


_ORACLES = {
    "http_stats": _oracle_http_stats,
    "service_stats": _oracle_service_stats,
    "net_flow_graph": _oracle_net_flow_graph,
    "sql_stats": _oracle_sql_stats,
    "perf_flamegraph": _oracle_perf_flamegraph,
    "device_join": _oracle_device_join,
}


class TestReplayShapeEquivalence:
    """Six of the small-replay shapes (analysis/bench_check.py), each
    run at depth 1, 2, 4 over one engine: the depth's answer equals the
    serial one, and the serial one is held to a numpy oracle."""

    @pytest.mark.parametrize("depth", [1, 2, 4])
    @pytest.mark.parametrize("shape", list(_ORACLES))
    def test_shape_checked_at_depth(self, shape, depth):
        from pixie_tpu.analysis.bench_check import (
            SHAPE_SCHEMAS, _shape_query,
        )
        from pixie_tpu.analysis.bound_check import _replay_engine

        # Small windows (the replay is ~4 of them a table), so that
        # there is a next window to stage while one computes; the depth
        # reaches the engine as a deployment's would, by the flag.
        with config.override_flag("window_rows", W), \
                config.override_flag("pipeline_depth", depth):
            eng = _replay_engine(SHAPE_SCHEMAS[shape], rows=4000)
        query = _shape_query(shape)

        def run():
            out = eng.execute_query(query, max_output_rows=1 << 20)
            return out["output"].to_pydict()

        got = run()
        assert eng.last_pipeline["depth"] == depth
        eng.pipeline_depth = 1
        serial = run()
        assert eng.last_pipeline["depth"] == 1
        host = {
            name: t.read_all().to_pydict() for name, t in eng.tables.items()
        }
        _ORACLES[shape](serial, host)
        assert set(got) == set(serial)
        for c in serial:
            np.testing.assert_array_equal(serial[c], got[c])
        _assert_no_prefetch_threads()


class _TripAfter:
    """Cancel-event stand-in that fires after N is_set() polls — a
    deterministic way to cancel MID-pipeline."""

    def __init__(self, n):
        self.n = n
        self.calls = 0

    def is_set(self):
        self.calls += 1
        return self.calls > self.n


def _plan_for(eng, q):
    from pixie_tpu.planner import CompilerState, compile_pxl

    state = CompilerState(
        schemas={nm: t.relation for nm, t in eng.tables.items()},
        registry=eng.registry,
    )
    return compile_pxl(q, state).plan


class TestCancellation:
    def test_mid_pipeline_cancel_joins_thread(self):
        eng = _mk_engine(n=30 * W, depth=3)
        plan = _plan_for(eng, AGG_Q)
        eng.execute_plan(plan)  # warm compile so cancel hits the fold
        with pytest.raises(QueryCancelled):
            eng.execute_plan(plan, cancel=_TripAfter(5))
        _assert_no_prefetch_threads()
        # The engine survives: a fresh un-cancelled run still works.
        out = eng.execute_plan(plan)
        assert out["output"].length == 41

    def test_streaming_cancel_joins_thread(self):
        from pixie_tpu.exec.streaming import stream_query

        eng = _mk_engine(n=20 * W, depth=3)
        ups = []
        cancel = _TripAfter(3)
        sq = stream_query(eng, AGG_Q, emit=ups.append, cancel=cancel)
        with pytest.raises(QueryCancelled):
            sq.poll()
        _assert_no_prefetch_threads()


class _BoomEngine(Engine):
    """Engine whose host->device staging explodes after a few windows
    (exercises the prefetch-thread error relay)."""

    device_residency = False  # force the _stage path

    def __init__(self, *a, boom_after=2, **kw):
        super().__init__(*a, **kw)
        self._boom_after = boom_after
        self._n_staged = 0

    def _stage(self, hb, capacity):
        self._n_staged += 1
        if self._n_staged > self._boom_after:
            raise RuntimeError("boom: staging failed")
        return super()._stage(hb, capacity)


class TestErrorPropagation:
    def test_staging_error_surfaces_with_traceback(self):
        eng = _BoomEngine(window_rows=W, pipeline_depth=2, boom_after=3)
        n = 10 * W
        eng.append_data("t", {
            "time_": np.arange(n, dtype=np.int64),
            "k": np.arange(n, dtype=np.int64) % 7,
            "v": np.full(n, 500, dtype=np.int64),
        })
        plan = _plan_for(eng, AGG_Q)
        with pytest.raises(RuntimeError, match="boom") as ei:
            eng.execute_plan(plan)
        # The original producer-side traceback survives the relay.
        funcs = [f.name for f in traceback.extract_tb(ei.value.__traceback__)]
        assert "_stage" in funcs
        assert "_produce" in funcs
        _assert_no_prefetch_threads()
        # Engine still usable after the failure.
        eng._n_staged = -(10 ** 9)
        out = eng.execute_plan(plan)
        assert out["output"].length == 7


@pytest.mark.stress
class TestConcurrentStress:
    def test_concurrent_queries_no_thread_leak(self):
        """Complete + cancelled + erroring pipelined queries across
        concurrent engines: threading.active_count() is restored and no
        prefetch thread survives."""
        _assert_no_prefetch_threads()
        base = threading.active_count()
        engines = [_mk_engine(n=8 * W, depth=3) for _ in range(3)]
        boom = _BoomEngine(window_rows=W, pipeline_depth=3, boom_after=2)
        n = 8 * W
        boom.append_data("t", {
            "time_": np.arange(n, dtype=np.int64),
            "k": np.arange(n, dtype=np.int64) % 7,
            "v": np.full(n, 500, dtype=np.int64),
        })
        plans = [_plan_for(e, AGG_Q) for e in engines]
        boom_plan = _plan_for(boom, AGG_Q)
        engines[0].execute_plan(plans[0])  # compile once up front
        errors = []

        def ok(e, p):
            try:
                for _ in range(4):
                    assert e.execute_plan(p)["output"].length == 41
            except Exception as exc:  # pragma: no cover
                errors.append(exc)

        def cancelled(e, p):
            try:
                for _ in range(4):
                    with pytest.raises(QueryCancelled):
                        e.execute_plan(p, cancel=_TripAfter(2))
            except Exception as exc:  # pragma: no cover
                errors.append(exc)

        def erroring():
            try:
                for _ in range(4):
                    boom._n_staged = 0
                    with pytest.raises(RuntimeError, match="boom"):
                        boom.execute_plan(boom_plan)
            except Exception as exc:  # pragma: no cover
                errors.append(exc)

        threads = [
            threading.Thread(target=ok, args=(engines[0], plans[0])),
            threading.Thread(target=ok, args=(engines[1], plans[1])),
            threading.Thread(target=cancelled, args=(engines[2], plans[2])),
            threading.Thread(target=erroring),
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
            assert not t.is_alive(), "stress worker hung"
        assert not errors, errors
        _assert_no_prefetch_threads()
        deadline = time.time() + 5
        while time.time() < deadline and threading.active_count() > base:
            time.sleep(0.01)
        assert threading.active_count() <= base


class TestStreamingPipelined:
    def test_incremental_polls_match_serial(self):
        from pixie_tpu.exec.streaming import stream_query

        def run(depth):
            eng = Engine(window_rows=W, pipeline_depth=depth)
            rng = np.random.default_rng(9)
            ups = []
            eng.append_data("t", {
                "time_": np.arange(3 * W, dtype=np.int64),
                "k": rng.integers(0, 11, 3 * W),
                "v": rng.integers(0, 100, 3 * W),
            })
            sq = stream_query(eng, AGG_Q, emit=ups.append)
            sq.poll()
            eng.append_data("t", {
                "time_": np.arange(3 * W, 6 * W, dtype=np.int64),
                "k": rng.integers(0, 11, 3 * W),
                "v": rng.integers(0, 100, 3 * W),
            })
            sq.poll()
            return [u.batch.to_pydict(decode_strings=False) for u in ups]

        serial, pipelined = run(1), run(3)
        assert len(serial) == len(pipelined) == 2
        for a, b in zip(serial, pipelined):
            assert set(a) == set(b)
            for c in a:
                np.testing.assert_array_equal(a[c], b[c])
        _assert_no_prefetch_threads()


class TestWindowedDeviceJoin:
    @pytest.mark.parametrize("how", ["inner", "left"])
    def test_bit_identical_to_single_shot(self, how):
        from pixie_tpu.exec.joins import _join_device
        from pixie_tpu.exec.plan import JoinOp
        from pixie_tpu.types.batch import HostBatch

        rng = np.random.default_rng(23)
        nl, nr = 700, 300
        left = HostBatch.from_pydict({
            "k": rng.integers(0, 80, nl),  # some keys match nothing
            "lv": np.arange(nl, dtype=np.int64),
        }, time_cols=())
        right = HostBatch.from_pydict({
            "k": rng.integers(0, 50, nr),  # dup keys -> N:M fan-out
            "rv": np.arange(nr, dtype=np.int64) + 1000,
        }, time_cols=())
        op = JoinOp(left_on=("k",), right_on=("k",), how=how)

        config.set_flag("join_probe_window_rows", 0)
        try:
            single = _join_device(left, right, op).to_pydict()
        finally:
            config.clear_flag("join_probe_window_rows")
        config.set_flag("join_probe_window_rows", 64)
        try:
            windowed = _join_device(left, right, op).to_pydict()
        finally:
            config.clear_flag("join_probe_window_rows")
        assert set(single) == set(windowed)
        for c in single:
            np.testing.assert_array_equal(single[c], windowed[c])
        _assert_no_prefetch_threads()

    def test_float_keys_windowed_not_truncated(self):
        """Float join keys must densify exactly, never cast to int64
        (1.2 and 1.7 are different keys)."""
        from pixie_tpu.exec.joins import _join_device
        from pixie_tpu.exec.plan import JoinOp
        from pixie_tpu.types.batch import HostBatch

        left = HostBatch.from_pydict({
            "k": np.array([1.2, 1.7, 2.5, 3.0], dtype=np.float64),
            "lv": np.arange(4, dtype=np.int64),
        }, time_cols=())
        right = HostBatch.from_pydict({
            "k": np.array([1.7, 2.5], dtype=np.float64),
            "rv": np.array([10, 20], dtype=np.int64),
        }, time_cols=())
        op = JoinOp(left_on=("k",), right_on=("k",), how="inner")
        config.set_flag("join_probe_window_rows", 2)
        try:
            out = _join_device(left, right, op).to_pydict()
        finally:
            config.clear_flag("join_probe_window_rows")
        assert sorted(out["rv"].tolist()) == [10, 20]  # 1.2 matches nothing

    @pytest.mark.parametrize("depth", [1, 2])  # serial must cancel too
    def test_windowed_join_respects_engine_cancel_and_depth(self, depth):
        from pixie_tpu.exec.joins import _join_device
        from pixie_tpu.exec.plan import JoinOp
        from pixie_tpu.types.batch import HostBatch

        n = 600
        left = HostBatch.from_pydict({
            "k": np.arange(n, dtype=np.int64) % 50,
            "lv": np.arange(n, dtype=np.int64),
        }, time_cols=())
        right = HostBatch.from_pydict({
            "k": np.arange(50, dtype=np.int64),
            "rv": np.arange(50, dtype=np.int64),
        }, time_cols=())
        op = JoinOp(left_on=("k",), right_on=("k",), how="inner")

        class _Eng:  # engine stand-in: depth + a fired cancel handle
            pipeline_depth = depth
            _cancel = _TripAfter(1)

            @staticmethod
            def _note_pipeline(pipe):
                pass

        config.set_flag("join_probe_window_rows", 64)
        try:
            with pytest.raises(QueryCancelled):
                _join_device(left, right, op, _Eng)
        finally:
            config.clear_flag("join_probe_window_rows")
        _assert_no_prefetch_threads()

    def test_multi_key_windowed(self):
        from pixie_tpu.exec.joins import _join_device
        from pixie_tpu.exec.plan import JoinOp
        from pixie_tpu.types.batch import HostBatch

        rng = np.random.default_rng(29)
        nl, nr = 400, 200
        left = HostBatch.from_pydict({
            "a": rng.integers(0, 9, nl), "b": rng.integers(0, 5, nl),
            "lv": np.arange(nl, dtype=np.int64),
        }, time_cols=())
        right = HostBatch.from_pydict({
            "a": rng.integers(0, 9, nr), "b": rng.integers(0, 5, nr),
            "rv": np.arange(nr, dtype=np.int64),
        }, time_cols=())
        op = JoinOp(left_on=("a", "b"), right_on=("a", "b"), how="inner")
        config.set_flag("join_probe_window_rows", 0)
        try:
            single = _join_device(left, right, op).to_pydict()
        finally:
            config.clear_flag("join_probe_window_rows")
        config.set_flag("join_probe_window_rows", 128)
        try:
            windowed = _join_device(left, right, op).to_pydict()
        finally:
            config.clear_flag("join_probe_window_rows")
        for c in single:
            np.testing.assert_array_equal(single[c], windowed[c])


class TestInstrumentation:
    def test_last_pipeline_and_analyze_stall(self):
        eng = _mk_engine(n=6 * W, depth=2)
        eng.execute_query(AGG_Q, analyze=True)
        lp = eng.last_pipeline
        assert lp is not None and lp["depth"] == 2
        assert lp["windows"] >= 6
        frag = eng.last_stats.fragments[-1]
        assert "stall" in frag.stages  # consumer wait time is attributed
        tot = eng.pipeline_totals
        assert tot["windows"] >= lp["windows"]

    def test_serial_depth_records_windows_only(self):
        eng = _mk_engine(n=3 * W, depth=1)
        eng.execute_query(AGG_Q)
        lp = eng.last_pipeline
        assert lp["depth"] == 1
        assert lp["windows"] >= 3
        assert lp["stall_secs"] == 0.0

    def test_observability_exports_pipeline_metrics(self):
        from pixie_tpu.services.observability import (
            MetricsRegistry,
            engine_collector,
        )

        eng = _mk_engine(n=2 * W, depth=2)
        eng.execute_query(AGG_Q)
        reg = MetricsRegistry()
        reg.register_collector(engine_collector(eng))
        body = reg.render()
        assert "pixie_pipeline_depth 2" in body
        assert "pixie_pipeline_windows_total" in body
        assert "pixie_pipeline_stage_seconds_total" in body
        # The stall is recorded once: the tracer's per-interval
        # histogram (its _sum is the lifetime total the gauge repeated).
        assert "pixie_pipeline_stall_seconds" not in body
        assert 'pixie_window_stage_seconds_count{stage="stall"}' in (
            eng.tracer.registry.render()
        )
