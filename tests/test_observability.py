"""Observability sweep: time-ordered union, cron runner, OTLP pusher,
string-carry guard, metrics/healthz endpoints."""

import json
import threading
import urllib.request

import numpy as np
import pytest

from pixie_tpu.exec import (
    AggExpr,
    AggOp,
    ColumnRef,
    Engine,
    MemorySourceOp,
    Plan,
    QueryError,
    ResultSinkOp,
    UnionOp,
)
from pixie_tpu.exec.plan import BridgeSinkOp, BridgeSourceOp
from pixie_tpu.services.observability import (
    MetricsRegistry,
    ObservabilityServer,
    engine_collector,
)
from pixie_tpu.services.script_runner import CronScript, ScriptRunner

C = ColumnRef


class TestTimeOrderedUnion:
    def test_union_merges_by_time(self):
        e = Engine(window_rows=1 << 10)
        e.append_data("a", {"time_": np.array([0, 10, 20], np.int64),
                            "v": np.array([1, 2, 3], np.int64)})
        e.append_data("b", {"time_": np.array([5, 15, 25], np.int64),
                            "v": np.array([9, 8, 7], np.int64)})
        p = Plan()
        sa = p.add(MemorySourceOp(table="a"))
        sb = p.add(MemorySourceOp(table="b"))
        u = p.add(UnionOp(), [sa, sb])
        p.add(ResultSinkOp("output"), [u])
        out = e.execute_plan(p)["output"].to_pydict()
        assert list(out["time_"]) == [0, 5, 10, 15, 20, 25]
        assert list(out["v"]) == [1, 9, 2, 8, 3, 7]


class TestStringCarryGuard:
    def _agent(self, strings):
        e = Engine(window_rows=1 << 10)
        e.append_data("t", {"time_": np.arange(len(strings), dtype=np.int64),
                            "k": np.ones(len(strings), np.int64),
                            "s": strings})
        return e

    def _plans(self):
        from pixie_tpu.planner.distributed.splitter import Splitter

        p = Plan()
        src = p.add(MemorySourceOp(table="t"))
        agg = p.add(
            AggOp(("k",), (AggExpr("first_s", "any", (C("s"),)),)), [src]
        )
        p.add(ResultSinkOp("output"), [agg])
        return Splitter().split(p)

    def test_unshared_dicts_rejected(self):
        split = self._plans()
        e1 = self._agent(["aaa", "bbb"])
        e2 = self._agent(["zzz", "aaa"])  # different dictionary object/order
        p1 = e1.execute_plan(split.before_blocking)[("bridge", 0)]
        p2 = e2.execute_plan(split.before_blocking)[("bridge", 0)]
        merge = Engine(window_rows=1 << 10)
        with pytest.raises(QueryError, match="string ids"):
            merge.execute_plan(
                split.after_blocking, bridge_inputs={0: [p1, p2]}
            )

    def test_shared_dict_allowed(self):
        from pixie_tpu.types.strings import StringDictionary

        split = self._plans()
        shared = StringDictionary(["aaa", "bbb", "zzz"])
        engines = []
        for strs in (["aaa", "bbb"], ["zzz", "aaa"]):
            e = Engine(window_rows=1 << 10)
            t = e.create_table("t")
            ids = np.array([shared.lookup(s) for s in strs], np.int32)
            from pixie_tpu.types.batch import HostBatch
            from pixie_tpu.types.dtypes import DataType
            from pixie_tpu.types.relation import Relation

            rel = Relation([("time_", DataType.TIME64NS),
                            ("k", DataType.INT64), ("s", DataType.STRING)])
            hb = HostBatch(relation=rel, cols={
                "time_": (np.arange(2, dtype=np.int64),),
                "k": (np.ones(2, np.int64),),
                "s": (ids,),
            }, length=2, dicts={"s": shared})
            e.append_data("t", hb)
            engines.append(e)
        payloads = [
            e.execute_plan(split.before_blocking)[("bridge", 0)]
            for e in engines
        ]
        merge = Engine(window_rows=1 << 10)
        out = merge.execute_plan(
            split.after_blocking, bridge_inputs={0: payloads}
        )["output"].to_pydict()
        assert out["first_s"][0] in ("aaa", "bbb", "zzz")


class TestScriptRunner:
    def _engine(self):
        e = Engine(window_rows=1 << 10)
        e.append_data("t", {"time_": np.arange(10, dtype=np.int64),
                            "v": np.arange(10, dtype=np.int64)})
        return e

    QUERY = "import px\ndf = px.DataFrame(table='t')\npx.display(df.head(3))\n"

    def test_tick_runs_due_scripts_on_frequency(self):
        runner = ScriptRunner(self._engine())
        runner.upsert(CronScript("s1", self.QUERY, frequency_s=10))
        recs = runner.tick(now_s=100.0)
        assert len(recs) == 1 and recs[0].ok
        assert recs[0].row_counts == {"output": 3}
        assert runner.tick(now_s=105.0) == []  # not due yet
        assert len(runner.tick(now_s=110.0)) == 1

    def test_broken_script_recorded_not_raised(self):
        runner = ScriptRunner(self._engine())
        runner.upsert(CronScript("bad", "import px\npx.nope()\n", 1))
        (rec,) = runner.tick(now_s=0.0)
        assert not rec.ok and rec.error

    def test_compare_state_reconciles(self):
        runner = ScriptRunner(self._engine())
        runner.upsert(CronScript("old", self.QUERY, 1))
        truth = {
            "s1": CronScript("s1", self.QUERY, 5),
            "s2": CronScript("s2", self.QUERY, 7, enabled=False),
        }
        runner.compare_state(truth)
        have = runner.scripts()
        assert set(have) == {"s1", "s2"}
        # checksum change (frequency) re-syncs
        runner.compare_state({"s1": CronScript("s1", self.QUERY, 9),
                              "s2": truth["s2"]})
        assert runner.scripts()["s1"].frequency_s == 9
        # disabled scripts never run
        assert all(r.script_id != "s2" for r in runner.tick(now_s=0.0))


class TestOTLPPusher:
    def _serve(self):
        import http.server

        received = []

        class H(http.server.BaseHTTPRequestHandler):
            def do_POST(self):
                body = self.rfile.read(int(self.headers["Content-Length"]))
                received.append((self.path, json.loads(body)))
                self.send_response(200)
                self.end_headers()

            def log_message(self, *a):
                pass

        httpd = http.server.ThreadingHTTPServer(("127.0.0.1", 0), H)
        threading.Thread(target=httpd.serve_forever, daemon=True).start()
        return httpd, received

    def test_pushes_metrics_and_traces(self):
        from pixie_tpu.exec.otel import OTLPHttpExporter

        httpd, received = self._serve()
        try:
            url = f"http://127.0.0.1:{httpd.server_address[1]}"
            exp = OTLPHttpExporter(url, headers=(("x-api-key", "k"),))
            exp({"resourceMetrics": [{"scopeMetrics": []}],
                 "resourceSpans": [{"scopeSpans": []}]})
            assert exp.pushed == 2
            paths = sorted(p for p, _ in received)
            assert paths == ["/v1/metrics", "/v1/traces"]
        finally:
            httpd.shutdown()

    def test_push_failure_raises_after_retries(self):
        from pixie_tpu.exec.otel import ExportError, OTLPHttpExporter

        exp = OTLPHttpExporter("http://127.0.0.1:9", max_retries=1,
                               timeout_s=0.2)
        with pytest.raises(ExportError):
            exp({"resourceMetrics": [{}]})
        assert exp.errors == 1

    def test_engine_export_hook(self):
        from pixie_tpu.exec.otel import OTLPHttpExporter

        httpd, received = self._serve()
        try:
            url = f"http://127.0.0.1:{httpd.server_address[1]}"
            e = Engine(window_rows=1 << 10)
            e.export_otel = OTLPHttpExporter(url)
            e.export_otel({"resourceMetrics": [{"x": 1}]})
            assert [p for p, _ in received] == ["/v1/metrics"]
        finally:
            httpd.shutdown()


class TestObservabilityServer:
    def test_endpoints(self):
        e = Engine(window_rows=1 << 10)
        e.append_data("t", {"time_": np.arange(7, dtype=np.int64),
                            "v": np.arange(7, dtype=np.int64)})
        reg = MetricsRegistry()
        reg.counter("pixie_queries_total", "Queries executed").inc(3)
        reg.register_collector(engine_collector(e))
        srv = ObservabilityServer(
            registry=reg, statusz_fn=lambda: {"role": "pem"}
        )
        port = srv.start(0)
        try:
            def get(path):
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}{path}", timeout=5
                ) as r:
                    return r.status, r.read().decode()

            code, body = get("/healthz")
            assert code == 200 and body.strip() == "ok"
            code, body = get("/statusz")
            st = json.loads(body)
            assert st["role"] == "pem" and "window_rows" in st["flags"]
            code, body = get("/metrics")
            assert "pixie_queries_total 3" in body
            assert 'pixie_table_rows{table="t"} 7' in body
            assert "pixie_device_cache_bytes" in body
        finally:
            srv.stop()

    def test_unhealthy_returns_503(self):
        srv = ObservabilityServer(health_fn=lambda: (False, "agent expired"))
        code, _, body = srv.handle("/healthz")
        assert code == 503 and "expired" in body


class TestMetricsRegistry:
    """ISSUE-3 satellite coverage: histogram exposition, HELP escaping,
    collector robustness, counter monotonicity, gauge inc/dec."""

    def test_histogram_exposition_format(self):
        reg = MetricsRegistry()
        h = reg.histogram("pixie_test_seconds", "latency",
                          buckets=(0.1, 1.0, 10.0))
        for v in (0.05, 0.05, 0.5, 1.0, 99.0):  # 1.0 lands in le="1"
            h.observe(v)
        body = reg.render()
        lines = body.splitlines()
        assert "# TYPE pixie_test_seconds histogram" in lines
        # Buckets are CUMULATIVE; an observation equal to a bound counts
        # in that bound's bucket; +Inf equals _count.
        assert 'pixie_test_seconds_bucket{le="0.1"} 2' in lines
        assert 'pixie_test_seconds_bucket{le="1"} 4' in lines
        assert 'pixie_test_seconds_bucket{le="10"} 4' in lines
        assert 'pixie_test_seconds_bucket{le="+Inf"} 5' in lines
        assert "pixie_test_seconds_count 5" in lines
        (sum_line,) = [x for x in lines if x.startswith("pixie_test_seconds_sum")]
        assert abs(float(sum_line.split()[-1]) - 100.6) < 1e-9

    def test_histogram_labels(self):
        reg = MetricsRegistry()
        h = reg.histogram("pixie_test_seconds", "", buckets=(1.0,))
        h.labels(stage="a").observe(0.5)
        h.labels(stage="b").observe(2.0)
        body = reg.render()
        assert 'pixie_test_seconds_bucket{stage="a",le="1"} 1' in body
        assert 'pixie_test_seconds_bucket{stage="b",le="1"} 0' in body
        assert 'pixie_test_seconds_bucket{stage="b",le="+Inf"} 1' in body
        assert 'pixie_test_seconds_count{stage="a"} 1' in body

    def test_histogram_quantiles(self):
        reg = MetricsRegistry()
        h = reg.histogram("pixie_test_seconds", "", buckets=(1.0, 2.0, 4.0))
        for v in np.linspace(0.1, 3.9, 100):
            h.observe(float(v))
        q = reg.quantiles("pixie_test_seconds", (0.5, 0.99))
        assert 1.5 < q[0.5] < 2.5
        assert 3.0 < q[0.99] <= 4.0
        assert reg.quantiles("pixie_nope") is None

    def test_help_text_escaped(self):
        reg = MetricsRegistry()
        reg.counter("pixie_weird_total", "line1\nline2 \\ backslash").inc()
        body = reg.render()
        assert "# HELP pixie_weird_total line1\\nline2 \\\\ backslash" in body
        # Exactly one HELP line — the newline must not split the comment.
        assert len([x for x in body.splitlines()
                    if x.startswith("# HELP pixie_weird_total")]) == 1

    def test_raising_collector_does_not_kill_render(self):
        reg = MetricsRegistry()
        reg.counter("pixie_good_total", "survives").inc(2)

        def bad_collector(r):
            raise RuntimeError("boom")

        def good_collector(r):
            r.gauge("pixie_pulled", "").set(7)

        reg.register_collector(bad_collector)
        reg.register_collector(good_collector)
        body = reg.render()
        assert "pixie_good_total 2" in body
        assert "pixie_pulled 7" in body
        assert 'pixie_collector_errors_total{collector="bad_collector"} 1' in body
        # Counted per failing render.
        assert 'collector="bad_collector"} 2' in reg.render()

    def test_counter_rejects_negative(self):
        reg = MetricsRegistry()
        c = reg.counter("pixie_mono_total", "")
        c.inc(3)
        with pytest.raises(ValueError, match="monotonic"):
            c.inc(-1)
        assert "pixie_mono_total 3" in reg.render()

    def test_gauge_inc_dec(self):
        reg = MetricsRegistry()
        g = reg.gauge("pixie_inflight", "")
        g.inc()
        g.inc(4)
        g.dec(2)
        assert "pixie_inflight 3" in reg.render()
        g.labels(pool="a").inc()
        assert 'pixie_inflight{pool="a"} 1' in reg.render()


class TestConcurrentScrapes:
    def test_metrics_scrapes_race_engine_loop(self):
        """ThreadingHTTPServer /metrics scrapes must stay clean while the
        engine executes queries (collector reads racing table/tracer
        writes) — every response parses, no 500s, no lost updates."""
        e = Engine(window_rows=1 << 10)
        n = 4096
        e.append_data("t", {"time_": np.arange(n, dtype=np.int64),
                            "k": np.arange(n, dtype=np.int64) % 3,
                            "v": np.arange(n, dtype=np.int64)})
        reg = MetricsRegistry()
        from pixie_tpu.exec.trace import Tracer

        e.tracer = Tracer(registry=reg)
        reg.register_collector(engine_collector(e))
        srv = ObservabilityServer(registry=reg, tracer=e.tracer)
        port = srv.start(0)
        stop = threading.Event()
        errors = []

        def query_loop():
            q = ("import px\ndf = px.DataFrame(table='t')\n"
                 "df = df.groupby('k').agg(n=('v', px.count))\npx.display(df)\n")
            while not stop.is_set():
                try:
                    e.execute_query(q)
                except Exception as ex:  # pragma: no cover
                    errors.append(ex)
                    return

        def scrape_loop():
            for _ in range(20):
                try:
                    with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/metrics", timeout=10
                    ) as r:
                        assert r.status == 200
                        body = r.read().decode()
                    assert "pixie_table_rows" in body
                    with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/debug/queryz", timeout=10
                    ) as r:
                        json.loads(r.read().decode())
                except Exception as ex:  # pragma: no cover
                    errors.append(ex)
                    return

        qt = threading.Thread(target=query_loop)
        scrapers = [threading.Thread(target=scrape_loop) for _ in range(4)]
        qt.start()
        for t in scrapers:
            t.start()
        for t in scrapers:
            t.join(timeout=60)
        stop.set()
        qt.join(timeout=60)
        srv.stop()
        assert not errors, errors[:1]
        # The scrape actually saw the trace spine's histograms.
        body = reg.render()
        assert "pixie_query_duration_seconds_bucket" in body


class TestCrashHandler:
    """services/crash.py: signal_action.h analog — hard-fault stack
    dumps, uncaught-exception recording, fatal-handler last gasps."""

    def test_segfault_dumps_stacks_to_crash_log(self, tmp_path):
        import subprocess
        import sys

        log = tmp_path / "crash.log"
        code = (
            "from pixie_tpu.services import crash\n"
            f"crash.install(crash_log_path={str(log)!r})\n"
            "import faulthandler\n"
            "faulthandler._sigsegv()\n"
        )
        p = subprocess.run(
            [sys.executable, "-c", code], cwd="/root/repo",
            capture_output=True, text=True, timeout=60,
            env={"JAX_PLATFORMS": "cpu",
                 "PATH": "/usr/bin:/bin"},
        )
        assert p.returncode != 0
        out = log.read_text()
        assert "Segmentation fault" in out or "Fatal Python error" in out
        assert "Current thread" in out or "Thread" in out  # stack dump

    def test_uncaught_exception_runs_fatal_handlers(self, tmp_path):
        import subprocess
        import sys

        log = tmp_path / "crash.log"
        gasp = tmp_path / "gasp.txt"
        code = (
            "from pixie_tpu.services import crash\n"
            f"crash.install(crash_log_path={str(log)!r})\n"
            "crash.register_fatal_handler(\n"
            f"    lambda: open({str(gasp)!r}, 'w').write('flushed'))\n"
            "raise RuntimeError('kaboom')\n"
        )
        p = subprocess.run(
            [sys.executable, "-c", code], cwd="/root/repo",
            capture_output=True, text=True, timeout=60,
            env={"JAX_PLATFORMS": "cpu",
                 "PATH": "/usr/bin:/bin"},
        )
        assert p.returncode != 0
        assert "kaboom" in log.read_text()  # recorded before re-raise
        assert gasp.read_text() == "flushed"  # last-gasp handler ran
        assert "kaboom" in p.stderr  # previous hook still reports

    def test_thread_exception_recorded(self, tmp_path):
        import subprocess
        import sys

        log = tmp_path / "crash.log"
        code = (
            "import threading\n"
            "from pixie_tpu.services import crash\n"
            f"crash.install(crash_log_path={str(log)!r})\n"
            "t = threading.Thread(target=lambda: 1/0, name='worker')\n"
            "t.start(); t.join()\n"
            "print('main alive')\n"
        )
        p = subprocess.run(
            [sys.executable, "-c", code], cwd="/root/repo",
            capture_output=True, text=True, timeout=60,
            env={"JAX_PLATFORMS": "cpu",
                 "PATH": "/usr/bin:/bin"},
        )
        assert p.returncode == 0 and "main alive" in p.stdout
        out = log.read_text()
        assert "thread-exception:worker" in out
        assert "ZeroDivisionError" in out
