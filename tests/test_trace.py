"""Query-lifecycle tracing tests (exec/trace.py — ISSUE 3 tentpole).

Covers the acceptance surface: default-flag queries yield a trace
(compile + fragment spans with window counts) retrievable from the ring
buffer and /debug/queryz; /metrics exposes the
pixie_query_duration_seconds histogram; an engine trace round-trips
through the OTLP span encoding and the OTLPHttpExporter; the slow-query
log fires on threshold; error/cancel statuses land; streaming queries
trace their lifetime; and the always-on spine never forces device sync
(sync=False unless analyze).
"""

from __future__ import annotations

import json
import logging
import threading

import numpy as np
import pytest

from pixie_tpu import config
from pixie_tpu.exec import Engine
from pixie_tpu.exec.stream import QueryCancelled, QueryError
from pixie_tpu.exec.trace import Tracer
from pixie_tpu.services.observability import (
    MetricsRegistry,
    ObservabilityServer,
)

W = 1 << 10

AGG_Q = (
    "import px\n"
    "df = px.DataFrame(table='t')\n"
    "df = df.groupby('k').agg(n=('v', px.count), s=('v', px.sum))\n"
    "px.display(df)\n"
)


def _mk_engine(n=5 * W + 13, **kw):
    eng = Engine(window_rows=W, **kw)
    rng = np.random.default_rng(3)
    eng.append_data("t", {
        "time_": np.arange(n, dtype=np.int64),
        "k": rng.integers(0, 11, n),
        "v": rng.integers(0, 1000, n),
    })
    return eng


class TestTraceSpine:
    def test_default_flags_query_yields_trace(self):
        eng = _mk_engine()
        eng.execute_query(AGG_Q)
        tr = eng.tracer.last()
        assert tr is not None and tr.status == "ok"
        names = [s.name for s in tr.spans]
        assert names[0] == "query" and "compile" in names
        frags = [s for s in tr.spans if s.name == "fragment"]
        assert len(frags) >= 1
        assert tr.windows >= 5  # one per streamed window
        assert tr.rows_in == 5 * W + 13
        # Span tree is consistent: every non-root parent exists.
        ids = {s.span_id for s in tr.spans}
        assert all(s.parent_id in ids for s in tr.spans if s.parent_id)
        assert tr.end_unix_nano >= tr.start_unix_nano
        # Always-on = never syncs: the spine runs with sync=False.
        assert tr.stats.sync is False
        assert all(f.sync is False for f in tr.stats.fragments)

    def test_fragment_span_attributes(self):
        eng = _mk_engine()
        eng.execute_query(AGG_Q)
        tr = eng.tracer.last()
        frag = next(s for s in tr.spans if s.name == "fragment")
        assert frag.attributes["windows"] >= 5
        assert frag.attributes["rows_in"] == 5 * W + 13
        assert "AggOp" in frag.attributes["ops"]
        assert frag.attributes.get("compute_seconds", 0) >= 0

    def test_window_spans_sampled(self):
        eng = _mk_engine()
        # cpu_fold_threads=1: the XLA fold the chip runs, not the CPU
        # backend's native kernel.
        with config.override_flag("trace_window_sample", 1), \
                config.override_flag("cpu_fold_threads", 1):
            eng.execute_query(AGG_Q)
        tr = eng.tracer.last()
        wspans = [s for s in tr.spans if s.name.startswith("window.")]
        assert {s.name for s in wspans} >= {"window.stall"}
        # A program's enqueue is a device.dispatch span, never sampled:
        # one a window here (the CPU backend folds window by window),
        # then the finalize; the result's readback is the device.wait.
        dispatches = [s for s in tr.spans if s.name == "device.dispatch"]
        assert [d.attributes["program"] for d in dispatches] == (
            ["fragment_update"] * tr.windows + ["fragment_finalize"]
        )
        # ... in two parts: the overflow flag (the finalize has run),
        # then the result's planes.
        waits = [s for s in tr.spans if s.name == "device.wait"]
        assert len(waits) == 2
        assert waits[0].start_ns >= dispatches[-1].end_ns
        frag_ids = {s.span_id for s in tr.spans if s.name == "fragment"}
        assert all(s.parent_id in frag_ids
                   for s in wspans + dispatches + waits)
        # sample=0 disables window spans entirely (not the programs').
        with config.override_flag("trace_window_sample", 0), \
                config.override_flag("cpu_fold_threads", 1):
            eng.execute_query(AGG_Q)
        tr0 = eng.tracer.last()
        assert not [s for s in tr0.spans if s.name.startswith("window.")]
        assert [s for s in tr0.spans if s.name == "device.dispatch"]

    def test_analyze_is_a_detail_level_of_the_trace(self):
        eng = _mk_engine()
        eng.execute_query(AGG_Q, analyze=True)
        tr = eng.tracer.last()
        assert tr.stats.sync is True
        assert eng.last_stats is tr.stats  # same spine object
        assert eng.last_stats.total_seconds > 0

    def test_error_status_recorded(self):
        eng = _mk_engine()
        with pytest.raises(Exception):
            eng.execute_query("import px\npx.display(px.DataFrame(table='nope'))\n")
        tr = eng.tracer.last()
        assert tr.status == "error" and tr.error
        reg = eng.tracer.registry
        assert reg.quantiles(
            "pixie_query_duration_seconds", (0.5,), status="error"
        )

    def test_cancel_status_recorded(self):
        eng = _mk_engine(pipeline_depth=2)
        ev = threading.Event()
        ev.set()
        from pixie_tpu.exec.plan import (
            AggExpr, AggOp, MemorySourceOp, Plan, ResultSinkOp,
        )
        from pixie_tpu.exec.plan import ColumnRef as C

        p = Plan()
        src = p.add(MemorySourceOp(table="t"))
        agg = p.add(AggOp(("k",), (AggExpr("n", "count", (C("v"),)),)), [src])
        p.add(ResultSinkOp("output"), [agg])
        with pytest.raises(QueryCancelled):
            eng.execute_plan(p, cancel=ev)
        assert eng.tracer.last().status == "cancelled"

    def test_override_raising_before_base_does_not_leak_trace(self):
        """An execute_plan override can raise before reaching the base
        implementation (DistributedEngine's replan) — execute_query's
        safety net must still end the trace."""

        class ReplanFails(Engine):
            def execute_plan(self, plan, **kw):
                raise QueryError("no live agent")

        eng = ReplanFails(window_rows=W)
        eng.append_data("t", {"time_": np.arange(8, dtype=np.int64),
                              "v": np.arange(8, dtype=np.int64)})
        with pytest.raises(QueryError):
            eng.execute_query(
                "import px\npx.display(px.DataFrame(table='t'))\n"
            )
        assert eng.tracer.in_flight() == []  # not leaked as running
        tr = eng.tracer.last()
        assert tr.status == "error" and "no live agent" in tr.error

    def test_ring_buffer_bounded(self):
        eng = _mk_engine(n=W)
        eng.tracer = Tracer(ring_size=3)
        for _ in range(5):
            eng.execute_query(AGG_Q)
        assert len(eng.tracer.recent()) == 3
        assert eng.tracer.in_flight() == []

    def test_plan_script_hash_stable(self):
        from pixie_tpu.exec.trace import plan_script
        from pixie_tpu.exec.plan import MemorySourceOp, Plan, ResultSinkOp

        def mk():
            p = Plan()
            src = p.add(MemorySourceOp(table="t"))
            p.add(ResultSinkOp("output"), [src])
            return p

        assert plan_script(mk()) == plan_script(mk())
        assert plan_script(mk()).startswith("plan:")


class TestQueryz:
    def test_debug_queryz_lists_recent_and_inflight(self):
        eng = _mk_engine()
        eng.execute_query(AGG_Q)
        srv = ObservabilityServer(
            registry=MetricsRegistry(), tracer=eng.tracer
        )
        code, ctype, body = srv.handle("/debug/queryz")
        assert code == 200 and "json" in ctype
        qz = json.loads(body)
        assert qz["in_flight"] == []
        row = qz["recent"][0]
        assert row["status"] == "ok"
        assert row["windows"] >= 5 and row["rows_in"] == 5 * W + 13
        assert row["duration_ms"] > 0
        assert len(row["script_hash"]) == 12
        assert row["query"].startswith("import px")
        assert row["fragments"] and row["fragments"][0]["windows"] >= 5
        # In-flight queries appear while running.
        tr = eng.tracer.begin_query(script="live one")
        qz2 = json.loads(srv.handle("/debug/queryz")[2])
        assert [r["id"] for r in qz2["in_flight"]] == [tr.trace_id]
        assert qz2["in_flight"][0]["status"] == "running"
        eng.tracer.end_query(tr)

    def test_queryz_404_without_tracer(self):
        srv = ObservabilityServer(registry=MetricsRegistry())
        assert srv.handle("/debug/queryz")[0] == 404

    def test_metrics_expose_query_histograms(self):
        eng = _mk_engine()
        reg = MetricsRegistry()
        eng.tracer = Tracer(registry=reg)
        eng.execute_query(AGG_Q)
        body = reg.render()
        assert 'pixie_query_duration_seconds_bucket{status="ok",le="+Inf"} 1' in body
        assert "pixie_query_duration_seconds_sum" in body
        assert 'pixie_query_duration_seconds_count{status="ok"} 1' in body
        assert 'pixie_window_stage_seconds_bucket{stage="compute",le="+Inf"}' in body
        assert "pixie_queries_total" in body


class TestOTLPRoundTrip:
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

    def test_engine_trace_round_trips_otlp(self):
        httpd, received = self._serve()
        try:
            url = f"http://127.0.0.1:{httpd.server_address[1]}"
            eng = _mk_engine()
            with config.override_flag("trace_export_url", url):
                eng.execute_query(AGG_Q)
            assert len(received) == 1
            path, payload = received[0]
            assert path == "/v1/traces"
            spans = payload["resourceSpans"][0]["scopeSpans"][0]["spans"]
            names = [s["name"] for s in spans]
            assert names[0] == "query" and "compile" in names
            root = spans[0]
            assert len(root["traceId"]) == 32 and len(root["spanId"]) == 16
            assert all(s["traceId"] == root["traceId"] for s in spans)
            kids = [s for s in spans if s.get("parentSpanId")]
            ids = {s["spanId"] for s in spans}
            assert kids and all(s["parentSpanId"] in ids for s in kids)
            frag = next(s for s in spans if s["name"] == "fragment")
            attrs = {
                kv["key"]: kv["value"]["stringValue"]
                for kv in frag["attributes"]
            }
            assert int(attrs["windows"]) >= 5
            res_attrs = {
                kv["key"]: kv["value"]["stringValue"]
                for kv in payload["resourceSpans"][0]["resource"]["attributes"]
            }
            assert res_attrs["service.name"] == "pixie-tpu-engine"
        finally:
            httpd.shutdown()

    def test_export_failure_never_fails_query(self):
        eng = _mk_engine(n=W)
        reg = MetricsRegistry()
        eng.tracer = Tracer(registry=reg)
        with config.override_flag("trace_export_url", "http://127.0.0.1:9"):
            eng.execute_query(AGG_Q)  # must not raise
        body = reg.render()
        assert "pixie_trace_export_errors_total 1" in body


class TestSlowQueryLog:
    def test_slow_query_dumps_trace(self, caplog):
        eng = _mk_engine()
        with config.override_flag("slow_query_threshold_ms", 0.0001):
            with caplog.at_level(logging.WARNING, logger="pixie_tpu.slow_query"):
                eng.execute_query(AGG_Q)
        msgs = [r.getMessage() for r in caplog.records]
        assert msgs and "slow query" in msgs[-1]
        payload = json.loads(msgs[-1][msgs[-1].index("{"):])
        assert payload["status"] == "ok" and payload["fragments"]

    def test_threshold_zero_disables(self, caplog):
        eng = _mk_engine(n=W)
        with config.override_flag("slow_query_threshold_ms", 0):
            with caplog.at_level(logging.WARNING, logger="pixie_tpu.slow_query"):
                eng.execute_query(AGG_Q)
        assert not caplog.records


class TestStreamingTrace:
    def test_stream_lifecycle_traced(self):
        from pixie_tpu.exec.streaming import stream_query

        eng = _mk_engine(n=3 * W)
        updates = []
        sq = stream_query(eng, AGG_Q, updates.append)
        assert [t["kind"] for t in eng.tracer.in_flight()] == ["stream"]
        sq.run(poll_interval_s=0.01, max_rounds=2)
        assert updates
        assert eng.tracer.in_flight() == []
        tr = eng.tracer.last()
        assert tr.kind == "stream" and tr.status == "ok"
        assert tr.rows_in == 3 * W and tr.windows == 3
        assert tr.script.startswith("import px")

    def test_stream_close_idempotent(self):
        from pixie_tpu.exec.streaming import stream_query

        eng = _mk_engine(n=W)
        sq = stream_query(eng, AGG_Q, lambda u: None)
        sq.poll()
        sq.close()
        sq.close()  # second close is a no-op
        assert eng.tracer.last().status == "ok"
        assert eng.tracer.in_flight() == []

    def test_stream_cancel_status(self):
        from pixie_tpu.exec.streaming import stream_query

        eng = _mk_engine(n=W)
        ev = threading.Event()
        sq = stream_query(eng, AGG_Q, lambda u: None, cancel=ev)
        ev.set()
        sq.run(poll_interval_s=0.01)
        assert eng.tracer.last().status == "cancelled"


class TestPipelineOverlapPreserved:
    def test_no_sync_introduced_by_tracing(self):
        """Serial vs pipelined outputs stay bit-identical with tracing
        always on (the broader A/B matrix lives in test_pipeline.py);
        the pipeline snapshot lands on the trace."""
        outs = {}
        for depth in (1, 2):
            eng = _mk_engine(n=5 * W + 13, pipeline_depth=depth)
            with config.override_flag("device_residency", False):
                outs[depth] = eng.execute_query(AGG_Q)["output"].to_pydict()
            tr = eng.tracer.last()
            assert tr.status == "ok"
            assert tr.pipeline and tr.pipeline["depth"] == depth
            assert tr.pipeline["windows"] >= 5
        for c in outs[1]:
            assert np.array_equal(outs[1][c], outs[2][c])
