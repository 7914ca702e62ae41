"""Distributed trace stitching e2e (ISSUE 10 acceptance).

A distributed query over >=2 agents WITH fault injection enabled
produces ONE stitched trace — the broker's dispatch span parents every
agent fragment/merge span, verified by trace id + parent ids in the
actual OTLP payloads — and its resource usage (bytes staged, device ms,
wire bytes) is reported with per-agent attribution through
`px debug queries` AND a bundled PxL script over ``__queries__``.

Also: the ack-subscription dedup regression (one ``query.{qid}.ack``
dispatcher thread per query, not two) and the OTLP export failure
paths (unreachable endpoint, 4xx vs 5xx retry policy, mid-export
tracer shutdown).
"""

from __future__ import annotations

import http.server
import json
import threading
import time

import numpy as np
import pytest

from pixie_tpu import config
from pixie_tpu.exec.trace import Tracer
from pixie_tpu.scripts import load_script
from pixie_tpu.services import (
    AgentTracker,
    KelvinAgent,
    MessageBus,
    PEMAgent,
    QueryBroker,
)
from pixie_tpu.services.faults import FaultInjector
from pixie_tpu.services.observability import MetricsRegistry

FAST = dict(heartbeat_interval_s=0.05)

AGG_SCRIPT = (
    "import px\n"
    "df = px.DataFrame(table='http_events')\n"
    "df = df.groupby('service').agg(\n"
    "    n=('latency_ns', px.count), s=('latency_ns', px.sum))\n"
    "px.display(df, 'o')\n"
)


@pytest.fixture
def cluster():
    """2 PEMs + 1 Kelvin + broker, with fault injection ENABLED
    (at-least-once dispatch: every agent.*.execute duplicated once) —
    stitching must hold under duplicate delivery."""
    bus = MessageBus()
    inj = FaultInjector(seed=7)
    inj.duplicate("agent.*.execute", count=2)
    bus.fault_injector = inj
    tracker = AgentTracker(bus, expiry_s=60.0, check_interval_s=60.0)
    pems = [PEMAgent(bus, f"pem-{i}", **FAST).start() for i in range(2)]
    kelvin = KelvinAgent(bus, "kelvin-0", **FAST).start()
    rng = np.random.default_rng(1)
    for i, pem in enumerate(pems):
        n = 1500 + 500 * i
        pem.append_data("http_events", {
            "time_": np.arange(n, dtype=np.int64),
            "latency_ns": rng.integers(1000, 1_000_000, n),
            "resp_status": rng.choice(np.array([200, 404]), n),
            "service": [f"svc-{(i + j) % 3}" for j in range(n)],
        })
    for pem in pems:
        pem._register()
    # Every PEM, not the first to register (the telemetry tables make
    # ``schemas()`` non-empty at once): a plan made before the last one
    # is known merges a part of the rows.
    deadline = time.time() + 30
    while time.time() < deadline and len(
        tracker.distributed_state().pems_with_table("http_events")
    ) < len(pems):
        time.sleep(0.01)
    broker = QueryBroker(bus, tracker)
    yield bus, tracker, pems, kelvin, broker
    for a in pems + [kelvin]:
        a.stop()
    broker.close()
    tracker.close()
    bus.close()


def _otlp_collector():
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


class TestOneStitchedTrace:
    def test_otlp_payloads_form_one_trace(self, cluster):
        bus, tracker, pems, kelvin, broker = cluster
        httpd, received = _otlp_collector()
        try:
            url = f"http://127.0.0.1:{httpd.server_address[1]}"
            with config.override_flag("trace_export_url", url):
                res = broker.execute_script(AGG_SCRIPT)
        finally:
            httpd.shutdown()
        assert res["tables"]["o"].length == 3
        btr = broker.tracer.last()
        tid = btr.trace_id
        # Gather every exported span of the distributed trace.
        spans: dict = {}
        sources = set()
        for _path, payload in received:
            for rs in payload.get("resourceSpans", []):
                attrs = {
                    kv["key"]: kv["value"]["stringValue"]
                    for kv in rs["resource"]["attributes"]
                }
                for ss in rs["scopeSpans"]:
                    for s in ss["spans"]:
                        if s["traceId"] != tid:
                            continue
                        spans[s["spanId"]] = s
                        sources.add(
                            attrs.get("service.instance.id", "broker")
                        )
        # Every participant exported into the SAME trace id.
        assert sources == {"broker", "pem-0", "pem-1", "kelvin-0"}
        dispatch = next(
            s for s in spans.values() if s["name"] == "dispatch"
        )
        # Agent roots (fragment/merge "query" spans) parent under the
        # broker's dispatch span; their fragment spans parent under
        # them — the full chain reaches the broker root.
        agent_roots = [
            s for s in spans.values()
            if s["name"] == "query"
            and s.get("parentSpanId") == dispatch["spanId"]
        ]
        assert len(agent_roots) == 3  # 2 fragments + 1 merge
        root_ids = {s["spanId"] for s in agent_roots}
        frag_spans = [
            s for s in spans.values()
            if s["name"] == "fragment"
            and s.get("parentSpanId") in root_ids
        ]
        assert len(frag_spans) >= 3
        # And the dispatch span itself chains to the broker's root.
        broker_root = spans[dispatch["parentSpanId"]]
        assert broker_root["name"] == "query"
        assert not broker_root.get("parentSpanId")
        # Fault injection really fired (duplicate dispatch delivered).
        assert ("duplicate", "agent.pem-0.execute") in bus.fault_injector.log

    def test_engine_tracers_share_trace_and_parents(self, cluster):
        bus, tracker, pems, kelvin, broker = cluster
        broker.execute_script(AGG_SCRIPT)
        btr = broker.tracer.last()
        dispatch = next(s for s in btr.spans if s.name == "dispatch")
        for agent, kind in ((pems[0], "fragment"), (pems[1], "fragment"),
                            (kelvin, "merge")):
            tr = agent.engine.tracer.last()
            assert tr.trace_id == btr.trace_id
            assert tr.kind == kind and tr.qid == btr.qid
            assert tr.root.parent_id == dispatch.span_id

    def test_tracez_stitches_cluster_wide(self, cluster):
        from pixie_tpu.services.observability import ObservabilityServer

        bus, tracker, pems, kelvin, broker = cluster
        broker.execute_script(AGG_SCRIPT)
        btr = broker.tracer.last()
        deadline = time.time() + 5
        row = None
        while time.time() < deadline:
            row = broker.trace_view.get(btr.trace_id)
            if row and len(row["agents"]) >= 4:
                break
            time.sleep(0.02)
        assert row is not None
        assert set(row["agents"]) == {"broker", "pem-0", "pem-1",
                                      "kelvin-0"}
        srv = ObservabilityServer(
            registry=MetricsRegistry(), trace_view=broker.trace_view
        )
        code, ctype, body = srv.handle("/debug/tracez")
        assert code == 200 and "json" in ctype
        listing = json.loads(body)
        assert any(
            t["trace_id"] == btr.trace_id for t in listing["traces"]
        )
        code, _, body = srv.handle(f"/debug/tracez/{btr.trace_id}")
        assert code == 200
        one = json.loads(body)
        names = {s["name"] for s in one["spans"]}
        assert {"query", "dispatch", "fragment"} <= names


class TestResourceAccounting:
    def test_per_agent_usage_flows_to_broker(self, cluster):
        bus, tracker, pems, kelvin, broker = cluster
        res = broker.execute_script(AGG_SCRIPT)
        assert set(res["agent_stats"]) == {"pem-0", "pem-1"}
        for aid, entry in res["agent_stats"].items():
            u = entry["usage"]
            assert u["rows_in"] > 0 and u["windows"] >= 1
            assert u["wire_bytes"] > 0  # shipped a bridge payload
        btr = broker.tracer.last()
        # The broker folds BOTH tiers into its trace: data-agent usage
        # plus the merge tier's (role="merge", delivered best-effort —
        # whether it lands inside the post-eos grace drain is a race,
        # so the expected sum must include whatever merge_stats
        # actually arrived, not assume it missed).
        assert btr.usage.rows_in == sum(
            e["usage"]["rows_in"] for e in res["agent_stats"].values()
        ) + sum(
            e.get("usage", {}).get("rows_in", 0)
            for e in res.get("merge_stats", {}).values()
        )
        assert set(btr.agent_usage) >= {"pem-0", "pem-1"}
        assert btr.usage.wire_bytes > 0

    def test_debug_queries_topic_reports_usage(self, cluster):
        bus, tracker, pems, kelvin, broker = cluster
        broker.serve()
        res = broker.execute_script(AGG_SCRIPT)
        reply = bus.request("broker.debug_queries", {"limit": 5})
        assert reply["ok"]
        row = next(
            r for r in reply["queries"] if r.get("qid") == res["qid"]
        )
        assert row["status"] == "ok"
        assert row["usage"]["rows_in"] > 0
        assert set(row["agent_usage"]) >= {"pem-0", "pem-1"}
        for u in row["agent_usage"].values():
            assert "bytes_staged" in u and "device_ms" in u

    def test_pxl_query_cost_over_cluster_telemetry(self, cluster):
        """The acceptance loop: the system queries its OWN telemetry
        through the normal distributed engine path, with per-agent
        attribution from each agent's local __queries__ rows."""
        bus, tracker, pems, kelvin, broker = cluster
        res = broker.execute_script(AGG_SCRIPT)
        qid = res["qid"]
        # Re-register so the tracker sees the (now nonempty) telemetry
        # tables in the next planning snapshot.
        for a in pems + [kelvin]:
            a._register()
        deadline = time.time() + 5
        while time.time() < deadline and "__queries__" not in tracker.schemas():
            time.sleep(0.02)
        out = broker.execute_script(
            "import px\n"
            "df = px.DataFrame(table='__queries__')\n"
            "df = df.groupby(['qid', 'agent_id']).agg(\n"
            "    bytes_staged=('bytes_staged', px.sum),\n"
            "    device_ms=('device_ms', px.sum),\n"
            "    wire_bytes=('wire_bytes', px.sum),\n"
            ")\n"
            "px.display(df, 'cost')\n",
            max_output_rows=1000,
        )
        d = out["tables"]["cost"].to_pydict()
        rows = {
            (q, a): (b, dm, w)
            for q, a, b, dm, w in zip(
                d["qid"], d["agent_id"], d["bytes_staged"],
                d["device_ms"], d["wire_bytes"],
            )
        }
        # The first query's fragments appear once per executing agent.
        mine = {k: v for k in rows if k[0] == qid for v in [rows[k]]}
        assert {a for (_q, a) in mine} == {"pem-0", "pem-1"}
        for (_q, _a), (_b, _dm, w) in mine.items():
            assert w > 0  # each data agent shipped bridge bytes
        # The bundled script compiles + runs over the same tables.
        cost = broker.execute_script(
            load_script("px/query_cost").pxl, max_output_rows=1000
        )
        cd = cost["tables"]["output"].to_pydict()
        assert {"pem-0", "pem-1"} <= set(cd["agent_id"])


class TestAckDedup:
    """Satellite: ONE query.{qid}.ack subscription (and dispatcher
    thread) per registered query — the retry manager observes the
    forwarder's subscription instead of spawning its own."""

    def test_single_ack_subscription_and_thread(self, cluster):
        bus, tracker, pems, kelvin, broker = cluster
        qid = "ackdedup1"
        topic = f"query.{qid}.ack"
        broker.forwarder.register_query(
            qid, ["pem-0"], merge_agent="kelvin-0"
        )
        try:
            assert len(bus._subs.get(topic, [])) == 1
            dispatches = {
                ("pem-0", "execute"):
                    ("agent.nobody.execute", {"qid": qid, "plan": None}),
                ("kelvin-0", "merge"):
                    ("agent.nobody.merge", {"qid": qid, "plan": None}),
            }
            broker._dispatch_with_retry(qid, dispatches)
            # Still exactly ONE ack subscription + dispatcher thread.
            assert len(bus._subs.get(topic, [])) == 1
            ack_threads = [
                t for t in threading.enumerate()
                if t.name == f"bus-sub-{topic}"
            ]
            assert len(ack_threads) == 1
            # Acks land through the forwarder's subscription; the retry
            # manager sees them and stands down without ever publishing
            # an agent_lost verdict.
            bus.publish(topic, {"ack": "execute", "agent": "pem-0"})
            bus.publish(topic, {"ack": "merge", "agent": "kelvin-0"})
            deadline = time.time() + 2
            while time.time() < deadline:
                got = broker.forwarder.acked_keys(qid)
                if got == {("pem-0", "execute"), ("kelvin-0", "merge")}:
                    break
                time.sleep(0.01)
            assert broker.forwarder.acked_keys(qid) == {
                ("pem-0", "execute"), ("kelvin-0", "merge"),
            }
        finally:
            broker.forwarder._deregister(qid)

    def test_no_ack_threads_leak_after_query(self, cluster):
        bus, tracker, pems, kelvin, broker = cluster
        broker.execute_script(AGG_SCRIPT)
        deadline = time.time() + 3
        while time.time() < deadline:
            leaked = [
                t.name for t in threading.enumerate()
                if t.name.startswith("bus-sub-query.")
                and t.name.endswith(".ack")
            ]
            if not leaked:
                break
            time.sleep(0.05)
        assert leaked == []

    def test_retry_via_forwarder_acks_survives_dropped_dispatch(self):
        """The polled ack path must still drive retries: drop the first
        execute dispatch, let the broker re-publish, query completes."""
        bus = MessageBus()
        inj = FaultInjector(seed=3)
        inj.drop("agent.pem-0.execute", count=1)
        bus.fault_injector = inj
        tracker = AgentTracker(bus, expiry_s=60.0, check_interval_s=60.0)
        pems = [PEMAgent(bus, f"pem-{i}", **FAST).start() for i in range(2)]
        kelvin = KelvinAgent(bus, "kelvin-0", **FAST).start()
        rng = np.random.default_rng(2)
        for pem in pems:
            pem.append_data("http_events", {
                "time_": np.arange(500, dtype=np.int64),
                "latency_ns": rng.integers(1000, 10_000, 500),
                "resp_status": np.full(500, 200),
                "service": ["svc-a"] * 500,
            })
            pem._register()
        # Both PEMs, not the first to register: a plan made before the
        # second is known merges 500 rows, not 1,000.
        deadline = time.time() + 30
        while time.time() < deadline and len(
            tracker.distributed_state().pems_with_table("http_events")
        ) < 2:
            time.sleep(0.01)
        broker = QueryBroker(bus, tracker)
        try:
            res = broker.execute_script(AGG_SCRIPT, timeout_s=15.0)
            assert res["tables"]["o"].to_pydict()["n"].sum() == 1000
            assert ("drop", "agent.pem-0.execute") in inj.log
        finally:
            for a in pems + [kelvin]:
                a.stop()
            broker.close()
            tracker.close()
            bus.close()


class TestOTLPFailurePaths:
    """Satellite: export failure coverage beyond the happy path."""

    def _tracer(self):
        reg = MetricsRegistry()
        return Tracer(registry=reg), reg

    def _count(self, reg, name):
        for ln in reg.render().splitlines():
            if ln.startswith(name + " "):
                return float(ln.split()[-1])
        return 0.0

    def test_unreachable_endpoint_counts_not_raises(self):
        tracer, reg = self._tracer()
        with config.override_flag("trace_export_url", "http://127.0.0.1:9"):
            tracer.end_query(tracer.begin_query(script="x"))
        assert self._count(reg, "pixie_trace_export_errors_total") == 1
        assert tracer.last().exported is False

    def test_5xx_retries_then_counts(self):
        hits = []

        class H(http.server.BaseHTTPRequestHandler):
            def do_POST(self):
                self.rfile.read(int(self.headers["Content-Length"]))
                hits.append(self.path)
                self.send_response(503)
                self.end_headers()

            def log_message(self, *a):
                pass

        httpd = http.server.ThreadingHTTPServer(("127.0.0.1", 0), H)
        threading.Thread(target=httpd.serve_forever, daemon=True).start()
        try:
            tracer, reg = self._tracer()
            url = f"http://127.0.0.1:{httpd.server_address[1]}"
            with config.override_flag("trace_export_url", url):
                tracer.end_query(tracer.begin_query(script="x"))
            # Default exporter: 1 attempt + 2 retries on 5xx.
            assert len(hits) == 3
            assert self._count(reg, "pixie_trace_export_errors_total") == 1
        finally:
            httpd.shutdown()

    def test_4xx_no_retry(self):
        hits = []

        class H(http.server.BaseHTTPRequestHandler):
            def do_POST(self):
                self.rfile.read(int(self.headers["Content-Length"]))
                hits.append(self.path)
                self.send_response(400)
                self.end_headers()

            def log_message(self, *a):
                pass

        httpd = http.server.ThreadingHTTPServer(("127.0.0.1", 0), H)
        threading.Thread(target=httpd.serve_forever, daemon=True).start()
        try:
            tracer, reg = self._tracer()
            url = f"http://127.0.0.1:{httpd.server_address[1]}"
            with config.override_flag("trace_export_url", url):
                tracer.end_query(tracer.begin_query(script="x"))
            assert len(hits) == 1  # a 4xx is never retried
            assert self._count(reg, "pixie_trace_export_errors_total") == 1
        finally:
            httpd.shutdown()

    def test_shutdown_mid_export_never_raises(self):
        """A slow collector + tracer shutdown racing an in-flight push:
        the exporting end_query must complete without raising, and no
        export runs after shutdown."""
        release = threading.Event()
        hits = []

        class H(http.server.BaseHTTPRequestHandler):
            def do_POST(self):
                self.rfile.read(int(self.headers["Content-Length"]))
                hits.append(self.path)
                release.wait(5.0)  # hold the push in flight
                self.send_response(200)
                self.end_headers()

            def log_message(self, *a):
                pass

        httpd = http.server.ThreadingHTTPServer(("127.0.0.1", 0), H)
        threading.Thread(target=httpd.serve_forever, daemon=True).start()
        try:
            tracer, reg = self._tracer()
            url = f"http://127.0.0.1:{httpd.server_address[1]}"
            errors = []

            def run():
                try:
                    with config.override_flag("trace_export_url", url):
                        tracer.end_query(tracer.begin_query(script="slow"))
                except BaseException as e:  # noqa: BLE001 — the assertion
                    errors.append(e)

            t = threading.Thread(target=run)
            t.start()
            deadline = time.time() + 5
            while time.time() < deadline and not hits:
                time.sleep(0.01)
            assert hits, "export never reached the collector"
            tracer.shutdown()  # mid-export
            release.set()
            t.join(timeout=10)
            assert not t.is_alive() and errors == []
            before = len(hits)
            with config.override_flag("trace_export_url", url):
                tracer.end_query(tracer.begin_query(script="after"))
            assert len(hits) == before  # shutdown: no further exports
        finally:
            release.set()
            httpd.shutdown()


class TestCliDebugQueries:
    def test_px_debug_queries_over_netbus(self, cluster, capsys):
        from pixie_tpu import cli
        from pixie_tpu.services.netbus import BusServer

        bus, tracker, pems, kelvin, broker = cluster
        broker.serve()
        res = broker.execute_script(AGG_SCRIPT)
        server = BusServer(bus)
        try:
            rc = cli.main([
                "debug", "queries",
                "--broker", f"127.0.0.1:{server.port}", "-v",
            ])
            assert rc == 0
            out = capsys.readouterr().out
            assert res["qid"] in out
            assert "pem-0" in out and "pem-1" in out
            rc = cli.main([
                "debug", "queries", "-o", "json",
                "--broker", f"127.0.0.1:{server.port}",
            ])
            assert rc == 0
            payload = json.loads(capsys.readouterr().out)
            row = next(
                r for r in payload["queries"] if r.get("qid") == res["qid"]
            )
            assert row["usage"]["rows_in"] > 0
        finally:
            server.close()
