"""CLI client, broker bus API, plan debugger, docgen, load tester.

Reference parity targets: ``src/pixie_cli`` (px run/script/get),
``src/api/proto/vizierpb`` ExecuteScript service surface,
``src/vizier/utils/loadtester``, and the planner debug dump.
"""

from __future__ import annotations

import io
import time
from contextlib import redirect_stdout

import numpy as np
import pytest

from pixie_tpu.cli import main as cli_main
from pixie_tpu.services.agent import KelvinAgent, PEMAgent
from pixie_tpu.services.load_tester import broker_executor, run_load
from pixie_tpu.services.msgbus import MessageBus
from pixie_tpu.services.query_broker import QueryBroker
from pixie_tpu.services.tracker import AgentTracker

FAST = dict(heartbeat_interval_s=0.05)

QUERY = """
import px
df = px.DataFrame(table='http_events')
df = df.groupby('service').agg(n=('latency_ns', px.count))
px.display(df)
"""


@pytest.fixture()
def served_cluster():
    bus = MessageBus()
    tracker = AgentTracker(bus, expiry_s=60.0, check_interval_s=60.0)
    pems = [PEMAgent(bus, f"pem-{i}", **FAST).start() for i in range(2)]
    kelvin = KelvinAgent(bus, "kelvin-0", **FAST).start()
    rng = np.random.default_rng(0)
    for i, pem in enumerate(pems):
        n = 1500
        pem.append_data(
            "http_events",
            {
                "time_": np.arange(n, dtype=np.int64),
                "latency_ns": rng.integers(1000, 1_000_000, n),
                "resp_status": rng.choice(np.array([200, 404]), n),
                "service": [f"svc-{(i + j) % 3}" for j in range(n)],
                "req_path": [f"/api/v{j % 2}/x" for j in range(n)],
            },
        )
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
    broker.serve()
    yield bus, tracker, broker
    for a in pems + [kelvin]:
        a.stop()
    tracker.close()


class TestBrokerBusAPI:
    def test_execute_over_bus(self, served_cluster):
        bus, _tracker, _broker = served_cluster
        res = bus.request(
            "broker.execute", {"query": QUERY, "timeout_s": 20.0},
            timeout_s=25.0,
        )
        assert res["ok"], res
        hb = res["tables"]["output"]
        got = hb.to_pydict()
        assert sorted(got["service"]) == ["svc-0", "svc-1", "svc-2"]
        assert int(got["n"].sum()) == 3000
        assert res["agent_stats"]

    def test_execute_error_in_band(self, served_cluster):
        bus, _t, _b = served_cluster
        res = bus.request(
            "broker.execute",
            {"query": "import px\npx.display(px.DataFrame(table='nope'))"},
            timeout_s=10.0,
        )
        assert not res["ok"]
        assert "nope" in res["error"]

    def test_schemas_agents_scripts(self, served_cluster):
        bus, _t, _b = served_cluster
        schemas = bus.request("broker.schemas", {}, timeout_s=5.0)
        assert schemas["ok"] and "http_events" in schemas["schemas"]
        agents = bus.request("broker.agents", {}, timeout_s=5.0)
        kinds = {a["kind"] for a in agents["agents"]}
        assert kinds == {"pem", "kelvin"}
        scripts = bus.request("broker.scripts", {}, timeout_s=5.0)
        assert "px/http_stats" in scripts["scripts"]


class TestLoadTester:
    def test_percentiles_and_errors(self, served_cluster):
        _bus, _t, broker = served_cluster
        rep = run_load(
            broker_executor(broker), QUERY, workers=2, per_worker=3,
            timeout_s=20.0,
        )
        d = rep.to_dict()
        assert d["queries"] == 6 and d["errors"] == 0
        assert d["p50_ms"] > 0 and d["p99_ms"] >= d["p50_ms"]

        bad = run_load(
            broker_executor(broker),
            "import px\npx.display(px.DataFrame(table='nope'))",
            workers=1, per_worker=2, timeout_s=5.0,
        )
        assert bad.errors == 2


def _run_cli(*argv) -> str:
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = cli_main(list(argv))
    assert rc == 0, buf.getvalue()
    return buf.getvalue()


class TestCLI:
    def test_script_list_and_show(self):
        out = _run_cli("script", "list")
        assert "px/http_stats" in out
        out = _run_cli("script", "show", "px/http_stats")
        assert "groupby" in out

    def test_docs(self):
        out = _run_cli("docs")
        assert "## Scalar functions" in out
        assert "`mean`" in out and "`count`" in out

    def test_explain_offline(self):
        out = _run_cli("explain", "px/http_stats")
        assert "MemorySource" in out and "Agg" in out
        assert "ResultSink" in out

    def test_run_local_synthetic(self):
        out = _run_cli(
            "run", "px/http_stats", "--local", "--synthetic", "5000",
            "-o", "json",
        )
        assert '"table": "output"' in out

    def test_run_local_csv(self):
        import csv
        import io

        out = _run_cli(
            "run", "px/http_stats", "--local", "--synthetic", "5000",
            "-o", "csv",
        )
        lines = out.splitlines()
        assert lines[0] == "# table: output"
        assert all("\r" not in ln for ln in lines)  # unix line endings
        rows = list(csv.reader(io.StringIO("\n".join(lines[1:]))))
        assert rows[0] == ["service", "req_path", "n", "lat_mean", "lat_max"]
        assert len(rows) > 1 and all(len(r) == 5 for r in rows[1:])
        assert sum(int(r[2]) for r in rows[1:]) > 0  # counts parse

    def test_run_against_served_broker(self, served_cluster, tmp_path):
        # End to end over the real framed-TCP netbus.
        from pixie_tpu.services.netbus import BusServer

        bus, _t, _b = served_cluster
        server = BusServer(bus)
        try:
            addr = f"127.0.0.1:{server.port}"
            out = _run_cli("run", "px/http_stats", "--broker", addr)
            assert "output" in out
            out = _run_cli("tables", "--broker", addr)
            assert "http_events" in out
            out = _run_cli("agents", "--broker", addr)
            assert "pem" in out and "kelvin" in out
        finally:
            server.close()

    def test_secured_deploy_rejects_unauthenticated(self, served_cluster):
        """With bus_secret set, the e2e netbus path requires the token:
        no/wrong secret -> connection refused at auth; right secret ->
        the CLI works unchanged (reference authcontext parity)."""
        from pixie_tpu.config import set_flag
        from pixie_tpu.services.netbus import BusServer, RemoteBus

        bus, _t, broker = served_cluster
        old_secret = broker.secret
        server = BusServer(bus, secret="deploy-secret")
        broker.secret = "deploy-secret"
        try:
            addr = f"127.0.0.1:{server.port}"
            # Wrong secret: rejected at connect.
            from pixie_tpu.services.auth import sign_token

            with pytest.raises(ConnectionError, match="auth"):
                RemoteBus("127.0.0.1", server.port,
                          token=sign_token("wrong", "intruder"))
            # No token at all: the server drops the connection before any
            # op reaches the bus (request times out client-side).
            rb = RemoteBus("127.0.0.1", server.port)
            with pytest.raises((TimeoutError, ConnectionError)):
                rb.request("broker.schemas", {}, timeout_s=0.5)
            rb.close()
            # CLI with the shared secret (flag/env path): works e2e.
            set_flag("bus_secret", "deploy-secret")
            out = _run_cli("tables", "--broker", addr)
            assert "http_events" in out
            out = _run_cli("run", "px/http_stats", "--broker", addr)
            assert "output" in out
        finally:
            set_flag("bus_secret", "")
            broker.secret = old_secret
            server.close()


class TestPlanDebug:
    def test_stats_annotation(self):
        from pixie_tpu.exec.engine import Engine
        from pixie_tpu.planner.debug import explain_plan
        from pixie_tpu.planner import CompilerState, compile_pxl

        eng = Engine()
        eng.create_table("t")
        eng.append_data("t", {
            "time_": np.arange(100, dtype=np.int64),
            "v": np.arange(100, dtype=np.int64),
        })
        q = (
            "import px\ndf = px.DataFrame(table='t')\n"
            "df = df.groupby('v').agg(n=('v', px.count))\npx.display(df)"
        )
        eng.execute_query(q, analyze=True)
        state = CompilerState(
            schemas={n: t.relation for n, t in eng.tables.items()},
            registry=eng.registry,
        )
        plan = compile_pxl(q, state).plan
        txt = explain_plan(plan, stats=eng.last_stats)
        assert "Agg by=[v]" in txt
        assert "stats: windows=" in txt


class TestPythonAPI:
    def test_client_execute_and_handlers(self, served_cluster):
        from pixie_tpu.api import Client, ScriptExecutionError, TableRecordHandler
        from pixie_tpu.services.netbus import BusServer

        bus, _t, _b = served_cluster
        server = BusServer(bus)
        rows_seen = []

        class Recorder(TableRecordHandler):
            def handle_record(self, record):
                rows_seen.append(record)

        try:
            with Client("127.0.0.1", server.port) as client:
                assert "px/http_stats" in client.list_scripts()
                assert "http_events" in client.schemas()
                assert len(client.agents()) == 3
                out = client.execute_script(
                    QUERY, handler_factory=lambda t: Recorder()
                )
                assert sorted(out["output"]["service"]) == [
                    "svc-0", "svc-1", "svc-2"
                ]
                assert len(rows_seen) == 3
                assert {"service", "n"} <= set(rows_seen[0])
                import pytest as _pytest

                with _pytest.raises(ScriptExecutionError, match="nope"):
                    client.execute_script(
                        "import px\npx.display(px.DataFrame(table='nope'))"
                    )
        finally:
            server.close()


@pytest.mark.slow
class TestDeployEndToEnd:
    def test_three_process_cluster_via_cli(self, tmp_path):
        """broker + pem + kelvin as REAL OS processes (deploy.py mains),
        seq-gen ingest on the pem, query + introspection via the CLI
        over the netbus — the full product loop."""
        import os
        import signal
        import socket as _socket
        import subprocess
        import sys
        import time as _time

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        s = _socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        env = {
            **os.environ,
            "JAX_PLATFORMS": "cpu",
            "PIXIE_TPU_NETBUS_PORT": str(port),
            "PIXIE_TPU_BROKER": f"127.0.0.1:{port}",
            "PIXIE_TPU_SEQGEN": "1",
        }
        qfile = tmp_path / "q.pxl"
        qfile.write_text(
            "import px\n"
            "df = px.DataFrame(table='sequences')\n"
            "s = df.groupby('modulo10').agg(n=('x', px.count))\n"
            "px.display(s)\n"
        )
        procs = []
        try:
            for role, aid in (("broker", ""), ("pem", "pem-e2e"),
                              ("kelvin", "kelvin-e2e")):
                e = dict(env)
                if aid:
                    e["PIXIE_TPU_AGENT_ID"] = aid
                procs.append(subprocess.Popen(
                    [sys.executable, "-m", "pixie_tpu.deploy", role],
                    env=e, cwd=repo,
                    stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                ))
                _time.sleep(1.5 if role == "broker" else 0.3)
            deadline = _time.time() + 90
            out = ""
            ok = False
            while _time.time() < deadline and not ok:
                r = subprocess.run(
                    [sys.executable, "-m", "pixie_tpu.cli", "run",
                     "--broker", f"127.0.0.1:{port}", "--timeout", "30",
                     str(qfile)],
                    env=env, cwd=repo,
                    capture_output=True, text=True, timeout=90,
                )
                out = r.stdout + r.stderr
                ok = r.returncode == 0 and "output" in r.stdout
                if not ok:
                    _time.sleep(3)
            assert ok, out[-2000:]
            r = subprocess.run(
                [sys.executable, "-m", "pixie_tpu.cli", "agents",
                 "--broker", f"127.0.0.1:{port}"],
                env=env, cwd=repo, capture_output=True, text=True,
                timeout=60,
            )
            assert "pem-e2e" in r.stdout and "kelvin-e2e" in r.stdout, (
                r.stdout + r.stderr
            )
        finally:
            for p in procs:
                p.send_signal(signal.SIGTERM)
            for p in procs:
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()


class TestNativeClient:
    """native/pxclient.cc: the C++ netbus client (reference pxapi Go
    client analog) — framed-TCP wire codec, HMAC token signing, and
    HostBatch result printing, all without Python on the client side."""

    @pytest.fixture()
    def binary(self):
        from pixie_tpu.native import build_executable

        path = build_executable("pxclient")
        if path is None:
            pytest.skip("no C++ toolchain")
        return path

    def _serve(self, served_cluster, secret=""):
        from pixie_tpu.services.netbus import BusServer

        bus, _tracker, _broker = served_cluster
        return BusServer(bus, secret=secret)

    def test_execute_prints_table(self, served_cluster, binary):
        import subprocess

        server = self._serve(served_cluster)
        try:
            p = subprocess.run(
                [binary, "--port", str(server.port), "--pxl", QUERY],
                capture_output=True, text=True, timeout=60,
            )
            assert p.returncode == 0, p.stderr
            assert "[output] 3 rows" in p.stdout
            assert "svc-0" in p.stdout and "svc-2" in p.stdout
            # counts sum to the seeded 2x1500 rows
            counts = [int(line.split("\t")[1])
                      for line in p.stdout.splitlines()
                      if line.startswith("svc-")]
            assert sum(counts) == 3000
        finally:
            server.close()

    def test_list_scripts(self, served_cluster, binary):
        import subprocess

        server = self._serve(served_cluster)
        try:
            p = subprocess.run(
                [binary, "--port", str(server.port), "--list"],
                capture_output=True, text=True, timeout=60,
            )
            assert p.returncode == 0, p.stderr
            assert "px/http_stats" in p.stdout
        finally:
            server.close()

    def test_signed_token_accepted_and_required(self, served_cluster, binary):
        import subprocess

        server = self._serve(served_cluster, secret="hunter2")
        try:
            ok = subprocess.run(
                [binary, "--port", str(server.port), "--secret", "hunter2",
                 "--pxl", QUERY],
                capture_output=True, text=True, timeout=60,
            )
            assert ok.returncode == 0, ok.stderr
            assert "[output] 3 rows" in ok.stdout
            bad = subprocess.run(
                [binary, "--port", str(server.port), "--secret", "wrong",
                 "--pxl", QUERY],
                capture_output=True, text=True, timeout=60,
            )
            assert bad.returncode != 0
            assert "auth" in bad.stderr.lower()
        finally:
            server.close()
