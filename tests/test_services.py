"""Service shell tests: agents over the bus, broker, forwarder, expiry.

Mirrors the reference's embedded-NATS query-broker tests
(``launch_query_test.go:92``, ``query_result_forwarder_test.go``) — a
whole PEM×N + Kelvin topology inside one process, no cluster.
"""

import time

import numpy as np
import pytest

from pixie_tpu.exec.engine import QueryError
from pixie_tpu.services import (
    AgentTracker,
    KelvinAgent,
    MessageBus,
    PEMAgent,
    QueryBroker,
    QueryTimeout,
)

FAST = dict(heartbeat_interval_s=0.05)


@pytest.fixture
def cluster():
    """3 PEMs with disjoint data + 1 Kelvin + broker."""
    bus = MessageBus()
    tracker = AgentTracker(bus, expiry_s=60.0, check_interval_s=60.0)
    pems = [PEMAgent(bus, f"pem-{i}", **FAST).start() for i in range(3)]
    kelvin = KelvinAgent(bus, "kelvin-0", **FAST).start()
    rng = np.random.default_rng(0)
    for i, pem in enumerate(pems):
        n = 2000 + 500 * i
        pem.append_data(
            "http_events",
            {
                "time_": np.arange(n, dtype=np.int64),
                "latency_ns": rng.integers(1000, 1_000_000, n),
                "resp_status": rng.choice(np.array([200, 200, 404, 500]), n),
                # Disjoint + overlapping services with per-PEM dictionaries
                # in different insertion orders.
                "service": [f"svc-{(i + j) % 4}" for j in range(n)],
            },
        )
    # Re-register so the tracker sees the post-ingest schemas.
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
    tracker.close()
    bus.close()


def _truth(pems):
    rows = []
    for pem in pems:
        hb = pem.engine.tables["http_events"].read_all()
        d = hb.to_pydict()
        rows.append(d)
    svc = np.concatenate([r["service"] for r in rows])
    lat = np.concatenate([r["latency_ns"] for r in rows])
    return svc, lat


class TestClusterQuery:
    def test_groupby_mean_across_agents(self, cluster):
        bus, tracker, pems, kelvin, broker = cluster
        res = broker.execute_script(
            "import px\n"
            "df = px.DataFrame(table='http_events')\n"
            "df = df.groupby('service').agg(\n"
            "    n=('latency_ns', px.count), avg=('latency_ns', px.mean))\n"
            "px.display(df, 'out')\n"
        )
        out = res["tables"]["out"].to_pydict()
        svc, lat = _truth(pems)
        got = {s: (int(n), float(a)) for s, n, a in zip(out["service"], out["n"], out["avg"])}
        for s in np.unique(svc):
            mask = svc == s
            n, avg = got[s]
            assert n == int(mask.sum())
            # Mean-of-means would be wrong here (unequal PEM sizes, %-level
            # error); carry merging must produce the true global mean up to
            # the f32 device finalize precision.
            np.testing.assert_allclose(avg, lat[mask].mean(), rtol=1e-6)

    def test_quantile_digest_merge_across_agents(self, cluster):
        bus, tracker, pems, kelvin, broker = cluster
        res = broker.execute_script(
            "import px\n"
            "df = px.DataFrame(table='http_events')\n"
            "df = df.agg(p=('latency_ns', px.quantiles))\n"
            "px.display(df, 'out')\n",
            timeout_s=300.0,  # cold t-digest JIT compile alone is ~1min
        )
        import json

        out = res["tables"]["out"].to_pydict()
        _, lat = _truth(pems)
        q = json.loads(out["p"][0])
        assert abs(q["p50"] - np.quantile(lat, 0.5)) / np.quantile(lat, 0.5) < 0.05

    def test_filter_rows_gather(self, cluster):
        bus, tracker, pems, kelvin, broker = cluster
        res = broker.execute_script(
            "import px\n"
            "df = px.DataFrame(table='http_events')\n"
            "df = df[df.resp_status == 500]\n"
            "px.display(df, 'errs')\n",
            max_output_rows=100_000,
        )
        out = res["tables"]["errs"].to_pydict()
        truth = 0
        for pem in pems:
            d = pem.engine.tables["http_events"].read_all().to_pydict()
            truth += int((d["resp_status"] == 500).sum())
        assert len(out["resp_status"]) == truth
        assert res["distributed_plan"].n_data_shards == 3

    def test_agent_stats_reported(self, cluster):
        bus, tracker, pems, kelvin, broker = cluster
        res = broker.execute_script(
            "import px\n"
            "df = px.DataFrame(table='http_events')\n"
            "df = df.groupby('service').agg(n=('resp_status', px.count))\n"
            "px.display(df, 'o')\n"
        )
        assert set(res["agent_stats"]) == {"pem-0", "pem-1", "pem-2"}


class TestElasticity:
    def test_dead_agent_expires_and_query_replans(self, cluster):
        bus, tracker, pems, kelvin, broker = cluster
        pems[2].stop()  # dies silently
        tracker.expiry_s = 0.1
        time.sleep(0.3)
        expired = tracker.expire_silent()
        assert "pem-2" in expired
        assert "pem-0" not in expired  # still heartbeating
        res = broker.execute_script(
            "import px\n"
            "df = px.DataFrame(table='http_events')\n"
            "df = df.groupby('service').agg(n=('resp_status', px.count))\n"
            "px.display(df, 'o')\n"
        )
        assert res["distributed_plan"].n_data_shards == 2
        n_total = sum(res["tables"]["o"].to_pydict()["n"])
        truth = sum(
            pems[i].engine.tables["http_events"].num_rows for i in range(2)
        )
        assert n_total == truth

    def test_reregister_after_expiry(self, cluster):
        bus, tracker, pems, kelvin, broker = cluster
        with tracker._lock:
            del tracker._agents["pem-0"]  # simulate expiry
        # Next heartbeat gets a reregister nudge; agent re-registers.
        deadline = time.time() + 5
        while time.time() < deadline and "pem-0" not in tracker.agent_ids():
            time.sleep(0.02)
        assert "pem-0" in tracker.agent_ids()

    def test_no_table_anywhere_fails(self, cluster):
        from pixie_tpu.planner.objects import PxLError

        bus, tracker, pems, kelvin, broker = cluster
        # Unknown table fails at compile (schema tracker knows nothing of
        # it) — same behavior as the reference compiler.
        with pytest.raises(PxLError):
            broker.execute_script(
                "import px\n"
                "df = px.DataFrame(table='nonexistent')\n"
                "px.display(df, 'o')\n"
            )
        # Known table that no LIVE agent can serve fails at planning.
        from pixie_tpu.types.dtypes import DataType
        from pixie_tpu.types.relation import Relation

        with tracker._lock:
            for rec in tracker._agents.values():
                rec.schemas.setdefault(
                    "ghost_table", Relation([("time_", DataType.TIME64NS)])
                )
        # Schemas known, but agent table sets (AgentInfo.tables) unchanged.
        with pytest.raises(QueryError):
            broker.execute_script(
                "import px\n"
                "df = px.DataFrame(table='ghost_table')\n"
                "px.display(df, 'o')\n"
            )


class TestForwarder:
    def test_error_propagates_in_band(self, cluster):
        bus, tracker, pems, kelvin, broker = cluster
        # Sabotage one PEM so its fragment fails at execution time.
        pems[1].engine.registry = None
        with pytest.raises(QueryError) as ei:
            broker.execute_script(
                "import px\n"
                "df = px.DataFrame(table='http_events')\n"
                "df = df.groupby('service').agg(n=('resp_status', px.count))\n"
                "px.display(df, 'o')\n"
            )
        assert "pem-1" in str(ei.value)

    def test_watchdog_timeout_cancels(self, cluster):
        bus, tracker, pems, kelvin, broker = cluster
        kelvin.stop()  # merge tier dead -> no results ever
        with pytest.raises(QueryTimeout):
            broker.execute_script(
                "import px\n"
                "df = px.DataFrame(table='http_events')\n"
                "df = df.groupby('service').agg(n=('resp_status', px.count))\n"
                "px.display(df, 'o')\n",
                timeout_s=0.5,
            )


class TestRemoteBusIdle:
    def test_idle_connection_survives_past_connect_timeout(self):
        """create_connection's timeout must not leak into the read loop:
        an idle client (no traffic for longer than connect_timeout_s)
        has to stay connected and deliver later messages (a stalled
        stream producer is not a dead connection)."""
        import time

        from pixie_tpu.services.msgbus import MessageBus
        from pixie_tpu.services.netbus import BusServer, RemoteBus

        bus = MessageBus()
        server = BusServer(bus)
        rb = RemoteBus("127.0.0.1", server.port, connect_timeout_s=0.5)
        try:
            got = []
            rb.subscribe("t", got.append)
            time.sleep(1.2)  # idle well past the connect timeout
            assert not rb._closed.is_set(), "idle client self-closed"
            bus.publish("t", {"late": 1})
            deadline = time.time() + 5
            while not got and time.time() < deadline:
                time.sleep(0.01)
            assert got == [{"late": 1}]
        finally:
            rb.close()
            server.close()
