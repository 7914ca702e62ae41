"""Deterministic fault injection: failure detection, retry, failover.

Mirrors the reference's embedded-NATS failure-path tests
(``query_result_forwarder_test.go``, ``agent_topic_listener_test.go``)
with a seeded ``FaultInjector`` on the in-process bus: agent death
before-dispatch / mid-fragment / mid-merge / mid-stream, ack-loss
retry, quarantine, and partial-result correctness — all without
sleeping out any watchdog. ``run_tests.sh --faults`` re-runs this file
across a fixed seed matrix (PIXIE_TPU_FAULT_SEED).
"""

import os
import threading
import time

import numpy as np
import pytest

from conftest import wait_until
from pixie_tpu.config import override_flag
from pixie_tpu.exec.engine import QueryError
from pixie_tpu.services import (
    AgentLost,
    AgentTracker,
    BusTimeout,
    FaultInjector,
    KelvinAgent,
    MessageBus,
    PEMAgent,
    QueryBroker,
    QueryTimeout,
)

SEED = int(os.environ.get("PIXIE_TPU_FAULT_SEED", "0"))

FAST = dict(heartbeat_interval_s=0.05)

AGG_Q = (
    "import px\n"
    "df = px.DataFrame(table='http_events')\n"
    "df = df.groupby('service').agg(n=('latency_ns', px.count))\n"
    "px.display(df, 'out')\n"
)

#: Small retry budget so lost-dispatch tests resolve in well under a
#: second (3 waits of ~20/40/80ms).
FAST_DISPATCH = dict(dispatch_retries=2, dispatch_backoff_ms=20.0)


def _mk_cluster(n_pems=3, rows=400, expiry_s=60.0):
    bus = MessageBus()
    tracker = AgentTracker(
        bus, expiry_s=expiry_s, check_interval_s=60.0,
        flap_threshold=3, flap_window_s=60.0, quarantine_s=60.0,
    )
    pems = [PEMAgent(bus, f"pem-{i}", **FAST).start() for i in range(n_pems)]
    kelvin = KelvinAgent(bus, "kelvin-0", **FAST).start()
    rng = np.random.default_rng(SEED)
    for i, pem in enumerate(pems):
        n = rows + 100 * i
        pem.append_data(
            "http_events",
            {
                "time_": np.arange(n, dtype=np.int64),
                "latency_ns": rng.integers(1000, 1_000_000, n),
                "resp_status": rng.choice(np.array([200, 404, 500]), n),
                "service": [f"svc-{(i + j) % 3}" for j in range(n)],
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
    return bus, tracker, pems, kelvin, broker


@pytest.fixture
def cluster():
    bus, tracker, pems, kelvin, broker = _mk_cluster()
    yield bus, tracker, pems, kelvin, broker
    bus.fault_injector = None
    for a in pems + [kelvin]:
        a.stop()
    broker.close()
    tracker.close()
    bus.close()


def _count_truth(pems, alive):
    total = 0
    for i in alive:
        total += pems[i].engine.tables["http_events"].num_rows
    return total


def _total_n(res):
    return int(np.sum(res["tables"]["out"].to_pydict()["n"]))


class TestInjectorDeterminism:
    def test_same_seed_same_decisions(self):
        """The core --faults contract: a (seed, workload) pair replays
        identically — every probabilistic decision comes from the one
        seeded RNG."""
        def run(seed):
            inj = FaultInjector(seed=seed)
            inj.drop("t.*", prob=0.5)
            bus = MessageBus()
            bus.fault_injector = inj
            got = []
            bus.subscribe("t.x", got.append)
            for i in range(64):
                bus.publish("t.x", {"i": i})
            deadline = time.time() + 2
            while time.time() < deadline and len(got) < 64 - inj.fired():
                time.sleep(0.01)
            log = list(inj.log)
            bus.close()
            return log, sorted(m["i"] for m in got)

        log_a, got_a = run(SEED)
        log_b, got_b = run(SEED)
        assert log_a == log_b
        assert got_a == got_b
        assert 0 < len(log_a) < 64  # prob=0.5 dropped some, not all

    def test_rule_mechanics(self):
        """drop count/after, duplicate, delay, where-predicates."""
        inj = FaultInjector(seed=SEED)
        inj.drop("a.b", count=1, after=1)  # drop only the 2nd message
        inj.duplicate("dup.*", count=1)
        inj.delay("slow", 0.15, count=1)
        inj.drop("pred", where=lambda m: m.get("kill"))
        bus = MessageBus()
        bus.fault_injector = inj
        got = {"ab": [], "dup": [], "slow": [], "pred": []}
        bus.subscribe("a.b", got["ab"].append)
        bus.subscribe("dup.x", got["dup"].append)
        bus.subscribe("slow", got["slow"].append)
        bus.subscribe("pred", got["pred"].append)
        for i in range(3):
            bus.publish("a.b", {"i": i})
        bus.publish("dup.x", {"i": 0})
        t0 = time.monotonic()
        bus.publish("slow", {"i": 0})
        bus.publish("pred", {"kill": True})
        bus.publish("pred", {"kill": False})
        deadline = time.time() + 5
        while time.time() < deadline and not (
            len(got["ab"]) == 2 and len(got["dup"]) == 2
            and got["slow"] and len(got["pred"]) == 1
        ):
            time.sleep(0.01)
        assert sorted(m["i"] for m in got["ab"]) == [0, 2]
        assert len(got["dup"]) == 2
        assert got["slow"] and time.monotonic() - t0 >= 0.15
        assert [m["kill"] for m in got["pred"]] == [False]
        bus.close()


class TestBusTimeout:
    def test_msgbus_and_netbus_raise_shared_bus_timeout(self):
        """Satellite: both transports raise one BusTimeout (a
        TimeoutError subclass) so retry logic catches uniformly."""
        from pixie_tpu.services.netbus import BusServer, RemoteBus

        bus = MessageBus()
        with pytest.raises(BusTimeout):
            bus.request("nobody.home", {}, timeout_s=0.05)
        bus.subscribe("silent", lambda m: None)  # responder never replies
        with pytest.raises(BusTimeout):
            bus.request("silent", {}, timeout_s=0.05)
        server = BusServer(bus)
        rb = RemoteBus("127.0.0.1", server.port)
        try:
            with pytest.raises(BusTimeout) as ei:
                rb.request("nobody.home", {}, timeout_s=0.05)
            assert isinstance(ei.value, TimeoutError)
        finally:
            rb.close()
            server.close()
        bus.close()


class TestDispatchRetry:
    def test_ack_loss_retries_and_completes_exactly_once(self, cluster):
        """Drop one PEM's first execute-dispatch ack: the broker
        retries, the agent dedups the repeat (re-acking), and the query
        completes with FULL results — no double-counted fragment."""
        bus, tracker, pems, kelvin, broker = cluster
        from pixie_tpu.services.observability import default_registry

        inj = FaultInjector(seed=SEED)
        inj.drop(
            "query.*.ack", count=1,
            where=lambda m: m.get("agent") == "pem-1"
            and m.get("ack") == "execute",
        )
        bus.fault_injector = inj
        with override_flag("dispatch_retries", 3), \
                override_flag("dispatch_backoff_ms", 20.0):
            res = broker.execute_script(AGG_Q)
        assert res["partial"] is False
        assert res["missing_agents"] == []
        assert _total_n(res) == _count_truth(pems, [0, 1, 2])
        assert set(res["agent_stats"]) == {"pem-0", "pem-1", "pem-2"}
        assert inj.fired("drop") == 1
        retries = default_registry.render()
        assert "pixie_dispatch_retries_total" in retries

    def test_duplicate_dispatch_is_idempotent(self, cluster):
        """A fault-duplicated execute dispatch (and bridge payload) must
        not double the dead-reckoned counts."""
        bus, tracker, pems, kelvin, broker = cluster
        inj = FaultInjector(seed=SEED)
        inj.duplicate("agent.pem-0.execute")
        inj.duplicate("agent.kelvin-0.bridge", count=2)
        bus.fault_injector = inj
        res = broker.execute_script(AGG_Q)
        assert res["partial"] is False, res.get("missing_reasons")
        assert _total_n(res) == _count_truth(pems, [0, 1, 2])

    def test_death_before_dispatch_degrades_to_partial(self, cluster):
        """An agent that never receives its fragment (all dispatches +
        retries lost) is declared lost after the retry budget; the query
        completes from the survivors in well under the watchdog."""
        bus, tracker, pems, kelvin, broker = cluster
        inj = FaultInjector(seed=SEED)
        inj.drop("agent.pem-2.execute")  # every copy, incl. retries
        bus.fault_injector = inj
        t0 = time.monotonic()
        with override_flag("dispatch_retries", 2), \
                override_flag("dispatch_backoff_ms", 20.0):
            res = broker.execute_script(AGG_Q, timeout_s=30.0)
        elapsed = time.monotonic() - t0
        assert res["partial"] is True
        assert res["missing_agents"] == ["pem-2"]
        assert "un-acked" in res["missing_reasons"]["pem-2"]
        assert _total_n(res) == _count_truth(pems, [0, 1])
        assert elapsed < 10.0, f"took {elapsed:.1f}s — waited out a watchdog?"


class TestAgentDeath:
    def test_killed_mid_fragment_returns_partial_fast(self, cluster):
        """THE acceptance scenario: a data agent dies mid-fragment (its
        bridge payloads never arrive, its heartbeats stop). Failure
        detection (force-expire at the trigger point) reaches the
        waiting forwarder immediately: partial results from the
        survivors, the dead agent listed, well under the watchdog."""
        bus, tracker, pems, kelvin, broker = cluster
        inj = FaultInjector(seed=SEED)
        dead = lambda m: m.get("from_agent") == "pem-2"  # noqa: E731
        inj.drop("agent.kelvin-0.bridge", where=dead)
        inj.drop("query.*.agent_done",
                 where=lambda m: m.get("agent") == "pem-2")
        inj.kill_agent("agent.kelvin-0.bridge", pems[2], tracker,
                       where=dead)
        bus.fault_injector = inj
        t0 = time.monotonic()
        res = broker.execute_script(AGG_Q, timeout_s=30.0)
        elapsed = time.monotonic() - t0
        assert res["partial"] is True
        assert res["missing_agents"] == ["pem-2"]
        assert _total_n(res) == _count_truth(pems, [0, 1])
        assert "pem-2" not in res["agent_stats"]
        assert elapsed < 10.0, f"took {elapsed:.1f}s — waited out a watchdog?"

    def test_require_complete_fails_fast(self, cluster):
        """Same death, require_complete=True: fail-closed — and FAST
        (the old behavior failed only at the full watchdog timeout)."""
        bus, tracker, pems, kelvin, broker = cluster
        inj = FaultInjector(seed=SEED)
        dead = lambda m: m.get("from_agent") == "pem-2"  # noqa: E731
        inj.drop("agent.kelvin-0.bridge", where=dead)
        inj.drop("query.*.agent_done",
                 where=lambda m: m.get("agent") == "pem-2")
        inj.kill_agent("agent.kelvin-0.bridge", pems[2], tracker,
                       where=dead)
        bus.fault_injector = inj
        t0 = time.monotonic()
        with pytest.raises(AgentLost) as ei:
            broker.execute_script(AGG_Q, timeout_s=30.0,
                                  require_complete=True)
        elapsed = time.monotonic() - t0
        assert "pem-2" in str(ei.value)
        assert "require_complete" in str(ei.value)
        assert elapsed < 5.0, f"took {elapsed:.1f}s — waited out a watchdog?"

    def test_merge_agent_death_fails_query_fast(self, cluster):
        """The merge agent is un-substitutable mid-query: its death must
        fail the query immediately (no partial path)."""
        bus, tracker, pems, kelvin, broker = cluster
        inj = FaultInjector(seed=SEED)
        inj.kill_agent("agent.kelvin-0.bridge", kelvin, tracker)
        bus.fault_injector = inj
        t0 = time.monotonic()
        with pytest.raises(QueryError) as ei:
            broker.execute_script(AGG_Q, timeout_s=30.0)
        elapsed = time.monotonic() - t0
        assert "merge agent kelvin-0" in str(ei.value)
        assert elapsed < 5.0

    def test_all_data_agents_lost_errors(self, cluster):
        bus, tracker, pems, kelvin, broker = cluster
        inj = FaultInjector(seed=SEED)
        inj.drop("agent.pem-*.execute")
        bus.fault_injector = inj
        with override_flag("dispatch_retries", 1), \
                override_flag("dispatch_backoff_ms", 20.0), \
                pytest.raises(AgentLost) as ei:
            broker.execute_script(AGG_Q, timeout_s=30.0)
        assert "all data agents lost" in str(ei.value)

    def test_timeout_message_reports_missing_and_dispatch_state(
        self, cluster
    ):
        """Satellite: a genuine watchdog timeout names the agents that
        did NOT report (not just those that did) and the per-agent
        dispatch/ack state."""
        bus, tracker, pems, kelvin, broker = cluster
        inj = FaultInjector(seed=SEED)
        # pem-1 stays alive + acked, but its bridge and done messages
        # vanish: nobody is ever declared lost, the merge never
        # completes, and the watchdog is the only way out.
        inj.drop("agent.kelvin-0.bridge",
                 where=lambda m: m.get("from_agent") == "pem-1")
        inj.drop("query.*.agent_done",
                 where=lambda m: m.get("agent") == "pem-1")
        bus.fault_injector = inj
        with pytest.raises(QueryTimeout) as ei:
            broker.execute_script(AGG_Q, timeout_s=1.0)
        msg = str(ei.value)
        assert "missing: ['pem-1']" in msg
        assert "pem-1:execute" in msg and "acked" in msg


class TestStreamFaults:
    def _start_stream(self, broker, updates, **kw):
        handle = broker.execute_script_streaming(
            AGG_Q, on_update=updates.append, poll_interval_s=0.05, **kw
        )
        deadline = time.time() + 10
        while time.time() < deadline and not any(
            u.get("mode") == "replace" for u in updates
        ):
            time.sleep(0.02)
        assert any(u.get("mode") == "replace" for u in updates), \
            "stream never produced a merged view"
        return handle

    @staticmethod
    def _last_total(updates):
        replaces = [u for u in updates if u.get("mode") == "replace"]
        if not replaces:
            return -1
        return int(np.sum(replaces[-1]["batch"].to_pydict()["n"]))

    def test_data_agent_death_degrades_stream(self, cluster):
        """Mid-stream data-agent death: the client gets a
        stream_degraded notice naming the dead agent and the live view
        re-merges from the survivors (not frozen stale state)."""
        bus, tracker, pems, kelvin, broker = cluster
        updates: list = []
        handle = self._start_stream(broker, updates)
        try:
            pems[2].stop()
            tracker.force_expire("pem-2", reason="killed mid-stream")
            deadline = time.time() + 10
            while time.time() < deadline and not any(
                u.get("stream_degraded") for u in updates
            ):
                time.sleep(0.02)
            degraded = [u for u in updates if u.get("stream_degraded")]
            assert degraded, "no degradation notice reached the client"
            assert degraded[0]["missing_agents"] == ["pem-2"]
            assert handle.data_agents == ("pem-0", "pem-1")
            assert handle.missing_agents == ("pem-2",)
            # New data on a survivor still flows into the (reduced) view.
            n0 = pems[0].engine.tables["http_events"].num_rows
            pems[0].append_data(
                "http_events",
                {
                    "time_": np.arange(n0, n0 + 200, dtype=np.int64),
                    "latency_ns": np.full(200, 5000, dtype=np.int64),
                    "resp_status": np.full(200, 200, dtype=np.int64),
                    "service": ["svc-0"] * 200,
                },
            )
            want = _count_truth(pems, [0, 1])
            deadline = time.time() + 10
            while (
                self._last_total(updates) != want
                and time.time() < deadline
            ):
                time.sleep(0.02)
            assert self._last_total(updates) == want
            assert not any("error" in u for u in updates), updates
        finally:
            handle.cancel()

    def test_data_agent_death_aborts_require_complete_stream(
        self, cluster
    ):
        bus, tracker, pems, kelvin, broker = cluster
        updates: list = []
        handle = self._start_stream(
            broker, updates, require_complete=True
        )
        try:
            pems[2].stop()
            tracker.force_expire("pem-2", reason="killed mid-stream")
            deadline = time.time() + 10
            while time.time() < deadline and not any(
                "error" in u for u in updates
            ):
                time.sleep(0.02)
            errs = [u for u in updates if "error" in u]
            assert errs and "require_complete" in errs[0]["error"]
            assert "pem-2" in errs[0]["error"]
            assert handle.qid not in broker._live_streams
        finally:
            handle.cancel()

    def test_merge_agent_death_aborts_stream_and_cancel_is_idempotent(
        self, cluster
    ):
        """Satellite: the merge agent (not a data agent) dies mid-stream
        — _abort_streams_of errors the client, reaps the watchdog entry,
        and a late client-side StreamHandle.cancel is a no-op."""
        bus, tracker, pems, kelvin, broker = cluster
        updates: list = []
        handle = self._start_stream(broker, updates)
        kelvin.stop()
        tracker.force_expire("kelvin-0", reason="killed mid-stream")
        deadline = time.time() + 10
        while time.time() < deadline and not any(
            "error" in u for u in updates
        ):
            time.sleep(0.02)
        errs = [u for u in updates if "error" in u]
        assert errs, "merge-agent death never surfaced"
        assert "merge agent" in errs[0]["error"]
        assert "kelvin-0" in errs[0]["error"]
        deadline = time.time() + 5
        while broker._live_streams and time.time() < deadline:
            time.sleep(0.02)
        assert not broker._live_streams
        n_updates = len(updates)
        handle.cancel()  # idempotent after the abort already cancelled
        handle.cancel()
        time.sleep(0.1)
        assert len(updates) == n_updates


class TestStreamDispatchLoss:
    def test_lost_stream_execute_dispatch_degrades(self, cluster):
        """A stream_execute dispatch that never reaches its (alive)
        agent is retried, then the stream degrades to the survivors —
        never a silent forever-partial view."""
        bus, tracker, pems, kelvin, broker = cluster
        inj = FaultInjector(seed=SEED)
        inj.drop("agent.pem-2.stream_execute")
        bus.fault_injector = inj
        updates: list = []
        with override_flag("dispatch_retries", 1), \
                override_flag("dispatch_backoff_ms", 20.0):
            handle = broker.execute_script_streaming(
                AGG_Q, on_update=updates.append, poll_interval_s=0.05,
            )
        try:
            deadline = time.time() + 10
            while time.time() < deadline and not any(
                u.get("stream_degraded") for u in updates
            ):
                time.sleep(0.02)
            degraded = [u for u in updates if u.get("stream_degraded")]
            assert degraded, "lost dispatch never degraded the stream"
            assert degraded[0]["missing_agents"] == ["pem-2"]
            assert "un-acked" in degraded[0]["reason"]
            want = _count_truth(pems, [0, 1])

            def last_total():
                replaces = [
                    u for u in updates if u.get("mode") == "replace"
                ]
                if not replaces:
                    return -1
                return int(
                    np.sum(replaces[-1]["batch"].to_pydict()["n"])
                )

            deadline = time.time() + 10
            while last_total() != want and time.time() < deadline:
                time.sleep(0.02)
            assert last_total() == want
        finally:
            handle.cancel()

    def test_lost_stream_merge_dispatch_aborts(self, cluster):
        """A stream_merge dispatch that never reaches the merge agent
        aborts the stream with {error} (nothing can ever merge)."""
        bus, tracker, pems, kelvin, broker = cluster
        inj = FaultInjector(seed=SEED)
        inj.drop("agent.kelvin-0.stream_merge")
        bus.fault_injector = inj
        updates: list = []
        with override_flag("dispatch_retries", 1), \
                override_flag("dispatch_backoff_ms", 20.0):
            handle = broker.execute_script_streaming(
                AGG_Q, on_update=updates.append, poll_interval_s=0.05,
            )
        try:
            deadline = time.time() + 10
            while time.time() < deadline and not any(
                "error" in u for u in updates
            ):
                time.sleep(0.02)
            errs = [u for u in updates if "error" in u]
            assert errs, "lost merge dispatch never aborted the stream"
            assert "un-acked" in errs[0]["error"]
            assert handle.qid not in broker._live_streams
        finally:
            handle.cancel()


ROWS_Q = (
    "import px\n"
    "df = px.DataFrame(table='http_events')\n"
    "df = df[df.resp_status == 500]\n"
    "px.display(df, 'errs')\n"
)


class TestStreamChunkDedup:
    def test_duplicated_stream_rows_chunks_not_double_counted(
        self, cluster
    ):
        """Append-mode (RowsPayload) stream chunks are deduped by the
        producer's cursor seq: an at-least-once transport (or injected
        duplicate) must not double rows into the live view."""
        bus, tracker, pems, kelvin, broker = cluster
        inj = FaultInjector(seed=SEED)
        inj.duplicate("agent.kelvin-0.stream_bridge")
        bus.fault_injector = inj
        updates: list = []
        handle = broker.execute_script_streaming(
            ROWS_Q, on_update=updates.append, poll_interval_s=0.05,
        )
        try:
            truth = 0
            for pem in pems:
                d = pem.engine.tables["http_events"].read_all().to_pydict()
                truth += int((d["resp_status"] == 500).sum())

            def total():
                return sum(
                    u["batch"].length for u in updates if "batch" in u
                )

            deadline = time.time() + 10
            while total() < truth and time.time() < deadline:
                time.sleep(0.02)
            time.sleep(0.5)  # settle: any double-counted dup would land
            assert total() == truth, (total(), truth)
            assert inj.fired("duplicate") > 0
        finally:
            handle.cancel()


class TestDispatchLossBlastRadius:
    def test_lost_dispatch_only_affects_its_own_stream(self, cluster):
        """A per-query dispatch-loss verdict must not abort OTHER live
        streams sharing the same merge agent (they acked theirs)."""
        bus, tracker, pems, kelvin, broker = cluster
        healthy_updates: list = []
        healthy = broker.execute_script_streaming(
            AGG_Q, on_update=healthy_updates.append, poll_interval_s=0.05,
        )
        try:
            deadline = time.time() + 10
            while time.time() < deadline and not any(
                u.get("mode") == "replace" for u in healthy_updates
            ):
                time.sleep(0.02)
            assert any(
                u.get("mode") == "replace" for u in healthy_updates
            )
            # Now lose a SECOND stream's merge dispatch entirely.
            inj = FaultInjector(seed=SEED)
            inj.drop("agent.kelvin-0.stream_merge")
            bus.fault_injector = inj
            doomed_updates: list = []
            with override_flag("dispatch_retries", 1), \
                    override_flag("dispatch_backoff_ms", 20.0):
                doomed = broker.execute_script_streaming(
                    AGG_Q, on_update=doomed_updates.append,
                    poll_interval_s=0.05,
                )
            deadline = time.time() + 10
            while time.time() < deadline and not any(
                "error" in u for u in doomed_updates
            ):
                time.sleep(0.02)
            errs = [u for u in doomed_updates if "error" in u]
            assert errs and "un-acked" in errs[0]["error"]
            assert doomed.qid not in broker._live_streams
            # The healthy stream survives and still updates.
            assert healthy.qid in broker._live_streams
            assert not any("error" in u for u in healthy_updates)
            n0 = pems[0].engine.tables["http_events"].num_rows
            pems[0].append_data(
                "http_events",
                {
                    "time_": np.arange(n0, n0 + 100, dtype=np.int64),
                    "latency_ns": np.full(100, 5000, dtype=np.int64),
                    "resp_status": np.full(100, 200, dtype=np.int64),
                    "service": ["svc-0"] * 100,
                },
            )
            want = _count_truth(pems, [0, 1, 2])

            def last_total():
                replaces = [
                    u for u in healthy_updates
                    if u.get("mode") == "replace"
                ]
                if not replaces:
                    return -1
                return int(
                    np.sum(replaces[-1]["batch"].to_pydict()["n"])
                )

            deadline = time.time() + 10
            while last_total() != want and time.time() < deadline:
                time.sleep(0.02)
            assert last_total() == want
        finally:
            healthy.cancel()
            bus.fault_injector = None


class TestLastDataAgentStream:
    def test_stream_aborts_when_last_data_agent_dies(self):
        """Losing the ONLY data agent leaves nothing to degrade to: the
        stream must error out, not sit silent forever."""
        bus, tracker, pems, kelvin, broker = _mk_cluster(n_pems=1)
        updates: list = []
        try:
            handle = broker.execute_script_streaming(
                AGG_Q, on_update=updates.append, poll_interval_s=0.05,
            )
            deadline = time.time() + 10
            while time.time() < deadline and not any(
                u.get("mode") == "replace" for u in updates
            ):
                time.sleep(0.02)
            assert any(u.get("mode") == "replace" for u in updates)
            pems[0].stop()
            tracker.force_expire("pem-0", reason="killed")
            deadline = time.time() + 10
            while time.time() < deadline and not any(
                "error" in u for u in updates
            ):
                time.sleep(0.02)
            errs = [u for u in updates if "error" in u]
            assert errs, "sourceless stream never errored"
            assert "no data agents left" in errs[0]["error"]
            assert handle.qid not in broker._live_streams
        finally:
            for a in pems + [kelvin]:
                a.stop()
            broker.close()
            tracker.close()
            bus.close()


class TestQuarantine:
    def test_flapping_agent_is_quarantined_out_of_planning(self, cluster):
        """3 expirations inside the flap window quarantine the agent:
        re-registered and heartbeating, but excluded from
        distributed_state() until the cooldown passes."""
        bus, tracker, pems, kelvin, broker = cluster
        for _ in range(3):  # flap: die + immediately re-register
            tracker.force_expire("pem-2", reason="flap")
            bus.publish(
                "agent.register",
                {"agent_id": "pem-2", "processes_data": True,
                 "schemas": pems[2]._schemas()},
            )
            deadline = time.time() + 5
            while (
                time.time() < deadline
                and "pem-2" not in tracker.agent_ids()
            ):
                time.sleep(0.01)
        assert tracker.is_quarantined("pem-2")
        assert "pem-2" in tracker.quarantined()
        assert "pem-2" in tracker.agent_ids()  # still tracked
        state = tracker.distributed_state()
        assert "pem-2" not in [a.agent_id for a in state.agents]
        assert state.quarantined == ["pem-2"]
        res = broker.execute_script(AGG_Q)
        assert res["distributed_plan"].n_data_shards == 2
        assert _total_n(res) == _count_truth(pems, [0, 1])
        info = {a["agent_id"]: a for a in tracker.agents_info()}
        assert info["pem-2"]["quarantined"] is True
        from pixie_tpu.services.observability import default_registry

        assert "pixie_agent_quarantined_total" in default_registry.render()

    def test_quarantine_lapses_after_cooldown(self):
        bus = MessageBus()
        tracker = AgentTracker(
            bus, expiry_s=60.0, check_interval_s=60.0,
            flap_threshold=2, flap_window_s=60.0, quarantine_s=0.2,
        )
        try:
            bus.publish("agent.register", {"agent_id": "a1", "schemas": {}})
            deadline = time.time() + 5
            while time.time() < deadline and "a1" not in tracker.agent_ids():
                time.sleep(0.01)
            for _ in range(2):
                tracker.force_expire("a1")
                bus.publish(
                    "agent.register", {"agent_id": "a1", "schemas": {}}
                )
                deadline = time.time() + 5
                while (
                    time.time() < deadline
                    and "a1" not in tracker.agent_ids()
                ):
                    time.sleep(0.01)
            assert tracker.is_quarantined("a1")
            deadline = time.time() + 5
            while time.time() < deadline and tracker.is_quarantined("a1"):
                time.sleep(0.02)
            assert not tracker.is_quarantined("a1")
            assert tracker.quarantined() == {}
            state = tracker.distributed_state()
            assert "a1" in [a.agent_id for a in state.agents]
        finally:
            tracker.close()
            bus.close()


class TestForwarderWatchdog:
    def test_unrelated_expiry_does_not_reset_watchdog(self):
        """Cluster churn from OTHER queries' agents must not postpone a
        hung query's timeout: the inactivity deadline only moves on
        query-relevant activity."""
        from pixie_tpu.services import QueryResultForwarder
        from pixie_tpu.services.tracker import TOPIC_EXPIRED

        bus = MessageBus()
        fwd = QueryResultForwarder(bus)
        fwd.register_query("q1", ["a0"], merge_agent="m")
        stop = threading.Event()

        def churn():  # unrelated agent flaps every 0.3s
            i = 0
            while not stop.wait(0.3):
                bus.publish(TOPIC_EXPIRED,
                            {"agent_id": f"other-{i}", "reason": "flap"})
                i += 1

        t = threading.Thread(target=churn, daemon=True)
        t.start()
        t0 = time.monotonic()
        try:
            with pytest.raises(QueryTimeout):
                fwd.wait("q1", timeout_s=1.0)
            elapsed = time.monotonic() - t0
            assert elapsed < 3.0, (
                f"watchdog postponed to {elapsed:.1f}s by unrelated churn"
            )
        finally:
            stop.set()
            bus.close()

    def test_post_eos_agent_loss_does_not_discard_results(self):
        """A merge agent expiring DURING the post-eos stats drain must
        not fail a completed query (and a data agent expiring there
        must not mislabel complete results partial)."""
        from pixie_tpu.services import QueryResultForwarder
        from pixie_tpu.services.tracker import TOPIC_EXPIRED

        bus = MessageBus()
        fwd = QueryResultForwarder(bus)
        fwd.register_query("q2", ["a0", "a1"], merge_agent="m")
        bus.publish("query.q2.results", {"table": "t", "batch": "B"})
        bus.publish("query.q2.agent_done",
                    {"agent": "a0", "exec_time_s": 0.01})
        bus.publish("query.q2.results", {"eos": True})
        # Let the per-topic dispatcher threads enqueue the above before
        # the deaths: cross-topic delivery order is otherwise unordered,
        # and this test is specifically about POST-eos losses.
        time.sleep(0.3)
        # Post-eos deaths: the merge agent AND the stats straggler.
        bus.publish(TOPIC_EXPIRED, {"agent_id": "m", "reason": "died"})
        bus.publish(TOPIC_EXPIRED, {"agent_id": "a1", "reason": "died"})
        res = fwd.wait("q2", timeout_s=5.0)
        assert res["tables"]["t"] == "B"
        assert res["partial"] is False
        assert res["missing_agents"] == []
        bus.close()


class TestGraceDrain:
    def test_post_eos_stats_drain_is_bounded_total(self):
        """Satellite: stats stragglers trickling in (<1s apart) must not
        extend the post-eos drain beyond ONE total grace budget — the
        old per-message wait drained ~1s × expected agents."""
        from pixie_tpu.services import QueryResultForwarder

        bus = MessageBus()
        inj = FaultInjector(seed=SEED)
        agents = [f"a{i}" for i in range(4)]
        # Stagger every agent_done 0.5s apart: each arrives within the
        # old PER-MESSAGE 1s grace, so the old drain ran ~2s; the single
        # total budget returns at ~1s.
        for i, aid in enumerate(agents):
            inj.delay("query.q1.agent_done", 0.5 * (i + 1),
                      where=lambda m, a=aid: m.get("agent") == a)
        bus.fault_injector = inj
        fwd = QueryResultForwarder(bus)
        fwd.register_query("q1", agents, merge_agent="m")
        bus.publish("query.q1.results", {"table": "t", "batch": "B"})
        for aid in agents:
            bus.publish("query.q1.agent_done",
                        {"agent": aid, "exec_time_s": 0.01})
        bus.publish("query.q1.results", {"eos": True})
        t0 = time.monotonic()
        res = fwd.wait("q1", timeout_s=8.0)
        elapsed = time.monotonic() - t0
        assert elapsed < 1.8, (
            f"drain took {elapsed:.2f}s — per-message grace resurrected?"
        )
        assert res["tables"]["t"] == "B"
        # Only sub-budget stragglers made the stats map; the result is
        # still COMPLETE (tables were merged before eos).
        assert "a0" in res["agent_stats"]
        assert "a3" not in res["agent_stats"]
        assert res["partial"] is False
        bus.close()


class TestDeadlineFault:
    """ISSUE 13 satellite: the deadline entry in the fault matrix.
    Cooperative cancellation means a dispatched query past its deadline
    aborts at the next window boundary with a well-formed ``partial``
    result (``missing_reasons`` values ``"deadline"``) — and the abort
    must leak NOTHING: no live prefetch threads, no stuck
    ``_exec_guard``, engines immediately serviceable."""

    @staticmethod
    def _slow_windows(pems, delay_s=0.2, window_rows=64):
        """Make every data fragment mid-pipeline slow: small host
        windows (the fixture's ~500 rows / 64 ≈ 8 boundaries per
        fragment) each staged ``delay_s`` apart, so a sub-second
        deadline deterministically trips BETWEEN windows — with one
        big default window the whole query could finish before the
        deadline and nothing would abort."""
        originals = []
        for p in pems:
            eng = p.engine
            orig = eng._staged_windows
            originals.append((eng, orig, eng.window_rows))
            eng.window_rows = window_rows

            def slow(stream, stats=None, _orig=orig):
                for w in _orig(stream, stats):
                    time.sleep(delay_s)
                    yield w

            eng._staged_windows = slow
        return originals

    @staticmethod
    def _prefetch_threads():
        return [
            t for t in threading.enumerate()
            if t.is_alive() and t.name == "pixie-window-prefetch"
        ]

    def test_mid_pipeline_deadline_abort_no_leaks(self, cluster):
        bus, tracker, pems, kelvin, broker = cluster
        broker.execute_script(AGG_Q)  # warm compiles outside the clock
        before_threads = len(self._prefetch_threads())
        originals = self._slow_windows(pems, delay_s=0.15)
        t0 = time.monotonic()
        try:
            res = broker.execute_script(
                AGG_Q, timeout_s=30.0, deadline_ms=300.0
            )
        finally:
            for eng, orig, wr in originals:
                eng._staged_windows = orig
                eng.window_rows = wr
        elapsed = time.monotonic() - t0
        # Well-formed degraded result: partial, every unreported agent
        # attributed to the deadline — not an error, not a timeout.
        assert res["partial"] is True
        assert res["interrupted"] == "deadline"
        assert res["missing_reasons"], res
        assert set(res["missing_reasons"].values()) == {"deadline"}
        # Cooperative: the abort lands within ~one window boundary of
        # the deadline, far from the 30s watchdog.
        assert elapsed < 5.0, f"deadline abort took {elapsed:.1f}s"
        # No leaked prefetch threads once the aborts drain.
        deadline = time.time() + 5
        while (
            time.time() < deadline
            and len(self._prefetch_threads()) > before_threads
        ):
            time.sleep(0.05)
        assert len(self._prefetch_threads()) <= before_threads, (
            self._prefetch_threads()
        )
        # No stuck _exec_guard: every engine serves a fresh query
        # immediately (acquire would block forever on a leaked guard).
        for p in pems:
            ok = p.engine._exec_guard.acquire(timeout=5.0)
            assert ok, f"{p.agent_id} _exec_guard still held post-abort"
            p.engine._exec_guard.release()
        res = broker.execute_script(AGG_Q, timeout_s=30.0)
        assert res["partial"] is False
        assert _total_n(res) == _count_truth(pems, [0, 1, 2])

    def test_delayed_bridge_fault_rule_degrades_at_deadline(self, cluster):
        """Matrix rule: one agent's bridge payloads are fault-delayed
        past the query deadline — the result degrades to partial AT the
        deadline with that agent marked ``"deadline"``, instead of
        stalling toward the watchdog."""
        bus, tracker, pems, kelvin, broker = cluster
        broker.execute_script(AGG_Q)  # warm
        inj = FaultInjector(seed=SEED)
        inj.delay(
            "agent.kelvin-0.bridge", 3.0,
            where=lambda m: m.get("from_agent") == "pem-2",
        )
        inj.delay(
            "query.*.agent_done", 3.0,
            where=lambda m: m.get("agent") == "pem-2",
        )
        bus.fault_injector = inj
        t0 = time.monotonic()
        res = broker.execute_script(
            AGG_Q, timeout_s=30.0, deadline_ms=500.0
        )
        elapsed = time.monotonic() - t0
        assert res["partial"] is True
        assert res["interrupted"] == "deadline"
        assert res["missing_reasons"].get("pem-2") == "deadline"
        assert elapsed < 3.0, f"took {elapsed:.1f}s — waited for the delay?"
        # What DID arrive is served (the merge may not have finalized
        # before the deadline, in which case tables are empty — a
        # well-formed degraded result either way, never an exception).
        if "out" in res["tables"]:
            assert _total_n(res) <= _count_truth(pems, [0, 1, 2])


class TestLoadUnderFaults:
    def test_load_tester_reports_failure_rates(self, cluster):
        """Satellite: the load tester, driven into injected faults,
        reports failure rate + error breakdown (and partial counts)."""
        from pixie_tpu.services.load_tester import (
            broker_executor,
            run_load,
        )

        bus, tracker, pems, kelvin, broker = cluster
        broker.execute_script(AGG_Q)  # warm compiles outside the clock
        inj = FaultInjector(seed=SEED)
        # Every 3rd pem-2 execute dispatch (and its retries) vanishes:
        # some queries degrade to partial, none should error.
        inj.drop("agent.pem-2.execute", prob=0.4)
        bus.fault_injector = inj
        with override_flag("dispatch_retries", 0), \
                override_flag("dispatch_backoff_ms", 20.0):
            report = run_load(
                broker_executor(broker), AGG_Q,
                workers=2, per_worker=3, timeout_s=30.0,
            )
        d = report.to_dict()
        assert d["queries"] == 6
        # (``to_dict`` rounds the rate to four places.)
        assert d["failure_rate"] == pytest.approx(report.errors / 6, abs=1e-4)
        assert d["partials"] + d["errors"] >= 0  # breakdown present
        assert isinstance(d["errors_by_type"], dict)
        # With require_complete, dropped dispatches become ERRORS the
        # report must break down.
        inj2 = FaultInjector(seed=SEED)
        inj2.drop("agent.pem-2.execute")
        bus.fault_injector = inj2

        def strict_execute(query, timeout_s):
            return broker.execute_script(
                query, timeout_s=timeout_s, require_complete=True
            )

        with override_flag("dispatch_retries", 0), \
                override_flag("dispatch_backoff_ms", 20.0):
            strict = run_load(
                strict_execute, AGG_Q, workers=1, per_worker=2,
                timeout_s=30.0,
            )
        assert strict.errors == 2
        assert strict.failure_rate == 1.0
        assert strict.errors_by_type == {"AgentLost": 2}


class TestPartitionDeterminism:
    """Satellite: FaultInjector.partition/heal — bidirectional peer-set
    cuts with the same fixed-seed replay contract as every other rule."""

    def _run(self, seed, prob):
        inj = FaultInjector(seed=seed)
        inj.partition("pem-*", "broker", prob=prob)
        bus = MessageBus()
        bus.fault_injector = inj
        got = {"to_agent": [], "to_broker": [], "intra": []}
        bus.subscribe("agent.pem-1.execute", got["to_agent"].append)
        bus.subscribe("agent.register", got["to_broker"].append)
        bus.subscribe("agent.pem-2.bridge", got["intra"].append)
        for i in range(32):
            # broker -> pem-1: crosses the cut.
            bus.publish("agent.pem-1.execute", {"qid": f"q{i}", "i": i})
            # pem-1 -> broker: crosses the cut (other direction).
            bus.publish("agent.register", {"agent_id": "pem-1", "i": i})
            # pem-1 -> pem-2: BOTH on the agent side — must always flow.
            bus.publish(
                "agent.pem-2.bridge", {"from_agent": "pem-1", "i": i}
            )
        deadline = time.time() + 3
        while time.time() < deadline and len(got["intra"]) < 32:
            time.sleep(0.01)
        log = list(inj.log)
        fired = inj.fired("partition")
        bus.close()
        return (
            log, fired,
            sorted(m["i"] for m in got["to_agent"]),
            sorted(m["i"] for m in got["to_broker"]),
            sorted(m["i"] for m in got["intra"]),
        )

    def test_same_seed_replays_identically(self):
        a = self._run(SEED, prob=0.5)
        b = self._run(SEED, prob=0.5)
        assert a == b
        log, fired, to_agent, to_broker, intra = a
        # prob=0.5: some crossing messages dropped, some delivered.
        assert 0 < fired < 64
        assert len(to_agent) < 32 or len(to_broker) < 32
        # Intra-set traffic is never a casualty of the cut.
        assert intra == list(range(32))

    def test_full_cut_and_heal(self):
        inj = FaultInjector(seed=SEED)
        inj.partition("pem-*", "broker")
        bus = MessageBus()
        bus.fault_injector = inj
        got = []
        bus.subscribe("agent.pem-0.execute", got.append)
        bus.publish("agent.pem-0.execute", {"i": 0})
        time.sleep(0.2)
        assert got == []  # hard cut: nothing crosses
        assert inj.heal() == 1
        bus.publish("agent.pem-0.execute", {"i": 1})
        deadline = time.time() + 3
        while time.time() < deadline and not got:
            time.sleep(0.01)
        assert [m["i"] for m in got] == [1]
        # heal() is idempotent and leaves non-partition rules alone.
        inj.drop("agent.pem-0.execute", count=1)
        assert inj.heal() == 0
        bus.publish("agent.pem-0.execute", {"i": 2})
        time.sleep(0.2)
        assert [m["i"] for m in got] == [1]  # the drop rule survived
        bus.close()

    def test_heal_removes_both_directions_of_every_cut(self):
        inj = FaultInjector(seed=SEED)
        inj.partition("pem-a", "broker")
        inj.partition("pem-b", "broker")
        assert inj.heal() == 2
        assert inj.heal() == 0


class TestQuarantineCooldownRecovery:
    """Satellite: the full flap -> quarantine -> cooldown -> re-register
    lifecycle, end-to-end through query execution on BOTH transports —
    the agent must land back in the dispatch set and the result cache
    must not serve the quarantine-era (2-shard) answer."""

    def _lifecycle(self, execute, bus, tracker, pems, broker):
        # Healthy: all 3 data shards answer, and the repeat is a hit.
        res = execute()
        assert set(res["agent_stats"]) == {"pem-0", "pem-1", "pem-2"}
        want_all = _count_truth(pems, [0, 1, 2])
        assert _total_n(res) == want_all
        assert execute().get("cache") == "hit"
        # Flap pem-2 past the threshold: quarantined out of planning.
        for _ in range(2):
            tracker.force_expire("pem-2", reason="flap")
            bus.publish(
                "agent.register",
                {"agent_id": "pem-2", "processes_data": True,
                 "schemas": pems[2]._schemas()},
            )
            wait_until(lambda: "pem-2" in tracker.agent_ids(),
                             "the flapped agent never registered again")
        assert tracker.is_quarantined("pem-2")
        res = execute()
        assert tracker.is_quarantined("pem-2"), (
            "the cooldown lapsed before the quarantine-era query ran")
        assert set(res["agent_stats"]) == {"pem-0", "pem-1"}
        assert _total_n(res) == _count_truth(pems, [0, 1])
        # Cooldown passes; the agent re-registers and is dispatchable.
        wait_until(lambda: not tracker.is_quarantined("pem-2"),
                         "the quarantine never lapsed")
        bus.publish(
            "agent.register",
            {"agent_id": "pem-2", "processes_data": True,
             "schemas": pems[2]._schemas()},
        )
        # The registration reaches the tracker and the broker on the
        # bus's own threads: the agent is back once the tracker plans it
        # again AND the broker has dropped what it cached without it
        # (its ``agent.register`` handler clears the result cache).
        wait_until(
            lambda: "pem-2" in [
                a.agent_id for a in tracker.distributed_state().agents
            ],
            "the released agent never came back into the plan",
        )
        wait_until(
            lambda: not broker.result_cache.cachez()["entries"],
            "the broker kept its quarantine-era cache after the "
            "re-registration",
        )
        res = execute()
        assert set(res["agent_stats"]) == {"pem-0", "pem-1", "pem-2"}
        assert _total_n(res) == want_all, (
            "stale quarantine-era cached result served after recovery"
        )
        assert res.get("cache") != "hit"

    def _mk_flappy_cluster(self):
        bus = MessageBus()
        # quarantine_s: a quarantined agent stays registered and is
        # plannable again the instant the cooldown lapses, so the
        # window has to outlast the test's own steps between the flap
        # and the quarantine-era query's planning (a register round
        # trip, then a request over the netbus): 0.4 s lapsed first on
        # a loaded box and the query found all three shards. The
        # lifecycle then WAITS for the lapse, so the window costs the
        # test its length and no more.
        tracker = AgentTracker(
            bus, expiry_s=60.0, check_interval_s=60.0,
            flap_threshold=2, flap_window_s=60.0, quarantine_s=3.0,
        )
        pems = [
            PEMAgent(bus, f"pem-{i}", **FAST).start() for i in range(3)
        ]
        kelvin = KelvinAgent(bus, "kelvin-0", **FAST).start()
        rng = np.random.default_rng(SEED)
        for i, pem in enumerate(pems):
            n = 300 + 50 * i
            pem.append_data("http_events", {
                "time_": np.arange(n, dtype=np.int64),
                "latency_ns": rng.integers(1000, 1_000_000, n),
                "resp_status": rng.choice(np.array([200, 404, 500]), n),
                "service": [f"svc-{(i + j) % 3}" for j in range(n)],
            })
            pem._register()
        # Every PEM's table, not the first to register it (as the helper
        # at the top of the file waits): a plan made before the last one
        # is known dispatches to a part of the shards.
        wait_until(
            lambda: len(tracker.agent_ids()) == 4
            and len(tracker.distributed_state().pems_with_table(
                "http_events")) == len(pems),
            "the cluster never registered",
        )
        broker = QueryBroker(bus, tracker)
        return bus, tracker, pems, kelvin, broker

    def _teardown(self, bus, tracker, pems, kelvin, broker):
        for a in pems + [kelvin]:
            a.stop()
        broker.close()
        tracker.close()
        bus.close()

    def test_recovery_in_process(self):
        bus, tracker, pems, kelvin, broker = self._mk_flappy_cluster()
        try:
            def execute():
                return broker.execute_script(AGG_Q, timeout_s=20.0)

            with override_flag("result_cache_mb", 64):
                self._lifecycle(execute, bus, tracker, pems, broker)
        finally:
            self._teardown(bus, tracker, pems, kelvin, broker)

    def test_recovery_over_netbus(self):
        from pixie_tpu.services.netbus import BusServer, RemoteBus

        bus, tracker, pems, kelvin, broker = self._mk_flappy_cluster()
        broker.serve()
        server = BusServer(bus)
        rb = RemoteBus("127.0.0.1", server.port)
        try:
            def execute():
                res = rb.request(
                    "broker.execute",
                    {"query": AGG_Q, "timeout_s": 20.0},
                    timeout_s=25.0,
                )
                assert res["ok"], res
                return res

            with override_flag("result_cache_mb", 64):
                self._lifecycle(execute, bus, tracker, pems, broker)
        finally:
            rb.close()
            server.close()
            self._teardown(bus, tracker, pems, kelvin, broker)
