"""Broker HA: leader leases, replicated state, in-flight failover.

The broker-kill acceptance gate: two replicas on one bus, queries in
flight, a hard kill of the leader — takeover within one lease window,
every in-flight query resolves (re-attached and completed normally, or
``partial`` with ``missing_reasons: "broker_failover"``), never a
hang; the deposed leader's queued dispatches are epoch-fenced; no
leaked forwarder subscriptions or threads. Plus the client-retry
satellite (`api.Client` retries idempotent requests through a failover
window, never ``execute_script``).
"""

import os
import threading
import time

import numpy as np
import pytest

from pixie_tpu.config import override_flag
from pixie_tpu.services import MessageBus
from pixie_tpu.services.agent import KelvinAgent, PEMAgent
from pixie_tpu.services.broker_ha import (
    TOPIC_LEASE,
    TOPIC_RECONCILE,
    BrokerReplica,
)
from pixie_tpu.services.faults import FaultInjector
from pixie_tpu.services.query_broker import (
    QueryAbandoned,
    QueryResultForwarder,
)

SEED = int(os.environ.get("PIXIE_TPU_FAULT_SEED", "0"))

FAST = dict(heartbeat_interval_s=5.0)
#: Fast lease clock: expiry well under a second so failover tests run
#: in test time, with enough slack over the interval that a busy box
#: doesn't false-expire a healthy leader.
LEASE = dict(lease_interval_s=0.05, lease_expiry_s=0.3)

AGG_Q = (
    "import px\n"
    "df = px.DataFrame(table='http_events')\n"
    "df = df.groupby('service').agg(n=('latency_ns', px.count))\n"
    "px.display(df, 'out')\n"
)

TRACKER_KW = dict(expiry_s=60.0, check_interval_s=60.0,
                  flap_threshold=3, flap_window_s=60.0,
                  quarantine_s=60.0)


def _mk_ha_cluster(n_pems=3, n_brokers=2, rows=300):
    bus = MessageBus()
    replicas = [
        BrokerReplica(bus, f"broker-{i}", tracker_kw=TRACKER_KW,
                      leader=(i == 0), **LEASE)
        for i in range(n_brokers)
    ]
    rng = np.random.default_rng(SEED)
    pems = []
    for i in range(n_pems):
        pem = PEMAgent(bus, f"pem-{i}", **FAST)
        n = rows + 50 * i
        pem.engine.append_data("http_events", {
            "time_": np.arange(n, dtype=np.int64),
            "latency_ns": rng.integers(1000, 1_000_000, n),
            "service": [f"svc-{(i + j) % 3}" for j in range(n)],
        })
        pems.append(pem.start())
    kelvin = KelvinAgent(bus, "kelvin-0", **FAST).start()
    lead = replicas[0]
    deadline = time.time() + 10
    while time.time() < deadline and (
        len(lead.tracker.agent_ids()) < n_pems + 1
        or "http_events" not in lead.tracker.schemas()
        # HA converged: every standby has processed a leader lease
        # (else a kill this early claims epoch 1, which cannot fence
        # the deposed epoch-1 leader — not the scenario under test).
        or any(r.epoch < lead.epoch for r in replicas[1:])
    ):
        time.sleep(0.02)
    return bus, replicas, pems, kelvin


@pytest.fixture
def ha_cluster():
    with override_flag("broker_reconcile_wait_s", 0.4), \
            override_flag("broker_reattach_timeout_s", 8.0):
        bus, replicas, pems, kelvin = _mk_ha_cluster()
        yield bus, replicas, pems, kelvin
        bus.fault_injector = None
        for a in pems + [kelvin]:
            a.stop()
        for r in replicas:
            if not r._dead:
                r.close()
        bus.close()


def _count_truth(pems):
    return sum(
        p.engine.tables["http_events"].num_rows for p in pems
    )


def _total_n(res):
    return int(np.sum(res["tables"]["out"].to_pydict()["n"]))


def _wait_for(pred, timeout_s=10.0, interval_s=0.02):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(interval_s)
    return pred()


class TestElection:
    def test_leader_serves_standby_mirrors(self, ha_cluster):
        bus, (r0, r1), pems, kelvin = ha_cluster
        assert r0.role == "leader" and r0.epoch == 1
        assert r1.role == "standby"
        res = bus.request(
            "broker.execute", {"query": AGG_Q, "timeout_s": 15.0},
            timeout_s=20.0,
        )
        assert res["ok"] and res["partial"] is False
        assert _total_n(res) == _count_truth(pems)
        # The leader streamed inflight/release (+ agent + cache) events;
        # the standby folded every one of them.
        s0, s1 = r0.statusz(), r1.statusz()
        assert s0["state_seq"] > 0
        assert _wait_for(
            lambda: r1.statusz()["applied_seq"] == r0.statusz()["state_seq"]
        )
        assert r1.statusz()["replay_lag"] == 0
        assert s0["role"] == "leader" and s1["role"] == "standby"
        assert s1["leader"] == "broker-0"
        # Released on completion — once the release event has folded
        # (the leader may publish it after the sequence read above).
        assert _wait_for(lambda: r1.statusz()["mirror_inflight"] == 0)
        assert s0["lease_age_s"] < 1.0

    def test_leader_resolution_topic(self, ha_cluster):
        bus, (r0, r1), pems, kelvin = ha_cluster
        # Every replica answers; whoever wins the inbox race names the
        # same leader.
        res = bus.request("broker.leader", {}, timeout_s=2.0)
        assert res["ok"] and res["broker"] == "broker-0"
        assert res["answered_by"] in ("broker-0", "broker-1")

    def test_statusz_reports_ha_fields(self, ha_cluster):
        bus, (r0, r1), pems, kelvin = ha_cluster
        s = r1.statusz()
        for key in ("broker", "role", "epoch", "leader", "lease_age_s",
                    "state_seq", "applied_seq", "replay_lag",
                    "mirror_inflight", "failovers"):
            assert key in s, key

    def test_equal_epoch_claim_tiebreaks_on_broker_id(self):
        """Two standbys racing to the same epoch: the higher id steps
        down on seeing the lower id's lease at its own epoch, so the
        cluster converges on ONE leader without a new epoch."""
        bus = MessageBus()
        try:
            r = BrokerReplica(bus, "broker-5", tracker_kw=TRACKER_KW,
                              leader=True, **LEASE)
            assert r.role == "leader"
            # A peer with a LOWER id leads at the same epoch.
            bus.publish(TOPIC_LEASE, {
                "broker": "broker-1", "role": "leader",
                "epoch": r.epoch, "state_seq": 0,
            })
            assert _wait_for(lambda: r.role == "standby", timeout_s=5.0)
            # ...but a higher-id peer's lease would NOT depose broker-1.
            r2 = BrokerReplica(bus, "broker-0", tracker_kw=TRACKER_KW,
                               leader=True, **LEASE)
            bus.publish(TOPIC_LEASE, {
                "broker": "broker-4", "role": "leader",
                "epoch": r2.epoch, "state_seq": 0,
            })
            time.sleep(0.3)
            assert r2.role == "leader"
            r.close()
            r2.close()
        finally:
            bus.close()


class TestFailover:
    def test_leader_kill_resolves_every_inflight_query(self, ha_cluster):
        """THE gate: kill the leader with queries in flight. Takeover
        within ~a lease window; every in-flight query resolves — either
        re-attached and completed with full results, or partial with
        every missing agent attributed to "broker_failover" — zero
        hangs, zero leaked forwarder registrations or threads."""
        bus, (r0, r1), pems, kelvin = ha_cluster
        threads_before = threading.active_count()
        # Stretch queries across the kill: bridge payloads delayed past
        # the whole failover window, so fragments/merges are still
        # pending when the new leader reconciles.
        inj = FaultInjector(seed=SEED)
        inj.delay("agent.kelvin-0.bridge", 1.5)
        bus.fault_injector = inj
        results: dict = {}

        def submit(i):
            try:
                results[i] = bus.request(
                    "broker.execute", {"query": AGG_Q, "timeout_s": 20.0},
                    timeout_s=25.0,
                )
            except Exception as e:
                results[i] = e

        workers = [
            threading.Thread(target=submit, args=(i,)) for i in range(3)
        ]
        for w in workers:
            w.start()
        # Let the queries dispatch (mirrored inflight on the standby),
        # then crash the leader.
        assert _wait_for(
            lambda: r1.statusz()["mirror_inflight"] >= 1, timeout_s=10.0
        ), "standby never mirrored the in-flight queries"
        t_kill = time.monotonic()
        r0.kill()
        assert _wait_for(lambda: r1.role == "leader", timeout_s=5.0), \
            "standby never took over"
        takeover_s = time.monotonic() - t_kill
        # One lease window: expiry + a couple of intervals of slack.
        assert takeover_s < 5 * LEASE["lease_expiry_s"], (
            f"takeover took {takeover_s:.2f}s"
        )
        assert r1.epoch > 1
        for w in workers:
            w.join(timeout=30.0)
        assert not any(w.is_alive() for w in workers), (
            "an in-flight query HUNG through failover"
        )
        for i, res in results.items():
            assert isinstance(res, dict), f"query {i} raised: {res!r}"
            assert res.get("ok"), f"query {i} failed: {res}"
            if res.get("partial"):
                reasons = set(res["missing_reasons"].values())
                assert reasons <= {"broker_failover"}, res
            else:
                assert _total_n(res) == _count_truth(pems)
        # At least one query actually rode the failover path.
        assert any(
            isinstance(r, dict) and r.get("failover") for r in results.values()
        ), "no query was adopted by the successor"
        # Zero leaks: the successor's forwarder drained, the killed
        # replica's threads exited, mirror emptied.
        assert _wait_for(lambda: not r1.broker.forwarder._active), \
            r1.broker.forwarder._active
        assert _wait_for(
            lambda: r1.statusz()["mirror_inflight"] == 0
        )
        assert _wait_for(
            lambda: threading.active_count() <= threads_before,
            timeout_s=12.0, interval_s=0.2,
        ), [t.name for t in threading.enumerate()]
        # The new leader serves: a fresh query completes fully.
        bus.fault_injector = None
        res = bus.request(
            "broker.execute", {"query": AGG_Q, "timeout_s": 15.0},
            timeout_s=20.0,
        )
        assert res["ok"] and res["partial"] is False
        assert _total_n(res) == _count_truth(pems)
        agents_res = bus.request("broker.agents", {}, timeout_s=5.0)
        assert agents_res["broker"] == "broker-1"

    def test_unrecoverable_inflight_resolves_partial_broker_failover(
        self, ha_cluster
    ):
        """An in-flight query whose merge agent died with the old
        leader is unrecoverable: the successor's reconcile finds no
        owner and resolves it as partial/broker_failover — it does NOT
        hang, and does NOT wait out the re-attach watchdog."""
        bus, (r0, r1), pems, kelvin = ha_cluster
        inj = FaultInjector(seed=SEED)
        inj.delay("agent.kelvin-0.bridge", 1.5)
        bus.fault_injector = inj
        result: dict = {}

        def submit():
            try:
                result["res"] = bus.request(
                    "broker.execute", {"query": AGG_Q, "timeout_s": 20.0},
                    timeout_s=25.0,
                )
            except Exception as e:
                result["res"] = e

        w = threading.Thread(target=submit)
        w.start()
        assert _wait_for(
            lambda: r1.statusz()["mirror_inflight"] >= 1, timeout_s=10.0
        )
        kelvin.stop()  # the merge dies silently...
        t0 = time.monotonic()
        r0.kill()      # ...and the leader right after
        w.join(timeout=30.0)
        elapsed = time.monotonic() - t0
        assert not w.is_alive(), "unrecoverable query hung"
        res = result["res"]
        assert isinstance(res, dict), repr(res)
        assert res.get("ok"), res
        assert res["partial"] is True
        assert set(res["missing_reasons"].values()) == {"broker_failover"}
        assert res.get("failover") is True
        # Resolved by the reconcile verdict (interrupt), not by the 8s
        # re-attach inactivity watchdog.
        assert elapsed < 6.0, f"took {elapsed:.1f}s — watchdog, not verdict"


class TestEpochFencing:
    def test_deposed_leader_dispatch_is_fenced(self, ha_cluster):
        """Regression: a deposed leader's queued dispatch (stamped with
        the old epoch) reaches an agent AFTER the agent saw the new
        epoch — the agent must reject it: no ack, no execution."""
        bus, (r0, r1), pems, kelvin = ha_cluster
        from pixie_tpu.services.observability import default_registry

        agent = pems[0]
        acks: list = []
        bus.subscribe("query.fence-test.ack", acks.append)
        # The new leader's reconcile probe carries epoch 2: fence up.
        bus.publish(TOPIC_RECONCILE, {
            "_reply_to": "fence.probe.reply", "epoch": 2,
        })
        assert _wait_for(lambda: agent._max_epoch == 2, timeout_s=5.0)
        # A deposed leader's dispatch at epoch 1: dropped at the fence.
        bus.publish(f"agent.{agent.agent_id}.execute", {
            "qid": "fence-test", "epoch": 1, "plan": {},
        })
        time.sleep(0.3)
        assert acks == [], "epoch-1 dispatch was acked past the fence"
        assert "fence-test" not in agent._running
        rendered = default_registry.render()
        assert "pixie_epoch_fenced_total" in rendered
        # Current-epoch traffic still flows (the ack comes back even
        # though the plan is junk — fencing happens before decode).
        bus.publish(f"agent.{agent.agent_id}.execute", {
            "qid": "fence-test", "epoch": 2, "plan": {},
        })
        assert _wait_for(lambda: len(acks) == 1, timeout_s=5.0), acks

    def test_epochless_dispatch_passes(self, ha_cluster):
        """Plain single-broker deploys stamp no epoch: epoch 0 must
        never be fenced, whatever the agent has seen."""
        bus, (r0, r1), pems, kelvin = ha_cluster
        res = r0.broker.execute_script(AGG_Q)
        assert res["partial"] is False  # epoch_fn stamps, agents accept
        # And a no-epoch message (legacy/single-broker) also passes.
        agent = pems[0]
        assert agent._epoch_ok({"qid": "x"}) is True


class TestAbandon:
    def test_abandon_releases_wait_without_cancelling(self):
        """kill() must NOT publish query.cancel: the agents' work keeps
        running so the successor can adopt it. The released waiter
        raises QueryAbandoned (its served reply is suppressed)."""
        bus = MessageBus()
        cancels: list = []
        bus.subscribe("query.cancel", cancels.append)
        fwd = QueryResultForwarder(bus)
        fwd.register_query("q-ab", ["a0"], merge_agent="m")
        out: dict = {}

        def wait():
            try:
                fwd.wait("q-ab", timeout_s=10.0)
            except QueryAbandoned as e:
                out["err"] = e

        t = threading.Thread(target=wait)
        t.start()
        time.sleep(0.1)
        assert fwd.active_qids() == ["q-ab"]
        assert fwd.abandon("q-ab", "broker_failover") is True
        t.join(timeout=5.0)
        assert not t.is_alive()
        assert "broker_failover" in str(out["err"])
        time.sleep(0.2)
        assert cancels == [], "abandon published query.cancel"
        assert fwd.active_qids() == []
        assert fwd.abandon("gone", "x") is False
        bus.close()


class TestReattachDeadline:
    def test_reattach_lapse_resolves_partial_broker_failover(self):
        """An adopted query whose fragment reports were published into
        the takeover gap (no forwarder subscribed — the bus drops them)
        can have a claimed owner yet never report again. The successor's
        re-attach wait must resolve it at the DEADLINE as a structured
        partial/broker_failover reply, never raise QueryTimeout (which
        the caller's ledger would count as a lost query)."""
        bus = MessageBus()
        fwd = QueryResultForwarder(bus)
        fwd.register_query("q-gap", ["pem-0", "pem-1"], merge_agent="m")
        t0 = time.monotonic()
        res = fwd.wait(
            "q-gap", 5.0,
            deadline=time.monotonic() + 0.4,
            deadline_reason="broker_failover",
        )
        assert time.monotonic() - t0 < 2.0, "rode the watchdog"
        assert res["partial"] is True
        assert res["interrupted"] == "broker_failover"
        assert set(res["missing_reasons"].values()) == {"broker_failover"}
        assert sorted(res["missing_agents"]) == ["pem-0", "pem-1"]
        assert fwd.active_qids() == []
        bus.close()


class TestClientRetry:
    """Satellite: api.Client retries idempotent control-plane reads
    through a failover window; execute_script is NEVER blind-retried —
    it surfaces a structured error naming the current leader."""

    class _FlakyBus:
        def __init__(self, fail_n, reply):
            from pixie_tpu.services.msgbus import BusTimeout

            self._exc = BusTimeout
            self.fail_n = fail_n
            self.reply = reply
            self.calls: list = []

        def request(self, topic, msg, timeout_s=10.0):
            self.calls.append(topic)
            if len([c for c in self.calls if c == topic]) <= self.fail_n:
                raise self._exc(f"no reply from {topic!r}")
            return dict(self.reply)

        def close(self):
            pass

    def _client(self, bus):
        from pixie_tpu.api import Client

        c = Client.__new__(Client)
        c._bus = bus
        return c

    def test_idempotent_request_retries_with_backoff(self):
        from pixie_tpu.services.observability import default_counter

        counter = default_counter(
            "pixie_client_retries_total",
            "Idempotent client requests retried after a bus timeout",
        )
        before = counter.value()
        bus = self._FlakyBus(fail_n=2, reply={"ok": True, "scripts": []})
        client = self._client(bus)
        with override_flag("client_request_retries", 3), \
                override_flag("client_retry_backoff_ms", 5.0):
            t0 = time.monotonic()
            out = client.list_scripts()
            elapsed = time.monotonic() - t0
        assert out == []
        assert bus.calls.count("broker.scripts") == 3  # 2 fails + 1 ok
        assert counter.value() == before + 2
        assert elapsed >= 0.005  # backoff actually slept

    def test_retries_exhausted_reraises(self):
        from pixie_tpu.services.msgbus import BusTimeout

        bus = self._FlakyBus(fail_n=99, reply={"ok": True})
        client = self._client(bus)
        with override_flag("client_request_retries", 2), \
                override_flag("client_retry_backoff_ms", 1.0), \
                pytest.raises(BusTimeout):
            client.schemas()
        assert bus.calls.count("broker.schemas") == 3

    def test_execute_script_never_blind_retried(self):
        from pixie_tpu.api import ScriptExecutionError

        class _Bus(self._FlakyBus):
            def request(self, topic, msg, timeout_s=10.0):
                self.calls.append(topic)
                if topic == "broker.leader":
                    return {"ok": True, "broker": "broker-1",
                            "epoch": 2, "role": "leader"}
                raise self._exc(f"no reply from {topic!r}")

        bus = _Bus(fail_n=0, reply={})
        client = self._client(bus)
        with override_flag("client_request_retries", 3), \
                pytest.raises(ScriptExecutionError) as ei:
            client.execute_script("import px", timeout_s=0.1)
        # Exactly ONE execute attempt — the retry budget does not apply.
        assert bus.calls.count("broker.execute") == 1
        msg = str(ei.value)
        assert "not retried" in msg and "non-idempotent" in msg
        assert "broker-1" in msg  # the structured error names the leader

    def test_execute_script_error_without_leader(self):
        from pixie_tpu.api import ScriptExecutionError

        bus = self._FlakyBus(fail_n=99, reply={})
        client = self._client(bus)
        with pytest.raises(ScriptExecutionError) as ei:
            client.execute_script("import px", timeout_s=0.1)
        assert "mid-failover" in str(ei.value)


class TestGracefulHandoff:
    def test_close_hands_over_without_inflight_loss(self, ha_cluster):
        """Graceful close (deploy rollover): the lease lapses, the
        standby claims, and queries submitted AFTER the handoff land on
        the new leader — no abandoned work because none was in flight."""
        bus, (r0, r1), pems, kelvin = ha_cluster
        r0.close()
        assert _wait_for(lambda: r1.role == "leader", timeout_s=5.0)
        # role flips before _takeover() re-serves broker.execute: retry
        # the fast-fail no-responder window like a real client would.
        from pixie_tpu.services.msgbus import BusTimeout

        res = None
        for _ in range(50):
            try:
                res = bus.request(
                    "broker.execute", {"query": AGG_Q, "timeout_s": 15.0},
                    timeout_s=20.0,
                )
                break
            except BusTimeout:
                time.sleep(0.05)
        assert res is not None, "new leader never served broker.execute"
        assert res["ok"] and res["partial"] is False
        assert _total_n(res) == _count_truth(pems)
        assert r1.statusz()["epoch"] > 1
