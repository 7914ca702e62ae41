"""Tenant-aware overload protection: quotas, scheduling, p99 isolation.

The contract under test (ROADMAP "broker-grade multi-tenancy"): a noisy
tenant must not move another tenant's p99. Pieces:

- ``services/tenancy.py``: registered tenant set, weights, shares,
  bounded-cardinality resolve.
- ``_Admission`` (services/query_broker.py): per-tenant budget shares,
  (priority, earliest-deadline-first) wait ordering, event-driven
  release wakeups, deadline shedding of queued queries.
- End-to-end: tenant identity threaded broker -> dispatch -> agent
  traces -> ``__queries__``; a queued query past deadline is shed with
  ZERO agent executions; the mixed-tenant load gate
  (``run_tests.sh --tenancy``) proving the victim tenant's p99 and
  shed count hold at solo baseline while a saturating noisy tenant's
  p99 rises.
"""

import threading
import time

import numpy as np
import pytest

from conftest import wait_until
from pixie_tpu.config import override_flag
from pixie_tpu.services import (
    AgentTracker,
    KelvinAgent,
    MessageBus,
    PEMAgent,
    QueryBroker,
)
from pixie_tpu.services.query_broker import AdmissionError, _Admission
from pixie_tpu.services.tenancy import (
    DEFAULT_TENANT,
    resolve_tenant,
    tenant_shares,
    tenant_weights,
)

FAST = dict(heartbeat_interval_s=30.0)

VICTIM_Q = (
    "import px\n"
    "df = px.DataFrame(table='http_events')\n"
    "df = df.groupby('service').agg(\n"
    "    n=('latency_ns', px.count), mean=('latency_ns', px.mean))\n"
    "px.display(df, 'out')\n"
)

# The saturation gate's noisy script: merge-free (filter + limit stays
# on the data agents) so the victim-vs-noisy comparison isolates the
# SCHEDULER's contribution — on this 1-core CI box any merge-tier
# noisy compute steals the core from the victim's merge no matter how
# the broker schedules, which would measure the machine, not the
# admission layer.
NOISY_CHEAP_Q = (
    "import px\n"
    "df = px.DataFrame(table='noise_events')\n"
    "df = df[df.latency_ns < 0]\n"
    "df = df.head(5)\n"
    "px.display(df, 'out')\n"
)


def _pred(n):
    return {"bytes_staged_hi": int(n), "origin": "sketch", "safety": 2.0}


class TestTenancyModel:
    def test_weights_parse_and_default_tenant(self):
        with override_flag("admission_tenant_weights", "dash:4, batch:1"):
            w = tenant_weights()
        assert w == {"dash": 4.0, "batch": 1.0, DEFAULT_TENANT: 1.0}
        # Empty flag: single shared tenant owning everything.
        with override_flag("admission_tenant_weights", ""):
            assert tenant_weights() == {DEFAULT_TENANT: 1.0}
            assert tenant_shares(600.0) == {DEFAULT_TENANT: 600.0}

    def test_malformed_entries_are_tolerated(self):
        with override_flag(
            "admission_tenant_weights", "a:x, :3, b, c:-2, ,d:2"
        ):
            w = tenant_weights()
        assert w["a"] == 1.0  # bad weight -> 1
        assert w["b"] == 1.0  # missing weight -> 1
        assert w["c"] == 0.0  # negative clamps to 0 (registered, off)
        assert w["d"] == 2.0
        assert DEFAULT_TENANT in w

    def test_shares_partition_budget(self):
        with override_flag("admission_tenant_weights", "a:3,b:1"):
            shares = tenant_shares(1000.0)
        assert shares == {"a": 600.0, "b": 200.0, DEFAULT_TENANT: 200.0}
        assert sum(shares.values()) == pytest.approx(1000.0)

    def test_weights_memoized_per_spec(self):
        with override_flag("admission_tenant_weights", "a:2,b:1"):
            w1 = tenant_weights()
            assert tenant_weights() is w1  # hot paths reuse the parse
        with override_flag("admission_tenant_weights", "a:3"):
            w2 = tenant_weights()
            assert w2 is not w1 and w2["a"] == 3.0

    def test_resolve_folds_unknown_into_shared_and_counts(self):
        from pixie_tpu.services.observability import default_counter

        c = default_counter("pixie_admission_unknown_tenant_total")
        with override_flag("admission_tenant_weights", "dash:2"):
            before = c.value()
            assert resolve_tenant("dash") == "dash"
            assert resolve_tenant(None) == DEFAULT_TENANT
            assert resolve_tenant("") == DEFAULT_TENANT
            assert c.value() == before  # known/empty: not "unknown"
            # Raw client strings NEVER reach metric labels: folded into
            # the shared tenant + counted once, unlabeled.
            assert resolve_tenant("rando-123") == DEFAULT_TENANT
            assert c.value() == before + 1


class TestAdmissionScheduler:
    def test_over_share_tenant_queues_behind_itself_only(self):
        """The isolation primitive: tenant A's backlog never queues
        tenant B — B admits THROUGH A's queued waiters."""
        adm = _Admission()
        with override_flag("admission_tenant_weights", "a:1,b:1"), \
                override_flag("admission_bytes_budget_mb", 3.0), \
                override_flag("admission_queue_s", 10.0):
            # Shares: a=1MB, b=1MB, shared=1MB.
            adm.admit("a1", _pred(900 << 10), tenant="a")
            order = []

            def a2():
                adm.admit("a2", _pred(900 << 10), tenant="a")
                order.append("a2")

            t = threading.Thread(target=a2)
            t.start()
            time.sleep(0.1)
            assert order == []  # a2 queued behind a's own in-flight
            # b sails through while a's backlog is queued.
            t0 = time.perf_counter()
            adm.admit("b1", _pred(900 << 10), tenant="b")
            assert time.perf_counter() - t0 < 0.5
            assert order == []
            adm.release("a1")
            t.join(5.0)
            assert order == ["a2"]
            assert set(adm.in_flight()) == {"a2", "b1"}
            adm.release("a2")
            adm.release("b1")

    def test_reject_predicted_over_tenant_share(self):
        adm = _Admission()
        with override_flag("admission_tenant_weights", "a:1,b:1"), \
                override_flag("admission_bytes_budget_mb", 3.0):
            with pytest.raises(AdmissionError) as ei:
                adm.admit("q", _pred(2 << 20), tenant="a")  # share = 1MB
        assert ei.value.diagnostic.code == "admission-reject"
        assert "share" in str(ei.value)
        assert adm.in_flight() == {}

    def test_wait_queue_orders_priority_then_deadline(self):
        """Release order is (priority desc, EDF, arrival) — not
        arrival."""
        adm = _Admission()
        order = []
        with override_flag("admission_bytes_budget_mb", 1.0), \
                override_flag("admission_queue_s", 15.0):
            adm.admit("hold", _pred(900 << 10))
            now = time.monotonic()

            def waiter(qid, priority, deadline):
                adm.admit(
                    "q" + qid, _pred(900 << 10),
                    priority=priority, deadline=deadline,
                )
                order.append(qid)
                adm.release("q" + qid)

            specs = [
                ("late-lowpri", 0, now + 60.0),
                ("early-lowpri", 0, now + 30.0),
                ("hipri", 5, None),
            ]
            threads = []
            for qid, pri, dl in specs:
                t = threading.Thread(target=waiter, args=(qid, pri, dl))
                t.start()
                threads.append(t)
                time.sleep(0.05)  # deterministic arrival order
            assert adm.queued()[0]["qid"] == "qhipri"
            adm.release("hold")
            for t in threads:
                t.join(10.0)
        assert order == ["hipri", "early-lowpri", "late-lowpri"]

    def test_queued_deadline_lapse_sheds_with_structured_diag(self):
        from pixie_tpu.services.observability import default_counter

        adm = _Admission()
        shed_c = default_counter("pixie_admission_shed_total").labels(
            tenant=DEFAULT_TENANT
        )
        before = shed_c.value()
        with override_flag("admission_bytes_budget_mb", 1.0), \
                override_flag("admission_queue_s", 30.0):
            adm.admit("hold", _pred(900 << 10))
            t0 = time.perf_counter()
            with pytest.raises(AdmissionError) as ei:
                adm.admit(
                    "q2", _pred(900 << 10),
                    deadline=time.monotonic() + 0.15,
                )
            waited = time.perf_counter() - t0
        assert ei.value.diagnostic.code == "admission-shed"
        assert 0.1 < waited < 5.0  # shed AT the deadline, not queue_s
        assert shed_c.value() == before + 1
        assert list(adm.in_flight()) == ["hold"]
        assert adm.queued() == []

    def test_release_wakes_waiter_immediately(self):
        """Satellite: release-to-admit latency is event-driven — a
        freed budget admits the next eligible query in well under any
        polling slice (the queue timeout here is 20s; the wakeup must
        be ~instant)."""
        adm = _Admission()
        admitted_at = {}
        with override_flag("admission_bytes_budget_mb", 1.0), \
                override_flag("admission_queue_s", 20.0):
            adm.admit("q1", _pred(900 << 10))

            def second():
                adm.admit("q2", _pred(900 << 10))
                admitted_at["t"] = time.perf_counter()

            t = threading.Thread(target=second)
            t.start()
            time.sleep(0.2)  # q2 is parked on its event
            released_at = time.perf_counter()
            adm.release("q1")
            t.join(5.0)
        latency = admitted_at["t"] - released_at
        assert latency < 0.05, f"release->admit took {latency:.3f}s"

    def test_shed_unblocks_lower_priority_waiters(self):
        """A shed waiter re-runs the scheduler on its way out: a
        high-priority waiter that was strictly-priority-blocking a
        lower-priority OTHER-tenant waiter must, when its deadline
        sheds it, admit that waiter immediately — no release event is
        ever coming, so without the reschedule the blocked waiter
        sleeps out its whole queue timeout."""
        adm = _Admission()
        admitted_at = {}
        with override_flag("admission_tenant_weights", "a:1,b:1"), \
                override_flag("admission_bytes_budget_mb", 3.0), \
                override_flag("admission_queue_s", 20.0):
            # Shares: a=1MB, b=1MB, shared=1MB. Fill a's share.
            adm.admit("a1", _pred(900 << 10), tenant="a")

            def high():
                with pytest.raises(AdmissionError) as ei:
                    adm.admit(
                        "aH", _pred(900 << 10), tenant="a", priority=5,
                        deadline=time.monotonic() + 0.3,
                    )
                admitted_at["shed_code"] = ei.value.diagnostic.code
                admitted_at["shed_t"] = time.perf_counter()

            def low():
                adm.admit("bL", _pred(900 << 10), tenant="b")
                admitted_at["bL"] = time.perf_counter()

            th = threading.Thread(target=high)
            th.start()
            time.sleep(0.05)  # aH queued (a's share full), priority 5
            tl = threading.Thread(target=low)
            tl.start()
            time.sleep(0.1)
            # bL fits b's empty share but yields to the waiting
            # priority-5 class (strict priority).
            assert "bL" not in admitted_at
            th.join(5.0)
            tl.join(5.0)
            assert admitted_at.get("shed_code") == "admission-shed"
            assert "bL" in admitted_at, "bL never admitted"
            # Event-driven: bL admits on aH's shed, not at queue_s.
            latency = admitted_at["bL"] - admitted_at["shed_t"]
            assert latency < 2.0, f"shed->admit took {latency:.3f}s"
            adm.release("a1")
            adm.release("bL")

    def test_same_tenant_small_queries_do_not_starve_blocked_big(self):
        """FIFO within a tenant: a stream of small queries must not
        overtake (and starve) the tenant's blocked larger query — the
        scheduler skips a BLOCKED tenant's later waiters instead of
        backfilling around its head."""
        adm = _Admission()
        order = []
        with override_flag("admission_bytes_budget_mb", 1.0), \
                override_flag("admission_queue_s", 20.0):
            adm.admit("b0", _pred(500 << 10))

            def waiter(qid, pred_kb):
                adm.admit(qid, _pred(pred_kb << 10))
                order.append(qid)

            big = threading.Thread(target=waiter, args=("big", 800))
            big.start()
            time.sleep(0.1)  # big queued (0.5 + 0.8 > 1MB)
            small = threading.Thread(target=waiter, args=("small", 400))
            small.start()
            time.sleep(0.2)
            # small FITS the free budget (0.5 + 0.4 < 1MB) but must
            # queue behind its tenant's blocked head.
            assert order == []
            adm.release("b0")
            big.join(5.0)
            assert order == ["big"]
            adm.release("big")
            small.join(5.0)
            assert order == ["big", "small"]
            adm.release("small")

    def test_holddown_armed_mid_sleep_still_wakes_waiter(self):
        """A hold-down armed WHILE a lower-priority waiter sleeps (the
        arming release skips it, and the lapse has no event) must not
        leave the freed budget idle until the waiter's queue timeout —
        sleep slices are bounded by one hold window."""
        adm = _Admission()
        admitted_at = {}
        with override_flag("admission_bytes_budget_mb", 1.0), \
                override_flag("admission_queue_s", 20.0), \
                override_flag("admission_priority_holddown_ms", 100.0):
            adm.admit("hi", _pred(900 << 10), priority=5)

            def low():
                adm.admit("lo", _pred(900 << 10))
                admitted_at["t"] = time.perf_counter()

            t = threading.Thread(target=low)
            t.start()
            time.sleep(0.2)  # lo parked, no hold armed yet
            released_at = time.perf_counter()
            adm.release("hi")  # arms the priority-5 hold-down
            t.join(10.0)
            assert "t" in admitted_at, "lo never admitted"
            latency = admitted_at["t"] - released_at
            # Admits within ~one hold window of the lapse, not at the
            # 20s queue timeout (generous bound for a loaded CI box).
            assert latency < 2.0, f"release->admit took {latency:.3f}s"
            adm.release("lo")

    def test_cancel_removes_queued_waiter(self):
        """_Admission.cancel: a queued waiter is removed so it can
        never admit, and its admit() raises the structured
        admission-cancelled Diagnostic."""
        adm = _Admission()
        caught = {}
        with override_flag("admission_bytes_budget_mb", 1.0), \
                override_flag("admission_queue_s", 20.0):
            adm.admit("hold", _pred(900 << 10))

            def second():
                try:
                    adm.admit("q2", _pred(900 << 10))
                except AdmissionError as e:
                    caught["diag"] = e.diagnostic
                    caught["t"] = time.perf_counter()

            t = threading.Thread(target=second)
            t.start()
            time.sleep(0.2)  # q2 parked
            assert adm.cancel("unknown") is False
            t0 = time.perf_counter()
            assert adm.cancel("q2") is True
            t.join(5.0)
            assert caught.get("diag") is not None, "q2 admitted?!"
            assert caught["diag"].code == "admission-cancelled"
            assert caught["t"] - t0 < 2.0  # event-driven, not a slice
            assert adm.queued() == []
            # Already-gone waiter: cancel is a no-op.
            assert adm.cancel("q2") is False
            adm.release("hold")

    def test_queued_counter_and_tenant_accounting(self):
        from pixie_tpu.services.observability import default_counter

        adm = _Admission()
        with override_flag("admission_tenant_weights", "a:1"), \
                override_flag("admission_bytes_budget_mb", 2.0), \
                override_flag("admission_queue_s", 10.0):
            queued_c = default_counter(
                "pixie_admission_queued_total"
            ).labels(tenant="a")
            before = queued_c.value()
            adm.admit("a1", _pred(900 << 10), tenant="a")
            assert queued_c.value() == before  # sailed through

            def second():
                adm.admit("a2", _pred(900 << 10), tenant="a")

            t = threading.Thread(target=second)
            t.start()
            time.sleep(0.1)
            assert queued_c.value() == before + 1
            assert adm.in_flight_by_tenant() == {"a": 900 << 10}
            adm.release("a1")
            t.join(5.0)
            adm.release("a2")


def _mk_cluster(n_pems=2, rows=6000, noise_rows=400):
    bus = MessageBus()
    tracker = AgentTracker(bus, expiry_s=60.0, check_interval_s=60.0)
    pems = [PEMAgent(bus, f"pem-{i}", **FAST).start() for i in range(n_pems)]
    kelvin = KelvinAgent(bus, "kelvin-0", **FAST).start()
    rng = np.random.default_rng(7)
    for pem in pems:
        # IDENTICAL content (and dictionary order) on every PEM: the
        # tenancy gate wants deterministic predictions at fixed seeds.
        pem.append_data("http_events", {
            "time_": np.arange(rows, dtype=np.int64),
            "latency_ns": rng.integers(1000, 1_000_000, rows),
            "resp_status": rng.choice(np.array([200, 200, 404, 500]), rows),
            "service": [f"svc-{j % 4}" for j in range(rows)],
        })
        pem.append_data("noise_events", {
            "time_": np.arange(noise_rows, dtype=np.int64),
            "latency_ns": rng.integers(1000, 1_000_000, noise_rows),
            "service": [f"noise-{j % 2}" for j in range(noise_rows)],
        })
        pem._register()
    deadline = time.time() + 5
    while time.time() < deadline and (
        "noise_events" not in tracker.schemas()
        or not tracker.table_stats()
    ):
        time.sleep(0.01)
    broker = QueryBroker(bus, tracker)
    return bus, tracker, pems, kelvin, broker


@pytest.fixture(scope="class")
def cluster():
    bus, tracker, pems, kelvin, broker = _mk_cluster()
    yield bus, tracker, pems, kelvin, broker
    for a in pems + [kelvin]:
        a.stop()
    broker.close()
    tracker.close()
    bus.close()


#: Ceiling on any one wait for an event (a fragment staged, a thread
#: joined), as ``conftest.wait_until``'s: a healthy run never comes near it.
WAIT_S = 120.0


def _predicted_bytes(broker, query):
    """Plan-time predicted staged bytes for one warm run of ``query``
    (admission off)."""
    broker.execute_script(query, timeout_s=30)
    pred = broker.tracer.recent()[0].get("predicted") or {}
    pb = pred.get("bytes_staged_hi")
    assert pb, f"no predicted cost for query (sketches missing?): {pred}"
    return int(pb)


class TestTenantEndToEnd:
    def test_tenant_threads_to_trace_result_and_telemetry(self, cluster):
        bus, tracker, pems, kelvin, broker = cluster
        with override_flag("admission_tenant_weights", "dash:2"):
            res = broker.execute_script(
                VICTIM_Q, timeout_s=30, tenant="dash"
            )
            assert res["tenant"] == "dash"
            row = broker.tracer.recent()[0]
            assert row["tenant"] == "dash"
            # Agents stamped the dispatch envelope's tenant onto their
            # fragment traces -> per-agent __queries__ rows carry it.
            deadline = time.time() + 5
            while time.time() < deadline:
                d = pems[0].engine.tables["__queries__"].read_all(
                ).to_pydict()
                if "dash" in list(d["tenant"]):
                    break
                time.sleep(0.05)
            assert "dash" in list(d["tenant"])
            # Unregistered tenant folds into shared (bounded labels).
            res = broker.execute_script(
                VICTIM_Q, timeout_s=30, tenant="not-registered"
            )
            assert res["tenant"] == DEFAULT_TENANT

    def test_queued_deadline_shed_never_dispatches(self, cluster):
        """Acceptance: a queued query whose deadline lapses is shed
        without dispatch — structured Diagnostic, zero agent
        executions."""
        bus, tracker, pems, kelvin, broker = cluster
        pred = _predicted_bytes(broker, VICTIM_Q)
        budget_mb = (pred * 1.5) / (1 << 20)
        executes = []
        subs = [
            bus.subscribe(f"agent.{p.agent_id}.execute", executes.append)
            for p in pems
        ]
        try:
            with override_flag("admission_bytes_budget_mb", budget_mb), \
                    override_flag("admission_queue_s", 30.0):
                # Fill the shared tenant's whole share, then offer a
                # deadline-bearing query that can only queue.
                broker.admission.admit("blocker", _pred(pred))
                t0 = time.perf_counter()
                with pytest.raises(AdmissionError) as ei:
                    broker.execute_script(
                        VICTIM_Q, timeout_s=30, deadline_ms=200.0
                    )
                waited = time.perf_counter() - t0
                broker.admission.release("blocker")
            assert ei.value.diagnostic.code == "admission-shed"
            assert waited < 5.0  # shed at its deadline, not queue_s
            time.sleep(0.1)  # any (buggy) dispatch would land by now
            assert executes == []  # never dispatched: zero agent work
        finally:
            for s in subs:
                s.unsubscribe()

    def test_cancel_query_reaches_admission_queued_query(self, cluster):
        """`px cancel` of a qid still WAITING for admission (visible in
        `px debug queries`) cancels it at the queue: True from
        cancel_query, a structured never-dispatched error for the
        caller, zero agent executions."""
        bus, tracker, pems, kelvin, broker = cluster
        pred = _predicted_bytes(broker, VICTIM_Q)
        budget_mb = (pred * 1.5) / (1 << 20)
        executes = []
        subs = [
            bus.subscribe(f"agent.{p.agent_id}.execute", executes.append)
            for p in pems
        ]
        out = {}
        try:
            with override_flag("admission_bytes_budget_mb", budget_mb), \
                    override_flag("admission_queue_s", 30.0):
                broker.admission.admit("blocker", _pred(pred))

                def run():
                    try:
                        broker.execute_script(VICTIM_Q, timeout_s=60)
                        out["res"] = "admitted"
                    except AdmissionError as e:
                        out["diag"] = e.diagnostic

                t = threading.Thread(target=run)
                t.start()
                qid = None
                deadline = time.time() + 5
                while time.time() < deadline and qid is None:
                    qid = next(
                        (q["qid"] for q in broker.admission.queued()), None
                    )
                    time.sleep(0.01)
                assert qid, "query never queued"
                assert broker.cancel_query(qid) is True
                t.join(10.0)
                assert not t.is_alive()
                broker.admission.release("blocker")
            assert out.get("diag") is not None, out
            assert out["diag"].code == "admission-cancelled"
            time.sleep(0.1)  # any (buggy) dispatch would land by now
            assert executes == []  # cancelled at the queue: zero work
        finally:
            for s in subs:
                s.unsubscribe()

    def test_served_front_door_is_per_tenant(self, cluster):
        """The REMOTE path's isolation: broker.execute workers are
        capped per tenant, so a noisy tenant whose requests are all
        parked in admission waits cannot occupy the front door — a
        victim tenant's request served concurrently completes promptly
        instead of rotting behind noisy's in a shared FIFO."""
        bus, tracker, pems, kelvin, broker = cluster
        pred = _predicted_bytes(broker, VICTIM_Q)
        # noisy's share fits ONE prediction; victim's fits many.
        budget_mb = (pred * 20) / (1 << 20)
        weights = "victim:17,noisy:1.5,shared:1.5"
        broker.serve()
        replies: dict = {}
        subs = []

        def _ask(key, tenant):
            topic = f"client.test.{key}"
            subs.append(bus.subscribe(
                topic, lambda m, _k=key: replies.setdefault(_k, m)
            ))
            bus.publish("broker.execute", {
                "query": VICTIM_Q, "timeout_s": 30.0, "tenant": tenant,
                "_reply_to": topic,
            })

        try:
            with override_flag("broker_execute_threads", 2), \
                    override_flag("admission_tenant_weights", weights), \
                    override_flag("admission_bytes_budget_mb", budget_mb), \
                    override_flag("admission_queue_s", 30.0):
                # Fill noisy's whole share: its requests can only park.
                broker.admission.admit(
                    "noisy-blocker", _pred(pred), tenant="noisy"
                )
                for i in range(4):  # 2 park in admission, 2 backlog
                    _ask(f"noisy-{i}", "noisy")
                t0 = time.perf_counter()
                _ask("victim", "victim")
                deadline = time.time() + 10
                while time.time() < deadline and "victim" not in replies:
                    time.sleep(0.02)
                waited = time.perf_counter() - t0
                assert replies.get("victim", {}).get("ok") is True, (
                    replies.get("victim")
                )
                assert waited < 8.0, f"victim waited {waited:.1f}s"
                assert not any(
                    k.startswith("noisy") for k in replies
                ), replies.keys()  # noisy still parked: isolation held
                broker.admission.release("noisy-blocker")
                deadline = time.time() + 20
                while time.time() < deadline and len(replies) < 5:
                    time.sleep(0.05)
            assert len(replies) == 5, sorted(replies)
            assert all(m.get("ok") for m in replies.values())
        finally:
            for s in subs:
                s.unsubscribe()

    def test_served_front_door_backlog_bounds_and_expires(self, cluster):
        """Overload at the front door itself fails fast: a tenant's
        backlog past cap x 8 gets an immediate BrokerOverloaded error,
        and a backlogged request whose own timeout elapsed before a
        worker freed is dropped with an error instead of dispatching
        dead agent work. Unknown served tenants count ONCE."""
        from pixie_tpu.services.observability import default_counter

        bus, tracker, pems, kelvin, broker = cluster
        pred = _predicted_bytes(broker, VICTIM_Q)
        broker.serve()
        replies: dict = {}
        subs = []
        executes = []
        subs.extend(
            bus.subscribe(f"agent.{p.agent_id}.execute", executes.append)
            for p in pems
        )

        def _ask(key, timeout_s):
            topic = f"client.fdtest.{key}"
            subs.append(bus.subscribe(
                topic, lambda m, _k=key: replies.setdefault(_k, m)
            ))
            bus.publish("broker.execute", {
                "query": VICTIM_Q, "timeout_s": timeout_s,
                "tenant": "unknown-tenant-string",
                "_reply_to": topic,
            })

        unknown_c = default_counter("pixie_admission_unknown_tenant_total")
        before_unknown = unknown_c.value()
        try:
            with override_flag("broker_execute_threads", 1), \
                    override_flag("admission_tenant_weights", "x:1"), \
                    override_flag("admission_bytes_budget_mb",
                                  (pred * 2 * 1.2) / (1 << 20)), \
                    override_flag("admission_queue_s", 30.0):
                # Fill the shared share: every request parks.
                broker.admission.admit("blocker", _pred(pred))
                n_before = len(executes)
                _ask("head", 30.0)       # holds the 1 worker (parked)
                time.sleep(0.2)
                for i in range(8):       # fills the cap*8 backlog
                    _ask(f"bl-{i}", 0.4)
                _ask("overflow", 30.0)   # past the bound: fail fast
                deadline = time.time() + 5
                while time.time() < deadline and "overflow" not in replies:
                    time.sleep(0.02)
                ov = replies.get("overflow")
                assert ov and ov["ok"] is False, ov
                assert "backlog full" in ov["error"], ov
                # The front door resolved all 10 requests WITHOUT
                # counting; only the one query that actually reached
                # execute_script (head, parked at admission) counted.
                assert unknown_c.value() - before_unknown == 1
                time.sleep(0.5)          # backlogged 0.4s timeouts lapse
                broker.admission.release("blocker")
                deadline = time.time() + 20
                while time.time() < deadline and len(replies) < 10:
                    time.sleep(0.05)
            assert len(replies) == 10, sorted(replies)
            assert replies["head"]["ok"] is True
            for i in range(8):
                r = replies[f"bl-{i}"]
                assert r["ok"] is False and "expired" in r["error"], r
            # Only the head dispatched agent work; expired backlog
            # entries and the overflow never did.
            assert len(executes) - n_before == len(pems), executes
        finally:
            for s in subs:
                s.unsubscribe()

    def test_cancel_query_returns_partial_cancelled(self, cluster):
        bus, tracker, pems, kelvin, broker = cluster
        # Hold every PEM at its first staged window until the cancel is
        # in: the query is then mid-flight by construction (dispatched,
        # so registered with the forwarder; no result can beat the
        # interrupt), however slow the box is.
        mid_flight, cancelled = threading.Event(), threading.Event()
        originals = []
        for p in pems:
            eng = p.engine
            orig = eng._staged_windows
            originals.append((eng, orig))

            def held(stream, stats=None, _orig=orig):
                for w in _orig(stream, stats):
                    mid_flight.set()
                    cancelled.wait(WAIT_S)
                    yield w

            eng._staged_windows = held
        out = {}

        def run():
            try:
                out["res"] = broker.execute_script(VICTIM_Q, timeout_s=WAIT_S)
            except Exception as e:  # noqa: BLE001 - recorded for assert
                out["err"] = e

        t = threading.Thread(target=run)
        t.start()
        try:
            assert mid_flight.wait(WAIT_S), (
                f"no PEM ever staged a window: {out.get('err')}")
            qid = next(
                (q.get("qid") for q in broker.tracer.in_flight()
                 if q.get("qid")), None,
            )
            assert qid, "a dispatched query is not visible in-flight"
            assert broker.cancel_query(qid) is True
            t.join(WAIT_S)
            assert not t.is_alive()
            res = out.get("res")
            assert res is not None, f"cancel errored: {out.get('err')}"
            assert res["partial"] is True
            assert res["interrupted"] == "cancelled"
            assert set(res["missing_reasons"].values()) == {"cancelled"}
        finally:
            cancelled.set()
            for eng, orig in originals:
                eng._staged_windows = orig
            t.join(WAIT_S)
        # cancel of an unknown qid is a clean no-op.
        assert broker.cancel_query("nonexistent") is False

    def test_cancel_mid_merge_stops_the_merge(self, cluster):
        """query.cancel reaches a RUNNING merge fragment, not just the
        data tier: the kelvin registers its merge's cancel event under
        the qid, so `px cancel` aborts the merge at its boundary (since
        PR 31 the merge is one program: before its dispatch) instead of
        computing it as dead work."""
        from pixie_tpu.exec import engine as engine_mod

        bus, tracker, pems, kelvin, broker = cluster
        merges = []
        kelvin.engine.tracer.add_listener(
            lambda t: merges.append(t) if t.kind == "merge" else None
        )
        real = engine_mod.merge_agg_bridge
        # The merge parks at its start until the gate opens (open from
        # the start for the reference run), so the cancel reaches a merge
        # that is running and has its program left to run, on any box.
        in_merge, gate = threading.Event(), threading.Event()

        def parked(*a, **k):
            in_merge.set()
            gate.wait(WAIT_S)
            return real(*a, **k)

        engine_mod.merge_agg_bridge = parked
        out = {}

        def run(key):
            try:
                out[key] = broker.execute_script(VICTIM_Q, timeout_s=WAIT_S)
            except Exception as e:  # noqa: BLE001 - recorded for assert
                out[key + "_err"] = e

        def merge_state(qid):
            """(the Kelvin holds the cancel, its merge is still running)"""
            with kelvin._lock:
                return qid in kelvin._cancelled, qid in kelvin._running

        def dispatched(qid):
            """Programs the Kelvin's merge of ``qid`` enqueued."""
            wait_until(lambda: any(t.qid == qid for t in merges),
                       "the merge's trace never finished")
            trace = next(t for t in merges if t.qid == qid)
            return [s.attributes["program"] for s in trace.spans
                    if s.name == "device.dispatch"]

        # Uncancelled reference run: what a full merge enqueues.
        gate.set()
        t = threading.Thread(target=run, args=("full",))
        t.start()
        t.join(WAIT_S)
        try:
            assert not t.is_alive() and "full" in out, out.get("full_err")
            assert dispatched(out["full"]["qid"]) == ["merge_finalize"]

            in_merge.clear()
            gate.clear()
            t = threading.Thread(target=run, args=("cancelled",))
            t.start()
            assert in_merge.wait(WAIT_S), "merge never started"
            qid = next(
                (q.get("qid") for q in broker.tracer.in_flight()
                 if q.get("qid")), None,
            )
            assert qid, "a merging query is not visible in-flight"
            assert broker.cancel_query(qid) is True
            t.join(WAIT_S)
            assert not t.is_alive()
            # The merge must actually STOP: once the Kelvin holds the
            # cancel, let the parked merge go on, wait until it has ended
            # one way or the other, and see that it enqueued nothing.
            wait_until(lambda: merge_state(qid)[0],
                        "the cancel never reached the Kelvin")
            gate.set()
            wait_until(lambda: not merge_state(qid)[1],
                        "the cancelled merge never ended")
            assert dispatched(qid) == [], "the merge ran after the cancel"
            res = out.get("cancelled")
            assert res is not None, f"err: {out.get('cancelled_err')}"
            assert res["partial"] is True
            assert res["interrupted"] == "cancelled"
        finally:
            gate.set()
            engine_mod.merge_agg_bridge = real
            t.join(WAIT_S)


class TestLoadTesterKwargs:
    def test_tenancy_kwargs_forward_independently(self):
        """deadline_ms / priority reach the executor even without a
        tenant — each kwarg forwards on its own, not gated on tenant."""
        from pixie_tpu.services.load_tester import run_load

        seen = []

        def execute(query, timeout_s, **kw):
            seen.append(kw)

        run_load(execute, "q", workers=1, per_worker=1, deadline_ms=500.0)
        assert seen and seen[0].get("deadline_ms") == 500.0
        assert "tenant" not in seen[0]
        seen.clear()
        run_load(execute, "q", workers=1, per_worker=1,
                 tenant="a", priority=3)
        assert seen[0] == {"tenant": "a", "priority": 3}

    def test_mixed_load_streams_sharing_tenant_stay_separate(self):
        """Two streams of the SAME tenant (e.g. two priorities) get
        separate LoadReports — their latency distributions must not
        silently merge under one tenant key."""
        from pixie_tpu.services.load_tester import (
            TenantStream, run_mixed_load,
        )

        def execute(query, timeout_s, **kw):
            pass

        reports = run_mixed_load(execute, [
            TenantStream(tenant="dash", query="q", workers=1,
                         per_worker=1, priority=5),
            TenantStream(tenant="dash", query="q", workers=1,
                         per_worker=2, priority=0),
        ])
        assert set(reports) == {"dash", "dash#1"}
        assert reports["dash"].queries == 1
        assert reports["dash#1"].queries == 2


@pytest.fixture(scope="class")
def gate_cluster():
    bus, tracker, pems, kelvin, broker = _mk_cluster(
        n_pems=2, rows=8000, noise_rows=300
    )
    yield bus, tracker, pems, kelvin, broker
    for a in pems + [kelvin]:
        a.stop()
    broker.close()
    tracker.close()
    bus.close()


@pytest.mark.slow
class TestP99Isolation:
    """The ``run_tests.sh --tenancy`` gate: with tenant ``noisy``
    saturating its share (offered in-flight predicted cost >= 2x the
    share) and tenant ``victim`` at its solo rate, the victim's p99
    degrades <= 25% vs its solo baseline and it sheds zero queries,
    while the noisy tenant's own p99 visibly rises. Fixed seeds; both
    runs use the SAME admission config so fixed costs cancel.

    Measurement design (each piece removes a NON-scheduler noise
    source from a single-digit-ms p99 comparison on a shared 1-core CI
    box):

    - A/B/A bracketing: the solo baseline runs BOTH before and after
      the mixed run and the bound compares against the max — system
      state drifts monotonically across a session (telemetry tables
      grow), so a baseline measured only before would blame the
      scheduler for drift.
    - gc off during measurement: a generational collection is a
      ~100ms pause that lands on whichever run it likes.
    - 200 victim queries: nearest-rank p99 is the 3rd-worst sample, so
      the one bounded priority inversion non-preemptive admission
      allows at t=0 (a noisy query admitted into an idle engine can
      overlap the victim's first arrivals for at most one noisy
      service time — both are already in flight; no scheduler can
      undo that without preemption) does not decide the gate.
    - priority hold-down (150ms >> the victim's ~1ms inter-arrival
      gap): engines execute one query at a time, so without the grace
      window a noisy query admitted BETWEEN two victim queries
      head-of-line blocks the second at the agent.
    """

    def test_noisy_tenant_does_not_move_victim_p99(self, gate_cluster):
        import gc

        from pixie_tpu.services.load_tester import (
            TenantStream, broker_executor, run_load, run_mixed_load,
        )
        from pixie_tpu.services.observability import default_counter

        bus, tracker, pems, kelvin, broker = gate_cluster
        execute = broker_executor(broker)
        # Warm every compile cache + learn predictions (admission off).
        pred_v = _predicted_bytes(broker, VICTIM_Q)
        pred_n = _predicted_bytes(broker, NOISY_CHEAP_Q)
        # Shares: noisy fits ONE query in flight (1.5x its per-query
        # prediction); victim gets 8x headroom so it never queues on
        # its own account. weight_v solves share_v = 8*pred_v given
        # share_n = 1.5*pred_n at weight 1 (shares are linear in
        # weights).
        weight_v = (8.0 * pred_v) / (1.5 * pred_n)
        budget_mb = 1.5 * pred_n * (weight_v + 2.0) / (1 << 20)
        weights = f"victim:{weight_v:.6f},noisy:1"

        def solo_victim():
            r = run_load(
                execute, VICTIM_Q, workers=1, per_worker=200,
                tenant="victim", priority=5,
            )
            assert r.errors == 0 and r.sheds == 0
            return r

        def measure():
            with override_flag("admission_tenant_weights", weights), \
                    override_flag("admission_bytes_budget_mb", budget_mb), \
                    override_flag("admission_queue_s", 60.0), \
                    override_flag("admission_priority_holddown_ms", 150.0):
                solo_n = run_load(
                    execute, NOISY_CHEAP_Q, workers=1, per_worker=10,
                    tenant="noisy",
                )
                solo_before = solo_victim()
                queued_before = default_counter(
                    "pixie_admission_queued_total"
                ).labels(tenant="noisy").value()
                mixed = run_mixed_load(execute, [
                    TenantStream(
                        tenant="victim", query=VICTIM_Q, workers=1,
                        per_worker=200, priority=5,
                    ),
                    # Saturation: 8 concurrent offers x pred_n >= 2x
                    # the noisy share (which fits ~1.5 predictions).
                    TenantStream(
                        tenant="noisy", query=NOISY_CHEAP_Q, workers=8,
                        per_worker=8, priority=0,
                    ),
                ])
                queued_after = default_counter(
                    "pixie_admission_queued_total"
                ).labels(tenant="noisy").value()
                solo_after = solo_victim()
            return (solo_n, solo_before, mixed, solo_after,
                    queued_before, queued_after)

        gc.collect()
        gc.disable()
        try:
            # ONE bounded re-measurement: on a shared 1-core CI box a
            # single ~10s window occasionally eats an unrelated
            # scheduling storm that lands in the victim's 3rd-worst
            # sample. A genuine isolation regression is systematic and
            # fails BOTH windows; a storm fails at most one.
            for attempt in (1, 2):
                (solo_n, solo_before, mixed, solo_after,
                 queued_before, queued_after) = measure()
                ok = (
                    mixed["victim"].percentile(99)
                    <= 1.25 * max(solo_before.percentile(99),
                                  solo_after.percentile(99))
                )
                if ok or attempt == 2:
                    break
        finally:
            gc.enable()
        victim, noisy = mixed["victim"], mixed["noisy"]
        # The victim tenant: zero sheds, zero failures, p99 within 25%
        # of its solo baseline (the acceptance bound).
        assert victim.errors == 0, victim.to_dict()
        assert victim.sheds == 0
        p99_solo = max(
            solo_before.percentile(99), solo_after.percentile(99)
        )
        p99_mixed = victim.percentile(99)
        assert p99_mixed <= 1.25 * p99_solo, (
            f"victim p99 moved {p99_solo * 1e3:.1f}ms -> "
            f"{p99_mixed * 1e3:.1f}ms "
            f"(noisy: {noisy.to_dict()}, victim: {victim.to_dict()})"
        )
        # The noisy tenant saturated: its queries actually queued
        # behind its own backlog and its p99 rose well above solo.
        assert queued_after > queued_before
        assert noisy.queries == 64
        assert noisy.errors == 0 and noisy.sheds == 0, noisy.to_dict()
        assert noisy.percentile(99) >= 1.5 * solo_n.percentile(99), (
            f"noisy p99 did not rise: solo "
            f"{solo_n.percentile(99) * 1e3:.1f}ms vs mixed "
            f"{noisy.percentile(99) * 1e3:.1f}ms"
        )
