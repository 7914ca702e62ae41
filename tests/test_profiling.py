"""Continuous-profiling tier tests (attributed CPU profiles).

Covers the profiling tier end to end: the truncation-marker fold fix,
the threadmap attribution registry (exec/threadmap.py), sampler
attribution majority + cross-tenant isolation + thread-leak freedom,
the 2-agent cluster merge through heartbeats and /debug/pprof, the
differential-profile math, the px/query_cpu end-to-end attribution
proof through a live broker, and the sampler overhead A/B on the
http_stats replay shape. See docs/OBSERVABILITY.md "Profiling tier".
"""

from __future__ import annotations

import sys
import threading
import time
import types

import numpy as np
import pytest

from pixie_tpu import config
from pixie_tpu.exec import threadmap
from pixie_tpu.exec.engine import Engine
from pixie_tpu.ingest.collector import Collector
from pixie_tpu.ingest.profiler import (
    TRUNCATED_MARKER,
    PerfProfilerConnector,
    _fold_stack,
    profile_summary,
)
from pixie_tpu.services.observability import (
    ObservabilityServer,
    default_counter,
)
from pixie_tpu.services.telemetry import (
    collapsed_text,
    counts_delta,
    flame_html,
    profile_counts,
    profile_diff,
)


def _trace(qid="", script_hash="", tenant=""):
    """A stand-in for QueryTrace: the attribution reader only touches
    these three attributes (and reads them LIVE, which the tests poke)."""
    return types.SimpleNamespace(qid=qid, script_hash=script_hash,
                                 tenant=tenant)


def _spin_alpha_marker(stop):
    while not stop.is_set():
        sum(range(200))


def _spin_beta_marker(stop):
    while not stop.is_set():
        sum(range(200))


class _AttributedSpin:
    """Worker thread parked in a uniquely-named spin function with its
    threadmap attribution bound around the spin — any sample whose
    stack contains the spin function's name was taken while bound."""

    def __init__(self, fn, trace, phase="host"):
        self.stop = threading.Event()
        self._fn, self._trace, self._phase = fn, trace, phase
        self.thread = threading.Thread(target=self._run, daemon=True)

    def _run(self):
        tok = threadmap.bind(trace=self._trace, phase=self._phase)
        try:
            self._fn(self.stop)
        finally:
            threadmap.unbind(tok)

    def __enter__(self):
        self.thread.start()
        return self

    def __exit__(self, *exc):
        self.stop.set()
        self.thread.join(timeout=5)


def _sweep(conn, n=25, dt=0.002):
    for _ in range(n):
        conn.sample()
        time.sleep(dt)


def _conn(agent_id):
    c = PerfProfilerConnector(
        pod=f"test/{agent_id}", agent_id=agent_id,
        sampling_period_s=0.0, push_period_s=0.0,
    )
    c.init()
    return c


class TestFoldStack:
    def test_truncated_marker_lands_at_root(self):
        def deep(n):
            if n:
                return deep(n - 1)
            return sys._getframe()

        frame = deep(10)
        trunc = _fold_stack(frame, max_depth=3)
        parts = trunc.split(";")
        assert parts[0] == TRUNCATED_MARKER
        assert len(parts) == 4  # marker + the 3 innermost frames
        assert parts[-1].endswith(":deep")

    def test_marker_disambiguates_deep_from_shallow(self):
        # The aliasing bug the marker fixes: a stack DEEPER than the
        # fold bound must not produce the same folded key as a stack
        # that genuinely IS the kept suffix.
        def deep(n):
            if n:
                return deep(n - 1)
            return sys._getframe()

        frame = deep(10)
        trunc = _fold_stack(frame, max_depth=3)
        kept_suffix = ";".join(trunc.split(";")[1:])
        assert trunc != kept_suffix
        assert trunc.startswith(TRUNCATED_MARKER + ";")

    def test_shallow_stack_has_no_marker(self):
        s = _fold_stack(sys._getframe())
        assert TRUNCATED_MARKER not in s
        assert s.endswith("test_profiling.py:test_shallow_stack_has_no_marker")


class TestThreadmap:
    def test_bind_unbind_nesting_restores(self):
        t1, t2 = _trace(qid="q1"), _trace(qid="q2")
        tok1 = threadmap.bind(trace=t1, phase="host")
        try:
            assert threadmap.current_entry()["trace"] is t1
            tok2 = threadmap.bind(trace=t2)
            assert threadmap.current_entry()["trace"] is t2
            threadmap.unbind(tok2)
            assert threadmap.current_entry()["trace"] is t1
        finally:
            threadmap.unbind(tok1)
        assert threadmap.current_entry() is None

    def test_set_phase_fast_exit_when_unattributed(self):
        assert threadmap.current_entry() is None
        tok = threadmap.set_phase("device_dispatch")
        assert tok is None
        threadmap.restore(tok)  # no-op, must not raise
        assert threadmap.current_entry() is None

    def test_set_phase_and_restore(self):
        with threadmap.attributed(trace=_trace(qid="q"), phase="host"):
            tok = threadmap.set_phase("device_dispatch")
            assert threadmap.current_entry()["phase"] == "device_dispatch"
            threadmap.restore(tok)
            assert threadmap.current_entry()["phase"] == "host"

    def test_attribution_reads_live_trace(self):
        # The broker stamps qid/tenant a few lines AFTER begin_query;
        # samples taken after the stamp must see the stamped values.
        tr = _trace(script_hash="aaaa")
        with threadmap.attributed(trace=tr, phase="host"):
            entry = threadmap.current_entry()
            assert threadmap.attribution(entry) == ("", "aaaa", "", "host")
            tr.qid = "q-9"
            tr.tenant = "alpha"
            assert threadmap.attribution(entry) == (
                "q-9", "aaaa", "alpha", "host"
            )

    def test_ctx_envelope_supplies_qid_fallback(self):
        with threadmap.attributed(ctx={"trace_id": "t-42"}):
            qid, sh, tenant, phase = threadmap.attribution(
                threadmap.current_entry()
            )
            assert qid == "t-42" and sh == "" and tenant == ""

    def test_base_inheritance_across_threads(self):
        # The pipeline prefetch thread rebinds its creator's entry.
        tr = _trace(qid="q1", tenant="alpha")
        seen = {}
        with threadmap.attributed(trace=tr, phase="host"):
            base = threadmap.current_entry()

            def child():
                tok = threadmap.bind(base=base, phase="stage")
                try:
                    seen["attr"] = threadmap.attribution(
                        threadmap.current_entry()
                    )
                finally:
                    threadmap.unbind(tok)
                seen["after"] = threadmap.current_entry()

            t = threading.Thread(target=child)
            t.start()
            t.join()
        assert seen["attr"] == ("q1", "", "alpha", "stage")
        assert seen["after"] is None


class TestSamplerAttribution:
    def test_attribution_majority(self):
        conn = _conn("attr-major")
        try:
            tr = _trace(qid="q-a", script_hash="hash-a", tenant="alpha")
            with _AttributedSpin(_spin_alpha_marker, tr):
                _sweep(conn)
            rows = profile_summary(agent_id="attr-major", top=0)
            marked = [r for r in rows if "_spin_alpha_marker" in r["stack"]]
            assert marked, "sampler never caught the spin thread"
            # Every sample of the uniquely-named spin function was taken
            # while bound: full attribution, not just a majority.
            for r in marked:
                assert r["tenant"] == "alpha"
                assert r["script_hash"] == "hash-a"
                assert r["qid"] == "q-a"
                assert r["phase"] == "host"
            # ... and hash-a is the top CPU consumer among attributed
            # stacks (nothing else was bound during the sweep).
            by_hash = {}
            for r in rows:
                if r["script_hash"]:
                    by_hash[r["script_hash"]] = (
                        by_hash.get(r["script_hash"], 0) + r["count"]
                    )
            assert max(by_hash, key=by_hash.get) == "hash-a"
        finally:
            conn.stop()

    def test_cross_tenant_isolation(self):
        conn = _conn("attr-iso")
        try:
            tr_a = _trace(qid="qa", script_hash="ha", tenant="alpha")
            tr_b = _trace(qid="qb", script_hash="hb", tenant="beta")
            with _AttributedSpin(_spin_alpha_marker, tr_a), \
                    _AttributedSpin(_spin_beta_marker, tr_b):
                _sweep(conn)
            rows = profile_summary(agent_id="attr-iso", top=0)
            a_rows = [r for r in rows if "_spin_alpha_marker" in r["stack"]]
            b_rows = [r for r in rows if "_spin_beta_marker" in r["stack"]]
            assert a_rows and b_rows
            assert all(r["tenant"] == "alpha" for r in a_rows)
            assert all(r["tenant"] == "beta" for r in b_rows)
        finally:
            conn.stop()

    def test_per_tenant_cpu_counter(self):
        with config.override_flag("admission_tenant_weights", "alpha:1"):
            from pixie_tpu.services.tenancy import resolve_tenant

            tenant = resolve_tenant("alpha")
            assert tenant == "alpha"
            counter = default_counter(
                "pixie_cpu_samples_total",
                "Profiler stack samples attributed to each tenant "
                "(samples * sampling period = CPU-seconds)",
            )
            before = counter.labels(tenant=tenant).value()
            conn = _conn("attr-counter")
            try:
                tr = _trace(qid="q", script_hash="h", tenant="alpha")
                with _AttributedSpin(_spin_alpha_marker, tr):
                    _sweep(conn, n=15)
            finally:
                conn.stop()
            assert counter.labels(tenant=tenant).value() > before

    def test_unregistered_tenant_folds_to_shared_label(self):
        # An attribution string outside the registered set must not mint
        # a new label series (bounded cardinality).
        from pixie_tpu.services.tenancy import DEFAULT_TENANT, resolve_tenant

        tenant = resolve_tenant(DEFAULT_TENANT)
        counter = default_counter(
            "pixie_cpu_samples_total",
            "Profiler stack samples attributed to each tenant "
            "(samples * sampling period = CPU-seconds)",
        )
        before = counter.labels(tenant=tenant).value()
        conn = _conn("attr-unreg")
        try:
            tr = _trace(qid="q", script_hash="h", tenant="not-registered-x")
            with _AttributedSpin(_spin_alpha_marker, tr):
                _sweep(conn, n=10)
        finally:
            conn.stop()
        # The unregistered name's samples landed on the shared label.
        assert counter.labels(tenant=tenant).value() > before

    def test_sampler_stop_leaves_no_threads_or_roster_entries(self):
        eng = Engine()
        before_threads = threading.active_count()
        coll = Collector()
        coll.wire_to(eng)
        conn = PerfProfilerConnector(
            pod="test/leak", agent_id="leak-check",
            sampling_period_s=0.0, push_period_s=0.0,
        )
        coll.register_source(conn)
        coll.run_as_thread()
        time.sleep(0.05)
        assert profile_summary(agent_id="leak-check", top=1) is not None
        coll.stop()
        deadline = time.time() + 5
        while time.time() < deadline \
                and threading.active_count() > before_threads:
            time.sleep(0.01)
        assert threading.active_count() <= before_threads
        # stop() deregistered the connector from the roster.
        assert profile_summary(agent_id="leak-check", top=0) == []


class TestStacksTable:
    def test_attributed_rows_reach_stacks_table(self):
        eng = Engine()
        coll = Collector()
        coll.wire_to(eng)
        conn = PerfProfilerConnector(
            pod="test/tbl", agent_id="tbl-agent",
            sampling_period_s=0.0, push_period_s=0.0,
        )
        coll.register_source(conn)
        try:
            tr = _trace(qid="q-t", script_hash="hash-t", tenant="alpha")
            with _AttributedSpin(_spin_alpha_marker, tr):
                for _ in range(15):
                    conn.transfer_data(coll, coll._data_tables)
                    time.sleep(0.002)
            coll.flush()
        finally:
            coll.stop()
        out = eng.execute_query(
            "import px\n"
            "df = px.DataFrame(table='__stacks__')\n"
            "px.display(df)\n",
            max_output_rows=10_000,
        )["output"].to_pydict()
        assert len(out["stack_trace"]), "no __stacks__ rows landed"
        assert set(out["agent_id"]) == {"tbl-agent"}
        idx = [i for i, s in enumerate(out["stack_trace"])
               if "_spin_alpha_marker" in s]
        assert idx, "spin thread missing from __stacks__"
        for i in idx:
            assert out["tenant"][i] == "alpha"
            assert out["script_hash"][i] == "hash-t"
            assert out["qid"][i] == "q-t"
            assert out["phase"][i] == "host"
        # The legacy anonymous aggregate still fills alongside.
        legacy = eng.execute_query(
            "import px\n"
            "df = px.DataFrame(table='stack_traces.beta')\n"
            "px.display(df)\n",
            max_output_rows=10_000,
        )["output"].to_pydict()
        assert any("_spin_alpha_marker" in s
                   for s in legacy["stack_trace"])

    def test_tenant_cpu_script_runs_on_real_rows(self):
        eng = Engine()
        coll = Collector()
        coll.wire_to(eng)
        conn = PerfProfilerConnector(
            pod="test/pxl", agent_id="pxl-agent",
            sampling_period_s=0.0, push_period_s=0.0,
        )
        coll.register_source(conn)
        try:
            tr = _trace(qid="q", script_hash="h", tenant="alpha")
            with _AttributedSpin(_spin_alpha_marker, tr):
                for _ in range(10):
                    conn.transfer_data(coll, coll._data_tables)
                    time.sleep(0.002)
            coll.flush()
        finally:
            coll.stop()
        from pixie_tpu.scripts import load_script

        out = eng.execute_query(
            load_script("px/tenant_cpu").pxl, max_output_rows=10_000,
        )["output"].to_pydict()
        assert "alpha" in set(out["tenant"])
        for i, t in enumerate(out["tenant"]):
            assert out["cpu_seconds"][i] == pytest.approx(
                out["samples"][i] / 100.0
            )


class TestDiffMath:
    BASE = {"a;b;c": 10, "a;b;d": 5}
    CMP = {"a;b;c": 10, "a;b;d": 20, "x;y": 3}

    def test_profile_diff_golden(self):
        rows = profile_diff(self.BASE, self.CMP)
        by_frame = {r["frame"]: r for r in rows}
        d = by_frame["d"]
        assert (d["self_base"], d["self_cmp"], d["self_delta"]) == (5, 20, 15)
        assert (d["total_base"], d["total_cmp"], d["total_delta"]) == (
            5, 20, 15
        )
        b = by_frame["b"]
        assert b["self_delta"] == 0  # b never a leaf
        assert (b["total_base"], b["total_cmp"], b["total_delta"]) == (
            15, 30, 15
        )
        y = by_frame["y"]
        assert (y["self_base"], y["self_delta"]) == (0, 3)
        c = by_frame["c"]
        assert c["self_delta"] == 0 and c["total_delta"] == 0
        # Sorted by largest absolute self delta first.
        assert rows[0]["frame"] == "d"

    def test_profile_diff_regression_direction(self):
        rows = profile_diff(self.CMP, self.BASE)  # swapped: a speedup
        by_frame = {r["frame"]: r for r in rows}
        assert by_frame["d"]["self_delta"] == -15
        assert by_frame["y"]["self_delta"] == -3

    def test_counts_delta_clamps_evictions(self):
        before = {"s": 5, "t": 3}
        after = {"s": 7}  # t evicted from a bounded summary
        assert counts_delta(before, after) == {"s": 2}
        assert counts_delta(after, after) == {}

    def test_collapsed_text_format(self):
        text = collapsed_text({"a;b": 2, "c": 9})
        assert text == "c 9\na;b 2\n"
        assert collapsed_text({}) == ""

    def test_profile_counts_filters(self):
        rows = [
            {"stack": "a;b", "count": 3, "tenant": "alpha",
             "script_hash": "h1", "phase": "host"},
            {"stack": "a;b", "count": 2, "tenant": "beta",
             "script_hash": "h2", "phase": "host"},
            {"stack": "c", "count": 1, "tenant": "alpha",
             "script_hash": "h1", "phase": "device_dispatch"},
        ]
        assert profile_counts(rows) == {"a;b": 5, "c": 1}
        assert profile_counts(rows, tenant="alpha") == {"a;b": 3, "c": 1}
        assert profile_counts(rows, script_hash="h2") == {"a;b": 2}
        assert profile_counts(rows, phase="device_dispatch") == {"c": 1}

    def test_flame_html_smoke(self):
        html = flame_html({"a;b;c": 10, "a;d": 5}, title="t<est>")
        assert html.startswith("<!doctype html>")
        assert "t&lt;est&gt;" in html
        for frame in ("\"a\"", "\"b\"", "\"d\""):
            assert frame in html
        assert "total samples: 15" in html


class TestClusterMergeAndPprof:
    def test_two_agent_merge_served_from_broker_endpoints(self):
        from pixie_tpu.services import (
            AgentTracker, KelvinAgent, MessageBus, PEMAgent, QueryBroker,
        )

        bus = MessageBus()
        tracker = AgentTracker(bus, expiry_s=60.0, check_interval_s=60.0)
        pem0 = PEMAgent(bus, "pem-0", heartbeat_interval_s=0.05).start()
        pem1 = PEMAgent(bus, "pem-1", heartbeat_interval_s=0.05).start()
        kelvin = KelvinAgent(
            bus, "kelvin-0", heartbeat_interval_s=0.05
        ).start()
        conn0, conn1 = _conn("pem-0"), _conn("pem-1")
        try:
            # Agent 0's distinctive stack: sample ONLY conn0 while the
            # alpha marker spins, then ONLY conn1 with the beta marker —
            # each agent ships a stack the other never saw.
            tr_a = _trace(qid="q0", script_hash="h0", tenant="alpha")
            with _AttributedSpin(_spin_alpha_marker, tr_a):
                _sweep(conn0, n=10)
            tr_b = _trace(qid="q1", script_hash="h1", tenant="beta")
            with _AttributedSpin(_spin_beta_marker, tr_b):
                _sweep(conn1, n=10)
            deadline = time.time() + 5
            while time.time() < deadline and not (
                {"pem-0", "pem-1"} <= set(tracker.profile_agents())
            ):
                time.sleep(0.01)
            assert {"pem-0", "pem-1"} <= set(tracker.profile_agents())
            broker = QueryBroker(bus, tracker)
            assert {"pem-0", "pem-1"} <= set(broker.profile_agents())
            merged = broker.profile_rows()
            stacks = "\n".join(r["stack"] for r in merged)
            assert "_spin_alpha_marker" in stacks  # from pem-0
            assert "_spin_beta_marker" in stacks   # from pem-1

            obs = ObservabilityServer(profilez_fn=broker.profile_rows)
            code, ctype, body = obs.handle("/debug/pprof")
            assert code == 200 and ctype.startswith("text/plain")
            assert "_spin_alpha_marker" in body
            assert "_spin_beta_marker" in body
            for line in body.strip().splitlines():
                stack, _, count = line.rpartition(" ")
                assert stack and int(count) > 0  # collapsed format

            # Attribution filters thread through the query string.
            _, _, alpha_only = obs.handle("/debug/pprof?tenant=alpha")
            assert "_spin_alpha_marker" in alpha_only
            assert "_spin_beta_marker" not in alpha_only
            _, _, h1_only = obs.handle("/debug/pprof?script=h1")
            assert "_spin_beta_marker" in h1_only
            assert "_spin_alpha_marker" not in h1_only

            code, ctype, page = obs.handle("/debug/flamez")
            assert code == 200 and ctype == "text/html"
            assert "_spin_alpha_marker" in page

            # Windowed pprof: keep sampling + heartbeating during the
            # window; the delta must contain the still-hot stack.
            with _AttributedSpin(_spin_alpha_marker, tr_a):
                stop = threading.Event()

                def bg():
                    while not stop.is_set():
                        conn0.sample()
                        time.sleep(0.002)

                t = threading.Thread(target=bg, daemon=True)
                t.start()
                try:
                    _, _, windowed = obs.handle(
                        "/debug/pprof?seconds=0.3"
                    )
                finally:
                    stop.set()
                    t.join(timeout=5)
            assert "_spin_alpha_marker" in windowed
        finally:
            conn0.stop()
            conn1.stop()
            pem0.stop()
            pem1.stop()
            kelvin.stop()
            tracker.close()

    def test_unwired_profile_endpoint_404s(self):
        obs = ObservabilityServer()
        code, _, body = obs.handle("/debug/pprof")
        assert code == 404 and "no profiler wired" in body


HEAVY_Q = (
    "import px\n"
    "df = px.DataFrame(table='t')\n"
    "df = df.groupby('k').agg(n=('v', px.count), s=('v', px.sum),"
    " mn=('v', px.min), mx=('v', px.max))\n"
    "px.display(df)\n"
)


class TestQueryCpuEndToEnd:
    def test_query_cpu_names_the_hot_script_and_tenant(self):
        """The acceptance proof: a CPU-heavy script run through a live
        broker under a registered tenant, with the profiler sampling,
        must surface in px/query_cpu as the top attributed consumer
        with the admitting tenant on the row."""
        from pixie_tpu.services import (
            AgentTracker, KelvinAgent, MessageBus, PEMAgent, QueryBroker,
        )

        with config.override_flag("admission_tenant_weights", "alpha:1"):
            bus = MessageBus()
            tracker = AgentTracker(bus, expiry_s=60.0, check_interval_s=60.0)
            pem = PEMAgent(bus, "pem-e2e", heartbeat_interval_s=0.05)
            kelvin = KelvinAgent(bus, "kelvin-e2e", heartbeat_interval_s=0.05)
            coll = Collector()
            coll.wire_to(pem.engine)
            conn = PerfProfilerConnector(
                pod="test/e2e", agent_id="pem-e2e",
                sampling_period_s=0.0, push_period_s=0.0,
            )
            coll.register_source(conn)
            pem.start()
            kelvin.start()
            try:
                n = 120_000
                rng = np.random.default_rng(11)
                pem.append_data("t", {
                    "time_": np.arange(n, dtype=np.int64),
                    "k": rng.integers(0, 13, n),
                    "v": rng.integers(0, 1000, n),
                })
                # Seed the __stacks__ table on the agent BEFORE schema
                # registration so the broker can plan over it.
                conn.transfer_data(coll, coll._data_tables)
                coll.flush()
                pem._register()
                deadline = time.time() + 5
                while time.time() < deadline and not (
                    {"t", "__stacks__", "__queries__"}
                    <= set(tracker.schemas())
                ):
                    time.sleep(0.01)
                broker = QueryBroker(bus, tracker)

                stop = threading.Event()

                def sampler():
                    while not stop.is_set():
                        conn.sample()
                        time.sleep(0.002)

                st = threading.Thread(target=sampler, daemon=True)
                st.start()
                try:
                    for _ in range(3):
                        res = broker.execute_script(
                            HEAVY_Q, timeout_s=60, tenant="alpha",
                        )
                        assert res["tables"]["output"].length == 13
                        rows = profile_summary(agent_id="pem-e2e", top=0)
                        if any(r["tenant"] == "alpha" for r in rows):
                            break
                finally:
                    stop.set()
                    st.join(timeout=5)
                conn.transfer_data(coll, coll._data_tables)
                coll.flush()

                # The fragment hashes this load executed on the agent.
                frag_hashes = {
                    t["script_hash"]
                    for t in pem.engine.tracer.recent()
                    if t.get("kind") == "fragment"
                    and t.get("tenant") == "alpha"
                }
                assert frag_hashes

                from pixie_tpu.scripts import load_script

                out = broker.execute_script(
                    load_script("px/query_cpu").pxl, timeout_s=60,
                )["tables"]["output"].to_pydict()
                assert len(out["script_hash"]), "px/query_cpu returned no rows"
                top = max(
                    range(len(out["samples"])),
                    key=lambda i: out["samples"][i],
                )
                assert out["script_hash"][top] in frag_hashes
                assert out["tenant"][top] == "alpha"
                assert out["cpu_seconds"][top] == pytest.approx(
                    out["samples"][top] / 100.0
                )
                assert out["queries"][top] >= 1
            finally:
                conn.stop()
                coll.stop()
                pem.stop()
                kelvin.stop()
                tracker.close()


class TestOverheadAB:
    @pytest.mark.slow
    def test_sampler_overhead_under_five_percent(self):
        """A/B the http_stats replay shape with and without a live
        100Hz sampler: the measured overhead gates at <5% (the number
        in docs/OBSERVABILITY.md comes from this test's print)."""
        from pixie_tpu.analysis.bench_check import (
            SHAPE_SCHEMAS, _shape_query,
        )
        from pixie_tpu.analysis.bound_check import _replay_engine

        eng = _replay_engine(SHAPE_SCHEMAS["http_stats"], rows=20_000)
        q = _shape_query("http_stats")
        for _ in range(2):
            eng.execute_query(q)  # warm the compile caches

        def best_of(n=7):
            best = float("inf")
            for _ in range(n):
                t0 = time.perf_counter()
                eng.execute_query(q)
                best = min(best, time.perf_counter() - t0)
            return best

        base = best_of()
        conn = _conn("overhead-ab")
        stop = threading.Event()

        def sampler():
            # The production rate: one full-thread sweep per 10ms.
            while not stop.is_set():
                conn.sample()
                time.sleep(PerfProfilerConnector.default_sampling_period_s)

        t = threading.Thread(target=sampler, daemon=True)
        t.start()
        try:
            profiled = best_of()
        finally:
            stop.set()
            t.join(timeout=5)
            conn.stop()
        overhead = (profiled - base) / base
        print(f"\n[profile] http_stats sampler overhead: "
              f"{overhead * 100:.2f}% (base {base * 1000:.1f}ms, "
              f"profiled {profiled * 1000:.1f}ms)", file=sys.stderr)
        assert overhead < 0.05, (
            f"sampler overhead {overhead * 100:.1f}% >= 5% "
            f"(base {base * 1000:.1f}ms, profiled {profiled * 1000:.1f}ms)"
        )


class TestLoadTesterCpuAccounting:
    def test_run_load_reports_tenant_cpu_seconds(self):
        from pixie_tpu.services.load_tester import run_load

        with config.override_flag("admission_tenant_weights", "alpha:1"):
            conn = _conn("lt-cpu")
            tr = _trace(qid="q", script_hash="h", tenant="alpha")
            spin = _AttributedSpin(_spin_alpha_marker, tr)
            try:
                spin.__enter__()

                def execute(query, timeout_s, **kw):
                    conn.sample()  # deterministic burn per query
                    return {}

                report = run_load(
                    execute, "q", workers=2, per_worker=5, tenant="alpha",
                )
            finally:
                spin.__exit__(None, None, None)
                conn.stop()
            assert report.queries == 10 and report.errors == 0
            assert report.cpu_seconds_by_tenant.get("alpha", 0) > 0
            d = report.to_dict()
            assert d["cpu_seconds_by_tenant"]["alpha"] == pytest.approx(
                report.cpu_seconds_by_tenant["alpha"]
            )

    def test_report_omits_cpu_key_when_no_samples(self):
        from pixie_tpu.services.load_tester import run_load

        report = run_load(
            lambda q, t, **kw: {}, "q", workers=1, per_worker=2,
        )
        assert report.cpu_seconds_by_tenant == {}
        assert "cpu_seconds_by_tenant" not in report.to_dict()
