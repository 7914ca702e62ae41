"""Device-tier observability (ISSUE 12): the compiled-program registry,
device memory accounting, the ``__programs__`` telemetry table, the
predicted-vs-observed calibration loop, and the admission observed
floor.

Acceptance pins: a repeated query shape is a registry cache HIT with
zero recompiles (visible in ``__programs__``), ``px/bound_accuracy``
returns a finite calibration ratio for every executed script hash, and
with ``admission_observed_floor`` on a sketch-less query whose script
hash has observed history is admitted against the observed floor.
"""

from __future__ import annotations

import time

import numpy as np
import pytest

from conftest import wait_until
from pixie_tpu.config import override_flag
from pixie_tpu.exec.engine import Engine
from pixie_tpu.exec.programs import (
    DeviceMemoryMonitor,
    ProgramRegistry,
    TrackedProgram,
    _analyses,
    default_program_registry,
    shape_signature,
)
from pixie_tpu.services.observability import MetricsRegistry


AGG_QUERY = """import px
df = px.DataFrame(table='{table}')
df = df.groupby(['k']).agg(n=('v', px.count), s=('v', px.sum))
px.display(df)
"""


def _mk_engine(table: str, n: int = 2000, mod: int = 5) -> Engine:
    eng = Engine()
    eng.append_data(table, {
        "time_": np.arange(n, dtype=np.int64),
        "k": (np.arange(n, dtype=np.int64) % mod),
        "v": np.arange(n, dtype=np.int64),
    })
    return eng


class TestRegistryCore:
    def test_repeat_shape_hits_without_recompile(self):
        """Same jit fn, same shapes: one compile, then hits."""
        import jax
        import jax.numpy as jnp

        reg = ProgramRegistry(MetricsRegistry())
        fn = jax.jit(lambda x: x * 2 + 1)
        tp = reg.wrap(fn, "test", ("t", 1), "x*2+1")
        assert isinstance(tp, TrackedProgram)
        x = jnp.arange(64, dtype=jnp.float32)
        a = tp(x)
        b = tp(x)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        st = reg.stats()
        assert st == {"programs": 1, "hits": 1, "compiles": 1}
        # Batched hit increments flush at every /metrics render — a
        # scrape must never under-report by the batch remainder.
        mreg = reg._metrics_registry
        out = mreg.render()
        assert "pixie_program_cache_hits_total 1" in out, out

    def test_shape_change_is_a_miss(self):
        import jax
        import jax.numpy as jnp

        reg = ProgramRegistry(MetricsRegistry())
        tp = reg.wrap(jax.jit(lambda x: x + 1), "test", ("t", 2), "")
        tp(jnp.arange(8, dtype=jnp.float32))
        tp(jnp.arange(16, dtype=jnp.float32))  # new shape: new program
        tp(jnp.arange(8, dtype=jnp.int32))  # new dtype: new program
        st = reg.stats()
        assert st["programs"] == 3 and st["compiles"] == 3
        assert st["hits"] == 0

    def test_results_match_plain_jit(self):
        import jax
        import jax.numpy as jnp

        reg = ProgramRegistry(MetricsRegistry())
        fn = jax.jit(
            lambda st, cols, valid: {
                "acc": st["acc"] + sum(p[0] for p in cols.values()).sum()
                * (valid[1] - valid[0])
            }
        )
        tp = reg.wrap(fn, "test", ("t", 3), "")
        state = {"acc": jnp.zeros(())}
        cols = {"a": (jnp.ones(32),), "b": (jnp.full(32, 2.0),)}
        valid = (np.int32(0), np.int32(32))
        want = fn(state, cols, valid)
        got = tp(state, cols, valid)
        got2 = tp(state, cols, valid)  # the cached-executable path
        np.testing.assert_allclose(
            np.asarray(got["acc"]), np.asarray(want["acc"])
        )
        np.testing.assert_allclose(
            np.asarray(got2["acc"]), np.asarray(want["acc"])
        )

    def test_cost_memory_fields_none_tolerant(self):
        """A fn whose AOT path raises degrades to a timing-only record:
        analysis fields None, execution still correct, every surface
        renders (the CPU/older-jax degradation contract)."""

        class FakeJit:
            def lower(self, *a):
                raise RuntimeError("no AOT on this backend")

            def __call__(self, x):
                return x + 1

        reg = ProgramRegistry(MetricsRegistry())
        tp = reg.wrap(FakeJit(), "test", ("t", 4), "fake")
        out = tp(np.arange(4))
        np.testing.assert_array_equal(out, np.arange(4) + 1)
        out = tp(np.arange(4))  # timing-only record still counts hits
        rec = reg.programz()["programs"][0]
        assert rec["cached"] is False
        assert rec["compiles"] == 1 and rec["hits"] == 1
        for f in ("flops", "bytes_accessed", "argument_bytes",
                  "temp_bytes", "peak_bytes"):
            assert rec[f] is None
        # The __programs__ drain renders Nones as zeros.
        _cursor, rows = reg.rows(0)
        assert rows[0]["flops"] == 0.0 and rows[0]["peak_bytes"] == 0

    def test_degrade_counts_the_jit_recompile(self):
        """An executable that fails at dispatch degrades the record —
        and the NEXT call is routed through the miss path so the jit
        recompile it triggers is counted, not mislabeled a free hit."""

        class Exe:
            def __init__(self):
                self.calls = 0

            def cost_analysis(self):
                return [{}]

            def memory_analysis(self):
                raise RuntimeError("n/a")

            def __call__(self, x):
                self.calls += 1
                raise RuntimeError("layout mismatch")

        class FakeJit:
            def __init__(self):
                self.exe = Exe()

            def lower(self, *a):
                fj = self

                class L:
                    def compile(self):
                        return fj.exe

                return L()

            def __call__(self, x):
                return x * 2

        mreg = MetricsRegistry()
        reg = ProgramRegistry(mreg)
        tp = reg.wrap(FakeJit(), "test", ("t", "degrade"), "")
        out = tp(np.arange(3))  # AOT dispatch fails -> jit fallback
        np.testing.assert_array_equal(out, np.arange(3) * 2)
        rec = reg.programz()["programs"][0]
        assert rec["cached"] is False and rec["compiles"] == 1
        # Next call: miss path again (jit cache cold when the degrade
        # happened mid-first-call is indistinguishable — here the
        # fallback already ran fn, so this IS a warm hit).
        out = tp(np.arange(3))
        np.testing.assert_array_equal(out, np.arange(3) * 2)
        rec = reg.programz()["programs"][0]
        assert rec["hits"] == 1 and rec["compiles"] == 1
        misses = mreg.counter("pixie_program_cache_misses_total")
        assert misses.value() == 1.0

    def test_concurrent_misses_compile_once(self):
        """Two threads first-dispatching the same program must not
        duplicate the XLA compile: the second waits for the first's
        executable."""
        import threading

        compiles = []

        class SlowExe:
            def cost_analysis(self):
                return [{"flops": 1.0}]

            def memory_analysis(self):
                raise RuntimeError("n/a")

            def __call__(self, x):
                return x + 10

        class SlowJit:
            def lower(self, *a):
                class L:
                    def compile(self):
                        compiles.append(1)
                        time.sleep(0.2)
                        return SlowExe()

                return L()

            def __call__(self, x):
                return x + 10

        reg = ProgramRegistry(MetricsRegistry())
        tp = reg.wrap(SlowJit(), "test", ("t", "dedup"), "")
        results = []

        def run():
            results.append(np.asarray(tp(np.arange(4))))

        ts = [threading.Thread(target=run) for _ in range(3)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(10.0)
        assert len(compiles) == 1, "duplicated XLA compile"
        assert len(results) == 3
        for r in results:
            np.testing.assert_array_equal(r, np.arange(4) + 10)

    def test_analyses_guarded(self):
        class Boom:
            def cost_analysis(self):
                raise RuntimeError("nope")

            def memory_analysis(self):
                raise RuntimeError("nope")

        assert _analyses(Boom()) == (None,) * 6

    def test_lru_eviction_counts(self):
        import jax
        import jax.numpy as jnp

        mreg = MetricsRegistry()
        reg = ProgramRegistry(mreg, size=2)
        tp = reg.wrap(jax.jit(lambda x: x + 1), "test", ("t", 5), "")
        for n in (4, 8, 16):
            tp(jnp.arange(n, dtype=jnp.float32))
        assert reg.stats()["programs"] == 2  # oldest evicted
        ev = mreg.counter("pixie_program_cache_evictions_total")
        assert ev.value() == 1.0
        # The evicted shape recompiles (counted as a miss; stats() sums
        # LIVE records only, so audit the cumulative counter) — and the
        # re-created record RESUMES its pre-eviction counters, keeping
        # the __programs__ per-program_id stream monotonic.
        tp(jnp.arange(4, dtype=jnp.float32))
        misses = mreg.counter("pixie_program_cache_misses_total")
        assert misses.value() == 4.0
        resumed = [
            r for r in reg.programz()["programs"] if r["compiles"] == 2
        ]
        assert len(resumed) == 1, reg.programz()["programs"]
        # The telemetry drain sees every program's final state — the
        # evicted-and-not-re-created one included (its seq was bumped
        # at eviction), so no counter increment is ever lost to
        # __programs__.
        _cursor, rows = reg.rows(0)
        assert len({r["program_id"] for r in rows}) == 3

    def test_disabled_registry_returns_fn(self):
        import jax

        reg = ProgramRegistry(MetricsRegistry(), size=0)
        fn = jax.jit(lambda x: x)
        assert reg.wrap(fn, "test", ("t", 6), "") is fn

    def test_unhashable_args_fall_through(self):
        import jax
        import jax.numpy as jnp

        reg = ProgramRegistry(MetricsRegistry())
        tp = reg.wrap(jax.jit(lambda x: x + 1), "test", ("t", 7), "")

        class Weird:  # unhashable sharding-less leaf container
            __hash__ = None
            shape = (2,)
            dtype = np.dtype(np.float32)

        # shape_signature itself must not blow up the call path: the
        # wrapper falls back to the plain jit fn for untrackable input.
        out = tp(jnp.arange(4.0))
        assert reg.stats()["compiles"] == 1
        np.testing.assert_array_equal(np.asarray(out), np.arange(4.0) + 1)

    def test_signature_distinguishes_scalar_kinds(self):
        s1 = shape_signature(((np.int32(0), np.int32(4)),))
        s2 = shape_signature(((np.int32(0), np.int32(8)),))
        assert s1 == s2  # same shapes/dtypes: value-independent
        s3 = shape_signature(((np.int64(0), np.int32(4)),))
        assert s1 != s3


class TestEnginePath:
    def test_repeated_query_zero_recompiles(self):
        """ISSUE 12 acceptance: on a repeated shape the second run is a
        cache hit with zero recompiles, visible in ``__programs__``."""
        from pixie_tpu.services.telemetry import enable_self_telemetry

        eng = _mk_engine("t_prog_accept")
        enable_self_telemetry(eng, agent_id="test-engine")
        reg = default_program_registry()
        q = AGG_QUERY.format(table="t_prog_accept")
        eng.execute_query(q)
        s1 = reg.stats()
        eng.execute_query(q)
        s2 = reg.stats()
        assert s2["compiles"] == s1["compiles"], "second run recompiled"
        assert s2["hits"] > s1["hits"]
        # __programs__ carries the hit: latest row per program shows
        # hits > 0 with compiles unchanged at 1 for this plan's programs.
        out = eng.execute_query(
            "import px\n"
            "df = px.DataFrame(table='__programs__')\n"
            "df = df.groupby(['program_id']).agg(\n"
            "    compiles=('compiles', px.max), hits=('hits', px.max))\n"
            "px.display(df)\n"
        )
        rows = out["output"].to_pydict()
        assert any(
            h > 0 and c == 1
            for c, h in zip(rows["compiles"], rows["hits"])
        ), rows

    def test_programz_surface(self):
        from pixie_tpu.services.observability import ObservabilityServer

        eng = _mk_engine("t_programz")
        eng.execute_query(AGG_QUERY.format(table="t_programz"))
        obs = ObservabilityServer(programs=default_program_registry())
        code, ctype, body = obs.handle("/debug/programz")
        assert code == 200 and "application/json" in ctype
        import json

        pz = json.loads(body)
        assert pz["count"] >= 1
        assert all("compile_ms" in r for r in pz["programs"])
        # Unwired server 404s.
        code, _, _ = ObservabilityServer().handle("/debug/programz")
        assert code == 404

    def test_join_driver_programs_tracked(self):
        eng = Engine()
        n = 1 << 16  # above DEVICE_JOIN_MIN ROWS so the device path runs
        eng.append_data("t_join_l", {
            "time_": np.arange(n, dtype=np.int64),
            "k": np.arange(n, dtype=np.int64) % 251,
            "v": np.arange(n, dtype=np.int64),
        })
        eng.append_data("t_join_r", {
            "time_": np.arange(251, dtype=np.int64),
            "k": np.arange(251, dtype=np.int64),
            "w": np.arange(251, dtype=np.int64) * 3,
        })
        reg = default_program_registry()
        before = {
            r["program_id"]
            for r in reg.programz()["programs"]
            if r["kind"].startswith("join")
        }
        q = """import px
l = px.DataFrame(table='t_join_l')
r = px.DataFrame(table='t_join_r')
j = l.merge(r, how='inner', left_on='k', right_on='k')
j = j.groupby(['k']).agg(n=('w', px.count))
px.display(j)
"""
        out = eng.execute_query(q)
        assert out["output"].length == 251
        after = {
            r["program_id"]
            for r in reg.programz()["programs"]
            if r["kind"].startswith("join")
        }
        if eng.last_join_decision is not None and (
            eng.last_join_decision.strategy in ("sorted", "radix", "single")
        ):
            assert after - before, (
                f"device join ({eng.last_join_decision.strategy}) "
                "produced no tracked program"
            )


class TestProgramsTable:
    def test_ring_respects_byte_budget(self):
        from pixie_tpu.ingest.schemas import PROGRAMS_RELATION

        eng = Engine()
        budget = 16 << 10
        t = eng.create_table("__programs__", PROGRAMS_RELATION,
                             max_bytes=budget)
        row = {
            "time_": [time.time_ns()],
            "agent_id": ["a"],
            "program_id": ["0123456789abcdef"],
            "kind": ["fragment_update"],
            "label": ["MapOp,AggOp"],
            "compiles": [1],
            "hits": [100],
            "compile_ms": [12.5],
            "flops": [1e6],
            "bytes_accessed": [1e6],
            "argument_bytes": [1 << 20],
            "temp_bytes": [1 << 18],
            "peak_bytes": [1 << 20],
        }
        for i in range(800):
            row["hits"] = [i]
            eng.append_data("__programs__", row)
        st = t.stats()
        assert st.bytes <= budget * 1.5, st.bytes  # ring expired oldest
        assert st.num_rows < 800

    def test_collector_folds_program_rows(self):
        from pixie_tpu.services.telemetry import enable_self_telemetry

        eng = _mk_engine("t_fold_prog")
        enable_self_telemetry(eng, agent_id="fold-test")
        eng.execute_query(AGG_QUERY.format(table="t_fold_prog"))
        # The fold runs at trace end; the registry had at least this
        # query's programs pending (plus anything earlier tests left).
        tablets = eng.table_store.tablets("__programs__")
        rows = sum(t.stats().num_rows for t in tablets)
        assert rows >= 1
        rel = eng.table_store.relation("__programs__")
        assert rel.has_column("compile_ms") and rel.has_column("hits")


class TestCalibration:
    def test_bound_accuracy_finite_ratio_per_script(self):
        """ISSUE 12 acceptance: px/bound_accuracy returns a finite
        calibration ratio for every executed script hash."""
        from pixie_tpu.scripts import load_script
        from pixie_tpu.services.telemetry import enable_self_telemetry

        eng = _mk_engine("t_calib", n=3000, mod=7)
        enable_self_telemetry(eng, agent_id="calib-test")
        q1 = AGG_QUERY.format(table="t_calib")
        q2 = (
            "import px\n"
            "df = px.DataFrame(table='t_calib')\n"
            "df = df[df.v > 10]\n"
            "df = df.groupby(['k']).agg(m=('v', px.max))\n"
            "px.display(df)\n"
        )
        import hashlib

        hashes = {
            hashlib.sha256(q.encode()).hexdigest()[:12] for q in (q1, q2)
        }
        eng.execute_query(q1)
        eng.execute_query(q1)
        eng.execute_query(q2)
        out = eng.execute_query(load_script("px/bound_accuracy").pxl)
        rows = out["output"].to_pydict()
        got = dict(zip(rows["script_hash"], rows["calib_mean"]))
        for h in hashes:
            assert h in got, (h, sorted(got))
            assert np.isfinite(got[h]) and got[h] >= 1.0, got[h]

    def test_queries_rows_carry_predicted(self):
        from pixie_tpu.services.telemetry import enable_self_telemetry

        eng = _mk_engine("t_pred_cols")
        enable_self_telemetry(eng)
        eng.execute_query(AGG_QUERY.format(table="t_pred_cols"))
        out = eng.execute_query(
            "import px\n"
            "df = px.DataFrame(table='__queries__')\n"
            "df = df[df.predicted_rows > 0]\n"
            "df = df.groupby(['script_hash']).agg(\n"
            "    pr=('predicted_rows', px.max), ri=('rows_in', px.max))\n"
            "px.display(df)\n"
        )
        rows = out["output"].to_pydict()
        assert rows["pr"] and all(p > 0 for p in rows["pr"])


class TestObservedFloor:
    def test_floor_predicted_semantics(self):
        from pixie_tpu.exec.trace import Tracer
        from pixie_tpu.services.telemetry import ObservedCostIndex

        tracer = Tracer(registry=MetricsRegistry())
        idx = ObservedCostIndex(tracer=tracer)
        tr = tracer.begin_query(script="q-floor")
        tr.usage.bytes_staged = 5000
        tracer.end_query(tr)
        h = tr.script_hash
        assert idx.observed(h)["bytes_staged"] == 5000
        # Unknown prediction -> floored at observed, origin "observed".
        p = idx.floor_predicted(None, h)
        assert p["bytes_staged_hi"] == 5000
        assert p["origin"] == "observed"
        assert p["observed_floor"] == 5000
        # Known-but-low prediction -> raised, origin annotated; the
        # input dict is never mutated (it may be on a trace already).
        src = {"bytes_staged_hi": 10, "origin": "sketch"}
        p = idx.floor_predicted(src, h)
        assert p["bytes_staged_hi"] == 5000
        assert p["origin"] == "sketch+observed"
        assert src["bytes_staged_hi"] == 10
        # At/above observed -> unchanged object.
        src = {"bytes_staged_hi": 9999999}
        assert idx.floor_predicted(src, h) is src
        # No history -> unchanged.
        assert idx.floor_predicted(None, "nohistory") is None

    def test_error_traces_not_indexed(self):
        from pixie_tpu.exec.trace import Tracer
        from pixie_tpu.services.telemetry import ObservedCostIndex

        tracer = Tracer(registry=MetricsRegistry())
        idx = ObservedCostIndex(tracer=tracer)
        tr = tracer.begin_query(script="q-err")
        tr.usage.bytes_staged = 777
        tracer.end_query(tr, status="error", error="boom")
        assert idx.observed(tr.script_hash) is None

    def test_broker_admits_against_observed_floor(self):
        """ISSUE 12 acceptance: sketch-less prediction unknown, script
        hash has observed history -> admitted AGAINST the observed
        floor: a budget below the floor rejects (floor on), admits
        (floor off), and a budget above it admits with the floored
        prediction stamped."""
        from pixie_tpu.services import (
            AgentTracker, KelvinAgent, MessageBus, PEMAgent, QueryBroker,
        )
        from pixie_tpu.services.query_broker import AdmissionError

        bus = MessageBus()
        tracker = AgentTracker(bus, expiry_s=60.0, check_interval_s=60.0)
        pem = PEMAgent(bus, "pem-0", heartbeat_interval_s=30.0).start()
        kelvin = KelvinAgent(
            bus, "kelvin-0", heartbeat_interval_s=30.0
        ).start()
        try:
            # Sketch-less (no ingest sketches -> unknown prediction) and
            # host-staged (no device residency -> bytes_staged > 0
            # observed, so the floor has a real value to work with).
            with override_flag("ingest_sketches", False), \
                    override_flag("device_residency", False):
                n = 3000
                pem.append_data("http_events", {
                    "time_": np.arange(n, dtype=np.int64),
                    "latency_ns": np.arange(n, dtype=np.int64),
                    "resp_status": np.full(n, 200, dtype=np.int64),
                    "service": [f"s-{i % 3}" for i in range(n)],
                })
                pem._register()
                # The table's schema, not any schema: the agents' first
                # registration carries their telemetry tables alone.
                wait_until(lambda: "http_events" in tracker.schemas(),
                           "the PEM's table never reached the tracker")
                broker = QueryBroker(bus, tracker)
                q = (
                    "import px\n"
                    "df = px.DataFrame(table='http_events')\n"
                    "df = df.groupby('service').agg("
                    "n=('latency_ns', px.count))\n"
                    "px.display(df)\n"
                )
                # Run 1 (no budget): establishes the observed history.
                res = broker.execute_script(q, timeout_s=20)
                assert res["tables"]["output"].length == 3
                pred1 = res["predicted_cost"]
                assert (pred1 or {}).get("bytes_staged_hi") in (None, 0) \
                    or pred1.get("origin") == "observed"
                tr1 = broker.tracer.last()
                obs = broker.observed_costs.observed(tr1.script_hash)
                assert obs is not None and obs["bytes_staged"] > 0
                floor = obs["bytes_staged"]
                tiny_mb = floor / 2 / (1 << 20)
                # Budget below the floor: REJECTED (admission accounted
                # the observed bytes, not zero).
                with override_flag("admission_bytes_budget_mb", tiny_mb):
                    with pytest.raises(AdmissionError) as ei:
                        broker.execute_script(q, timeout_s=20)
                assert "observed" in str(ei.value)
                # Same budget with the floor OFF: admitted at zero (the
                # pre-floor behavior the flag guards).
                with override_flag("admission_bytes_budget_mb", tiny_mb), \
                        override_flag("admission_observed_floor", False):
                    res = broker.execute_script(q, timeout_s=20)
                    assert res["tables"]["output"].length == 3
                # Budget above the floor: admitted, floored prediction
                # stamped end to end.
                big_mb = floor * 4 / (1 << 20)
                with override_flag("admission_bytes_budget_mb", big_mb):
                    res = broker.execute_script(q, timeout_s=20)
                assert res["tables"]["output"].length == 3
                assert res["predicted_cost"]["origin"] == "observed"
                assert res["predicted_cost"]["bytes_staged_hi"] >= floor
        finally:
            pem.stop()
            kelvin.stop()
            tracker.close()
            bus.close()


class TestDeviceMemory:
    def test_cpu_snapshot_none_guarded(self):
        mon = DeviceMemoryMonitor(MetricsRegistry())
        snap = mon.snapshot()
        assert isinstance(snap, dict)  # {} on CPU: stats are None
        tok = mon.query_begin()
        assert mon.query_end(tok) >= 0

    def test_collector_renders_without_devices(self):
        reg = MetricsRegistry()
        mon = DeviceMemoryMonitor(reg)
        mon.install_collector()
        out = reg.render()  # must not raise on a stat-less backend
        assert "pixie_collector_errors_total" not in out

    def test_poll_thread_start_stop(self):
        mon = DeviceMemoryMonitor(MetricsRegistry())
        mon.start(poll_s=0.01)
        try:
            tok = mon.query_begin()
            time.sleep(0.05)
            assert mon.query_end(tok) >= 0
        finally:
            mon.stop()
        assert mon._thread is None

    def test_engine_stamps_device_peak(self):
        eng = _mk_engine("t_devpeak")
        eng.execute_query(AGG_QUERY.format(table="t_devpeak"))
        tr = eng.tracer.last()
        # CPU: memory_stats() is None -> 0, never an error.
        assert tr.usage.device_peak_bytes == 0
        assert "device_peak_bytes" in tr.usage.to_dict()

    def test_usage_merge_takes_max_of_peaks(self):
        from pixie_tpu.exec.trace import QueryResourceUsage

        u = QueryResourceUsage(device_peak_bytes=100)
        u.merge({"device_peak_bytes": 500, "bytes_staged": 10})
        u.merge({"device_peak_bytes": 200})
        assert u.device_peak_bytes == 500
        assert u.bytes_staged == 10


class TestLoadTesterHistogram:
    def test_per_run_histogram_quantiles(self):
        from pixie_tpu.services.load_tester import run_load

        eng = _mk_engine("t_load_hist")
        q = AGG_QUERY.format(table="t_load_hist")

        def execute(query, timeout_s):
            return eng.execute_query(query)

        rep = run_load(execute, q, workers=2, per_worker=3)
        d = rep.to_dict()
        assert rep.queries == 6 and rep.errors == 0
        assert d["qps"] > 0
        # The engine tracer observed every query into the default
        # registry's duration histogram; the run's delta is exactly 6.
        assert rep.hist_count == 6
        assert d["hist_p50_ms"] > 0 and d["hist_p99_ms"] >= d["hist_p50_ms"]

    def test_delta_quantiles_none_paths(self):
        from pixie_tpu.services.observability import delta_quantiles

        assert delta_quantiles(None, None) is None
        bounds = (0.1, 1.0)
        before = (bounds, [1, 0, 0], 1, 0.05)
        assert delta_quantiles(before, before) is None  # no new obs
        after = (bounds, [1, 2, 0], 3, 1.0)
        qs = delta_quantiles(before, after)
        assert qs is not None and 0.1 <= qs[0.5] <= 1.0


class TestCliPredObs:
    def _run_debug(self, rows, capsys, argv=()):
        from pixie_tpu import cli

        class StubClient:
            def __enter__(self):
                return self

            def __exit__(self, *a):
                return False

            def debug_queries(self, limit=20):
                return {"queries": rows, "in_flight": []}

        import unittest.mock as mock

        with mock.patch.object(cli, "_client", lambda addr: StubClient()):
            rc = cli.main([
                "debug", "queries", "--broker", "x:1", *argv
            ])
        assert rc == 0
        return capsys.readouterr().out

    def test_pred_obs_column(self, capsys):
        row = {
            "id": "tid0", "qid": "q-ratio", "status": "ok",
            "duration_ms": 5.0, "rows_out": 10,
            "usage": {"bytes_staged": 1000, "device_ms": 1.0,
                      "wire_bytes": 0, "rows_out": 10},
            "predicted": {"bytes_staged_hi": 2000},
            "agent_usage": {},
        }
        out = self._run_debug([row], capsys)
        assert "pred/obs" in out
        assert "2.00" in out  # 2000 predicted / 1000 observed

    def test_pred_obs_blank_when_unknown(self, capsys):
        rows = [
            {  # unknown prediction
                "id": "tid1", "qid": "q-nopred", "status": "ok",
                "duration_ms": 1.0, "rows_out": 1,
                "usage": {"bytes_staged": 500}, "agent_usage": {},
            },
            {  # zero observed staging (device-resident run)
                "id": "tid2", "qid": "q-noobs", "status": "ok",
                "duration_ms": 1.0, "rows_out": 1,
                "usage": {"bytes_staged": 0},
                "predicted": {"bytes_staged_hi": 4096},
                "agent_usage": {},
            },
            {  # observed-floored "prediction": history, not a bound —
                # a <1 ratio here is table growth, never shown as a
                # soundness violation.
                "id": "tid3", "qid": "q-floored", "status": "ok",
                "duration_ms": 1.0, "rows_out": 1,
                "usage": {"bytes_staged": 9000},
                "predicted": {"bytes_staged_hi": 5000,
                              "origin": "observed"},
                "agent_usage": {},
            },
        ]
        out = self._run_debug(rows, capsys)
        for line in out.splitlines():
            if any(q in line for q in ("q-nopred", "q-noobs", "q-floored")):
                cols = line.split()
                assert "-" in cols  # blank ratio marker
                assert "0.56" not in cols  # floored 5000/9000 never shown


class TestMergeTierIdentity:
    """ISSUE 13 satellite: merge fragments rebuild string dictionaries
    from wire payloads, so the fragment cache's old id()-keying missed
    on every distributed query and XLA recompiled the merge/limit
    programs each run (PR 12's ``/debug/programz`` showed one new
    record per repeat). Content-addressed dictionary identity
    (``StringDictionary.content_key``) must make repeats hit: zero new
    program records on a repeated distributed query."""

    def test_content_key_semantics(self):
        from pixie_tpu.types.strings import StringDictionary

        a = StringDictionary(["x", "y"])
        b = StringDictionary(["x", "y"])  # fresh object, equal content
        assert a.content_key() == b.content_key()
        assert a.content_key() == a.content_key()  # stable
        # Order is identity: ids resolve differently.
        c = StringDictionary(["y", "x"])
        assert c.content_key() != a.content_key()
        # Concatenation ambiguity is length-prefixed away.
        d = StringDictionary(["xy"])
        e = StringDictionary(["x", "y"])
        assert d.content_key() != e.content_key()
        # Growth re-keys (cached fragments resolved the old prefix);
        # the incremental hash extends rather than restarts.
        k2 = a.content_key()
        a.get_or_add("z")
        k3 = a.content_key()
        assert k3 != k2
        b.get_or_add("z")
        assert b.content_key() == k3
        # Empty dictionaries agree too.
        assert (StringDictionary().content_key()
                == StringDictionary().content_key())

    def test_repeated_distributed_query_adds_no_programs(self):
        """Acceptance: repeated distributed queries add ZERO new
        merge-tier records to /debug/programz."""
        from pixie_tpu.services import (
            AgentTracker, KelvinAgent, MessageBus, PEMAgent, QueryBroker,
        )

        bus = MessageBus()
        tracker = AgentTracker(bus, expiry_s=60.0, check_interval_s=60.0)
        pems = [
            PEMAgent(bus, f"pem-{i}", heartbeat_interval_s=30.0).start()
            for i in range(2)
        ]
        kelvin = KelvinAgent(
            bus, "kelvin-0", heartbeat_interval_s=30.0
        ).start()
        try:
            n = 4000
            for pem in pems:
                pem.append_data("http_events", {
                    "time_": np.arange(n, dtype=np.int64),
                    "latency_ns": np.arange(n, dtype=np.int64) * 7 % 9973,
                    "resp_status": np.full(n, 200, dtype=np.int64),
                    "service": [f"svc-{i % 3}" for i in range(n)],
                })
                pem._register()
            wait_until(
                lambda: len(tracker.distributed_state().pems_with_table(
                    "http_events")) == len(pems),
                "a PEM's table never reached the tracker",
            )
            broker = QueryBroker(bus, tracker)
            # String group keys force dictionary-bearing bridge payloads
            # through the merge agent — the exact path that recompiled.
            q = (
                "import px\n"
                "df = px.DataFrame(table='http_events')\n"
                "df = df.groupby('service').agg(\n"
                "    n=('latency_ns', px.count),\n"
                "    m=('latency_ns', px.mean))\n"
                "px.display(df, 'out')\n"
            )
            res = broker.execute_script(q, timeout_s=30)  # warm: compiles
            assert res["tables"]["out"].length == 3
            reg = default_program_registry()
            before = {r["program_id"] for r in reg.programz()["programs"]}
            for _ in range(3):
                res = broker.execute_script(q, timeout_s=30)
                assert res["tables"]["out"].length == 3
            after = {r["program_id"] for r in reg.programz()["programs"]}
            assert after == before, (
                f"repeated distributed query registered "
                f"{len(after - before)} new program(s): "
                f"{sorted(after - before)}"
            )
        finally:
            for a in pems + [kelvin]:
                a.stop()
            broker.close()
            tracker.close()
            bus.close()


class TestProfilerSweep:
    def test_single_lock_sweep_counts(self):
        from pixie_tpu.ingest.profiler import PerfProfilerConnector

        c = PerfProfilerConnector()
        c.sample()
        c.sample()
        # Other live threads (pytest workers etc.) may or may not
        # exist; the contract is: no crash, counts merge under the lock
        # and survive to the drain.
        with c._lock:
            total = sum(c._counts.values())
        assert total >= 0

    def test_hashlib_hoisted(self):
        import inspect

        from pixie_tpu.ingest import profiler

        src = inspect.getsource(profiler.PerfProfilerConnector.transfer_data)
        assert "import hashlib" not in src
