"""Repeat-serving tier tests (ISSUE 16): the watermark-validated
result cache, incremental materialized views, and push-down partial
aggregation.

Covers the acceptance matrix:

- cache dispositions (miss/hit/stale/bypass) driven purely by
  event-time watermark comparison — never wall-clock TTL;
- a distributed repeat with unchanged watermarks is a hit with ZERO
  agent dispatches and ZERO new XLA compiles;
- view answers are bit-identical to a full rescan, across group
  rebucketing and ring-expiry churn;
- a PEM-safe union below a partial agg ships merge state over one
  agg_state_merge bridge, shrinking wire bytes >= 10x at equal (within
  sketch tolerance) results;
- agent loss clears the broker cache so a repeat degrades through the
  partial-results machinery instead of serving a stale merged answer;
- exactly one freshness sweep (``table.max_watermark_ns``) per cache
  hit and per streaming poll round.

``run_tests.sh --cache`` runs this file; it is part of ``--tier1``.
"""

from __future__ import annotations

import time

import numpy as np
import pytest

from pixie_tpu.config import override_flag
from pixie_tpu.exec import Engine
from pixie_tpu.exec import result_cache as rc
from pixie_tpu.exec.plan import (
    AggExpr,
    AggOp,
    ColumnRef,
    MemorySourceOp,
    Plan,
    ResultSinkOp,
    UnionOp,
)
from pixie_tpu.exec.result_cache import ResultCache, result_nbytes
from pixie_tpu.planner.distributed.splitter import (
    AGG_STATE_MERGE,
    ROW_GATHER,
    Splitter,
)
from pixie_tpu.services.observability import MetricsRegistry

C = ColumnRef

W = 1 << 10

AGG_Q = (
    "import px\n"
    "df = px.DataFrame(table='t')\n"
    "df = df.groupby('k').agg(n=('v', px.count), s=('v', px.sum))\n"
    "px.display(df)\n"
)

HEAD_Q = (
    "import px\n"
    "df = px.DataFrame(table='t')\n"
    "px.display(df.head(5))\n"
)


def _mk_engine(n=3 * W + 7, keys=11):
    eng = Engine(window_rows=W)
    rng = np.random.default_rng(3)
    eng.append_data("t", {
        "time_": np.arange(n, dtype=np.int64),
        "k": rng.integers(0, keys, n),
        "v": rng.integers(0, 1000, n),
    })
    return eng


def _push(eng, off, n, keys=11, seed=None):
    rng = np.random.default_rng(off if seed is None else seed)
    eng.append_data("t", {
        "time_": np.arange(off, off + n, dtype=np.int64),
        "k": rng.integers(0, keys, n),
        "v": rng.integers(0, 1000, n),
    })


def _pydicts(out):
    return {k: v.to_pydict() for k, v in out.items()}


def _same(a, b) -> bool:
    a, b = _pydicts(a), _pydicts(b)
    if a.keys() != b.keys():
        return False
    for name in a:
        da, db = a[name], b[name]
        if da.keys() != db.keys():
            return False
        for col in da:
            if not np.array_equal(np.asarray(da[col]),
                                  np.asarray(db[col])):
                return False
    return True


# ---------------------------------------------------------------------------
# Local engine: dispositions, key, budget semantics
# ---------------------------------------------------------------------------


class TestLocalDispositions:
    def test_disabled_by_default_no_cache_involvement(self):
        eng = _mk_engine()
        eng.execute_query(AGG_Q)
        assert eng.tracer.last().cache == ""
        eng.execute_query(AGG_Q)
        assert eng.tracer.last().cache == ""
        assert eng.result_cache.cachez()["enabled"] is False
        assert eng.result_cache.cachez()["entries"] == []

    def test_miss_then_hit_same_result(self):
        eng = _mk_engine()
        with override_flag("result_cache_mb", 64):
            first = eng.execute_query(AGG_Q)
            assert eng.tracer.last().cache == rc.MISS
            second = eng.execute_query(AGG_Q)
            assert eng.tracer.last().cache == rc.HIT
        assert _same(first, second)

    def test_watermark_advance_stales_at_zero_budget(self):
        # result_cache_staleness_ms defaults to 0: ANY event-time
        # watermark advance invalidates. The stale repeat re-executes,
        # restores, and the next repeat hits the refreshed entry.
        eng = _mk_engine(n=2000)
        with override_flag("result_cache_mb", 64):
            old = eng.execute_query(AGG_Q)
            _push(eng, 2000, 500)
            fresh = eng.execute_query(AGG_Q)
            assert eng.tracer.last().cache == rc.STALE
            assert not _same(old, fresh)  # the new rows are visible
            again = eng.execute_query(AGG_Q)
            assert eng.tracer.last().cache == rc.HIT
        assert _same(fresh, again)

    def test_budget_allows_bounded_staleness(self):
        # A large staleness budget serves the OLD answer across a small
        # watermark advance — budgeted staleness, re-stamped honestly.
        eng = _mk_engine(n=2000)
        with override_flag("result_cache_mb", 64), \
                override_flag("result_cache_staleness_ms", 1e9):
            old = eng.execute_query(AGG_Q)
            _push(eng, 2000, 500)
            served = eng.execute_query(AGG_Q)
            assert eng.tracer.last().cache == rc.HIT
        assert _same(old, served)

    def test_analyze_and_pxtrace_never_served(self):
        eng = _mk_engine()
        with override_flag("result_cache_mb", 64):
            eng.execute_query(AGG_Q)
            eng.execute_query(AGG_Q)
            assert eng.tracer.last().cache == rc.HIT
            eng.execute_query(AGG_Q, analyze=True)
            assert eng.tracer.last().cache == ""  # executed for real

    def test_key_includes_max_output_rows(self):
        eng = _mk_engine()
        with override_flag("result_cache_mb", 64):
            eng.execute_query(AGG_Q, max_output_rows=10_000)
            eng.execute_query(AGG_Q, max_output_rows=100)
            assert eng.tracer.last().cache == rc.MISS  # separate entry
            eng.execute_query(AGG_Q, max_output_rows=100)
            assert eng.tracer.last().cache == rc.HIT

    def test_key_excludes_now_ns_for_time_free_scripts(self):
        # A dashboard replay passes an advancing now; with no time
        # predicate in the plan the answer cannot depend on it.
        eng = _mk_engine()
        with override_flag("result_cache_mb", 64):
            eng.execute_query(AGG_Q, now_ns=1_000)
            eng.execute_query(AGG_Q, now_ns=2_000_000_000)
            assert eng.tracer.last().cache == rc.HIT

    def test_hit_restamps_freshness_lag(self):
        eng = _mk_engine()
        with override_flag("result_cache_mb", 64):
            eng.execute_query(AGG_Q)
            t0 = eng.tracer.last().usage.freshness_lag_ms
            time.sleep(0.02)
            eng.execute_query(AGG_Q)
            tr = eng.tracer.last()
            assert tr.cache == rc.HIT
            # Event times are synthetic (~epoch), so the lag is huge —
            # what matters is that the hit re-measured it NOW, not that
            # it copied the stored value.
            assert tr.usage.freshness_lag_ms >= t0


# ---------------------------------------------------------------------------
# ResultCache unit behavior: LRU budget, regression drop, metrics
# ---------------------------------------------------------------------------


def _plan_for(table="t"):
    p = Plan()
    src = p.add(MemorySourceOp(table=table))
    p.add(ResultSinkOp("output"), [src])
    return p


class TestResultCacheUnit:
    def test_lru_evicts_oldest_within_byte_budget(self):
        cache = ResultCache(registry=MetricsRegistry())
        big = {"output": b"x" * 600_000}
        with override_flag("result_cache_mb", 1):
            cache.store("script-a", 1, 10_000, _plan_for(), big, lambda t: 1)
            cache.store("script-b", 1, 10_000, _plan_for(), big, lambda t: 1)
            sa, _, _ = cache.lookup("script-a", 1, 10_000, lambda t: 1)
            sb, eb, _ = cache.lookup("script-b", 1, 10_000, lambda t: 1)
            z = cache.cachez()
        assert sa == rc.MISS  # evicted: 2 x 600KB > 1MB
        assert sb == rc.HIT and eb.result is big
        assert z["bytes"] <= z["budget_bytes"]
        assert [e["script_hash"] for e in z["entries"]] == [
            rc.script_sha("script-b")[:12]
        ]

    def test_oversized_result_never_stored(self):
        cache = ResultCache(registry=MetricsRegistry())
        with override_flag("result_cache_mb", 1):
            cache.store("big", 1, 10_000, _plan_for(),
                        {"output": b"x" * (2 << 20)}, lambda t: 1)
        assert cache.cachez()["entries"] == []

    def test_watermark_regression_drops_entry(self):
        # Expiry churn / agent loss can REGRESS the observed watermark:
        # the cached answer may cover rows that no longer exist, so the
        # entry must drop (miss), not serve.
        cache = ResultCache(registry=MetricsRegistry())
        with override_flag("result_cache_mb", 64):
            cache.store("s", 1, 10_000, _plan_for(),
                        {"output": b"y"}, lambda t: 100)
            status, _, _ = cache.lookup("s", 1, 10_000, lambda t: 50)
            assert status == rc.MISS
            assert cache.cachez()["entries"] == []

    def test_bypass_when_no_watermark(self):
        cache = ResultCache(registry=MetricsRegistry())
        with override_flag("result_cache_mb", 64):
            got = cache.store("s", 1, 10_000, _plan_for(),
                              {"output": b"y"}, lambda t: None)
        assert got == rc.BYPASS
        assert cache.cachez()["entries"] == []

    def test_metrics_counters_and_bytes_gauge(self):
        reg = MetricsRegistry()
        cache = ResultCache(registry=reg)
        with override_flag("result_cache_mb", 64):
            cache.lookup("s", 1, 10_000, lambda t: 1)          # miss
            cache.store("s", 1, 10_000, _plan_for(),
                        {"output": b"y" * 100}, lambda t: 1)
            cache.lookup("s", 1, 10_000, lambda t: 1)          # hit
            cache.lookup("s", 1, 10_000, lambda t: 10**12)     # stale
        assert reg.counter("pixie_result_cache_misses_total").value() == 1
        assert reg.counter("pixie_result_cache_hits_total").value() == 1
        assert reg.counter("pixie_result_cache_stale_total").value() == 1
        assert reg.gauge("pixie_result_cache_bytes").value() > 0
        cache.clear()
        assert reg.gauge("pixie_result_cache_bytes").value() == 0

    def test_result_nbytes_counts_batches(self):
        assert result_nbytes({"a": b"xx", "b": "yyy"}) >= 5
        assert result_nbytes(np.zeros(100, np.int64)) == 800


# ---------------------------------------------------------------------------
# Materialized views: bit-identity across appends, rebucket, expiry
# ---------------------------------------------------------------------------


class TestMaterializedViews:
    def test_auto_registration_after_min_runs(self):
        eng = _mk_engine()
        with override_flag("view_auto_min_runs", 2):
            plain = eng.execute_query(AGG_Q)
            assert eng.tracer.last().cache == ""  # run 1: below threshold
            served = eng.execute_query(AGG_Q)
            assert eng.tracer.last().cache == rc.VIEW
        assert _same(plain, served)
        eng.views.close()

    def test_view_fold_bit_identical_to_rescan_after_appends(self):
        eng = _mk_engine(n=3000)
        with override_flag("view_auto_min_runs", 1):
            eng.execute_query(AGG_Q)  # registers + full first fold
            _push(eng, 3000, 1500)
            _push(eng, 4500, 700)
            view_out = eng.execute_query(AGG_Q)
            assert eng.tracer.last().cache == rc.VIEW
        eng.views.close()
        rescan = eng.execute_query(AGG_Q)  # flags off: the plain path
        assert eng.tracer.last().cache == ""
        assert _same(view_out, rescan)

    def test_view_survives_group_rebucket(self):
        # Register over a low-cardinality prefix, then flood new keys:
        # the state overflows, rebuckets at doubled capacity, refolds —
        # and the next answer still matches a from-scratch rescan.
        eng = _mk_engine(n=2000, keys=3)
        with override_flag("view_auto_min_runs", 1):
            eng.execute_query(AGG_Q)
            _push(eng, 2000, 2000, keys=301)
            view_out = eng.execute_query(AGG_Q)
            assert eng.tracer.last().cache == rc.VIEW
        eng.views.close()
        rescan = eng.execute_query(AGG_Q)
        assert _same(view_out, rescan)
        d = view_out["output"].to_pydict()
        assert len(d["k"]) > 100  # the flood actually widened the state

    def test_view_survives_ring_expiry_churn(self):
        # A byte-capped ring expires the oldest batches as new ones
        # land; the view must refold from the LIVE rows, never keep
        # counting rows a rescan would no longer see.
        eng = Engine(window_rows=W)
        row_bytes = 3 * 8
        eng.create_table("t", max_bytes=2000 * row_bytes)
        _push(eng, 0, 1500)
        with override_flag("view_auto_min_runs", 1):
            eng.execute_query(AGG_Q)
            for off in range(1500, 6000, 1500):
                _push(eng, off, 1500)  # expires earlier batches
            view_out = eng.execute_query(AGG_Q)
            assert eng.tracer.last().cache == rc.VIEW
        eng.views.close()
        rescan = eng.execute_query(AGG_Q)
        assert _same(view_out, rescan)
        t = eng.tables["t"]
        assert t.num_rows < 6000  # churn really happened

    def test_non_streamable_script_falls_back_to_execution(self):
        # head() has no finalize-over-state; registration fails once,
        # is remembered, and every repeat executes normally.
        eng = _mk_engine()
        with override_flag("view_auto_min_runs", 1):
            for _ in range(2):
                out = eng.execute_query(HEAD_Q)
                assert eng.tracer.last().cache == ""
                assert len(out["output"].to_pydict()["v"]) == 5
            assert eng.views.viewz() == []

    def test_manifest_views_inert_without_serving_tier(self):
        # materialize: true in a bundled manifest is a HINT — with
        # result_cache_mb=0 and no auto-registration the all-defaults
        # path must stay the plain execute path.
        from pixie_tpu.exec.views import view_candidates_enabled

        assert not view_candidates_enabled(AGG_Q)
        with override_flag("view_auto_min_runs", 1):
            assert view_candidates_enabled(AGG_Q)


# ---------------------------------------------------------------------------
# Freshness sweep dedup: one max_watermark_ns call per hit / per poll
# ---------------------------------------------------------------------------


class _SweepCounter:
    """Counts max_watermark_ns sweeps over ONE engine's tablets. The
    wrap is module-global, but scoping by tablet identity keeps the
    count immune to sweeps from unrelated engines — in the full tier-1
    sweep, agent heartbeat threads leaked by earlier test files ship
    per-table freshness through this same helper."""

    def __init__(self, monkeypatch, eng):
        from pixie_tpu.table_store import table as table_mod

        self.calls = 0
        mine = {id(t) for t in eng.table_store.tablets("t")}
        real = table_mod.max_watermark_ns

        def counting(tablets):
            tablets = list(tablets)
            if any(id(t) in mine for t in tablets):
                self.calls += 1
            return real(tablets)

        monkeypatch.setattr(table_mod, "max_watermark_ns", counting)


class TestFreshnessSweepDedup:
    def test_cache_hit_is_one_sweep(self, monkeypatch):
        eng = _mk_engine()
        with override_flag("result_cache_mb", 64):
            eng.execute_query(AGG_Q)  # miss: lookup/store/scan sweeps
            sweeps = _SweepCounter(monkeypatch, eng)
            eng.execute_query(AGG_Q)
            assert eng.tracer.last().cache == rc.HIT
        # THE hit contract: validity is one watermark read per scanned
        # table — no compile, no scan, no second sweep at store time.
        assert sweeps.calls == 1

    def test_streaming_poll_is_one_sweep(self, monkeypatch):
        from pixie_tpu.exec.streaming import stream_query

        eng = _mk_engine(n=2000)
        ups = []
        sq = stream_query(eng, AGG_Q, emit=ups.append)
        sweeps = _SweepCounter(monkeypatch, eng)
        sq.poll()
        assert sweeps.calls == 1
        _push(eng, 2000, 500)
        sq.poll()  # a folding round sweeps once too, not per window
        assert sweeps.calls == 2
        sq.close()

    def test_rebucket_retry_does_not_resweep(self, monkeypatch):
        from pixie_tpu.exec.streaming import stream_query

        eng = _mk_engine(n=2000, keys=3)
        ups = []
        sq = stream_query(eng, AGG_Q, emit=ups.append)
        sq.poll()
        _push(eng, 2000, 2000, keys=301)  # forces overflow -> rebucket
        sweeps = _SweepCounter(monkeypatch, eng)
        sq.poll()
        assert sweeps.calls == 1  # the rebucket retry re-enters the
        sq.close()                # fold, not the sweep


# ---------------------------------------------------------------------------
# Push-down partial aggregation: splitter shape, wire shrink, equivalence
# ---------------------------------------------------------------------------


def _union_agg_plan(aggs=None, max_groups=4096):
    p = Plan()
    s1 = p.add(MemorySourceOp(table="t1"))
    s2 = p.add(MemorySourceOp(table="t2"))
    u = p.add(UnionOp(), [s1, s2])
    agg = p.add(
        AggOp(
            group_cols=("k",),
            aggs=aggs or (AggExpr("n", "count", (C("v"),)),),
            max_groups=max_groups,
        ),
        [u],
    )
    p.add(ResultSinkOp("output"), [agg])
    return p


SKETCH_AGGS = (
    AggExpr("n", "count", (C("v"),)),
    AggExpr("s", "sum", (C("v"),)),
    AggExpr("m", "mean", (C("v"),)),
    AggExpr("nd", "count_distinct", (C("u"),)),
    AggExpr("p50", "_quantile_p50", (C("lat"),)),
)


def _sketch_engine(n, seed):
    rng = np.random.default_rng(seed)
    eng = Engine(window_rows=W)
    for table in ("t1", "t2"):
        eng.append_data(table, {
            "time_": np.arange(n, dtype=np.int64),
            "k": rng.integers(0, 4, n),
            "v": rng.integers(0, 1000, n),
            "u": rng.integers(0, 5000, n),
            "lat": rng.gamma(2.0, 50.0, n),
        })
    return eng


class TestPushdownSplit:
    def test_union_stays_on_data_tier_below_partial_agg(self):
        split = Splitter().split(_union_agg_plan())
        before = [type(n.op).__name__
                  for n in split.before_blocking.nodes.values()]
        assert "UnionOp" in before and "AggOp" in before
        assert [b.kind for b in split.bridges] == [AGG_STATE_MERGE]
        pem_agg = next(n.op for n in split.before_blocking.nodes.values()
                       if isinstance(n.op, AggOp))
        assert pem_agg.mode == "partial"

    def test_flag_off_falls_back_to_row_gather(self):
        with override_flag("pushdown_union_agg", False):
            split = Splitter().split(_union_agg_plan())
        before = [type(n.op).__name__
                  for n in split.before_blocking.nodes.values()]
        assert "UnionOp" not in before
        assert [b.kind for b in split.bridges] == [ROW_GATHER, ROW_GATHER]

    def test_union_without_agg_not_pushed(self):
        p = Plan()
        s1 = p.add(MemorySourceOp(table="t1"))
        s2 = p.add(MemorySourceOp(table="t2"))
        u = p.add(UnionOp(), [s1, s2])
        p.add(ResultSinkOp("output"), [u])
        split = Splitter().split(p)
        assert all(b.kind == ROW_GATHER for b in split.bridges)

    def test_planner_verifies_pushdown_plan(self):
        from pixie_tpu.planner.distributed import (
            DistributedPlanner,
            DistributedState,
        )
        from pixie_tpu.udf.registry import default_registry

        dstate = DistributedState.homogeneous(2, 1)
        dplan = DistributedPlanner(default_registry()).plan(
            _union_agg_plan(SKETCH_AGGS), dstate
        )
        assert any(b.kind == AGG_STATE_MERGE for b in dplan.split.bridges)


class TestPushdownExecution:
    N = 6000  # per table per agent: state stays constant, rows scale

    def _merge(self, split, engines):
        payloads: dict = {}
        for e in engines:
            res = e.execute_plan(split.before_blocking)
            for key, p in res.items():
                if isinstance(key, tuple) and key[0] == "bridge":
                    payloads.setdefault(key[1], []).append(p)
        merge = Engine(window_rows=W)
        out = merge.execute_plan(
            split.after_blocking, bridge_inputs=payloads
        )
        return out, payloads

    def test_equivalence_and_wire_shrink(self):
        from pixie_tpu.exec.bridge import payload_nbytes

        engines = [_sketch_engine(self.N, seed) for seed in (1, 2)]
        # The compiled path sizes agg state from the ingest NDV sketch
        # (4 distinct keys here); mirror that so the shipped state is
        # proportional to GROUPS, not the 4096-group default padding.
        plan = _union_agg_plan(SKETCH_AGGS, max_groups=8)
        split_on = Splitter().split(plan)
        out_on, pay_on = self._merge(split_on, engines)
        with override_flag("pushdown_union_agg", False):
            split_off = Splitter().split(plan)
            out_off, pay_off = self._merge(split_off, engines)

        wire_on = sum(payload_nbytes(p)
                      for ps in pay_on.values() for p in ps)
        wire_off = sum(payload_nbytes(p)
                       for ps in pay_off.values() for p in ps)
        assert wire_off / wire_on >= 10.0, (wire_on, wire_off)

        a = out_on["output"].to_pydict()
        b = out_off["output"].to_pydict()
        oa, ob = np.argsort(a["k"]), np.argsort(b["k"])
        # Keys, counts and HLL registers merge order-insensitively ->
        # exact; float folds and t-digest merges reorder -> tolerance.
        assert np.array_equal(np.asarray(a["k"])[oa],
                              np.asarray(b["k"])[ob])
        assert np.array_equal(np.asarray(a["n"])[oa],
                              np.asarray(b["n"])[ob])
        assert np.array_equal(np.asarray(a["nd"])[oa],
                              np.asarray(b["nd"])[ob])
        np.testing.assert_allclose(np.asarray(a["s"])[oa],
                                   np.asarray(b["s"])[ob], rtol=1e-5)
        np.testing.assert_allclose(np.asarray(a["m"])[oa],
                                   np.asarray(b["m"])[ob], rtol=1e-5)
        np.testing.assert_allclose(np.asarray(a["p50"])[oa],
                                   np.asarray(b["p50"])[ob],
                                   rtol=0.05, atol=0.05)

    def test_pushdown_counts_match_numpy_truth(self):
        engines = [_sketch_engine(self.N, seed) for seed in (3, 4)]
        split = Splitter().split(_union_agg_plan())
        out, _ = self._merge(split, engines)
        d = out["output"].to_pydict()
        assert int(np.sum(d["n"])) == 4 * self.N  # 2 tables x 2 agents


# ---------------------------------------------------------------------------
# Distributed: zero-dispatch hits, agent-loss degradation
# ---------------------------------------------------------------------------


DIST_Q = (
    "import px\n"
    "df = px.DataFrame(table='http_events')\n"
    "df = df.groupby('service').agg(n=('latency_ns', px.count))\n"
    "px.display(df, 'out')\n"
)


@pytest.fixture
def cluster():
    from pixie_tpu.services import (
        AgentTracker,
        KelvinAgent,
        MessageBus,
        PEMAgent,
        QueryBroker,
    )

    bus = MessageBus()
    tracker = AgentTracker(
        bus, expiry_s=60.0, check_interval_s=60.0,
        flap_threshold=10, flap_window_s=60.0, quarantine_s=60.0,
    )
    fast = dict(heartbeat_interval_s=0.05)
    pems = [PEMAgent(bus, f"pem-{i}", **fast).start() for i in range(2)]
    kelvin = KelvinAgent(bus, "kelvin-0", **fast).start()
    rng = np.random.default_rng(0)
    for i, pem in enumerate(pems):
        n = 400 + 100 * i
        pem.append_data("http_events", {
            "time_": np.arange(n, dtype=np.int64),
            "latency_ns": rng.integers(1000, 1_000_000, n),
            "service": [f"svc-{(i + j) % 3}" for j in range(n)],
        })
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


class TestDistributedCache:
    def test_repeat_is_hit_with_zero_dispatch_zero_compile(self, cluster):
        from pixie_tpu.exec.programs import default_program_registry

        bus, tracker, pems, kelvin, broker = cluster
        dispatches = []
        for a in pems + [kelvin]:
            for kind in ("execute", "merge"):
                bus.subscribe(f"agent.{a.agent_id}.{kind}",
                              dispatches.append)
        with override_flag("result_cache_mb", 64):
            first = broker.execute_script(DIST_Q)
            assert first["cache"] == rc.MISS
            assert dispatches  # the miss really dispatched
            dispatches.clear()
            programz = default_program_registry().programz()
            before = (programz["count"], programz["compiles"])
            second = broker.execute_script(DIST_Q)
            assert second["cache"] == rc.HIT
            programz = default_program_registry().programz()
            after = (programz["count"], programz["compiles"])
        assert dispatches == []  # ZERO agent traffic on a hit
        assert after == before   # ZERO new XLA programs/compiles
        assert _same(first["tables"], second["tables"])
        assert second["freshness_lag_ms"] >= 0

    def test_trace_and_queryz_carry_disposition(self, cluster):
        bus, tracker, pems, kelvin, broker = cluster
        with override_flag("result_cache_mb", 64):
            broker.execute_script(DIST_Q)
            broker.execute_script(DIST_Q)
        recent = broker.tracer.recent()  # most recent first
        assert [t.get("cache") for t in recent[:2]] == [rc.HIT, rc.MISS]

    def test_agent_loss_clears_cache_and_degrades(self, cluster):
        bus, tracker, pems, kelvin, broker = cluster
        with override_flag("result_cache_mb", 64):
            first = broker.execute_script(DIST_Q)
            assert first["cache"] == rc.MISS
            pems[1].stop()
            tracker.force_expire("pem-1")
            deadline = time.time() + 5
            while (time.time() < deadline
                   and broker.result_cache.cachez()["entries"]):
                time.sleep(0.01)
            assert broker.result_cache.cachez()["entries"] == []
            second = broker.execute_script(
                DIST_Q, require_complete=False
            )
            # Not served from cache: the repeat re-executed against the
            # survivors and says so (partial-results machinery).
            assert second["cache"] != rc.HIT
        n_first = int(np.sum(first["tables"]["out"].to_pydict()["n"]))
        n_second = int(np.sum(second["tables"]["out"].to_pydict()["n"]))
        assert n_second < n_first  # pem-1's shard really fell out
