"""Concurrent query serving on ONE engine (pxlock's certified unlock).

Engine._exec_guard no longer serializes whole queries: per-query
execution state lives on a thread-local ``_QueryScratch``, so
independent queries overlap (ISSUE 15 / ROADMAP "concurrent-query
serving"). These tests are the certification:

- two concurrent small queries demonstrably overlap (wall < 2x solo,
  asserted against a staging-latency phase — on this 1-core CI box
  pure compute cannot beat 2x no matter how the locks behave, so the
  test models the device staging latency that IS the overlap
  opportunity in production, with the same ``_staged_windows`` wrap the
  tenancy suite uses);
- results stay bit-identical to serial execution;
- per-query state (stats spine, cancel handle, join decision, table
  sinks) never leaks across overlapping queries;
- the load tester's ``--concurrency`` axis reports qps/p99 per client
  count.

Runs under lockdep in ``./run_tests.sh --locks``.
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from pixie_tpu.exec.engine import Engine
from pixie_tpu.exec.stream import QueryCancelled

ROWS = 600_000

AGG_Q = (
    "import px\n"
    "df = px.DataFrame(table='t')\n"
    "df = df.groupby('k').agg(n=('v', px.count), m=('v', px.mean))\n"
    "px.display(df, 'o')\n"
)
AGG_Q2 = (
    "import px\n"
    "df = px.DataFrame(table='t2')\n"
    "df = df.groupby('g').agg(lo=('w', px.min), hi=('w', px.max))\n"
    "px.display(df, 'o2')\n"
)


def _mk_engine(window_rows: int = 1 << 17) -> Engine:
    rng = np.random.default_rng(7)
    eng = Engine(window_rows=window_rows)
    eng.append_data("t", {
        "time_": np.arange(ROWS, dtype=np.int64),
        "v": rng.integers(0, 1_000_000, ROWS),
        "k": rng.integers(0, 512, ROWS),
    })
    eng.append_data("t2", {
        "time_": np.arange(ROWS // 2, dtype=np.int64),
        "w": rng.integers(0, 1_000_000, ROWS // 2),
        "g": rng.integers(0, 64, ROWS // 2),
    })
    return eng


def _batches_equal(a, b) -> bool:
    da, db = a.to_pydict(), b.to_pydict()
    if list(da) != list(db):
        return False
    return all(np.array_equal(da[c], db[c]) for c in da)


@pytest.fixture(scope="module")
def engine():
    return _mk_engine()


class TestOverlap:
    def test_two_queries_overlap_wall_under_2x_solo(self, engine):
        """The acceptance gate: two concurrent small queries overlap on
        one engine — wall-clock < 2x solo — with bit-identical results
        vs serial. Each window pays a simulated staging latency (the
        device phase; pure sleep, no lock held), so under
        the old whole-query ``_exec_guard`` serialization this wall
        would be ~2.0x solo regardless of core count, while overlapped
        staging lands near 1x."""
        eng = engine
        orig = eng._staged_windows

        def slow(stream, stats=None):
            for w in orig(stream, stats):
                time.sleep(0.02)
                yield w

        eng._staged_windows = slow
        results = {}

        def run(key):
            t0 = time.perf_counter()
            res = eng.execute_query(AGG_Q)
            results[key] = (time.perf_counter() - t0, res)

        try:
            run("warm")  # compile once; measured runs reuse the program
            solos = []
            for i in range(3):
                run(f"solo{i}")
                solos.append(results[f"solo{i}"][0])
            solo = sorted(solos)[1]  # median
            eng.max_inflight = 0
            t0 = time.perf_counter()
            threads = [
                threading.Thread(target=run, args=(f"conc{i}",))
                for i in range(2)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
        finally:
            eng._staged_windows = orig
        assert eng.max_inflight == 2, "queries never overlapped"
        # The acceptance bound is < 2x; assert with margin (observed
        # ~1.05x here) so a real re-serialization regression — which
        # lands at 2.0x — can never pass on noise.
        assert wall < 1.7 * solo, (
            f"no overlap: two concurrent queries took {wall * 1e3:.0f}ms "
            f"vs solo {solo * 1e3:.0f}ms (>= 1.7x)"
        )
        # Bit-identical: both concurrent results match the solo run.
        for key in ("conc0", "conc1"):
            assert _batches_equal(
                results[key][1]["o"], results["solo0"][1]["o"]
            ), f"{key} diverged from serial execution"

    def test_concurrent_mixed_queries_bit_identical(self, engine):
        """Different queries overlapping on one engine (no simulated
        latency: the pure-compute path) return exactly what serial
        execution returns, across repeats."""
        eng = engine
        serial = {
            "a": eng.execute_query(AGG_Q)["o"],
            "b": eng.execute_query(AGG_Q2)["o2"],
        }
        out: dict = {}
        errs: list = []

        def run(key, q, name):
            try:
                out[key] = eng.execute_query(q)[name]
            except Exception as e:  # noqa: BLE001 - recorded for assert
                errs.append((key, e))

        threads = []
        for rep in range(3):
            threads.extend([
                threading.Thread(
                    target=run, args=(f"a{rep}", AGG_Q, "o")
                ),
                threading.Thread(
                    target=run, args=(f"b{rep}", AGG_Q2, "o2")
                ),
            ])
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs, errs
        for rep in range(3):
            assert _batches_equal(out[f"a{rep}"], serial["a"])
            assert _batches_equal(out[f"b{rep}"], serial["b"])


class TestScratchIsolation:
    def test_per_query_stats_do_not_cross(self, engine):
        """Each overlapping query's trace accounts ITS OWN rows_in —
        the stats spine is scratch state, not engine state (under the
        old engine-attribute scheme, overlap would corrupt this)."""
        eng = engine
        barrier = threading.Barrier(2, timeout=10.0)
        orig = eng._staged_windows

        def synced(stream, stats=None):
            # Both queries inside execution at once before any windows
            # flow — guarantees true overlap for the assertion below.
            try:
                barrier.wait()
            except threading.BrokenBarrierError:
                pass
            yield from orig(stream, stats)

        eng._staged_windows = synced
        try:
            threads = [
                threading.Thread(
                    target=eng.execute_query, args=(AGG_Q,)
                ),
                threading.Thread(
                    target=eng.execute_query, args=(AGG_Q2,)
                ),
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        finally:
            eng._staged_windows = orig
        by_rows = sorted(
            t["usage"]["rows_in"] for t in eng.tracer.recent()[:2]
        )
        assert by_rows == [ROWS // 2, ROWS], (
            f"overlapping queries cross-contaminated their stats: "
            f"{by_rows}"
        )

    def test_cancel_is_per_query(self, engine):
        """Cancelling one in-flight query must not touch its concurrent
        neighbor (the cancel handle is scratch, not an engine attr)."""
        eng = engine
        cancel = threading.Event()
        started = threading.Event()
        orig = eng._staged_windows

        def slow(stream, stats=None):
            for w in orig(stream, stats):
                started.set()
                time.sleep(0.01)
                yield w

        eng._staged_windows = slow
        out: dict = {}

        def run_cancelled():
            from pixie_tpu.planner import CompilerState, compile_pxl

            state = CompilerState(
                schemas={
                    n: t.relation for n, t in eng.tables.items()
                },
                registry=eng.registry,
            )
            plan = compile_pxl(AGG_Q, state).plan
            try:
                eng.execute_plan(plan, cancel=cancel)
                out["cancelled"] = "completed"
            except QueryCancelled:
                out["cancelled"] = "cancelled"

        def run_free():
            try:
                out["free"] = eng.execute_query(AGG_Q2)["o2"]
            except Exception as e:  # noqa: BLE001 - recorded for assert
                out["free"] = e

        try:
            t1 = threading.Thread(target=run_cancelled)
            t2 = threading.Thread(target=run_free)
            t1.start()
            assert started.wait(10.0)
            t2.start()
            cancel.set()
            t1.join(15.0)
            t2.join(15.0)
        finally:
            eng._staged_windows = orig
        assert out["cancelled"] == "cancelled"
        assert not isinstance(out["free"], Exception), out["free"]
        assert _batches_equal(
            out["free"], eng.execute_query(AGG_Q2)["o2"]
        )

    def test_table_sinks_are_per_query(self):
        """Two concurrent TableSinkOp queries each record their own
        sink rows on their scratch (engine-level last_table_sinks is a
        last-finished snapshot, not the correctness surface)."""
        eng = _mk_engine(window_rows=1 << 16)
        barrier = threading.Barrier(2, timeout=10.0)
        orig = eng._staged_windows

        def synced(stream, stats=None):
            try:
                barrier.wait()
            except threading.BrokenBarrierError:
                pass
            yield from orig(stream, stats)

        eng._staged_windows = synced

        def run(key, q):
            eng.execute_query(q)

        qa = (
            "import px\n"
            "df = px.DataFrame(table='t')\n"
            "df = df.groupby('k').agg(n=('v', px.count))\n"
            "px.display(df, 'oa')\n"
            "px.to_table(df, 'sink_a')\n"
        )
        qb = (
            "import px\n"
            "df = px.DataFrame(table='t2')\n"
            "df = df.groupby('g').agg(n=('w', px.count))\n"
            "px.display(df, 'ob')\n"
            "px.to_table(df, 'sink_b')\n"
        )
        try:
            threads = [
                threading.Thread(target=run, args=("a", qa)),
                threading.Thread(target=run, args=("b", qb)),
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        finally:
            eng._staged_windows = orig
        # Each query stored to ITS table with the right row count; the
        # cross-query check is on the STORED DATA (authoritative).
        assert eng.tables["sink_a"].num_rows == 512
        assert eng.tables["sink_b"].num_rows == 64


class TestFragmentCacheRace:
    def test_concurrent_misses_agree_and_eviction_never_throws(self):
        """Regression (pxlock lock audit): the fragment cache's
        insert/evict path is now locked — two concurrent queries
        evicting the same oldest key used to KeyError, and duplicate
        misses must adopt ONE canonical fragment (downstream step
        caches key on id())."""
        from pixie_tpu.exec import fragment as frag_mod
        from pixie_tpu.exec.plan import MapOp
        from pixie_tpu.types.relation import Relation
        from pixie_tpu.udf.registry import default_registry
        from pixie_tpu.exec.expr import ColumnRef

        rel = Relation([("v", "INT64")])
        reg = default_registry()
        old_max = frag_mod._FRAGMENT_CACHE_MAX
        frag_mod._FRAGMENT_CACHE_MAX = 4  # force constant eviction
        errs: list = []
        frags: dict = {}

        def worker(wid):
            try:
                for i in range(12):
                    ops = (
                        MapOp(exprs=(
                            (f"c{i % 6}", ColumnRef("v")),
                        )),
                    )
                    f = frag_mod.compile_fragment_cached(
                        list(ops), rel, {}, reg
                    )
                    frags[(wid, i % 6)] = f
            except Exception as e:  # noqa: BLE001 - recorded for assert
                errs.append(e)

        try:
            threads = [
                threading.Thread(target=worker, args=(w,))
                for w in range(4)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        finally:
            frag_mod._FRAGMENT_CACHE_MAX = old_max
        assert not errs, errs


class TestLoadTesterConcurrency:
    def test_concurrency_sweep_reports_qps_p99(self):
        from pixie_tpu.services.load_tester import (
            local_executor, run_concurrency_sweep,
        )

        execute = local_executor(rows=50_000, window_rows=1 << 14)
        reports = run_concurrency_sweep(
            execute, AGG_Q.replace("table='t'", "table='http_events'")
            .replace("'k'", "'service'").replace("'v'", "'latency_ns'"),
            concurrencies=(1, 2), per_worker=3,
        )
        assert sorted(reports) == [1, 2]
        for n, rep in reports.items():
            d = rep.to_dict()
            assert rep.errors == 0, d
            assert d["qps"] > 0
            for k in ("p50_ms", "p95_ms", "p99_ms"):
                assert d[k] > 0
            # The serving-process histogram delta backs the report:
            # exactly this run's n * per_worker observations.
            assert d.get("hist_count", 0) == n * 3
        assert execute.engine.max_inflight >= 2
