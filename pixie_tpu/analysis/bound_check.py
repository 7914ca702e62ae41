"""pxbound soundness gate (``run_tests.sh --bounds``; runs in
``--analyze``/``--tier1``).

The resource-bound pass (``analysis/bounds.py``) is load-bearing — the
broker's admission control rejects queries on its predictions — so it
must be FALSIFIABLE, not advisory. This gate replays every shape of
``bench_check.SHAPE_SCHEMAS`` (over synthetic ingest pushed through
the real table-store append path so the sketches exist) plus
the bundled self-monitoring scripts, and asserts for each query that
the OBSERVED ``QueryResourceUsage`` (PR 7 telemetry: the trace's
``bytes_staged``/``rows_in``/``rows_out``) stays <= the PREDICTED
bound (which already includes the ``BOUNDS_SAFETY`` factor). It then
proves the rejection half of the contract: an intentionally
over-budget query fails AT COMPILE with a structured ``resource-bound``
``Diagnostic`` — never an OOM or a silent truncation at run time.

Also reports pass overhead relative to compile time: like the plan
verifier, pxbound rides inside the ``compile`` span and is budgeted at
<5% of it.
"""

from __future__ import annotations

import sys
import time

import numpy as np

from .bench_check import SHAPE_SCHEMAS, _shape_query

#: Rows appended per table in the replay (small: the gate checks
#: bound SOUNDNESS, not throughput).
GATE_ROWS = 4096

#: (observed usage key, predicted cost key) pairs the gate asserts on.
CHECKS = (
    ("bytes_staged", "bytes_staged_hi"),
    ("rows_in", "rows_in_hi"),
    ("rows_out", "rows_out_hi"),
)


def _synth_column(dtype, n: int, rng, col: str):
    from ..types.dtypes import DataType

    if dtype == DataType.TIME64NS:
        t0 = time.time_ns() - n * 1_000_000
        return t0 + np.arange(n, dtype=np.int64) * 1_000_000
    if dtype == DataType.INT64:
        if col == "resp_status":
            return rng.choice(np.array([200, 200, 404, 500]), n)
        return rng.integers(0, 1_000, n).astype(np.int64)
    if dtype == DataType.FLOAT64:
        return rng.random(n)
    if dtype == DataType.BOOLEAN:
        return rng.integers(0, 2, n).astype(bool)
    # STRING: a small vocabulary (realistic NDV), named by the column's
    # last word so that px/net_flow_graph's join of ``remote_addr`` to
    # ``src_addr`` matches.
    kind = col.rsplit("_", 1)[-1]
    vocab = [f"{kind}-{i}" for i in range(16)]
    return [vocab[int(i)] for i in rng.integers(0, len(vocab), n)]


def _replay_engine(schemas, rows: int = GATE_ROWS, tiered: bool = False):
    """A fresh Engine with ``rows`` synthetic rows per table pushed
    through the REAL append path (so ingest sketches exist and pxbound
    sees what production would). ``tiered=True`` replays onto
    byte-bounded tables with the cold tier on (docs/STORAGE.md) so
    most windows demote — the cold-heavy regime the decode bound is
    stated against."""
    import contextlib

    from ..config import override_flag
    from ..exec.engine import Engine
    from .bounds import _row_bytes

    win = 256
    ctx = (
        override_flag("cold_tier_mb", 64)
        if tiered else contextlib.nullcontext()
    )
    with ctx:
        engine = Engine(window_rows=win) if tiered else Engine()
        rng = np.random.default_rng(7)
        for table, rel in schemas.items():
            data = {
                name: _synth_column(dt, rows, rng, name)
                for name, dt in rel.items()
            }
            if not tiered:
                engine.append_data(table, data)
                continue
            # Hot budget of ~1/4 the replay: ~3/4 of windows end cold.
            engine.create_table(
                table, relation=rel,
                max_bytes=max((_row_bytes(rel) or 32) * rows // 4, win),
            )
            for lo in range(0, rows, win):
                engine.append_data(table, {
                    c: v[lo:lo + win] for c, v in data.items()
                })
    return engine


def _check_one(name, engine, query, verbose) -> tuple[int, float, float]:
    """Run one query; compare observed usage vs the predicted report.
    Returns (failures, compile_s, bounds_s)."""
    from ..planner import CompilerState, compile_pxl
    from .bounds import plan_bounds

    t0 = time.perf_counter()
    engine.execute_query(query)
    report = engine.last_resource_report
    trace = engine.tracer.recent()[0]
    observed = trace["usage"]
    failures = 0
    if report is None:
        print(f"[bounds] {name}: FAIL (no resource report attached)",
              file=sys.stderr)
        return 1, (0.0, 0.0), (0.0, 0.0)
    cost = report.cost()
    for obs_key, pred_key in CHECKS:
        pred = cost.get(pred_key)
        if pred is None:
            continue  # unbounded: trivially sound
        obs = int(observed.get(obs_key, 0))
        if obs > pred:
            failures += 1
            print(
                f"[bounds] {name}: FAIL — observed {obs_key}={obs} > "
                f"predicted {pred_key}={pred} (unsound bound)",
                file=sys.stderr,
            )
    # Overhead: re-time a warm compile (every memo hot — the repeat-
    # compile regime the <5% budget is about) and the UNcached bounds
    # walk (what an ingest-invalidated snapshot pays).
    state = CompilerState(
        schemas={n: t.relation for n, t in engine.tables.items()},
        registry=engine.registry,
        table_stats=engine._compile_table_stats(),
    )
    compiled = compile_pxl(query, state)  # warm the memos
    from .bounds import apply_plan_bounds

    def best_of(fn, n=5):
        best = float("inf")
        for _ in range(n):
            t = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t)
        return best

    # A genuinely NOVEL compile span (cache-busted script: every memo
    # misses, the rule passes run) — the denominator the verifier's
    # <5% budget is stated against; repeat compiles only get cheaper.
    novel = best_of(
        lambda: compile_pxl(query + f"\n# cold {time.monotonic_ns()}",
                            state),
        n=3,
    )
    warm_compile = best_of(lambda: compile_pxl(query, state))
    # The memoized in-compile cost (key build + cache hit + re-presize)
    # — what the always-on pass actually adds to a repeat compile.
    hit = best_of(lambda: apply_plan_bounds(
        compiled.plan, state.schemas, state.registry, state.table_stats,
        script=query,
        plan_params=(state.max_output_rows, state.max_groups),
    ))
    # The cold walk an ingest-invalidated snapshot pays (uncached).
    cold = best_of(lambda: plan_bounds(
        compiled.plan, state.schemas, state.registry, state.table_stats,
    ), n=3)
    if verbose and not failures:
        print(
            f"[bounds] {name}: ok — staged {observed['bytes_staged']}/"
            f"{cost['bytes_staged_hi']} rows_in {observed['rows_in']}/"
            f"{cost['rows_in_hi']} rows_out {observed['rows_out']}/"
            f"{cost['rows_out_hi']} (observed/predicted, origin "
            f"{cost['origin']}, total {time.perf_counter() - t0:.2f}s)",
            file=sys.stderr,
        )
    return failures, (novel, warm_compile), (hit, cold)


def _check_cold_decode(name, engine, verbose) -> int:
    """Cold-heavy soundness (ISSUE 20): with most replay windows
    demoted, observed decoded bytes must hold ``<= predicted
    cold_decode_bytes_hi`` (zone-map skipping only lowers the
    realized value — the bound assumes every cold window decodes)."""
    tiers = [
        t._tier for t in engine.tables.values()
        if getattr(t, "_tier", None) is not None
    ]
    cold_rows = sum(t.table.stats().cold_rows for t in tiers)
    if not tiers or not cold_rows:
        print(f"[bounds] {name}: FAIL — tiered replay produced no cold "
              "windows (gate is vacuous)", file=sys.stderr)
        return 1
    pred = engine.last_resource_report.cold_decode_bytes_hi
    obs = sum(t.store.decoded_bytes for t in tiers)
    if pred is None or obs > pred:
        print(f"[bounds] {name}: FAIL — observed decoded bytes {obs} > "
              f"predicted cold_decode_bytes_hi {pred}", file=sys.stderr)
        return 1
    if verbose:
        print(f"[bounds] {name}: cold decode ok — {obs}/{pred} bytes "
              f"(observed/predicted, {cold_rows} cold rows)",
              file=sys.stderr)
    return 0


def _check_rejection(verbose: bool) -> int:
    """The admission half: an over-budget query must fail at COMPILE
    with a structured resource-bound Diagnostic (and never execute)."""
    from ..config import override_flag
    from .diagnostics import PlanCheckError

    schemas = SHAPE_SCHEMAS["http_stats"]
    engine = _replay_engine(schemas, rows=GATE_ROWS)
    executed = {"n": 0}
    orig = engine._execute_plan_inner
    engine._execute_plan_inner = lambda *a, **k: (
        executed.__setitem__("n", executed["n"] + 1) or orig(*a, **k)
    )
    # GATE_ROWS rows x ~20B/row x safety ~= 160KB >> 0.01MB budget.
    with override_flag("bounds_query_budget_mb", 0.01):
        try:
            engine.execute_query(_shape_query("http_stats"))
        except PlanCheckError as e:
            codes = {d.code for d in e.diagnostics}
            if "resource-bound" in codes and executed["n"] == 0:
                if verbose:
                    print(
                        "[bounds] over-budget rejection: ok (compile-"
                        f"time resource-bound diagnostic, 0 executions)",
                        file=sys.stderr,
                    )
                return 0
            print(
                f"[bounds] over-budget rejection: FAIL (codes {codes}, "
                f"{executed['n']} executions)", file=sys.stderr,
            )
            return 1
    print(
        "[bounds] over-budget rejection: FAIL (query was admitted)",
        file=sys.stderr,
    )
    return 1


def check_bounds(verbose: bool = True) -> int:
    """Replay every shape + the bundled self-monitoring scripts
    against pxbound's predictions; returns the failure count."""
    from ..scripts import load_script
    from ..services.telemetry import enable_self_telemetry
    from .obs_check import OBS_SCRIPTS

    failures = 0
    compile_total = warm_total = hit_total = cold_total = 0.0
    for shape, schemas in SHAPE_SCHEMAS.items():
        tiered = shape == "cold_scan"
        engine = _replay_engine(schemas, tiered=tiered)
        f, c, b = _check_one(shape, engine, _shape_query(shape), verbose)
        if tiered:
            f += _check_cold_decode(shape, engine, verbose)
        failures += f
        compile_total += c[0]
        warm_total += c[1]
        hit_total += b[0]
        cold_total += b[1]

    # The bundled self-monitoring scripts run over the telemetry tables
    # a self-observing engine maintains — including the sketch-LESS
    # fallback path (telemetry rings carry few sketched columns), which
    # must degrade to unbounded predictions, never crash or reject.
    engine = _replay_engine(SHAPE_SCHEMAS["http_stats"])
    enable_self_telemetry(engine)
    engine.execute_query(_shape_query("http_stats"))  # seed __queries__
    for name in OBS_SCRIPTS:
        f, c, b = _check_one(
            name, engine, load_script(name).pxl, verbose
        )
        failures += f
        compile_total += c[0]
        warm_total += c[1]
        hit_total += b[0]
        cold_total += b[1]

    failures += _check_rejection(verbose)
    if verbose and compile_total > 0:
        pct = hit_total / compile_total
        print(
            f"[bounds] novel compile {compile_total * 1e3:.1f}ms (repeat "
            f"{warm_total * 1e3:.1f}ms); in-compile pass (memoized, the "
            f"always-on repeat cost) {hit_total * 1e3:.2f}ms "
            f"({pct:.1%} of compile); cold walk on a fresh stats "
            f"snapshot {cold_total * 1e3:.1f}ms "
            f"({cold_total / compile_total:.1%})",
            file=sys.stderr,
        )
        if pct >= 0.05:
            failures += 1
            print(
                "[bounds] FAIL: memoized pass exceeds 5% of the compile "
                "span", file=sys.stderr,
            )
    return failures


def main() -> int:
    failures = check_bounds()
    n = len(SHAPE_SCHEMAS)
    if failures:
        print(f"[bounds] {failures} soundness check(s) failed",
              file=sys.stderr)
        return 1
    print(
        f"[bounds] all {n} replay shapes + self-monitoring scripts hold "
        "observed <= predicted; over-budget rejection verified",
        file=sys.stderr,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
