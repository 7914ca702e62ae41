"""Self-observability gate (``run_tests.sh --obs``; runs in --tier1).

Compiles every bundled self-monitoring PxL script (px/slow_queries,
px/query_cost, px/agent_health, px/program_cost, px/bound_accuracy)
against the telemetry table schemas
(``ingest/schemas.py`` TELEMETRY_SCHEMAS) with the always-on plan
verifier active, then splits each through the DistributedPlanner (2
PEMs + 1 Kelvin) and runs the full distributed schema walk — the same
contract ``bench_check.py`` enforces for the small-replay shapes. A
schema drift in the TelemetryCollector's fold (services/telemetry.py)
surfaces HERE as an unbound-column diagnostic, before any cluster
runs it.
"""

from __future__ import annotations

import sys

#: The bundled self-monitoring scripts this gate covers.
OBS_SCRIPTS = (
    "px/slow_queries", "px/query_cost", "px/agent_health",
    # Device tier (PR 12): the program registry's __programs__ table
    # and the predicted-vs-observed calibration over __queries__.
    "px/program_cost", "px/bound_accuracy",
    # Storage tier: cluster-merged table health + per-agent watermark
    # lag over the __tables__ snapshots (TableStatsCollector fold).
    "px/table_health", "px/ingest_lag",
    # Result cache: hit/miss/stale/bypass/view rollup per script hash
    # over the __queries__ cache column (exec/result_cache.py).
    "px/cache_stats",
    # Profiling tier: attributed CPU from the __stacks__ ring — per
    # script/tenant burn, per-tenant phase split, and the diff-ready
    # folded-stack feed (ingest/profiler.py + exec/threadmap.py).
    "px/query_cpu", "px/tenant_cpu", "px/flame_diff",
    # Transport tier: per-topic-class bus throughput/lag/queue
    # high-water and request/reply RTT over the __bus__ snapshots
    # (services/busstats.py + BusStatsCollector fold).
    "px/bus_health", "px/rpc_latency",
)


def check_obs_scripts(verbose: bool = True) -> int:
    """Compile + verify every self-monitoring script; returns the
    number of failing scripts (0 = green)."""
    from ..ingest.schemas import TELEMETRY_SCHEMAS
    from ..planner import CompilerState, compile_pxl
    from ..planner.distributed import DistributedPlanner
    from ..planner.distributed.distributed_state import DistributedState
    from ..scripts import load_script
    from ..udf.registry import default_registry
    from .diagnostics import PlanCheckError, Severity
    from .verifier import verify_distributed_plan, verify_plan

    registry = default_registry()
    dstate = DistributedState.homogeneous(2, 1)
    schemas = dict(TELEMETRY_SCHEMAS)
    failures = 0
    for name in OBS_SCRIPTS:
        try:
            pxl = load_script(name).pxl
            state = CompilerState(schemas=dict(schemas), registry=registry)
            compiled = compile_pxl(pxl, state)
            diags = verify_plan(compiled.plan, schemas, registry)
            dplan = DistributedPlanner(registry).plan(compiled.plan, dstate)
            diags += verify_distributed_plan(dplan, schemas, registry)
        except (PlanCheckError, Exception) as e:  # noqa: BLE001 — gate
            failures += 1
            if verbose:
                print(f"[obs] {name}: FAIL\n{e}", file=sys.stderr)
            continue
        errors = [d for d in diags if d.severity == Severity.ERROR]
        if errors:
            failures += 1
            if verbose:
                print(f"[obs] {name}: FAIL", file=sys.stderr)
                for d in errors:
                    print(f"  {d.render()}", file=sys.stderr)
        elif verbose:
            print(
                f"[obs] {name}: ok ({len(compiled.plan.nodes)} logical "
                f"nodes, {len(dplan.split.before_blocking.nodes)}+"
                f"{len(dplan.split.after_blocking.nodes)} split)",
                file=sys.stderr,
            )
    return failures


def main() -> int:
    failures = check_obs_scripts()
    if failures:
        print(f"[obs] {failures} self-monitoring script(s) failed "
              "verification", file=sys.stderr)
        return 1
    print(f"[obs] all {len(OBS_SCRIPTS)} self-monitoring scripts verify "
          "clean against the telemetry schemas", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
