"""Plan verification over the small-replay shapes (``run_tests.sh
--analyze``), and the one place those shapes are written down.

A replay shape is a query with the schemas of the tables it reads: the
five shipped library scripts the deployments in ``benchmark/`` serve
(px/http_stats, px/service_stats, px/net_flow_graph, px/sql_stats,
px/perf_flamegraph), three N:M join queries and a selective scan of a
mostly-cold table. ``SHAPE_SCHEMAS`` and ``_shape_query`` own them;
``bound_check._replay_engine`` builds an engine holding synthetic rows
of a shape, and the safety gates (this one, ``bound_check``, the
overhead A/Bs in tests/test_profiling.py and tests/test_bus_obs.py,
the depth equivalence in tests/test_pipeline.py) all replay these.

This gate compiles every shape's query against its schemas with the
always-on plan verifier active, then splits each through the
DistributedPlanner (2 PEMs + 1 Kelvin) and runs the full distributed
schema walk. Any diagnostic is a regression: these plans must stay
statically clean, and a script whose columns drift from its schema
fails here with an unbound-column diagnostic, which is the point.

Also reports verifier overhead relative to compile time: the pass
rides inside the ``compile`` span, budgeted at <5% of its p50.
"""

from __future__ import annotations

import sys
import time

from ..types.dtypes import DataType
from ..types.relation import Relation

T, I, F, S = (
    DataType.TIME64NS, DataType.INT64, DataType.FLOAT64, DataType.STRING,
)

#: shape -> (tables, query source loader). Queries load lazily so a
#: missing script surfaces as THIS shape's failure, not an import error.
SHAPE_SCHEMAS = {
    "http_stats": {
        "http_events": Relation([
            ("time_", T), ("latency_ns", I), ("resp_status", I),
            ("service", S), ("req_path", S),
        ]),
    },
    "service_stats": {
        "http_events": Relation([
            ("time_", T), ("latency_ns", I), ("resp_status", I),
            ("service", S), ("req_path", S),
        ]),
    },
    "net_flow_graph": {
        "conn_stats": Relation([
            ("time_", T), ("src_addr", S), ("src_pod", S),
            ("remote_addr", S), ("bytes_sent", I), ("bytes_recv", I),
        ]),
    },
    "sql_stats": {
        "mysql_events": Relation([
            ("time_", T), ("query_str", S), ("latency_ns", I),
        ]),
    },
    "perf_flamegraph": {
        "stack_traces.beta": Relation([
            ("time_", T), ("stack_trace_id", I), ("stack_trace", S),
            ("count", I), ("pod", S),
        ]),
    },
    "device_join": {
        "conn_l": Relation([("time_", T), ("k", I), ("b", I)]),
        "conn_r": Relation([("time_", T), ("k", I), ("v", I)]),
    },
    # The join-distribution shapes (skewed keys / selective clustered
    # keys) share one query whose group keys span BOTH sides — the
    # eager-agg rewrite cannot fire, so this verifies the REAL N:M
    # JoinOp plan the windowed/radix drivers execute.
    "device_join_skew": {
        "conn_l": Relation([("time_", T), ("k", I), ("b", I)]),
        "conn_r": Relation([("time_", T), ("k", I), ("c", I), ("v", I)]),
    },
    "device_join_select": {
        "conn_l": Relation([("time_", T), ("k", I), ("b", I)]),
        "conn_r": Relation([("time_", T), ("k", I), ("c", I), ("v", I)]),
    },
    # Storage-tier shape (ISSUE 20): selective scan whose FilterOp
    # drives zone-map window skipping over a mostly-cold table.
    "cold_scan": {
        "events": Relation([
            ("time_", T), ("shard", I), ("latency_ns", I), ("service", S),
        ]),
    },
}

# The shapes whose queries are not shipped library scripts.
_DEVICE_JOIN_QUERY = """
import px
l = px.DataFrame(table='conn_l')
r = px.DataFrame(table='conn_r')
g = l.merge(r, how='inner', left_on=['k'], right_on=['k'], suffixes=['', '_r'])
out = g.groupby('b').agg(n=('v', px.count), s=('v', px.sum))
px.display(out)
"""

_COLD_SCAN_QUERY = """
import px
df = px.DataFrame(table='events')
df = df[df.shard == 7]
out = df.groupby('shard').agg(
    n=('latency_ns', px.count), s=('latency_ns', px.sum))
px.display(out)
"""

_JOIN_BOTH_SIDES_QUERY = """
import px
l = px.DataFrame(table='conn_l')
r = px.DataFrame(table='conn_r')
g = l.merge(r, how='inner', left_on=['k'], right_on=['k'], suffixes=['', '_r'])
out = g.groupby(['b', 'c']).agg(n=('v', px.count), s=('v', px.sum))
px.display(out)
"""


def _shape_query(shape: str) -> str:
    if shape == "device_join":
        return _DEVICE_JOIN_QUERY
    if shape in ("device_join_skew", "device_join_select"):
        return _JOIN_BOTH_SIDES_QUERY
    if shape == "cold_scan":
        return _COLD_SCAN_QUERY
    from ..scripts import load_script

    return load_script(f"px/{shape}").pxl


def check_bench_shapes(verbose: bool = True) -> int:
    """Compile + verify every replay shape; returns the number of
    failing shapes (0 = green)."""
    from ..planner import CompilerState, compile_pxl
    from ..planner.distributed import DistributedPlanner
    from ..planner.distributed.distributed_state import DistributedState
    from ..udf.registry import default_registry
    from .diagnostics import PlanCheckError, Severity
    from .verifier import verify_distributed_plan, verify_plan

    registry = default_registry()
    dstate = DistributedState.homogeneous(2, 1)
    failures = 0
    compile_total = verify_total = 0.0
    for shape, schemas in SHAPE_SCHEMAS.items():
        state = CompilerState(schemas=dict(schemas), registry=registry)
        try:
            t0 = time.perf_counter()
            compiled = compile_pxl(_shape_query(shape), state)
            t1 = time.perf_counter()
            # Re-run the verifier standalone to time it (inside
            # compile_pxl it already ran once, included in t1-t0).
            diags = verify_plan(compiled.plan, schemas, registry)
            dplan = DistributedPlanner(registry).plan(
                compiled.plan, dstate
            )
            diags += verify_distributed_plan(dplan, schemas, registry)
            t2 = time.perf_counter()
        except PlanCheckError as e:
            failures += 1
            if verbose:
                print(f"[analyze] {shape}: FAIL\n{e}", file=sys.stderr)
            continue
        compile_total += t1 - t0
        verify_total += t2 - t1
        errors = [d for d in diags if d.severity == Severity.ERROR]
        if errors:
            failures += 1
            if verbose:
                print(f"[analyze] {shape}: FAIL", file=sys.stderr)
                for d in errors:
                    print(f"  {d.render()}", file=sys.stderr)
        elif verbose:
            print(
                f"[analyze] {shape}: ok "
                f"({len(compiled.plan.nodes)} logical nodes, "
                f"{len(dplan.split.before_blocking.nodes)}+"
                f"{len(dplan.split.after_blocking.nodes)} split)",
                file=sys.stderr,
            )
    if verbose and compile_total > 0:
        # verify_total counts a FULL standalone re-verify + the whole
        # distributed split+walk; the in-compile incremental cost is
        # smaller still.
        print(
            f"[analyze] compile {compile_total * 1e3:.1f}ms, "
            f"standalone verify+split {verify_total * 1e3:.1f}ms "
            f"({verify_total / compile_total:.1%} of compile)",
            file=sys.stderr,
        )
    return failures


def main() -> int:
    failures = check_bench_shapes()
    if failures:
        print(f"[analyze] {failures} replay shape(s) failed verification",
              file=sys.stderr)
        return 1
    print(f"[analyze] all {len(SHAPE_SCHEMAS)} replay shapes verify clean",
          file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
