"""What the program's own tracing costs, on the machine it runs on.

    python tools/span_cost.py [--workload http_pem_1chip.dash_recent \\
        --seed 7 --seconds 8 --rounds 2] [--rehearse-rows N]

Always: the cost of one span with no profiler session on, ns (a
``with trace.span(...)`` block, which is also a
``jax.profiler.TraceAnnotation``; an ``add_span`` of two stamps taken
elsewhere; one read of the clock), over ``--spans`` spans.

With ``--workload``: the cell's deployment by the benchmark's own
builder, warmed up, then ``--rounds`` pairs of ``--seconds``-long
windows of the cell's traffic, the first of a pair with no profiler
session and the second with one on (the options the benchmark's traced
run uses): each window's median refresh on the client's clock, and the
spans a request leaves on its three traces, by trace and by name. The
last line is one JSON object. ``--rehearse-rows`` walks the same flow on
the CPU at that size.

With ``--fetch`` (and no cell): what a program's outputs cost to bring
to the host on that machine, at the three shapes the cells' fragments
ship (``FETCH_SHAPES``), three ways, ms over ``--reps`` fresh runs of
one program: (a) a ``np.asarray`` a leaf after ``block_until_ready``;
(b) one ``jax.device_get`` after it; (c) ``copy_to_host_async`` on every
leaf at the dispatch, the flag's read as the sync, then one
``jax.device_get``: what ``exec/stream.py`` ``_start_fetch`` /
``_fetch_tree`` do. ``fetch_ms`` is the sync's return to the last leaf
on the host, ``total_ms`` the dispatch to it.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import statistics
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def span_ns(n: int) -> dict:
    """ns a span, a stamped span and a clock read, each over ``n``."""
    from pixie_tpu.exec import trace as tr

    def per(fn) -> float:
        t0 = time.perf_counter_ns()
        fn()
        return (time.perf_counter_ns() - t0) / n

    def with_spans():
        # Fresh traces: one keeps MAX_SPANS_PER_TRACE spans at most.
        for _ in range(n // 256):
            t = tr.QueryTrace(None)
            for _ in range(256):
                with t.span("x", k=1):
                    pass

    def added_spans():
        for _ in range(n // 256):
            t = tr.QueryTrace(None)
            for _ in range(256):
                t.add_span("x", 1, 2, k=1)

    def clock_reads():
        for _ in range(n):
            tr.clock_ns()

    def loop_alone():
        for _ in range(n // 256):
            tr.QueryTrace(None)
            for _ in range(256):
                pass

    base = per(loop_alone)
    return {
        "with_span_ns": round(per(with_spans) - base, 1),
        "add_span_ns": round(per(added_spans) - base, 1),
        "clock_ns_ns": round(per(clock_reads), 1),
    }


#: name -> (leaves of i32, leaves of i64, slots): the states the cells'
#: fragments ship. The dashboard scripts' dense states (15 leaves a
#: refresh at 2,145 slots, 0.22 MB); ``px/sql_stats``' keyed state (7
#: leaves at 2^17 slots, 4.85 MB read, 5.2 here); the re-aggregation's
#: result in ``flow_recent`` (5 leaves at 2^16 slots, 1.64 MB read, 1.57
#: here). Each with a 0-d flag beside it, as a state's ``overflow``.
FETCH_SHAPES = {
    "dense_15x15KB": (5, 10, 2145),
    "keyed_7x0.7MB": (4, 3, 1 << 17),
    "result_5x0.33MB": (4, 1, 1 << 16),
}


def fetch_ms(reps: int) -> dict:
    """The three ways of bringing a program's outputs to the host, at
    ``FETCH_SHAPES``: median ms of ``reps`` runs each, the ways taking
    turns run by run."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import pixie_tpu  # noqa: F401  (x64 on, as the engine's states are)

    def timed(way, fn, x, salt):
        t0 = time.perf_counter_ns()
        outs = fn(x, salt)
        flag = outs[-1]
        if way == "c":
            for o in outs:
                o.copy_to_host_async()
            bool(np.asarray(flag))
        else:
            jax.block_until_ready(outs)
        t1 = time.perf_counter_ns()
        if way == "a":
            host = [np.asarray(o) for o in outs]
        else:
            host = jax.device_get(outs)
        t2 = time.perf_counter_ns()
        assert all(isinstance(h, np.ndarray) for h in host)
        return (t2 - t1) / 1e6, (t2 - t0) / 1e6

    out = {}
    for name, (n32, n64, slots) in FETCH_SHAPES.items():
        def program(x, salt, n32=n32, n64=n64, slots=slots):
            # Some device time in front of the outputs (a 2^21-row
            # sort, a window fold's order of work), so that copies
            # started at the dispatch queue behind a running program.
            s = jnp.sort(x ^ salt)[:slots]
            leaves = [s + jnp.int32(i) for i in range(n32)]
            leaves += [s.astype(jnp.int64) << (i + 1) for i in range(n64)]
            return (*leaves, jnp.any(s > jnp.int32(1 << 30)))

        fn = jax.jit(program)
        x = jnp.arange(1 << 21, dtype=jnp.int32)[::-1] % jnp.int32(99991)
        for i in range(3):  # compile, and warm each way
            for way in "abc":
                timed(way, fn, x, jnp.int32(i))
        runs = {way: [] for way in "abc"}
        for i in range(reps):
            for way in ("abc", "bca", "cab")[i % 3]:
                runs[way].append(timed(way, fn, x, jnp.int32(3 + i)))
        row = {
            "leaves": n32 + n64 + 1,
            "bytes": slots * (4 * n32 + 8 * n64) + 1,
        }
        for way, label in (("a", "a_asarray_a_leaf"), ("b", "b_device_get"),
                           ("c", "c_async_at_dispatch")):
            row[label] = {
                "fetch_ms": round(statistics.median(
                    f for f, _ in runs[way]), 3),
                "total_ms": round(statistics.median(
                    t for _, t in runs[way]), 3),
            }
        out[name] = row
        print(json.dumps({name: row}), flush=True)
    return out


def spans_a_request(spans: dict, qids: list) -> dict:
    """Median spans a request on each of its traces, and by name over
    the three."""
    kinds = {"broker": "distributed", "pem": "fragment", "kelvin": "merge"}
    per_trace, per_name = {}, {}
    for who, kind in kinds.items():
        by_qid = {t.qid: t for t in spans[who] if t.kind == kind}
        traces = [by_qid[q] for q in qids if q in by_qid]
        per_trace[who] = statistics.median(len(t.spans) for t in traces)
        for name in {s.name for t in traces for s in t.spans}:
            per_name[name] = per_name.get(name, 0) + statistics.median(
                sum(1 for s in t.spans if s.name == name) for t in traces
            )
    return {"by_trace": per_trace, "total": sum(per_trace.values()),
            "by_name": dict(sorted(per_name.items()))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--spans", type=int, default=1 << 18)
    ap.add_argument("--workload", default=None)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rounds", type=int, default=2)
    ap.add_argument("--rehearse-rows", type=int, default=None)
    ap.add_argument("--fetch", action="store_true",
                    help="the three device-to-host readings")
    ap.add_argument("--reps", type=int, default=30)
    args = ap.parse_args(argv)

    if args.fetch:
        import jax

        out = {"device": jax.devices()[0].device_kind,
               "fetch": fetch_ms(args.reps)}
        print(json.dumps(out), flush=True)
        return 0

    out = {"span": span_ns(args.spans)}
    print(json.dumps(out["span"]), flush=True)
    if args.workload is None:
        print(json.dumps(out), flush=True)
        return 0

    import jax

    from benchmark import harness
    from merge_trace import warmed_cell

    with warmed_cell(args.workload, args.seed, args.rehearse_rows) as cell:
        if cell is None:
            return 2
        out.update(workload=args.workload, device=cell.device_kind,
                   rehearsal=cell.rehearsal, windows=[])
        for i in range(2 * args.rounds):
            profiled = bool(i % 2)
            trace_dir = tempfile.mkdtemp(prefix="span_cost_")
            if profiled:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
            try:
                window = cell.driver.run(
                    cell.stack, cell.traffic, cell.requests, args.seconds,
                    cell.now_ns, harness.mark,
                )
            finally:
                if profiled:
                    jax.profiler.stop_trace()
                shutil.rmtree(trace_dir, ignore_errors=True)
            ms = [(recs[-1]["t1"] - recs[0]["t0"]) * 1e3
                  for recs in window["refreshes"]]
            row = {"profiler": profiled, "refreshes": len(ms),
                   "failed": window["failed"],
                   "refresh_p50_ms": statistics.median(ms)}
            out["windows"].append(row)
            print(json.dumps(row), flush=True)
            spans = cell.log.cut()
        qids = [r["qid"] for recs in window["refreshes"] for r in recs]
        out["spans_a_request"] = spans_a_request(spans, qids)
        out["spans_a_refresh"] = (
            out["spans_a_request"]["total"] * len(cell.requests)
        )
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
