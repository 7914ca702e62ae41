"""The Kelvin's ``merge`` trace of one request of each script of a
benchmark cell, span by span: what a request pays after the PEM's last
``device.wait``, by name.

    python tools/merge_trace.py --workload http_pem_1chip.dash_recent \\
        --seed 7 [--refreshes 12] [--rehearse-rows N]

Builds the cell's deployment with the benchmark's own builder (broker,
one PEM, a Kelvin on one bus), warms it up, issues ``--refreshes``
refreshes of the cell's traffic and prints, a script: the median length
of the Kelvin's merge trace (root to root) and of each of its spans,
then the LAST refresh's trace as a table (offset from the root's start,
length, attributes; the rows between two spans are host work with no
span of its own), and the PEM's fragment trace of the same request
after it. One more refresh then counts, inside the Kelvin's
``execute_plan`` and inside its ``merge_agg_bridge`` alone, the calls of
``StringDictionary.get_or_add`` and the strings ``content_key`` hashed,
and the programs JAX compiled: a warm merge is expected to make none of
them (what is left in ``execute_plan`` is the telemetry fold encoding
the finished trace's own rows). The last line is one JSON
object. ``--rehearse-rows`` walks the same flow on the CPU at that size.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import threading

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

_SHOWN = ("program", "windows", "slots", "prepared", "ops", "from", "to",
          "fold", "ride", "rows", "strategy", "where", "build_rows",
          "probe_rows", "rows_out")


def _merge_traces(spans: dict, qids: list, tracer: str = "kelvin",
                  kind: str = "merge") -> list:
    by_qid = {t.qid: t for t in spans[tracer] if t.kind == kind}
    return [by_qid[q] for q in qids if q in by_qid]


def _print_table(rows: list) -> None:
    for row in rows:
        attrs = {k: v for k, v in row.items()
                 if k not in ("span", "at_ms", "ms")}
        print(f"  {row['span']:<16} +{row['at_ms']:>9.3f} "
              f"{row['ms']:>9.3f} ms  {attrs or ''}")


def _ms(span) -> float:
    return (span.end_ns - span.start_ns) / 1e6


def _table(trace) -> list:
    """The trace's spans in start order, each with the unnamed host time
    since the previous span of its depth ended."""
    t0 = trace.root.start_ns
    rows = []
    for s in sorted(trace.spans, key=lambda s: (s.start_ns, -s.end_ns)):
        if not s.end_ns:
            continue
        rows.append({
            "span": s.name, "at_ms": round((s.start_ns - t0) / 1e6, 3),
            "ms": round(_ms(s), 3),
            **{k: v for k, v in s.attributes.items() if k in _SHOWN},
        })
    return rows


class _KelvinCounter:
    """Counts ``get_or_add`` calls and strings hashed by ``content_key``
    on the thread that runs the Kelvin's ``execute_plan``, while it
    runs, and of those the ones inside ``merge_agg_bridge`` (the rest of
    ``execute_plan`` holds the telemetry fold of the finished trace,
    which encodes its own rows' strings)."""

    def __init__(self, engine):
        from pixie_tpu.exec import engine as engine_mod
        from pixie_tpu.types.strings import StringDictionary as SD

        self.sd, self.engine, self.mod = SD, engine, engine_mod
        self.on = threading.local()
        self.counts = {"get_or_add_calls": 0, "strings_hashed": 0,
                       "get_or_add_calls_in_merge": 0,
                       "strings_hashed_in_merge": 0}
        self._real = (SD.get_or_add, SD.content_key, engine.execute_plan,
                      engine_mod.merge_agg_bridge)
        real_add, real_key, real_exec, real_merge = self._real
        me = self

        def count(name, n):
            if getattr(me.on, "plan", False):
                me.counts[name] += n
                if getattr(me.on, "merge", False):
                    me.counts[name + "_in_merge"] += n

        def get_or_add(d, s):
            count("get_or_add_calls", 1)
            return real_add(d, s)

        def content_key(d):
            count("strings_hashed", len(d._strings) - d._fp_len)
            return real_key(d)

        def execute_plan(*a, **k):
            me.on.plan = True
            try:
                return real_exec(*a, **k)
            finally:
                me.on.plan = False

        def merge_agg_bridge(*a, **k):
            me.on.merge = True
            try:
                return real_merge(*a, **k)
            finally:
                me.on.merge = False

        SD.get_or_add, SD.content_key = get_or_add, content_key
        engine.execute_plan = execute_plan
        engine_mod.merge_agg_bridge = merge_agg_bridge

    def close(self):
        self.sd.get_or_add, self.sd.content_key = self._real[:2]
        self.mod.merge_agg_bridge = self._real[3]
        del self.engine.execute_plan


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--refreshes", type=int, default=12)
    ap.add_argument("--rehearse-rows", type=int, default=None)
    args = ap.parse_args(argv)

    from benchmark import harness

    spec = harness.load_cell(args.workload)
    cfg, traffic = spec["config"], spec["traffic"]
    from pixie_tpu.utils.cache import configure_jax_cache

    configure_jax_cache()
    import contextlib

    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices = jax.devices()
    if args.rehearse_rows is None and devices[0].platform != "tpu":
        print("error: no TPU (use --rehearse-rows)", file=sys.stderr)
        return 2
    rows = cfg["rows"] if args.rehearse_rows is None else args.rehearse_rows
    window_rows = cfg["window_rows"]
    if args.rehearse_rows is not None:
        window_rows = max(1024, rows // (cfg["rows"] // window_rows))
    flags = contextlib.ExitStack()
    if devices[0].platform != "tpu":
        from pixie_tpu.config import override_flag

        flags.enter_context(override_flag("cpu_fold_threads", 1))
    meter = harness.CompileMeter()
    builder = harness.module("builders", cfg["builder"])
    driver = harness.module("drivers", traffic["driver"])
    out = {"workload": args.workload, "device": devices[0].device_kind,
           "rehearsal": args.rehearse_rows is not None, "scripts": {}}
    with flags:
        stack = builder.build(cfg, window_rows)
        try:
            stack.ingest(builder.make_data(cfg, args.seed, rows))
            _lo, now_ns = harness.range_lo_ns(cfg, traffic)
            requests = harness.requests_of(spec)
            log = harness.SpanLog(stack.tracers)

            def refresh():
                recs, _ = driver.refresh(
                    stack, requests, now_ns, traffic["timeout_s"],
                    harness.mark,
                )
                return [r["qid"] for r in recs]

            quiet, n = False, 0
            while not quiet and n < harness.MAX_WARMUPS:
                before = meter.programs
                refresh()
                n += 1
                quiet = meter.programs == before
            for _ in range(traffic["warmup_extra"]):
                refresh()
            log.cut()
            qids = [refresh() for _ in range(args.refreshes)]
            spans = log.cut()
            for i, req in enumerate(requests):
                traces = _merge_traces(spans, [q[i] for q in qids])
                names = sorted({s.name for t in traces for s in t.spans})
                med = {
                    name: round(statistics.median(
                        sum(_ms(s) for s in t.spans if s.name == name)
                        for t in traces
                    ), 3)
                    for name in names
                }
                counts = {
                    name: statistics.median(
                        sum(1 for s in t.spans if s.name == name)
                        for t in traces
                    )
                    for name in names
                }
                out["scripts"][req["label"]] = {
                    "merge_ms_p50": round(statistics.median(
                        _ms(t.root) for t in traces), 3),
                    "span_ms_p50": med, "span_count_p50": counts,
                    "usage": traces[-1].usage.to_dict(),
                    "last": _table(traces[-1]),
                }
                print(f"== {req['label']}: merge trace, root to root, "
                      f"p50 of {len(traces)} = "
                      f"{out['scripts'][req['label']]['merge_ms_p50']} ms")
                _print_table(out["scripts"][req["label"]]["last"])
                # The same request on the PEM, for what the Kelvin's
                # parts cost beside the folds.
                pem = _merge_traces(spans, [q[i] for q in qids],
                                    "pem", "fragment")
                out["scripts"][req["label"]]["pem_ms_p50"] = round(
                    statistics.median(_ms(t.root) for t in pem), 3)
                out["scripts"][req["label"]]["pem_last"] = _table(pem[-1])
                print(f"== {req['label']}: the PEM's fragment trace, p50 = "
                      f"{out['scripts'][req['label']]['pem_ms_p50']} ms")
                _print_table(out["scripts"][req["label"]]["pem_last"])
            counter = _KelvinCounter(stack.kelvin.engine)
            before = meter.programs
            try:
                refresh()
            finally:
                counter.close()
            out["warm_request"] = {
                **counter.counts,
                "programs_compiled": meter.programs - before,
            }
        finally:
            stack.close()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
