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
after it, then the whole request on one axis: the broker's, the PEM's
and the Kelvin's spans by their start (offset from the broker root's
start), with a row ``(unnamed)`` for every stretch of the broker's root
that no span of the three traces names (what ``unnamed_ms`` sums: the
spans that only hold others or only say that someone waited do not
count). One more refresh then counts, inside the Kelvin's
``execute_plan`` and inside its ``merge_agg_bridge`` alone, the calls of
``StringDictionary.get_or_add`` and the strings ``content_key`` hashed,
and the programs JAX compiled: a warm merge is expected to make none of
them (what is left in ``execute_plan`` is the telemetry fold encoding
the finished trace's own rows). The last line is one JSON
object. ``--rehearse-rows`` walks the same flow on the CPU at that size.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import statistics
import sys
import threading

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

_SHOWN = ("program", "windows", "slots", "prepared", "ops", "from", "to",
          "fold", "ride", "max_words", "rows", "string_bytes", "estimate",
          "strategy", "where", "domain", "build_rows", "probe_rows",
          "rows_out", "leaves", "bytes", "cached", "memo", "skipped",
          "topic", "listeners")
#: Under this a stretch no span names is not worth a row.
_UNNAMED_MIN_MS = 0.05


def _merge_traces(spans: dict, qids: list, tracer: str = "kelvin",
                  kind: str = "merge") -> list:
    by_qid = {t.qid: t for t in spans[tracer] if t.kind == kind}
    return [by_qid[q] for q in qids if q in by_qid]


def _print_table(rows: list) -> None:
    for row in rows:
        attrs = {k: v for k, v in row.items()
                 if k not in ("span", "at_ms", "ms", "who")}
        who = f"{row['who']:<7}" if "who" in row else ""
        print(f"  {who}{row['span']:<16} +{row['at_ms']:>9.3f} "
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


def _request_table(traces: dict) -> tuple:
    """(rows, unnamed ms): the spans of a request's traces ({who:
    trace}, the broker's among them) in start order on the broker
    root's axis, and a row for every stretch of that root no span
    names."""
    from benchmark.layer_metrics.unnamed_ms import named_intervals
    from benchmark.xplane import _clip, _union

    root = traces["broker"].root
    rows = [
        {"who": who, **row, "at_ms": round(
            row["at_ms"] + (t.root.start_ns - root.start_ns) / 1e6, 3)}
        for who, t in traces.items() for row in _table(t)
    ]
    cur, unnamed = root.start_ns, 0.0
    named = _union(_clip(named_intervals(list(traces.values())),
                         root.start_ns, root.end_ns))
    for lo, hi in [*named, (root.end_ns, root.end_ns)]:
        gap = (lo - cur) / 1e6
        unnamed += gap
        if gap >= _UNNAMED_MIN_MS:
            rows.append({"who": "", "span": "(unnamed)", "ms": round(gap, 3),
                         "at_ms": round((cur - root.start_ns) / 1e6, 3)})
        cur = max(cur, hi)
    rows.sort(key=lambda r: (r["at_ms"], -r["ms"]))
    return rows, round(unnamed, 3)


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


@contextlib.contextmanager
def warmed_cell(workload: str, seed: int, rehearse_rows: int | None = None):
    """A cell's deployment by the benchmark's own builder, its data
    ingested and every program of its traffic compiled (the harness's
    warm-up), as a namespace: ``stack``, ``spec``, ``traffic``,
    ``driver``, ``requests``, ``now_ns``, ``meter`` (the compile meter),
    ``log`` (a ``SpanLog`` cut after the warm-up), ``device_kind``,
    ``rehearsal`` and ``refresh()`` (one refresh; its records). Yields
    None, with the error said, where there is no TPU and no
    ``rehearse_rows`` (which walks the same flow on the CPU at that
    size). Closes the stack on the way out."""
    import types

    import jax

    from benchmark import harness
    from pixie_tpu.utils.cache import configure_jax_cache

    spec = harness.load_cell(workload)
    cfg, traffic = spec["config"], spec["traffic"]
    configure_jax_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices = jax.devices()
    if rehearse_rows is None and devices[0].platform != "tpu":
        print("error: no TPU (use --rehearse-rows)", file=sys.stderr)
        yield None
        return
    rows = cfg["rows"] if rehearse_rows is None else rehearse_rows
    window_rows = cfg["window_rows"]
    if rehearse_rows is not None:
        window_rows = max(1024, rows // (cfg["rows"] // window_rows))
    with contextlib.ExitStack() as flags:
        if devices[0].platform != "tpu":
            from pixie_tpu.config import override_flag

            flags.enter_context(override_flag("cpu_fold_threads", 1))
        meter = harness.CompileMeter()
        builder = harness.module("builders", cfg["builder"])
        driver = harness.module("drivers", traffic["driver"])
        stack = builder.build(cfg, window_rows)
        flags.callback(stack.close)
        stack.ingest(builder.make_data(cfg, seed, rows))
        _lo, now_ns = harness.range_lo_ns(cfg, traffic)
        requests = harness.requests_of(spec)
        log = harness.SpanLog(stack.tracers)

        def refresh():
            recs, _ = driver.refresh(
                stack, requests, now_ns, traffic["timeout_s"], harness.mark
            )
            return recs

        quiet, n = False, 0
        while not quiet and n < harness.MAX_WARMUPS:
            before = meter.programs
            refresh()
            n += 1
            quiet = meter.programs == before
        for _ in range(traffic["warmup_extra"]):
            refresh()
        log.cut()
        yield types.SimpleNamespace(
            stack=stack, spec=spec, traffic=traffic, driver=driver,
            requests=requests, now_ns=now_ns, meter=meter, log=log,
            device_kind=devices[0].device_kind,
            rehearsal=rehearse_rows is not None, refresh=refresh,
        )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--refreshes", type=int, default=12)
    ap.add_argument("--rehearse-rows", type=int, default=None)
    args = ap.parse_args(argv)

    with warmed_cell(args.workload, args.seed, args.rehearse_rows) as cell:
        if cell is None:
            return 2
        stack, meter, requests = cell.stack, cell.meter, cell.requests
        out = {"workload": args.workload, "device": cell.device_kind,
               "rehearsal": cell.rehearsal, "scripts": {}}

        def refresh():
            return [r["qid"] for r in cell.refresh()]

        qids = [refresh() for _ in range(args.refreshes)]
        spans = cell.log.cut()
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
            broker = _merge_traces(spans, [qids[-1][i]], "broker",
                                   "distributed")
            if broker:
                rows, unnamed = _request_table({
                    "broker": broker[-1], "pem": pem[-1],
                    "kelvin": traces[-1],
                })
                out["scripts"][req["label"]].update(
                    request_last=rows, unnamed_ms_last=unnamed,
                    broker_ms_last=round(_ms(broker[-1].root), 3),
                )
                print(f"== {req['label']}: the request on the broker "
                      f"root's axis, {_ms(broker[-1].root):.3f} ms, of "
                      f"it unnamed {unnamed} ms")
                _print_table(rows)
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
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
