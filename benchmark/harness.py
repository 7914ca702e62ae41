"""One cell, once: load, warm up, measure, compare, report.

Everything that belongs to one configuration, one traffic mix or one
metric is a file found by the name ``BENCHMARK.json`` gives:

    configs/<config>.json      sizes, guarantees, and its ``builder``
    builders/<builder>.py      make_data(cfg, seed, rows), build(cfg, window_rows)
    traffic/<traffic>/         traffic.json (its ``driver``, scripts), PxL
    drivers/<driver>.py        run(stack, traffic, requests, seconds, now_ns, mark)
    reference/<reference>.py   answer, rows, numbers, LIMITS
    end_to_end/<metric>.py     read(ctx) -> value or None
    layer_metrics/<metric>.py  read(ctx) -> value or None
    peaks.json                 device_kind -> peaks

so a later PR adds a cell, a mix or a metric by adding files.
"""

from __future__ import annotations

import contextlib
import gc
import importlib
import json
import os
import shutil
import time

from . import readers, xplane

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
PACKAGE = os.path.basename(BENCH)
MAX_WARMUPS = 6


class NoChip(RuntimeError):
    """The machine lacks the cell's chips: no result, non-zero exit."""


def say(**kw) -> None:
    """An earlier line of the output: one JSON object, flushed."""
    print(json.dumps(kw, default=str), flush=True)


def module(kind: str, name: str):
    return importlib.import_module(f"{PACKAGE}.{kind}.{name}")


def load_json(*parts) -> dict:
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def load_cell(workload: str) -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, config["file"])) as f:
        cfg = json.load(f)
    traffic_dir = os.path.join(BENCH, "traffic", cell["traffic"])
    traffic = load_json("traffic", cell["traffic"], "traffic.json")
    return {"bench": bench, "cell": cell, "config": cfg,
            "traffic": traffic, "traffic_dir": traffic_dir}


def metrics_of(bench: dict, kind: str, workload: str) -> list:
    """The cell's metrics of ``kind``: those that list it, and those
    that list no cells."""
    return [m for m in bench[kind]
            if workload in m.get("workloads", [workload])]


def requests_of(spec: dict) -> list:
    """The traffic's scripts with their PxL text: ``bundled`` is the
    program's own script of that name, anything else a file beside
    ``traffic.json``."""
    out = []
    for s in spec["traffic"]["scripts"]:
        if s["pxl"] == "bundled":
            from pixie_tpu.scripts import load_script

            pxl = load_script(s["name"]).pxl
        else:
            with open(os.path.join(spec["traffic_dir"], s["pxl"])) as f:
                pxl = f.read()
        out.append({**s, "pxl": pxl})
    return out


def mark(name: str):
    """A host span in the profiler's own trace, from the benchmark's
    driver only; costs a flag test when no trace is on."""
    import jax.profiler

    return jax.profiler.TraceAnnotation(name)


class CompileMeter:
    """Counts what JAX compiled or fetched from the persistent cache,
    from JAX's own monitoring events: every jit in the process. (Copied
    from ``chip_smoke.py``, PR 22.)"""

    def __init__(self):
        import jax.monitoring

        self.programs = 0
        self.fetched = 0
        self.secs = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.secs += secs
        elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
            self.fetched += 1


class SpanLog:
    """Finished traces of each tracer, through ``Tracer.add_listener``.
    The listener only appends; reading happens after the window."""

    def __init__(self, tracers: dict):
        self.traces = {k: [] for k in tracers}
        for k, tracer in tracers.items():
            tracer.add_listener(self.traces[k].append)

    def cut(self) -> dict:
        """The traces so far, and a clean slate (traces may finish on
        other threads meanwhile: only what was copied is dropped)."""
        out = {}
        for k, lst in self.traces.items():
            n = len(lst)
            out[k] = lst[:n]
            del lst[:n]
        return out


def device_report(devices, chips: int) -> dict:
    peak = 0
    for d in devices[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def range_lo_ns(cfg: dict, traffic: dict):
    """The lower end of the traffic's time range (None: all retention)
    and the instant every request passes as ``now_ns``."""
    now_ns = cfg[traffic["now"]]
    if traffic["range_s"] is None:
        return None, now_ns
    return now_ns - traffic["range_s"] * 1_000_000_000, now_ns


def compare(requests: list, data: dict, lo_ns, answers: list):
    """Every refresh's rows against the plain reference: the worst of
    each number over the refreshes, beside its limit. ``answers`` is a
    list of refreshes, each a list of decoded tables in script order."""
    worst, limits = {}, {}
    for i, req in enumerate(requests):
        ref_mod = module("reference", req["reference"])
        ref = ref_mod.answer(data, lo_ns)
        limits.update(ref_mod.LIMITS)
        for refresh in answers:
            got = ref_mod.rows(refresh[i])
            for name, value in ref_mod.numbers(got, ref).items():
                worst[name] = max(worst.get(name, 0), value)
    return worst, limits


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t0: float, rehearse_rows: int | None = None,
             break_path=None) -> dict:
    """The whole run; returns the result line as a dict. ``t0`` is the
    process's start on ``time.time()``'s clock. ``rehearse_rows`` runs
    the same flow on whatever devices there are at that row count, and
    marks the result a rehearsal. ``break_path(stack)`` lets a test
    break the timed path underneath before the warm-up."""
    spec = load_cell(workload)
    cell, cfg, traffic = spec["cell"], spec["config"], spec["traffic"]
    chips = cell["chips"]
    if cfg["chips"] != chips:
        raise SystemExit(f"{workload}: cell and configuration disagree on chips")

    from pixie_tpu.utils.cache import configure_jax_cache

    cache_dir = configure_jax_cache()
    import jax

    # Every program goes to the persistent cache, the sub-second ones
    # too: after a checkout's first run set-up compiles nothing.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    devices = jax.devices()
    on_chip = devices[0].platform == "tpu" and len(devices) >= chips
    if rehearse_rows is None and not on_chip:
        raise NoChip(
            f"{workload} needs {chips} TPU chip(s); JAX found "
            f"{len(devices)} x {devices[0].platform}"
        )
    if len(devices) < chips:
        raise NoChip(f"{workload} needs {chips} devices, have {len(devices)}")
    peaks = load_json("peaks.json")
    kind = devices[0].device_kind
    if rehearse_rows is None and kind not in peaks:
        raise SystemExit(f"device kind {kind!r} is not in peaks.json")
    rows = cfg["rows"] if rehearse_rows is None else rehearse_rows
    window_rows = cfg["window_rows"]
    if rehearse_rows is not None:
        window_rows = max(1024, rehearse_rows // (cfg["rows"] // window_rows))
    say(workload=workload, seed=seed, seconds=seconds, trace=trace,
        rows=rows, window_rows=window_rows, cache_dir=cache_dir,
        device=[devices[0].platform, kind, len(devices)],
        rehearsal=rehearse_rows is not None)

    meter = CompileMeter()
    builder = module("builders", cfg["builder"])
    driver = module("drivers", traffic["driver"])
    flags = contextlib.ExitStack()
    if devices[0].platform != "tpu":
        # The CPU backend's native fold is off in a rehearsal, so that
        # the XLA fold the chip runs is what rehearses.
        from pixie_tpu.config import override_flag

        flags.enter_context(override_flag("cpu_fold_threads", 1))
    stack = None
    with flags:
        try:
            t = time.perf_counter()
            data = builder.make_data(cfg, seed, rows)
            data_s = time.perf_counter() - t
            stack = builder.build(cfg, window_rows)
            stack.ingest(data)
            resident = stack.resident()
            if resident["rows"] != rows or resident["devices"] != chips:
                raise RuntimeError(
                    f"resident {resident}, want {rows} rows on {chips} chips"
                )
            lo_ns, now_ns = range_lo_ns(cfg, traffic)
            in_range = rows if lo_ns is None else int(
                (data["time_"] >= lo_ns).sum()
            )
            say(step="ingest", data_s=data_s, ingest_s=stack.ingest_s,
                resident=resident, rows_in_range=in_range)
            requests = requests_of(spec)
            if break_path is not None:
                break_path(stack)
            log = SpanLog(stack.tracers)

            # Warm-up: refreshes of exactly this traffic until one
            # compiles nothing, then a fixed number more.
            t = time.perf_counter()
            warmups, quiet = 0, False
            while not quiet:
                if warmups == MAX_WARMUPS:
                    raise RuntimeError(
                        f"refresh {warmups} of the warm-up still compiled"
                    )
                before = meter.programs
                driver.refresh(stack, requests, now_ns,
                               traffic["timeout_s"], mark)
                warmups += 1
                quiet = meter.programs == before
            for _ in range(traffic["warmup_extra"]):
                driver.refresh(stack, requests, now_ns,
                               traffic["timeout_s"], mark)
            warmup_s = time.perf_counter() - t
            say(step="warmup", refreshes=warmups + traffic["warmup_extra"],
                warmup_s=warmup_s, programs=meter.programs,
                from_persistent_cache=meter.fetched,
                compile_s=meter.secs)
            log.cut()
            gc.collect()
            gc.freeze()

            compiled_before = meter.programs
            traced, untimed = None, []
            setup_s = time.time() - t0
            if trace and devices[0].platform == "tpu":
                trace_dir = os.path.join(ROOT, ".bench_trace")
                shutil.rmtree(trace_dir, ignore_errors=True)
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                with mark(xplane.WINDOW_MARK):
                    first = driver.run(
                        stack, traffic, requests,
                        min(seconds, traffic["trace_seconds"]), now_ns, mark,
                    )
                jax.profiler.stop_trace()
                log.cut()
                rest = max(seconds - traffic["trace_seconds"], 1.0)
                window = driver.run(stack, traffic, requests, rest,
                                    now_ns, mark)
                for k in ("attempted", "failed"):
                    window[k] += first[k]
                window["errors"] = first["errors"] + window["errors"]
                window["answers"] = first["answers"] + window["answers"]
                untimed = first["refreshes"]
                trace_file = xplane.newest_trace(trace_dir)
                traced = xplane.reduce(xplane.load(trace_file), chips)
                traced["refreshes"] = len(first["refreshes"])
                keep = os.environ.get("PXBENCH_KEEP_TRACE")
                if keep:
                    shutil.copy(trace_file, keep)
                shutil.rmtree(trace_dir, ignore_errors=True)
            else:
                window = driver.run(stack, traffic, requests, seconds,
                                    now_ns, mark)
            spans = log.cut()
            window_compiles = meter.programs - compiled_before
            device = device_report(devices, chips)
        finally:
            if stack is not None:
                stack.close()

    # The comparison, once the window has closed and the stack is down.
    t = time.perf_counter()
    numbers, limits = compare(requests, data, lo_ns, window["answers"])
    # ``untimed``: the traced part's refreshes, whose answers are
    # compared though the clock metrics leave them out.
    partial = sum(r["partial"] for recs in untimed + window["refreshes"]
                  for r in recs)
    correct = (
        window["failed"] == 0 and partial == 0
        and len(window["answers"]) > 0
        and all(numbers[k] <= limits[k] for k in limits)
    )
    # Each number compared beside its limit (1e300 stands for "could not
    # be compared", which JSON cannot say).
    compared = {k: [min(float(numbers[k]), 1e300), limits[k]] for k in limits}
    say(step="compare", refreshes=len(window["answers"]),
        reference_s=time.perf_counter() - t, numbers=compared,
        partial=partial, errors=window["errors"][:5], correct=correct)

    ctx = {
        "cell": cell, "config": cfg, "traffic": traffic, "chips": chips,
        "requests": requests, "rows_in_range": in_range,
        "peaks": peaks.get(kind), "window": window, "spans": spans,
        "trace": traced, "window_compiles": window_compiles,
        "setup": {"setup_s": setup_s, "warmup_s": warmup_s,
                  "ingest_s": stack.ingest_s, "data_s": data_s,
                  "rows": rows},
    }
    # Beside the result, in every run: where a run-to-run difference of
    # the refresh time sits (client, broker path, engine, host CPU).
    ms = readers.refresh_ms(ctx)
    engine = readers.per_refresh(ctx, readers.engine_ms(ctx))
    say(step="study", refreshes=len(ms), window_s=readers.window_s(ctx),
        refresh_ms=[readers.percentile(ms, q) for q in (0, 25, 50, 75, 100)],
        engine_ms_p50=readers.percentile(engine, 50),
        cpu_ms_per_refresh=window["cpu_s"] * 1e3 / max(len(ms), 1),
        window_compiles=window_compiles,
        busy_s=traced and traced["busy_s"],
        traced_s=traced and traced["window_s"])
    # Each refresh of the window, so that a late one, or what a shorter
    # window would have read, can be seen afterwards.
    say(step="refreshes",
        start_s=[round(recs[0]["t0"] - window["t_open"], 4)
                 for recs in window["refreshes"]],
        ms=[round(float(v), 3) for v in ms])
    kind_dir = "layer_metrics" if trace else "end_to_end"
    metrics = {}
    for m in metrics_of(spec["bench"],
                        "per_layer" if trace else "end_to_end", workload):
        value = module(kind_dir, m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    result = {"correct": bool(correct), "attempted": window["attempted"],
              "failed": window["failed"], "metrics": metrics,
              "device": device, "numbers": compared}
    if rehearse_rows is not None:
        result["rehearsal"] = True
    if traced is not None and rehearse_rows is None:
        device["busy_s"] = traced["busy_s"]
        device["window_s"] = traced["window_s"]
        result["breakdown"] = {
            "device_ops": xplane.top(traced["ops"]),
            "idle_gaps": xplane.top(traced["gaps"]),
        }
    return result
