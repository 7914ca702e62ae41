"""The readers of the program's own spans (``benchmark/span_readers.py``
and the metrics built on it) on a rehearsed stack, the four-chip
cell's entries, and ``benchmark/span_gaps.py``. On the CPU: never a
device number from here."""

import gzip
import importlib
import json
import os
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
BENCH = os.path.join(ROOT, "benchmark")
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCHMARK = json.load(f)

#: This PR's per-layer metrics, by the layer each is filed under.
SPAN_METRICS = {
    "head_ms": "broker path", "tail_ms": "broker path",
    "broker_self_ms": "broker path", "merge_ms": "broker path",
    "device_wait_ms": "engine", "dispatch_ms": "engine",
    "device_interval_ms": "engine", "device_dispatches": "engine",
    "span_idle_pct": "device", "background_ms": "host",
    "slowest_refresh_background_ms": "host",
}


def _read(name, ctx):
    return importlib.import_module(f"benchmark.layer_metrics.{name}").read(ctx)


def _window(seconds):
    """``ctx`` of a rehearsed ``dash_recent`` window of ``seconds`` on
    the served stack, as ``harness.run_cell`` builds it (the parts the
    span readers use)."""
    from benchmark import harness
    from pixie_tpu.config import override_flag

    spec = harness.load_cell("http_pem_1chip.dash_recent")
    cfg, traffic = spec["config"], spec["traffic"]
    builder = harness.module("builders", cfg["builder"])
    driver = harness.module("drivers", traffic["driver"])
    with override_flag("cpu_fold_threads", 1):
        stack = builder.build(cfg, 1 << 13)
        try:
            stack.ingest(builder.make_data(cfg, 3_000_000_019, 1 << 15))
            requests = harness.requests_of(spec)
            log = harness.SpanLog(stack.tracers)
            _lo, now_ns = harness.range_lo_ns(cfg, traffic)
            for _ in range(3):
                driver.refresh(stack, requests, now_ns, 120, harness.mark)
            log.cut()
            window = driver.run(stack, traffic, requests, seconds, now_ns,
                                harness.mark)
            spans = log.cut()
        finally:
            stack.close()
    assert window["failed"] == 0 and window["refreshes"]
    return {"window": window, "spans": spans, "trace": None}


@pytest.fixture(scope="module")
def one_refresh():
    # A window shorter than a refresh closes with its first one (kept
    # to that one even if a stalled start let a second begin).
    ctx = _window(0.05)
    ctx["window"]["refreshes"] = ctx["window"]["refreshes"][:1]
    return ctx


@pytest.fixture(scope="module")
def window():
    return _window(1.0)


def test_head_tail_and_device_interval_make_up_the_brokers_root(one_refresh):
    ctx = one_refresh
    (refresh,) = ctx["window"]["refreshes"]
    roots = {t.qid: t for t in ctx["spans"]["broker"]}
    root_ms = sum((roots[r["qid"]].end_ns - roots[r["qid"]].start_ns) / 1e6
                  for r in refresh)
    head, tail, interval = (_read(m, ctx) for m in (
        "head_ms", "tail_ms", "device_interval_ms"
    ))
    assert min(head, tail, interval) > 0
    assert head + tail + interval == pytest.approx(root_ms, abs=1e-6)
    # ... and the root is the client's request, less what the client
    # does around the call.
    client_ms = sum((r["t1"] - r["t0"]) * 1e3 for r in refresh)
    assert root_ms < client_ms < root_ms + 25
    # The parts lie inside what holds them.
    assert _read("device_wait_ms", ctx) + _read("dispatch_ms", ctx) <= interval
    assert _read("merge_ms", ctx) < tail
    assert _read("broker_self_ms", ctx) < head + tail
    assert _read("device_dispatches", ctx) == 2 * (1 + 2)


@pytest.mark.parametrize("metric", sorted(SPAN_METRICS))
def test_span_metric_reads_a_rehearsed_window(window, metric):
    value = _read(metric, window)
    assert value is not None and value == value and value >= 0
    if metric == "span_idle_pct":
        assert 0 < value < 100
    if metric == "background_ms":
        # The telemetry folds alone (four a refresh) are in the ring.
        assert 0 < value < 1000


def test_slowest_refresh_is_laid_over_the_ring(window, monkeypatch):
    from pixie_tpu.exec import trace

    refreshes = window["window"]["refreshes"]
    slow = max(refreshes, key=lambda r: r[-1]["t1"] - r[0]["t0"])
    lo, hi = slow[0]["t0"] * 1e9, slow[-1]["t1"] * 1e9
    ring = trace.BackgroundRing()
    ring.record("heartbeat", int(lo) - 5_000_000, int(lo) + 2_000_000)
    ring.record("heartbeat.bus_fold", int(lo), int(lo) + 1_000_000)  # inside
    ring.record("gc.gen2", int(hi) - 3_000_000, int(hi) + 9_000_000)
    ring.record("tracker.sweep", int(hi) + 20_000_000, int(hi) + 21_000_000)
    monkeypatch.setattr(trace, "background", ring)
    assert _read("slowest_refresh_background_ms", window) == pytest.approx(
        2.0 + 3.0, abs=1e-3
    )
    # Over the window: the union of the entries (the fold inside its
    # heartbeat counts once), cut at the window's ends.
    w = window["window"]
    w_lo, w_hi = w["t_open"] * 1e9, w["t_close"] * 1e9
    inside = sum(
        max(0.0, min(b, w_hi) - max(a, w_lo)) for a, b in (
            (lo - 5e6, lo + 2e6), (hi - 3e6, hi + 9e6),
            (hi + 20e6, hi + 21e6),
        )
    )
    assert _read("background_ms", window) == pytest.approx(
        inside / 1e6 / (w["t_close"] - w["t_open"]), rel=1e-3
    )


def test_a_program_without_the_spans_reads_nothing(window, monkeypatch):
    """The parent commit's traces (no one clock, no ``device.*`` spans,
    no ring): every new reader returns None and none raises."""
    from pixie_tpu.exec import trace

    def old(t):
        root = types.SimpleNamespace(span_id="r", start_unix_nano=1,
                                     end_unix_nano=2, name="query",
                                     parent_id="")
        return types.SimpleNamespace(qid=t.qid, kind=t.kind, root=root,
                                     spans=[root], duration_s=t.duration_s)

    ctx = dict(window, spans={k: [old(t) for t in v]
                              for k, v in window["spans"].items()})
    monkeypatch.delattr(trace, "background")
    for metric in SPAN_METRICS:
        assert _read(metric, ctx) is None, metric
    # The readers the benchmark had still read these traces.
    assert _read("engine_ms", ctx) > 0


def test_benchmark_json_has_the_span_metrics_and_the_four_chip_cell():
    spec = importlib.util.spec_from_file_location(
        "four_chip", os.path.join(os.path.dirname(__file__),
                                  "test_benchmark_run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    four = mod.FOUR_CHIP
    per_layer = {m["name"]: m for m in BENCHMARK["per_layer"]}
    for name, layer in SPAN_METRICS.items():
        m = per_layer[name]
        assert m["layer"] == layer and m["moves"] == "refresh_p50_ms"
        assert m["source"] in ("program_span", "program_counter")
        assert "workloads" not in m and "bound" not in m
    assert per_layer["collective_ms"] == four["per_layer"]
    cell = next(w for w in BENCHMARK["workloads"]
                if w["name"] == four["workload"]["name"])
    assert {k: cell[k] for k in four["workload"]} == four["workload"]
    config = next(c for c in BENCHMARK["configs"]
                  if c["name"] == four["config"]["name"])
    assert config["file"] == four["config"]["file"]
    assert config["reduced"] == ["pem_processes"]
    assert len(config["source"]) <= 200
    cells4 = [w for w in BENCHMARK["workloads"] if w["chips"] == 4]
    assert len(cells4) == 1 <= max(1, len(BENCHMARK["workloads"]) // 2)
    # served_rows_per_s stays where a benchmark issue left it.
    assert per_layer["served_rows_per_s"]["workloads"] == [
        "http_pem_1chip.dash_full"
    ]


# -- span_gaps ----------------------------------------------------------------


def _events():
    ms = 1e6
    return {
        "host": [["bench:traced_window", 0.0, 100 * ms],
                 ["request:a", 0.0, 60 * ms],
                 ["check", 60 * ms, 10 * ms],
                 ["request:a", 80 * ms, 20 * ms]],
        "devices": {
            0: {"modules": [], "ops": [["%fusion.1", 10 * ms, 40 * ms],
                                       ["sort.3", 85 * ms, 10 * ms]]},
            1: {"modules": [], "ops": [["%fusion.1", 10 * ms, 5 * ms]]},
        },
        "program": [["await", 2 * ms, 56 * ms],        # broker thread
                    ["device.dispatch", 8 * ms, 1 * ms],  # PEM thread
                    ["device.wait", 9 * ms, 43 * ms],
                    ["publish", 52 * ms, 4 * ms],
                    ["heartbeat", 70 * ms, 12 * ms],
                    ["finish", 96 * ms, 2 * ms]],
    }


def test_span_gaps_gives_each_idle_piece_to_the_narrowest_cover():
    from benchmark import span_gaps

    r = span_gaps.reduce(_events())
    assert r["window_s"] == pytest.approx(0.100)
    # Chip 0 is the busiest: idle [0, 10), [50, 85), [95, 100).
    assert r["idle_s"] == pytest.approx(0.050)
    assert r["by_span"] == pytest.approx({
        span_gaps.UNCOVERED: 0.002 + 0.012 + 0.003 + 0.001 + 0.002,
        "await": 0.006 + 0.002,            # [2, 8) and [56, 58)
        "device.dispatch": 0.001,          # narrower than await
        "device.wait": 0.001 + 0.002,      # [9, 10) and [50, 52)
        "publish": 0.004,
        "heartbeat": 0.012,                # between the requests
        "finish": 0.002,
    })
    assert sum(r["by_span"].values()) == pytest.approx(r["idle_s"])
    # Inside the two requests: [0, 10), [50, 60), [80, 85), [95, 100);
    # no program span covers [0, 2), [58, 60), [82, 85), [95, 96), [98, 100).
    assert r["in_requests_s"] == pytest.approx(0.030)
    assert r["named_in_requests_s"] == pytest.approx(0.030 - 0.010)
    assert "device.wait" in span_gaps.table(r)


def test_span_gaps_knows_the_programs_names_only():
    from benchmark import span_gaps

    for name in ("device.wait", "await.stats", "heartbeat.bus_fold",
                 "collector.perf_profiler", "telemetry.fold"):
        assert span_gaps.is_program_span(name)
    for name in ("request:http_stats", "check", "bench:traced_window",
                 "PjitFunction(update)", "gc.gen2"):
        assert not span_gaps.is_program_span(name)
    ev = _events()
    ev["host"] = ev["host"][1:]
    with pytest.raises(ValueError, match="traced_window"):
        span_gaps.reduce(ev)


def test_span_gaps_on_a_trace_recorded_on_the_chip():
    """``testdata/``'s second trace (see its README): the first 0.9 s
    of a ``dash_recent`` window, with the program's annotations."""
    from benchmark import span_gaps, xplane

    packed = os.path.join(BENCH, "testdata",
                          "dash_recent_spans_first_0.9s.xplane.pb.gz")
    events = span_gaps.load(packed)
    assert [h[0] for h in events["host"][:3]] == [
        "bench:traced_window", "request:http_stats", "request:service_stats",
    ]
    names = {a[0] for a in events["program"]}
    assert {"query:fragment", "query:merge", "device.dispatch",
            "device.wait", "window.stage", "materialize", "publish",
            "telemetry.fold", "await.results", "register"} <= names
    r = span_gaps.reduce(events)
    # The same gaps xplane.reduce labels by the driver's marks ...
    old = xplane.reduce(events, chips=1)
    assert r["window_s"] == pytest.approx(0.9)
    assert r["idle_s"] == pytest.approx(0.9 - old["busy_s"])
    assert r["idle_s"] == pytest.approx(0.261279455)
    assert r["in_requests_s"] == pytest.approx(
        old["gaps"]["request:http_stats"]
        + old["gaps"]["request:service_stats"]
    )
    # ... one level in: 97 % of the idle time inside requests lies
    # under a span of the program's own.
    assert r["named_in_requests_s"] == pytest.approx(0.240423326)
    assert r["named_in_requests_s"] / r["in_requests_s"] > 0.9
    top = xplane.top(r["by_span"], 4)
    assert [name for name, _ in top] == [
        "device.wait", "query:merge", "query:fragment", "window.stage",
    ]
    assert top[0][1] == pytest.approx(0.07873871)
    assert sum(r["by_span"].values()) == pytest.approx(r["idle_s"])
