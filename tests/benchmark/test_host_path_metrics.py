"""The seven readers of the host path's spans (ISSUE 37: ``bus_ms``,
``pem_head_ms``, ``pem_tail_ms``, ``fetch_ms``, ``fetch_mb``,
``client_ms``, ``unnamed_ms``) on a rehearsed stack, and on a program
without the spans. On the CPU: never a device number from here."""

import importlib
import json
import os
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCHMARK = json.load(f)

#: metric -> (unit, source, layer), as ``BENCHMARK.json`` files it.
HOST_PATH_METRICS = {
    "bus_ms": ("ms", "program_span", "broker path"),
    "pem_head_ms": ("ms", "program_span", "engine"),
    "pem_tail_ms": ("ms", "program_span", "engine"),
    "fetch_ms": ("ms", "program_span", "engine"),
    "fetch_mb": ("MB", "program_counter", "engine"),
    "client_ms": ("ms", "host_clock", "client"),
    "unnamed_ms": ("ms", "program_span", "broker path"),
}
#: What only a program with this PR's spans and counters gives.
NEW_SPANS_ONLY = ("bus_ms", "fetch_ms", "fetch_mb")


def _read(name, ctx):
    return importlib.import_module(f"benchmark.layer_metrics.{name}").read(ctx)


@pytest.fixture(scope="module")
def window():
    """``ctx`` of a rehearsed ``dash_recent`` window of a second on the
    served stack, as ``test_span_readers`` builds it."""
    from test_span_readers import _window

    return _window(1.0)


@pytest.mark.parametrize("metric", sorted(HOST_PATH_METRICS))
def test_host_path_metric_reads_a_rehearsed_window(window, metric):
    value = _read(metric, window)
    assert value is not None and value == value and value >= 0
    if metric != "unnamed_ms":
        assert value > 0


def test_the_parts_lie_inside_what_holds_them(window):
    head, tail, interval, wait = (_read(m, window) for m in (
        "head_ms", "tail_ms", "device_interval_ms", "device_wait_ms",
    ))
    assert _read("pem_head_ms", window) + _read("bus_ms", window) < (
        head + tail
    )
    assert _read("pem_head_ms", window) < head
    assert _read("pem_tail_ms", window) < tail
    # A fetch is inside a wait; the Kelvin's are outside the PEM's
    # interval, so compare the PEM's alone by its traces.
    pem_fetch = sum(
        s.end_ns - s.start_ns for t in window["spans"]["pem"]
        for s in t.spans if s.name == "device.fetch"
    )
    pem_wait = sum(
        s.end_ns - s.start_ns for t in window["spans"]["pem"]
        for s in t.spans if s.name == "device.wait"
    )
    assert 0 < pem_fetch < pem_wait and wait < interval
    assert _read("unnamed_ms", window) < head + interval + tail
    # Two scripts a refresh, a state of a few dozen KB each and the
    # Kelvin's planes: megabytes are not.
    assert 0.01 < _read("fetch_mb", window) < 10


def test_unnamed_is_the_root_less_the_union_of_the_naming_spans():
    """On hand-made traces: spans that hold others or only wait do not
    count; overlapping and out-of-root spans are clipped and counted
    once."""
    ms = 1_000_000

    def span(name, a, b, sid=None):
        return types.SimpleNamespace(name=name, start_ns=a * ms,
                                     end_ns=b * ms, span_id=sid or name,
                                     attributes={})

    def trace(kind, qid, root, spans):
        return types.SimpleNamespace(kind=kind, qid=qid, root=root,
                                     spans=[root, *spans])

    b_root = span("query", 0, 100, "b")
    broker = trace("distributed", "q", b_root, [
        span("compile", 2, 10), span("dispatch", 10, 14),
        span("await", 14, 96), span("await.results", 14, 95),
        span("bus.deliver", 90, 92), span("finish", 96, 99),
        span("trace.sinks", 100, 103),  # after the root: clipped away
    ])
    p_root = span("query", 16, 60, "p")
    pem = trace("fragment", "q", p_root, [
        span("bus.deliver", 12, 15), span("fragment", 18, 58),
        span("device.dispatch", 20, 21), span("device.wait", 21, 50),
        span("device.fetch", 40, 50), span("publish", 61, 63),
    ])
    k_root = span("query", 64, 88, "k")
    kelvin = trace("merge", "q", k_root, [
        span("merge.wait", 15, 64), span("device.wait", 66, 80),
    ])
    ctx = {
        "spans": {"broker": [broker], "pem": [pem], "kelvin": [kelvin]},
        "window": {"refreshes": [[{"qid": "q", "t0": -0.001, "t1": 0.104,
                                   "label": "a"}]]},
    }
    # Named: [2, 15) [20, 50) [61, 63) [66, 80) [90, 92) [96, 99).
    named = 13 + 30 + 2 + 14 + 2 + 3
    assert _read("unnamed_ms", ctx) == pytest.approx(100 - named)
    assert _read("bus_ms", ctx) == pytest.approx(2 + 3)
    assert _read("fetch_ms", ctx) == pytest.approx(10)
    assert _read("pem_head_ms", ctx) == pytest.approx(20 - 16)
    assert _read("pem_tail_ms", ctx) == pytest.approx(63 - 50)
    assert _read("client_ms", ctx) == pytest.approx(1 + 4)


def test_a_program_without_the_spans_reads_nothing(window):
    """The parent commit's traces hold their roots, ``device.*`` spans
    and ``publish`` on the one clock, and none of this PR's spans nor
    its counters: what reads those reports nothing, what the parent's
    spans already give is read, and none raises. A program from before
    the one clock reads nothing at all."""
    new = {"bus.deliver", "device.fetch", "plan.walk", "fragment.bind",
           "state.init", "window.select", "payload", "trace.sinks",
           "pipeline.start",
           "merge.compact", "join.align", "join.assemble", "restream"}

    def parent(t):
        usage = types.SimpleNamespace(**{
            k: v for k, v in vars(t.usage).items()
            if k not in ("bytes_fetched", "fetches")
        })
        return types.SimpleNamespace(
            qid=t.qid, kind=t.kind, root=t.root, usage=usage,
            duration_s=t.duration_s,
            spans=[s for s in t.spans if s.name not in new],
        )

    ctx = dict(window, spans={k: [parent(t) for t in v]
                              for k, v in window["spans"].items()})
    for metric in HOST_PATH_METRICS:
        value = _read(metric, ctx)
        if metric in NEW_SPANS_ONLY:
            assert value is None, metric
        else:
            assert value is not None and value >= 0, metric
    assert _read("unnamed_ms", ctx) > _read("unnamed_ms", window)

    def old(t):
        root = types.SimpleNamespace(span_id="r", start_unix_nano=1,
                                     end_unix_nano=2, name="query",
                                     parent_id="")
        return types.SimpleNamespace(qid=t.qid, kind=t.kind, root=root,
                                     spans=[root], duration_s=t.duration_s)

    ctx = dict(window, spans={k: [old(t) for t in v]
                              for k, v in window["spans"].items()})
    for metric in HOST_PATH_METRICS:
        assert _read(metric, ctx) is None, metric


def test_benchmark_json_files_the_seven_at_the_end():
    names = [m["name"] for m in BENCHMARK["per_layer"]]
    assert names[-7:] == ["bus_ms", "pem_head_ms", "pem_tail_ms", "fetch_ms",
                          "fetch_mb", "client_ms", "unnamed_ms"]
    per_layer = {m["name"]: m for m in BENCHMARK["per_layer"]}
    for name, (unit, source, layer) in HOST_PATH_METRICS.items():
        assert per_layer[name] == {
            "name": name, "unit": unit, "better": "lower", "source": source,
            "layer": layer, "moves": "refresh_p50_ms",
        }
