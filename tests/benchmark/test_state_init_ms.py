"""``state_init_ms`` (ISSUE 48): the time inside a refresh's ``state.init``
spans, on every PEM tracer of the stack and on the Kelvin's merge trace.
On rehearsed windows of a one-PEM cell, of the cell whose Kelvin folds the
join's rows again and of the four-node cell; on hand-made traces; on a
program without the span. On the CPU: never a device number from here."""

import importlib
import json
import os
import types

import numpy as np
import pytest

from test_fold_fill import _window
from test_http_cluster import FILED

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCHMARK = json.load(f)

#: cell -> (the tracers whose traces hold a ``state.init``, folds a
#: refresh on them: a script's PEM fragments with an AggOp, the Kelvin's
#: re-aggregation).
CELLS = {
    "http_pem_1chip.dash_recent": (("pem",), 2),
    "conn_flow_1chip.flow_recent": (("pem", "kelvin"), 3),
    "http_cluster_4chip.cluster_recent": (
        ("pem", "pem.1", "pem.2", "pem.3"), 8),
}


def _read(name, ctx):
    return importlib.import_module(f"benchmark.layer_metrics.{name}").read(ctx)


def test_the_metric_is_filed_under_the_engine_after_what_was_there():
    """New entries go last: what was filed is a prefix of the list,
    relatively, so that the next PR supersedes nothing. It lists no
    cells: every cell's folds stamp the span, the parent's too."""
    names = [m["name"] for m in BENCHMARK["per_layer"]]
    filed = list(FILED["per_layer"]) + ["merge_rebins", "state_init_ms"]
    assert names[:len(filed)] == filed
    assert len(set(names)) == len(names)
    assert BENCHMARK["per_layer"][len(filed) - 1] == {
        "name": "state_init_ms", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "engine",
        "moves": "refresh_p50_ms",
    }
    assert os.path.exists(os.path.join(
        ROOT, "benchmark", "layer_metrics", "state_init_ms.py"))


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_rehearsed_window_reads_the_spans_of_every_engine(cell):
    ctx = _window(cell)
    tracers, folds = CELLS[cell]
    value = _read("state_init_ms", ctx)
    assert value is not None and value == value and value > 0
    spans = {
        tracer: [s for t in ctx["spans"][tracer] for s in t.spans
                 if s.name == "state.init"]
        for tracer in ctx["spans"]
    }
    assert {k for k, v in spans.items() if v} == set(tracers)
    refreshes = ctx["window"]["refreshes"]
    qids = {r["qid"] for recs in refreshes for r in recs}
    mine = [s for v in spans.values() for s in v]
    assert len(mine) >= folds * len(refreshes)
    # One program a span, its leaves the state's.
    for s in mine:
        assert s.attributes["programs"] == 1 and s.attributes["leaves"] >= 3
    # The reader's number is the spans' own, a refresh at a time.
    by_qid = {}
    for tracer in tracers:
        for t in ctx["spans"][tracer]:
            if t.qid in qids:
                by_qid[t.qid] = by_qid.get(t.qid, 0) + sum(
                    s.end_ns - s.start_ns for s in t.spans
                    if s.name == "state.init")
    sums = [sum(by_qid[r["qid"]] for r in recs) / 1e6 for recs in refreshes]
    assert value == pytest.approx(float(np.percentile(sums, 50)))
    # Inside what holds it: the PEMs' before their first dispatch.
    if tracers == ("pem",):
        assert value < _read("pem_head_ms", ctx)


def _span(name, a, b, **attributes):
    ms = 1_000_000
    return types.SimpleNamespace(name=name, start_ns=a * ms, end_ns=b * ms,
                                 span_id=f"{name}{a}", attributes=attributes)


def _trace(kind, qid, a, b, spans):
    root = _span("query", a, b)
    return types.SimpleNamespace(kind=kind, qid=qid, root=root,
                                 spans=[root, *spans])


def test_it_sums_the_pems_and_the_kelvins_spans_a_refresh():
    """Two requests a refresh on hand-made traces: two folds on ``pem``,
    one on ``pem.1``, one on the Kelvin; a span that never ended and a
    trace of another kind are left out; the median over two refreshes."""
    def refresh(n, pem_fold):
        a, b = f"a{n}", f"b{n}"
        return {
            "pem": [
                _trace("fragment", a, 0, 50, [
                    _span("state.init", 2, 2 + pem_fold),
                    _span("device.dispatch", 6, 7),
                    _span("state.init", 20, 21.5)]),
                _trace("fragment", b, 60, 90, [_span("state.init", 61, 62)]),
                _trace("query", a, 0, 50, [_span("state.init", 0, 40)]),
            ],
            "pem.1": [
                _trace("fragment", a, 0, 40, [_span("state.init", 3, 3.25)]),
                _trace("fragment", b, 60, 80, [_span("device.wait", 61, 70)]),
            ],
            "kelvin": [
                _trace("merge", a, 50, 58, [
                    _span("state.init", 51, 53),
                    types.SimpleNamespace(name="state.init", start_ns=1,
                                          end_ns=0, span_id="open",
                                          attributes={})]),
                _trace("merge", b, 90, 95, []),
            ],
            "broker": [_trace("distributed", a, 0, 59, []),
                       _trace("distributed", b, 59, 96, [])],
        }, [{"qid": a, "t0": 0, "t1": 0.06, "label": "x"},
            {"qid": b, "t0": 0.06, "t1": 0.1, "label": "y"}]

    spans, refreshes = {}, []
    for n, pem_fold in enumerate((3, 1)):
        more, recs = refresh(n, pem_fold)
        refreshes.append(recs)
        for k, v in more.items():
            spans.setdefault(k, []).extend(v)
    ctx = {"spans": spans, "window": {"refreshes": refreshes}}
    # A refresh: pem (fold + 1.5 + 1) + pem.1 0.25 + kelvin 2.
    assert _read("state_init_ms", ctx) == pytest.approx(
        ((3 + 4.75) + (1 + 4.75)) / 2)
    one_pem = {k: v for k, v in spans.items() if k != "pem.1"}
    assert _read("state_init_ms", {**ctx, "spans": one_pem}) == pytest.approx(
        ((3 + 4.5) + (1 + 4.5)) / 2)


def test_it_reads_nothing_on_a_program_without_the_span():
    """A program from before the span stamps none on any engine: the
    reader returns None, raises nothing, and the line leaves the metric
    out; so does one from before the one clock."""
    ctx = _window("http_pem_1chip.dash_recent")

    def without(t):
        return types.SimpleNamespace(
            qid=t.qid, kind=t.kind, root=t.root, usage=t.usage,
            duration_s=t.duration_s,
            spans=[s for s in t.spans if s.name != "state.init"])

    bare = {**ctx, "spans": {k: [without(t) for t in v]
                             for k, v in ctx["spans"].items()}}
    assert _read("state_init_ms", bare) is None
    assert _read("pem_head_ms", bare) is not None

    def old(t):
        root = types.SimpleNamespace(span_id="r", start_unix_nano=1,
                                     end_unix_nano=2, name="query",
                                     parent_id="")
        return types.SimpleNamespace(qid=t.qid, kind=t.kind, root=root,
                                     spans=[root], duration_s=t.duration_s)

    older = {**ctx, "spans": {k: [old(t) for t in v]
                              for k, v in ctx["spans"].items()}}
    assert _read("state_init_ms", older) is None
