"""``fold_fill_pct`` (ISSUE 44): the rows in the requests' ranges over
the rows the PEM's fold programs were handed, from the ``range_rows``
and ``rows`` attributes of their ``device.dispatch`` spans. On rehearsed
windows of three cells, on an engine that hands its programs whole
windows, and on a program whose spans carry neither attribute. On the
CPU: never a device number from here."""

import copy
import importlib
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCHMARK = json.load(f)

#: The rehearsals' window (its capacity too) and table.
WINDOW_ROWS, ROWS = 1 << 13, 1 << 15


def _read(ctx):
    return importlib.import_module(
        "benchmark.layer_metrics.fold_fill_pct").read(ctx)


def _window(cell, before=None):
    """``ctx`` of a rehearsed window of ``cell``, as ``harness.run_cell``
    builds it (the parts the span readers use). ``before(stack)`` runs on
    the fresh stack."""
    from benchmark import harness
    from pixie_tpu.config import override_flag

    spec = harness.load_cell(cell)
    cfg, traffic = spec["config"], spec["traffic"]
    builder = harness.module("builders", cfg["builder"])
    driver = harness.module("drivers", traffic["driver"])
    with override_flag("cpu_fold_threads", 1):
        stack = builder.build(cfg, WINDOW_ROWS)
        try:
            if before is not None:
                before(stack)
            stack.ingest(builder.make_data(cfg, 4_400_000_019, ROWS))
            requests = harness.requests_of(spec)
            log = harness.SpanLog(stack.tracers)
            _lo, now_ns = harness.range_lo_ns(cfg, traffic)
            driver.refresh(stack, requests, now_ns, 120, harness.mark)
            log.cut()
            window = driver.run(stack, traffic, requests, 0.3, now_ns,
                                harness.mark)
            spans = log.cut()
        finally:
            stack.close()
    assert window["failed"] == 0 and window["refreshes"]
    return {"window": window, "spans": spans, "trace": None}


def _whole_windows(stack):
    stack.pem.engine.slice_windows = False


@pytest.fixture(scope="module")
def recent():
    return _window("http_pem_1chip.dash_recent")


def test_the_metric_is_filed_under_the_engine():
    entry = BENCHMARK["per_layer"][-1]
    assert entry == {
        "name": "fold_fill_pct", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "engine",
        "moves": "refresh_p50_ms",
    }


#: cell -> (rows in a script's range, rows its fold is handed, scripts).
#: '-5m' of the hour the 2^15 rows span is 2,731 rows, inside the last
#: window: over a quarter of 2^13, so a half; the whole table is four
#: full windows.
CELLS = {
    "http_pem_1chip.dash_recent": (2_731, 1 << 12, 2),
    "http_pem_1chip.dash_full": (ROWS, ROWS, 2),
    "sql_stats_1chip.sql_recent": (None, 1 << 12, 1),
}


@pytest.mark.parametrize("cell", CELLS)
def test_fold_fill_is_the_rows_in_range_over_the_rows_folded(cell, recent):
    ctx = recent if cell.endswith("dash_recent") else _window(cell)
    in_range, folded, scripts = CELLS[cell]
    folds = [s.attributes for t in ctx["spans"]["pem"] for s in t.spans
             if s.name == "device.dispatch" and "rows" in s.attributes]
    assert folds and all("fold" in a for a in folds)
    requests = len(ctx["spans"]["pem"])  # a trace a request
    assert requests % scripts == 0
    # (A window a dispatch on the CPU's routes: four make up the table.)
    assert {a["rows"] for a in folds} <= {folded, WINDOW_ROWS}
    assert sum(a["rows"] for a in folds) == folded * requests
    if in_range is None:  # the builder's own rate: what the spans say
        in_range = folds[0]["range_rows"]
        assert folded // 2 < in_range <= folded
    assert sum(a["range_rows"] for a in folds) == in_range * requests
    assert _read(ctx) == pytest.approx(100.0 * in_range / folded, rel=1e-12)


def test_whole_windows_read_the_padding_too():
    """An engine that hands its programs every window whole (as the mesh
    step does) stamps the capacity: the same rows in range, half the
    fill."""
    ctx = _window("http_pem_1chip.dash_recent", before=_whole_windows)
    assert _read(ctx) == pytest.approx(100.0 * 2_731 / WINDOW_ROWS, rel=1e-12)


def _without(ctx, *attributes):
    stripped = {"window": ctx["window"], "trace": None, "spans": {}}
    for tracer, traces in ctx["spans"].items():
        out = []
        for t in traces:
            t = copy.copy(t)
            t.spans = [copy.copy(s) for s in t.spans]
            for s in t.spans:
                s.attributes = {k: v for k, v in s.attributes.items()
                                if k not in attributes}
            out.append(t)
        stripped["spans"][tracer] = out
    return stripped


def test_the_reader_reads_nothing_on_a_program_without_the_counts(recent):
    """The parent's dispatch spans carry no ``rows``: nothing, and no
    exception; nor on a window of which one request's spans are lost."""
    assert _read(_without(recent, "rows", "range_rows")) is None
    assert _read({**recent, "spans": {**recent["spans"], "pem": []}}) is None
    lost = copy.copy(recent)
    lost["spans"] = {**recent["spans"], "pem": recent["spans"]["pem"][1:]}
    first = recent["window"]["refreshes"][0]
    assert any(r["qid"] == recent["spans"]["pem"][0].qid for r in first)
    assert _read(lost) == _read(recent)  # the median of the other refreshes
