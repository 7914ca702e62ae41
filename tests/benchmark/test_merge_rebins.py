"""``merge_rebins`` (ISSUE 47): the ``merge_ordered`` runs of the Kelvin's
k-way folds, from the ``rebins`` of their ``device.wait`` spans
(``usage.merge_rebins``). On ``test_http_cluster.py``'s rehearsed window
of the four-node cell under the chip's routes (its service graph's edges
are disjoint by node: 0 runs, though ``px/http_stats``' groups overlap),
on a one-PEM cell's window and on a program without the counter. On the
CPU: never a device number from here."""

import copy
import dataclasses
import importlib
import json
import os
import types

import pytest

from test_http_cluster import (  # noqa: F401
    CELL, FILED, NEW_METRICS, NODES, window,
)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCHMARK = json.load(f)


def _read(ctx):
    return importlib.import_module(
        "benchmark.layer_metrics.merge_rebins").read(ctx)


def test_the_metric_is_filed_under_the_engine_after_what_was_there():
    """New entries go last: what was filed is a prefix of the list
    (``test_http_cluster.py``'s test of the lists, which holds the
    per-layer one to its own length, ``tests/conftest.py`` marks
    superseded), relatively, so that the next PR supersedes nothing."""
    names = [m["name"] for m in BENCHMARK["per_layer"]]
    filed = list(FILED["per_layer"]) + ["merge_rebins"]
    assert names[:len(filed)] == filed
    assert len(set(names)) == len(names)
    assert BENCHMARK["per_layer"][len(filed) - 1] == {
        "name": "merge_rebins", "unit": "merges", "better": "lower",
        "source": "program_counter", "layer": "engine",
        "moves": "refresh_p50_ms", "workloads": [CELL],
    }


def test_nothing_that_was_filed_before_lists_the_four_node_cell():
    """``test_http_cluster.py``'s test of (nearly) this name with one more
    name among the cell's own (``tests/conftest.py`` marks it
    superseded): the cell reads every per-layer metric that lists no
    cells, PR 46's five and this one; no accepted entry was edited to
    take it in."""
    own = set(NEW_METRICS) | {"merge_rebins"}
    for m in BENCHMARK["per_layer"] + BENCHMARK["end_to_end"]:
        if m["name"] not in own:
            assert CELL not in m.get("workloads", []), m["name"]
    assert len(next(m for m in BENCHMARK["per_layer"]
                    if m["name"] == "http_stats_p50_ms")["workloads"]) == 4


def test_disjoint_edges_rebin_nothing_and_overlapping_groups_are_counted(
        window):  # noqa: F811
    """Four nodes' states, one k-way fold a script: the service graph's
    merge joins no slot and runs ``merge_ordered`` never; ``px/http_stats``'
    merge joins most of its slots and holds no digest to re-bin."""
    kelvin = window["spans"]["kelvin"]
    assert len(kelvin) >= 2
    for graph, stats in zip(kelvin[0::2], kelvin[1::2]):
        waits = []
        for t in (graph, stats):
            (wait,) = [s for s in t.spans if s.name == "device.wait"]
            waits.append(wait.attributes)
            assert t.usage.merge_rebins == wait.attributes["rebins"] == 0
            assert t.usage.merge_payloads == NODES
        assert waits[0]["contended_slots"] == 0 < waits[1]["contended_slots"]
    assert _read(window) == 0.0
    for tracer in ("pem", "pem.1", "pem.2", "pem.3", "broker"):
        for t in window["spans"][tracer]:
            assert not any(
                {"rebins", "contended_slots"} & set(s.attributes)
                for s in t.spans), tracer


def test_a_contended_digest_is_counted(window):  # noqa: F811
    """The reader sums a refresh's requests: a merge whose wait says
    three runs reads 3 (no cell's traffic joins a digest; the fold that
    does is held by ``tests/test_bridge_merge.py``)."""
    ctx = {**window, "spans": dict(window["spans"])}
    last = window["window"]["refreshes"][-1]
    kelvin = []
    for t in window["spans"]["kelvin"]:
        t = copy.copy(t)
        if t.qid == last[0]["qid"]:
            t.usage = copy.copy(t.usage)
            t.usage.merge_rebins = NODES - 1
        kelvin.append(t)
    ctx["spans"]["kelvin"] = kelvin
    ctx["window"] = {**window["window"], "refreshes": [last]}
    assert _read(ctx) == NODES - 1


def test_it_reads_nothing_on_a_program_without_the_counter(
        window):  # noqa: F811
    """The parent's usage record has no ``merge_rebins``: the reader
    returns None and the line leaves the metric out."""
    ctx = {**window, "spans": dict(window["spans"])}
    kelvin = []
    for t in window["spans"]["kelvin"]:
        t = copy.copy(t)
        t.usage = types.SimpleNamespace(**{
            k: v for k, v in dataclasses.asdict(t.usage).items()
            if k != "merge_rebins"})
        kelvin.append(t)
    ctx["spans"]["kelvin"] = kelvin
    assert _read(ctx) is None


@pytest.mark.parametrize("cell", ["http_pem_1chip.dash_recent"])
def test_one_pem_folds_nothing_and_reads_zero(cell):
    """A one-PEM cell's merges fold nothing: no wait says ``rebins``, the
    usage reads 0 (the metric lists the four-node cell alone)."""
    from test_fold_fill import _window

    ctx = _window(cell)
    merges = [t for t in ctx["spans"]["kelvin"] if t.kind == "merge"]
    assert merges
    for t in merges:
        assert t.usage.merge_rebins == 0
        assert not any("rebins" in s.attributes for s in t.spans)
    assert _read(ctx) == 0.0
    entry = next(m for m in BENCHMARK["per_layer"]
                 if m["name"] == "merge_rebins")
    assert cell not in entry["workloads"]
