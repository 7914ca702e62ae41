"""Configuration ``http_cluster_4chip`` and its cell: the file's arithmetic
and source against ``BENCHMARK.json``, ``http_pem_4chip``'s four nodes and
``http_edges_1chip``'s cluster, the nodes its builder parts the cluster's
one stream into (a dictionary of its own a node), one PEM against four,
the five readers this configuration brought on a rehearsed window of four
PEMs, each on a device of its own, and a rehearsal of the cell, sound and
with the timed path broken underneath. On the CPU's eight host devices
(the TPU's routes by substituting ``ops/routes.py`` ``routes_platform``):
never a device number from here."""

import copy
import dataclasses
import importlib
import inspect
import json
import os
import time
import types

import numpy as np
import pytest

from conftest import routes_of
from test_http_edges import FILED as FILED_BEFORE
from test_stack_flame import _cut_the_answer

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
BENCH = os.path.join(ROOT, "benchmark")
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCHMARK = json.load(f)


def _config(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


CFG = _config("http_cluster_4chip")
CELL = "http_cluster_4chip.cluster_recent"
BIG = 4_600_000_019  # the driver's seeds pass 2**31
NEW_METRICS = {
    "merge_payloads": ("payloads", "program_counter", "broker path", "lower"),
    "merge_remap_entries": ("entries", "program_counter", "engine", "lower"),
    "merge_upload_mb": ("MB", "program_counter", "engine", "lower"),
    "pem_spread_ms": ("ms", "program_span", "broker path", "lower"),
    "pem_devices": ("devices", "program_counter", "engine", "higher"),
}
EXACT = ("service_graph.keys_differ", "service_graph.throughput_differ",
         "service_graph.bytes_differ", "http_stats.keys_differ",
         "http_stats.n_differ", "http_stats.lat_max_differ")
RANK = tuple(f"service_graph.{p}_rank_err" for p in ("p50", "p90", "p99"))
LO_NS = CFG["t_end_ns"] - 300 * 10**9
NODES = 4


def _builder():
    from benchmark.builders import served_http_nodes

    return served_http_nodes


def _make(seed, rows, cfg=CFG):
    return _builder().make_data(cfg, seed, rows)


def _entry(kind, name):
    return next(e for e in BENCHMARK[kind] if e["name"] == name)


# -- the files ----------------------------------------------------------------

#: ``BENCHMARK.json``'s lists as they were filed, PR by PR
#: (``test_http_edges.py``'s, and this PR's entries after them: new entries
#: go last, so what was filed is a PREFIX of what is there).
FILED = {
    "configs": FILED_BEFORE["configs"] + ("http_cluster_4chip",),
    "workloads": FILED_BEFORE["workloads"] + (CELL,),
    "per_layer": FILED_BEFORE["per_layer"] + (
        "fold_fill_pct",  # PR 44's one
        "merge_payloads", "merge_remap_entries", "merge_upload_mb",
        "pem_spread_ms", "pem_devices"),
    "end_to_end": FILED_BEFORE["end_to_end"],
}


@pytest.mark.parametrize("kind", sorted(FILED))
def test_what_was_filed_is_a_prefix_of_the_list(kind):
    names = [e["name"] for e in BENCHMARK[kind]]
    assert names[:len(FILED[kind])] == list(FILED[kind])
    assert len(set(names)) == len(names)
    # One configuration, one cell and five per-layer metrics were
    # appended (after PR 44's ``fold_fill_pct``), and nothing else.
    assert len(FILED[kind]) - len(FILED_BEFORE[kind]) == {
        "configs": 1, "workloads": 1, "per_layer": 6, "end_to_end": 0}[kind]
    assert len(names) == len(FILED[kind])


def test_benchmark_json_has_the_span_metrics_and_two_four_chip_cells():
    """``test_span_readers.py``'s test of (nearly) this name, every
    assertion of it but ``len(cells4) == 1`` (``tests/conftest.py`` marks
    it superseded for that line): two of nine cells ask for four chips."""
    from test_benchmark_run import FOUR_CHIP as four
    from test_span_readers import SPAN_METRICS

    per_layer = {m["name"]: m for m in BENCHMARK["per_layer"]}
    for name, layer in SPAN_METRICS.items():
        m = per_layer[name]
        assert m["layer"] == layer and m["moves"] == "refresh_p50_ms"
        assert m["source"] in ("program_span", "program_counter")
        assert "workloads" not in m and "bound" not in m
    assert per_layer["collective_ms"] == four["per_layer"]
    cell = _entry("workloads", four["workload"]["name"])
    assert {k: cell[k] for k in four["workload"]} == four["workload"]
    config = _entry("configs", four["config"]["name"])
    assert config["file"] == four["config"]["file"]
    assert config["reduced"] == ["pem_processes"]
    assert len(config["source"]) <= 200
    cells4 = [w for w in BENCHMARK["workloads"] if w["chips"] == 4]
    assert [w["name"] for w in cells4] == [four["workload"]["name"], CELL]
    assert len(cells4) == 2 <= len(BENCHMARK["workloads"]) // 2
    assert len(BENCHMARK["workloads"]) == 9
    assert per_layer["served_rows_per_s"]["workloads"] == [
        "http_pem_1chip.dash_full"
    ]


def test_fold_fill_pct_is_as_it_was_filed():
    """``test_fold_fill.py``'s ``test_the_metric_is_filed_under_the_engine``
    but for its ``per_layer[-1]`` (``tests/conftest.py`` marks it
    superseded; the order is held above)."""
    assert _entry("per_layer", "fold_fill_pct") == {
        "name": "fold_fill_pct", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "engine",
        "moves": "refresh_p50_ms",
    }


def test_the_file_agrees_with_benchmark_json_and_the_two_it_is_made_of():
    pem4, edges = _config("http_pem_4chip"), _config("http_edges_1chip")
    full = _config("http_full_1chip")
    entry = _entry("configs", CFG["name"])
    assert entry["source"] == CFG["source"] and len(CFG["source"]) <= 200
    for part in ("k8s/vizier/pem/base/pem_daemonset.yaml", "a PEM a node",
                 "src/carnot/planner/distributed", "px/cluster",
                 "service_let_graph", "px/http_stats",
                 "PL_TABLE_STORE_DATA_LIMIT_MB=1280"):
        assert part in CFG["source"], part
    assert entry["file"] == "benchmark/configs/http_cluster_4chip.json"
    assert entry["reduced"] == ["pem_processes"] == list(CFG["reduced"])
    for said in ("threads of ONE host process", "upper bounds",
                 "rows is NOT reduced"):
        assert said in CFG["reduced"]["pem_processes"], said
    cell = _entry("workloads", CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "http_cluster_4chip", "cluster_recent", 4)
    assert cell["why"] == (
        "closed loop, 1 client, -5m (2.6 M rows on 4 PEMs, a chip each): "
        "service graph then http_stats; k = 4 keyed and digest states, "
        "dictionaries that differ, one Kelvin merge each: only across agents")
    for e in (cell, entry):
        assert len(e["why"]) <= 200 and "\n" not in e["why"]
    # http_pem_4chip's four nodes, row for row in size.
    for k in ("table", "rows", "window_rows", "columns", "bytes_per_row",
              "nodes", "budget_bytes_per_node", "span_s", "t_end_ns",
              "chips"):
        assert CFG[k] == pem4[k], k
    assert CFG["rows"] == 4 * 7_895_160 == 31_580_640
    assert CFG["budget_bytes_per_node"] == 512 << 20
    assert CFG["rows"] // CFG["span_s"] == 8_772
    assert (CFG["chips"], CFG["nodes"], CFG["engine"]) == (4, 4, "Engine")
    # http_edges_1chip's cluster and skew, key for key.
    assert CFG["values"] == edges["values"]
    assert CFG["max_output_rows"] == edges["max_output_rows"] == (
        full["max_output_rows"]) == 131_072
    # The guarantees of the two scripts' configurations, word for word,
    # and the cluster's own.
    g = CFG["guarantees"]
    assert g["complete"] == edges["guarantees"]["complete"] == (
        full["guarantees"]["complete"])
    assert {"complete": g["complete"], **g["service_graph"]} == (
        edges["guarantees"])
    assert {"complete": g["complete"], **g["http_stats"]} == (
        full["guarantees"])
    assert "every node's rows" in g["every_node"]
    assert "partial and not correct" in g["every_node"]
    builder = _builder()
    assert set(CFG["requires"]) == {
        "joint_key_sizing", "keyed_digest_fold", "engine_device"
    } <= set(builder.CAPABILITIES)
    assert all(check() for check in builder.CAPABILITIES.values())
    for k in ("joint_key_sizing", "keyed_digest_fold"):
        assert CFG["requires"][k] == edges["requires"][k]
    for k in ("node_assignment", "dictionaries", "kelvin", "events_per_s",
              "table_store_data_limit_mb", "script", "values", "clients",
              "requires"):
        assert CFG["assumed"][k], k
    assert "8772 events/s" in CFG["assumed"]["events_per_s"]
    assert "node 0's chip" in CFG["assumed"]["kelvin"]
    for k in ("script", "script_departures", "values", "key_skew",
              "path_ownership", "services", "permutation", "clients"):
        assert CFG["assumed"][k] == edges["assumed"][k], k


def test_the_shares_and_the_budget_are_the_files():
    """The nodes' shares by the law, the rows the builder counted at full
    size (three seeds, stated in the file) and the one flag: the least
    budget whose 40 % holds the fullest node."""
    from pixie_tpu.ingest.schemas import table_budgets

    builder = _builder()
    shares = builder.node_shares(CFG)
    stated = CFG["node_shares"]
    assert shares == pytest.approx(stated["by_the_law"], abs=1e-12)
    assert sum(shares) == pytest.approx(1.0)
    assert [round(s, 3) for s in shares] == [0.26, 0.262, 0.246, 0.231]
    assert stated["seeds"] == [1, 2, 3]
    for rows, share in zip(stated["node_rows"], stated["node_share"]):
        assert sum(rows) == CFG["rows"]
        assert share == [round(r / CFG["rows"], 4) for r in rows]
        # As drawn, a node's share is the law's to a tenth of a percent
        # of the cluster's rows: the same cluster whatever the seed.
        assert np.allclose(share, shares, atol=1e-3)
        # Every node's table ends inside its fourth window, and its last
        # five minutes (a twelfth of its rows) lie inside that window,
        # over half of it: one 2^20-row slice a node on every seed.
        for r in rows:
            assert 3 * CFG["window_rows"] < r * 11 // 12
            assert r < 4 * CFG["window_rows"] - 50_000
            assert (1 << 19) * 1.1 < r // 12 < (1 << 20)
    assert stated["fullest_node_rows"] == [max(r) for r in
                                           stated["node_rows"]]
    limit = CFG["flags"]["table_store_data_limit_mb"]
    assert CFG["flags"] == {"table_store_data_limit_mb": limit}
    assert limit == builder.least_data_limit_mb(CFG) == 1_357
    held = table_budgets(limit)["http_events"] // CFG["bytes_per_row"]
    room = stated["fullest_node_rows_with_headroom"]
    assert max(stated["fullest_node_rows"]) < room <= held
    assert table_budgets(limit - 1)["http_events"] < (
        room * CFG["bytes_per_row"])
    # The upstream default would lose rows on the fullest node.
    assert table_budgets(1_280)["http_events"] // CFG["bytes_per_row"] == (
        7_895_160) < min(stated["fullest_node_rows"])
    counted = CFG["groups_in_range"]
    assert counted["rows_in_range"] == 2_631_741
    for total, by_node in (("live_edges", "node_live_edges"),
                           ("http_stats_groups", "node_http_stats_groups")):
        assert len(counted[total]) == len(counted[by_node]) == 3
    # An edge holds its pod: the nodes' edges are disjoint, and add up.
    for total, nodes in zip(counted["live_edges"],
                            counted["node_live_edges"]):
        assert sum(nodes) == total <= counted["possible_edges"] == 81_920
    # (service, req_path) is seen by every node: the groups overlap.
    for total, nodes in zip(counted["http_stats_groups"],
                            counted["node_http_stats_groups"]):
        assert max(nodes) < total <= counted["possible_groups"] == 65_536
        assert 2.9 * total < sum(nodes) < 4 * total
        # Far from the power of two a PEM's capacity would flip at: the
        # joint-key sketch's estimate (1.6 % standard error) with its
        # 1.25 head-room stays under 2^16 slots by five of them.
        assert all(n * 1.25 * 1.08 < 1 << 16 for n in nodes)
    for rows in counted["node_rows_in_range"]:
        assert sum(rows) == counted["rows_in_range"]


@pytest.mark.parametrize("metric", sorted(NEW_METRICS))
def test_the_new_metrics_are_filed_under_their_layers(metric):
    unit, source, layer, better = NEW_METRICS[metric]
    assert _entry("per_layer", metric) == {
        "name": metric, "unit": unit, "better": better, "source": source,
        "layer": layer, "moves": "refresh_p50_ms", "workloads": [CELL],
    }
    assert layer in {m["layer"] for m in BENCHMARK["per_layer"]
                     if m["name"] not in NEW_METRICS}
    assert callable(importlib.import_module(
        f"benchmark.layer_metrics.{metric}").read)


def test_nothing_the_benchmark_had_lists_the_new_cell():
    """The cell reads every per-layer metric that lists no cells and its
    own five; no accepted entry was edited to take it in
    (``http_stats_p50_ms`` lists four cells and stays as it is)."""
    for m in BENCHMARK["per_layer"] + BENCHMARK["end_to_end"]:
        if m["name"] not in NEW_METRICS:
            assert CELL not in m.get("workloads", []), m["name"]
    assert len(_entry("per_layer", "http_stats_p50_ms")["workloads"]) == 4


def test_the_traffic_is_the_issues():
    from benchmark import harness

    spec = harness.load_cell(CELL)
    traffic = spec["traffic"]
    assert {k: traffic[k] for k in (
        "driver", "clients", "think_ms", "now", "range_s", "timeout_s",
        "trace_seconds", "warmup_extra")} == {
        "driver": "closed_loop", "clients": 1, "think_ms": 0,
        "now": "t_end_ns", "range_s": 300, "timeout_s": 240,
        "trace_seconds": 8, "warmup_extra": 2}
    graph, stats = traffic["scripts"]
    assert (graph["label"], graph["reference"]) == (
        "service_graph", "px_service_graph")
    assert (stats["label"], stats["reference"]) == (
        "http_stats", "px_http_stats")
    # The scripts and what they read are the accepted cells', copied.
    for script, cell in ((graph, "http_edges_1chip.graph_recent"),
                         (stats, "http_full_1chip.dash_recent")):
        other = harness.load_cell(cell)
        theirs = next(s for s in other["traffic"]["scripts"]
                      if s["label"] == script["label"])
        assert script["reads"] == theirs["reads"]
        with open(os.path.join(spec["traffic_dir"], script["pxl"])) as f, \
                open(os.path.join(other["traffic_dir"], theirs["pxl"])) as g:
            assert f.read() == g.read()
    for request in harness.requests_of(spec):
        assert "px.DataFrame(table='http_events', start_time='-5m')" in (
            request["pxl"])


def test_a_program_without_a_devices_engine_is_refused_at_once(monkeypatch):
    """The parent's program under these benchmark files: it exits with
    the file's reason and another code than 0 before a row is made."""
    from pixie_tpu.exec import engine

    def parents_init(self, registry=None, window_rows=None,
                     pipeline_depth=None):
        raise AssertionError("no engine is built")

    monkeypatch.setattr(engine.Engine, "__init__", parents_init)
    assert "device" not in inspect.signature(engine.Engine.__init__).parameters
    t = time.perf_counter()
    with pytest.raises(SystemExit, match="engine_device") as e:
        _make(7, CFG["rows"])
    assert e.value.code not in (0, None)
    assert CFG["requires"]["engine_device"] in str(e.value.code)
    assert time.perf_counter() - t < 1.0


# -- the data: one stream, parted by node ------------------------------------

@pytest.fixture(scope="module")
def parted():
    return _make(BIG, 120_000)


def test_the_union_is_http_edges_stream_and_the_nodes_part_it(parted):
    from benchmark.builders import served_http_edges

    builder = _builder()
    union = served_http_edges.make_data(
        {**_config("http_edges_1chip"), "rows": CFG["rows"], "requires": {}},
        BIG, 120_000)
    for col in ("time_", "remote_addr", "pod", "service", "req_path",
                "latency_ns", "resp_status", "resp_body_size"):
        np.testing.assert_array_equal(parted[col], union[col])
    assert parted["names"] == union["names"]
    node = builder.node_of_pod(CFG, BIG)
    assert node.shape == (4_096,) and set(node.tolist()) == {0, 1, 2, 3}
    # A service's 128 pods spread evenly over the nodes.
    assert (np.bincount(node.reshape(32, 128).ravel() * 32
                        + np.repeat(np.arange(32), 128)) == 32).all()
    parts = parted["parts"]
    assert len(parts) == NODES
    assert sum(len(p["time_"]) for p in parts) == 120_000
    names = parted["names"]
    for n, part in enumerate(parts):
        rows = np.flatnonzero(node[parted["pod"]] == n)
        # Its rows, in their order in the stream, every column.
        for col in ("time_", "latency_ns", "resp_status", "resp_body_size"):
            np.testing.assert_array_equal(part[col], parted[col][rows])
        for i in (0, 1):
            np.testing.assert_array_equal(part["upid"][i],
                                          parted["upid"][i][rows])
        # Its strings are the union's, under ids of its own.
        for col in builder.STRING_COLUMNS:
            mine = np.asarray(part["names"][col], object)[part[col]]
            theirs = np.asarray(names[col], object)[parted[col][rows]]
            assert (mine == theirs).all(), (n, col)


def test_each_nodes_dictionaries_are_its_own(parted):
    """A node's dictionary holds the strings of its rows alone, in the
    order it first saw them; no two nodes' dictionaries of ``pod``,
    ``remote_addr`` or ``req_path`` read alike."""
    from benchmark.builders import served_http_skew
    from pixie_tpu.types.strings import StringDictionary

    for part in parted["parts"]:
        for col in _builder().STRING_COLUMNS:
            codes, strings = part[col], part["names"][col]
            assert len(set(strings)) == len(strings) == codes.max() + 1
            _seen, first = np.unique(codes, return_index=True)
            assert (np.diff(first) > 0).all(), col  # id i first seen i-th
    for col in ("pod", "remote_addr", "req_path"):
        keys = [StringDictionary(p["names"][col]).content_key()
                for p in parted["parts"]]
        assert len(set(keys)) == NODES, col
    pods = [set(p["names"]["pod"]) for p in parted["parts"]]
    assert not set.intersection(*pods) and len(set.union(*pods)) > 3_000
    # Two PEMs are never handed one dictionary object.
    dicts = [next(iter(served_http_skew.batches(p, 1 << 12))).dicts
             for p in parted["parts"]]
    for col in _builder().STRING_COLUMNS:
        assert len({id(d[col]) for d in dicts}) == NODES


def test_a_services_pods_go_round_the_nodes_in_the_order_of_their_load():
    """``load_ranks`` replays the law's permutations: the services and the
    pods it calls the hottest ARE the hottest; a service's pod of rank r
    runs r nodes round from its hottest, and the hottest service starts
    at node 0, every pair after it one node further."""
    builder = _builder()
    d = _make(BIG, 600_000)
    svc_rank, pod_rank = builder.load_ranks(CFG, BIG)
    pod_rank = pod_rank.reshape(32, 128)
    assert sorted(svc_rank.tolist()) == list(range(32))
    assert (np.sort(pod_rank, axis=1) == np.arange(128)).all()
    by_service = np.bincount(d["service"], minlength=32)
    assert (np.argsort(-by_service)[:6] == np.argsort(svc_rank)[:6]).all()
    count = np.bincount(d["pod"], minlength=4_096).reshape(32, 128)
    busy = np.argsort(svc_rank)[:8]  # the services with rows
    assert (np.argsort(-count[busy], axis=1)[:, :3]
            == np.argsort(pod_rank[busy], axis=1)[:, :3]).all()
    other = builder.load_ranks(CFG, BIG + 1)
    assert (pod_rank.ravel() != other[1]).any()
    node = builder.node_of_pod(CFG, BIG).reshape(32, 128)
    first = node[np.arange(32), np.argmin(pod_rank, axis=1)]
    assert first[np.argsort(svc_rank)].tolist() == [
        ((s + 1) // 2) % 4 for s in range(32)]
    assert ((node - first[:, None]) % 4 == pod_rank % 4).all()
    rows = [len(p["time_"]) for p in d["parts"]]
    assert np.allclose(np.asarray(rows) / 600_000,
                       builder.node_shares(CFG), atol=0.01)


def test_the_builder_counts_what_the_file_states():
    from benchmark import harness

    spec = harness.load_cell(CELL)
    small = {**spec["config"], "rows": 240_000}
    got = _builder().count_cluster(small, spec["traffic"], BIG)
    assert got["rows_in_range"] == 20_001 == sum(got["node_rows_in_range"])
    assert sum(got["node_rows"]) == 240_000
    assert sum(got["node_live_edges"]) == got["live_edges"] > 5_000
    assert max(got["node_http_stats_groups"]) < got["http_stats_groups"] < (
        sum(got["node_http_stats_groups"]))
    # A node's dictionary of pods holds its own pods alone (1,024 at most).
    assert all(0 < s["pod"] <= 1_024 for s in got["node_strings"])
    assert sum(s["pod"] for s in got["node_strings"]) <= 4_096


# -- four PEMs, a device each, on a rehearsed window --------------------------

def _read(name, ctx):
    return importlib.import_module(f"benchmark.layer_metrics.{name}").read(ctx)


@pytest.fixture(scope="module")
def window():
    """``ctx`` of a rehearsed window of the cell under the TPU's routes, as
    ``harness.run_cell`` builds it (the parts the span readers use), with
    what the stack's engines held while it ran: every program's outputs by
    the device of the scope that enqueued them, the tables' devices, the
    nodes' dictionaries and the Kelvin's prepared merges. Any implicit
    copy from one device to another fails the request."""
    from unittest import mock

    from benchmark import harness
    from pixie_tpu.config import override_flag
    from pixie_tpu.exec import programs
    from test_engine_device import _watched

    spec = harness.load_cell(CELL)
    cfg, traffic = spec["config"], spec["traffic"]
    builder = harness.module("builders", cfg["builder"])
    driver = harness.module("drivers", traffic["driver"])
    rows = 90_000
    data = builder.make_data(cfg, BIG, rows)
    degraded = []
    real_degrade = programs.ProgramRegistry._degrade

    def spy_degrade(self, rec):
        degraded.append(rec.kind)
        return real_degrade(self, rec)

    held = {}
    with _watched() as enqueued, mock.patch.object(
            programs.ProgramRegistry, "_degrade", spy_degrade), \
            routes_of("tpu"), override_flag("cpu_fold_threads", 1):
        stack = builder.build(cfg, rows // 15)
        try:
            stack.ingest(data)
            held["resident"] = stack.resident()
            requests = harness.requests_of(spec)
            log = harness.SpanLog(stack.tracers)
            _lo, now_ns = harness.range_lo_ns(cfg, traffic)
            for _ in range(3):
                driver.refresh(stack, requests, now_ns, 240, harness.mark)
            log.cut()
            window = driver.run(stack, traffic, requests, 0.5, now_ns,
                                harness.mark)
            time.sleep(0.1)  # the agents' publish spans close after eos
            spans = log.cut()
            held["devices"] = [p.engine.device for p in stack.pems]
            held["kelvin_device"] = stack.kelvin.engine.device
            held["dicts"] = [
                {c: d.content_key() for c, d in
                 p.engine.tables[cfg["table"]].dicts.items()}
                for p in stack.pems]
            merges = stack.kelvin.engine._prepared_merges.values()
            held["remaps"] = [[sorted(remap) for remap in rec.remaps]
                              for rec in merges]
            held["remap_devices"] = [
                t.devices() for rec in merges
                for remap in rec.remaps for t in remap.values()]
        finally:
            stack.close()
    assert window["failed"] == 0 and window["refreshes"], window["errors"]
    return {"window": window, "spans": spans, "trace": None,
            "requests": requests, "data": data, "held": held,
            "enqueued": enqueued, "degraded": degraded}


def test_every_node_lives_on_a_device_of_its_own(window):
    """A PEM's windows, what its programs hand back and the Kelvin's
    remaps are on the engine's own device and on no other: no array of
    node n > 0 lies on device 0, and no request copied from one device to
    another (the fixture's guard would have failed it)."""
    import jax

    held = window["held"]
    devices = jax.devices()[:NODES]
    assert held["devices"] == devices and held["kelvin_device"] == devices[0]
    assert held["resident"]["rows"] == 90_000
    assert held["resident"]["devices"] == NODES
    assert [n["devices"] for n in held["resident"]["by_node"]] == [
        [d.id] for d in devices]
    scopes = {scope for scope, _leaves in window["enqueued"]}
    assert scopes == set(devices)  # never outside an engine's scope
    for scope, leaves in window["enqueued"]:
        assert leaves and all(d == {scope} for d in leaves), scope
    assert held["remap_devices"] and all(
        d == {devices[0]} for d in held["remap_devices"])
    # An executable compiled for one node's device never met another's
    # arrays: no record lost its executable.
    assert window["degraded"] == []


def test_the_nodes_dictionaries_differ_and_every_payload_is_remapped(window):
    held = window["held"]
    for col in ("pod", "remote_addr", "req_path"):
        assert len({d[col] for d in held["dicts"]}) == NODES, col
    # A record a script (and a bucket): a remap for every payload but at
    # most the first, whose dictionary the canonical one starts from.
    assert len(held["remaps"]) >= 2
    for remaps in held["remaps"]:
        assert len(remaps) == NODES
        assert all(remaps[1:]), remaps
    planes = {tuple(r) for remaps in held["remaps"] for r in remaps[1:]}
    assert (0, 1, 2) in planes  # the service graph's three string keys
    assert (0, 1) in planes     # http_stats' two


def test_one_served_refreshs_span_shape(window):
    """The broker dispatches to four data agents and the merge agent;
    every PEM's dispatches and fetches name its device; the Kelvin's one
    ``merge_finalize`` a script says what four payloads cost it."""
    spans = window["spans"]
    broker = spans["broker"][-1]
    (dispatch,) = [s for s in broker.spans if s.name == "dispatch"]
    assert dispatch.attributes["agents"] == NODES + 1
    assert dispatch.attributes["data_agents"] == "pem-0,pem-1,pem-2,pem-3"
    assert dispatch.attributes["merge_agent"] == "kelvin-0"
    for n, tracer in enumerate(("pem", "pem.1", "pem.2", "pem.3")):
        t = spans[tracer][-1]
        on = [s.attributes["device"] for s in t.spans
              if s.name in ("device.dispatch", "device.fetch")]
        assert on and set(on) == {n}, tracer
        assert len([s for s in t.spans if s.name == "publish"]) == 1
    kelvin = spans["kelvin"]
    assert {t.kind for t in kelvin} == {"merge"}
    for t in kelvin[-2:]:  # the last refresh's two scripts
        (merge,) = [s for s in t.spans if s.name == "device.dispatch"]
        a = merge.attributes
        assert (a["program"], a["prepared"], a["device"]) == (
            "merge_finalize", "hit", 0)
        assert (a["payloads"], a["merges"]) == (NODES, NODES - 1)
        assert a["remap_entries"] > 0 and a["remap_entries"] % 1_024 == 0
        assert a["upload_bytes"] > 100_000
        u = t.usage
        assert (u.merge_payloads, u.merge_remap_entries,
                u.merge_upload_bytes) == (
            a["payloads"], a["remap_entries"], a["upload_bytes"])
        assert u.merge_prepared_hits == 1
    for t in spans["pem"]:
        assert t.usage.merge_payloads == 0 == t.usage.merge_upload_bytes
    assert not [s for traces in spans.values() for t in traces
                for s in t.spans if s.name == "rebucket"]


def test_the_new_readers_read_the_spans_and_the_counters(window):
    assert _read("merge_payloads", window) == 2 * NODES
    kelvin = {t.qid: t.usage for t in window["spans"]["kelvin"]}
    last = window["window"]["refreshes"][-1]
    assert _read("merge_remap_entries", window) == sum(
        kelvin[r["qid"]].merge_remap_entries for r in last) > 0
    assert _read("merge_upload_mb", window) == pytest.approx(sum(
        kelvin[r["qid"]].merge_upload_bytes for r in last) / 1e6)
    # The states the PEMs shipped are what the Kelvin uploads, less the
    # slots that hold no group (the upload is compacted).
    assert 0 < _read("merge_upload_mb", window) <= sum(
        t.usage.wire_bytes for k, traces in window["spans"].items()
        if k.startswith("pem") for t in traces
        if t.qid in {r["qid"] for r in last}) / 1e6
    assert _read("pem_devices", window) == NODES
    spread = _read("pem_spread_ms", window)
    assert 0 < spread < sum(
        (r["t1"] - r["t0"]) * 1e3 for r in last) * 2


def test_the_accepted_span_readers_read_node_zeros_fragment(window):
    """The readers that list no cell find what they read on the broker's,
    the Kelvin's and node 0's traces."""
    assert _read("device_dispatches", window) >= 2 + 2
    assert _read("group_refolds", window) == 0
    assert _read("staged_mb", window) == 0
    for name in ("merge_ms", "head_ms", "tail_ms", "engine_ms",
                 "device_wait_ms", "broker_self_ms", "plan_ms", "fetch_mb",
                 "fetch_ms", "unnamed_ms", "client_ms", "bus_ms",
                 "pem_head_ms", "pem_tail_ms", "dispatch_ms",
                 "device_interval_ms", "group_slots", "fold_fill_pct"):
        assert _read(name, window) > 0, name


def test_the_new_readers_read_nothing_on_a_program_without_them(window):
    """The parent's spans name no device and say nothing of a merge's
    payloads, and its usage record has no such counter: the five readers
    then report nothing and do not raise; nor with one PEM's tracer."""
    gone = ("device", "payloads", "merges", "remap_entries", "upload_bytes")
    stripped = {**window, "spans": {}}
    for tracer, traces in window["spans"].items():
        out = []
        for t in traces:
            t = copy.copy(t)
            t.usage = types.SimpleNamespace(**{
                k: v for k, v in dataclasses.asdict(t.usage).items()
                if not k.startswith("merge_")})
            spans = []
            for s in t.spans:
                s = copy.copy(s)
                s.attributes = {k: v for k, v in s.attributes.items()
                                if k not in gone}
                spans.append(s)
            t.spans = [s for s in spans if s.name != "publish"]
            out.append(t)
        stripped["spans"][tracer] = out
    for name in NEW_METRICS:
        assert _read(name, stripped) is None, name
    assert _read("group_slots", stripped) == _read("group_slots", window)
    one_pem = {**window, "spans": {
        k: v for k, v in window["spans"].items() if not k.startswith("pem.")}}
    assert _read("pem_spread_ms", one_pem) is None
    assert _read("pem_devices", one_pem) == 1
    empty = {**window, "spans": {k: [] for k in window["spans"]}}
    for name in NEW_METRICS:
        assert _read(name, empty) is None, name


# -- one PEM against four -----------------------------------------------------

def _answers(nodes, data):
    """Both scripts' decoded rows from a stack of ``nodes`` PEMs over the
    same union, the second refresh's."""
    from benchmark import harness
    from pixie_tpu.config import override_flag

    spec = harness.load_cell(CELL)
    cfg = {**spec["config"], "nodes": nodes}
    builder = harness.module("builders", cfg["builder"])
    driver = harness.module("drivers", spec["traffic"]["driver"])
    parts = builder.part_by_node(
        data, builder.node_of_pod(cfg, BIG), nodes)
    with routes_of("tpu"), override_flag("cpu_fold_threads", 1):
        stack = builder.build(cfg, len(data["time_"]) // 15)
        try:
            stack.ingest({**data, "parts": parts})
            assert stack.resident()["devices"] == nodes
            requests = harness.requests_of(spec)
            _lo, now_ns = harness.range_lo_ns(cfg, spec["traffic"])
            for _ in range(2):
                recs, answers = driver.refresh(stack, requests, now_ns, 240,
                                               harness.mark)
        finally:
            stack.close()
    assert not any(r["partial"] for r in recs)
    return requests, answers


def test_one_pem_against_four():
    """The same union on one PEM and parted over four gives the same keys,
    counts, sums and maxima exactly, and quantiles inside the reference's
    limits, for both scripts."""
    from benchmark import harness
    from benchmark.reference import px_http_stats, px_service_graph

    data = _make(BIG, 150_000)
    requests, one = _answers(1, data)
    _requests, four = _answers(NODES, data)
    g1, g4 = (px_service_graph.rows(a[0]) for a in (one, four))
    assert g1["key"] == g4["key"] and len(g1["key"]) > 5_000
    for col in ("throughput", "bytes", "error_rate"):
        np.testing.assert_array_equal(g1[col], g4[col])
    s1, s4 = (px_http_stats.rows(a[1]) for a in (one, four))
    assert s1["key"] == s4["key"] and len(s1["key"]) > 3_000
    for col in ("n", "lat_max", "lat_mean"):
        np.testing.assert_array_equal(s1[col], s4[col])
    for answers in (one, four):
        numbers, limits = harness.compare(requests, data, LO_NS, [answers])
        assert all(numbers[k] <= limits[k] for k in limits), numbers
        assert all(numbers[k] == 0 for k in EXACT)


# -- a rehearsal of the cell, sound and broken underneath ---------------------

def _rehearse(platform="tpu", rows=100_000, **kw):
    from benchmark import harness

    with routes_of(platform):
        return harness.run_cell(CELL, BIG, 1.0, True, time.time(),
                                rehearse_rows=rows, **kw)


@pytest.mark.parametrize("platform", ["tpu", "cpu"])
def test_a_rehearsal_of_the_cell_is_sound(platform):
    """The four PEMs' answers merged on the Kelvin against the plain
    references over the union, on both platforms' routes: exact keys,
    counts, sums and maxima, every quantile inside the limits. (At this
    size an edge is a handful of rows, each a centroid of its own. The
    CPU's routes bin a window's digest at 4,096 bins where a PEM's edges
    fit 8,192 slots, ``ops/routes.py`` ``digest_hist_bins``, which a
    larger rehearsal there shows in the service graph's rank errors, 0.24
    at 400,000 rows; the chip's keyed fold orders the values by a sort.)"""
    from benchmark.reference import px_http_stats, px_service_graph

    result = _rehearse(platform)
    assert result["rehearsal"] is True and result["failed"] == 0
    assert result["correct"] is True, result["numbers"]
    numbers = result["numbers"]
    assert set(numbers) == set(px_service_graph.LIMITS) | set(
        px_http_stats.LIMITS)
    assert {k: numbers[k] for k in EXACT} == {k: [0.0, 0] for k in EXACT}
    for k in RANK:
        assert numbers[k][0] <= numbers[k][1] / 2, k
    metrics = result["metrics"]
    assert metrics["merge_payloads"]["value"] == 2 * NODES
    assert metrics["merge_remap_entries"]["value"] > 0
    assert metrics["merge_upload_mb"]["value"] > 0
    assert metrics["pem_devices"]["value"] == NODES
    assert metrics["pem_spread_ms"]["value"] > 0
    assert metrics["window_compiles"]["value"] == 0
    assert metrics["group_refolds"]["value"] == 0
    assert metrics["staged_mb"]["value"] == 0
    for absent in ("http_stats_p50_ms", "service_stats_p50_ms", "join_ms",
                   "digest_mb", "collective_ms", "refresh_p80_ms"):
        assert absent not in metrics, absent
    assert result["device"]["count"] >= NODES


def _every_engine(stack):
    return [p.engine for p in stack.pems] + [stack.kelvin.engine]


def _f32_sums(stack):
    """Every engine's ``sum`` of an INT64 column in 32-bit floats, one
    precision under the exact INT64 sum the file states (as
    ``test_stack_flame.py``'s, over the five engines)."""
    import jax.numpy as jnp

    from pixie_tpu.types.dtypes import DataType

    for engine in _every_engine(stack):
        reg = engine.registry.clone("broken-sum")
        reg._uda["sum"] = [
            dataclasses.replace(d, finalize=lambda c: c.astype(
                jnp.float32).astype(jnp.int64))
            if d.arg_types == (DataType.INT64,) else d
            for d in reg._uda["sum"]
        ]
        engine.registry = reg


class _Broken:
    """A function of ``pixie_tpu.exec`` replaced for one rehearsal."""

    def __init__(self, module, name, wrap):
        self.module, self.name = module, name
        self.real = getattr(module, name)
        self.broken = wrap(self.real)

    def __call__(self, _stack):
        setattr(self.module, self.name, self.broken)

    def mend(self):
        setattr(self.module, self.name, self.real)


def _drop_a_payload():
    """The Kelvin merges three of the four payloads: pem-3's is taken
    out where the merge starts, as an agent whose state never arrived
    would leave it, without the refresh being marked partial."""
    from pixie_tpu.exec import engine

    def wrap(real):
        def merge(eng, pending, tail=()):
            kept = type(pending)(pending.payloads[:-1])
            return real(eng, kept, tail)
        return merge

    return _Broken(engine, "merge_agg_bridge", wrap)


def _identity_remaps():
    """Every payload's key ids are read as the canonical dictionary's:
    the remaps of the prepared merge are emptied."""
    from pixie_tpu.exec import bridge

    def wrap(real):
        def prepare(eng, payloads, tail, slots, key):
            rec = real(eng, payloads, tail, slots, key)
            return dataclasses.replace(
                rec, remaps=tuple({} for _ in rec.remaps))
        return prepare

    return _Broken(bridge, "_prepare_merge", wrap)


@pytest.mark.parametrize("control", ["payload_dropped", "identity_remaps",
                                     "f32_sums", "answer_cut"])
def test_a_control_is_not_correct(control):
    """Each way of answering for less than the whole cluster, or less
    exactly, comes out not ``correct``, by the number that names it."""
    broken = {"payload_dropped": _drop_a_payload,
              "identity_remaps": _identity_remaps}.get(control)
    break_path = broken() if broken else {
        "f32_sums": _f32_sums, "answer_cut": _cut_the_answer}[control]
    try:
        result = _rehearse(break_path=break_path)
    finally:
        if broken:
            break_path.mend()
    assert result["failed"] == 0 and result["correct"] is False
    numbers = {k: v[0] for k, v in result["numbers"].items()}
    if control == "payload_dropped":
        # A node's edges are its own: all of them are missing; the
        # paths it alone saw too, and the others' counts fall short.
        assert numbers["service_graph.keys_differ"] > 1_000
        assert numbers["http_stats.keys_differ"] > 0
    elif control == "identity_remaps":
        assert (numbers["service_graph.keys_differ"] > 0
                or numbers["service_graph.throughput_differ"] > 0)
        assert (numbers["http_stats.keys_differ"] > 0
                or numbers["http_stats.n_differ"] > 0)
    elif control == "f32_sums":
        assert numbers["service_graph.bytes_differ"] > 0
        assert numbers["service_graph.keys_differ"] == 0
        assert numbers["service_graph.throughput_differ"] == 0
    else:  # at a rehearsal's size the answers are under 10,000 rows: 500
        assert numbers["service_graph.keys_differ"] > 0
        assert numbers["http_stats.keys_differ"] > 0
