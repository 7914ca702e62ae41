"""Configuration ``http_edges_1chip`` and its cell: the file's arithmetic
and source against ``BENCHMARK.json``, ``http_full_1chip``'s table and
``conn_flow_1chip``'s cluster, the clients its builder draws from the
seed, the plain reference against the script spelled out edge by edge,
the controls, the readers this configuration brought on a rehearsed
window, and a rehearsal of the cell on both platforms' routes, sound and
with the timed path broken underneath. On the CPU (the TPU's routes by
substituting ``ops/routes.py`` ``routes_platform``): never a device
number from here."""

import copy
import dataclasses
import importlib
import json
import os
import time
import types

import numpy as np
import pytest

from conftest import routes_of
# Two ways to break the timed path underneath, as the seventh cell's tests
# break it: the answer cut at 500 rows, INT64 sums finalized in f32.
from test_stack_flame import _cut_the_answer, _f32_sums

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
BENCH = os.path.join(ROOT, "benchmark")
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCHMARK = json.load(f)


def _config(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


CFG = _config("http_edges_1chip")
CELL = "http_edges_1chip.graph_recent"
BIG = 4_100_000_019  # the driver's seeds pass 2**31
NEW_METRICS = {"digest_slots": ("slots", "fragment programs"),
               "digest_states": ("states", "fragment programs"),
               "digest_mb": ("MB", "engine")}
EXACT = ("service_graph.keys_differ", "service_graph.throughput_differ",
         "service_graph.bytes_differ")
RANK = tuple(f"service_graph.{p}_rank_err" for p in ("p50", "p90", "p99"))
LO_NS = CFG["t_end_ns"] - 300 * 10**9


def _make(seed, rows, cfg=CFG):
    from benchmark.builders.served_http_edges import make_data

    return make_data(cfg, seed, rows)


# -- the files ----------------------------------------------------------------

#: ``BENCHMARK.json``'s lists as they were filed, PR by PR. New entries go
#: last, so what was filed is a PREFIX of what is there: held here once
#: and relatively. A later PR appends to the file and edits nothing here;
#: every other test of this file finds its entry by name.
FILED = {
    "configs": (
        "http_pem_1chip", "http_pem_4chip", "http_full_1chip",
        "conn_flow_1chip", "sql_stats_1chip", "stack_flame_1chip",
        "http_edges_1chip"),
    "workloads": (
        "http_pem_1chip.dash_full", "http_pem_1chip.dash_recent",
        "http_pem_4chip.dash_full", "http_full_1chip.dash_recent",
        "conn_flow_1chip.flow_recent", "sql_stats_1chip.sql_recent",
        "stack_flame_1chip.flame_recent", CELL),
    "per_layer": (
        "http_stats_p50_ms", "service_stats_p50_ms", "refresh_max_ms",
        "served_rows_per_s", "broker_ms", "plan_ms", "engine_ms",
        "window_compiles", "warmup_s", "fold_roofline_pct",
        "pallas_busy_pct", "ingest_rows_per_s", "host_cpu_ms",
        "device_idle_pct", "collective_ms", "head_ms", "tail_ms",
        "broker_self_ms", "merge_ms", "device_wait_ms", "dispatch_ms",
        "device_interval_ms", "device_dispatches", "span_idle_pct",
        "background_ms", "slowest_refresh_background_ms", "group_slots",
        "group_refolds", "staged_mb", "net_flow_graph_p50_ms", "join_ms",
        "join_rows", "wire_mb", "sql_stats_p50_ms", "dict_udf_ms",
        "dict_udf_strings", "remap_entries",
        # PR 37's seven, PR 39's three, this PR's three.
        "bus_ms", "pem_head_ms", "pem_tail_ms", "fetch_ms", "fetch_mb",
        "client_ms", "unnamed_ms",
        "perf_flamegraph_p50_ms", "answer_rows", "answer_string_mb",
        "digest_slots", "digest_states", "digest_mb"),
    "end_to_end": ("refresh_p50_ms", "refresh_p80_ms", "setup_s"),
}


def _entry(kind, name):
    return next(e for e in BENCHMARK[kind] if e["name"] == name)


@pytest.mark.parametrize("kind", sorted(FILED))
def test_what_was_filed_is_a_prefix_of_the_list(kind):
    """New entries go last (an entry put first or in the middle reads as
    a change to what was there): what ``test_host_path_metrics.py`` and
    ``test_stack_flame.py`` held by asserting that THEIR entries are the
    last (``tests/conftest.py`` marks those superseded)."""
    names = [e["name"] for e in BENCHMARK[kind]]
    assert names[:len(FILED[kind])] == list(FILED[kind])
    assert len(set(names)) == len(names)


def test_the_file_agrees_with_benchmark_json_and_the_two_it_is_made_of():
    full, conn = _config("http_full_1chip"), _config("conn_flow_1chip")
    entry = _entry("configs", CFG["name"])
    assert entry["source"] == CFG["source"] and len(CFG["source"]) <= 200
    for part in ("px/cluster/cluster.pxl", "service_let_graph",
                 "px/namespace", "px/service_edge_stats", "exectime",
                 "PL_TABLE_STORE_DATA_LIMIT_MB", "zipfian 0.99"):
        assert part in CFG["source"], part
    assert entry["file"] == "benchmark/configs/http_edges_1chip.json"
    assert entry["reduced"] == ["rows"] == list(CFG["reduced"])
    assert CFG["reduced"]["rows"] == full["reduced"]["rows"]
    cell = _entry("workloads", CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "http_edges_1chip", "graph_recent", 1)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    # Eight cells as filed, one of them on four chips.
    assert [w["chips"] for w in BENCHMARK["workloads"]
            if w["name"] in FILED["workloads"]].count(4) == 1
    # http_full_1chip's PEM, row for row in size.
    for k in ("table", "rows", "window_rows", "columns", "bytes_per_row",
              "budget_bytes_per_node", "span_s", "t_end_ns", "flags",
              "max_output_rows", "engine", "chips", "nodes"):
        assert CFG[k] == full[k], k
    assert CFG["rows"] * CFG["bytes_per_row"] == 4_250_000_000
    assert divmod(CFG["rows"], CFG["window_rows"]) == (29, 1_682_592)
    assert CFG["rows"] // CFG["span_s"] == 17_361
    for k, v in full["values"].items():
        assert CFG["values"][k] == v, k
    # conn_flow_1chip's cluster: one address a pod, as many outside,
    # twenty peers a pod.
    for k in ("outside_addrs", "peers", "services", "pods", "skew"):
        assert CFG["values"][k] == conn["values"][k], k
    pods = CFG["values"]["services"] * CFG["values"]["pods"]
    peers = sum(CFG["values"]["peers"].values())
    assert (pods, peers, pods * peers) == (4_096, 20, 81_920)
    assert CFG["edges_in_range"]["possible_edges"] == 81_920
    assert all(79_000 < n <= 81_920
               for n in CFG["edges_in_range"]["live_edges"])
    from benchmark.builders.served_http_edges import CAPABILITIES

    assert set(CFG["requires"]) == {
        "joint_key_sizing", "keyed_digest_fold"
    } <= set(CAPABILITIES)
    assert all(check() for check in CAPABILITIES.values())
    assert "EVERY edge" in CFG["guarantees"]["quantiles"]
    assert "K = 128" in CFG["guarantees"]["quantiles"]
    for k in ("script", "script_departures", "clients", "values",
              "max_output_rows", "requires"):
        assert CFG["assumed"][k], k


def test_stack_flames_file_agrees_with_benchmark_json_and_the_programs_split():
    """``test_stack_flame.py``'s test of (nearly) this name, every
    assertion of it but the one that its configuration is the LAST
    (``tests/conftest.py`` marks it superseded for that line; the order
    is held by ``test_what_was_filed_is_a_prefix_of_the_list``)."""
    from benchmark.builders.served_stacks import CAPABILITIES
    from pixie_tpu.ingest.schemas import table_budgets

    flame, conn = _config("stack_flame_1chip"), _config("conn_flow_1chip")
    entry = _entry("configs", flame["name"])
    assert entry["source"] == flame["source"] and len(flame["source"]) <= 200
    for part in ("px/perf_flamegraph", "kStackTraceTable", "11 ms", "30 s",
                 "InitSchemas", "BASELINE[4]"):
        assert part in flame["source"], part
    assert entry["file"] == "benchmark/configs/stack_flame_1chip.json"
    assert entry["reduced"] == [] and flame["reduced"] == {}
    cell = _entry("workloads", "stack_flame_1chip.flame_recent")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "stack_flame_1chip", "flame_recent", 1
    )
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    # The one deployment setting, as the two newest configurations had
    # it; the table's share of the rest, by the program's mirror of
    # upstream's split, is the table.
    assert flame["flags"] == conn["flags"]
    limit = flame["flags"]["table_store_data_limit_mb"]
    assert table_budgets(limit)["stack_traces.beta"] == (
        flame["budget_bytes_per_node"]
    ) == 531_261_030
    assert flame["rows"] == 531_261_030 // 48 == 11_067_938
    # Five full windows and one padded; '-5m' lies in the last two.
    assert divmod(flame["rows"], flame["window_rows"]) == (5, 582_178)
    assert flame["max_output_rows"] == 1_048_576
    assert set(flame["requires"]) == {
        "joint_key_sizing", "join_tail_sizing", "sorted_fold_any"
    } <= set(CAPABILITIES)
    assert all(check() for check in CAPABILITIES.values())
    assert flame["guarantees"]["complete"].startswith(
        conn["guarantees"]["complete"])
    assert flame["t_end_ns"] == conn["t_end_ns"]
    for k in ("services", "pods", "skew"):
        assert flame["values"][k] == conn["values"][k], k


def test_the_host_paths_seven_are_as_they_were_filed():
    """What ``test_host_path_metrics.py`` and then ``test_stack_flame.py``
    held beside the order: each of PR 37's seven, whole."""
    from test_host_path_metrics import HOST_PATH_METRICS

    assert len(HOST_PATH_METRICS) == 7
    for name, (unit, source, layer) in HOST_PATH_METRICS.items():
        assert _entry("per_layer", name) == {
            "name": name, "unit": unit, "better": "lower", "source": source,
            "layer": layer, "moves": "refresh_p50_ms",
        }


@pytest.mark.parametrize("metric", ("answer_rows", "answer_string_mb",
                                    "perf_flamegraph_p50_ms"))
def test_stack_flames_metrics_are_filed_under_their_layers(metric):
    """``test_stack_flame.py``'s test of this name but for its
    ``in per_layer[-3:]`` (superseded; the order is held above)."""
    from test_stack_flame import NEW_METRICS as FLAME_METRICS

    assert sorted(FLAME_METRICS) == ["answer_rows", "answer_string_mb",
                                     "perf_flamegraph_p50_ms"]
    entry = _entry("per_layer", metric)
    assert (entry["layer"], entry["source"]) == FLAME_METRICS[metric]
    assert entry["moves"] == "refresh_p50_ms"
    assert entry["workloads"] == ["stack_flame_1chip.flame_recent"]


@pytest.mark.parametrize("metric", sorted(NEW_METRICS))
def test_the_new_metrics_are_filed_under_their_layers(metric):
    entry = _entry("per_layer", metric)
    assert (entry["unit"], entry["layer"]) == NEW_METRICS[metric]
    assert entry == {
        "name": metric, "unit": entry["unit"], "better": "lower",
        "source": "program_counter", "layer": entry["layer"],
        "moves": "refresh_p50_ms", "workloads": [CELL],
    }
    assert callable(importlib.import_module(
        f"benchmark.layer_metrics.{metric}").read)


def test_nothing_the_benchmark_had_lists_the_new_cell():
    """The cell reads every per-layer metric that lists no cells and
    its own three; no accepted entry was edited to take it in."""
    for m in BENCHMARK["per_layer"] + BENCHMARK["end_to_end"]:
        if m["name"] not in NEW_METRICS:
            assert CELL not in m.get("workloads", []), m["name"]


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
    (script,) = traffic["scripts"]
    assert script["reference"] == "px_service_graph"
    assert script["reads"] == ["time_", "remote_addr", "pod", "service",
                               "resp_status", "resp_body_size", "latency_ns"]
    assert sum(CFG["columns"][c] for c in script["reads"]) == 44
    (request,) = harness.requests_of(spec)
    for line in (
        "px.DataFrame(table='http_events', start_time='-5m')",
        "df.groupby(['remote_addr', 'pod', 'service']).agg(",
        "latency_quantiles=('latency_ns', px.quantiles)",
        "error_rate=('failure', px.mean)",
        "throughput_total=('latency_ns', px.count)",
        "outbound_bytes_total=('resp_body_size', px.sum)",
        "px.pluck_float64(edges.latency_quantiles, 'p99')",
    ):
        assert line in request["pxl"], line


def test_the_table_is_the_programs_http_events():
    from benchmark.builders import served_http_edges
    from pixie_tpu.ingest.replay import HTTP_EVENTS_RELATION
    from pixie_tpu.types.dtypes import DataType, host_dtypes

    assert [(c, DataType[t]) for c, t in served_http_edges.COLUMNS] == list(
        HTTP_EVENTS_RELATION.items())
    d = _make(7, 1 << 12)
    for col, dtype in HTTP_EVENTS_RELATION.items():
        planes = d[col] if isinstance(d[col], tuple) else (d[col],)
        assert tuple(p.dtype for p in planes) == tuple(
            np.dtype(t) for t in host_dtypes(dtype)), col


@pytest.mark.parametrize("module,name,capability", [
    ("pixie_tpu.exec.engine:Engine", "probe_group_keys", "joint_key_sizing"),
    ("pixie_tpu.ops.tdigest", "merge_ordered", "keyed_digest_fold"),
])
def test_a_program_that_lacks_what_the_file_requires_is_refused_at_once(
        monkeypatch, module, name, capability):
    """The parent's program under these benchmark files: it exits with
    the file's reason and another code than 0 before a row is made (run
    on the chip without the entry it answered in 31.9 s a refresh, its
    quantiles outside the limits: PERF.md section 6)."""
    path, _, attr = module.partition(":")
    owner = importlib.import_module(path)
    monkeypatch.delattr(getattr(owner, attr) if attr else owner, name)
    t = time.perf_counter()
    with pytest.raises(SystemExit, match=capability) as e:
        _make(7, CFG["rows"])
    assert e.value.code not in (0, None)
    assert CFG["requires"][capability] in str(e.value.code)
    assert time.perf_counter() - t < 1.0


def test_a_fold_plan_without_digests_is_refused_at_once(monkeypatch):
    from pixie_tpu.exec import fold_plan

    @dataclasses.dataclass(frozen=True)
    class ParentsFoldPlan:
        platform: str = ""

    monkeypatch.setattr(fold_plan, "FoldPlan", ParentsFoldPlan)
    with pytest.raises(SystemExit, match="keyed_digest_fold") as e:
        _make(7, CFG["rows"])
    assert CFG["requires"]["keyed_digest_fold"] in str(e.value.code)


# -- the data -----------------------------------------------------------------

def test_data_is_the_seeds_and_http_fulls_but_for_the_clients():
    from benchmark.builders import served_http_skew

    a, b, other = _make(BIG, 50_000), _make(BIG, 50_000), _make(BIG + 1, 50_000)
    full = served_http_skew.make_data(
        {**_config("http_full_1chip"), "requires": {}}, BIG, 50_000)
    for col in ("time_", "service", "pod", "req_path", "latency_ns",
                "resp_status", "resp_body_size"):
        np.testing.assert_array_equal(a[col], b[col])
        np.testing.assert_array_equal(a[col], full[col])
    np.testing.assert_array_equal(a["remote_addr"], b["remote_addr"])
    assert np.any(a["remote_addr"] != other["remote_addr"])
    assert np.any(a["remote_addr"] != full["remote_addr"])
    assert len(a["names"]["remote_addr"]) == 8_192 == len(
        set(a["names"]["remote_addr"]))


def test_a_pod_has_twenty_clients_drawn_by_rank():
    d = _make(BIG, 400_000)
    pair = np.unique(d["pod"].astype(np.int64) << 32 | d["remote_addr"])
    per_pod = np.bincount((pair >> 32).astype(np.int64), minlength=4_096)
    assert per_pod.max() == 20 and len(pair) <= 81_920
    # The busiest pod's clients by rank: p(r) ~ 1 / r^0.99.
    pod = np.bincount(d["pod"]).argmax()
    n = np.sort(np.bincount(d["remote_addr"][d["pod"] == pod]))[::-1][:20]
    want = 1.0 / np.arange(1, 21) ** 0.99
    assert np.all(np.abs(n / n.sum() - want / want.sum()) < 0.03)
    # Sixteen of a pod's clients are pods, four outside the cluster.
    clients = np.unique(d["remote_addr"][d["pod"] == pod])
    assert len(clients) == 20 and np.sum(clients < 4_096) == 4
    assert d["names"]["remote_addr"][0].startswith("198.")
    assert d["names"]["remote_addr"][4_096].startswith("10.")


def test_the_builder_counts_what_the_file_states():
    from benchmark import harness
    from benchmark.builders.served_http_edges import count_edges

    spec = harness.load_cell(CELL)
    small = {**spec["config"], "rows": 240_000}
    got = count_edges(small, spec["traffic"], BIG)
    assert got["rows_in_range"] == 20_001
    assert got["live_edges"] == got["answer_rows"] > 5_000
    assert got["median_rows"] <= 2 and got["largest"] > 50


def test_build_keeps_the_heap_and_execute_hands_out_copies(monkeypatch):
    from benchmark.builders import served_conn, served_http_edges

    kept = []
    monkeypatch.setattr(served_conn, "keep_the_heap", lambda: kept.append(1))
    monkeypatch.setattr(served_http_edges.EdgeStack, "__init__",
                        lambda self, cfg, window_rows: None)
    stack = served_http_edges.build(CFG, 1024)
    assert kept == [1]
    planes = {"n": np.arange(4), "name": np.asarray(["a", "b"], object)}
    monkeypatch.setattr(
        served_http_edges.served_http_skew.SkewStack, "execute",
        lambda self, pxl, timeout_s, now_ns: {"qid": "q", "partial": False,
                                               "rows": dict(planes)})
    rows = stack.execute("", 1.0, 0)["rows"]
    assert rows["n"] is not planes["n"] and rows["name"] is planes["name"]
    np.testing.assert_array_equal(rows["n"], planes["n"])


# -- the reference and the controls -------------------------------------------

def _edge_by_edge(data, lo_ns):
    """The script spelled out: a dict of rows an edge, numpy's own
    quantiles, Python's own sums."""
    keep = np.flatnonzero(data["time_"] >= lo_ns)
    names = data["names"]
    edges = {}
    for i in keep:
        k = (names["remote_addr"][data["remote_addr"][i]],
             names["pod"][data["pod"][i]],
             names["service"][data["service"][i]])
        edges.setdefault(k, []).append(i)
    out = {}
    for k, idx in edges.items():
        lat = data["latency_ns"][idx]
        out[k] = (
            len(idx), int(sum(int(v) for v in data["resp_body_size"][idx])),
            float(np.mean(data["resp_status"][idx] >= 400)),
            *np.quantile(lat, [0.5, 0.9, 0.99]),
        )
    return out


def test_the_reference_equals_the_script_spelled_out_edge_by_edge():
    from benchmark.reference import px_service_graph as ref

    data = _make(BIG, 60_000)
    want = _edge_by_edge(data, LO_NS)
    got = ref.answer(data, LO_NS)
    assert got["key"] == sorted(want)
    for i, k in enumerate(got["key"]):
        n, total, rate, p50, p90, p99 = want[k]
        assert got["throughput"][i] == n and got["bytes"][i] == total
        assert got["error_rate"][i] == pytest.approx(rate, abs=1e-15)
        for col, q in (("p50", p50), ("p90", p90), ("p99", p99)):
            assert got[col][i] == pytest.approx(q, rel=1e-12), (k, col)
    # The whole retention too.
    assert sum(ref.answer(data, None)["throughput"]) == 60_000


def test_an_exact_answer_compares_clean_and_a_rows_slack_is_a_rows():
    from benchmark.reference import px_service_graph as ref

    data = _make(BIG, 200_000)
    exact = ref.answer(data, LO_NS)
    assert ref.numbers(exact, exact) == dict.fromkeys(ref.LIMITS, 0)
    # Any estimate between two neighbouring rows is as good as another.
    i = int(np.argmax(exact["throughput"]))
    rows = exact["lat"][exact["start"][i]:][:exact["throughput"][i]]
    n = len(rows)
    assert n > 30
    mid = (rows[n // 2 - 1] + rows[n // 2]) / 2
    for est in (rows[n // 2 - 1], mid, rows[n // 2]):
        got = {**exact, "p50": exact["p50"].copy()}
        got["p50"][i] = est
        assert ref.rank_err(exact, got["p50"], 0.5)[i] == 0.0
    # The edge's largest row for its median is half the rows off: all
    # but itself lie under it, less the one row's slack.
    got["p50"][i] = rows[-1]
    err = ref.numbers(got, exact)["service_graph.p50_rank_err"]
    assert err == pytest.approx((n - 1) / n - 0.5 - 1.0 / n)
    got["p50"][i] = np.nan
    assert ref.numbers(got, exact)["service_graph.p50_rank_err"] == np.inf


def test_a_value_error_is_held_on_the_large_edges_alone():
    """``pXX_relerr`` reads the edges of ``VALUE_EDGE_ROWS`` rows or
    more (8,192: 28 edges a seed at full size); the rank error reads
    every edge."""
    from benchmark.reference import px_service_graph as ref

    assert ref.VALUE_EDGE_ROWS == 8_192
    exact = ref.answer(_make(BIG, 200_000), LO_NS)
    n = exact["throughput"]
    value_rows = int(np.sort(n)[-3])
    large, small = int(np.argmax(n)), int(np.argmin(n))
    for i, moved in ((small, False), (large, True)):
        got = {**exact, "p99": exact["p99"].copy()}
        got["p99"][i] *= 3.0
        numbers = ref.numbers(got, exact, value_rows)
        assert (numbers["service_graph.p99_relerr"] > 0) == moved
        assert numbers["service_graph.p99_rank_err"] >= 0
    assert numbers["service_graph.p99_relerr"] == pytest.approx(2.0)
    assert numbers["service_graph.p99_rank_err"] > 0


def test_an_edge_with_no_failed_row_is_held_to_zero():
    from benchmark.reference import px_service_graph as ref

    data = _make(BIG, 60_000)
    exact = ref.answer(data, LO_NS)
    i = int(np.flatnonzero(exact["error_rate"] == 0)[0])
    got = {**exact, "error_rate": exact["error_rate"].copy()}
    got["error_rate"][i] = 1e-3
    assert ref.numbers(got, exact)[
        "service_graph.error_rate_relerr"] == pytest.approx(1e-3)


def test_a_lost_or_invented_edge_is_counted():
    from benchmark.reference import px_service_graph as ref

    data = _make(BIG, 60_000)
    exact = ref.answer(data, LO_NS)
    lost = {k: v[1:] for k, v in exact.items()
            if k in ("key", "p50", "p90", "p99", "error_rate", "throughput",
                     "bytes")}
    numbers = ref.numbers(lost, exact)
    assert numbers["service_graph.keys_differ"] == 1
    assert numbers["service_graph.throughput_differ"] == len(exact["key"])
    assert all(numbers[k] == np.inf for k in RANK)


def test_the_controls_are_not_correct_at_a_rehearsals_size():
    """The sums in 32-bit floats, the answer cut as the broker's default
    cuts it (at a rehearsal's size, where the answer is under 10,000
    rows, at 500) and the digest at the 256 bins the parent's windows
    were binned at: none is ``correct``, each by what it departs in. A
    digest built from the exact order (``bins`` 0) is inside the limits:
    what they leave room for. At the cell's size the three are run by
    hand (``benchmark/control_service_graph.py``; PERF.md section 2)."""
    from benchmark.control_service_graph import PARENT_BINS, control_numbers
    from benchmark.reference.px_service_graph import LIMITS

    assert PARENT_BINS == 256
    controls, limits = control_numbers(CELL, BIG, 400_000, 500)
    assert limits == LIMITS and len(controls) == 3
    for control, numbers in controls.items():
        assert [k for k in limits if numbers[k] > limits[k]], control
    f32 = controls["f32 sums"]
    assert f32["service_graph.bytes_differ"] > 0
    assert f32["service_graph.keys_differ"] == 0
    assert f32["service_graph.throughput_differ"] == 0
    assert controls["cut at 500 rows"]["service_graph.keys_differ"] > 0
    binned = controls["digest at 256 bins"]
    assert all(binned[k] == 0 for k in EXACT)
    assert all(binned[k] > 5 * limits[k] for k in RANK)
    sound, _ = control_numbers(CELL, BIG, 400_000, 500, bins=0)
    unbinned = sound["digest at 0 bins"]
    assert all(unbinned[k] <= limits[k] for k in limits), unbinned


def test_a_small_edges_value_error_is_the_edges_own_spacing():
    """Why the value error starts at ``VALUE_EDGE_ROWS``: a digest built
    from an edge's exact order (``bins`` 0, nothing of the program in
    it) is inside every rank limit on every edge, and yet its value
    error grows as the threshold falls, because neighbouring rows at
    the 99th percentile lie 45 / n apart in the value's logarithm. At
    full size the same witness reads ``p99_relerr`` 0.030-0.057 from
    4,096 rows where the program read 0.038-0.067 on the same eight
    seeds, and 0.020-0.033 from 8,192 (PERF.md section 2)."""
    from benchmark.control_service_graph import control_numbers

    worst = []
    for value_rows in (256, 64):
        controls, limits = control_numbers(
            CELL, BIG, 400_000, 500, bins=0, value_rows=value_rows)
        witness = controls["digest at 0 bins"]
        assert all(witness[k] <= limits[k] for k in RANK)
        worst.append(witness["service_graph.p99_relerr"])
    assert worst[0] < worst[1]
    assert worst[1] > 10 * limits["service_graph.p99_relerr"]


def test_a_digest_of_single_rows_reads_inside_a_rows_slack():
    """Most edges hold a handful of rows: each is a centroid of its own,
    and the digest's interpolation (another convention than numpy's)
    stays within a row of the rank asked for."""
    from benchmark.control_service_graph import binned_digest_quantiles
    from benchmark.reference import px_service_graph as ref

    exact = ref.answer(_make(BIG, 120_000), LO_NS)
    got = binned_digest_quantiles(exact, 0)
    small = exact["throughput"] <= 64
    assert small.sum() > 1_000
    for name, q in ref.QUANTILES:
        assert np.all(ref.rank_err(exact, got[name], q)[small] == 0.0), name


# -- the readers this configuration brought, on a rehearsed window ------------

def _read(name, ctx):
    return importlib.import_module(f"benchmark.layer_metrics.{name}").read(ctx)


@pytest.fixture(scope="module")
def window():
    """``ctx`` of a rehearsed window of the cell under the TPU's routes,
    as ``harness.run_cell`` builds it (the parts the span readers use),
    and its data. Three of the windows hold '-5m', as at full size."""
    from benchmark import harness
    from pixie_tpu.config import override_flag

    spec = harness.load_cell(CELL)
    cfg, traffic = spec["config"], spec["traffic"]
    builder = harness.module("builders", cfg["builder"])
    driver = harness.module("drivers", traffic["driver"])
    rows = 90_000
    data = builder.make_data(cfg, BIG, rows)
    with routes_of("tpu"), override_flag("cpu_fold_threads", 1):
        stack = builder.build(cfg, rows // 29)
        try:
            stack.ingest(data)
            assert stack.resident()["rows"] == rows
            requests = harness.requests_of(spec)
            log = harness.SpanLog(stack.tracers)
            _lo, now_ns = harness.range_lo_ns(cfg, traffic)
            for _ in range(3):
                driver.refresh(stack, requests, now_ns, 240, harness.mark)
            log.cut()
            window = driver.run(stack, traffic, requests, 0.5, now_ns,
                                harness.mark)
            spans = log.cut()
        finally:
            stack.close()
    assert window["failed"] == 0 and window["refreshes"]
    return {"window": window, "spans": spans, "trace": None,
            "requests": requests, "data": data}


def test_one_served_requests_span_shape(window):
    """The PEM folds the three windows in range in one scan program
    whose integer aggregates ride the keyed sort and whose digests are
    built beside it; the Kelvin's one ``merge_finalize`` reads them."""
    from benchmark.reference import px_service_graph as ref

    pem = window["spans"]["pem"][-1]
    (fold,) = [s.attributes for s in pem.spans
               if s.name == "device.dispatch" and "fold" in s.attributes]
    slots = fold["slots"]
    # (At full size three 2^21-row windows hold '-5m' and their sums
    # ride the key sort as payload; a rehearsal's windows are short
    # against its slots.)
    assert fold == {
        "program": "fragment_scan_fold", "windows": fold["windows"],
        "fold": "mixed:sorted_int=3,keyed_digest=3", "group": "sorted",
        "slots": slots, "digests": 3, "digest_slots": slots * 128,
        "digest_bins": 1 << 32, "ride": "index",
    }
    assert fold["windows"] in (3, 4)
    assert not [s for t in window["spans"]["pem"] + window["spans"]["kelvin"]
                for s in t.spans if s.name == "rebucket"]
    (payload,) = [s for s in pem.spans if s.name == "payload"]
    assert payload.attributes == {
        "kind": "agg_state", "digest_bytes": 3 * 2 * slots * 128 * 4}
    assert pem.usage.digest_bytes == payload.attributes["digest_bytes"]
    assert pem.usage.digest_bytes < pem.usage.wire_bytes
    kelvin = window["spans"]["kelvin"][-1]
    assert [s.attributes["program"] for s in kelvin.spans
            if s.name == "device.dispatch"] == ["merge_finalize"]
    assert kelvin.usage.digest_bytes == 0
    want = ref.answer(window["data"], LO_NS)
    assert kelvin.usage.answer_rows == len(want["key"])


def test_the_new_readers_read_the_spans_and_the_counter(window):
    slots = _read("group_slots", window)
    assert _read("digest_slots", window) == slots * 128
    assert _read("digest_states", window) == 3
    assert _read("digest_mb", window) == pytest.approx(
        3 * 2 * slots * 128 * 4 / 1e6)
    assert _read("digest_mb", window) < _read("wire_mb", window)


def test_the_accepted_span_readers_read_the_new_cell(window):
    assert _read("device_dispatches", window) == 2
    assert _read("group_refolds", window) == 0
    assert _read("staged_mb", window) == 0
    assert _read("answer_rows", window) > 2_000
    for name in ("merge_ms", "head_ms", "tail_ms", "engine_ms",
                 "device_wait_ms", "broker_self_ms", "plan_ms", "fetch_mb",
                 "unnamed_ms", "client_ms"):
        assert _read(name, window) > 0, name
    # Named from inside: what no span covers is a small share.
    assert _read("unnamed_ms", window) < 0.15 * (
        _read("head_ms", window) + _read("tail_ms", window))


def test_the_new_readers_read_nothing_on_a_program_without_them(window):
    """The parent's dispatch spans carry no digest attribute and its
    usage record no ``digest_bytes``: the three readers then report
    nothing and do not raise."""
    stripped = {**window, "spans": {}}
    for tracer, traces in window["spans"].items():
        out = []
        for t in traces:
            t = copy.copy(t)
            t.usage = types.SimpleNamespace(**{
                k: v for k, v in dataclasses.asdict(t.usage).items()
                if k != "digest_bytes"})
            spans = []
            for s in t.spans:
                s = copy.copy(s)
                s.attributes = {k: v for k, v in s.attributes.items()
                                if not k.startswith("digest")}
                spans.append(s)
            t.spans = spans
            out.append(t)
        stripped["spans"][tracer] = out
    for name in NEW_METRICS:
        assert _read(name, stripped) is None, name
    assert _read("group_slots", stripped) == _read("group_slots", window)
    assert _read("wire_mb", stripped) == _read("wire_mb", window)
    empty = {**window, "spans": {k: [] for k in window["spans"]}}
    for name in NEW_METRICS:
        assert _read(name, empty) is None, name


# -- a rehearsal of the cell, sound and broken underneath ---------------------

def _rehearse(platform="tpu", rows=150_000, **kw):
    from benchmark import harness

    with routes_of(platform):
        return harness.run_cell(CELL, BIG, 1.5, True, time.time(),
                                rehearse_rows=rows, **kw)


@pytest.mark.parametrize("platform", ["tpu", "cpu"])
def test_a_rehearsal_of_the_cell_is_sound(platform):
    """The served stack's answer against the plain reference on both
    platforms' routes: exact keys, counts and byte sums, every edge's
    quantiles inside the limits."""
    from benchmark.reference.px_service_graph import LIMITS

    result = _rehearse(platform)
    assert result["rehearsal"] is True and result["failed"] == 0
    assert result["correct"] is True, result["numbers"]
    numbers = result["numbers"]
    assert set(numbers) == set(LIMITS)
    assert {k: numbers[k] for k in EXACT} == {k: [0.0, 0] for k in EXACT}
    relerr, limit = numbers["service_graph.error_rate_relerr"]
    assert 0 < relerr < 1.2e-7 < limit  # an f32 plane, one rounding
    for k in RANK:
        assert numbers[k][0] <= numbers[k][1] / 2, k
    metrics = result["metrics"]
    # One scan-folded program a request on the TPU's routes; on the
    # CPU's a dispatch a window, each holding the three carries.
    assert metrics["digest_states"]["value"] == (
        3 if platform == "tpu" else 3 * 4)
    slots = metrics["group_slots"]["value"]
    assert slots >= 8_192
    assert metrics["digest_slots"]["value"] == slots * 128
    assert metrics["digest_mb"]["value"] == pytest.approx(
        6 * slots * 128 * 4 / 1e6)
    assert metrics["window_compiles"]["value"] == 0
    assert metrics["group_refolds"]["value"] == 0
    assert metrics["staged_mb"]["value"] == 0
    assert "service_stats_p50_ms" not in metrics and "join_ms" not in metrics


def test_a_cut_answer_is_not_correct():
    result = _rehearse(break_path=_cut_the_answer)
    assert result["correct"] is False and result["failed"] == 0
    assert result["numbers"]["service_graph.keys_differ"][0] > 0


def test_f32_sums_are_not_correct():
    """``resp_body_size`` is up to 2^20: an edge of a few dozen rows
    passes 2^24 and its f32 sum is not the integer."""
    result = _rehearse(break_path=_f32_sums)
    assert result["failed"] == 0 and result["correct"] is False
    numbers = result["numbers"]
    assert numbers["service_graph.bytes_differ"][0] > 0
    assert numbers["service_graph.keys_differ"] == [0.0, 0]
    assert numbers["service_graph.throughput_differ"] == [0.0, 0]


def _digest_at_256_bins(stack):
    """Every window's digest built as the parent built it at 2^17
    groups: rows binned at the top 8 bits of their f32 pattern (the
    histogram's width then), each bin a centroid."""
    import jax
    import jax.numpy as jnp

    from pixie_tpu.exec import fragment
    from pixie_tpu.ops import tdigest

    def binned(lead, folded_flag, values, num_groups, k=128, ranked=True):
        v = values.astype(jnp.float32)
        bits = jax.lax.bitcast_convert_type(v, jnp.uint32)
        top = jnp.where(v < 0, ~bits, bits | jnp.uint32(0x80000000)) >> 24
        # The bin's lower edge stands for its rows.
        back = (top << 24) ^ jnp.uint32(0x80000000)
        coarse = jax.lax.bitcast_convert_type(back, jnp.float32)
        return tdigest.ordered_batch_to_digest(
            lead, folded_flag, jnp.where(jnp.isfinite(v), coarse, v),
            num_groups, k, ranked)

    fragment.ordered_batch_to_digest = binned
    fragment._FRAGMENT_CACHE.clear()


def test_a_digest_at_256_bins_is_not_correct():
    from pixie_tpu.exec import fragment
    from pixie_tpu.ops import tdigest

    try:
        result = _rehearse(break_path=_digest_at_256_bins)
    finally:
        fragment.ordered_batch_to_digest = tdigest.ordered_batch_to_digest
        fragment._FRAGMENT_CACHE.clear()
    assert result["failed"] == 0 and result["correct"] is False
    numbers = result["numbers"]
    assert {k: numbers[k] for k in EXACT} == {k: [0.0, 0] for k in EXACT}
    assert any(numbers[k][0] > numbers[k][1] for k in RANK), numbers
