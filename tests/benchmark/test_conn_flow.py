"""Configuration ``conn_flow_1chip`` and its cell: the file's arithmetic
and source against ``BENCHMARK.json`` and the program's own schema and
budget split, the data its builder makes from the seed, the plain
reference against a row-by-row one, the readers this configuration
brought on a rehearsed window, a rehearsal of the cell sound and with
the answer cut, and the two controls. On the CPU: never a device number
from here."""

import copy
import importlib
import json
import os
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
BENCH = os.path.join(ROOT, "benchmark")
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCHMARK = json.load(f)
with open(os.path.join(BENCH, "configs", "conn_flow_1chip.json")) as f:
    CFG = json.load(f)
CELL = "conn_flow_1chip.flow_recent"
BIG = 3_000_000_019  # the driver's seeds pass 2**31
NEW_METRICS = {"net_flow_graph_p50_ms": ("client", "host_clock"),
               "join_ms": ("engine", "program_span"),
               "join_rows": ("engine", "program_counter"),
               "wire_mb": ("broker path", "program_counter")}
EXACT = {"net_flow_graph.keys_differ": 0,
         "net_flow_graph.bytes_sent_differ": 0,
         "net_flow_graph.bytes_recv_differ": 0}


def _make(seed, rows):
    from benchmark.builders.served_conn import make_data

    return make_data(CFG, seed, rows)


def test_the_file_agrees_with_benchmark_json_and_the_programs_split():
    from pixie_tpu.ingest.schemas import table_budgets

    entry = next(c for c in BENCHMARK["configs"] if c["name"] == CFG["name"])
    assert entry["source"] == CFG["source"] and len(CFG["source"]) <= 200
    assert entry["file"] == "benchmark/configs/conn_flow_1chip.json"
    assert entry["reduced"] == [] and CFG["reduced"] == {}
    cell = next(w for w in BENCHMARK["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "conn_flow_1chip", "flow_recent", 1
    )
    assert len(cell["why"]) <= 200
    # The one deployment setting: the least budget whose 40 % holds the
    # chip's share of the replay's http_events; conn_stats' share of the
    # rest, by the program's mirror of upstream's split, is the table.
    assert set(CFG["flags"]) == {"table_store_data_limit_mb"}
    limit = CFG["flags"]["table_store_data_limit_mb"]
    share = 125_000_000 * 68
    assert table_budgets(limit)["http_events"] >= share > (
        table_budgets(limit - 1)["http_events"]
    )
    assert table_budgets(limit)["conn_stats"] == (
        CFG["budget_bytes_per_node"]
    ) == 531_261_030
    assert CFG["rows"] == 531_261_030 // 109 == 4_873_954
    # Two full windows and one padded, which holds all of '-5m'.
    assert divmod(CFG["rows"], CFG["window_rows"]) == (2, 679_650)
    assert CFG["max_output_rows"] == 131_072
    from benchmark.builders.served_conn import CAPABILITIES

    # What the cell cannot run without, named where the deployment is.
    assert set(CFG["requires"]) == {
        "joint_key_sizing", "join_tail_sizing"
    } <= set(CAPABILITIES)
    assert all(check() for check in CAPABILITIES.values())
    full = json.load(open(os.path.join(BENCH, "configs",
                                       "http_full_1chip.json")))
    assert CFG["guarantees"]["complete"] == full["guarantees"]["complete"]
    assert CFG["t_end_ns"] == full["t_end_ns"]
    assert (CFG["values"]["services"], CFG["values"]["pods"]) == (
        full["values"]["services"], full["values"]["pods"]
    )


def test_the_table_is_the_programs_conn_stats():
    from benchmark.builders import served_conn
    from pixie_tpu.ingest.schemas import CONN_STATS_RELATION
    from pixie_tpu.types.dtypes import DataType, host_dtypes

    assert [(c, DataType[t]) for c, t in served_conn.COLUMNS] == list(
        CONN_STATS_RELATION.items()
    )
    assert tuple(CFG["columns"]) == tuple(CONN_STATS_RELATION.column_names)
    for col, dtype in CONN_STATS_RELATION.items():
        assert CFG["columns"][col] == sum(
            np.dtype(d).itemsize for d in host_dtypes(dtype)
        ), col
    assert CFG["bytes_per_row"] == sum(CFG["columns"].values()) == 109
    d = _make(7, 1 << 10)
    for col, dtype in CONN_STATS_RELATION.items():
        planes = d[col] if isinstance(d[col], tuple) else (d[col],)
        assert tuple(p.dtype for p in planes) == tuple(
            np.dtype(t) for t in host_dtypes(dtype)
        ), col


@pytest.mark.parametrize("module,name,capability", [
    ("pixie_tpu.exec.engine:Engine", "probe_group_keys", "joint_key_sizing"),
    ("pixie_tpu.exec.joins", "_in_hand_build_stats", "join_tail_sizing"),
    ("pixie_tpu.exec.stream", "_rows_in_hand", "join_tail_sizing"),
])
def test_a_program_that_lacks_what_the_file_requires_is_refused_at_once(
        monkeypatch, module, name, capability):
    """The parent's program under these benchmark files: it exits with
    the file's reason and another code than 0 before a row is made (its
    first request would compile past the request's timeout)."""
    path, _, attr = module.partition(":")
    owner = importlib.import_module(path)
    monkeypatch.delattr(getattr(owner, attr) if attr else owner, name)
    t = time.perf_counter()
    with pytest.raises(SystemExit, match=capability) as e:
        _make(7, CFG["rows"])
    assert e.value.code not in (0, None)
    assert CFG["requires"][capability] in str(e.value.code)
    assert time.perf_counter() - t < 1.0


@pytest.mark.parametrize("metric", sorted(NEW_METRICS))
def test_the_new_metrics_are_filed_under_their_layers(metric):
    entry = next(m for m in BENCHMARK["per_layer"] if m["name"] == metric)
    assert (entry["layer"], entry["source"]) == NEW_METRICS[metric]
    assert entry["moves"] == "refresh_p50_ms"
    assert entry["workloads"] == [CELL]


@pytest.mark.parametrize("metric", ["http_stats_p50_ms",
                                    "service_stats_p50_ms"])
def test_a_script_the_cell_does_not_run_lists_the_cells_that_do(metric):
    """The dashboards' two per-script medians read nothing in a cell
    whose traffic is another script: they list the accepted cells."""
    entry = next(m for m in BENCHMARK["per_layer"] if m["name"] == metric)
    assert CELL not in entry["workloads"]
    assert set(entry["workloads"]) == {
        w["name"] for w in BENCHMARK["workloads"]
        if w["traffic"].startswith("dash_")
    }


def test_flow_recent_pxl_differs_by_start_time_only():
    from pixie_tpu.scripts import load_script

    with open(os.path.join(BENCH, "traffic", "flow_recent",
                           "net_flow_graph.pxl")) as f:
        recent = f.read()
    bundled = load_script("px/net_flow_graph").pxl
    assert recent != bundled
    assert recent.replace(", start_time='-5m')", ")") == bundled
    assert recent.count("start_time") == 1


def test_data_is_the_seeds():
    a, b, c = (_make(s, 1 << 16) for s in (BIG, BIG, 7))
    assert a.pop("names") == b.pop("names") == c.pop("names")
    for k in a:
        for pa, pb in zip(*(x[k] if isinstance(x[k], tuple) else (x[k],)
                            for x in (a, b))):
            assert np.array_equal(pa, pb), k
    assert not np.array_equal(a["src_pod"], c["src_pod"])
    assert not np.array_equal(a["remote_addr"], c["remote_addr"])
    assert not np.array_equal(a["bytes_sent"], c["bytes_sent"])
    assert np.array_equal(a["time_"], c["time_"])
    assert a["time_"][-1] == CFG["t_end_ns"]
    assert np.all(np.diff(a["time_"]) > 0)


def test_five_minutes_hold_614366_rows_whatever_the_seed():
    """The times are evenly spaced from the configuration alone: at the
    cell's size '-5m' is the last 614,366 rows, inside the padded window."""
    rows = CFG["rows"]
    step = CFG["span_s"] * 10**9 // rows
    assert 300 * 10**9 // step + 1 == 614_366 < rows % CFG["window_rows"]
    d = _make(11, 1 << 16)
    lo = CFG["t_end_ns"] - 300 * 10**9
    small_step = CFG["span_s"] * 10**9 // (1 << 16)
    assert (d["time_"] >= lo).sum() == 300 * 10**9 // small_step + 1


def test_pods_peers_and_addresses_are_the_configurations():
    from pixie_tpu.config import get_flag

    rows = 1 << 20
    d = _make(BIG, rows)
    names, values = d["names"], CFG["values"]
    n_pods = values["services"] * values["pods"]
    assert len(names["src_pod"]) == len(set(names["src_pod"])) == n_pods == 4096
    assert len(names["src_addr"]) == len(set(names["src_addr"])) == n_pods
    assert len(set(names["remote_addr"])) == 8192
    # One address a pod; as a remote_addr it has another code.
    assert np.array_equal(d["src_addr"], d["src_pod"])
    assert names["remote_addr"][4096:] == names["src_addr"]
    assert not set(names["remote_addr"][:4096]) & set(names["src_addr"])
    assert np.array_equal(d["upid"][1], d["src_pod"].astype(np.uint64))
    # Every pod talks to at most 20 peers, 4 of them outside.
    pair = np.unique(d["src_pod"].astype(np.int64) * 8192 + d["remote_addr"])
    per_pod = np.bincount(pair // 8192, minlength=n_pods)
    outside = np.bincount((pair // 8192)[pair % 8192 < 4096], minlength=n_pods)
    assert per_pod.max() == 20 and outside.max() == 4
    assert len(pair) <= 81_920 < 1 << 17
    # Codes 0..len-1 and NULL_ID: what ``_static_key_domains`` multiplies.
    assert (n_pods + 1) * (8192 + 1) > get_flag("dense_domain_limit")
    lo, hi = values["bytes"]
    for col in ("bytes_sent", "bytes_recv"):
        assert lo <= d[col].min() and d[col].max() < hi == 1 << 20
    for col in ("remote_port", "trace_role", "addr_family", "protocol",
                "ssl", "conn_open", "conn_close", "conn_active"):
        assert np.all(d[col] == values[col]), col

    def law(n):
        p = 1.0 / np.arange(1, n + 1) ** 0.99
        return p / p.sum()

    # Pods by rank, the first ranks within five binomial deviations;
    # the hottest pod's peers likewise.
    got = np.sort(np.bincount(d["src_pod"], minlength=n_pods))[::-1] / rows
    want = law(n_pods)
    assert np.all(np.abs(got[:64] - want[:64])
                  < 5 * np.sqrt(want[:64] / rows) + 1e-4)
    hot = np.argmax(np.bincount(d["src_pod"], minlength=n_pods))
    mine = d["remote_addr"][d["src_pod"] == hot]
    got = np.sort(np.bincount(mine))[::-1][:20] / len(mine)
    assert np.all(np.abs(got - law(20)) < 5 * np.sqrt(law(20) / len(mine)))
    # Hot pods are not neighbouring codes (the permutation).
    top = np.argsort(np.bincount(d["src_pod"], minlength=n_pods))[::-1][:8]
    assert not np.array_equal(np.sort(top),
                              np.arange(top.min(), top.min() + 8))


# -- the plain reference ------------------------------------------------------


def _row_by_row(data, lo_ns):
    """The script's semantics spelled out over Python dicts and strings."""
    names = data["names"]
    flows, addrs = {}, set()
    for i in range(len(data["time_"])):
        if lo_ns is not None and data["time_"][i] < lo_ns:
            continue
        pod = names["src_pod"][data["src_pod"][i]]
        key = (pod, names["remote_addr"][data["remote_addr"][i]])
        sent, recv = flows.get(key, (0, 0))
        flows[key] = (sent + int(data["bytes_sent"][i]),
                      recv + int(data["bytes_recv"][i]))
        addrs.add((names["src_addr"][data["src_addr"][i]], pod))
    out = {}
    for (pod, remote), (sent, recv) in flows.items():
        for addr, dst in addrs:
            if addr == remote:
                s, r = out.get((pod, dst), (0, 0))
                out[(pod, dst)] = (s + sent, r + recv)
    return out


@pytest.mark.parametrize("lo", [None, "5m"])
def test_the_reference_equals_the_script_spelled_out_row_by_row(lo):
    from benchmark.reference import px_net_flow_graph as ref

    d = _make(BIG, 6000)
    # Shared addresses too (the cell has one a pod): pods p and p + 2,048
    # answer to one address, so the join fans out.
    d["src_addr"] = d["src_pod"] % 2048
    lo_ns = None if lo is None else CFG["t_end_ns"] - 300 * 10**9
    got, want = ref.answer(d, lo_ns), _row_by_row(d, lo_ns)
    assert got["key"] == sorted(want) and len(want) > 20
    assert [want[k][0] for k in got["key"]] == got["bytes_sent"].tolist()
    assert [want[k][1] for k in got["key"]] == got["bytes_recv"].tolist()
    # ``rows`` orders the program's table as the reference orders its own.
    order = np.random.default_rng(3).permutation(len(got["key"]))
    table = {"src_pod": [got["key"][i][0] for i in order],
             "src_pod_dst": [got["key"][i][1] for i in order],
             "bytes_sent": got["bytes_sent"][order],
             "bytes_recv": got["bytes_recv"][order]}
    assert ref.numbers(ref.rows(table), got) == EXACT
    table["bytes_recv"] = table["bytes_recv"] + (order == 0)
    assert ref.numbers(ref.rows(table), got) == {
        **EXACT, "net_flow_graph.bytes_recv_differ": 1
    }
    del table["src_pod"][-1:], table["src_pod_dst"][-1:]
    short = {k: v[:len(order) - 1] for k, v in table.items()}
    assert ref.numbers(ref.rows(short), got)[
        "net_flow_graph.keys_differ"] == 1


@pytest.mark.parametrize("rows,cut", [(1 << 16, 500), (None, None)],
                         ids=["rehearsal", "full_size"])
def test_the_controls_are_not_correct(rows, cut):
    """f32 sums, and the answer cut as the broker's default cuts it (at a
    rehearsal's size, where the answer is under 10,000 rows, at 500):
    neither is ``correct``, at the cell's size and at a rehearsal's."""
    from benchmark.control_net_flow import BROKER_DEFAULT_CUT, control_numbers

    controls, limits = control_numbers(CELL, BIG, rows,
                                       cut or BROKER_DEFAULT_CUT)
    assert limits == EXACT and len(controls) == 2
    for control, numbers in controls.items():
        over = [k for k in limits if numbers[k] > limits[k]]
        assert over, control
    f32 = controls["f32 pairwise sums"]
    assert f32["net_flow_graph.keys_differ"] == 0
    assert f32["net_flow_graph.bytes_sent_differ"] > 0
    assert f32["net_flow_graph.bytes_recv_differ"] > 0


# -- the readers this configuration brought, on a rehearsed window ------------


def _read(name, ctx):
    return importlib.import_module(f"benchmark.layer_metrics.{name}").read(ctx)


@pytest.mark.parametrize("name", ["M_MMAP_THRESHOLD", "M_TRIM_THRESHOLD",
                                  "M_TOP_PAD"])
def test_build_tells_malloc_to_keep_the_heap(name, monkeypatch):
    """``build`` sets the allocator's policy before the stack is made
    (what steadies the cell's median: PERF.md section 6, PR 32); glibc
    accepts every parameter, and each is one of malloc.h's."""
    from benchmark.builders import served_conn

    malloc_h = {"M_TRIM_THRESHOLD": -1, "M_TOP_PAD": -2,
                "M_MMAP_THRESHOLD": -3}
    params = {n: (p, v) for n, p, v in served_conn.MALLOC_KEEP}
    assert params[name][0] == malloc_h[name]
    assert 0 < params[name][1] < 1 << 31  # mallopt takes an int
    accepted = served_conn.keep_the_heap()
    assert accepted == {} or accepted[name] is True
    order = []
    monkeypatch.setattr(served_conn, "keep_the_heap",
                        lambda: order.append("malloc"))
    monkeypatch.setattr(served_conn, "ConnStack",
                        lambda cfg, rows: order.append("stack"))
    served_conn.build(CFG, 1 << 13)
    assert order == ["malloc", "stack"]


@pytest.fixture(scope="module")
def window():
    """``ctx`` of a rehearsed window of the cell, as ``harness.run_cell``
    builds it (the parts the span readers use), and its data."""
    from benchmark import harness
    from pixie_tpu.config import override_flag

    spec = harness.load_cell(CELL)
    cfg, traffic = spec["config"], spec["traffic"]
    builder = harness.module("builders", cfg["builder"])
    driver = harness.module("drivers", traffic["driver"])
    data = builder.make_data(cfg, BIG, 1 << 15)
    with override_flag("cpu_fold_threads", 1):
        stack = builder.build(cfg, 1 << 13)
        try:
            stack.ingest(data)
            assert stack.resident()["rows"] == 1 << 15
            requests = harness.requests_of(spec)
            log = harness.SpanLog(stack.tracers)
            _lo, now_ns = harness.range_lo_ns(cfg, traffic)
            for _ in range(3):
                driver.refresh(stack, requests, now_ns, 120, harness.mark)
            log.cut()
            window = driver.run(stack, traffic, requests, 0.5, now_ns,
                                harness.mark)
            spans = log.cut()
        finally:
            stack.close()
    assert window["failed"] == 0 and window["refreshes"]
    return {"window": window, "spans": spans, "trace": None,
            "requests": requests, "data": data}


def test_the_join_readers_read_the_kelvins_span(window):
    from benchmark.reference import px_net_flow_graph as ref

    joins = [s for t in window["spans"]["kelvin"] for s in t.spans
             if s.name == "join"]
    assert len(joins) == len(window["window"]["refreshes"])
    assert not any(s.name == "join" for t in window["spans"]["pem"]
                   for s in t.spans)
    a = joins[0].attributes
    assert _read("join_rows", window) == a["build_rows"] + a["probe_rows"]
    # The build is the pods with a row in range, the probe the live
    # pairs, the output the answer's edges (a pod has one address).
    d = window["data"]
    keep = d["time_"] >= CFG["t_end_ns"] - 300 * 10**9
    assert a["build_rows"] == len(np.unique(d["src_pod"][keep]))
    assert a["probe_rows"] == len(np.unique(
        d["src_pod"][keep].astype(np.int64) * 8192 + d["remote_addr"][keep]
    ))
    want = ref.answer(d, CFG["t_end_ns"] - 300 * 10**9)
    assert a["rows_out"] == len(want["key"])
    ms = sorted((s.end_ns - s.start_ns) / 1e6 for s in joins)
    assert ms[0] <= _read("join_ms", window) <= ms[-1]
    assert 0 < _read("join_ms", window) < _read("merge_ms", window)


def test_wire_mb_and_the_scripts_median(window):
    wire = {t.usage.wire_bytes for t in window["spans"]["pem"]
            if t.kind == "fragment"}
    assert len(wire) == 1 and _read("wire_mb", window) == wire.pop() / 1e6 > 0
    ms = [(r["t1"] - r["t0"]) * 1e3
          for recs in window["window"]["refreshes"] for r in recs]
    assert _read("net_flow_graph_p50_ms", window) == pytest.approx(
        np.median(ms))
    # The dashboards' medians find no request of theirs here.
    assert _read("http_stats_p50_ms", window) is None
    assert _read("service_stats_p50_ms", window) is None


def test_the_accepted_span_readers_read_the_new_cell(window):
    """Two keyed chains, two merges, a join and a re-aggregation a
    request: what the readers that list no cell make of them."""
    # The PEM's two folds and the Kelvin's two merges, re-aggregation
    # fold and finalize.
    assert _read("device_dispatches", window) == 2 + 4
    assert _read("group_refolds", window) == 0
    assert _read("staged_mb", window) == 0
    assert _read("group_slots", window) >= 1024
    for name in ("merge_ms", "head_ms", "tail_ms", "engine_ms",
                 "device_wait_ms", "broker_self_ms", "plan_ms"):
        assert _read(name, window) > 0, name


def test_the_join_readers_read_nothing_on_a_program_without_the_span(window):
    """The parent's engines leave no ``join`` span: ``join_ms`` and
    ``join_rows`` then report nothing and do not raise."""
    stripped = {**window, "spans": {}}
    for tracer, traces in window["spans"].items():
        out = []
        for t in traces:
            t = copy.copy(t)
            t.spans = [s for s in t.spans if s.name != "join"]
            out.append(t)
        stripped["spans"][tracer] = out
    assert _read("join_ms", stripped) is None
    assert _read("join_rows", stripped) is None
    assert _read("wire_mb", stripped) == _read("wire_mb", window)


# -- a rehearsal of the cell, sound and cut -----------------------------------


def _rehearse(**kw):
    from benchmark import harness

    return harness.run_cell(CELL, BIG, 1.5, True, time.time(),
                            rehearse_rows=1 << 16, **kw)


def test_a_rehearsal_of_the_cell_is_sound():
    result = _rehearse()
    assert result["rehearsal"] is True and result["failed"] == 0
    assert result["correct"] is True
    assert result["numbers"] == {k: [0.0, 0] for k in EXACT}
    metrics = result["metrics"]
    for name in NEW_METRICS:
        assert metrics[name]["value"] > 0, name
    assert metrics["window_compiles"]["value"] == 0
    assert metrics["group_refolds"]["value"] == 0
    assert metrics["staged_mb"]["value"] == 0
    assert "http_stats_p50_ms" not in metrics


def _cut_the_answer(stack):
    """Every request asks for 500 rows a table, as the broker's default
    cuts a full-size answer at 10,000."""
    import functools

    stack._execute = functools.partial(stack._execute.func,
                                       max_output_rows=500)


def test_a_cut_answer_is_not_correct():
    result = _rehearse(break_path=_cut_the_answer)
    assert result["correct"] is False and result["failed"] == 0
    assert result["numbers"]["net_flow_graph.keys_differ"][0] > 0
