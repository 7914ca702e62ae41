"""Configuration ``stack_flame_1chip`` and its cell: the file's arithmetic
and source against ``BENCHMARK.json`` and the program's own schema and
budget split, the pushes its builder makes from the seed, the plain
reference against the script spelled out row by row, the readers this
configuration brought on a rehearsed window, a rehearsal of the cell
sound and with the timed path broken underneath, and the controls. On
the CPU under the TPU's routes (``ops/routes.py``): never a device
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

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
BENCH = os.path.join(ROOT, "benchmark")
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCHMARK = json.load(f)
with open(os.path.join(BENCH, "configs", "stack_flame_1chip.json")) as f:
    CFG = json.load(f)
CELL = "stack_flame_1chip.flame_recent"
BIG = 3_900_000_019  # the driver's seeds pass 2**31
NEW_METRICS = {"perf_flamegraph_p50_ms": ("client", "host_clock"),
               "answer_rows": ("broker path", "program_counter"),
               "answer_string_mb": ("broker path", "program_counter")}
BENCHMARK_ORDER = ("perf_flamegraph_p50_ms", "answer_rows",
                   "answer_string_mb")
EXACT = {"perf_flamegraph.keys_differ": 0, "perf_flamegraph.count_differ": 0,
         "perf_flamegraph.stack_differ": 0}
RELERR = "perf_flamegraph.percent_relerr"
LO_NS = CFG["t_end_ns"] - 300 * 10**9


def _make(seed, rows):
    from benchmark.builders.served_stacks import make_data

    return make_data(CFG, seed, rows)


def test_the_file_agrees_with_benchmark_json_and_the_programs_split():
    from pixie_tpu.ingest.schemas import table_budgets

    entry = next(c for c in BENCHMARK["configs"] if c["name"] == CFG["name"])
    assert entry is BENCHMARK["configs"][-1]  # new entries go last
    assert entry["source"] == CFG["source"] and len(CFG["source"]) <= 200
    for part in ("px/perf_flamegraph", "kStackTraceTable", "11 ms", "30 s",
                 "InitSchemas", "BASELINE[4]"):
        assert part in CFG["source"], part
    assert entry["file"] == "benchmark/configs/stack_flame_1chip.json"
    assert entry["reduced"] == [] and CFG["reduced"] == {}
    cell = next(w for w in BENCHMARK["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "stack_flame_1chip", "flame_recent", 1
    )
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    # The one deployment setting, as the two newest configurations have
    # it; the table's share of the rest, by the program's mirror of
    # upstream's split, is the table.
    conn = json.load(open(os.path.join(BENCH, "configs",
                                       "conn_flow_1chip.json")))
    assert CFG["flags"] == conn["flags"]
    limit = CFG["flags"]["table_store_data_limit_mb"]
    assert table_budgets(limit)["stack_traces.beta"] == (
        CFG["budget_bytes_per_node"]
    ) == 531_261_030
    assert CFG["rows"] == 531_261_030 // 48 == 11_067_938
    # Five full windows and one padded; '-5m' lies in the last two.
    assert divmod(CFG["rows"], CFG["window_rows"]) == (5, 582_178)
    assert CFG["max_output_rows"] == 1_048_576
    from benchmark.builders.served_stacks import CAPABILITIES

    assert set(CFG["requires"]) == {
        "joint_key_sizing", "join_tail_sizing", "sorted_fold_any"
    } <= set(CAPABILITIES)
    assert all(check() for check in CAPABILITIES.values())
    assert CFG["guarantees"]["complete"].startswith(
        conn["guarantees"]["complete"])
    assert CFG["t_end_ns"] == conn["t_end_ns"]
    for k in ("services", "pods", "skew"):
        assert CFG["values"][k] == conn["values"][k], k


def test_the_table_is_the_programs_stack_traces():
    from benchmark.builders import served_stacks
    from pixie_tpu.ingest.schemas import STACK_TRACES_RELATION
    from pixie_tpu.types.dtypes import DataType, host_dtypes

    assert [(c, DataType[t]) for c, t in served_stacks.COLUMNS] == list(
        STACK_TRACES_RELATION.items()
    )
    assert tuple(CFG["columns"]) == tuple(STACK_TRACES_RELATION.column_names)
    for col, dtype in STACK_TRACES_RELATION.items():
        assert CFG["columns"][col] == sum(
            np.dtype(d).itemsize for d in host_dtypes(dtype)
        ), col
    assert CFG["bytes_per_row"] == sum(CFG["columns"].values()) == 48
    d = _make(7, 1 << 12)
    for col, dtype in STACK_TRACES_RELATION.items():
        planes = d[col] if isinstance(d[col], tuple) else (d[col],)
        assert tuple(p.dtype for p in planes) == tuple(
            np.dtype(t) for t in host_dtypes(dtype)
        ), col


@pytest.mark.parametrize("module,name,capability", [
    ("pixie_tpu.exec.engine:Engine", "probe_group_keys", "joint_key_sizing"),
    ("pixie_tpu.exec.joins", "_in_hand_build_stats", "join_tail_sizing"),
    ("pixie_tpu.exec.fold_plan", "_sort_max", "sorted_fold_any"),
])
def test_a_program_that_lacks_what_the_file_requires_is_refused_at_once(
        monkeypatch, module, name, capability):
    """The parent's program under these benchmark files: it exits with
    the file's reason and another code than 0 before a row is made (its
    first request passed the request's timeout: read on the chip)."""
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
    assert entry in BENCHMARK["per_layer"][-3:]
    assert (entry["layer"], entry["source"]) == NEW_METRICS[metric]
    assert entry["moves"] == "refresh_p50_ms"
    assert entry["workloads"] == [CELL]


def test_the_three_follow_the_host_paths_seven():
    """What ``test_host_path_metrics.py`` held of the list's end (it
    asserts PR 37's seven are LAST; ``tests/conftest.py`` marks it
    superseded): the seven as they were filed, then this cell's three."""
    from test_host_path_metrics import HOST_PATH_METRICS

    names = [m["name"] for m in BENCHMARK["per_layer"]]
    assert names[-10:] == [
        "bus_ms", "pem_head_ms", "pem_tail_ms", "fetch_ms", "fetch_mb",
        "client_ms", "unnamed_ms", *BENCHMARK_ORDER]
    per_layer = {m["name"]: m for m in BENCHMARK["per_layer"]}
    for name, (unit, source, layer) in HOST_PATH_METRICS.items():
        assert per_layer[name] == {
            "name": name, "unit": unit, "better": "lower", "source": source,
            "layer": layer, "moves": "refresh_p50_ms",
        }


def test_flame_recent_pxl_differs_by_start_time_only():
    from pixie_tpu.scripts import load_script

    with open(os.path.join(BENCH, "traffic", "flame_recent",
                           "perf_flamegraph.pxl")) as f:
        recent = f.read()
    bundled = load_script("px/perf_flamegraph").pxl
    assert recent != bundled
    assert recent.replace(", start_time='-5m')", ")") == bundled
    assert recent.count("start_time") == 1
    # Upstream's shape: the keys, the ``any``, the join, the percent.
    for part in ("groupby(['pod', 'stack_trace_id'])", "px.any", "px.sum",
                 "groupby(['pod'])", "how='inner'",
                 "100.0 * out['count'] / out['count_x']"):
        assert part in bundled, part


def test_data_is_the_seeds():
    a, b, c = (_make(s, 60_000) for s in (BIG, BIG, 7))
    assert a["names"] == b["names"] and a["names"] != c["names"]
    for k in set(a) - {"names"}:
        for pa, pb in zip(*(x[k] if isinstance(x[k], tuple) else (x[k],)
                            for x in (a, b))):
            assert np.array_equal(pa, pb), k
    assert not np.array_equal(a["pod"], c["pod"])
    assert not np.array_equal(a["count"], c["count"])
    assert np.array_equal(a["time_"], c["time_"])
    assert a["time_"][-1] == CFG["t_end_ns"]
    assert np.all(np.diff(a["time_"]) >= 0)


def test_a_push_has_no_duplicate_pair_and_eleven_lie_in_five_minutes():
    """Upstream's profiler pushes one row a distinct (upid, stack) of the
    interval, every row at the push's instant; ``stack_trace_id`` names
    the pair; the rows split over 120 pushes as evenly as integers allow,
    so '-5m' holds the same rows whatever the seed."""
    from benchmark.builders.served_stacks import push_rows

    per = push_rows(CFG, CFG["rows"])
    assert len(per) == 120 and per.sum() == CFG["rows"]
    assert set(per.tolist()) == {92_232, 92_233}
    assert per[-11:].sum() == 1_014_552 < 2 * CFG["window_rows"]
    assert per[-11:].sum() > CFG["rows"] % CFG["window_rows"]  # two windows
    rows = 60_000
    d = _make(11, rows)
    times, starts, counts = np.unique(d["time_"], return_index=True,
                                      return_counts=True)
    assert len(times) == 120 and counts.tolist() == push_rows(
        CFG, rows).tolist()
    assert set(np.diff(times).tolist()) == {30 * 10**9}
    assert (d["time_"] >= LO_NS).sum() == counts[-11:].sum()
    assert (d["time_"] >= LO_NS + 1).sum() == counts[-10:].sum()
    n_stacks = CFG["values"]["stacks_per_binary"]
    for s, n in zip(starts.tolist(), counts.tolist()):
        sid = d["stack_trace_id"][s:s + n]
        assert len(np.unique(sid)) == n  # no pair twice in a push
    # The id alone names (pod, stack): one counter, in arrival order.
    first = np.unique(d["stack_trace_id"], return_index=True)[1]
    assert np.array_equal(np.sort(first), first)
    assert d["stack_trace_id"][first].tolist() == list(range(len(first)))
    pair = d["pod"].astype(np.int64) * (32 * n_stacks) + d["stack_trace"]
    assert len(np.unique(pair)) == len(first)
    assert len(np.unique(np.stack([pair, d["stack_trace_id"]]), axis=1).T
               ) == len(first)
    assert d["count"].min() >= 1 and d["count"].max() > 1
    assert np.array_equal(d["upid"][1], d["pod"].astype(np.uint64))
    # A pod's stacks are its service's binary's: svc-<i>::main at the root.
    names, pods = d["names"]["stack_trace"], d["names"]["pod"]
    for i in np.random.default_rng(0).integers(0, rows, 50).tolist():
        stack = names[d["stack_trace"][i]]
        assert stack.startswith(pods[d["pod"][i]].split("/")[0] + "::main;")
        assert 8 <= stack.count(";") + 1 <= 64
    assert any("[k] " in s for s in names)
    assert len(set(names)) == len(names)  # arrival order, each once
    assert d["stack_trace"][0] == 0


# -- the plain reference ------------------------------------------------------


def _row_by_row(data, lo_ns):
    """The script's semantics spelled out over Python dicts and strings."""
    pods, stacks = data["names"]["pod"], data["names"]["stack_trace"]
    groups, totals = {}, {}
    for i in range(len(data["time_"])):
        if lo_ns is not None and int(data["time_"][i]) < lo_ns:
            continue
        pod, n = pods[data["pod"][i]], int(data["count"][i])
        key = (pod, int(data["stack_trace_id"][i]))
        seen = groups.setdefault(key, [set(), 0])
        seen[0].add(stacks[data["stack_trace"][i]])
        seen[1] += n
        totals[pod] = totals.get(pod, 0) + n
    return {k: (sorted(v[0]), v[1], 100.0 * v[1] / totals[k[0]])
            for k, v in groups.items()}


@pytest.mark.parametrize("lo", [None, "5m"])
def test_the_reference_equals_the_script_spelled_out_row_by_row(lo):
    from benchmark.control_perf_flamegraph import as_rows
    from benchmark.reference import px_perf_flamegraph as ref

    d = _make(BIG, 30_000)
    lo_ns = None if lo is None else LO_NS
    got, want = ref.answer(d, lo_ns), _row_by_row(d, lo_ns)
    pods = d["names"]["pod"]
    keys = [(pods[p], s) for p, s in got["key"].tolist()]
    assert sorted(keys) == sorted(want) and len(want) > 2_000
    assert [want[k][0] for k in keys] == [[s] for s in got["stack_trace"]]
    assert [want[k][1] for k in keys] == got["count"].tolist()
    assert [want[k][2] for k in keys] == got["percent"].tolist()
    sound = {**EXACT, RELERR: 0.0}
    # ``numbers`` orders the program's table as the reference orders its
    # own, whatever order the rows came in.
    order = np.random.default_rng(3).permutation(len(keys))
    table = {k: v[order] for k, v in as_rows(got).items()}
    assert ref.numbers(ref.rows(table), got) == sound
    f32 = dict(table, percent=table["percent"].astype(np.float32))
    assert 0 < ref.numbers(ref.rows(f32), got)[RELERR] < 6e-8
    table["count"] = table["count"] + (order == 0)
    assert ref.numbers(ref.rows(table), got) == {
        **sound, "perf_flamegraph.count_differ": 1}
    table["stack_trace"] = np.where(order == 1, "main;lost",
                                    table["stack_trace"])
    assert ref.numbers(ref.rows(table), got) == {
        **sound, "perf_flamegraph.count_differ": 1,
        "perf_flamegraph.stack_differ": 1}
    short = {k: v[:len(order) - 1] for k, v in table.items()}
    assert ref.numbers(ref.rows(short), got)[
        "perf_flamegraph.keys_differ"] == 1
    stranger = dict(table, pod=np.where(order == 2, "svc-x/pod-x",
                                        table["pod"]))
    assert ref.numbers(ref.rows(stranger), got)[
        "perf_flamegraph.keys_differ"] == 2


def test_the_controls_are_not_correct_at_a_rehearsals_size():
    """The percent one precision down (a float16 plane) and the answer
    cut as the broker's default cuts it (at a rehearsal's size, where
    the answer is under 10,000 rows, at 500): neither is ``correct``.
    The f32 sums are no control here, and the line says so: no count
    and no pod's total passes 2^24, so they are exact. At the cell's
    size all three are run by hand
    (``benchmark/control_perf_flamegraph.py``; PERF.md section 2)."""
    from benchmark.control_perf_flamegraph import NO_CONTROL, control_numbers
    from benchmark.reference.px_perf_flamegraph import LIMITS

    controls, limits = control_numbers(CELL, BIG, 120_000, 500)
    assert limits == LIMITS and len(controls) == 3
    exact_sums = controls.pop(NO_CONTROL)
    assert exact_sums == {**EXACT, RELERR: 0.0}
    for control, numbers in controls.items():
        assert [k for k in limits if numbers[k] > limits[k]], control
    half = controls["percent in float16"]
    assert {k: half[k] for k in EXACT} == EXACT
    assert 1e-4 < half[RELERR] < 2.0 ** -11 and limits[RELERR] == 2.0 ** -21
    assert controls["cut at 500 rows"]["perf_flamegraph.keys_differ"] > 0


# -- the readers this configuration brought, on a rehearsed window ------------


def _read(name, ctx):
    return importlib.import_module(f"benchmark.layer_metrics.{name}").read(ctx)


def test_build_keeps_the_heap(monkeypatch):
    """What steadied ``conn_flow_1chip`` is applied from the start: the
    allocator's policy before the stack is made."""
    from benchmark.builders import served_conn, served_stacks

    order = []
    monkeypatch.setattr(served_conn, "keep_the_heap",
                        lambda: order.append("malloc"))
    monkeypatch.setattr(served_stacks, "StackTraceStack",
                        lambda cfg, rows: order.append("stack"))
    served_stacks.build(CFG, 1 << 13)
    assert order == ["malloc", "stack"]


def test_kept_hands_out_views_of_its_own_memory_until_it_is_full():
    """``Kept``: a column's values in the stack's own block, as an array
    like it (numbers at 64-byte steps, strings by reference); what no
    longer fits is copied or handed on."""
    from benchmark.builders.served_stacks import Kept

    kept = Kept(1_000)
    assert (len(kept.objects), len(kept.numbers)) == (50, 600)
    a = np.arange(10, dtype=np.int64)
    f = np.linspace(0, 1, 7)
    s = np.asarray(["x", "yy", None], object)
    for v in (a, f, s):
        out = kept.keep(v)
        assert out.dtype == v.dtype and out.tolist() == v.tolist()
        assert out.base is not None and not np.shares_memory(out, v)
    assert np.shares_memory(kept.keep(a), kept.numbers)
    assert kept.numbers_at % 64 == 0
    big = np.arange(80, dtype=np.int64)
    out = kept.keep(big)  # 640 B do not fit: the client's own copy
    assert not np.shares_memory(out, kept.numbers) and out is not big
    many = np.asarray(["z"] * 60, object)
    assert kept.keep(many) is many


def test_the_harness_is_handed_views_of_kept():
    """``ingest`` sizes ``Kept`` by the rows (4 GiB at the
    configuration's size) and ``execute`` hands the harness views of it:
    the arrays the decode made are freed at once."""
    from benchmark import harness
    from benchmark.builders import served_stacks

    assert served_stacks.HARNESS_KEEPS_BYTES == 4 << 30
    rows = 20_000
    stack = served_stacks.build(CFG, 4_096)
    try:
        data = served_stacks.make_data(CFG, 3, rows)
        stack.ingest(data)
        kept = stack.kept
        assert kept.numbers.nbytes + kept.objects.nbytes == (
            (4 << 30) * rows // CFG["rows"])
        (req,) = harness.requests_of(harness.load_cell(CELL))
        with routes_of("tpu"):
            res = stack.execute(req["pxl"], 120, CFG["t_end_ns"])
    finally:
        stack.close()
    assert len(res["rows"]["pod"]) > 0
    for col, v in res["rows"].items():
        block = kept.objects if v.dtype == object else kept.numbers
        assert np.shares_memory(v, block), col


@pytest.fixture(scope="module")
def window():
    """``ctx`` of a rehearsed window of the cell under the TPU's routes,
    as ``harness.run_cell`` builds it (the parts the span readers use),
    and its data. Two of the six windows hold '-5m', as at full size."""
    from benchmark import harness
    from pixie_tpu.config import override_flag

    spec = harness.load_cell(CELL)
    cfg, traffic = spec["config"], spec["traffic"]
    builder = harness.module("builders", cfg["builder"])
    driver = harness.module("drivers", traffic["driver"])
    rows = 60_000
    data = builder.make_data(cfg, BIG, rows)
    with routes_of("tpu"), override_flag("cpu_fold_threads", 1):
        stack = builder.build(cfg, rows // 5 - 1_000)
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
    """The PEM folds the two windows in range in one scan program a
    chain: (pod, stack_trace_id) by the sort with the ``any``'s one word
    among its keys, pod by the integer kernel; the Kelvin merges both,
    joins, runs the percent ``Map`` as a fragment of its own and hands
    the answer back with its rows and string bytes counted."""
    from benchmark.reference import px_perf_flamegraph as ref

    pem = window["spans"]["pem"][-1]
    folds = [s.attributes for s in pem.spans
             if s.name == "device.dispatch" and "fold" in s.attributes]
    assert [(a["fold"], a["group"], a["windows"]) for a in folds] == [
        ("sorted_int", "sorted", 2), ("pallas_int", "dense", 2)]
    keyed, dense = folds
    assert keyed["max_words"] == 1 and keyed["ride"] == "index"
    assert "max_words" not in dense and dense["slots"] == 4_097
    assert not [s for t in window["spans"]["pem"] + window["spans"]["kelvin"]
                for s in t.spans if s.name == "rebucket"]
    kelvin = window["spans"]["kelvin"][-1]
    names = [s.name for s in kelvin.spans]
    assert names.count("join") == 1 and names.count("restream") == 1
    programs = [s.attributes["program"] for s in kelvin.spans
                if s.name == "device.dispatch"]
    assert programs == ["merge_finalize", "merge_finalize", "fragment_update"]
    (answer,) = [s for s in kelvin.spans if s.name == "payload"]
    want = ref.answer(window["data"], LO_NS)
    assert answer.attributes == {
        "kind": "result", "rows": len(want["key"]),
        "string_bytes": sum(len(s) for s in want["stack_trace"]) + sum(
            len(want["pods"][p]) for p in want["key"][:, 0].tolist()),
    }
    assert kelvin.usage.answer_rows == len(want["key"])
    assert kelvin.usage.string_bytes_out == answer.attributes["string_bytes"]
    assert pem.usage.answer_rows == 0 == pem.usage.string_bytes_out


def test_the_new_readers_read_the_engines_counters(window):
    from benchmark.reference import px_perf_flamegraph as ref

    want = ref.answer(window["data"], LO_NS)
    assert _read("answer_rows", window) == len(want["key"])
    assert _read("answer_string_mb", window) == pytest.approx(
        window["spans"]["kelvin"][-1].usage.string_bytes_out / 1e6)
    ms = [(r["t1"] - r["t0"]) * 1e3
          for recs in window["window"]["refreshes"] for r in recs]
    assert _read("perf_flamegraph_p50_ms", window) == pytest.approx(
        np.median(ms))
    for other in ("http_stats_p50_ms", "service_stats_p50_ms",
                  "net_flow_graph_p50_ms", "sql_stats_p50_ms"):
        assert _read(other, window) is None, other


def test_the_accepted_span_readers_read_the_new_cell(window):
    """Two chains, two merges, a join and a ``Map`` a request: what the
    readers that list no cell (and the join's, which list another) make
    of them."""
    assert _read("device_dispatches", window) == 2 + 3
    assert _read("group_refolds", window) == 0
    assert _read("staged_mb", window) == 0
    assert _read("group_slots", window) >= 8_192
    assert _read("join_rows", window) == _read("answer_rows", window) + len(
        np.unique(window["data"]["pod"][window["data"]["time_"] >= LO_NS]))
    for name in ("merge_ms", "head_ms", "tail_ms", "engine_ms", "join_ms",
                 "device_wait_ms", "broker_self_ms", "plan_ms", "fetch_mb"):
        assert _read(name, window) > 0, name


def test_the_new_readers_read_nothing_on_a_program_without_them(window):
    """The parent's usage record has neither counter: the two readers
    then report nothing and do not raise; the script's median is the
    client's."""
    stripped = {**window, "spans": {}}
    for tracer, traces in window["spans"].items():
        out = []
        for t in traces:
            t = copy.copy(t)
            t.usage = types.SimpleNamespace(**{
                k: v for k, v in dataclasses.asdict(t.usage).items()
                if k not in ("answer_rows", "string_bytes_out")})
            out.append(t)
        stripped["spans"][tracer] = out
    assert _read("answer_rows", stripped) is None
    assert _read("answer_string_mb", stripped) is None
    assert _read("perf_flamegraph_p50_ms", stripped) == (
        _read("perf_flamegraph_p50_ms", window))
    assert _read("group_slots", stripped) == _read("group_slots", window)
    assert _read("answer_rows", {**window, "spans": {
        k: [] for k in window["spans"]}}) is None


# -- a rehearsal of the cell, sound and broken underneath ---------------------


def _rehearse(rows=120_000, **kw):
    from benchmark import harness

    with routes_of("tpu"):
        return harness.run_cell(CELL, BIG, 1.5, True, time.time(),
                                rehearse_rows=rows, **kw)


def test_a_rehearsal_of_the_cell_is_sound():
    result = _rehearse()
    assert result["rehearsal"] is True and result["failed"] == 0
    assert result["correct"] is True
    assert {k: result["numbers"][k] for k in EXACT} == {
        k: [0.0, 0] for k in EXACT}
    relerr, limit = result["numbers"][RELERR]
    assert 0 < relerr < 1.2e-7 < limit  # f32 planes, two roundings
    metrics = result["metrics"]
    assert metrics["perf_flamegraph_p50_ms"]["value"] > 0
    assert metrics["answer_rows"]["value"] > 5_000
    assert metrics["answer_string_mb"]["value"] > 5
    assert metrics["group_slots"]["value"] >= 8_192
    assert metrics["window_compiles"]["value"] == 0
    assert metrics["group_refolds"]["value"] == 0
    assert metrics["staged_mb"]["value"] == 0
    assert "sql_stats_p50_ms" not in metrics and "join_ms" not in metrics


def _cut_the_answer(stack):
    """Every request asks for 500 rows a table, as the broker's default
    cuts a full-size answer at 10,000."""
    import functools

    stack._execute = functools.partial(stack._execute.func,
                                       max_output_rows=500)


def test_a_cut_answer_is_not_correct():
    result = _rehearse(break_path=_cut_the_answer)
    assert result["correct"] is False and result["failed"] == 0
    assert result["numbers"]["perf_flamegraph.keys_differ"][0] > 0


def _with_uda(stack, name, arg_type, **changes):
    """Both engines' ``name`` UDA of ``arg_type`` with ``changes``."""
    for engine in (stack.pem.engine, stack.kelvin.engine):
        reg = engine.registry.clone(f"broken-{name}")
        reg._uda[name] = [
            dataclasses.replace(d, **changes) if d.arg_types == (arg_type,)
            else d for d in reg._uda[name]
        ]
        engine.registry = reg


def _lose_the_any(stack):
    """``any`` of a string hands back the string BEFORE the group's in
    the dictionary: a member of no row of the group."""
    import jax.numpy as jnp

    from pixie_tpu.types.dtypes import DataType

    _with_uda(stack, "any", DataType.STRING,
              finalize=lambda c: jnp.maximum(c - 1, 0))


def test_a_lost_any_is_not_correct():
    result = _rehearse(break_path=_lose_the_any)
    assert result["failed"] == 0 and result["correct"] is False
    numbers = result["numbers"]
    assert numbers["perf_flamegraph.stack_differ"][0] > 0
    assert numbers["perf_flamegraph.keys_differ"] == [0.0, 0]
    assert numbers["perf_flamegraph.count_differ"] == [0.0, 0]


def _f32_sums(stack):
    """Both engines' ``sum`` of an INT64 column in 32-bit floats, one
    precision under the exact INT64 sum the file states (the fold keeps
    its route: the carry is cast where the state is finalized)."""
    import jax.numpy as jnp

    from pixie_tpu.types.dtypes import DataType

    _with_uda(stack, "sum", DataType.INT64,
              finalize=lambda c: c.astype(jnp.float32).astype(jnp.int64))


def test_f32_sums_are_exact_on_this_data_and_sound():
    """What ISSUE.md took for a control is none: a count is at most a
    few thousand and a pod's five minutes of samples under 2^24, so an
    f32 holds every sum exactly and the run is ``correct``. The
    precision control of this cell is the percent's plane
    (``control_perf_flamegraph.py``)."""
    d = _make(BIG, 120_000)
    keep = d["time_"] >= LO_NS
    assert np.bincount(d["pod"][keep], weights=d["count"][keep]).max() < (
        1 << 24)
    result = _rehearse(break_path=_f32_sums)
    assert result["failed"] == 0 and result["correct"] is True
