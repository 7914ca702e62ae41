"""Configuration ``sql_stats_1chip`` and its cell: the file's arithmetic
and source against ``BENCHMARK.json`` and the program's own schema and
budget split, the statements its builder makes from the seed, the plain
reference's scanner against the templates and the program's normaliser,
the reference against a row-by-row one, the readers this configuration
brought on a rehearsed window, a rehearsal of the cell sound and with
the timed path broken underneath three ways, and the two controls. On
the CPU: never a device number from here."""

import copy
import dataclasses
import importlib
import json
import os
import time
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
BENCH = os.path.join(ROOT, "benchmark")
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCHMARK = json.load(f)
with open(os.path.join(BENCH, "configs", "sql_stats_1chip.json")) as f:
    CFG = json.load(f)
CELL = "sql_stats_1chip.sql_recent"
BIG = 3_400_000_019  # the driver's seeds pass 2**31
NEW_METRICS = {"sql_stats_p50_ms": ("client", "host_clock"),
               "dict_udf_ms": ("fragment programs", "program_span"),
               "dict_udf_strings": ("fragment programs", "program_counter"),
               "remap_entries": ("fragment programs", "program_counter")}
EXACT = {"sql_stats.keys_differ": 0, "sql_stats.n_differ": 0}


def _make(seed, rows):
    from benchmark.builders.served_sql import make_data

    return make_data(CFG, seed, rows)


def test_the_file_agrees_with_benchmark_json_and_the_programs_split():
    from pixie_tpu.ingest.schemas import table_budgets

    entry = next(c for c in BENCHMARK["configs"] if c["name"] == CFG["name"])
    assert entry["source"] == CFG["source"] and len(CFG["source"]) <= 200
    assert entry["file"] == "benchmark/configs/sql_stats_1chip.json"
    assert entry["reduced"] == [] and CFG["reduced"] == {}
    cell = next(w for w in BENCHMARK["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "sql_stats_1chip", "sql_recent", 1
    )
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    # The one deployment setting, as ``conn_flow_1chip`` has it: the
    # least budget whose 40 % holds the chip's share of the replay's
    # http_events; mysql_events' share of the rest, by the program's
    # mirror of upstream's split, is the table.
    conn = json.load(open(os.path.join(BENCH, "configs",
                                       "conn_flow_1chip.json")))
    assert CFG["flags"] == conn["flags"]
    limit = CFG["flags"]["table_store_data_limit_mb"]
    assert table_budgets(limit)["http_events"] >= 125_000_000 * 68 > (
        table_budgets(limit - 1)["http_events"]
    )
    assert table_budgets(limit)["mysql_events"] == (
        CFG["budget_bytes_per_node"]
    ) == 531_261_030
    assert CFG["rows"] == 531_261_030 // 56 == 9_486_804
    # Four full windows and one padded, which holds all of '-5m'.
    assert divmod(CFG["rows"], CFG["window_rows"]) == (4, 1_098_196)
    assert CFG["max_output_rows"] == 131_072 >= 290 * 301
    from benchmark.builders.served_sql import CAPABILITIES

    # What the cell cannot run without, named where the deployment is.
    assert set(CFG["requires"]) == {
        "joint_key_sizing", "computed_key_sizing", "dictionary_udf_memo"
    } <= set(CAPABILITIES)
    assert all(check() for check in CAPABILITIES.values())
    full = json.load(open(os.path.join(BENCH, "configs",
                                       "http_full_1chip.json")))
    assert CFG["guarantees"]["complete"] == full["guarantees"]["complete"]
    assert CFG["t_end_ns"] == full["t_end_ns"]
    assert CFG["values"]["services"] == full["values"]["services"]
    assert CFG["values"]["skew"] == full["values"]["skew"]


def test_the_table_is_the_programs_mysql_events():
    from benchmark.builders import served_sql
    from pixie_tpu.ingest.schemas import MYSQL_EVENTS_RELATION
    from pixie_tpu.types.dtypes import DataType, host_dtypes

    assert [(c, DataType[t]) for c, t in served_sql.COLUMNS] == list(
        MYSQL_EVENTS_RELATION.items()
    )
    assert tuple(CFG["columns"]) == tuple(MYSQL_EVENTS_RELATION.column_names)
    for col, dtype in MYSQL_EVENTS_RELATION.items():
        assert CFG["columns"][col] == sum(
            np.dtype(d).itemsize for d in host_dtypes(dtype)
        ), col
    assert CFG["bytes_per_row"] == sum(CFG["columns"].values()) == 56
    d = _make(7, 1 << 10)
    for col, dtype in MYSQL_EVENTS_RELATION.items():
        planes = d[col] if isinstance(d[col], tuple) else (d[col],)
        assert tuple(p.dtype for p in planes) == tuple(
            np.dtype(t) for t in host_dtypes(dtype)
        ), col


@pytest.mark.parametrize("module,name,capability", [
    ("pixie_tpu.exec.engine:Engine", "probe_group_keys", "joint_key_sizing"),
    ("pixie_tpu.exec.stream", "_computed_group_keys", "computed_key_sizing"),
    ("pixie_tpu.types.strings:StringDictionary", "image",
     "dictionary_udf_memo"),
])
def test_a_program_that_lacks_what_the_file_requires_is_refused_at_once(
        monkeypatch, module, name, capability):
    """The parent's program under these benchmark files: it exits with
    the file's reason and another code than 0 before a row is made (its
    first request would pass the request's timeout: read on the chip)."""
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


def test_sql_recent_pxl_differs_by_start_time_only():
    from pixie_tpu.scripts import load_script

    with open(os.path.join(BENCH, "traffic", "sql_recent",
                           "sql_stats.pxl")) as f:
        recent = f.read()
    bundled = load_script("px/sql_stats").pxl
    assert recent != bundled
    assert recent.replace(", start_time='-5m')", ")") == bundled
    assert recent.count("start_time") == 1


def test_data_is_the_seeds():
    a, b, c = (_make(s, 1 << 16) for s in (BIG, BIG, 7))
    assert a["names"] == b["names"] and a["names"] != c["names"]
    for k in set(a) - {"names"}:
        for pa, pb in zip(*(x[k] if isinstance(x[k], tuple) else (x[k],)
                            for x in (a, b))):
            assert np.array_equal(pa, pb), k
    assert not np.array_equal(a["service"], c["service"])
    assert not np.array_equal(a["latency_ns"], c["latency_ns"])
    assert np.array_equal(a["time_"], c["time_"])
    assert a["time_"][-1] == CFG["t_end_ns"]
    assert np.all(np.diff(a["time_"]) > 0)


def test_five_minutes_hold_790568_rows_whatever_the_seed():
    """The times are evenly spaced from the configuration alone: at the
    cell's size '-5m' is the last 790,568 rows, inside the padded window."""
    rows = CFG["rows"]
    step = CFG["span_s"] * 10**9 // rows
    assert step == 379_474
    assert 300 * 10**9 // step + 1 == 790_568 < rows % CFG["window_rows"]
    d = _make(11, 1 << 16)
    lo = CFG["t_end_ns"] - 300 * 10**9
    small_step = CFG["span_s"] * 10**9 // (1 << 16)
    assert (d["time_"] >= lo).sum() == 300 * 10**9 // small_step + 1


def test_a_transaction_is_sysbenchs_twenty_statements_in_order():
    from benchmark.builders import served_sql

    values = CFG["values"]
    kinds = [k for k, _t in served_sql.STATEMENTS]
    assert len(kinds) == values["statements_per_transaction"] == 20
    assert kinds.count("point") == 10
    assert (kinds[0], kinds[-1]) == ("begin", "commit")
    rows = 20 * 13_000
    d = _make(BIG, rows)
    names = d["names"]["query_str"]
    text = [names[c] for c in d["query_str"][:40].tolist()]
    # The first two transactions, statement for statement.
    for tx in (text[:20], text[20:]):
        table = tx[1].split()[3]
        assert table.startswith("sbtest")
        assert tx[0] == "BEGIN" and tx[19] == "COMMIT"
        assert all(s.startswith(f"SELECT c FROM {table} WHERE id=")
                   for s in tx[1:11])
        lo, hi = (int(x) for x in tx[11].split("BETWEEN ")[1].split(" AND "))
        assert hi - lo == values["range_size"] - 1
        assert tx[12].startswith(f"SELECT SUM(k) FROM {table} ")
        assert tx[13].endswith("ORDER BY c") and "DISTINCT" not in tx[13]
        assert tx[14].startswith(f"SELECT DISTINCT c FROM {table} ")
        assert tx[15].startswith(f"UPDATE {table} SET k=k+1 WHERE id=")
        c = tx[16].split("'")[1]
        assert len(c) == 119 and c.count("-") == 9
        gone = tx[17].split("id=")[1]
        assert tx[18].startswith(
            f"INSERT INTO {table} (id, k, c, pad) VALUES ({gone}, ")
        assert len(tx[18].split("'")[3]) == 59
    # A row's service is its transaction's; the table is the service's.
    svc = d["service"].reshape(-1, 20)
    assert np.all(svc == svc[:, :1])
    assert text[1].split()[3] == f"sbtest{svc[0, 0] + 1}"
    assert np.array_equal(d["upid"][1], d["service"].astype(np.uint64))
    assert np.all(d["req_cmd"] == values["req_cmd"])
    assert np.all(d["resp_status"] == values["resp_status"])
    lo, hi = values["latency_ns_loguniform"]
    assert lo <= d["latency_ns"].min() and d["latency_ns"].max() < hi
    assert d["latency_ns"].max() > 1 << 24  # an f32 cannot hold it
    # The dictionary is in arrival order and holds every code once.
    codes = d["query_str"]
    assert len(names) == len(set(names)) == codes.max() + 1
    first = np.unique(codes, return_index=True)[1]
    assert np.all(np.diff(first) > 0)
    # The two statements with a random c are new every time: a tenth of
    # the rows; the others repeat.
    assert len(names) > rows // 10
    fresh = sum(1 for s in names if "'" in s)
    assert fresh == 2 * (rows // 20)

    # Services by rank, within five binomial deviations.
    def law(n):
        p = 1.0 / np.arange(1, n + 1) ** 0.99
        return p / p.sum()

    tx = rows // 20
    got = np.sort(np.bincount(svc[:, 0], minlength=32))[::-1] / tx
    assert np.all(np.abs(got - law(32)) < 5 * np.sqrt(law(32) / tx) + 1e-4)


def test_the_deployment_has_290_query_shapes():
    from benchmark.builders import served_sql

    shapes = served_sql.shapes(CFG)
    assert len(shapes) == len(set(shapes)) == 290 == 9 * 32 + 2
    assert not any(ch.isdigit() for s in shapes
                   for ch in s.replace("sbtest", " ").split(" ", 1)[0])


def _templates():
    from benchmark.builders import served_sql

    return sorted(dict(served_sql.STATEMENTS))


@pytest.mark.parametrize("kind", _templates())
def test_scanner_and_normalizer_give_the_templates_shape(kind):
    """The reference's scanner and the program's ``normalize_sql`` make
    of every statement of the kind the generator's own template with
    ``?`` for its literals, statement for statement."""
    from benchmark.builders import served_sql
    from benchmark.reference import px_sql_stats as ref
    from pixie_tpu.udf.builtins.sql_ops import normalize_sql

    rows = 20 * 1_600
    d = _make(BIG, rows)
    column = [k for k, _t in served_sql.STATEMENTS].index(kind)
    shapes = set(served_sql.shapes(CFG))
    seen = 0
    for tx in range(0, rows, 20):
        q = d["names"]["query_str"][d["query_str"][tx + column]]
        t = d["service"][tx] + 1
        want = dict(served_sql.STATEMENTS)[kind].replace("k=k+1", "k=k+?")
        want = want.replace("'{c}'", "?").replace("'{pad}'", "?").format(
            t=t, id="?", a="?", b="?", k="?")
        assert ref.shape(q) == normalize_sql(q) == want
        assert want in shapes
        seen += 1
    assert seen == 1_600


@pytest.mark.parametrize("q", [
    "select * from t where a in (1, 2,3 ) and b IN(4)",
    "SELECT 'it''s', \"a\\\"b\", 'x\\'y' FROM t1 WHERE x=1.50 AND y=2.",
    "UPDATE t2 SET v=v+10, w='12' WHERE id=7 AND name='o''brien'",
    "SELECT col1, t1.c2, 3e5, 0x1F, .5, 1.5e3 FROM db1.t",
    "  SELECT\t1 ,\n 'unclosed FROM t WHERE id IN ( 1 , 2 )  ",
    "select abc'x'123, 9abc, abc9, _9, 9_ from t",
    "INSERT INTO t VALUES (1,'a'),(2,\"b\"); select x in (select 1)",
    "SELECT 'a\\\\', 'b' WHERE id in (?,?) or id IN (?, 5)",
    "",
])
def test_the_scanner_is_the_normalizers_rule_off_the_templates(q):
    """Quotes with escapes and doubled quotes, numbers inside names and
    with fractions, IN-lists in either case, white space: one rule."""
    from benchmark.reference import px_sql_stats as ref
    from pixie_tpu.udf.builtins.sql_ops import normalize_sql

    assert ref.shape(q) == normalize_sql(q)


# -- the plain reference ------------------------------------------------------


def _row_by_row(data, lo_ns):
    """The script's semantics spelled out over Python dicts and strings,
    the shape by the program's own normaliser."""
    from pixie_tpu.udf.builtins.sql_ops import normalize_sql

    names = data["names"]["query_str"]
    out = {}
    for i in range(len(data["time_"])):
        t = int(data["time_"][i])
        if lo_ns is not None and t < lo_ns:
            continue
        key = (normalize_sql(names[data["query_str"][i]]), t - t % 10**9)
        n, total = out.get(key, (0, 0))
        out[key] = (n + 1, total + int(data["latency_ns"][i]))
    return out


@pytest.mark.parametrize("lo", [None, "5m"])
def test_the_reference_equals_the_script_spelled_out_row_by_row(lo):
    from benchmark.reference import px_sql_stats as ref

    d = _make(BIG, 20_000)
    lo_ns = None if lo is None else CFG["t_end_ns"] - 300 * 10**9
    got, want = ref.answer(d, lo_ns), _row_by_row(d, lo_ns)
    assert got["key"] == sorted(want) and len(want) > 200
    assert [want[k][0] for k in got["key"]] == got["n"].tolist()
    assert [want[k][1] / want[k][0] for k in got["key"]] == (
        got["lat_mean"].tolist())
    sound = {**EXACT, "sql_stats.lat_mean_relerr": pytest.approx(0, abs=6e-8),
             "sql_stats.lat_mean_misrounded_share": 0.0}
    # ``rows`` orders the program's table as the reference orders its
    # own; the program's lat_mean is the f32 nearest the exact mean.
    order = np.random.default_rng(3).permutation(len(got["key"]))
    table = {"query_norm": [got["key"][i][0] for i in order],
             "window": np.asarray([got["key"][i][1] for i in order]),
             "n": got["n"][order],
             "lat_mean": got["lat_mean"][order].astype(np.float32)}
    assert ref.numbers(ref.rows(table), got) == sound
    table["n"] = table["n"] + (order == 0)
    assert ref.numbers(ref.rows(table), got) == {
        **sound, "sql_stats.n_differ": 1}
    short = {k: v[:len(order) - 1] for k, v in table.items()}
    assert ref.numbers(ref.rows(short), got)["sql_stats.keys_differ"] == 1


def test_the_controls_are_not_correct_at_a_rehearsals_size():
    """f32 sums, and the answer cut as the broker's default cuts it (at a
    rehearsal's size, where the answer is under 10,000 rows, at 500):
    neither is ``correct``. At the cell's size both are run by hand
    (``benchmark/control_sql_stats.py``; PERF.md section 2)."""
    from benchmark.control_sql_stats import control_numbers
    from benchmark.reference.px_sql_stats import LIMITS

    controls, limits = control_numbers(CELL, BIG, 1 << 18, 500)
    assert limits == LIMITS and len(controls) == 2
    for control, numbers in controls.items():
        assert [k for k in limits if numbers[k] > limits[k]], control
    f32 = controls["f32 pairwise sums"]
    assert f32["sql_stats.keys_differ"] == f32["sql_stats.n_differ"] == 0
    # (0.077 here, where most groups hold a row or two; 0.23-0.24 at the
    # cell's size: PERF.md section 2.)
    assert f32["sql_stats.lat_mean_misrounded_share"] > (
        limits["sql_stats.lat_mean_misrounded_share"])
    cut = controls["cut at 500 rows"]
    assert cut["sql_stats.keys_differ"] > 0


# -- the readers this configuration brought, on a rehearsed window ------------


def _read(name, ctx):
    return importlib.import_module(f"benchmark.layer_metrics.{name}").read(ctx)


def test_build_keeps_the_heap_and_hands_out_copies(monkeypatch):
    """What steadied ``conn_flow_1chip`` is applied from the start: the
    allocator's policy before the stack is made, and the answer's number
    columns as the client's own copies."""
    from benchmark.builders import served_conn, served_sql

    assert served_sql.SqlStack.execute is served_conn.ConnStack.execute
    order = []
    monkeypatch.setattr(served_conn, "keep_the_heap",
                        lambda: order.append("malloc"))
    monkeypatch.setattr(served_sql, "SqlStack",
                        lambda cfg, rows: order.append("stack"))
    served_sql.build(CFG, 1 << 13)
    assert order == ["malloc", "stack"]


@pytest.fixture(scope="module")
def window():
    """``ctx`` of a rehearsed window of the cell, as ``harness.run_cell``
    builds it (the parts the span readers use), with the traces of the
    warm-up's first request kept aside, and its data."""
    from benchmark import harness
    from pixie_tpu.config import override_flag

    spec = harness.load_cell(CELL)
    cfg, traffic = spec["config"], spec["traffic"]
    builder = harness.module("builders", cfg["builder"])
    driver = harness.module("drivers", traffic["driver"])
    data = builder.make_data(cfg, BIG, 1 << 16)
    with override_flag("cpu_fold_threads", 1):
        stack = builder.build(cfg, 1 << 14)
        try:
            stack.ingest(data)
            assert stack.resident()["rows"] == 1 << 16
            requests = harness.requests_of(spec)
            log = harness.SpanLog(stack.tracers)
            _lo, now_ns = harness.range_lo_ns(cfg, traffic)
            for _ in range(3):
                driver.refresh(stack, requests, now_ns, 120, harness.mark)
            warmup = log.cut()
            window = driver.run(stack, traffic, requests, 0.5, now_ns,
                                harness.mark)
            spans = log.cut()
        finally:
            stack.close()
    assert window["failed"] == 0 and window["refreshes"]
    return {"window": window, "spans": spans, "trace": None,
            "requests": requests, "data": data, "warmup": warmup}


def test_the_dict_udf_readers_read_the_engines_spans(window):
    """Warm, a request binds nothing: no ``dict_udf`` span, 0 strings,
    0 ms. The warm-up's first request bound the UDF over the whole
    dictionary once, and every later bind of it hit."""
    assert _read("dict_udf_strings", window) == 0
    assert _read("dict_udf_ms", window) == 0
    spans = [s for tracer in ("pem", "kelvin")
             for t in window["warmup"][tracer] for s in t.spans
             if s.name == "dict_udf"]
    entries = len(window["data"]["names"]["query_str"])
    assert spans and {s.attributes["udf"] for s in spans} == {
        "normalize_mysql"}
    assert {s.attributes["entries"] for s in spans} == {entries}
    memo = [s.attributes["memo"] for s in sorted(spans,
                                                 key=lambda s: s.start_ns)]
    assert memo[0] == "miss" and set(memo[1:]) == {"hit"}
    assert sum(s.attributes["strings"] for s in spans) == entries
    counted = sum(t.usage.dict_udf_strings for tracer in ("pem", "kelvin")
                  for t in window["warmup"][tracer])
    assert counted == entries


def test_remap_entries_and_the_scripts_median(window):
    from pixie_tpu.types.batch import bucket_capacity

    entries = len(window["data"]["names"]["query_str"])
    assert _read("remap_entries", window) == bucket_capacity(entries + 1)
    ms = [(r["t1"] - r["t0"]) * 1e3
          for recs in window["window"]["refreshes"] for r in recs]
    assert _read("sql_stats_p50_ms", window) == pytest.approx(np.median(ms))
    for other in ("http_stats_p50_ms", "service_stats_p50_ms",
                  "net_flow_graph_p50_ms", "join_ms", "join_rows"):
        assert _read(other, window) is None, other


def test_the_accepted_span_readers_read_the_new_cell(window):
    """One keyed chain and one merge a request: what the readers that
    list no cell make of them."""
    assert _read("device_dispatches", window) == 1 + 1
    assert _read("group_refolds", window) == 0
    assert _read("staged_mb", window) == 0
    assert _read("group_slots", window) >= 1024
    for name in ("merge_ms", "head_ms", "tail_ms", "engine_ms",
                 "device_wait_ms", "broker_self_ms", "plan_ms"):
        assert _read(name, window) > 0, name


def test_the_new_readers_read_nothing_on_a_program_without_them(window):
    """The parent's usage record has no ``dict_udf_strings`` and its
    dispatch spans no ``remap_entries``: the three readers then report
    nothing and do not raise; the script's median is the client's."""
    stripped = {**window, "spans": {}}
    for tracer, traces in window["spans"].items():
        out = []
        for t in traces:
            t = copy.copy(t)
            t.usage = types.SimpleNamespace(**{
                k: v for k, v in dataclasses.asdict(t.usage).items()
                if k != "dict_udf_strings"})
            mine, t.spans = t.spans, []
            for s in mine:
                s = copy.copy(s)
                s.attributes = {k: v for k, v in s.attributes.items()
                                if k != "remap_entries"}
                t.spans.append(s)
            out.append(t)
        stripped["spans"][tracer] = out
    assert _read("dict_udf_strings", stripped) is None
    assert _read("dict_udf_ms", stripped) is None
    assert _read("remap_entries", stripped) is None
    assert _read("sql_stats_p50_ms", stripped) == (
        _read("sql_stats_p50_ms", window))
    assert _read("group_slots", stripped) == _read("group_slots", window)


# -- a rehearsal of the cell, sound and broken underneath ---------------------


def _rehearse(rows=1 << 17, **kw):
    from benchmark import harness

    return harness.run_cell(CELL, BIG, 1.5, True, time.time(),
                            rehearse_rows=rows, **kw)


def test_a_rehearsal_of_the_cell_is_sound():
    result = _rehearse()
    assert result["rehearsal"] is True and result["failed"] == 0
    assert result["correct"] is True
    assert {k: result["numbers"][k] for k in EXACT} == {
        k: [0.0, 0] for k in EXACT}
    assert result["numbers"]["sql_stats.lat_mean_misrounded_share"][0] == 0
    metrics = result["metrics"]
    assert metrics["sql_stats_p50_ms"]["value"] > 0
    assert metrics["remap_entries"]["value"] >= 1024
    assert metrics["dict_udf_strings"]["value"] == 0
    assert metrics["dict_udf_ms"]["value"] == 0
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
    assert result["numbers"]["sql_stats.keys_differ"][0] > 0


def test_a_stale_memo_is_not_correct(monkeypatch):
    """An image that was not taken on by the strings its dictionary
    gained (the newer half of them read shape 0): the groups' counts are
    wrong, and the run is not ``correct``."""
    from pixie_tpu.types.strings import DictImage, StringDictionary

    real = StringDictionary.image

    def stale(self, fn, key):
        img, memo, ran = real(self, fn, key)
        if len(img.remap) < 1000:
            return img, memo, ran
        remap = img.remap.copy()
        remap[len(remap) // 2:] = 0
        return DictImage(img.dict, remap), memo, ran

    monkeypatch.setattr(StringDictionary, "image", stale)
    result = _rehearse()
    assert result["correct"] is False and result["failed"] == 0
    assert result["numbers"]["sql_stats.n_differ"][0] > 0


def _f32_sums(stack):
    """Both engines' ``mean`` of an INT64 column sums in 32-bit floats:
    one precision under the exact INT64 sum the file states."""
    import jax
    import jax.numpy as jnp

    from pixie_tpu.types.dtypes import DataType

    def update(c, gids, mask, v):
        g = c[0].shape[0]
        at = jnp.where(mask, gids, 0)
        return (
            c[0] + jax.ops.segment_sum(
                jnp.where(mask, v.astype(jnp.float32), 0), at, g),
            c[1] + jax.ops.segment_sum(mask.astype(jnp.int64), at, g),
        )

    for engine in (stack.pem.engine, stack.kelvin.engine):
        reg = engine.registry.clone("f32-mean")
        reg._uda["mean"] = [
            dataclasses.replace(
                d, update=update,
                init=lambda g: (jnp.zeros(g, jnp.float32),
                                jnp.zeros(g, jnp.int64)),
                finalize=lambda c: jnp.where(
                    c[1] > 0, c[0] / jnp.maximum(c[1], 1), jnp.nan),
            ) if d.arg_types == (DataType.INT64,) else d
            for d in reg._uda["mean"]
        ]
        engine.registry = reg


def test_f32_sums_are_not_correct():
    result = _rehearse(break_path=_f32_sums, rows=1 << 19)
    assert result["failed"] == 0 and result["correct"] is False
    assert {k: result["numbers"][k] for k in EXACT} == {
        k: [0.0, 0] for k in EXACT}
    share, limit = result["numbers"]["sql_stats.lat_mean_misrounded_share"]
    assert share > limit
