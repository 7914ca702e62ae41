"""Self-checks of the benchmark's own files (``benchmark/``): what
``BENCHMARK.json`` names exists and agrees with it, the yardstick's
arithmetic, and that a later PR can add a cell by adding files."""

import importlib
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
BENCH = os.path.join(ROOT, "benchmark")

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCHMARK = json.load(f)
CELLS = [w["name"] for w in BENCHMARK["workloads"]]


@pytest.mark.parametrize("metric", BENCHMARK["per_layer"],
                         ids=lambda m: m["name"])
def test_per_layer_metric_has_its_reader(metric):
    mod = importlib.import_module(f"benchmark.layer_metrics.{metric['name']}")
    assert callable(mod.read)
    e2e = {m["name"]: m for m in BENCHMARK["end_to_end"]}
    moved = e2e[metric["moves"]]
    for cell in metric.get("workloads", CELLS):
        assert cell in moved.get("workloads", CELLS), (
            f"{metric['name']} moves {metric['moves']}, which {cell} "
            "does not report"
        )


@pytest.mark.parametrize("metric", BENCHMARK["end_to_end"],
                         ids=lambda m: m["name"])
def test_end_to_end_metric_has_its_reader(metric):
    mod = importlib.import_module(f"benchmark.end_to_end.{metric['name']}")
    assert callable(mod.read)
    assert 0.01 <= metric["bound"] <= 0.25


@pytest.mark.parametrize("cell", BENCHMARK["workloads"],
                         ids=lambda w: w["name"])
def test_cell_files_exist(cell):
    from benchmark import harness

    spec = harness.load_cell(cell["name"])
    cfg, traffic = spec["config"], spec["traffic"]
    assert cfg["name"] == cell["config"] and cfg["chips"] == cell["chips"]
    assert traffic["name"] == cell["traffic"]
    assert cfg["bytes_per_row"] == sum(cfg["columns"].values())
    # What the budget holds at the table's real width, no row more.
    assert cfg["rows"] == cfg["nodes"] * (
        cfg["budget_bytes_per_node"] // cfg["bytes_per_row"]
    )
    entry = next(c for c in BENCHMARK["configs"] if c["name"] == cfg["name"])
    assert entry["source"] == cfg["source"]
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"])
    importlib.import_module(f"benchmark.builders.{cfg['builder']}")
    importlib.import_module(f"benchmark.drivers.{traffic['driver']}")
    for req in harness.requests_of(spec):
        ref = importlib.import_module(f"benchmark.reference.{req['reference']}")
        assert ref.LIMITS and "import px" in req["pxl"]
        assert set(req["reads"]) <= set(cfg["columns"])
    names = [m["name"] for m in harness.metrics_of(
        BENCHMARK, "end_to_end", cell["name"]
    )]
    assert "setup_s" in names and len(names) >= 2


@pytest.mark.parametrize("script", ["http_stats", "service_stats"])
def test_dash_recent_pxl_differs_by_start_time_only(script):
    from pixie_tpu.scripts import load_script

    with open(os.path.join(BENCH, "traffic", "dash_recent",
                           f"{script}.pxl")) as f:
        recent = f.read()
    bundled = load_script(f"px/{script}").pxl
    assert recent != bundled
    assert recent.replace(", start_time='-5m')", ")") == bundled
    assert recent.count("start_time") == 1


@pytest.mark.parametrize("config", ["http_pem_1chip", "http_pem_4chip"])
def test_table_is_the_programs_http_events_and_the_replays_values(config):
    """The configuration's table is ``http_events`` at the width and
    column order the program's own schema has, and the values are
    drawn as ``gen_http_events`` draws them: no column, vocabulary or
    distribution of the benchmark's own."""
    import inspect

    from benchmark.builders import served_http
    from pixie_tpu.ingest.replay import HTTP_EVENTS_RELATION, gen_http_events
    from pixie_tpu.types.dtypes import DataType

    cfg = json.load(open(os.path.join(BENCH, "configs", f"{config}.json")))
    assert [(c, DataType[t]) for c, t in served_http.COLUMNS] == list(
        HTTP_EVENTS_RELATION.items()
    )
    assert tuple(cfg["columns"]) == tuple(HTTP_EVENTS_RELATION.column_names)
    assert cfg["bytes_per_row"] == sum(cfg["columns"].values()) == 68
    assert cfg["rows"] == cfg["nodes"] * (cfg["budget_bytes_per_node"] // 68)
    values = cfg["values"]
    defaults = {k: p.default for k, p in
                inspect.signature(gen_http_events).parameters.items()}
    assert (values["services"], values["pods"], values["paths"]) == (
        defaults["n_services"], defaults["n_pods"], defaults["n_paths"]
    )
    replay = next(gen_http_events(1 << 16, chunk=1 << 16))
    mine = served_http.make_data(cfg, 7, 1 << 16)
    names = mine["names"]
    for col in ("req_method", "req_path", "service", "pod", "remote_addr"):
        assert set(replay[col]) <= set(names[col])
    assert set(replay["resp_status"]) == set(mine["resp_status"])
    for col, tol in (("latency_ns", 0.02), ("resp_body_size", 0.02)):
        q = [0.1, 0.5, 0.9]
        assert np.quantile(mine[col], q) == pytest.approx(
            np.quantile(replay[col], q), rel=tol + 0.03
        )
    assert np.mean(mine["resp_status"] >= 400) == pytest.approx(0.08, abs=0.01)
    assert np.mean(np.asarray(names["req_method"])[mine["req_method"]]
                   == "GET") == pytest.approx(0.5, abs=0.01)


def test_peaks_table_has_the_v5e_and_no_default():
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    v5e = peaks["TPU v5 lite"]
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["source"]
    assert "default" not in peaks and "cpu" not in peaks


def test_fold_bytes_from_shapes():
    from benchmark.fold_bytes import fold_bytes

    cfg = {"columns": {"time_": 8, "latency_ns": 8, "resp_status": 8,
                       "service": 4, "req_path": 4}}
    http = {"reads": ["resp_status", "latency_ns", "service", "req_path"]}
    assert fold_bytes(cfg, http, 1 << 24) == 24 << 24
    recent = {"reads": ["time_", "resp_status", "latency_ns", "service"]}
    assert fold_bytes(cfg, recent, 1398102) == 28 * 1398102


def test_data_is_the_seeds_and_ranges_hold_the_same_rows():
    from benchmark.builders.served_http import make_data

    cfg = json.load(open(os.path.join(BENCH, "configs",
                                      "http_pem_1chip.json")))
    big = 3_000_000_019  # the driver's seeds pass 2**31
    a, b, c = (make_data(cfg, s, 1 << 16) for s in (big, big, 7))
    assert a.pop("names") == b.pop("names") == c.pop("names")
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["latency_ns"], c["latency_ns"])
    assert np.array_equal(a["time_"], c["time_"])
    assert a["time_"][-1] == cfg["t_end_ns"]
    assert np.all(np.diff(a["time_"]) > 0)
    lo = cfg["t_end_ns"] - 300 * 10**9
    assert (a["time_"] >= lo).sum() == (1 << 16) // 12 + 1


# -- the trace reducer --------------------------------------------------------


def _events():
    ms = 1e6
    return {
        "host": [
            ["bench:traced_window", 0.0, 100 * ms],
            ["request:a", 0.0, 60 * ms],
            ["check", 60 * ms, 10 * ms],
            ["request:a", 80 * ms, 20 * ms],
        ],
        "devices": {
            0: {"modules": [["jit_update(7)", 10 * ms, 40 * ms],
                            ["jit_finalize(9)", 85 * ms, 10 * ms]],
                "ops": [["%fusion.1", 10 * ms, 20 * ms],
                        ["%fusion.22", 25 * ms, 25 * ms],
                        ["sort.3", 85 * ms, 10 * ms],
                        ["copy.1", 150 * ms, 10 * ms]]},
            1: {"modules": [], "ops": []},
        },
    }


def test_reduce_unions_overlaps_and_labels_gaps():
    from benchmark import xplane

    r = xplane.reduce(_events(), chips=1)
    assert r["window_s"] == pytest.approx(0.100)
    # [10, 50) and [85, 95): overlapping ops count once, the op past
    # the window not at all.
    assert r["busy_s"] == pytest.approx(0.050)
    # fusion.1 runs [10, 30), fusion.22 [25, 50): the shared 5 ms count
    # once, so that the operations' own times add up to busy_s.
    assert r["ops"] == pytest.approx({
        "jit_update/fusion": 0.040, "jit_finalize/sort": 0.010,
    })
    assert r["gaps"] == pytest.approx({
        "request:a": 0.010 + 0.010 + 0.005 + 0.005,
        "check": 0.010, "between_refreshes": 0.010,
    })
    assert sum(r["gaps"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"]
    )


def test_reduce_refuses_a_trace_of_other_chips():
    from benchmark import xplane

    with pytest.raises(ValueError, match="ran on 1 chips"):
        xplane.reduce(_events(), chips=4)
    ev = _events()
    ev["host"] = ev["host"][1:]
    with pytest.raises(ValueError, match="traced_window"):
        xplane.reduce(ev, chips=1)


def test_reduce_nests_a_loop_and_its_body():
    from benchmark import xplane

    ms = 1e6
    ev = {"host": [["bench:traced_window", 0.0, 50 * ms]],
          "devices": {0: {
              "modules": [["jit_update_all(1)", 0.0, 50 * ms]],
              "ops": [["%while.5 = (u32[]) while(...)", 0.0, 40 * ms],
                      ["%fusion.1 = u32[8] fusion(...), kind=kCustom, "
                       "calls=%f", 5 * ms, 10 * ms],
                      ["%fusion.2 = u32[8] fusion(...), kind=kCustom, "
                       "calls=%g", 20 * ms, 15 * ms],
                      ['%custom-call.7 = f32[8] custom-call(...), '
                       'custom_call_target="tpu_custom_call"',
                       42 * ms, 4 * ms]]}}}
    r = xplane.reduce(ev, chips=1)
    assert r["busy_s"] == pytest.approx(0.044)
    assert r["ops"] == pytest.approx({
        "jit_update_all/while": 0.015,
        "jit_update_all/fusion:kCustom": 0.025,
        "jit_update_all/custom-call:tpu_custom_call": 0.004,
    })


def test_recorded_trace_reduces_to_the_numbers_read_by_hand(tmp_path):
    """``testdata/``'s trace from the chip (see its README): the first
    0.9 s of a ``dash_recent`` window, two refreshes."""
    import gzip

    from benchmark import xplane

    packed = os.path.join(BENCH, "testdata",
                          "dash_recent_first_0.9s.xplane.pb.gz")
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(gzip.open(packed).read())
    events = xplane.load(str(path))
    assert list(events["devices"]) == [0]
    assert len(events["devices"][0]["ops"]) == 2831
    assert [h[0] for h in events["host"][:4]] == [
        "bench:traced_window", "request:http_stats",
        "request:service_stats", "check",
    ]
    r = xplane.reduce(events, chips=1)
    assert r["window_s"] == pytest.approx(0.9)
    assert r["busy_s"] == pytest.approx(0.639304804)
    assert sum(r["ops"].values()) == pytest.approx(r["busy_s"])
    top = xplane.top(r["ops"], 3)
    assert [name for name, _ in top] == [
        "jit_update/fusion:kCustom", "jit_update/sort",
        "jit_finalize/fusion:kCustom",
    ]
    assert top[0][1] == pytest.approx(0.559667806)
    assert r["gaps"]["request:http_stats"] == pytest.approx(0.134162905)
    assert r["gaps"]["request:service_stats"] == pytest.approx(0.126401251)
    assert sum(r["gaps"].values()) == pytest.approx(0.9 - 0.639304804)


# -- added as new files, found with no edit -----------------------------------


def _copy_with_one_more_of_each(tmp_path):
    """A copy of the benchmark with a configuration, a traffic mix and
    a per-layer metric added as NEW files (and their entries)."""
    root = tmp_path / "copy"
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    cfg = json.load(open(root / "benchmark/configs/http_pem_1chip.json"))
    cfg.update(name="http_pem_small", rows=cfg["rows"] // 2,
               reduced={"rows": "half, for the test"})
    json.dump(cfg, open(root / "benchmark/configs/http_pem_small.json", "w"))
    mix = root / "benchmark/traffic/dash_15m"
    shutil.copytree(root / "benchmark/traffic/dash_recent", mix)
    traffic = json.load(open(mix / "traffic.json"))
    traffic.update(name="dash_15m", range_s=900)
    json.dump(traffic, open(mix / "traffic.json", "w"))
    for pxl in ("http_stats.pxl", "service_stats.pxl"):
        text = (mix / pxl).read_text().replace("'-5m'", "'-15m'")
        (mix / pxl).write_text(text)
    (root / "benchmark/layer_metrics/refreshes.py").write_text(
        'def read(ctx):\n    return len(ctx["window"]["refreshes"])\n'
    )
    bench = json.loads(json.dumps(BENCHMARK))
    bench["configs"].append({
        "name": "http_pem_small", "source": cfg["source"] + " (half)",
        "file": "benchmark/configs/http_pem_small.json",
        "reduced": ["rows"], "why": "test",
    })
    bench["workloads"].append({
        "name": "http_pem_small.dash_15m", "config": "http_pem_small",
        "traffic": "dash_15m", "chips": 1, "why": "test",
    })
    bench["per_layer"].append({
        "name": "refreshes", "unit": "refreshes", "better": "higher",
        "source": "program_counter", "layer": "client",
        "moves": "refresh_p50_ms", "workloads": ["http_pem_small.dash_15m"],
    })
    for m in bench["end_to_end"]:
        if m["name"] == "refresh_p80_ms":
            m["workloads"].append("http_pem_small.dash_15m")
    json.dump(bench, open(root / "BENCHMARK.json", "w"))
    return root


def _run(root, *args, pythonpath=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    if pythonpath:
        env["PYTHONPATH"] = pythonpath
    return subprocess.run(
        [sys.executable, "benchmark/run.py", *args], cwd=root, env=env,
        capture_output=True, text=True, timeout=600,
    )


def test_new_cell_traffic_and_metric_are_found_with_no_edit(tmp_path):
    root = _copy_with_one_more_of_each(tmp_path)
    done = _run(root, "--workload", "http_pem_small.dash_15m", "--seed", "5",
                "--seconds", "2", "--trace", "1", "--rehearse-rows", "32768",
                pythonpath=ROOT)
    assert done.returncode == 1, done.stderr[-2000:]  # a rehearsal
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True
    assert last["numbers"]["http_stats.n_differ"] == [0, 0]
    assert last["metrics"]["refreshes"]["value"] >= 1
    assert '"rows_in_range": 8193' in done.stdout  # 32768 * 900 / 3600 + 1
    # The shipped files of the copy are byte for byte the repo's.
    for dirpath, _dirs, files in os.walk(BENCH):
        if "__pycache__" in dirpath:
            continue
        for f in files:
            src = os.path.join(dirpath, f)
            dst = os.path.join(root, os.path.relpath(src, ROOT))
            assert open(src, "rb").read() == open(dst, "rb").read()


def test_without_the_program_or_a_chip_there_is_no_result(tmp_path):
    root = _copy_with_one_more_of_each(tmp_path)
    alone = _run(root, "--workload", "http_pem_1chip.dash_full", "--seed",
                 "5", "--seconds", "1", "--trace", "0")
    assert alone.returncode != 0 and '"correct"' not in alone.stdout
    no_chip = _run(ROOT, "--workload", "http_pem_1chip.dash_full", "--seed",
                   "5", "--seconds", "1", "--trace", "0")
    assert no_chip.returncode == 2 and '"correct"' not in no_chip.stdout
    assert "TPU" in no_chip.stderr
