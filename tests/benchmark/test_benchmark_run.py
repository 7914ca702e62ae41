"""The runner, rehearsed on the CPU at tiny sizes (never a device
number from here), and the two proofs "How correct is decided" asks
for: the lower-precision control fails, and a run whose timed path is
broken underneath comes out not correct."""

import json
import os
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCHMARK = json.load(f)
CELLS = [w["name"] for w in BENCHMARK["workloads"]]


#: The four-chip configuration's files are kept though its cell is not
#: shipped yet (PERF.md, Open questions, row 1): the entries a later PR
#: adds to ``BENCHMARK.json`` for it, so that it stays rehearsed.
FOUR_CHIP = {
    "config": {"name": "http_pem_4chip",
               "file": "benchmark/configs/http_pem_4chip.json"},
    "workload": {"name": "http_pem_4chip.dash_full",
                 "config": "http_pem_4chip", "traffic": "dash_full",
                 "chips": 4},
    "per_layer": {"name": "collective_ms", "unit": "ms", "better": "lower",
                  "source": "device_trace", "layer": "mesh",
                  "moves": "refresh_p50_ms",
                  "workloads": ["http_pem_4chip.dash_full"]},
}


@pytest.fixture
def with_four_chip_cell(tmp_path, monkeypatch):
    """``BENCHMARK.json`` with the four-chip cell's entries added, in a
    root of its own; the benchmark's files stay where they are."""
    from benchmark import harness

    bench = json.loads(json.dumps(BENCHMARK))
    bench["configs"].append(FOUR_CHIP["config"])
    bench["workloads"].append(FOUR_CHIP["workload"])
    bench["per_layer"].append(FOUR_CHIP["per_layer"])
    for m in bench["per_layer"]:
        if m["name"] == "served_rows_per_s":
            m["workloads"].append(FOUR_CHIP["workload"]["name"])
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    os.symlink(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark")
    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    return bench


def _rehearse(cell, trace, **kw):
    from benchmark import harness

    return harness.run_cell(cell, 3_000_000_019, 1.5, trace, time.time(),
                            rehearse_rows=1 << 15, **kw)


def _check_rehearsal(result, bench, cell, trace):
    assert result["rehearsal"] is True
    for name, (value, limit) in result["numbers"].items():
        # A t-digest over the hundred or so rows a service has here is
        # coarser than at the cells' sizes, which the limits are for;
        # the p99 of so few log-normal rows is the largest one or two.
        if name.endswith("p99_relerr"):
            assert np.isfinite(value), name
        else:
            assert value <= (0.35 if name.endswith("p50_relerr")
                             else limit), name
    assert result["failed"] == 0 and result["attempted"] >= 1
    kind = "per_layer" if trace else "end_to_end"
    listed = {m["name"] for m in bench[kind]
              if cell in m.get("workloads", [cell])}
    got = set(result["metrics"])
    device_trace = {m["name"] for m in bench["per_layer"]
                    if m["source"] == "device_trace"}
    assert got <= listed
    # What the host's clock, the spans and the counters give is there;
    # nothing that only a device trace can give is.
    assert listed - got == (device_trace & listed if trace else set())
    assert "busy_s" not in result["device"]
    assert "breakdown" not in result
    if trace:
        assert result["metrics"]["window_compiles"]["value"] == 0


@pytest.mark.parametrize("trace", [False, True], ids=["e2e", "layers"])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_walks_the_whole_run(cell, trace):
    _check_rehearsal(_rehearse(cell, trace), BENCHMARK, cell, trace)


@pytest.mark.parametrize("trace", [False, True], ids=["e2e", "layers"])
def test_rehearsal_walks_the_four_chip_configuration(with_four_chip_cell,
                                                     trace):
    cell = FOUR_CHIP["workload"]["name"]
    _check_rehearsal(_rehearse(cell, trace), with_four_chip_cell, cell, trace)


def test_a_machine_without_the_chips_gives_no_result():
    from benchmark import harness

    with pytest.raises(harness.NoChip):
        harness.run_cell(CELLS[0], 1, 1, False, time.time())


def _alter_a_count(stack):
    execute = stack.execute

    def altered(pxl, timeout_s, now_ns):
        res = execute(pxl, timeout_s, now_ns)
        if "n" in res["rows"]:
            res["rows"]["n"][3] += 1
        return res

    stack.execute = altered


def _drop_a_window(stack):
    """The PEM answers from a table that lacks its newest rows: what a
    fold that leaves out a part of the batch would produce."""
    execute = stack.execute
    now = stack.cfg["t_end_ns"]
    step = stack.cfg["span_s"] * 10**9 // stack.rows

    def altered(pxl, timeout_s, now_ns):
        pxl = pxl.replace("table='http_events'",
                          f"table='http_events', end_time={now - 50 * step}")
        return execute(pxl, timeout_s, now_ns)

    stack.execute = altered


@pytest.mark.parametrize("break_path", [_alter_a_count, _drop_a_window],
                         ids=lambda f: f.__name__.strip("_"))
@pytest.mark.parametrize("cell", CELLS[:2])
def test_a_broken_timed_path_is_not_correct(cell, break_path):
    result = _rehearse(cell, False, break_path=break_path)
    assert result["correct"] is False
    assert result["failed"] == 0  # it answered; the answers were wrong


@pytest.mark.parametrize("traffic", ["dash_full", "dash_recent"])
@pytest.mark.parametrize("seed", [11, 3_000_000_019, 77])
def test_lower_precision_control_is_not_correct(traffic, seed):
    """The reference with its sums in 32-bit floats (pairwise, the most
    accurate plain f32 sum), put in the program's place, at a size a
    test run holds (4 Mi rows; the readings at the cells' own size are
    in PERF.md): the share of groups whose ``lat_mean`` is not the f32
    nearest the exact mean passes its limit, by the margin the limit
    was set with. Even rounding only the finished sum to f32 does."""
    from benchmark import harness
    from benchmark.builders.served_http import make_data
    from benchmark.reference import px_http_stats as ref

    cfg = json.load(open(os.path.join(ROOT, "benchmark", "configs",
                                      "http_pem_1chip.json")))
    spec = json.load(open(os.path.join(ROOT, "benchmark", "traffic", traffic,
                                       "traffic.json")))
    data = make_data(cfg, seed, 1 << 22)
    lo, _now = harness.range_lo_ns(cfg, spec)
    exact = ref.answer(data, lo)
    control = ref.numbers(ref.answer(data, lo, sums="f32"), exact)
    share = "http_stats.lat_mean_misrounded_share"
    assert control[share] > 3 * ref.LIMITS[share]
    assert control["http_stats.n_differ"] == 0
    total = exact["lat_mean"] * exact["n"]
    mild = dict(exact, lat_mean=(total.astype(np.float32) / exact["n"]
                                 ).astype(np.float32).astype(np.float64))
    assert ref.numbers(mild, exact)[share] > 3 * ref.LIMITS[share]
    # The f32 result plane's one rounding, all the program's own answer
    # may differ by, is inside both limits.
    rounded = dict(exact, lat_mean=exact["lat_mean"].astype(np.float32)
                   .astype(np.float64))
    sound = ref.numbers(rounded, exact)
    assert sound[share] == 0
    gap = "http_stats.lat_mean_relerr"
    assert sound[gap] < ref.LIMITS[gap] / 2.5
