"""Configuration ``http_full_1chip`` and its cell: the file's arithmetic
and source against ``BENCHMARK.json``, the data its builder makes from
the seed (skew, path ownership, ranges), the per-layer readers this
configuration brought on a rehearsed window, and a rehearsal of the
cell with the group capacity pinned too small and the retry broken.
On the CPU: never a device number from here."""

import importlib
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
with open(os.path.join(ROOT, "benchmark", "configs",
                       "http_full_1chip.json")) as f:
    CFG = json.load(f)
CELL = "http_full_1chip.dash_recent"
NEW_METRICS = {"group_slots": "fragment programs", "group_refolds": "engine",
               "staged_mb": "table store"}


def _make(seed, rows):
    from benchmark.builders.served_http_skew import make_data

    return make_data(CFG, seed, rows)


def test_the_file_agrees_with_benchmark_json():
    entry = next(c for c in BENCHMARK["configs"] if c["name"] == CFG["name"])
    assert entry["source"] == CFG["source"] and len(CFG["source"]) <= 200
    assert entry["file"] == "benchmark/configs/http_full_1chip.json"
    # One cut of scale: the chip's share of 125,000,000 rows halved once,
    # with the times of one run against the 360 s it is given.
    assert sorted(entry["reduced"]) == sorted(CFG["reduced"]) == ["rows"]
    assert "360 s" in CFG["reduced"]["rows"]
    cell = next(w for w in BENCHMARK["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "http_full_1chip", "dash_recent", 1
    )
    assert len(cell["why"]) <= 200
    assert CFG["bytes_per_row"] == sum(CFG["columns"].values()) == 68
    assert CFG["rows"] == CFG["nodes"] * (CFG["budget_bytes_per_node"] // 68)
    assert CFG["rows"] == 125_000_000 // 2 and CFG["window_rows"] == 1 << 21
    # 29 full windows and one padded; '-5m' of the hour.
    assert divmod(CFG["rows"], CFG["window_rows"])[0] == 29
    assert CFG["rows"] * 300 // CFG["span_s"] == 5_208_333
    values = CFG["values"]
    assert values["skew"] == {"distribution": "zipfian", "constant": 0.99}
    assert values["paths"] == 65_536 == (
        values["services"] * values["paths_per_service"]
    )
    # The one deployment setting: the budget that holds the table. No
    # flag of the group-by or of the device cache.
    assert set(CFG["flags"]) == {"table_store_data_limit_mb"}
    held = CFG["flags"]["table_store_data_limit_mb"] * (1 << 20) * 40 // 100
    assert held >= CFG["rows"] * 68 > (
        (CFG["flags"]["table_store_data_limit_mb"] - 1) * (1 << 20) * 40 // 100
    )
    one_chip = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "http_pem_1chip.json"
    )))
    assert CFG["guarantees"] == one_chip["guarantees"]
    assert CFG["max_output_rows"] == 131_072 > values["paths"]
    # What the cell cannot run without, named where the deployment is.
    from benchmark.builders.served_http_skew import CAPABILITIES

    assert set(CFG["requires"]) == {"joint_key_sizing"} <= set(CAPABILITIES)


@pytest.mark.parametrize("metric", sorted(NEW_METRICS))
def test_the_new_metrics_are_filed_under_their_layers(metric):
    entry = next(m for m in BENCHMARK["per_layer"] if m["name"] == metric)
    assert entry["layer"] == NEW_METRICS[metric]
    assert entry["moves"] == "refresh_p50_ms" and "workloads" not in entry


def test_data_is_the_seeds():
    big = 3_000_000_019  # the driver's seeds pass 2**31
    a, b, c = (_make(s, 1 << 16) for s in (big, big, 7))
    assert a.pop("names") == b.pop("names") == c.pop("names")
    for k in a:
        for pa, pb in zip(*(x[k] if isinstance(x[k], tuple) else (x[k],)
                            for x in (a, b))):
            assert np.array_equal(pa, pb), k
    assert not np.array_equal(a["latency_ns"], c["latency_ns"])
    assert not np.array_equal(a["req_path"], c["req_path"])
    assert np.array_equal(a["time_"], c["time_"])
    assert a["time_"][-1] == CFG["t_end_ns"]
    assert np.all(np.diff(a["time_"]) > 0)
    lo = CFG["t_end_ns"] - 300 * 10**9
    assert (a["time_"] >= lo).sum() == (1 << 16) // 12 + 1


def test_a_program_that_cannot_size_the_joint_key_is_refused_at_once(
        monkeypatch):
    """A program without the capability the configuration's ``requires``
    names, under these benchmark files: it exits with the file's reason
    and another code than 0 before a row is made (at the planner's bound
    its run would not end inside the limit a run is given)."""
    from pixie_tpu.exec.engine import Engine

    monkeypatch.delattr(Engine, "probe_group_keys")
    t = time.perf_counter()
    with pytest.raises(SystemExit, match="joint key") as e:
        _make(7, CFG["rows"])
    assert e.value.code not in (0, None)
    assert CFG["requires"]["joint_key_sizing"] in str(e.value.code)
    assert time.perf_counter() - t < 1.0


def test_a_path_belongs_to_the_service_of_its_row_and_the_domain_is_not_dense():
    from pixie_tpu.config import get_flag

    d = _make(11, 1 << 16)
    per = CFG["values"]["paths_per_service"]
    assert np.array_equal(d["req_path"] // per, d["service"])
    assert np.array_equal(d["pod"] // CFG["values"]["pods"], d["service"])
    assert np.array_equal(d["remote_addr"], d["service"])
    names = d["names"]
    assert len(names["req_path"]) == 65_536 == len(set(names["req_path"]))
    assert len(names["service"]) == 32 and len(names["pod"]) == 32 * 128
    assert names["req_path"][5 * per + 7].startswith("/api/v1/svc-5/")
    # Codes 0..len-1 and NULL_ID: what ``_static_key_domains`` multiplies.
    assert (len(names["service"]) + 1) * (len(names["req_path"]) + 1) > (
        get_flag("dense_domain_limit")
    )


def test_rank_frequency_follows_the_zipfian_law():
    rows = 1 << 20
    d = _make(3_000_000_019, rows)

    def law(n):
        p = 1.0 / np.arange(1, n + 1) ** 0.99
        return p / p.sum()

    # Services: every rank, within five binomial deviations.
    got = np.sort(np.bincount(d["service"], minlength=32))[::-1] / rows
    want = law(32)
    assert np.all(np.abs(got - want) < 5 * np.sqrt(want / rows) + 1e-4)
    # Paths, inside the hottest service: the first ranks.
    hot = np.argmax(np.bincount(d["service"], minlength=32))
    mine = d["req_path"][d["service"] == hot] - hot * 2048
    got = np.sort(np.bincount(mine, minlength=2048))[::-1] / len(mine)
    want = law(2048)
    assert np.all(np.abs(got[:32] - want[:32])
                  < 5 * np.sqrt(want[:32] / len(mine)) + 2e-4)
    assert got[0] > 8 * got[63]  # 1/1 against 1/64^0.99, loosely
    # Hot keys are not neighbouring codes (the permutation).
    top = np.argsort(np.bincount(mine, minlength=2048))[::-1][:8]
    assert not np.array_equal(np.sort(top), np.arange(top.min(),
                                                      top.min() + 8))
    # Methods, statuses and latency as ``served_http`` draws them.
    assert np.mean(d["resp_status"] >= 400) == pytest.approx(0.08, abs=0.005)
    assert np.median(d["latency_ns"]) == pytest.approx(np.exp(15.0), rel=0.02)


# -- the readers this configuration brought, on a rehearsed window ------------


def _read(name, ctx):
    return importlib.import_module(f"benchmark.layer_metrics.{name}").read(ctx)


def _settled_slots(rows):
    """The capacity a keyed fold of the cell at ``rows`` rows settles on:
    the next power of two over its live groups with the program's
    head-room (``exec/stream.py``), from the plain reference's count."""
    from benchmark.reference import px_http_stats
    from pixie_tpu.exec.stream import _probed_capacity

    lo = CFG["t_end_ns"] - 300 * 10**9
    live = len(px_http_stats.answer(_make(3_000_000_019, rows), lo)["key"])
    return _probed_capacity(live, 1 << 22)


def _window(seconds, before=None):
    """``ctx`` of a rehearsed window of the cell, as ``harness.run_cell``
    builds it (the parts the span readers use). ``before(stack)`` runs
    after the warm-up, before the window."""
    from benchmark import harness
    from pixie_tpu.config import override_flag

    spec = harness.load_cell(CELL)
    cfg, traffic = spec["config"], spec["traffic"]
    builder = harness.module("builders", cfg["builder"])
    driver = harness.module("drivers", traffic["driver"])
    with override_flag("cpu_fold_threads", 1):
        stack = builder.build(cfg, 1 << 13)
        try:
            stack.ingest(builder.make_data(cfg, 3_000_000_019, 1 << 15))
            requests = harness.requests_of(spec)
            log = harness.SpanLog(stack.tracers)
            _lo, now_ns = harness.range_lo_ns(cfg, traffic)
            for _ in range(3):
                driver.refresh(stack, requests, now_ns, 120, harness.mark)
            log.cut()
            if before is not None:
                before(stack)
            window = driver.run(stack, traffic, requests, seconds, now_ns,
                                harness.mark)
            spans = log.cut()
        finally:
            stack.close()
    assert window["failed"] == 0 and window["refreshes"]
    return {"window": window, "spans": spans, "trace": None}


@pytest.fixture(scope="module")
def window():
    return _window(1.0)


def test_group_slots_is_the_keyed_folds_capacity(window):
    # 2,731 rows in range and fewer groups: a power of two over them,
    # far under what the columns' NDVs multiply to.
    assert _read("group_slots", window) == _settled_slots(1 << 15) == 2048
    groups = {
        s.attributes["group"] for t in window["spans"]["pem"]
        for s in t.spans if s.name == "device.dispatch"
        and "group" in s.attributes
    }
    assert groups == {"hashed", "dense"}  # the CPU's 'auto'; 'sorted' on a TPU


def test_no_refold_and_nothing_staged_in_the_steady_state(window):
    assert _read("group_refolds", window) == 0
    assert _read("staged_mb", window) == 0


def _forget_the_capacity(stack):
    """The PEM forgets what it learned: the window's first request
    starts from the plan's capacity again, shrunk by hand to force a
    climb."""
    eng = stack.pem.engine
    keys = [k for k in eng._join_capacity_cache if k[0] == "agg"]
    assert len(keys) == 1  # http_stats'; service_stats' fold is dense
    eng._join_capacity_cache[keys[0]] = 512


def test_group_refolds_counts_the_rungs_of_a_climb():
    ctx = _window(0.05, before=_forget_the_capacity)
    ctx["window"]["refreshes"] = ctx["window"]["refreshes"][:1]
    # 512 -> 1,024 -> 2,048: two re-folds, in the one refresh that paid
    # for them, at the capacity it settled on.
    assert _read("group_refolds", ctx) == 2
    assert _read("group_slots", ctx) == 2048


def _evict_the_table(stack):
    """The device cache loses every window of the table, as LRU under a
    whole-table scan leaves the ones a request needs next."""
    stack.pem.engine.tables["http_events"]._device_cache.clear()


def test_staged_mb_sees_a_window_the_device_cache_missed():
    ctx = _window(0.05, before=_evict_the_table)
    ctx["window"]["refreshes"] = ctx["window"]["refreshes"][:1]
    # One of the four windows is in range: http_stats' scan stages it
    # again (its padded planes, every column), service_stats finds it.
    restaged = [t.usage.bytes_restaged for t in ctx["spans"]["pem"]]
    assert all(t.usage.bytes_staged == 0 for t in ctx["spans"]["pem"])
    assert min(restaged) == 0 and max(restaged) > (1 << 13) * 60
    assert _read("staged_mb", ctx) == max(restaged) / 1e6


def test_readers_read_nothing_on_a_program_without_the_spans(window):
    """The parent's spans carry no ``slots`` and its usage record no
    ``rebuckets``: the readers then report nothing and do not raise."""
    import copy

    class Usage:  # as QueryResourceUsage was
        bytes_staged = 0

    stripped = {"window": window["window"], "trace": None, "spans": {}}
    for tracer, traces in window["spans"].items():
        out = []
        for t in traces:
            t = copy.copy(t)
            t.usage = Usage()
            t.spans = [copy.copy(s) for s in t.spans if s.name != "rebucket"]
            for s in t.spans:
                s.attributes = {k: v for k, v in s.attributes.items()
                                if k not in ("group", "slots")}
            out.append(t)
        stripped["spans"][tracer] = out
    assert _read("group_slots", stripped) is None
    assert _read("group_refolds", stripped) is None
    assert _read("staged_mb", stripped) == 0


# -- a rehearsal of the cell, sound and broken --------------------------------


def _rehearse(**kw):
    from benchmark import harness

    return harness.run_cell(CELL, 3_000_000_019, 1.5, True, time.time(),
                            rehearse_rows=1 << 16, **kw)


def _http_stats_holds(result):
    return all(value <= limit for name, (value, limit)
               in result["numbers"].items() if name.startswith("http_stats."))


def test_a_rehearsal_of_the_cell_is_sound():
    result = _rehearse()
    assert result["rehearsal"] is True and result["failed"] == 0
    # Every number the configuration guarantees exactly, and lat_mean;
    # quantiles of a few hundred rows a service are outside what the
    # limits were set for (as in test_benchmark_run's rehearsals).
    assert _http_stats_holds(result)
    for name in ("keys_differ", "throughput_differ"):
        assert result["numbers"][f"service_stats.{name}"][0] == 0
    metrics = result["metrics"]
    assert metrics["group_slots"]["value"] == _settled_slots(1 << 16)
    assert metrics["group_refolds"]["value"] == 0
    assert metrics["staged_mb"]["value"] == 0
    assert metrics["window_compiles"]["value"] == 0


def _pin_the_capacity_and_break_the_retry(stack):
    """The PEM folds the keyed aggregate into 64 slots whatever the plan
    says, and its overflow flag never reaches the ladder: what a lost
    rebucket would ship."""
    import jax.numpy as jnp

    from pixie_tpu.exec.fragment import compile_fragment_cached
    from pixie_tpu.exec.stream import _with_agg_groups

    eng = stack.pem.engine
    fold = eng._fold_agg_state

    def broken(stream, frag, stats=None):
        if frag.group == "dense":
            return fold(stream, frag, stats)
        small = compile_fragment_cached(
            _with_agg_groups(stream.chain, 64), stream.relation,
            stream.dicts, eng.registry,
        )
        state = fold(stream, small, stats)
        return {**state, "overflow": jnp.zeros((), dtype=jnp.bool_)}

    eng._fold_agg_state = broken


def test_a_pinned_capacity_with_a_broken_retry_is_not_correct():
    result = _rehearse(break_path=_pin_the_capacity_and_break_the_retry)
    assert result["correct"] is False and result["failed"] == 0
    assert result["numbers"]["http_stats.keys_differ"][0] > 0
    assert not _http_stats_holds(result)
