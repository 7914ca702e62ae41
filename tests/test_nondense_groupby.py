"""The non-dense group-by at a size the CPU holds: configuration
``http_full_1chip``'s data (Zipf keys, 65,536 request paths owned by 32
services), whose ``service`` x ``req_path`` domain (33 x 65,537 packed
codes) is over ``dense_domain_limit`` at any row count, so
``px/http_stats`` takes ``window_group_ids`` + ``regroup_pair`` +
``scatter_carry`` with a keyed state. Both shipped scripts, through a
bare ``Engine`` and through broker, PEMs and Kelvin, against the
benchmark's plain numpy reference: exact keys, counts and ``lat_max``,
``lat_mean`` one f32 rounding from exact; over both platforms' routes
(the TPU's sorts, the CPU's hash table), over a starting capacity below and above the live groups
(the ladder is climbed once and remembered), and over one and three
PEM partial states merged by the Kelvin."""

import json
import os
import time

import numpy as np
import pytest

from benchmark.builders import served_http_skew
from conftest import routes_of
from benchmark.reference import px_http_stats, px_service_stats
from pixie_tpu.config import get_flag
from pixie_tpu.exec.engine import Engine
from pixie_tpu.scripts import load_script

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "configs",
                       "http_full_1chip.json")) as f:
    CFG = json.load(f)
ROWS = 1 << 16
WINDOW = 1 << 14
EVERY_GROUP = 1 << 17  # max_output_rows: the default cuts at 10,000
SCRIPTS = {
    "http_stats": (load_script("px/http_stats").pxl, px_http_stats),
    "service_stats": (load_script("px/service_stats").pxl, px_service_stats),
}


@pytest.fixture(scope="module")
def data():
    return served_http_skew.make_data(CFG, 3_000_000_019, ROWS)


@pytest.fixture(scope="module")
def answers(data):
    return {k: ref.answer(data, None) for k, (_pxl, ref) in SCRIPTS.items()}


def _batches(data, lo=0, hi=ROWS):
    return served_http_skew.batches(data, WINDOW, lo, hi)


def _engine(data) -> Engine:
    eng = Engine(window_rows=WINDOW)
    for hb in _batches(data):
        eng.append_data("http_events", hb)
    return eng


def _check(script, table, want, served):
    """The decoded rows against the reference: what the configuration
    guarantees exactly, exactly. Quantiles over the few hundred rows a
    small service has here are coarser than the cell's limits are for
    (as in ``tests/benchmark``'s rehearsals)."""
    ref = SCRIPTS[script][1]
    got = ref.rows(table)
    if script == "http_stats" and not served:
        # A bare Engine hands back the f64 quotient; the served path
        # rounds it once into an f32 result plane.
        got["lat_mean"] = got["lat_mean"].astype(np.float32).astype(
            np.float64
        )
    numbers = ref.numbers(got, want)
    for name, value in numbers.items():
        if name.endswith("p99_relerr"):
            assert np.isfinite(value), name
        elif name.endswith("p50_relerr"):
            assert value <= 0.35, name
        else:
            assert value <= ref.LIMITS[name], (name, value)
    return numbers


def _fold_spans(trace):
    return [s for s in trace.spans
            if s.name == "device.dispatch" and "group" in s.attributes]


def _rebuckets(trace):
    return [s for s in trace.spans if s.name == "rebucket"]


def test_the_key_domain_is_over_the_dense_limit(data):
    doms = [len(data["names"][c]) + 1 for c in ("service", "req_path")]
    assert doms[0] * doms[1] > get_flag("dense_domain_limit")


@pytest.mark.parametrize("platform", ["tpu", "cpu"])
@pytest.mark.parametrize("script", list(SCRIPTS))
def test_engine_equals_the_reference(data, answers, script, platform):
    with routes_of(platform):
        eng = _engine(data)
        out = eng.execute_query(SCRIPTS[script][0],
                                max_output_rows=EVERY_GROUP)
    _check(script, out["output"].to_pydict(), answers[script], served=False)
    groups = {s.attributes["group"] for s in _fold_spans(eng.tracer.last())}
    if script == "http_stats":
        assert groups == {"sorted" if platform == "tpu" else "hashed"}
        assert len(answers[script]["key"]) > 10_000
    else:
        assert groups <= {"dense"}  # (the CPU's native fold has no spans)


@pytest.mark.parametrize("platform", ["tpu", "cpu"])
@pytest.mark.parametrize("start", [1024, 1 << 18], ids=["below", "above"])
def test_the_ladder_is_climbed_once_and_remembered(data, answers, start,
                                                   platform):
    """From a capacity below the live groups the fold doubles until it
    fits (a ``rebucket`` span a rung, counted in ``usage.rebuckets``);
    from one far above, it folds there once. Either way the answer is
    the reference's, the second run starts at what the first settled on
    or observed, and climbs nothing."""
    from pixie_tpu.planner import CompilerState, compile_pxl

    live = len(answers["http_stats"]["key"])
    with routes_of(platform):
        eng = _engine(data)

        def run():
            # Sketch-less, so that the plan's capacity is ``start``.
            state = CompilerState(
                schemas={n: t.relation for n, t in eng.tables.items()},
                registry=eng.registry, now_ns=CFG["t_end_ns"],
                max_output_rows=EVERY_GROUP, max_groups=start,
            )
            out = eng.execute_plan(
                compile_pxl(SCRIPTS["http_stats"][0], state).plan
            )
            return out, eng.tracer.last()

        first, t1 = run()
        second, t2 = run()
    for out in (first, second):
        _check("http_stats", out["output"].to_pydict(),
               answers["http_stats"], served=False)
    rungs = [(s.attributes["from"], s.attributes["to"], s.attributes["where"])
             for s in _rebuckets(t1)]
    settled = max(s.attributes["slots"] for s in _fold_spans(t2))
    assert settled >= live and settled < 4 * live
    if start < live:
        assert rungs[0][0] == start and rungs[-1][1] == settled
        assert all(b == 2 * a and w == "pem" for a, b, w in rungs)
    else:
        assert rungs == [] and settled < start
    assert t1.usage.rebuckets == len(rungs)
    assert _rebuckets(t2) == [] and t2.usage.rebuckets == 0
    assert {s.attributes["slots"] for s in _fold_spans(t2)} == {settled}


def test_the_first_request_of_a_chain_probes_the_joint_key_once(data, answers):
    """The planner bounds ``service`` x ``req_path`` by the product of
    the columns' NDVs; no request folds there. The first reads a sketch
    of the joint key (one ``group_probe`` span: the plan's ``slots``,
    the ``estimate``) and folds at the capacity that gives; the second
    finds it remembered and probes nothing."""
    from pixie_tpu.exec.stream import _probed_capacity

    live = len(answers["http_stats"]["key"])
    eng = _engine(data)
    traces = []
    for _ in range(2):
        out = eng.execute_query(SCRIPTS["http_stats"][0],
                                max_output_rows=EVERY_GROUP)
        _check("http_stats", out["output"].to_pydict(), answers["http_stats"],
               served=False)
        traces.append(eng.tracer.last())
    (probe,) = [s for s in traces[0].spans if s.name == "group_probe"]
    planned, estimate = probe.attributes["slots"], probe.attributes["estimate"]
    assert planned >= 8 * live  # the product bound, not the groups
    assert abs(estimate - live) < 0.05 * live
    assert [s for s in traces[1].spans if s.name == "group_probe"] == []
    for t in traces:
        assert {s.attributes["slots"] for s in _fold_spans(t)} == {
            _probed_capacity(estimate, planned)
        }
        assert _rebuckets(t) == []


FILTERED = """import px
df = px.DataFrame(table='http_events')
df = df.groupby(['service', 'req_path']).agg(n=('latency_ns', px.count))
df = df[df.n > 200]
px.display(df)
"""


@pytest.mark.parametrize("probe", [False, True], ids=["no_probe", "probe"])
def test_a_filter_over_the_groups_is_not_their_count(data, probe,
                                                     monkeypatch):
    """The rows a keyed aggregate returns have been through the script's
    filters, so they say nothing of its live groups: a fold that fits is
    remembered by nobody, and every later run folds where the first did
    and climbs nothing. With the probe off (as ``DistributedEngine``
    has it) that is the plan's capacity; with it on, the sketch's."""
    monkeypatch.setattr(Engine, "probe_group_keys", probe)
    keys = data["service"].astype(np.int64) << 32 | data["req_path"]
    _, counts = np.unique(keys, return_counts=True)
    eng = _engine(data)
    slots = []
    for _ in range(3):
        out = eng.execute_query(FILTERED, max_output_rows=EVERY_GROUP)
        got = np.sort(np.asarray(out["output"].to_pydict()["n"]))
        assert np.array_equal(got, np.sort(counts[counts > 200]))
        trace = eng.tracer.last()
        assert _rebuckets(trace) == [] and trace.usage.rebuckets == 0
        slots.append({s.attributes["slots"] for s in _fold_spans(trace)})
    assert 0 < len(got) < len(counts) // 8
    assert len(slots[0]) == 1 and slots[0] == slots[1] == slots[2]
    assert min(slots[0]) >= len(counts)


@pytest.fixture(params=[1, 3], ids=["one_pem", "three_pems"])
def cluster(request, data):
    """Broker, Kelvin and 1 or 3 PEMs, each PEM holding a contiguous
    share of the rows under the same dictionaries."""
    from pixie_tpu.services import (
        AgentTracker, KelvinAgent, MessageBus, PEMAgent, QueryBroker,
    )

    k = request.param
    bus = MessageBus()
    tracker = AgentTracker(bus, expiry_s=60.0, check_interval_s=60.0)
    pems = [
        PEMAgent(bus, f"pem-{i}", heartbeat_interval_s=0.05,
                 engine=Engine(window_rows=WINDOW)).start()
        for i in range(k)
    ]
    kelvin = KelvinAgent(bus, "kelvin-0", heartbeat_interval_s=0.05).start()
    cuts = [ROWS * i // k // WINDOW * WINDOW for i in range(k)] + [ROWS]
    for pem, lo, hi in zip(pems, cuts, cuts[1:]):
        for hb in _batches(data, lo, hi):
            pem.append_data("http_events", hb)
        pem._register()
    deadline = time.time() + 10
    # Every PEM's re-registration, not the first one's: until it lands a
    # PEM is registered with no table and is planned around.
    while len(tracker.distributed_state().pems_with_table("http_events")) < k:
        assert time.time() < deadline, "a PEM's schema did not reach the tracker"
        time.sleep(0.01)
    yield QueryBroker(bus, tracker), pems, kelvin
    for a in pems + [kelvin]:
        a.stop()
    tracker.close()
    bus.close()


@pytest.mark.parametrize("platform", ["tpu", "cpu"])
@pytest.mark.parametrize("script", list(SCRIPTS))
def test_the_served_path_equals_the_reference(cluster, answers, script,
                                              platform):
    """The PEMs' partial states, keyed, merged by the Kelvin's regroup:
    the parts add up to the whole, twice (the second request starts
    from what the first remembered)."""
    broker, pems, kelvin = cluster
    with routes_of(platform):
        for _ in range(2):
            res = broker.execute_script(
                SCRIPTS[script][0], timeout_s=120,
                max_output_rows=EVERY_GROUP,
            )
            assert not res.get("partial")
            _check(script, res["tables"]["output"].to_pydict(),
                   answers[script], served=True)
    if script == "http_stats":
        want = "sorted" if platform == "tpu" else "hashed"
        for pem in pems:
            frag = next(t for t in pem.engine.tracer.recent()
                        if t["kind"] == "fragment")
            assert {f["group"] for f in frag["fragments"]
                    if "group" in f} == {want}
        # The second request folded nothing twice, anywhere.
        for agent in pems + [kelvin]:
            assert agent.engine.tracer.last().usage.rebuckets == 0
