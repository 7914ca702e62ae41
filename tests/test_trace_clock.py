"""The one clock, the served path's stages, the device wait and the
background ring (exec/trace.py — ISSUE 25): every span of a brokered
query takes both ends from ``time.perf_counter_ns()`` where the work
runs and nests inside its parent; ``device_ms`` is the device interval;
a profiler session holds the spans as annotations; background work is
recorded beside the traces and reaches no listener."""

from __future__ import annotations

import gc
import glob
import json
import os
import re
import time

import numpy as np
import pytest

from pixie_tpu import config
from pixie_tpu.exec import trace as trace_mod
from pixie_tpu.exec.trace import BackgroundRing, background, clock_ns
from pixie_tpu.scripts import load_script
from pixie_tpu.services import (
    AgentTracker, KelvinAgent, MessageBus, PEMAgent, QueryBroker,
)
from pixie_tpu.services.observability import (
    MetricsRegistry, ObservabilityServer,
)

HEARTBEAT_S = 0.05
#: (the two ``bus.deliver``: the Kelvin's rows and its eos on the
#: results topic; ``trace.sinks`` joins the trace once its sinks ran)
BROKER_SPANS = ["snapshot", "snapshot", "compile", "plan", "admit",
                "register", "dispatch", "await", "await.results",
                "bus.deliver", "bus.deliver", "await.stats", "finish",
                "trace.sinks"]


@pytest.fixture(scope="module")
def served():
    """Broker, one PEM and one Kelvin on an in-process bus, with the
    chip's XLA fold (not the CPU backend's native kernel), and every
    finished trace of the three tracers."""
    from pixie_tpu.ingest.replay import gen_http_events

    with config.override_flag("cpu_fold_threads", 1):
        bus = MessageBus()
        tracker = AgentTracker(bus)
        pem = PEMAgent(bus, "pem-0", heartbeat_interval_s=HEARTBEAT_S).start()
        kelvin = KelvinAgent(
            bus, "kelvin-0", heartbeat_interval_s=HEARTBEAT_S
        ).start()
        for chunk in gen_http_events(1 << 13, chunk=1 << 13):
            pem.append_data("http_events", chunk)
        pem._register()
        deadline = time.time() + 10
        while "http_events" not in tracker.schemas():
            assert time.time() < deadline
            time.sleep(0.01)
        broker = QueryBroker(bus, tracker)
        seen = {"broker": [], "pem": [], "kelvin": []}
        broker.tracer.add_listener(seen["broker"].append)
        pem.engine.tracer.add_listener(seen["pem"].append)
        kelvin.engine.tracer.add_listener(seen["kelvin"].append)
        pxl = load_script("px/http_stats").pxl
        t0 = clock_ns()
        for _ in range(3):
            res = broker.execute_script(pxl, timeout_s=60)
        t1 = clock_ns()
        time.sleep(4 * HEARTBEAT_S)
        yield {"broker": broker, "pem": pem, "kelvin": kelvin, "seen": seen,
               "qid": res["qid"], "t0": t0, "t1": t1, "pxl": pxl}
        pem.stop()
        kelvin.stop()
        broker.close()
        tracker.close()
        bus.close()


def _last(served, who):
    return next(t for t in reversed(served["seen"][who])
                if t.qid == served["qid"])


@pytest.mark.parametrize("who", ["broker", "pem", "kelvin"])
def test_every_span_is_on_the_one_clock_and_nests(served, who):
    tr = _last(served, who)
    by_id = {s.span_id: s for s in tr.spans}
    assert tr.root.start_ns == tr.start_ns and tr.root.end_ns == tr.end_ns
    for s in tr.spans:
        # Stamped, ordered, and inside the test's own readings of the
        # same clock: no second clock, nothing back-dated.
        assert served["t0"] <= s.start_ns <= s.end_ns <= clock_ns(), s.name
        assert s.end_unix_nano - s.start_unix_nano == s.end_ns - s.start_ns
        parent = by_id.get(s.parent_id)
        if parent is None:
            continue
        where = s.attributes.get("outside_root")
        if where == "before":  # the handler's wait before the engine's trace
            assert s.end_ns <= parent.start_ns
        elif where == "after":  # its publish after it
            assert s.start_ns >= parent.end_ns
        else:
            assert parent.start_ns <= s.start_ns, (s.name, parent.name)
            assert s.end_ns <= parent.end_ns, (s.name, parent.name)
    assert abs(tr.start_unix_nano - time.time_ns()) < 300e9


def test_broker_trace_names_the_served_paths_stages(served):
    tr = _last(served, "broker")
    assert [s.name for s in tr.spans[1:]] == BROKER_SPANS
    spans = {s.name: s for s in tr.spans}
    assert spans["admit"].attributes == {"queued": False}
    assert spans["await.results"].parent_id == spans["await"].span_id
    assert spans["await.stats"].start_ns >= spans["await.results"].end_ns
    # The stages tile the root: every one of them there, in this order,
    # none overlapping the next, all inside the root. (How much of the
    # root no stage covers is a size, not an order: the benchmark's
    # ``unnamed_ms`` measures it on the chip's host; on a loaded test
    # box it was a coin's toss, ROADMAP D13.)
    top = [s for s in tr.spans if s.parent_id == tr.root.span_id
           and s.attributes.get("outside_root") is None]
    assert [s.name for s in top] == [
        "snapshot", "snapshot", "compile", "plan", "admit", "register",
        "dispatch", "await", "finish",
    ]
    edges = [tr.start_ns]
    for s in top:
        edges += [s.start_ns, s.end_ns]
    edges.append(tr.end_ns)
    assert edges == sorted(edges)
    sinks = spans["trace.sinks"]
    assert sinks.attributes["outside_root"] == "after"
    assert sinks.start_ns == tr.end_ns <= sinks.end_ns


def test_agents_traces_lie_inside_the_brokers_root(served):
    b = _last(served, "broker")
    dispatch = next(s for s in b.spans if s.name == "dispatch")
    for who in ("pem", "kelvin"):
        tr = _last(served, who)
        assert tr.trace_id == b.trace_id
        assert tr.root.parent_id == dispatch.span_id
        assert b.start_ns <= tr.start_ns <= tr.end_ns <= b.end_ns
    # The PEM's publish and the Kelvin's wait for it meet.
    publish = next(s for s in _last(served, "pem").spans
                   if s.name == "publish")
    wait = next(s for s in _last(served, "kelvin").spans
                if s.name == "merge.wait")
    assert wait.start_ns < publish.end_ns and publish.start_ns < wait.end_ns


@pytest.mark.parametrize("who,programs", [
    ("pem", ["fragment_update"]),
    ("kelvin", ["merge_finalize"]),
])
def test_device_spans_and_device_ms(served, who, programs):
    tr = _last(served, who)
    dispatches = [s for s in tr.spans if s.name == "device.dispatch"]
    waits = [s for s in tr.spans if s.name == "device.wait"]
    assert [d.attributes["program"] for d in dispatches] == programs
    assert all(d.attributes["windows"] == 1 for d in dispatches)
    # The PEM waits for its state, the Kelvin's merge once for its one
    # program's planes, validity and overflow flag.
    assert len(waits) == {"pem": 1, "kelvin": 1}[who]
    if who == "kelvin":
        assert dispatches[0].attributes["prepared"] == "hit"
        assert not [s for s in tr.spans if s.name == "window.stage"]
    frag_ids = {s.span_id for s in tr.spans if s.name == "fragment"}
    assert {s.parent_id for s in dispatches + waits} == frag_ids
    assert not [s for s in tr.spans if s.name == "window.compute"]
    # device_ms is what its name says: per fragment, first dispatch
    # start to last wait end.
    want = 0.0
    for fid in frag_ids:
        mine = [s for s in dispatches + waits if s.parent_id == fid]
        want += (max(s.end_ns for s in mine)
                 - min(s.start_ns for s in mine)) / 1e6
    assert tr.usage.device_ms == pytest.approx(want, abs=1e-6)
    assert 0 < tr.usage.device_ms <= tr.duration_s * 1e3


def test_window_spans_are_stamped_where_they_run():
    """A window staged on the prefetch thread is a span with that
    thread's two stamps, and the fragment's stage timer is their sum
    (the served path's windows are resident and the Kelvin stages
    nothing: a bare engine without residency does)."""
    from pixie_tpu.exec import Engine

    with config.override_flag("device_residency", False):
        eng = Engine(window_rows=1 << 10)
        n = 1 << 10
        eng.append_data("t", {"time_": np.arange(n, dtype=np.int64),
                              "k": np.arange(n) % 5, "v": np.arange(n)})
        eng.execute_query(
            "import px\ndf = px.DataFrame(table='t')\n"
            "df = df.groupby('k').agg(n=('v', px.count))\npx.display(df)\n"
        )
    tr = eng.tracer.last()
    stage = next(s for s in tr.spans if s.name == "window.stage")
    frag = next(s for s in tr.spans if s.span_id == stage.parent_id)
    assert frag.attributes["stage_seconds"] == pytest.approx(
        (stage.end_ns - stage.start_ns) / 1e9, abs=1e-5
    )
    assert [s.name for s in tr.spans].count("materialize") == 1


def test_every_interval_of_a_short_query_is_a_span():
    """Every interval of a stage up to ``trace_window_sample``, then
    every that-many-th: a script that folds five windows keeps all of
    them, a long scan stays bounded."""
    from pixie_tpu.exec import Engine

    eng = Engine(window_rows=1 << 10)
    n = 7 * (1 << 10)
    eng.append_data("t", {"time_": np.arange(n, dtype=np.int64),
                          "k": np.arange(n) % 5, "v": np.arange(n)})
    q = ("import px\ndf = px.DataFrame(table='t')\n"
         "df = df.groupby('k').agg(n=('v', px.count))\npx.display(df)\n")
    eng.execute_query(q)
    stalls = [s for s in eng.tracer.last().spans if s.name == "window.stall"]
    assert [s.attributes["interval"] for s in stalls] == list(range(8))
    with config.override_flag("trace_window_sample", 3):
        eng.execute_query(q)
    stalls = [s for s in eng.tracer.last().spans if s.name == "window.stall"]
    assert [s.attributes["interval"] for s in stalls] == [0, 1, 2, 3, 6]


def test_only_the_anchor_reads_the_wall_clock():
    src = open(trace_mod.__file__).read()
    hits = [ln for ln in src.splitlines() if "time_ns()" in ln]
    assert len(hits) == 1 and hits[0].startswith("_UNIX_ANCHOR_NS = ")
    assert not re.search(r"perf_counter\(\)\s*[-+]", src)
    assert trace_mod.unix_ns(0) == 0
    assert abs(trace_mod.unix_ns(clock_ns()) - time.time_ns()) < 50e6


def test_a_profiler_session_holds_the_spans_as_annotations(served, tmp_path):
    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        qid = served["broker"].execute_script(
            served["pxl"], timeout_s=60
        )["qid"]
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"
    ))[0]
    events = [
        e for plane in ProfileData.from_file(path).planes
        if plane.name == "/host:CPU"
        for line in plane.lines for e in line.events
    ]
    names = {e.name for e in events}
    assert {"device.wait", "device.dispatch", "await.results", "register",
            "publish"} <= names
    waits = [e for e in events if e.name == "device.wait"]
    assert all(dict(e.stats).get("qid") == qid for e in waits)
    # The annotation and the span are one interval, on two clocks.
    tr = next(t for t in reversed(served["seen"]["pem"]) if t.qid == qid)
    span = next(s for s in tr.spans if s.name == "device.wait")
    # (the annotation is entered just before the first stamp and left
    # just after the second)
    assert len(waits) == 1 + 1  # the PEM's, the Kelvin's
    assert any(0 <= w.duration_ns - (span.end_ns - span.start_ns) < 2e6
               for w in waits)


def test_the_ring_holds_the_heartbeats_turns(served):
    entries = background.entries(served["t0"])
    turns = [e for e in entries if e["name"] == "heartbeat"]
    assert len(turns) >= 2  # two agents, several intervals
    parts = {"heartbeat.freshness", "heartbeat.tables_fold",
             "heartbeat.bus_fold"}
    assert parts <= {e["name"] for e in entries}
    for part in (e for e in entries if e["name"] in parts):
        # A turn is recorded when it ends, after its parts: a part later
        # than its thread's last recorded turn belongs to one in flight.
        if part["start_ns"] >= max((t["end_ns"] for t in turns
                                    if t["thread"] == part["thread"]),
                                   default=0):
            continue
        assert any(t["thread"] == part["thread"]
                   and t["start_ns"] <= part["start_ns"]
                   and part["end_ns"] <= t["end_ns"] for t in turns), part
    assert all(e["start_ns"] <= e["end_ns"] <= clock_ns() for e in entries)
    # The telemetry fold of every agent trace, on the handler's thread.
    folds = [e for e in entries if e["name"] == "telemetry.fold"]
    assert len(folds) >= 6  # three requests, PEM and Kelvin


def test_background_work_reaches_no_listener_and_no_counter(served):
    reg = served["pem"].engine.tracer.registry
    def queries():
        return sum(v for k, v in reg.values("pixie_queries_total").items())
    before = {k: len(v) for k, v in served["seen"].items()}
    n_queries = queries()
    with background.turn("tracker.sweep"):
        pass
    time.sleep(3 * HEARTBEAT_S)  # heartbeats meanwhile
    assert {k: len(v) for k, v in served["seen"].items()} == before
    assert queries() == n_queries
    kinds = {t.kind for traces in served["seen"].values() for t in traces}
    assert kinds == {"distributed", "fragment", "merge"}
    assert "__queries__" in served["pem"].engine.tables
    rows = served["pem"].engine.tables["__queries__"].num_rows
    time.sleep(2 * HEARTBEAT_S)
    assert served["pem"].engine.tables["__queries__"].num_rows == rows


def test_queryz_serves_the_ring_beside_the_traces(served):
    srv = ObservabilityServer(registry=MetricsRegistry(),
                              tracer=served["broker"].tracer)
    qz = json.loads(srv.handle("/debug/queryz")[2])
    assert {"in_flight", "recent", "background"} <= set(qz)
    assert {"name", "thread", "start_ns", "end_ns"} == set(
        qz["background"][-1]
    )
    assert any(e["name"] == "heartbeat" for e in qz["background"])


def test_ring_is_bounded_and_records_long_collections():
    ring = BackgroundRing(size=4)
    for i in range(9):
        ring.record(f"t{i}", i, i + 1)
    assert [e["name"] for e in ring.entries()] == ["t5", "t6", "t7", "t8"]
    assert [e["name"] for e in ring.entries(since_ns=9)] == ["t8"]
    ring = BackgroundRing()
    ring.watch_gc(min_ms=0.0)
    ring.watch_gc(min_ms=0.0)  # idempotent: one callback
    try:
        assert gc.callbacks.count(ring._gc_callback) == 1
        gc.collect()
    finally:
        gc.callbacks.remove(ring._gc_callback)
    (e,) = [e for e in ring.entries() if e["name"] == "gc.gen2"]
    assert e["start_ns"] < e["end_ns"]
    quiet = BackgroundRing()
    quiet.watch_gc(min_ms=60_000.0)  # nothing is that slow
    try:
        gc.collect()
    finally:
        gc.callbacks.remove(quiet._gc_callback)
    assert quiet.entries() == []
