"""The host path named from inside (ISSUE 37): the bus hops, the PEM's
head and tail, the fetch inside ``device.wait``, the join's pieces and
the trace's own sinks are spans on the one clock, stamped where the
work happens. Orders and stamps on the served stack, never durations;
on the CPU."""

from __future__ import annotations

import os
import re
import time

import pytest

from pixie_tpu import config
from pixie_tpu.exec import trace as trace_mod
from pixie_tpu.exec.bridge import payload_nbytes
from pixie_tpu.scripts import load_script
from pixie_tpu.services import (
    AgentTracker, KelvinAgent, MessageBus, PEMAgent, QueryBroker,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def served():
    """Broker, one PEM and one Kelvin on an in-process bus with the XLA
    fold, three warm requests of px/http_stats: the last one's three
    traces and the bridge payloads the PEM shipped for it."""
    from pixie_tpu.ingest.replay import gen_http_events

    with config.override_flag("cpu_fold_threads", 1):
        bus = MessageBus()
        tracker = AgentTracker(bus)
        pem = PEMAgent(bus, "pem-0").start()
        kelvin = KelvinAgent(bus, "kelvin-0").start()
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
        shipped = []
        tap = bus.subscribe("agent.kelvin-0.bridge", shipped.append)
        pxl = load_script("px/http_stats").pxl
        for _ in range(3):
            res = broker.execute_script(pxl, timeout_s=60)
        time.sleep(0.1)  # the agents' publish spans close after eos
        qid = res["qid"]
        yield {
            "qid": qid,
            "shipped": [m["payload"] for m in shipped if m["qid"] == qid],
            **{who: next(t for t in reversed(traces) if t.qid == qid)
               for who, traces in seen.items()},
        }
        tap.unsubscribe()
        pem.stop()
        kelvin.stop()
        broker.close()
        tracker.close()
        bus.close()


def _named(trace, name):
    return [s for s in trace.spans if s.name == name]


def _one(trace, name):
    (span,) = _named(trace, name)
    return span


def test_bus_deliver_runs_from_the_publishers_span_to_the_handlers_entry(
    served,
):
    b, pem, kelvin = served["broker"], served["pem"], served["kelvin"]
    # The execute message: enqueued inside the broker's ``dispatch``,
    # handed to the PEM's handler before its trace began.
    hop = _one(pem, "bus.deliver")
    dispatch = _one(b, "dispatch")
    assert hop.attributes["outside_root"] == "before"
    assert hop.attributes["topic"] == "agent.execute"
    assert dispatch.start_ns <= hop.start_ns <= dispatch.end_ns
    assert hop.start_ns <= hop.end_ns <= pem.root.start_ns
    # The bridge payload: enqueued inside the PEM's ``publish``, handed
    # to the Kelvin's handler before its merge began; ``merge.wait``
    # keeps meaning installed-to-start.
    hop = _one(kelvin, "bus.deliver")
    publish = _one(pem, "publish")
    assert hop.attributes["topic"] == "agent.bridge"
    assert publish.start_ns <= hop.start_ns <= publish.end_ns
    assert hop.end_ns <= kelvin.root.start_ns
    wait = _one(kelvin, "merge.wait")
    assert wait.end_ns == kelvin.root.start_ns and wait.start_ns < hop.end_ns
    # The results (rows, then eos): enqueued inside the Kelvin's
    # ``publish``, children of the broker's ``await.results``.
    results = _one(b, "await.results")
    publish = _one(kelvin, "publish")
    hops = _named(b, "bus.deliver")
    assert len(hops) == 2
    for hop in hops:
        assert hop.parent_id == results.span_id
        assert hop.attributes["topic"] == "query.results"
        assert publish.start_ns <= hop.start_ns <= publish.end_ns
        assert results.start_ns <= hop.start_ns <= hop.end_ns <= results.end_ns
    # The bus's estimate of a message's bytes, where it has one.
    assert all(s.attributes["bytes"] >= 0 for t in (b, pem, kelvin)
               for s in _named(t, "bus.deliver"))


def test_device_fetch_lies_inside_its_wait_and_counts_what_shipped(served):
    pem, kelvin = served["pem"], served["kelvin"]
    wait, fetch = _one(pem, "device.wait"), _one(pem, "device.fetch")
    assert fetch.parent_id == wait.span_id
    assert wait.start_ns < fetch.start_ns <= fetch.end_ns <= wait.end_ns
    # Its bytes are the shipped state's leaves', which is what the wire
    # carried and what the usage record counts.
    (payload,) = served["shipped"]
    assert fetch.attributes["bytes"] == payload_nbytes(payload) > 0
    assert fetch.attributes["bytes"] == pem.usage.wire_bytes
    assert (pem.usage.bytes_fetched, pem.usage.fetches) == (
        fetch.attributes["bytes"], 1
    )
    import jax

    assert fetch.attributes["leaves"] == len(
        jax.tree_util.tree_leaves(payload.state)
    )
    assert "bytes" not in wait.attributes
    # The Kelvin's merge fetches by one batched get: its wait carries
    # the leaves and bytes itself and has no child.
    wait = _one(kelvin, "device.wait")
    assert not _named(kelvin, "device.fetch")
    assert wait.attributes["leaves"] > 0
    assert (kelvin.usage.bytes_fetched, kelvin.usage.fetches) == (
        wait.attributes["bytes"], 1
    )
    # Merged across the agents on the broker's trace, as bytes_staged is
    # (the Kelvin's stats ride their own topic and may miss the fold).
    b = served["broker"]
    assert pem.agent_id in b.agent_usage
    for field in ("bytes_fetched", "fetches", "bytes_staged"):
        assert getattr(b.usage, field) == sum(
            u[field] for u in b.agent_usage.values()
        )
    assert b.usage.bytes_fetched >= pem.usage.bytes_fetched > 0


def test_the_pems_head_and_tail_are_named_in_the_order_they_run(served):
    pem = served["pem"]
    first = min(_named(pem, "device.dispatch"), key=lambda s: s.start_ns)
    last = max(_named(pem, "device.wait"), key=lambda s: s.end_ns)
    head = [_named(pem, n)[0] for n in (
        "plan.walk", "fragment.bind", "state.init", "pipeline.start",
        "window.select",
    )]
    # On the query's thread one after the other; the window is selected
    # on the prefetch thread, which runs as soon as it is started.
    edges = [pem.root.start_ns]
    for s in head[:4]:
        edges += [s.start_ns, s.end_ns]
    assert edges + [first.start_ns] == sorted(edges + [first.start_ns])
    assert head[3].start_ns <= head[4].start_ns
    assert head[4].end_ns <= first.start_ns
    assert head[1].attributes["cached"] == "hit"
    assert head[4].attributes == {"skipped": 0}
    frag = _one(pem, "fragment")
    assert {s.parent_id for s in head[:2]} == {pem.root.span_id}
    assert {s.parent_id for s in head[2:]} == {frag.span_id}
    tail = [_one(pem, n) for n in ("payload", "trace.sinks", "publish")]
    edges = [last.end_ns]
    for s in tail:
        edges += [s.start_ns, s.end_ns]
    assert edges == sorted(edges)
    assert tail[0].end_ns <= pem.root.end_ns == tail[1].start_ns
    assert tail[0].attributes["kind"] == "agg_state"
    assert tail[1].attributes["outside_root"] == "after"
    assert tail[1].attributes["listeners"] >= 1
    # ... and on the Kelvin's merge trace the same sinks, its compaction
    # and its prepared merge's lookup before its one dispatch.
    kelvin = served["kelvin"]
    sinks, publish = _one(kelvin, "trace.sinks"), _one(kelvin, "publish")
    assert kelvin.root.end_ns == sinks.start_ns <= sinks.end_ns
    assert sinks.end_ns <= publish.start_ns
    bind = _one(kelvin, "fragment.bind")
    dispatch = _one(kelvin, "device.dispatch")
    assert bind.attributes["cached"] == dispatch.attributes["prepared"]
    compact = _named(kelvin, "merge.compact")
    assert [s.end_ns <= dispatch.start_ns for s in compact] == [True, True]


def test_head_ms_is_the_brokers_stages_the_hop_and_the_pems_head(served):
    """``head_ms`` (broker root start to the PEM's first dispatch) by
    stamps: the broker's stages up to the enqueue, the ``bus.deliver``
    hop, the handler's preamble, then ``pem_head_ms``."""
    b, pem = served["broker"], served["pem"]
    hop = _one(pem, "bus.deliver")
    first = min(s.start_ns for s in _named(pem, "device.dispatch"))
    head = first - b.root.start_ns
    pem_head = first - pem.root.start_ns
    stages = hop.start_ns - b.root.start_ns
    preamble = pem.root.start_ns - hop.end_ns
    assert min(stages, preamble, pem_head) >= 0
    assert head == stages + (hop.end_ns - hop.start_ns) + preamble + pem_head
    # Every broker stage before ``dispatch`` has ended by the enqueue.
    for name in ("snapshot", "compile", "plan", "admit", "register"):
        assert all(s.end_ns <= hop.start_ns for s in _named(b, name))


@pytest.fixture(scope="module")
def rehearsed():
    """Two refreshes of each script the suite rehearses, on the
    benchmark's own builders at a small size: ``spans`` {cell: {tracer:
    [traces]}} and ``join_programs`` {cell: the join programs the
    refreshes registered}."""
    from benchmark import harness
    from pixie_tpu.exec.programs import default_program_registry

    def join_programs():
        return {r["program_id"]
                for r in default_program_registry().programz()["programs"]
                if r["kind"].startswith("join")}

    out = {"spans": {}, "join_programs": {}}
    for cell in ("http_pem_1chip.dash_recent", "conn_flow_1chip.flow_recent",
                 "sql_stats_1chip.sql_recent",
                 "stack_flame_1chip.flame_recent",
                 "http_edges_1chip.graph_recent",
                 "http_cluster_4chip.cluster_recent"):
        spec = harness.load_cell(cell)
        cfg, traffic = spec["config"], spec["traffic"]
        builder = harness.module("builders", cfg["builder"])
        driver = harness.module("drivers", traffic["driver"])
        with config.override_flag("cpu_fold_threads", 1):
            stack = builder.build(cfg, 1 << 13)
            try:
                stack.ingest(builder.make_data(cfg, 3_700_000_019, 1 << 15))
                log = harness.SpanLog(stack.tracers)
                _lo, now_ns = harness.range_lo_ns(cfg, traffic)
                before = join_programs()
                for _ in range(2):
                    driver.refresh(stack, harness.requests_of(spec), now_ns,
                                   120, harness.mark)
                time.sleep(0.1)
                out["spans"][cell] = log.cut()
                out["join_programs"][cell] = join_programs() - before
            finally:
                stack.close()
    return out


@pytest.fixture(scope="module")
def rehearsed_names(rehearsed):
    return rehearsed["spans"]


def test_served_scripts_stamp_no_name_outside_span_names(rehearsed_names):
    stamped = {s.name for spans in rehearsed_names.values()
               for traces in spans.values() for t in traces for s in t.spans}
    assert stamped <= trace_mod.SPAN_NAMES, stamped - trace_mod.SPAN_NAMES
    assert set(trace_mod.STAGE_SPANS.values()) <= trace_mod.SPAN_NAMES
    # What this issue added is stamped by the served scripts themselves.
    assert {"bus.deliver", "plan.walk", "fragment.bind", "state.init",
            "pipeline.start", "window.select", "device.fetch", "payload", "trace.sinks",
            "merge.compact", "join.align", "join.assemble",
            "restream"} <= stamped


def test_the_docs_and_the_docstring_list_every_span_name():
    doc = open(os.path.join(ROOT, "docs", "OBSERVABILITY.md")).read()
    for text, where in ((doc, "docs/OBSERVABILITY.md"),
                        (trace_mod.__doc__, "trace.py's docstring")):
        words = set(re.findall(r"[a-z_]+(?:\.[a-z_]+)*", text))
        missing = {n for n in trace_mod.SPAN_NAMES if n not in words}
        assert not missing, (where, sorted(missing))


#: What a ``quantiles`` fold says of its digests (PR 41): on its fold
#: programs' ``device.dispatch`` and on the shipped state's ``payload``.
DIGEST_ATTRIBUTES = ("digests", "digest_outputs", "digest_slots",
                     "digest_bins")


def test_the_docs_and_the_docstring_name_the_digests_attributes():
    doc = open(os.path.join(ROOT, "docs", "OBSERVABILITY.md")).read()
    for text, where in ((doc, "docs/OBSERVABILITY.md"),
                        (trace_mod.__doc__, "trace.py's docstring")):
        for word in (*DIGEST_ATTRIBUTES, "digest_bytes", "keyed_digest"):
            assert re.search(rf"\b{word}\b", text), (where, word)
    assert "digest_bytes" in trace_mod.QueryResourceUsage.__doc__
    assert trace_mod.QueryResourceUsage().digest_bytes == 0


def test_a_served_quantiles_fold_says_its_digests(rehearsed_names):
    """The service graph's fold dispatches on the PEM carry the four
    attributes (ONE carry for the three plucked quantiles of one
    column), its shipped state's ``payload`` the digest's bytes, counted
    once, and the usage record sums them; a script without ``quantiles``
    carries none of them."""
    spans = rehearsed_names["http_edges_1chip.graph_recent"]
    pem = spans["pem"][-1]
    folds = [s.attributes for s in _named(pem, "device.dispatch")
             if "fold" in s.attributes]
    assert folds
    for a in folds:
        assert (a["digests"], a["digest_outputs"]) == (1, 3)
        assert a["digest_slots"] == a["slots"] * 128
        assert a["digest_bins"] in (8192, 4096, 1 << 32)
    (payload,) = _named(pem, "payload")
    assert payload.attributes["kind"] == "agg_state"
    assert payload.attributes["digest_bytes"] == (
        1 * 2 * folds[0]["digest_slots"] * 4) == pem.usage.digest_bytes
    assert 0 < pem.usage.digest_bytes < pem.usage.wire_bytes
    assert spans["kelvin"][-1].usage.digest_bytes == 0
    for t in rehearsed_names["sql_stats_1chip.sql_recent"]["pem"]:
        assert t.usage.digest_bytes == 0
        for s in t.spans:
            assert not set(s.attributes) & {*DIGEST_ATTRIBUTES,
                                            "digest_bytes"}, s.name


#: What the Kelvin's ``merge_finalize`` dispatch says of its k payloads
#: (PR 46), the usage record's counters of them, and the device every
#: ``device.dispatch`` and ``device.fetch`` names.
MERGE_ATTRIBUTES = ("payloads", "merges", "remap_entries", "upload_bytes")
MERGE_COUNTERS = ("merge_payloads", "merge_remap_entries",
                  "merge_upload_bytes")
CLUSTER = "http_cluster_4chip.cluster_recent"


def test_the_docs_and_the_docstring_name_the_device_and_the_merges_cost():
    doc = open(os.path.join(ROOT, "docs", "OBSERVABILITY.md")).read()
    for text, where in ((doc, "docs/OBSERVABILITY.md"),
                        (trace_mod.__doc__, "trace.py's docstring")):
        for word in (*MERGE_ATTRIBUTES, *MERGE_COUNTERS, "device", "agents",
                     "placement"):
            assert re.search(rf"\b{word}\b", text), (where, word)
    usage = trace_mod.QueryResourceUsage()
    for counter in MERGE_COUNTERS:
        assert counter in trace_mod.QueryResourceUsage.__doc__
        assert getattr(usage, counter) == 0
    other = trace_mod.QueryResourceUsage(merge_payloads=4,
                                         merge_upload_bytes=10)
    usage.merge(other)
    usage.merge(other.to_dict())
    assert (usage.merge_payloads, usage.merge_upload_bytes) == (8, 20)


def test_every_served_dispatch_and_fetch_names_its_device(rehearsed_names):
    """An engine's spans name its device: the one it was given (a PEM a
    node on a device of its own in the cluster's cell, the Kelvin beside
    node 0's) or, given none, the device JAX puts its work on. The
    broker's spans name none."""
    for cell, spans in rehearsed_names.items():
        for tracer, traces in spans.items():
            want = ({"pem.1": 1, "pem.2": 2, "pem.3": 3}.get(tracer, 0)
                    if cell == CLUSTER else 0)
            named = [s for t in traces for s in t.spans
                     if s.name in ("device.dispatch", "device.fetch")]
            assert bool(named) == (tracer != "broker"), (cell, tracer)
            assert all(s.attributes["device"] == want for s in named), (
                cell, tracer)
            assert not any("device" in s.attributes for t in traces
                           for s in t.spans if s not in named), (cell, tracer)
    (dispatch,) = _named(rehearsed_names[CLUSTER]["broker"][-1], "dispatch")
    assert dispatch.attributes["agents"] == 4 + 1
    (dispatch,) = _named(
        rehearsed_names["http_pem_1chip.dash_recent"]["broker"][-1],
        "dispatch")
    assert dispatch.attributes["agents"] == 1 + 1


def test_a_served_merge_says_what_its_payloads_cost(rehearsed_names):
    """One PEM: one payload, no merge folded, no remap read. Four PEMs
    with dictionaries of their own: four payloads, three merges, a remap
    a payload and string key column; the usage record sums them, and no
    other span carries them."""
    for cell, spans in rehearsed_names.items():
        k = 4 if cell == CLUSTER else 1
        merges = [(t, s) for t in spans["kelvin"]
                  for s in _named(t, "device.dispatch")
                  if s.attributes["program"] == "merge_finalize"]
        assert merges, cell
        for t, s in merges:
            a = s.attributes
            assert (a["payloads"], a["merges"]) == (k, k - 1), (cell, a)
            # (Absent without a remap, as a fold program's.)
            assert (a.get("remap_entries", 0) > 0) == (k > 1) == (
                "remap_entries" in a), (cell, a)
            assert a["upload_bytes"] > 0
        for t in spans["kelvin"]:
            mine = [s.attributes for _t, s in merges if _t is t]
            for counter, attr in zip(MERGE_COUNTERS, (
                    "payloads", "remap_entries", "upload_bytes")):
                assert getattr(t.usage, counter) == sum(
                    a.get(attr, 0) for a in mine), (cell, counter)
        for tracer, traces in spans.items():
            for t in traces:
                if tracer.startswith("pem"):  # (the broker's sums them)
                    assert not any(getattr(t.usage, c, 0)
                                   for c in MERGE_COUNTERS), (cell, tracer)
                for s in t.spans:
                    if s.attributes.get("program") != "merge_finalize":
                        assert not set(s.attributes) & {
                            "payloads", "merges", "upload_bytes"} or (
                            s.name == "merge.compact"), (cell, s.name)


#: What a fold of resident windows says of the rows it was handed (PR
#: 44): on its fold programs' ``device.dispatch``.
RANGE_ATTRIBUTES = ("rows", "range_rows")


def test_the_docs_and_the_docstring_name_the_range_attributes():
    doc = open(os.path.join(ROOT, "docs", "OBSERVABILITY.md")).read()
    for text, where in ((doc, "docs/OBSERVABILITY.md"),
                        (trace_mod.__doc__, "trace.py's docstring")):
        for word in (*RANGE_ATTRIBUTES, "_fold_rows"):
            assert re.search(rf"\b{word}\b", text), (where, word)


def test_a_served_fold_of_resident_windows_says_its_rows(rehearsed_names):
    """Every PEM fold dispatch of every rehearsed cell carries ``rows``
    (a quarter, a half or the whole of each window's 2^13-row capacity)
    and ``range_rows`` (no more than that); the Kelvin's programs, whose
    windows are staged batches under a mask, carry neither."""
    for cell, spans in rehearsed_names.items():
        folds = [s.attributes for t in spans["pem"]
                 for s in _named(t, "device.dispatch")
                 if "fold" in s.attributes]
        assert folds, cell
        for a in folds:
            assert a["rows"] in {a["windows"] << k for k in (11, 12, 13)}, (
                cell, a)
            assert 0 < a["range_rows"] <= a["rows"], (cell, a)
        for t in spans["kelvin"]:
            for s in _named(t, "device.dispatch"):
                assert not set(s.attributes) & set(RANGE_ATTRIBUTES), (
                    cell, s.attributes)


#: What a fold's ``state.init`` says of the one program it holds (PR 48).
STATE_INIT_ATTRIBUTES = ("programs", "leaves")


def test_the_docs_and_the_docstring_say_what_state_init_holds():
    doc = open(os.path.join(ROOT, "docs", "OBSERVABILITY.md")).read()
    for text, where in ((doc, "docs/OBSERVABILITY.md"),
                        (trace_mod.__doc__, "trace.py's docstring")):
        # The span's entry: from its name to the next entry's.
        (line,) = re.findall(
            r"(?:- ``|├── )state\.init(?:``)?\s(.*?)\n(?:- ``|│   ├── )",
            text, re.S)
        for word in (*STATE_INIT_ATTRIBUTES, "fragment_init_state"):
            assert re.search(rf"\b{word}\b", line), (where, word)
        line = " ".join(line.replace("│", " ").split())
        assert "ONE program" in line, where
        assert "eager" not in line, where


def test_every_served_fold_makes_its_state_by_one_program(rehearsed_names):
    """Every ``state.init`` of every rehearsed cell (a PEM fragment with
    an AggOp, the Kelvin's re-aggregation) says one program and the
    state's leaves, ends before its fragment's first ``device.dispatch``
    and lies inside none; no other span carries the two attributes."""
    for cell, spans in rehearsed_names.items():
        seen = 0
        for tracer, traces in spans.items():
            for t in traces:
                dispatches = _named(t, "device.dispatch")
                for s in t.spans:
                    if s.name != "state.init":
                        assert not set(s.attributes) & {"programs"}, (
                            cell, s.name)
                        continue
                    seen += 1
                    assert tracer != "broker"
                    assert set(s.attributes) == set(STATE_INIT_ATTRIBUTES)
                    assert s.attributes["programs"] == 1
                    assert s.attributes["leaves"] >= 3, (cell, s.attributes)
                    mine = [d for d in dispatches
                            if d.parent_id == s.parent_id]
                    assert mine and s.end_ns <= min(
                        d.start_ns for d in mine), cell
                    assert not any(
                        d.start_ns < s.end_ns and s.start_ns < d.end_ns
                        for d in dispatches), cell
        assert seen, cell


def test_the_joins_pieces_are_children_of_its_span(rehearsed_names):
    kelvin = next(t for t in rehearsed_names["conn_flow_1chip.flow_recent"]
                  ["kelvin"] if _named(t, "join"))
    join = _one(kelvin, "join")
    align, assemble = _one(kelvin, "join.align"), _one(kelvin, "join.assemble")
    assert align.parent_id == assemble.parent_id == join.span_id
    assert join.start_ns <= align.start_ns <= align.end_ns
    assert align.end_ns <= assemble.start_ns <= assemble.end_ns <= join.end_ns
    # The two address columns' dictionaries differ: a union was built.
    assert align.attributes["memo"] == "miss"
    assert align.attributes["strings"] > 0
    assert assemble.attributes["rows_out"] == join.attributes["rows_out"]
    # The join's rows made the source of the re-aggregation.
    restream = _one(kelvin, "restream")
    assert restream.parent_id == kelvin.root.span_id
    assert restream.attributes["rows"] == join.attributes["rows_out"]
    assert join.end_ns <= restream.start_ns
    # The walk's stretches and the work between them do not overlap.
    walks = sorted(_named(kelvin, "plan.walk"), key=lambda s: s.start_ns)
    assert len(walks) >= 3
    for a, b in zip(walks, walks[1:]):
        assert a.end_ns <= b.start_ns
    assert not any(w.start_ns < join.end_ns and join.start_ns < w.end_ns
                   for w in walks)


@pytest.mark.parametrize("cell,build_is", [
    ("conn_flow_1chip.flow_recent", "one address a pod"),
    ("stack_flame_1chip.flame_recent", "one total a pod"),
])
def test_the_served_joins_are_lookups_on_the_host(rehearsed, cell, build_is):
    """``px/net_flow_graph``'s and ``px/perf_flamegraph``'s join on the
    Kelvin: a merged aggregate probed against a smaller one that is
    unique on one dictionary-coded key (ISSUE 40). The span says so, its
    pieces stay its children, and no join program is registered."""
    kelvins = [t for t in rehearsed["spans"][cell]["kelvin"]
               if _named(t, "join")]
    assert len(kelvins) == 2
    for kelvin in kelvins:
        join = _one(kelvin, "join")
        a = join.attributes
        assert (a["strategy"], a["where"], a["how"]) == (
            "host_table", "host", "inner"), build_is
        # A code of the key's dictionary a slot, and the null's.
        assert a["domain"] > a["build_rows"] > 0
        assert 0 < a["rows_out"] <= a["probe_rows"]
        align = _one(kelvin, "join.align")
        assemble = _one(kelvin, "join.assemble")
        assert align.parent_id == assemble.parent_id == join.span_id
        assert align.end_ns <= assemble.start_ns <= assemble.end_ns
        assert assemble.attributes["rows_out"] == a["rows_out"]
    assert rehearsed["join_programs"][cell] == set()


def test_one_stamp_a_bus_message_serves_the_lag_and_the_span():
    """The bus reads ``trace.clock_ns`` once at the enqueue, always; a
    handler finds the hop under ``current_delivery``, no one else does."""
    from pixie_tpu.services import msgbus

    bus = MessageBus()
    got = []
    t0 = trace_mod.clock_ns()
    sub = bus.subscribe("soak.hop", lambda m: got.append(
        (msgbus.current_delivery(), trace_mod.clock_ns())
    ))
    bus.publish("soak.hop", {"x": 1})
    deadline = time.time() + 5
    while not got:
        assert time.time() < deadline
        time.sleep(0.001)
    (enq_ns, entered_ns, cls, nbytes), seen_ns = got[0]
    assert t0 <= enq_ns <= entered_ns <= seen_ns
    assert cls == "soak.hop" and nbytes >= 0
    assert msgbus.current_delivery() is None  # not a dispatcher thread
    with config.override_flag("bus_telemetry", False):
        quiet = MessageBus()
    assert quiet.stats is None
    quiet.subscribe("soak.hop", lambda m: got.append(
        msgbus.current_delivery()
    ))
    quiet.publish("soak.hop", {})
    while len(got) < 2:
        assert time.time() < deadline
        time.sleep(0.001)
    assert got[1][0] <= got[1][1] and got[1][3] == 0
    sub.unsubscribe()
    bus.close()
    quiet.close()
