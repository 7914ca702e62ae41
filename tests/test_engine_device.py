"""An engine that lives on one named device (ISSUE 46): its tables stage
there, its programs run there, its operands, carries and remaps are made
there, and what it hands back is fetched from there, beside other engines
of the process on theirs; an engine given no device is as it always was.
On the CPU's eight host devices; a copy from one device to another that
nothing asked for fails the request (``jax_transfer_guard_device_to_device``)."""

from __future__ import annotations

import contextlib
import time
from unittest import mock

import jax
import numpy as np
import pytest

from conftest import routes_of, wait_until
from pixie_tpu import config
from pixie_tpu.exec import bridge, joins, placement, programs, stream
from pixie_tpu.exec import streaming
from pixie_tpu.exec.engine import Engine
from pixie_tpu.ingest.replay import gen_http_events
from pixie_tpu.scripts import load_script
from pixie_tpu.services import (
    AgentTracker, KelvinAgent, MessageBus, PEMAgent, QueryBroker,
)

ROWS, WINDOW = 1 << 13, 1 << 11
SCRIPTS = ("px/http_stats", "px/service_stats")


@contextlib.contextmanager
def _watched():
    """No implicit copy between devices inside the block, and every
    program's outputs (where it is enqueued: ``stream._start_fetch``) by
    the device of the scope that enqueued them: [(scope, [devices])]."""
    enqueued = []
    real = stream._start_fetch

    def start(tree):
        enqueued.append((placement.current(), [
            leaf.devices() for leaf in jax.tree_util.tree_leaves(tree)
            if isinstance(leaf, jax.Array)]))
        return real(tree)

    jax.config.update("jax_transfer_guard_device_to_device", "disallow")
    try:
        with contextlib.ExitStack() as patches:
            for mod in (stream, bridge, joins, streaming):
                if hasattr(mod, "_start_fetch"):
                    patches.enter_context(
                        mock.patch.object(mod, "_start_fetch", start))
            yield enqueued
    finally:
        jax.config.update("jax_transfer_guard_device_to_device", "allow")


def _fill(engine, seed=3):
    for chunk in gen_http_events(ROWS, chunk=WINDOW, seed=seed):
        engine.append_data("http_events", chunk)


def _window_devices(engine) -> set:
    out = set()
    for win, _lo, _hi in engine.tables["http_events"].device_scan(
            None, None, window_rows=WINDOW):
        for planes in win.cols.values():
            for plane in planes:
                out |= plane.devices()
    return out


def _answer(engine, name):
    out = engine.execute_query(load_script(name).pxl)["output"].to_pydict()
    order = np.lexsort([out[c] for c in sorted(out) if out[c].dtype == object])
    return {c: v[order] for c, v in out.items()}


def _dispatches(engine):
    return [s for s in engine.tracer.last().spans
            if s.name in ("device.dispatch", "device.fetch")]


@pytest.mark.parametrize("platform", ["tpu", "cpu"])
def test_an_engine_keeps_its_table_its_folds_and_its_answer_on_its_device(
        platform):
    device = jax.devices()[3]
    with routes_of(platform), config.override_flag("cpu_fold_threads", 1):
        plain, pinned = Engine(window_rows=WINDOW), Engine(
            window_rows=WINDOW, device=device)
        _fill(plain)
        _fill(pinned)
        assert pinned.device is device
        assert pinned.tables["http_events"].stage_sharding == (
            jax.sharding.SingleDeviceSharding(device))
        assert _window_devices(pinned) == {device}
        assert _window_devices(plain) == {jax.devices()[0]}
        for name in SCRIPTS:
            want = _answer(plain, name)
            with _watched() as enqueued:
                got = _answer(pinned, name)
            assert sorted(got) == sorted(want)
            for col in want:
                np.testing.assert_array_equal(got[col], want[col])
            # Everything a program handed back was made on the engine's
            # device, inside its scope.
            assert enqueued and all(
                scope is device and leaves and all(
                    d == {device} for d in leaves)
                for scope, leaves in enqueued), enqueued
            named = _dispatches(pinned)
            assert named and {s.attributes["device"] for s in named} == {3}
        # The one helper commits to the engine's device.
        put = pinned._put({"a": np.arange(4), "b": (np.ones(2),)})
        assert all(a.devices() == {device} and a.committed
                   for a in jax.tree_util.tree_leaves(put))


def test_an_engine_given_no_device_is_as_it_was():
    with config.override_flag("cpu_fold_threads", 1):
        engine = Engine(window_rows=WINDOW)
        _fill(engine)
        assert engine.device is None and engine._stage_sharding is None
        assert engine.tables["http_events"].stage_sharding is None
        assert engine.tracer.device_id is None  # learnt at its first request
        with engine._on_device():  # no scope: JAX's own default
            assert placement.current() is None
        put = engine._put(np.arange(4))
        assert put.devices() == {jax.devices()[0]} and not put.committed
        _answer(engine, "px/http_stats")
        first = jax.devices()[0].id
        assert engine.tracer.device_id == first
        assert {s.attributes["device"] for s in _dispatches(engine)} == {first}


def test_without_its_scope_a_devices_engine_copies_between_devices():
    """What the scope is for, and that the guard of these tests has
    teeth: the same engine with no scope around its request makes its
    fold's empty state where JAX puts things (device 0) beside windows
    committed to device 3, and the copy is refused."""
    with routes_of("tpu"), config.override_flag("cpu_fold_threads", 1):
        engine = Engine(window_rows=WINDOW, device=jax.devices()[3])
        _fill(engine)
        engine._on_device = contextlib.nullcontext
        with _watched(), pytest.raises(Exception, match="device-to-device"):
            _answer(engine, "px/http_stats")


def test_a_tracked_call_of_host_arrays_names_the_scopes_device():
    """A call whose leaves are host arrays carries no device: its
    signature names the scope's, so an executable compiled for one
    engine's device never serves another's, and none is dropped."""
    one, two = jax.devices()[1], jax.devices()[2]
    x = np.arange(8, dtype=np.int32)
    assert programs.shape_signature((x,)) == programs.shape_signature((x,))
    with placement.scope(one):
        sig_one = programs.shape_signature((x,))
    with placement.scope(two):
        sig_two = programs.shape_signature((x,))
    assert len({sig_one, sig_two, programs.shape_signature((x,))}) == 3
    assert sig_one[:2] == sig_two[:2] and sig_one[2] is one
    registry = programs.ProgramRegistry(size=8)
    program = registry.wrap(jax.jit(lambda a: a + 1), "probe", ("probe",))
    dropped = []
    with mock.patch.object(registry, "_degrade", dropped.append):
        for device in (one, two, one, two):
            with placement.scope(device):
                out = program(x)
            assert out.devices() == {device}
            np.testing.assert_array_equal(np.asarray(out), x + 1)
    stats = registry.stats()
    assert (stats["compiles"], stats["hits"]) == (2, 2) and dropped == []


def test_an_operand_table_has_a_copy_a_device():
    from pixie_tpu.exec.expr import Operand

    table = Operand(np.arange(16, dtype=np.int32))
    first, third = jax.devices()[0], jax.devices()[3]
    plain = table.device()
    assert plain.devices() == {first} and table.device() is plain
    with placement.scope(third):
        there = table.device()
        assert there.devices() == {third} and there.committed
        assert table.device() is there
    assert table.device() is plain
    np.testing.assert_array_equal(np.asarray(there), table.host)


@pytest.fixture(scope="module")
def cluster():
    """Broker, three PEMs on devices 1, 2 and 3 with events of their own
    (dictionaries that differ), a Kelvin on device 0: the last request's
    traces a script, and the same scripts' answers from engines given no
    device."""
    def serve(devices):
        bus = MessageBus()
        tracker = AgentTracker(bus)
        pems = [
            PEMAgent(bus, f"pem-{n}", engine=Engine(
                window_rows=WINDOW, device=device)).start()
            for n, device in enumerate(devices[1:])
        ]
        kelvin = KelvinAgent(bus, "kelvin-0",
                             engine=Engine(device=devices[0])).start()
        seen = {"broker": [], "kelvin": [], **{
            p.agent_id: [] for p in pems}}
        try:
            for n, pem in enumerate(pems):
                _fill(pem.engine, seed=n + 1)
                pem._register()
                pem.engine.tracer.add_listener(seen[pem.agent_id].append)
            wait_until(lambda: len(tracker.distributed_state().pems) == 3
                       and "http_events" in tracker.schemas(),
                       "the PEMs' schemas reached the tracker")
            broker = QueryBroker(bus, tracker)
            broker.tracer.add_listener(seen["broker"].append)
            kelvin.engine.tracer.add_listener(seen["kelvin"].append)
            out = {}
            for name in SCRIPTS:
                # (The second merge is at the bucket the first saw the
                # union fit: the third finds it prepared.)
                for _ in range(3):
                    res = broker.execute_script(load_script(name).pxl,
                                                timeout_s=120)
                assert not res.get("partial")
                rows = res["tables"]["output"].to_pydict()
                order = np.lexsort([rows[c] for c in sorted(rows)
                                    if rows[c].dtype == object])
                time.sleep(0.05)
                out[name] = {
                    "rows": {c: v[order] for c, v in rows.items()},
                    **{who: next(t for t in reversed(traces)
                                 if t.qid == res["qid"])
                       for who, traces in seen.items()},
                }
            broker.close()
            return out
        finally:
            for pem in pems:
                pem.stop()
            kelvin.stop()
            tracker.close()
            bus.close()

    with routes_of("tpu"), config.override_flag("cpu_fold_threads", 1):
        plain = serve([None] * 4)
        with _watched() as enqueued:
            pinned = serve(jax.devices()[:4])
    return {"plain": plain, "pinned": pinned, "enqueued": enqueued}


@pytest.mark.parametrize("script", SCRIPTS)
def test_engines_on_their_devices_answer_as_engines_on_none(cluster, script):
    want, got = (cluster[k][script]["rows"] for k in ("plain", "pinned"))
    assert sorted(got) == sorted(want) and len(want["service"]) > 0
    for col in want:
        np.testing.assert_array_equal(got[col], want[col])
    # (The PEMs' programs: the Kelvin's one fetch is its sync, and its
    # merge's span names its device below.)
    scopes = {scope for scope, _leaves in cluster["enqueued"]}
    assert scopes == set(jax.devices()[1:4])
    assert all(d == {scope} for scope, leaves in cluster["enqueued"]
               for d in leaves)


@pytest.mark.parametrize("script", SCRIPTS)
def test_the_spans_name_the_device_and_what_k_payloads_cost(cluster, script):
    traces = cluster["pinned"][script]
    (dispatch,) = [s for s in traces["broker"].spans if s.name == "dispatch"]
    assert dispatch.attributes["agents"] == 3 + 1
    assert dispatch.attributes["data_agents"] == "pem-0,pem-1,pem-2"
    for n in range(3):
        named = [s.attributes["device"] for s in traces[f"pem-{n}"].spans
                 if s.name in ("device.dispatch", "device.fetch")]
        assert named and set(named) == {n + 1}
        assert traces[f"pem-{n}"].usage.merge_payloads == 0
    kelvin = traces["kelvin"]
    (merge,) = [s for s in kelvin.spans if s.name == "device.dispatch"]
    a = merge.attributes
    assert (a["program"], a["device"], a["prepared"]) == (
        "merge_finalize", 0, "hit")
    assert (a["payloads"], a["merges"]) == (3, 2)
    # Events of another seed: a dictionary of its own a PEM, so every
    # payload but the first reads a remap a string key column.
    assert a["remap_entries"] >= 2 * 32 and a["upload_bytes"] > 0
    u = kelvin.usage
    assert (u.merge_payloads, u.merge_remap_entries, u.merge_upload_bytes) == (
        3, a["remap_entries"], a["upload_bytes"])
    # An engine given no device reads the same counters; its spans name
    # the device JAX puts its work on.
    plain = cluster["plain"][script]["kelvin"]
    assert plain.usage.merge_payloads == 3
    assert plain.usage.merge_remap_entries == u.merge_remap_entries
    assert {s.attributes["device"] for s in plain.spans
            if s.name == "device.dispatch"} == {jax.devices()[0].id}


def test_a_stream_polls_on_its_engines_device():
    from pixie_tpu.exec.streaming import StreamingQuery
    from pixie_tpu.planner import CompilerState, compile_pxl

    device = jax.devices()[2]
    with routes_of("tpu"), config.override_flag("cpu_fold_threads", 1):
        engine = Engine(window_rows=WINDOW, device=device)
        _fill(engine)
        plan = compile_pxl(load_script("px/http_stats").pxl, CompilerState(
            schemas={n: t.relation for n, t in engine.tables.items()},
            registry=engine.registry, now_ns=0, max_output_rows=10_000,
        )).plan
        emitted = []
        query = StreamingQuery(engine, plan, emitted.append)
        try:
            with _watched() as enqueued:
                assert query.poll() == ROWS
        finally:
            query.close()
        assert emitted and emitted[-1].batch.length > 0
        assert enqueued and all(
            scope is device and all(d == {device} for d in leaves)
            for scope, leaves in enqueued)
        assert engine.tracer.device_id == 2
