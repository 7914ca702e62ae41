"""A fold's empty state is ONE program's output (ISSUE 48): beside
``update`` / ``update_all`` / ``finalize`` a compiled aggregate fragment
carries ``init_program``, the jitted ``init_state``, a tracked program of
no argument (``fragment_init_state``) that the engines run once a fold
under the ``state.init`` span, on the device of the scope they run it in;
the mesh's replicates its output. Here, on the CPU's host devices: the
program against the plain function leaf for leaf over every fold the tree
builds, a warm request that makes no other device array before its first
dispatch, two engines of one process on a device each sharing one cached
fragment, and a mesh engine whose donated state is made anew."""

from __future__ import annotations

import contextlib
import time
from unittest import mock

import jax
import numpy as np
import pytest

import pixie_tpu  # noqa: F401  (x64 on)
from conftest import routes_of
from pixie_tpu import config
from pixie_tpu.exec import fragment
from pixie_tpu.exec.engine import Engine
from pixie_tpu.exec.programs import default_program_registry
from pixie_tpu.scripts import load_script
from test_engine_device import _answer, _fill, _watched
from test_window_slice import WINDOW, _events, _run

#: (a form of ``tests/test_window_slice.py``, the platform whose routes
#: fold it, the fold's layout): the chip's six folds, and what the CPU's
#: routes build of a keyed and a dense one (a keyed fold there is the id
#: form: no payload sort).
FOLDS = [
    ("dense_int", "tpu", "dense"), ("dense_digest", "tpu", "dense"),
    ("keyed_payload", "tpu", "sorted"), ("keyed_remap", "tpu", "sorted"),
    ("keyed_any", "tpu", "sorted"), ("keyed_digest", "tpu", "sorted"),
    ("keyed_payload", "cpu", "hashed"), ("keyed_digest", "cpu", "hashed"),
    ("dense_digest", "cpu", "dense"),
]


@contextlib.contextmanager
def _compiled_fragments():
    """Every aggregate fragment compiled inside the block, as the cache
    keeps it (its programs tracked)."""
    seen, real = [], fragment.compile_fragment_cached

    def spy(*args, **kwargs):
        frag = real(*args, **kwargs)
        if frag.is_agg and not any(f is frag for f in seen):
            seen.append(frag)
        return frag

    from pixie_tpu.exec import bridge, engine, stream

    with contextlib.ExitStack() as patches:
        for mod in (engine, stream, bridge):
            if hasattr(mod, "compile_fragment"):
                patches.enter_context(
                    mock.patch.object(mod, "compile_fragment", spy))
        yield seen


@pytest.mark.parametrize("form,platform,layout", FOLDS)
def test_the_program_gives_the_plain_functions_state_leaf_for_leaf(
        form, platform, layout):
    eng = Engine(window_rows=WINDOW)
    eng.append_data("events", _events())
    with routes_of(platform), config.override_flag("cpu_fold_threads", 1):
        with _compiled_fragments() as frags:
            _rows, folds = _run(eng, form, 100, 900, platform=platform)
        assert folds and {a["group"] for a in folds} == {layout}
        assert frags
        for frag in frags:
            assert frag.init_program.kind == "fragment_init_state"
            made, plain = frag.init_program(), frag.init_state()
            assert (jax.tree_util.tree_structure(made)
                    == jax.tree_util.tree_structure(plain))
            assert set(made) == {"keys", "valid", "carries", "overflow"}
            for (path, got), want in zip(
                    jax.tree_util.tree_leaves_with_path(made),
                    jax.tree_util.tree_leaves(plain)):
                assert isinstance(got, jax.Array), path
                assert got.aval == want.aval, (path, got.aval, want.aval)
                np.testing.assert_array_equal(
                    np.asarray(got), np.asarray(want), err_msg=str(path))


# -- a warm request ----------------------------------------------------------


@contextlib.contextmanager
def _eager_primitives():
    """Every primitive applied eagerly inside the block (an array made
    or computed outside any program, each a dispatch of its own), as
    (``perf_counter_ns``, name); a jitted call is not one."""
    from jax._src import core

    applied, real = [], core.EvalTrace.process_primitive

    def spy(self, primitive, *args, **kwargs):
        applied.append((time.perf_counter_ns(), primitive.name))
        return real(self, primitive, *args, **kwargs)

    with mock.patch.object(core.EvalTrace, "process_primitive", spy):
        yield applied


class _Compiles:
    """Programs XLA compiled in the process, from JAX's own events."""

    def __init__(self):
        self.programs = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1


@pytest.fixture(scope="module")
def compiles():
    return _Compiles()


def test_the_spy_sees_the_plain_functions_constructions():
    """The eager form is what the spy counts: a handful of dispatches a
    state where the program is none."""
    eng = Engine(window_rows=WINDOW)
    eng.append_data("events", _events())
    with routes_of("tpu"), _compiled_fragments() as frags:
        _run(eng, "dense_int", 100, 900)
    (frag,) = frags
    frag.init_program()
    with _eager_primitives() as applied:
        frag.init_program()
        assert applied == []
        leaves = len(jax.tree_util.tree_leaves(frag.init_state()))
        assert len(applied) >= leaves >= 6


def test_a_warm_request_runs_one_program_and_makes_no_other_array(compiles):
    """Broker, PEM and Kelvin in process, ``px/http_stats`` three times:
    the third compiles nothing; its ``state.init`` says one program and
    the state's leaves; from the PEM's root to its first
    ``device.dispatch`` nothing is applied eagerly and the one tracked
    program called is ``fragment_init_state``, a hit."""
    from pixie_tpu.exec import programs
    from pixie_tpu.ingest.replay import gen_http_events
    from pixie_tpu.services import (
        AgentTracker, KelvinAgent, MessageBus, PEMAgent, QueryBroker,
    )

    called, real_call = [], programs.TrackedProgram.__call__

    def call(self, *args):
        called.append((time.perf_counter_ns(), self.kind))
        return real_call(self, *args)

    with config.override_flag("cpu_fold_threads", 1):
        bus = MessageBus()
        tracker = AgentTracker(bus)
        pem = PEMAgent(bus, "pem-0").start()
        kelvin = KelvinAgent(bus, "kelvin-0").start()
        try:
            for chunk in gen_http_events(1 << 13, chunk=1 << 13):
                pem.append_data("http_events", chunk)
            pem._register()
            deadline = time.time() + 10
            while "http_events" not in tracker.schemas():
                assert time.time() < deadline
                time.sleep(0.01)
            broker = QueryBroker(bus, tracker)
            traces = []
            pem.engine.tracer.add_listener(traces.append)
            pxl = load_script("px/http_stats").pxl
            for _ in range(2):
                broker.execute_script(pxl, timeout_s=60)
            registry = default_program_registry()
            before = (compiles.programs, registry.stats()["compiles"])
            with _eager_primitives() as applied, mock.patch.object(
                    programs.TrackedProgram, "__call__", call):
                qid = broker.execute_script(pxl, timeout_s=60)["qid"]
            time.sleep(0.1)
            assert (compiles.programs, registry.stats()["compiles"]) == before
            broker.close()
        finally:
            pem.stop()
            kelvin.stop()
            tracker.close()
            bus.close()
    trace = next(t for t in reversed(traces) if t.qid == qid)
    (init,) = [s for s in trace.spans if s.name == "state.init"]
    assert init.attributes == {"programs": 1, "leaves": 6}
    first = min(s.start_ns for s in trace.spans if s.name == "device.dispatch")
    assert trace.root.start_ns < init.start_ns < init.end_ns <= first
    head = (trace.root.start_ns, first)
    assert [name for at, name in applied if head[0] <= at < head[1]] == []
    assert [kind for at, kind in called if head[0] <= at < head[1]] == [
        "fragment_init_state"]
    assert [kind for at, kind in called
            if init.start_ns <= at < init.end_ns] == ["fragment_init_state"]
    # Outside ``device.dispatch``: the fold's one program is the one
    # dispatch the PEM counts.
    dispatched = [s.attributes["program"] for s in trace.spans
                  if s.name == "device.dispatch"]
    assert dispatched == ["fragment_update"]
    rows = [r for r in default_program_registry().programz()["programs"]
            if r["kind"] == "fragment_init_state"]
    assert rows and all(r["compiles"] == 1 for r in rows)
    assert sum(r["hits"] for r in rows) >= 2


# -- a device each -----------------------------------------------------------


@contextlib.contextmanager
def _states_made(engine):
    """[(sharding, committed) a leaf] of every empty state ``engine``
    makes inside the block, read where it is made (a donated state's
    buffers are gone afterwards)."""
    made, real = [], engine._compile_steps

    def steps(frag):
        init, agg, rows = real(frag)
        if init is None:
            return init, agg, rows

        def run():
            state = init()
            made.append([(leaf.sharding, leaf.committed)
                         for leaf in jax.tree_util.tree_leaves(state)])
            return state

        return run, agg, rows

    engine._compile_steps = steps
    try:
        yield made
    finally:
        del engine._compile_steps


@pytest.mark.parametrize("platform", ["tpu", "cpu"])
def test_an_engine_gets_its_state_on_its_device_from_a_shared_fragment(
        platform):
    """Device 0's engine answers first and fills the fragment cache; the
    same script on device 1's engine binds that fragment (``cached``
    ``hit``), runs ITS record of the one program and folds with no copy
    between devices; then device 0's again."""
    zero, one = jax.devices()[0], jax.devices()[1]
    with routes_of(platform), config.override_flag("cpu_fold_threads", 1):
        engines = [Engine(window_rows=1 << 11, device=d) for d in (zero, one)]
        for engine in engines:
            _fill(engine)
        want = None
        for engine, device in (*zip(engines, (zero, one)), (engines[0], zero)):
            with _watched(), _states_made(engine) as made, \
                    _compiled_fragments() as frags:
                got = _answer(engine, "px/http_stats")
            if want is None:
                want, shared = got, frags
            else:
                assert [f is s for f, s in zip(frags, shared)] == [True]
                (bind,) = [s for s in engine.tracer.last().spans
                           if s.name == "fragment.bind"]
                assert bind.attributes["cached"] == "hit"
            for col in want:
                np.testing.assert_array_equal(got[col], want[col])
            (state,) = made
            assert len(state) == 6 and all(
                sharding.device_set == {device}
                for sharding, _committed in state), (device, state)
    records = [r for r in default_program_registry().records()
               if r.kind == "fragment_init_state"
               and r.fn_id in {id(shared[0].init_program.fn)}]
    assert len(records) == 2, "one record a device, of one program"
    assert len({r.program_id for r in records}) == 2


# -- the mesh ----------------------------------------------------------------


def test_the_mesh_engine_makes_its_donated_state_anew_and_replicated():
    """``DistributedEngine``'s step donates its state: the same script
    twice running answers twice (a state handed out twice would raise
    on its deleted buffers), each request's state the output of one
    program, replicated over the mesh's four devices."""
    from pixie_tpu.parallel.executor import DistributedEngine
    from pixie_tpu.parallel.mesh import agent_mesh

    dist = DistributedEngine(window_rows=WINDOW, mesh=agent_mesh(4))
    dist.append_data("events", _events())
    one = Engine(window_rows=WINDOW)
    one.append_data("events", _events())
    want, _folds = _run(one, "dense_int", 3_500, 5_596)
    with _states_made(dist) as made, _eager_primitives() as applied:
        for _ in range(3):
            got, folds = _run(dist, "dense_int", 3_500, 5_596)
            assert got == want
            assert {a["program"] for a in folds} == {"mesh_agg_step"}
            inits = [s.attributes for s in dist.tracer.last().spans
                     if s.name == "state.init"]
            assert inits == [{"programs": 1, "leaves": 8}]
        trace = dist.tracer.last()
        first = min(s.start_ns for s in trace.spans
                    if s.name == "device.dispatch")
        assert [name for at, name in applied
                if trace.root.start_ns <= at < first] == []
    assert len(made) == 3
    mesh_devices = set(dist.mesh.devices.flat)
    assert len(mesh_devices) == 4
    for state in made:
        assert len(state) == 8
        for sharding, _committed in state:
            assert sharding.is_fully_replicated
            assert sharding.device_set == mesh_devices
    # One program a (fragment, mesh), cached beside the steps.
    assert sum(1 for key in dist._step_cache
               if key[-1] == "init_state") == 1


def test_a_streaming_fold_starts_from_the_program():
    """``exec/streaming.py``'s persistent state is the program's output
    too (its one call site): a poll of a live aggregate on device 2's
    engine calls ``fragment_init_state`` once, applies nothing eagerly
    before its first fold and copies nothing between devices; the next
    poll folds into the state it kept."""
    from pixie_tpu.exec import programs
    from pixie_tpu.exec.streaming import StreamingQuery
    from pixie_tpu.planner import CompilerState, compile_pxl

    called, real_call = [], programs.TrackedProgram.__call__

    def call(self, *args):
        called.append(self.kind)
        return real_call(self, *args)

    with routes_of("tpu"), config.override_flag("cpu_fold_threads", 1):
        engine = Engine(window_rows=1 << 11, device=jax.devices()[2])
        _fill(engine)
        plan = compile_pxl(load_script("px/http_stats").pxl, CompilerState(
            schemas={n: t.relation for n, t in engine.tables.items()},
            registry=engine.registry, now_ns=0, max_output_rows=10_000,
        )).plan
        query = StreamingQuery(engine, plan, lambda update: None)
        try:
            with _watched(), mock.patch.object(
                    programs.TrackedProgram, "__call__", call), \
                    _eager_primitives() as applied:
                assert query.poll() == 1 << 13
                folds = [k for k in called if k != "fragment_finalize"]
                assert folds[0] == "fragment_init_state"
                assert folds.count("fragment_init_state") == 1
                assert applied == []
                _fill(engine, seed=4)
                del called[:]
                assert query.poll() == 1 << 13
                assert "fragment_init_state" not in called
        finally:
            query.close()
