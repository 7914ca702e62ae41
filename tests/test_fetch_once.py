"""A fragment's result leaves the device in one transfer (ISSUE 38):
whatever a program made comes to the host by ONE batched get
(``exec/stream.py`` ``_fetch_tree``) whose copies were started where the
program was enqueued (``_start_fetch``), never by a blocking copy a
leaf. Held here on the CPU: what ships is, leaf for leaf and dtype for
dtype, what ``tree_map(np.asarray, ...)`` gave; one batched fetch a
fragment; an overflowed fold drops its started copies and climbs as
before. The ``device.fetch`` span's place, ``leaves`` and ``bytes`` are
``tests/test_host_path_spans.py``'s."""

from __future__ import annotations

import contextlib
from unittest import mock

import jax
import numpy as np
import pytest
from conftest import routes_of

from pixie_tpu import config
from pixie_tpu.exec import bridge, joins, stream, streaming
from pixie_tpu.exec.engine import DeviceResult, Engine
from pixie_tpu.exec.plan import (
    AggExpr, AggOp, ColumnRef as C, JoinOp, MemorySourceOp, Plan,
    ResultSinkOp,
)
from pixie_tpu.exec.streaming import StreamingQuery
from pixie_tpu.planner.distributed.splitter import Splitter
from pixie_tpu.types.batch import HostBatch

LAYOUTS = ("dense", "keyed", "digest")
ROWS = 600
#: Integer keys too far apart for a dense domain: a keyed state (the
#: payload-carrying sort on the TPU's routes).
CODES = np.array([3, 10**6 + 1, 10**9 + 7, 10**11 + 3, 10**12 + 9])
#: The old way, a blocking copy a leaf: the reference (bound here, so
#: that a reference made inside ``_spied`` is not counted as a stray).
_ASARRAY = np.asarray


def _a_copy_a_leaf(tree):
    return jax.tree_util.tree_map(_ASARRAY, tree)


class _Spy:
    """What crossed to the host while the block ran: every
    ``_start_fetch`` (its leaves), every ``_fetch_tree`` (tree in, tree
    out) and every ``np.asarray`` of a device array with a dimension
    made OUTSIDE a ``_fetch_tree`` (a copy a leaf; a 0-d flag's read is
    a path's sync and is not counted)."""

    def __init__(self):
        self.events: list = []  # ("start" | "fetch", ...) in order
        self.strays: list = []

    @property
    def starts(self):
        return [e[1] for e in self.events if e[0] == "start"]

    @property
    def fetches(self):
        return [e[1:] for e in self.events if e[0] == "fetch"]


@contextlib.contextmanager
def _spied():
    spy, inside = _Spy(), [0]
    real_start, real_fetch, real_asarray = (
        stream._start_fetch, stream._fetch_tree, np.asarray
    )

    def start(tree):
        spy.events.append(("start", jax.tree_util.tree_leaves(tree)))
        return real_start(tree)

    def fetch(tree):
        inside[0] += 1
        try:
            out = real_fetch(tree)
        finally:
            inside[0] -= 1
        spy.events.append(("fetch", tree, out))
        return out

    def asarray(a, *args, **kw):
        if not inside[0] and isinstance(a, jax.Array) and a.ndim:
            spy.strays.append(a.shape)
        return real_asarray(a, *args, **kw)

    with contextlib.ExitStack() as stack:
        for mod in (stream, bridge, joins, streaming):
            for name, fn in (("_start_fetch", start), ("_fetch_tree", fetch)):
                if hasattr(mod, name):
                    stack.enter_context(mock.patch.object(mod, name, fn))
        stack.enter_context(mock.patch.object(np, "asarray", asarray))
        yield spy


def _same(got, want) -> None:
    """``got`` is ``want`` leaf for leaf: structure, type, dtype, shape
    and bits."""
    got_leaves, got_def = jax.tree_util.tree_flatten(got)
    want_leaves, want_def = jax.tree_util.tree_flatten(want)
    assert got_def == want_def
    for g, w in zip(got_leaves, want_leaves):
        assert type(g) is type(w) is np.ndarray
        assert (g.dtype, g.shape) == (w.dtype, w.shape)
        assert g.tobytes() == w.tobytes()


def _rows(rows: int = ROWS, seed: int = 38) -> dict:
    rng = np.random.default_rng(seed)
    names = [f"svc-{i}" for i in range(7)]
    return {
        "time_": np.arange(rows, dtype=np.int64),
        "svc": [names[i] for i in rng.zipf(1.5, rows) % len(names)],
        "code": CODES[rng.integers(0, len(CODES), rows)],
        "lat": rng.integers(1, 10**9, rows).astype(np.int64),
    }


def _engine(rows=None) -> Engine:
    eng = Engine(window_rows=1 << 8)  # three windows: a fold and merges
    eng.append_data("t", rows or _rows())
    return eng


def _plan(layout: str, max_groups: int = 4096) -> Plan:
    keys = ("svc", "code") if layout == "keyed" else ("svc",)
    if layout == "digest":
        aggs = (AggExpr("n", "count", (C("lat"),)),
                AggExpr("p50", "_quantile_p50", (C("lat"),)))
    else:
        aggs = (AggExpr("n", "count", (C("lat"),)),
                AggExpr("total", "sum", (C("lat"),)),
                AggExpr("worst", "max", (C("lat"),)))
    p = Plan()
    src = p.add(MemorySourceOp(table="t"))
    agg = p.add(AggOp(keys, aggs, max_groups=max_groups), [src])
    p.add(ResultSinkOp("output"), [agg])
    return p


@contextlib.contextmanager
def _routes(platform: str):
    """``platform``'s fold routes with XLA doing the fold (the CPU's
    native multi-core fold hands over a state it built on the host)."""
    with routes_of(platform), config.override_flag("cpu_fold_threads", 1):
        yield


def _by_group(batch: HostBatch) -> dict:
    out = batch.to_pydict()
    keys = [k for k in ("svc", "code") if k in out]
    vals = [c for c in out if c not in keys]
    return {
        tuple(out[k][i] for k in keys): tuple(out[c][i] for c in vals)
        for i in range(batch.length)
    }


def _spans(eng, name):
    return [s for s in eng.tracer.last().spans if s.name == name]


# -- the helper ---------------------------------------------------------------


@pytest.mark.parametrize("case", ["device", "mixed", "empty"])
def test_fetch_tree_is_asarray_a_leaf_and_passes_host_leaves_through(case):
    import jax.numpy as jnp

    host = np.arange(5, dtype=np.int16)
    tree = {
        "device": {"keys": (), "valid": jnp.arange(6) > 2,
                   "carries": {"n": (jnp.arange(6, dtype=jnp.int64) << 40,
                                     jnp.float32(1.5) * jnp.ones((6, 2)))},
                   "overflow": jnp.asarray(False)},
        "mixed": (jnp.arange(3, dtype=jnp.uint32), host, 7, None, "s"),
        "empty": (),
    }[case]
    stream._start_fetch(tree)  # no sync, nothing returned, any leaf
    stream._start_fetch(tree)  # a copy in flight is not started twice
    got = stream._fetch_tree(tree)
    if case == "mixed":
        assert got[1] is host and got[2:] == (7, None, "s")
        _same(got[0], _ASARRAY(tree[0]))
    else:
        _same(got, _a_copy_a_leaf(tree))


# -- the PEM's shipped state --------------------------------------------------


@pytest.mark.parametrize("platform", ["tpu", "cpu"])
@pytest.mark.parametrize("layout", LAYOUTS)
def test_a_folds_state_ships_by_one_batched_get(layout, platform):
    split = Splitter().split(_plan(layout))
    with _routes(platform), _spied() as spy:
        eng = _engine()
        payload = eng.execute_plan(split.before_blocking)[("bridge", 0)]
    # Started once, where the fold's last program was enqueued, then
    # ONE get of the same tree; no copy a leaf beside it.
    assert [e[0] for e in spy.events] == ["start", "fetch"]
    (tree, out), = spy.fetches
    assert all(isinstance(a, jax.Array) for a in spy.starts[0])
    assert [id(a) for a in spy.starts[0]] == [
        id(a) for a in jax.tree_util.tree_leaves(tree)
    ]
    assert spy.strays == []
    assert payload.state is out
    _same(out, _a_copy_a_leaf(tree))
    # One ``device.fetch`` a fragment, with what shipped on it.
    (fetch,) = _spans(eng, "device.fetch")
    leaves = jax.tree_util.tree_leaves(out)
    assert fetch.attributes["leaves"] == len(leaves)
    assert fetch.attributes["bytes"] == sum(a.nbytes for a in leaves)
    assert eng.tracer.last().usage.fetches == 1
    if platform == "tpu":
        (fold,) = {s.attributes["fold"] for s in _spans(eng, "device.dispatch")
                   if "fold" in s.attributes}
        assert fold == {"dense": "pallas_int", "keyed": "sorted_int",
                        "digest": "mixed:pallas_int=1,sorted_digest=1"}[layout]


@pytest.mark.parametrize("what", ["bridge", "result", "cursor"])
def test_an_overflowed_fold_drops_its_copies_and_climbs(what):
    """Four slots for 35 (service, code) groups: every attempt starts
    its copies at the dispatch, only the one that fits is fetched, and
    the climb goes through ``rebucket`` as before."""
    rows = _rows()
    plan = _plan("keyed", max_groups=4)
    split = Splitter().split(plan)
    with _routes("tpu"):
        want = _by_group(_engine(rows).execute_plan(_plan("keyed"))["output"])
        assert len(want) > 16
        with _spied() as spy:
            eng = _engine(rows)
            if what == "bridge":
                got = eng.execute_plan(split.before_blocking)[("bridge", 0)]
            elif what == "result":
                got = eng.execute_plan(plan, materialize=False)["output"]
                assert isinstance(got, DeviceResult)
                got = got.to_host()
            else:
                ups: list = []
                cursor = StreamingQuery(eng, split.before_blocking, ups.append)
                cursor.poll()
                cursor.close()
                got = ups[-1].batch
    climbs = len(spy.starts) - 1
    assert climbs >= 3  # 4 -> 8 -> 16 -> 32 -> 64 slots
    assert len(spy.fetches) == 1 and spy.events[-1][0] == "fetch"
    assert spy.strays == []
    if what == "result":
        assert _by_group(got) == want
    else:
        (tree, out), = spy.fetches
        assert got.state is out
        _same(out, _a_copy_a_leaf(tree))
        assert int(out["valid"].sum()) == len(want)
    if what != "cursor":
        assert len(_spans(eng, "rebucket")) == climbs


# -- a materialized result ----------------------------------------------------


@pytest.mark.parametrize("layout", LAYOUTS)
def test_a_device_results_copies_start_where_finalize_is_dispatched(layout):
    with _routes("tpu"), _spied() as spy:
        eng = _engine()
        res = eng.execute_plan(_plan(layout), materialize=False)["output"]
        assert isinstance(res, DeviceResult)
        # Dispatched, not read: the planes ``to_host`` reads and the
        # overflow flag are on their way, nothing has been waited for.
        assert [e[0] for e in spy.events] == ["start"]
        batch = res.to_host()
    assert [e[0] for e in spy.events] == ["start", "fetch"]
    (tree, (valid, planes)), = spy.fetches
    started = {id(a) for a in spy.starts[0]}
    assert {id(a) for a in jax.tree_util.tree_leaves(tree)} <= started
    assert len(started) == len(jax.tree_util.tree_leaves(tree)) + 1
    assert spy.strays == []
    _same((valid, planes), _a_copy_a_leaf(tree))
    # The one wait of ``to_host`` is all fetch, and what it fetched is
    # what the batch was assembled from.
    (fetch,) = _spans(eng, "device.fetch")
    assert fetch.attributes["leaves"] == 1 + sum(len(ps) for ps in planes)
    assert batch.length == int(valid.sum())
    with _routes("tpu"):
        assert _by_group(batch) == _by_group(
            _engine().execute_plan(_plan(layout))["output"]
        )


def test_a_row_windows_planes_start_before_its_sync():
    """The row path (no aggregate): a window's validity read is its
    sync, as ever, with every plane's copy started before it
    (``synced`` False); then one get a window."""
    from pixie_tpu.exec.plan import FilterOp, FuncCall, Literal
    from pixie_tpu.types.dtypes import DataType

    p = Plan()
    src = p.add(MemorySourceOp(table="t"))
    keep = p.add(FilterOp(FuncCall("greaterThan", (
        C("lat"), Literal(5 * 10**8, DataType.INT64),
    ))), [src])
    p.add(ResultSinkOp("output"), [keep])
    rows = _rows()
    with _spied() as spy:
        eng = _engine(rows)
        out = eng.execute_plan(p)["output"]
    windows = -(-ROWS // (1 << 8))
    assert [e[0] for e in spy.events] == ["start", "fetch"] * windows
    # The one blocking read beside the gets: each window's validity
    # (a padded window's length).
    assert len(spy.strays) == windows
    assert spy.strays == [tree[0].shape for tree, _ in spy.fetches]
    for (tree, got), started in zip(spy.fetches, spy.starts):
        assert [id(a) for a in started] == [
            id(a) for a in jax.tree_util.tree_leaves(tree)
        ]
        _same(got, _a_copy_a_leaf(tree))
    assert len(_spans(eng, "device.fetch")) == windows
    assert out.length == int(np.sum(rows["lat"] > 5 * 10**8))


# -- the join's outputs -------------------------------------------------------


def _join_sides():
    rng = np.random.default_rng(38)
    left = HostBatch.from_pydict({"k": rng.integers(0, 80, 3000),
                                  "v": np.arange(3000)})
    right = HostBatch.from_pydict({"k": np.arange(64, dtype=np.int64),
                                   "w": np.arange(64) * 3})
    return left, right


def _pairs(batch: HostBatch) -> list:
    out = batch.to_pydict()
    return sorted(zip(out["v"].tolist(), out["w"].tolist()))


def test_the_single_shot_joins_six_outputs_come_by_one_get():
    left, right = _join_sides()
    op = JoinOp(how="inner", left_on=("k",), right_on=("k",))
    with routes_of("tpu"), _spied() as spy:
        out = joins._join_device(left, right, op, Engine(),
                                 cap_key=("plan", 38))
    (tree, got), = spy.fetches
    assert len(tree) == 6 and all(isinstance(a, jax.Array) for a in tree)
    assert spy.strays == []
    _same(got, _a_copy_a_leaf(tree))
    assert not got[5]  # the overflow flag rode the batch
    assert _pairs(out) == _pairs(joins._join_host(left, right, op))
    assert out.length == int(np.sum(left.cols["k"][0] < 64))


def test_the_windowed_joins_read_back_is_one_get_a_window():
    left, right = _join_sides()
    op = JoinOp(how="inner", left_on=("k",), right_on=("k",))
    eng = Engine()
    with config.override_flag("join_strategy", "sorted"), \
            config.override_flag("join_probe_window_rows", 1 << 10), \
            _spied() as spy:
        out = joins._join_device(left, right, op, eng)
    assert eng.last_join_decision.strategy == "sorted"
    assert len(spy.fetches) == -(-3000 // (1 << 10)) and spy.strays == []
    for tree, got in spy.fetches:
        assert len(tree) == 6
        _same(got, _a_copy_a_leaf(tree))
    assert _pairs(out) == _pairs(joins._join_host(left, right, op))


# -- the streaming cursor -----------------------------------------------------


@pytest.mark.parametrize("layout", LAYOUTS)
def test_the_cursors_shipped_state_is_one_get_and_stays_on_the_device(layout):
    split = Splitter().split(_plan(layout))
    rows = _rows()
    with _routes("tpu"), _spied() as spy:
        eng = _engine(rows)
        ups: list = []
        cursor = StreamingQuery(eng, split.before_blocking, ups.append)
        cursor.poll()
        (tree, out), = spy.fetches
        assert [e[0] for e in spy.events] == ["start", "fetch"]
        assert spy.strays == []
        assert ups[-1].mode == "state" and ups[-1].batch.state is out
        _same(out, _a_copy_a_leaf(tree))
        # The fold goes on from the state on the device: the next poll
        # folds the new rows into it and ships the sum.
        assert all(isinstance(a, jax.Array)
                   for a in jax.tree_util.tree_leaves(cursor._state))
        more = _rows(seed=39)
        more["time_"] = more["time_"] + ROWS
        eng.append_data("t", more)
        cursor.poll()
        cursor.close()
        one_shot = eng.execute_plan(split.before_blocking)[("bridge", 0)]
    assert len(spy.fetches) == 3 and spy.strays == []
    _same(ups[-1].batch.state["valid"], one_shot.state["valid"])
    _same(ups[-1].batch.state["carries"]["n"], one_shot.state["carries"]["n"])


def test_the_cursors_replaced_aggregate_is_one_get_a_poll():
    with _routes("tpu"), _spied() as spy:
        eng = _engine()
        ups: list = []
        cursor = StreamingQuery(eng, _plan("keyed"), ups.append)
        cursor.poll()
        cursor.close()
        assert [e[0] for e in spy.events] == ["start", "fetch"]
        assert spy.strays == []
        assert ups[-1].mode == "replace"
        assert _by_group(ups[-1].batch) == _by_group(
            eng.execute_plan(_plan("keyed"))["output"]
        )


# -- nothing else is left -----------------------------------------------------


@pytest.mark.parametrize("module", ["bridge", "stream", "streaming", "joins"])
def test_no_copy_a_leaf_of_a_programs_outputs_is_left(module):
    """``tree_map(np.asarray, ...)`` and a generator of ``np.asarray``
    over a program's outputs were the old way: one helper serves every
    site."""
    import os
    import re

    path = os.path.join(os.path.dirname(stream.__file__), module + ".py")
    with open(path) as f:
        src = f.read()
    assert not re.search(r"tree_map\(\s*np\.asarray", src)
    assert not re.search(r"np\.asarray\((\w+)\)\s+for\s+\1\s+in\s+"
                         r"(out|fn\(|cols\[)", src)
