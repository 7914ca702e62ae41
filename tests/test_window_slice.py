"""A fold is handed the rows in range, not the padded window (PR 44):
where a window of an ``Engine`` fold arrives resident with a (lo, hi)
pair, the fold program cuts ``rows`` rows of each plane from ``start``
(``exec/fragment.py`` ``RowSlice``), ``rows`` the least of a quarter, a
half and the whole of the window's capacity that holds the range
(``exec/stream.py`` ``_fold_rows``). Here, on the CPU under the chip's
routes: a sliced fold against the whole-window fold of the same rows,
EQUAL value for value (rows out of range add nothing and the rows in
range keep their order), over the forms the cells fold and ranges at a
window's start, at its end, across each edge between two lengths, in a
partial last window and over several windows; what a run of windows, a
full window, the mesh step and a staged window are handed; and that a
range moving inside one length compiles nothing."""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import pixie_tpu  # noqa: F401  (x64 on)
from conftest import routes_of
from pixie_tpu.config import override_flag
from pixie_tpu.exec.engine import Engine
from pixie_tpu.exec.fragment import RowSlice
from pixie_tpu.exec.programs import default_program_registry
from pixie_tpu.exec.stream import _fold_rows
from pixie_tpu.planner import CompilerState, compile_pxl

#: A window's capacity: a quarter is 1,024 rows, a half 2,048.
WINDOW = 4_096
#: Two full windows and one of 1,808 live rows; a row's time is its id.
ROWS = 10_000

HEAD = "import px\ndf = px.DataFrame(table='events', start_time=%d, end_time=%d)\n"
#: name -> (the script after its DataFrame, ``dense_domain_limit``,
#: ``max_groups``, what the fold dispatches' ``fold`` must say).
FORMS = {
    # px/http_stats on a dense domain: the integer Pallas kernel.
    "dense_int": ("""df = df.groupby(['svc', 'path']).agg(
    n=('lat', px.count), m=('lat', px.mean), mx=('lat', px.max),
    mn=('lat', px.min), s=('size', px.sum))
px.display(df)
""", 1 << 20, 256, "pallas_int"),
    # px/service_stats: the kernel beside a digest built by one sort.
    "dense_digest": ("""df.failure = df.status >= 400
df = df.groupby('svc').agg(
    q=('flat', px.quantiles), err=('failure', px.mean),
    n=('flat', px.count))
df.p50 = px.pluck_float64(df.q, 'p50')
df.p99 = px.pluck_float64(df.q, 'p99')
df = df[['svc', 'p50', 'p99', 'err', 'n']]
px.display(df)
""", 1 << 20, 256, "mixed:pallas_int=2,sorted_digest=2"),
    # px/net_flow_graph's kind: the sums ride the key sort (n >= 4 g at
    # every length).
    "keyed_payload": ("""df = df.groupby(['svc', 'path']).agg(
    a=('lat', px.sum), b=('size', px.sum))
px.display(df)
""", 16, 256, "sorted_int"),
    # px/sql_stats: a dictionary-side UDF's remap is the programs'
    # operand, and both group keys are computed.
    "keyed_remap": ("""df.shape = px.normalize_mysql(df.q)
df.window = px.bin(df.time_, 500)
df = df.groupby(['shape', 'window']).agg(
    n=('lat', px.count), m=('lat', px.mean))
px.display(df)
""", 16, 256, "sorted_int"),
    # px/perf_flamegraph: ``any`` of a string rides the sort as a
    # maximum, and a window short against its slots folds WITH the state.
    "keyed_any": ("""df = df.groupby(['svc', 'path']).agg(
    a=('q', px.any), c=('size', px.sum))
px.display(df)
""", 16, 2_048, "sorted_int"),
    # The service graph: a keyed digest beside the keyed integer fold.
    "keyed_digest": ("""df.failure = df.status >= 400
df = df.groupby(['svc', 'path']).agg(
    q=('flat', px.quantiles), err=('failure', px.mean),
    n=('flat', px.count), s=('size', px.sum))
df.p50 = px.pluck_float64(df.q, 'p50')
df.p99 = px.pluck_float64(df.q, 'p99')
df = df[['svc', 'path', 'p50', 'p99', 'err', 'n', 's']]
px.display(df)
""", 16, 256, "mixed:sorted_int=3,keyed_digest=2"),
}


#: The forms' quantile columns, by place in their answers' rows.
QUANTILE_COLUMNS = {"dense_digest": (1, 2), "keyed_digest": (2, 3)}


def _events():
    rng = np.random.default_rng(44)
    n = ROWS
    return {
        "time_": np.arange(n, dtype=np.int64),
        "lat": rng.integers(-(1 << 40), 1 << 40, n).astype(np.int64),
        "size": rng.integers(0, 1 << 16, n).astype(np.int64),
        "flat": rng.lognormal(10.0, 2.0, n),
        "status": rng.choice(np.array([200, 404, 500], np.int64), n),
        "svc": [f"svc-{i}" for i in rng.integers(0, 8, n)],
        "path": [f"/api/{i}" for i in rng.integers(0, 25, n)],
        "q": [f"SELECT {i} FROM t{i % 5} WHERE id = {i * 7}"
              for i in rng.integers(0, 600, n)],
    }


@pytest.fixture(scope="module")
def engines():
    """{True: an ``Engine`` that slices (as shipped), False: one that
    hands every program whole windows}, over equal tables."""
    data, out = _events(), {}
    for sliced in (True, False):
        eng = Engine(window_rows=WINDOW)
        eng.slice_windows = sliced
        eng.append_data("events", data)
        out[sliced] = eng
    return out


def _run(eng, form, lo, hi, flags=(), platform="tpu"):
    """(the answer's rows, sorted; the fold dispatches' attributes),
    under ``platform``'s routes."""
    body, dense_limit, slots, _fold = FORMS[form]
    with routes_of(platform), override_flag("dense_domain_limit", dense_limit):
        state = CompilerState(
            schemas={n: t.relation for n, t in eng.tables.items()},
            registry=eng.registry, now_ns=0, max_groups=slots,
        )
        plan = compile_pxl(HEAD % (lo, hi) + body, state).plan
        with override_flag(*flags) if flags else contextlib.nullcontext():
            out = eng.execute_plan(plan)
    d = out["output"].to_pydict()
    rows = sorted(zip(*(d[c] for c in d)))
    folds = [s.attributes for s in eng.tracer.last().spans
             if s.name == "device.dispatch" and "fold" in s.attributes]
    return rows, folds


def _same(got, want, ulps=()):
    """Value for value: NaN is NaN, nothing is approximately anything,
    but the columns ``ulps`` names (a digest's read-outs): a centroid's
    mean is an f32 running sum over the window's sorted rows, whose
    blocks (``ops/scan.py``) fall elsewhere in a shorter window, so a
    quantile may differ in its last bits."""
    assert len(got) == len(want)
    for g, w in zip(got, want):
        for i, (a, b) in enumerate(zip(g, w)):
            if i in ulps:
                assert a == pytest.approx(b, rel=1e-6), (g, w)
            else:
                assert a == b or (a != a and b != b), (g, w)


#: name -> (lo, hi, [(windows, rows) a fold dispatch]): ``rows`` is the
#: length the program folds of each window times the run's windows.
RANGES = {
    "at_the_start": (0, 700, [(1, 1_024)]),
    # The slice backs up from ``lo`` so that it ends with the window.
    "at_the_end": (3_500, 4_096, [(1, 1_024)]),
    "one_row": (4_000, 4_001, [(1, 1_024)]),
    "a_quarter_less_one": (100, 1_123, [(1, 1_024)]),
    "a_quarter": (100, 1_124, [(1, 1_024)]),
    "a_quarter_and_one": (100, 1_125, [(1, 2_048)]),
    "a_half_less_one": (1_000, 3_047, [(1, 2_048)]),
    "a_half": (1_000, 3_048, [(1, 2_048)]),
    "a_half_and_one": (1_000, 3_049, [(1, 4_096)]),
    "a_half_at_the_end": (2_048, 4_096, [(1, 2_048)]),
    # 1,808 live rows in a window padded to 4,096.
    "the_partial_last_window": (8_192, ROWS, [(1, 2_048)]),
    "inside_the_partial_last_window": (8_300, 9_000, [(1, 1_024)]),
    # 596 rows of one window and 1,500 of the next: ONE program, at the
    # longer window's length.
    "a_run_of_unequal_windows": (3_500, 5_596, [(2, 2 * 2_048)]),
    "a_run_of_short_windows": (3_800, 4_800, [(2, 2 * 1_024)]),
    # A full window in the run: the whole-window program.
    "a_run_with_a_full_window": (3_000, ROWS, [(3, 3 * 4_096)]),
    "the_whole_table": (0, ROWS, [(3, 3 * 4_096)]),
}


@pytest.mark.parametrize("name", RANGES)
@pytest.mark.parametrize("form", FORMS)
def test_a_sliced_fold_answers_as_the_whole_window_does(engines, form, name):
    lo, hi, dispatches = RANGES[name]
    got, folds = _run(engines[True], form, lo, hi)
    want, whole = _run(engines[False], form, lo, hi)
    assert got, "no group in range"
    _same(got, want, ulps=QUANTILE_COLUMNS.get(form, ()))
    assert {a["fold"] for a in folds} == {FORMS[form][3]}
    assert [(a["windows"], a["rows"]) for a in folds] == dispatches
    # Whole windows, counted the same way, and the same rows in range.
    assert [(a["windows"], a["rows"]) for a in whole] == [
        (w, w * WINDOW) for w, _rows in dispatches]
    assert [a["range_rows"] for a in folds] == [hi - lo] == [
        a["range_rows"] for a in whole]
    # One program a run of resident windows, however short its ranges.
    programs = {1: "fragment_update"}
    assert [a["program"] for a in folds] == [
        programs.get(w, "fragment_scan_fold") for w, _rows in dispatches]


@pytest.mark.parametrize("form", FORMS)
def test_an_empty_range_folds_nothing(engines, form):
    got, folds = _run(engines[True], form, 5_000, 5_000)
    want, _folds = _run(engines[False], form, 5_000, 5_000)
    assert got == want == [] and folds == []


@pytest.mark.parametrize("capacity,rows,length", [
    (1 << 21, 0, 1 << 19), (1 << 21, 1, 1 << 19),
    (1 << 21, 432_374, 1 << 19), (1 << 21, 1 << 19, 1 << 19),
    (1 << 21, (1 << 19) + 1, 1 << 20), (1 << 21, 582_178, 1 << 20),
    (1 << 21, 658_000, 1 << 20), (1 << 21, 790_568, 1 << 20),
    (1 << 21, 1 << 20, 1 << 20), (1 << 21, (1 << 20) + 1, 1 << 21),
    (1 << 21, 1_428_589, 1 << 21), (1 << 21, 1_603_704, 1 << 21),
    (1 << 21, 1 << 21, 1 << 21), (1_024, 256, 256), (1_024, 257, 512),
])
def test_the_length_is_the_least_of_three_that_holds_the_range(
        capacity, rows, length):
    assert _fold_rows(capacity, rows) == length
    assert {_fold_rows(capacity, r) for r in range(0, capacity + 1, 97)} == {
        capacity // 4, capacity // 2, capacity}


# -- the programs, by hand ------------------------------------------------------


def _frag_and_window(form):
    """The form's aggregate fragment as the engine compiles it, and the
    table's first resident window."""
    from pixie_tpu.exec import fragment

    eng = Engine(window_rows=WINDOW)
    eng.append_data("events", _events())
    seen, real = [], fragment.compile_fragment

    def spy(*args, **kwargs):
        frag = real(*args, **kwargs)
        if frag.is_agg:
            seen.append(frag)
        return frag

    fragment._FRAGMENT_CACHE.clear()
    fragment.compile_fragment = spy
    try:
        _run(eng, form, 0, 100)
    finally:
        fragment.compile_fragment = real
    win, _lo, _hi = next(eng.tables["events"].device_scan(window_rows=WINDOW))
    return seen[-1], win.cols


@pytest.mark.parametrize("form", ["dense_int", "keyed_remap", "keyed_any"])
def test_the_programs_cut_the_planes_they_are_handed(form):
    """``update`` with a ``RowSlice`` is ``update`` of the same rows by
    their place in the whole window (``keyed_remap``'s under its
    ``OperandProgram``); ``update_all`` cuts each window at its own
    start; an empty slice leaves the state as it was."""
    with routes_of("tpu"):
        frag, cols = _frag_and_window(form)
        i32 = np.int32
        whole = frag.update(frag.init_state(), cols, (i32(1_500), i32(2_300)))
        cut = frag.update(frag.init_state(), cols, (i32(100), i32(900)),
                          RowSlice(i32(1_400), 1_024))
        assert _tree_equal(_answer(frag, cut), _answer(frag, whole))
        both = frag.update_all(
            frag.init_state(), (cols, cols),
            np.array([1_500, 3_900], i32), np.array([2_300, 4_096], i32))
        runs = frag.update_all(
            frag.init_state(), (cols, cols),
            np.array([100, 828], i32), np.array([900, 1_024], i32),
            RowSlice(np.array([1_400, 3_072], i32), 1_024))
        assert _tree_equal(_answer(frag, runs), _answer(frag, both))
        empty = frag.update(frag.init_state(), cols, (i32(7), i32(7)),
                            RowSlice(i32(0), 1_024))
        assert not np.asarray(empty["valid"]).any()
        assert not bool(empty["overflow"])


def _answer(frag, state):
    cols, valid, overflow = jax.device_get(frag.finalize(state))
    assert not bool(overflow)
    order = np.lexsort([np.asarray(p) for ps in cols.values() for p in ps])
    live = order[np.asarray(valid)[order]]
    return {c: tuple(np.asarray(p)[live] for p in ps)
            for c, ps in cols.items()}


def _tree_equal(a, b) -> bool:
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    return ta == tb and all(
        np.array_equal(np.asarray(x), np.asarray(y), equal_nan=True)
        for x, y in zip(la, lb))


def test_a_slice_is_part_of_the_programs_structure_and_its_start_is_not():
    a = RowSlice(jnp.int32(5), 1_024)
    b = RowSlice(jnp.int32(900), 1_024)
    c = RowSlice(jnp.int32(5), 2_048)
    ta, tb, tc = (jax.tree_util.tree_structure(x) for x in (a, b, c))
    assert ta == tb and hash(ta) == hash(tb) and ta != tc
    assert jax.tree_util.tree_leaves(b) == [jnp.int32(900)]
    back = jax.tree_util.tree_unflatten(tc, [jnp.int32(1)])
    assert (int(back.start), back.rows) == (1, 2_048)


# -- what compiles ------------------------------------------------------------


def _compiles():
    return default_program_registry().stats()["compiles"]


def _fold_programs():
    return {
        (r["program_id"], r["kind"])
        for r in default_program_registry().programz()["programs"]
        if r["kind"] in ("fragment_update", "fragment_scan_fold")
    }


def test_a_range_that_moves_inside_one_length_compiles_nothing(engines):
    eng = engines[True]
    _run(eng, "dense_int", 200, 900)
    _run(eng, "dense_int", 3_900, 4_600)
    before = _compiles()
    for lo, hi in [(0, 1), (150, 1_100), (3_072, 4_096), (3_500, 4_096),
                   (5_000, 5_700), (8_300, 9_000)]:
        _rows, folds = _run(eng, "dense_int", lo, hi)
        assert [a["rows"] for a in folds] == [1_024]
    for lo, hi in [(4_000, 4_200), (3_100, 4_900), (8_100, 8_900)]:
        _rows, folds = _run(eng, "dense_int", lo, hi)
        assert [a["rows"] for a in folds] == [2 * 1_024]
    assert _compiles() == before


def test_a_program_is_compiled_at_no_more_than_three_lengths():
    """Forty ranges of every length and place over a fresh table's
    windows: ``update`` and ``update_all`` (of two and of three windows)
    each compile a quarter, a half and the whole, and nothing else."""
    eng = Engine(window_rows=WINDOW)
    data = _events()
    data["svc"] = [s + "-b" for s in data["svc"]]  # programs of its own
    eng.append_data("events", data)
    before = _fold_programs()
    rng = np.random.default_rng(3)
    for _ in range(40):
        lo = int(rng.integers(0, ROWS - 1))
        hi = int(min(ROWS, lo + 1 + rng.integers(0, 3) * 1_500
                     + rng.integers(0, 1_500)))
        _run(eng, "dense_int", lo, hi)
    for lo, hi in [(10, 20), (10, 2_000), (10, 4_000), (4_000, 4_200),
                   (3_000, 5_500), (3_000, 8_000), (3_900, 8_300),
                   (2_500, 9_900), (0, ROWS)]:
        _run(eng, "dense_int", lo, hi)
    new = sorted(kind for _pid, kind in _fold_programs() - before)
    # ``update_all``: a length a count of windows (two: three lengths;
    # three hold a full window: the whole).
    assert new == ["fragment_scan_fold"] * 4 + ["fragment_update"] * 3


# -- who is not sliced ----------------------------------------------------------


def test_a_staged_window_is_a_mask_and_is_not_sliced(engines):
    """With residency off the windows reach the fold staged, under a
    mask: the whole-window program, and no ``rows`` on its span."""
    got, folds = _run(engines[True], "dense_int", 100, 900,
                      flags=("device_residency", False))
    want, _folds = _run(engines[False], "dense_int", 100, 900)
    _same(got, want)
    assert folds and not any("rows" in a or "range_rows" in a for a in folds)


def test_the_mesh_step_folds_whole_windows_and_says_so():
    """A ``DistributedEngine``'s windows are row-sharded: its step is
    handed every one whole, and its spans carry the two counts."""
    from pixie_tpu.parallel.executor import DistributedEngine
    from pixie_tpu.parallel.mesh import agent_mesh

    dist = DistributedEngine(window_rows=WINDOW, mesh=agent_mesh(4))
    assert dist.slice_windows is False
    dist.append_data("events", _events())
    one = Engine(window_rows=WINDOW)
    one.append_data("events", _events())
    got, folds = _run(dist, "dense_int", 3_500, 5_596)
    want, _folds = _run(one, "dense_int", 3_500, 5_596)
    _same(got, want)
    assert [(a["program"], a["rows"], a["range_rows"]) for a in folds] == [
        ("mesh_agg_step", WINDOW, 596), ("mesh_agg_step", WINDOW, 1_500)]
