"""A join against a unique build is a lookup (ISSUE 40): an inner / left
``JoinOp`` whose build side is unique on ONE dense key (a dictionary's
codes, or integers in a range within ``int_dense_domain_limit``) reads a
table by key code at the probe's length on the host (``host_table``),
at any size and ahead of every bulk strategy. Whatever the input does
not show to be such a table takes the route it took before, with the
same rows. Each case holds the answer (against the host hash join and
the single-shot kernel) AND the route."""

import numpy as np
import pytest
from conftest import routes_of

from pixie_tpu.config import override_flag
from pixie_tpu.exec import joins
from pixie_tpu.exec.engine import Engine
from pixie_tpu.exec.plan import JoinOp
from pixie_tpu.types.batch import HostBatch
from pixie_tpu.types.dtypes import DataType
from pixie_tpu.types.relation import Relation
from pixie_tpu.types.strings import StringDictionary

SEED = 4_000_000_007  # the driver's seeds pass 2**31
HOWS = ("inner", "left")


def _strings(col: str, ids, dictionary, **values) -> HostBatch:
    """A batch whose ``col`` is STRING ids of ``dictionary`` as given
    (-1 a null), beside INT64 value columns."""
    rel = Relation([(col, DataType.STRING)]
                   + [(c, DataType.INT64) for c in values])
    return HostBatch.from_pydict(
        {col: np.asarray(ids, dtype=np.int32),
         **{c: np.asarray(v, dtype=np.int64) for c, v in values.items()}},
        relation=rel, dicts={col: dictionary})


def _ints(**cols) -> HostBatch:
    return HostBatch.from_pydict(
        {c: np.asarray(v, dtype=np.int64) for c, v in cols.items()},
        time_cols=())


def _rows(hb: HostBatch) -> list:
    """The batch as a sorted multiset of rows (strings decoded, so two
    sides' dictionaries may differ)."""
    cols = [np.asarray(v).tolist() for v in hb.to_pydict().values()]
    return sorted(zip(*cols), key=repr)


def _dispatch(left, right, op, strategy="auto"):
    eng = Engine()
    with override_flag("join_strategy", strategy):
        out = joins._join_dispatch(left, right, op, eng)
    return out, eng.last_join_decision


def _references(left, right, op, kernel=True) -> list:
    """The same join by the host hash join and, where asked, by the
    single-shot kernel: the rows every route has to give."""
    want = _rows(joins._join_host_nm(left, right, op))
    if kernel:
        assert _rows(joins._join_device(left, right, op, Engine())) == want
    return want


def _op(how, on=("k",)) -> JoinOp:
    return JoinOp(how=how, left_on=tuple(on), right_on=tuple(on))


# -- the sides -----------------------------------------------------------------


def _pods(n=12) -> StringDictionary:
    return StringDictionary([f"ns/pod-{i}" for i in range(n)])


def one_dictionary():
    """Both sides are codes of ONE dictionary (``px/perf_flamegraph``'s
    ``pod``); pods 9-11 have no build row: unmatched probe rows."""
    d = _pods()
    rng = np.random.default_rng(SEED)
    left = _strings("k", rng.integers(0, 12, 300), d, v=np.arange(300))
    right = _strings("k", rng.permutation(9), d, w=np.arange(9) * 7)
    return left, right, 12 + 1


def every_row_matches():
    d = _pods(6)
    left = _strings("k", np.arange(60) % 6, d, v=np.arange(60))
    right = _strings("k", np.arange(6)[::-1], d, w=np.arange(6) + 100)
    return left, right, 6 + 1


def two_dictionaries():
    """The sides' dictionaries differ in content and order
    (``px/net_flow_graph``'s address columns): ``_align_join_dicts``
    builds their union, whose ids the table is in. Some probe strings
    the build never saw, some build strings the probe never did."""
    lstr = [f"10.0.0.{i}" for i in range(10)]
    rstr = [f"10.0.0.{i}" for i in (7, 3, 12, 5, 1, 14, 9)]
    rng = np.random.default_rng(SEED + 1)
    left = _strings("k", rng.integers(0, 10, 200), StringDictionary(lstr),
                    v=np.arange(200))
    right = _strings("k", np.arange(7), StringDictionary(rstr),
                     w=np.arange(7) + 50)
    return left, right, 12 + 1  # the union: 10 + {12, 14}, and the null


def null_on_the_probe():
    left, right, dom = one_dictionary()
    ids = left.cols["k"][0].copy()
    ids[::7] = -1
    return _strings("k", ids, left.dicts["k"], v=left.cols["v"][0]), right, dom


def null_on_the_build():
    left, right, dom = one_dictionary()
    ids = right.cols["k"][0].copy()
    ids[2] = -1
    return left, _strings("k", ids, right.dicts["k"],
                          w=right.cols["w"][0]), dom


def null_on_both():
    """An id is compared as every other route compares it: a null joins
    the build side's one null row."""
    left, _right, dom = null_on_the_probe()
    _left, right, _dom = null_on_the_build()
    # One dictionary object again.
    right = _strings("k", right.cols["k"][0], left.dicts["k"],
                     w=right.cols["w"][0])
    return left, right, dom


def two_nulls_on_the_build():
    """Two null build rows are a duplicate key: N:M, not a table."""
    left, right, _dom = null_on_both()
    ids = right.cols["k"][0].copy()
    ids[5] = -1
    return left, _strings("k", ids, left.dicts["k"],
                          w=right.cols["w"][0]), None


def int_inside_the_limit():
    """An INT64 key whose build range is narrow; probe keys run past it
    on both ends."""
    rng = np.random.default_rng(SEED + 2)
    left = _ints(k=rng.integers(900, 1200, 400), v=np.arange(400))
    right = _ints(k=1000 + rng.permutation(128)[:100], w=np.arange(100))
    return left, right, int(right.cols["k"][0].max()
                            - right.cols["k"][0].min()) + 1


def int_far_probe_keys():
    """Probe keys at the ends of INT64: the range check may not wrap."""
    left = _ints(k=[np.iinfo(np.int64).min, 5, 7, np.iinfo(np.int64).max, 6],
                 v=np.arange(5))
    right = _ints(k=[7, 5, 9], w=[70, 50, 90])
    return left, right, 5


def int_past_the_limit():
    """Two build keys 2^40 apart: no dense domain."""
    left = _ints(k=[1, 1 << 40, 3, 1], v=np.arange(4))
    right = _ints(k=[1, 1 << 40], w=[10, 20])
    return left, right, None


def time_key():
    rel = Relation([("k", DataType.TIME64NS), ("v", DataType.INT64)])
    t0 = 1_700_000_000_000_000_000
    left = HostBatch.from_pydict(
        {"k": t0 + np.arange(50, dtype=np.int64) % 10, "v": np.arange(50)},
        relation=rel)
    right = HostBatch.from_pydict(
        {"k": t0 + np.arange(8, dtype=np.int64), "v": np.arange(8) * 3},
        relation=rel)
    return left, right, 8


def one_duplicate_key():
    """ONE build key twice (N:M): the routes of before, the same rows."""
    left, right, _dom = one_dictionary()
    ids = right.cols["k"][0].copy()
    ids[1] = ids[0]
    return left, _strings("k", ids, left.dicts["k"],
                          w=right.cols["w"][0]), None


def float_key():
    left = HostBatch.from_pydict({"k": np.array([1.0, 2.0, 2.5, 1.0]),
                                  "v": np.arange(4)}, time_cols=())
    right = HostBatch.from_pydict({"k": np.array([2.5, 1.0]),
                                   "w": np.array([25, 10])}, time_cols=())
    return left, right, None


TABLES = [one_dictionary, every_row_matches, two_dictionaries,
          null_on_the_probe, null_on_the_build, null_on_both,
          int_inside_the_limit, int_far_probe_keys, time_key]
#: (sides, the route under ``auto`` on the CPU's routes below the row
#: limit of the dict join)
NOT_TABLES = [(two_nulls_on_the_build, "host_hash"),
              (int_past_the_limit, "host_dict"),
              (one_duplicate_key, "host_hash"),
              (float_key, "host_dict")]


@pytest.mark.parametrize("how", HOWS)
@pytest.mark.parametrize("sides", TABLES, ids=lambda f: f.__name__)
def test_a_unique_dense_build_is_looked_up(sides, how):
    left, right, dom = sides()
    out, decision = _dispatch(left, right, _op(how))
    assert (decision.strategy, decision.domain) == ("host_table", dom)
    assert decision.reason == "unique dense build"
    assert _rows(out) == _references(left, right, _op(how))
    # Probe order, one row a probe row at most.
    if how == "left":
        assert out.length == left.length
        assert np.array_equal(out.cols["v"][0], left.cols["v"][0])
    else:
        assert np.all(np.diff(out.cols["v"][0]) > 0)


@pytest.mark.parametrize("how", HOWS)
@pytest.mark.parametrize("sides,route", NOT_TABLES,
                         ids=lambda a: getattr(a, "__name__", a))
def test_what_is_not_a_table_takes_the_route_it_took(sides, route, how):
    left, right, _dom = sides()
    # A duplicate key sends the dict join on to the bulk route, the
    # CPU's here; a key with no dense codes stays the small dict join's.
    out, decision = _dispatch(left, right, _op(how))
    assert (decision.strategy, decision.domain) == (route, 0)
    assert _rows(out) == _references(left, right, _op(how))


@pytest.mark.parametrize("how", HOWS)
def test_every_row_matched_passes_the_left_planes_through(how):
    left, right, _dom = every_row_matches()
    out, _decision = _dispatch(left, right, _op(how))
    assert out.cols["k"][0] is left.cols["k"][0]
    assert out.cols["v"][0] is left.cols["v"][0]
    assert out.cols["w"][0].tolist() == [100 + 5 - i % 6 for i in range(60)]


@pytest.mark.parametrize("how", HOWS)
def test_a_two_column_key_is_the_dict_joins(how):
    rng = np.random.default_rng(SEED + 3)
    left = _ints(a=rng.integers(0, 6, 90), b=rng.integers(0, 4, 90),
                 v=np.arange(90))
    pairs = rng.permutation(24)[:20]
    right = _ints(a=pairs // 4, b=pairs % 4, w=np.arange(20))
    op = _op(how, on=("a", "b"))
    out, decision = _dispatch(left, right, op)
    assert decision.strategy == "host_dict"
    assert _rows(out) == _references(left, right, op)


@pytest.mark.parametrize("how", HOWS)
def test_a_two_plane_key_is_the_dict_joins(how):
    """UINT128 (a upid) is two planes of one column."""
    def upids(lo):
        lo = np.asarray(lo, dtype=np.uint64)
        return np.stack([np.ones(len(lo), np.uint64), lo], axis=1)

    rng = np.random.default_rng(SEED + 4)
    left = HostBatch.from_pydict({"k": upids(rng.integers(0, 9, 70)),
                                  "v": np.arange(70)}, time_cols=())
    right = HostBatch.from_pydict({"k": upids(rng.permutation(9)[:7]),
                                   "w": np.arange(7)}, time_cols=())
    out, decision = _dispatch(left, right, _op(how))
    assert decision.strategy == "host_dict"
    got = sorted(zip(out.cols["v"][0].tolist(), out.cols["w"][0].tolist()))
    by_key = {int(k): int(w) for k, w in zip(right.cols["k"][1],
                                             right.cols["w"][0])}
    want = sorted(
        (int(v), by_key.get(int(k), 0))
        for k, v in zip(left.cols["k"][1], left.cols["v"][0])
        if how == "left" or int(k) in by_key)
    assert got == want


@pytest.mark.parametrize("forced,small,bulk", [
    ("host", "host_dict", "host_hash"),
    ("single", "host_dict", "single"),
    ("sorted", "host_dict", "sorted"),
    ("radix", "host_dict", "radix"),
])
def test_a_forced_strategy_is_obeyed(forced, small, bulk, monkeypatch):
    """``join_strategy`` other than ``auto`` routes as before the table
    was there: the dict join under the row limit, the forced route over
    it (probe windows of 64 rows make ``sorted`` / ``radix`` windowed)."""
    left, right, _dom = int_inside_the_limit()
    want = _references(left, right, _op("inner"), kernel=False)
    out, decision = _dispatch(left, right, _op("inner"), forced)
    assert decision.strategy == small and _rows(out) == want
    monkeypatch.setattr(joins, "DEVICE_JOIN_MIN_ROWS", 0)
    with override_flag("join_probe_window_rows", 64):
        out, decision = _dispatch(left, right, _op("inner"), forced)
    assert decision.strategy == bulk and _rows(out) == want


@pytest.mark.parametrize("how", HOWS)
@pytest.mark.parametrize("platform", ["cpu", "tpu"])
def test_a_probe_over_the_dict_joins_row_limit_is_looked_up(platform, how):
    """70 k probe rows against 4,096 (the benchmark's shape, and over
    ``DEVICE_JOIN_MIN_ROWS``): the same lookup on either platform's
    routes, where the bulk route was the host hash join / the kernel."""
    d = StringDictionary([f"ns/pod-{i}" for i in range(4096)])
    rng = np.random.default_rng(SEED + 5)
    n = 70_000
    assert n > joins.DEVICE_JOIN_MIN_ROWS
    left = _strings("k", rng.integers(0, 4096, n), d, v=np.arange(n))
    right = _strings("k", rng.permutation(4096)[:4000], d,
                     w=np.arange(4000))
    with routes_of(platform):
        out, decision = _dispatch(left, right, _op(how))
        assert (decision.strategy, decision.domain) == ("host_table", 4097)
        with override_flag("join_strategy", "host"):
            want = joins._join_host_nm(left, right, _op(how))
    assert _rows(out) == _rows(want)
    assert out.length == (n if how == "left" else int(np.isin(
        left.cols["k"][0], right.cols["k"][0]).sum()))


def test_the_rule_of_a_table_is_stated_once():
    """``_unique_dense_build`` is the one place that says what a dense
    unique build side is; the host lookup and the fused lookup's build
    both ask it (and differ in whether a null id is a key)."""
    kb = np.array([3, -1, 0], dtype=np.int32)
    lo, dom, row_of = joins._unique_dense_build(kb, DataType.STRING, 4, True)
    assert (lo, dom, row_of.tolist()) == (-1, 5, [1, 2, -1, -1, 0])
    assert row_of.dtype == np.int32
    lo, dom, row_of = joins._unique_dense_build(kb, DataType.STRING, 4, False)
    assert (lo, dom, row_of.tolist()) == (0, 5, [2, -1, -1, 0, -1])
    dup = np.array([3, 0, 3], dtype=np.int32)
    assert joins._unique_dense_build(dup, DataType.STRING, 4, True) is None
    ints = np.array([10, 12, 11], dtype=np.int64)
    assert joins._unique_dense_build(ints, DataType.INT64, None, True)[:2] == (
        10, 3)
    with override_flag("int_dense_domain_limit", 2):
        assert joins._unique_dense_build(
            ints, DataType.INT64, None, True) is None
    assert joins._unique_dense_build(
        np.array([0.5]), DataType.FLOAT64, None, True) is None
    import inspect

    for user in (joins._join_host_table, joins._host_table_build):
        src = inspect.getsource(user)
        assert "_unique_dense_build(" in src
        assert "int_dense_domain_limit" not in src


def test_the_fused_lookups_build_reads_the_same_table():
    """``_host_table_build`` (the fused path's) from the shared rule: a
    null build key is left out, a duplicate is not a table."""
    d = _pods(5)
    right = _strings("k", [3, -1, 0], d, w=[30, 99, 10])
    op = _op("inner")
    lo, dom, found, tables, _rel = joins._host_table_build(
        right, op, DataType.STRING, {"k": d}, "k", "k")
    assert (lo, dom) == (0, 6)
    assert found.tolist() == [True, False, False, True, False, False]
    assert tables["w"][0].tolist() == [10, 0, 0, 30, 0, 0]
    dup = _strings("k", [3, 3, 0], d, w=[30, 99, 10])
    assert joins._host_table_build(
        dup, op, DataType.STRING, {"k": d}, "k", "k") is None
