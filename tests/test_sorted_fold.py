"""The keyed fold as a payload-carrying sort (``ops/groupby.py``
``sorted_group_fold`` and the route ``exec/fragment.py`` gives it: a
non-dense key, every aggregate an exact integer statistic, the TPU's
routes). On the CPU under ``routes_of("tpu")`` and by calling the
function: against numpy over one to three key planes, masked rows, empty
windows, wrapping sums and negative extremes; against the id form
(``dense_group_ids`` + ``uda.update`` + ``regroup_pair`` +
``scatter_carry``) on the same input; over one, three and seven windows;
over a capacity the groups overflow; and through broker, PEMs with
dictionaries of their own and the Kelvin, whose merge sees keys in no
order."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import pixie_tpu  # noqa: F401  (x64 on)
from conftest import routes_of
from pixie_tpu.config import override_flag
from pixie_tpu.exec.engine import Engine
from pixie_tpu.exec.fragment import compile_fragment
from pixie_tpu.exec.plan import AggExpr, AggOp, ColumnRef
from pixie_tpu.ops import routes
from pixie_tpu.ops.groupby import (
    _to_bits, join_u32, sorted_group_fold, split_u32,
)
from pixie_tpu.ops.routes import sorted_fold_ride
from pixie_tpu.types.dtypes import DataType
from pixie_tpu.types.relation import Relation
from pixie_tpu.types.strings import NULL_ID, StringDictionary
from pixie_tpu.udf.builtins.math_sketches import QUANTILE_FIELDS
from pixie_tpu.udf.registry import default_registry

I64 = np.iinfo(np.int64)


# -- the function, against numpy ------------------------------------------------

def _key_planes(kind, n, rng, distinct):
    """One to three key planes of ``distinct``-ish values each."""
    ids = rng.integers(NULL_ID, max(distinct - 1, 0), n).astype(np.int32)
    planes = [ids]
    if kind in ("ids_int64", "ids_int64_float"):
        planes.append(
            rng.integers(-2, 2, n).astype(np.int64) * (1 << 40) + 7
        )
    if kind == "ids_int64_float":
        planes.append(rng.choice(
            np.array([np.nan, -0.0, 0.0, 1.5, -np.inf], np.float32), n
        ))
    return planes


def _canonical(planes, i):
    """Row i's key as python values that compare the way groups do:
    bit-identical NaNs are one key, -0.0 is 0.0."""
    out = []
    for p in planes:
        v = p[i]
        if p.dtype.kind == "f":
            out.append("nan" if np.isnan(v) else float(v) + 0.0)
        else:
            out.append(int(v))
    return tuple(out)


SHAPES = ["random_masked", "empty_window", "one_group", "own_group",
          "wrapping_sums", "negative_extremes"]


def _case(kind, shape, seed):
    rng = np.random.default_rng(seed)
    n = 257
    planes = _key_planes(kind, n, rng, distinct=1 if shape == "one_group" else 6)
    valid = rng.random(n) < 0.8
    a = rng.integers(-(1 << 40), 1 << 40, n).astype(np.int64)
    b = rng.integers(-50, 50, n).astype(np.int64)
    if shape == "empty_window":
        valid[:] = False
    elif shape == "one_group":
        planes = [np.full_like(p, p[0]) for p in planes]
    elif shape == "own_group":
        planes[0] = np.arange(n, dtype=np.int32) - 1  # NULL_ID among them
    elif shape == "wrapping_sums":
        a = rng.choice(np.array([I64.max, I64.max - 1, I64.min], np.int64), n)
    elif shape == "negative_extremes":
        a = -np.abs(a) - 1
        b = rng.choice(np.array([I64.min, I64.min + 1, -1], np.int64), n)
    return planes, valid, a, b


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("kind", ["ids", "ids_int64", "ids_int64_float"])
def test_fold_equals_numpy(kind, shape):
    planes, valid, a, b = _case(kind, shape, seed=len(kind) * 31 + len(shape))
    g = 512
    words, widths = [], []
    for p in planes:
        w = split_u32(_to_bits(jnp.asarray(p)))
        words += w
        widths.append(len(w))
    ja, jb = jnp.asarray(a), jnp.asarray(b)
    # sum(a), sum(b); max(a) (a sum plane too), min(b) as max(~b), max(b).
    keys_g, valid_g, rows, sums, maxes, n_groups = jax.jit(
        lambda words, valid, ja, jb: sorted_group_fold(
            words, valid, [ja, jb], [ja, ~jb, jb], g)
    )(words, jnp.asarray(valid), ja, jb)
    want = {}
    for i in np.flatnonzero(valid):
        want.setdefault(_canonical(planes, i), []).append(i)
    assert int(n_groups) == len(want)
    valid_g = np.asarray(valid_g)
    assert valid_g.sum() == len(want) and valid_g[: len(want)].all()
    got_planes, at = [], 0
    for p, k in zip(planes, widths):
        got_planes.append(np.asarray(join_u32(keys_g[at:at + k], p.dtype)))
        at += k
    rows, sums, maxes = jax.device_get((rows, sums, maxes))
    seen = set()
    for s in np.flatnonzero(valid_g):
        key = _canonical(got_planes, s)
        idx = want[key]
        seen.add(key)
        assert rows[s] == len(idx)
        with np.errstate(over="ignore"):
            assert sums[0][s] == a[idx].sum() and sums[1][s] == b[idx].sum()
        assert maxes[0][s] == a[idx].max()
        assert ~maxes[1][s] == b[idx].min()
        assert maxes[2][s] == b[idx].max()
    assert len(seen) == len(want)
    empty = ~valid_g
    assert (rows[empty] == 0).all() and (sums[0][empty] == 0).all()
    assert (maxes[0][empty] == I64.min).all()


@pytest.mark.parametrize("n,g", [(4_096, 512), (1_024, 512)],
                         ids=["window", "merge"])
@pytest.mark.parametrize("order", ["id_first", "id_second", "id_alone"])
def test_a_maximum_of_dictionary_ids_is_one_word(order, n, g):
    """``any`` of a string: an int32 plane among ``maxes`` rides as ONE
    order word (NULL_ID and INT32_MIN among the ids), as the sort's last
    key when it is the first maximum and by a sort of its own otherwise,
    beside a sum; at window length (n >= 4 g) and as a merge (n = 2 g).
    Bit for bit numpy's; empty slots read INT32_MIN."""
    rng = np.random.default_rng(n + len(order))
    key = rng.integers(0, 120, n).astype(np.int32)
    big = rng.integers(0, 3, n).astype(np.int64) * (1 << 40)
    valid = rng.random(n) < 0.9
    ids = rng.choice(np.array(
        [NULL_ID, -(1 << 31), 0, 1, 65_536, (1 << 31) - 1], np.int32), n)
    a = rng.integers(-(1 << 40), 1 << 40, n).astype(np.int64)
    words = [w for p in (key, big)
             for w in split_u32(_to_bits(jnp.asarray(p)))]
    maxes = {"id_first": [ids, a], "id_second": [a, ids],
             "id_alone": [ids]}[order]
    sorts = _sort_eqns(jax.make_jaxpr(lambda w, v, s, m: sorted_group_fold(
        w, v, [s], m, g))(words, jnp.asarray(valid), jnp.asarray(a),
                          [jnp.asarray(m) for m in maxes]))
    # The key sort: the flag, three key words, the first maximum's words.
    assert sorts[0][1] == 4 + (2 if order == "id_second" else 1)
    _k, valid_g, rows, sums, maxes_g, n_groups = jax.jit(
        lambda w, v, s, m: sorted_group_fold(w, v, [s], m, g)
    )(words, jnp.asarray(valid), jnp.asarray(a),
      [jnp.asarray(m) for m in maxes])
    live = np.flatnonzero(valid)
    order_np = live[np.lexsort((big[live], key[live]))]
    k2 = np.stack([key[order_np], big[order_np]], 1)
    starts = np.flatnonzero(
        np.r_[True, (k2[1:] != k2[:-1]).any(1)]) if len(k2) else []
    assert int(n_groups) == len(starts)
    valid_g = np.asarray(valid_g)
    for got, plane in zip(maxes_g, maxes):
        got = np.asarray(got)
        assert got.dtype == plane.dtype
        want = np.maximum.reduceat(plane[order_np], starts)
        assert (got[: len(starts)] == want).all()
        assert (got[~valid_g] == np.iinfo(plane.dtype).min).all()
    assert (np.asarray(sums[0])[: len(starts)]
            == np.add.reduceat(a[order_np], starts)).all()
    assert (np.asarray(rows)[: len(starts)]
            == np.diff(np.r_[starts, len(order_np)])).all()


@pytest.mark.parametrize("rows_valid", ["some", "none"])
def test_a_packed_code_needs_no_flag_operand(rows_valid):
    """``folded_flag``: a first key word that is never 0xFFFFFFFF on a
    valid row carries "not valid" itself."""
    rng = np.random.default_rng(5)
    n, g = 300, 64
    code = rng.integers(0, 40, n).astype(np.uint32)
    valid = (rng.random(n) < 0.7) & (rows_valid == "some")
    v = rng.integers(-9, 9, n).astype(np.int64)
    (codes,), valid_g, rows, (s,), (mx,), n_groups = sorted_group_fold(
        [jnp.asarray(code)], jnp.asarray(valid), [jnp.asarray(v)],
        [jnp.asarray(v)], g, folded_flag=True,
    )
    live = np.unique(code[valid])
    assert int(n_groups) == len(live)
    k = len(live)
    assert np.array_equal(np.asarray(codes)[:k], live)
    assert np.array_equal(
        np.asarray(rows)[:k], [np.sum(valid & (code == c)) for c in live])
    assert np.array_equal(
        np.asarray(s)[:k], [v[valid & (code == c)].sum() for c in live])
    assert np.array_equal(
        np.asarray(mx)[:k], [v[valid & (code == c)].max() for c in live])
    assert not np.asarray(valid_g)[k:].any()


def test_more_groups_than_slots_is_reported():
    """n_groups counts every group; the first g in key order stand."""
    n, g = 100, 16
    code = np.arange(n, dtype=np.uint32)[::-1].copy()
    v = np.arange(n, dtype=np.int64)
    (codes,), valid_g, rows, (s,), _mx, n_groups = sorted_group_fold(
        [jnp.asarray(code)], jnp.ones(n, jnp.bool_), [jnp.asarray(v)], [], g,
        folded_flag=True,
    )
    assert int(n_groups) == n > g
    assert np.asarray(valid_g).all()
    assert np.array_equal(np.asarray(codes), np.arange(g))
    assert np.array_equal(np.asarray(s), n - 1 - np.arange(g))
    assert (np.asarray(rows) == 1).all()


# -- a window's sums ride the key sort (PR 35) ----------------------------------

def _sort_eqns(jaxpr):
    """Every ``sort`` equation of a jaxpr, nested ones too, in order:
    (operand shapes, num_keys, dimension, the primitives that made each
    operand)."""
    made, out = {}, []

    def walk(jp):
        for e in jp.eqns:
            for v in e.outvars:
                made[id(v)] = e.primitive.name
            for sub in jax.core.jaxprs_in_params(e.params):
                walk(sub)
            if e.primitive.name == "sort":
                out.append((
                    [tuple(v.aval.shape) for v in e.invars],
                    e.params["num_keys"], e.params["dimension"],
                    [made.get(id(v)) for v in e.invars],
                ))

    walk(jaxpr.jaxpr)
    return out


def _ride_case(keys, planes, shape, n, g, seed):
    """(key words, valid, sum planes) of an n-row window: ``packed`` is
    one u32 code, ``lead_id`` a dictionary id + 1 and an INT64's two
    words, as ``exec/fragment.py`` hands a packed and an unpacked key
    (``folded_flag`` both)."""
    rng = np.random.default_rng(seed)
    distinct = 3 * g if shape == "overflow" else max(g // 2, 1)
    label = rng.integers(0, distinct, n)
    if keys == "packed":
        words = [label.astype(np.uint32)]
    else:
        second = (label % 7).astype(np.int64) * (1 << 33) - (1 << 34)
        words = [(label // 7).astype(np.uint32)] + [
            np.asarray(w) for w in split_u32(_to_bits(jnp.asarray(second)))]
    valid = rng.random(n) < 0.75
    vals = [rng.integers(-(1 << 50), 1 << 50, n).astype(np.int64)
            for _ in range(planes)]
    if shape == "int64_ends":
        vals = [rng.choice(np.array(
            [I64.max, I64.max - 1, I64.min, I64.min + 1, -1, 1], np.int64), n)
            for _ in range(planes)]
    if shape == "junk_invalid":  # what a masked row holds must not count
        for v in vals:
            v[~valid] = rng.choice(
                np.array([I64.max, I64.min, 12345], np.int64), (~valid).sum())
    return words, valid, vals


def _assert_window_is_numpys(got, words, valid, vals, g):
    """Keys, counts and wrapping INT64 sums of the first g groups in key
    order, bit for bit; empty slots zero."""
    keys_g, valid_g, rows, sums, _maxes, n_groups = jax.device_get(got)
    order = np.lexsort([w[valid] for w in words[::-1]])
    live, first, count = np.unique(
        np.stack([w[valid][order] for w in words]), axis=1,
        return_index=True, return_counts=True)
    k = min(live.shape[1], g)
    assert int(n_groups) == live.shape[1]
    assert valid_g[:k].all() and not valid_g[k:].any()
    for got_w, want_w in zip(keys_g, live):
        assert np.array_equal(got_w[:k], want_w[:k])
    assert np.array_equal(rows[:k], count[:k]) and not rows[k:].any()
    for got_s, v in zip(sums, vals):
        with np.errstate(over="ignore"):
            want = np.add.reduceat(v[valid][order], first)
        assert np.array_equal(got_s[:k], want[:k]) and not got_s[k:].any()


def _fold_no_max(g):
    def fold(words, valid, vals):
        return sorted_group_fold(words, valid, vals, [], g, folded_flag=True)
    return fold


def _assert_sorts_are(sorts, way, n, n_words, planes):
    """The window program's sorts, by operand count: on the payload way
    the one key sort carries the sums' words and there is no row index, no
    inverse sort and no batched [P, N] sort; on the index way those three.
    Last ``_front``'s one-operand sort of ``pos``."""
    counts = [len(shapes) for shapes, _k, _d, _m in sorts]
    assert counts == {
        "payload": [n_words + 2 * planes, 1],
        "index": [n_words + 1, 2, 2, 1],  # keys + iota, inverse, batched
    }[way]
    shapes, num_keys, dimension, made = sorts[0]
    assert shapes == [(n,)] * counts[0]
    assert (num_keys, dimension) == (n_words, 0)
    assert ("iota" in made) == (way == "index")
    batched = [shapes[0] for shapes, _k, d, _m in sorts if d == 1]
    assert batched == ([(2 * planes, n)] if way == "index" else [])


@pytest.mark.parametrize("shape", ["junk_invalid", "overflow", "int64_ends"])
@pytest.mark.parametrize("planes", [1, 2])
@pytest.mark.parametrize("keys", ["packed", "lead_id"])
def test_a_windows_sums_with_no_max_ride_the_key_sort(keys, planes, shape):
    """Sums and NO maximum at window length (N >= 4 g), as ``px/sql_stats``
    (three unpacked key words, one sum) and ``px/net_flow_graph`` (one
    packed word, two sums) fold: numpy's keys, counts and wrapping INT64
    sums, the sum words operands of the one sort. Three key words and two
    sums are seven operands, past the limit: the same answer by the row
    index."""
    n, g = 4_096, 256
    words, valid, vals = _ride_case(keys, planes, shape, n, g,
                                    seed=len(keys) + 7 * planes)
    way = "index" if (keys, planes) == ("lead_id", 2) else "payload"
    assert sorted_fold_ride(n, g, len(words), planes) == way
    fold = _fold_no_max(g)
    args = ([jnp.asarray(w) for w in words], jnp.asarray(valid),
            [jnp.asarray(v) for v in vals])
    _assert_window_is_numpys(jax.jit(fold)(*args), words, valid, vals, g)
    _assert_sorts_are(_sort_eqns(jax.make_jaxpr(fold)(*args)), way, n,
                      len(words), planes)


@pytest.mark.parametrize("case", ["at_the_limit", "a_plane_more", "no_room"])
def test_each_side_of_the_operand_limit(case):
    """Key words and sum words within ``SORT_PAYLOAD_MAX_OPERANDS`` ride
    one sort; a plane more than fits, or key words that leave no room for
    one, take the index way. One answer, numpy's, whichever."""
    limit = routes.SORT_PAYLOAD_MAX_OPERANDS
    n_words, planes, way = {
        "at_the_limit": (limit - 4, 2, "payload"),
        "a_plane_more": (limit - 4, 3, "index"),
        "no_room": (limit - 1, 1, "index"),
    }[case]
    n, g = 2_048, 128
    rng = np.random.default_rng(n_words)
    label = rng.integers(0, 90, n)
    # Word i holds digit i of the label (base 3), the last the rest.
    words = [(label // 3 ** i % 3).astype(np.uint32) for i in range(n_words - 1)]
    words.append((label // 3 ** (n_words - 1)).astype(np.uint32))
    valid = rng.random(n) < 0.8
    vals = [rng.integers(I64.min, I64.max, n).astype(np.int64)
            for _ in range(planes)]
    assert sorted_fold_ride(n, g, n_words, planes) == way
    fold = _fold_no_max(g)
    args = ([jnp.asarray(w) for w in words], jnp.asarray(valid),
            [jnp.asarray(v) for v in vals])
    _assert_window_is_numpys(jax.jit(fold)(*args), words, valid, vals, g)
    _assert_sorts_are(_sort_eqns(jax.make_jaxpr(fold)(*args)), way, n,
                      n_words, planes)


@pytest.mark.parametrize("sums_are", ["ride", "primary_and_ride"])
def test_a_merge_keeps_the_index_way_whatever_the_limit(monkeypatch, sums_are):
    """N = 2 g, the merge of two states: the row index rides the key sort,
    an inverse sort, the sums' words in one batched [P, N] sort, and
    ``_front``'s planes in another, as before PR 35; the jaxpr does not
    read the operand limit."""
    g = 128
    n = 2 * g
    rng = np.random.default_rng(9)
    code = jnp.asarray(rng.integers(0, 100, n).astype(np.uint32))
    valid = jnp.asarray(rng.random(n) < 0.9)
    a, b = (jnp.asarray(rng.integers(-99, 99, n).astype(np.int64))
            for _ in range(2))

    def fold(code, valid, a, b):
        maxes = [a] if sums_are == "primary_and_ride" else []
        return sorted_group_fold([code], valid, [a, b], maxes, g,
                                 folded_flag=True)

    jaxpr = jax.make_jaxpr(fold)(code, valid, a, b)
    rides = 1 if sums_are == "primary_and_ride" else 2
    n_keys = 3 if sums_are == "primary_and_ride" else 1
    assert sorted_fold_ride(n, g, n_keys, rides) == "index"
    main, inverse, carried, front = _sort_eqns(jaxpr)
    assert main[0] == [(n,)] * (n_keys + 1) and main[3][-1] == "iota"
    assert (inverse[0], inverse[1]) == ([(n,), (n,)], 1)
    assert carried[0] == [(2 * rides, n)] * 2 and carried[2] == 1
    assert front[0][0][1] == n and front[2] == 1
    for limit in (0, 100):
        monkeypatch.setattr(routes, "SORT_PAYLOAD_MAX_OPERANDS", limit)
        assert str(jax.make_jaxpr(fold)(code, valid, a, b)) == str(jaxpr)


def test_the_cells_own_shapes():
    """2^21 rows into 2^17 slots, as ``sql_stats_1chip.sql_recent`` (three
    key words, one sum: five operands) folds its padded window."""
    n, g = 1 << 21, 1 << 17
    words, valid, vals = _ride_case("lead_id", 1, "junk_invalid", n, g, 35)
    valid[790_568:] = False  # the rows ``-5m`` holds; the rest is padding
    assert sorted_fold_ride(n, g, 3, 1) == "payload"
    got = jax.jit(lambda w, v, s: sorted_group_fold(
        w, v, s, [], g, folded_flag=True))(
        [jnp.asarray(w) for w in words], jnp.asarray(valid),
        [jnp.asarray(v) for v in vals])
    _assert_window_is_numpys(got, words, valid, vals, g)


# -- the route, against the id form ---------------------------------------------

REL = Relation([
    ("lat", DataType.INT64), ("bytes", DataType.INT64),
    ("t", DataType.TIME64NS), ("err", DataType.BOOLEAN),
    ("ratio", DataType.FLOAT64),
    ("svc", DataType.STRING), ("path", DataType.STRING),
    ("shard", DataType.INT64),
])
DICTS = {"svc": StringDictionary(f"s{i}" for i in range(20)),
         "path": StringDictionary(f"p{i}" for i in range(70_000))}
AGG_SETS = {
    "count_mean_max": (("n", "count", "lat"), ("m", "mean", "lat"),
                       ("mx", "max", "lat")),
    "sum_min_boolmean": (("s", "sum", "lat"), ("mn", "min", "lat"),
                         ("e", "mean", "err"), ("es", "sum", "err")),
    "two_extremes_two_sums": (("a", "max", "lat"), ("b", "min", "bytes"),
                              ("c", "sum", "bytes"), ("d", "sum", "lat"),
                              ("e", "max", "bytes")),
    "time_extremes": (("first", "min", "t"), ("last", "max", "t"),
                      ("n", "count", "t")),
    # px/perf_flamegraph's kind: ``any`` of a STRING (a maximum of its
    # dictionary ids, one word) and of an INT64, beside a sum.
    "any_string_sum": (("st", "any", "path"), ("c", "sum", "bytes")),
    "any_int64_sum_any_string": (("one", "any", "lat"), ("c", "sum", "bytes"),
                                 ("st", "any", "svc"), ("n", "count", "lat")),
}
KEY_SETS = {"strings": ("svc", "path"), "string_int": ("svc", "shard")}
N_ROWS, WINDOW = 6_000, 1_024


def _table(seed=11):
    rng = np.random.default_rng(seed)
    n = N_ROWS
    return {
        "lat": rng.integers(-(1 << 45), 1 << 45, n).astype(np.int64),
        "bytes": rng.integers(0, 1 << 20, n).astype(np.int64),
        "t": rng.integers(1 << 60, (1 << 60) + 10_000, n).astype(np.int64),
        "err": rng.random(n) < 0.3,
        "ratio": rng.random(n).astype(np.float32),
        "svc": rng.integers(NULL_ID, 5, n).astype(np.int32),
        "path": rng.integers(0, 70_000, n).astype(np.int32) % 97 * 700,
        "shard": rng.integers(-3, 3, n).astype(np.int64) * (1 << 33),
    }


def _frag(keys, aggs, g, platform="tpu", extra=(), allow_dense=True):
    aggs = tuple(AggExpr(o, u, (ColumnRef(c),)) for o, u, c in aggs + extra)
    with routes_of(platform):
        return compile_fragment(
            [AggOp(tuple(keys), aggs, max_groups=g)], REL, DICTS,
            default_registry(), allow_dense=allow_dense,
        )


def _windows(table, n_windows):
    """The table cut into ``n_windows`` windows of one capacity, the
    last padded (its tail masked by the row range)."""
    per = -(-N_ROWS // n_windows)
    cap = -(-per // 8) * 8
    out = []
    for w in range(n_windows):
        lo, hi = w * per, min((w + 1) * per, N_ROWS)
        cols = {}
        for c, v in table.items():
            plane = np.zeros(cap, v.dtype)
            plane[: hi - lo] = v[lo:hi]
            cols[c] = (jnp.asarray(plane),)
        out.append((cols, hi - lo))
    return out


def _fold(frag, table, n_windows=3, scan=False):
    state = frag.init_state()
    wins = _windows(table, n_windows)
    if scan:
        state = frag.update_all(
            state, tuple(c for c, _n in wins),
            jnp.zeros(len(wins), jnp.int32),
            jnp.asarray([n for _c, n in wins], jnp.int32),
        )
    else:
        for cols, rows in wins:
            state = frag.update(state, cols, (jnp.int32(0), jnp.int32(rows)))
    cols, valid, overflow = jax.device_get(frag.finalize(state))
    return cols, valid, bool(overflow)


def _by_key(cols, valid, keys, outs):
    """{key tuple: tuple of outputs} of a finalized state's live slots."""
    live = np.flatnonzero(valid)
    ks = list(zip(*(np.asarray(cols[k][0])[live].tolist() for k in keys)))
    assert len(set(ks)) == len(ks)
    vs = zip(*(np.asarray(cols[o][0])[live].tolist() for o in outs))
    return dict(zip(ks, vs))


def _numpy_answer(table, keys, aggs):
    groups = {}
    for i in range(N_ROWS):
        groups.setdefault(tuple(table[k][i].item() for k in keys), []).append(i)
    fn = {"count": lambda v: len(v), "sum": lambda v: int(v.astype(np.int64).sum()),
          "mean": lambda v: float(int(v.astype(np.int64).sum())) / len(v),
          "max": lambda v: int(v.max()), "min": lambda v: int(v.min()),
          "any": lambda v: int(v.max())}
    return {k: tuple(fn[u](table[c][idx]) for _o, u, c in aggs)
            for k, idx in groups.items()}


@pytest.mark.parametrize("keys", list(KEY_SETS))
@pytest.mark.parametrize("aggs", list(AGG_SETS))
def test_route_equals_the_id_form_and_numpy(aggs, keys):
    """The same windows through the sorted fold and through the id form
    (a FLOAT64 sum beside the same aggregates keeps the whole AggOp on
    ``dense_group_ids`` + ``uda.update`` + ``regroup_pair`` +
    ``scatter_carry``): equal after ``finalize``, and numpy's."""
    table = _table()
    g = 2_048
    outs = [o for o, _u, _c in AGG_SETS[aggs]]
    new = _frag(KEY_SETS[keys], AGG_SETS[aggs], g)
    old = _frag(KEY_SETS[keys], AGG_SETS[aggs], g,
                extra=(("f", "sum", "ratio"),))
    assert (new.fold, new.group) == ("sorted_int", "sorted")
    assert (old.fold, old.group) == ("xla", "sorted")
    got = _by_key(*_fold(new, table)[:2], KEY_SETS[keys], outs)
    was = _by_key(*_fold(old, table)[:2], KEY_SETS[keys], outs)
    assert got == was
    assert got == _numpy_answer(table, KEY_SETS[keys], AGG_SETS[aggs])


@pytest.mark.parametrize("aggs,keys,platform,allow_dense,fold", [
    ("count_mean_max", ("svc", "path"), "tpu", True, "sorted_int"),
    ("count_mean_max", ("svc", "path"), "tpu", False, "sorted_int"),
    ("count_mean_max", ("svc", "path"), "cpu", True, "xla"),
    # A dense domain: the dense fold, each aggregate on its own route.
    ("count_mean_max", ("svc",), "tpu", True, "pallas_int"),
    ("count_mean_max", (), "tpu", True, "xla"),  # no key at all
])
def test_what_chooses_the_route(aggs, keys, platform, allow_dense, fold):
    frag = _frag(keys, AGG_SETS[aggs], 256, platform=platform,
                 allow_dense=allow_dense)
    assert frag.fold == fold
    if fold == "sorted_int":
        assert frag.group == "sorted"


RIDE_AGGS = dict(AGG_SETS, sum_alone=(("s", "sum", "bytes"),),
                 two_sums=(("a", "sum", "lat"), ("b", "sum", "bytes")))


@pytest.mark.parametrize("keys", list(KEY_SETS))
@pytest.mark.parametrize("aggs", list(RIDE_AGGS))
def test_the_span_says_what_the_window_program_holds(aggs, keys):
    """``CompiledFragment.ride`` reckons from the plan what
    ``sorted_group_fold`` decides from its operands: at window length
    ``update``'s n-long sorts hold the sums' words, or a row index and a
    batched [P, n] sort, as the span's ``ride`` will say."""
    n, g = 2_048, 256
    frag = _frag(KEY_SETS[keys], RIDE_AGGS[aggs], g)
    cols = {c: (jnp.zeros(n, dt),) for c, dt in (
        ("lat", jnp.int64), ("bytes", jnp.int64), ("t", jnp.int64),
        ("err", jnp.bool_), ("svc", jnp.int32), ("path", jnp.int32),
        ("shard", jnp.int64))}
    sorts = [s for s in _sort_eqns(jax.make_jaxpr(frag.update)(
        frag.init_state(), cols, (jnp.int32(0), jnp.int32(n))))
        if s[0][0][-1] == n]
    shapes, num_keys, _d, made = sorts[0]
    in_program = (
        "index" if any(d == 1 for _s, _k, d, _m in sorts)
        else "payload" if len(shapes) > num_keys else ""
    )
    assert frag.ride(n) == in_program
    assert ("iota" in made) == (in_program == "index")
    assert frag.ride(2 * g) in ("", "index")  # as a merge folds


@pytest.mark.parametrize("uda,col,fold", [
    ("sum", "ratio", "xla"), ("max", "ratio", "xla"),
])
def test_an_aggregate_that_needs_row_ids_keeps_the_id_form(uda, col, fold):
    frag = _frag(("svc", "path"), AGG_SETS["count_mean_max"], 256,
                 extra=(("x", uda, col),))
    assert frag.fold == fold and frag.group == "sorted"
    assert not frag.plan.payload_sort


def test_a_quantiles_beside_them_leaves_the_integers_on_the_sort():
    """Since PR 41 a ``quantiles`` needs no row ids: the integer
    aggregates keep the payload sort and the digest is built by a sort
    of its own under the same key words; with one in the state a window
    folds alone and merges (no ``absorb``)."""
    frag = _frag(("svc", "path"), AGG_SETS["count_mean_max"], 256,
                 extra=(("x", "quantiles", "lat"),))
    assert frag.fold == "mixed:sorted_int=3,keyed_digest=1"
    assert frag.group == "sorted" and frag.plan.payload_sort
    assert (frag.plan.digests, frag.plan.digest_slots,
            frag.plan.digest_bins) == (1, 256 * 128, 1 << 32)


# -- one digest an argument: the plucked quantiles of one column share a
# carry, and each answers what it answers computed alone -----------------------

PLUCKS = (("p50", "_quantile_p50", "lat"), ("p90", "_quantile_p90", "lat"),
          ("p99", "_quantile_p99", "lat"))
#: (keys, platform, allow_dense, an aggregate beside them, layout, fold).
SHARE_FORMS = {
    "dense_tpu": (("svc",), "tpu", True, (), "dense",
                  "mixed:pallas_int=2,sorted_digest=3"),
    "dense_cpu": (("svc",), "cpu", True, (), "dense", "xla"),
    "keyed_pem": (("svc", "path"), "tpu", True, (), "sorted",
                  "mixed:sorted_int=2,keyed_digest=3"),
    "keyed_kelvin": (("svc", "path"), "tpu", False, (), "sorted",
                     "mixed:sorted_int=2,keyed_digest=3"),
    # A FLOAT64 sum needs row ids: the id form, sorted and hashed.
    "ids_tpu": (("svc", "path"), "tpu", True, (("x", "sum", "ratio"),),
                "sorted", "mixed:sorted_digest=3,xla=3"),
    "ids_cpu": (("svc", "path"), "cpu", True, (), "hashed", "xla"),
}
_BESIDE = (("n", "count", "lat"), ("m", "mean", "lat"))


def _share_frag(form, plucks):
    keys, platform, allow_dense, more, _layout, _fold = SHARE_FORMS[form]
    return _frag(keys, _BESIDE + plucks, 1_024, platform, extra=more,
                 allow_dense=allow_dense)


def _share_fold(frag, platform, fold, **kw):
    with routes_of(platform):
        cols, valid, overflow = fold(frag, **kw)
    assert not overflow
    return cols, valid


def _merged(frag, table, k):
    """k windows folded apart, then merged: a fold of k - 1 merges."""
    states = [
        frag.window_state(cols, (jnp.int32(0), jnp.int32(rows)))
        for cols, rows in _windows(table, k)
    ]
    acc = states[0]
    for s in states[1:]:
        acc = frag.merge_states(acc, s)
    cols, valid, overflow = jax.device_get(frag.finalize(acc))
    return cols, valid, bool(overflow)


def _assert_each_pluck_is_its_own(form, fold):
    """The AggOp with the three plucks (and an unplucked ``quantiles``
    of the same column) against the same AggOp with each pluck alone:
    equal VALUE FOR VALUE, group for group (the shared state is each of
    theirs bit for bit, and one read-out at all the points reads each
    point as a read-out of its own does)."""
    keys, platform, _ad, _more, layout, label = SHARE_FORMS[form]
    shared = _share_frag(form, PLUCKS + (("q", "quantiles", "lat"),))
    assert shared.plan.layout == layout
    assert shared.plan.digests == 1
    assert len(shared.plan.digest_owners) == 4
    assert set(shared.init_state()["carries"]) >= {"n", "m", "p50"}
    assert not {"p90", "p99", "q"} & set(shared.init_state()["carries"])
    three = _share_frag(form, PLUCKS)
    assert three.fold == label and three.plan.digests == 1
    cols, valid = _share_fold(shared, platform, fold)
    got = _by_key(cols, valid, keys, ("n", "m", "p50", "p90", "p99"))
    struct = dict(zip(
        _by_key(cols, valid, keys, ("n",)),
        np.asarray(cols["q"][0])[np.flatnonzero(valid)]))
    assert len(got) >= 6
    for at, pluck in enumerate(PLUCKS):
        alone = _share_frag(form, (pluck,))
        assert alone.plan.digests == 1
        assert set(alone.init_state()["carries"]) == (
            {"n", "m", pluck[0]} | ({"x"} if form == "ids_tpu" else set()))
        a_cols, a_valid = _share_fold(alone, platform, fold)
        want = _by_key(a_cols, a_valid, keys, ("n", "m", pluck[0]))
        assert set(want) == set(got)
        for k, (n, m, q) in want.items():
            assert got[k][:2] == (n, m)
            np.testing.assert_array_equal(
                np.float32(got[k][2 + at]), np.float32(q), err_msg=str(k))
            # The unplucked struct's column of that point, too.
            field = QUANTILE_FIELDS.index(pluck[0])
            np.testing.assert_array_equal(
                np.float32(struct[k][field]), np.float32(q))


@pytest.mark.parametrize("n_windows,scan", [(1, False), (3, True), (5, False)])
@pytest.mark.parametrize("form", list(SHARE_FORMS))
def test_plucks_that_share_a_digest_answer_as_alone(form, n_windows, scan):
    table = _table(seed=5)
    _assert_each_pluck_is_its_own(
        form, lambda frag: _fold(frag, table, n_windows, scan))


@pytest.mark.parametrize("k", [2, 4])
@pytest.mark.parametrize("form", list(SHARE_FORMS))
def test_merged_states_that_share_a_digest_answer_as_alone(form, k):
    table = _table(seed=6)
    _assert_each_pluck_is_its_own(
        form, lambda frag: _merged(frag, table, k))


@pytest.mark.parametrize("allow_dense", [True, False],
                         ids=["packed_code", "key_planes"])
@pytest.mark.parametrize("n_windows,scan", [(1, False), (3, True), (7, False)])
def test_any_cut_into_windows_gives_one_answer(n_windows, scan, allow_dense):
    """Associativity: one, three (one ``update_all`` scan) and seven
    windows, with the keys packed into one word (the PEM's fragment) and
    as planes (the Kelvin's, ``allow_dense=False``)."""
    table = _table(seed=3)
    aggs = AGG_SETS["count_mean_max"]
    frag = _frag(("svc", "path"), aggs, 1_024, allow_dense=allow_dense)
    cols, valid, overflow = _fold(frag, table, n_windows, scan)
    assert not overflow
    got = _by_key(cols, valid, ("svc", "path"), ("n", "m", "mx"))
    assert got == _numpy_answer(table, ("svc", "path"), aggs)


@pytest.mark.parametrize("allow_dense", [True, False], ids=["pem", "kelvin"])
@pytest.mark.parametrize("n_windows,scan", [(1, False), (3, True), (7, False)])
@pytest.mark.parametrize("aggs", ["any_string_sum",
                                  "any_int64_sum_any_string"])
def test_an_any_beside_a_sum_gives_numpys_answer(aggs, n_windows, scan,
                                                 allow_dense):
    """px/perf_flamegraph's kind on (a string, an INT64): one window long
    against the slots (6,000 rows into 1,024: folded alone, then
    merged), three in one ``update_all`` scan and seven (short: folded
    WITH the state), on the PEM's fragment and on the Kelvin's. Bit for
    bit numpy's maximum of ids / of values and its sums."""
    table = _table(seed=5)
    keys = KEY_SETS["string_int"]
    frag = _frag(keys, AGG_SETS[aggs], 1_024, allow_dense=allow_dense)
    assert (frag.fold, frag.group) == ("sorted_int", "sorted")
    assert frag.plan.max_words == {"any_string_sum": 1}.get(aggs, 3)
    cols, valid, overflow = _fold(frag, table, n_windows, scan)
    assert not overflow
    outs = [o for o, _u, _c in AGG_SETS[aggs]]
    assert _by_key(cols, valid, keys, outs) == _numpy_answer(
        table, keys, AGG_SETS[aggs])


@pytest.mark.parametrize("aggs", ["any_string_sum", "count_mean_max",
                                  "two_extremes_two_sums"])
def test_a_short_window_is_folded_with_the_state_in_one_set_of_sorts(aggs):
    """n < 4 g (a 2^21-row window into 2^20 slots): ``update`` lifts the
    rows to partial groups and folds g + n of them once, where the
    window's fold and the merge would each sort. The answer is the one
    ``merge_states(state, window_state(...))`` gives; at n >= 4 g the
    program is that composition, as it was."""
    table = _table(seed=6)
    keys = KEY_SETS["string_int"]
    g = 1_024
    frag = _frag(keys, AGG_SETS[aggs], g)
    (first, n1), (second, n2) = _windows(table, 3)[:2]
    state = frag.update(frag.init_state(), first, (jnp.int32(0), jnp.int32(n1)))
    rng = (jnp.int32(0), jnp.int32(n2))
    one = jax.device_get(frag.finalize(frag.update(state, second, rng)))
    two = jax.device_get(frag.finalize(jax.jit(
        lambda st, c, r: frag.merge_states(st, frag.window_state(c, r))
    )(state, second, rng)))
    outs = [o for o, _u, _c in AGG_SETS[aggs]]
    assert _by_key(one[0], one[1], keys, outs) == _by_key(
        two[0], two[1], keys, outs)

    def sorts(n):
        cols = {c: (jnp.zeros(n, p[0].dtype),) for c, p in second.items()}
        return _sort_eqns(jax.make_jaxpr(frag.update)(
            frag.init_state(), cols, (jnp.int32(0), jnp.int32(n))))

    short, long = sorts(2 * g), sorts(4 * g)
    assert {s[0][0][-1] for s in short} == {3 * g}  # g slots + n rows, once
    assert {s[0][0][-1] for s in long} == {4 * g, 2 * g}  # a fold, a merge
    assert len(short) < len(long)


def test_a_merge_takes_its_sides_in_any_order():
    """Two states whose slots are shuffled (as the Kelvin's arrive after
    the remap into its own dictionary) merge to what the table holds."""
    table = _table(seed=4)
    aggs = AGG_SETS["count_mean_max"]
    frag = _frag(("svc", "path"), aggs, 1_024, allow_dense=False)
    rng = np.random.default_rng(0)
    halves = []
    for cols, rows in _windows(table, 2):
        st = frag.update(frag.init_state(), cols, (jnp.int32(0), jnp.int32(rows)))
        perm = rng.permutation(1_024)
        halves.append(jax.tree_util.tree_map(
            lambda a: a[perm] if a.ndim else a, st))
    merged = jax.jit(frag.merge_states)(halves[1], halves[0])
    cols, valid, overflow = jax.device_get(frag.finalize(merged))
    assert not bool(overflow)
    got = _by_key(cols, valid, ("svc", "path"), ("n", "m", "mx"))
    assert got == _numpy_answer(table, ("svc", "path"), aggs)


@pytest.mark.parametrize("g", [64, 512])
def test_overflow_is_raised_by_the_window_and_by_the_merge(g):
    """582 live groups: a window alone overflows 64 slots; at 512 each
    of seven windows (~450 groups) fits and the merged state does not."""
    table = _table(seed=3)
    frag = _frag(("svc", "path"), AGG_SETS["count_mean_max"], g)
    wins = _windows(table, 7)
    first = frag.window_state(wins[0][0], (jnp.int32(0), jnp.int32(wins[0][1])))
    assert bool(first["overflow"]) == (g == 64)
    assert _fold(frag, table, 7)[2]


# -- the engine and the served path ---------------------------------------------

PXL = """import px
df = px.DataFrame(table='events')
df = df.groupby(['svc', 'path']).agg(
    n=('lat', px.count), m=('lat', px.mean), mx=('lat', px.max),
    mn=('lat', px.min), s=('size', px.sum))
px.display(df)
"""


def _events(seed, n, svc_names, n_paths):
    rng = np.random.default_rng(seed)
    return {
        "time_": np.arange(n, dtype=np.int64),
        "lat": rng.integers(-(1 << 40), 1 << 40, n).astype(np.int64),
        "size": rng.integers(0, 1 << 16, n).astype(np.int64),
        "svc": [svc_names[i] for i in rng.integers(0, len(svc_names), n)],
        "path": [f"/api/{i}" for i in rng.integers(0, n_paths, n)],
    }


def _events_answer(parts):
    groups = {}
    for d in parts:
        for s, p, lat, size in zip(d["svc"], d["path"], d["lat"], d["size"]):
            groups.setdefault((s, p), []).append((int(lat), int(size)))
    out = {}
    for k, rows in groups.items():
        lat = [r[0] for r in rows]
        out[k] = (len(rows), sum(lat) / len(rows), max(lat), min(lat),
                  sum(r[1] for r in rows))
    return out


def _rows_by_key(table):
    d = table.to_pydict()
    got = {}
    for s, p, n, m, mx, mn, sz in zip(d["svc"], d["path"], d["n"], d["m"],
                                      d["mx"], d["mn"], d["s"]):
        assert (s, p) not in got
        got[s, p] = (int(n), float(m), int(mx), int(mn), int(sz))
    return got


def _assert_rows(got, want):
    assert got.keys() == want.keys(), (
        len(got), len(want), sorted(want.keys() - got.keys())[:5],
        sorted(got.keys() - want.keys())[:5])
    for k, (n, m, mx, mn, sz) in want.items():
        gn, gm, gmx, gmn, gsz = got[k]
        assert (gn, gmx, gmn, gsz) == (n, mx, mn, sz)
        assert gm == pytest.approx(m, rel=2e-6, abs=1e-3)


@pytest.mark.parametrize("start", [64, 1 << 14], ids=["overflows", "fits"])
def test_the_engine_refolds_after_an_overflow(start):
    """From 64 slots the fold reports overflow and the engine climbs to a
    capacity that holds the ~4,850 groups; the answer is numpy's either
    way, and every fold dispatch says ``sorted_int``."""
    from pixie_tpu.planner import CompilerState, compile_pxl

    data = _events(7, 5_000, [f"svc-{i}" for i in range(40)], 2_000)
    with routes_of("tpu"), \
            override_flag("dense_domain_limit", 1_024):
        eng = Engine(window_rows=1_024)
        eng.append_data("events", data)
        state = CompilerState(
            schemas={n: t.relation for n, t in eng.tables.items()},
            registry=eng.registry, now_ns=0, max_output_rows=1 << 17,
            max_groups=start,
        )
        out = eng.execute_plan(compile_pxl(PXL, state).plan)
    trace = eng.tracer.last()
    _assert_rows(_rows_by_key(out["output"]), _events_answer([data]))
    folds = [s for s in trace.spans
             if s.name == "device.dispatch" and "fold" in s.attributes]
    assert folds and {s.attributes["fold"] for s in folds} == {"sorted_int"}
    assert {s.attributes["group"] for s in folds} == {"sorted"}
    assert (trace.usage.rebuckets > 0) == (start == 64)


RIDE_PXL = """import px
df = px.DataFrame(table='events')
df = df.groupby(['svc', 'path']).agg(%s)
px.display(df)
"""


@pytest.mark.parametrize("aggs,slots,ride", [
    # px/sql_stats' and px/net_flow_graph's kind: sums, no maximum.
    ("n=('lat', px.count), m=('lat', px.mean)", 256, "payload"),
    ("a=('lat', px.sum), b=('size', px.sum)", 256, "payload"),
    # A window short against its slots folds as a merge does.
    ("n=('lat', px.count), m=('lat', px.mean)", 2_048, "index"),
    # px/http_stats' kind: the sum IS the primary maximum's plane, and a
    # count is no plane at all.
    ("m=('lat', px.mean), mx=('lat', px.max)", 256, None),
    ("n=('lat', px.count)", 256, None),
    # px/perf_flamegraph's kind: an ``any`` is a maximum the sort carries
    # (two words an INT64), the sum beside it rides.
    ("a=('lat', px.any), c=('size', px.sum)", 256, "payload"),
    ("a=('lat', px.any), c=('size', px.sum)", 2_048, "index"),
])
def test_a_sorted_window_dispatch_says_how_its_sums_ride(aggs, slots, ride):
    """Span shape: a ``sorted_int`` window's ``device.dispatch`` carries
    ``ride`` beside ``fold`` / ``group`` / ``slots`` on the TPU's routes,
    and no attribute where no sum plane rides; ``max_words`` says how
    many words of maxima its sorts carry as keys, absent at 0."""
    from pixie_tpu.planner import CompilerState, compile_pxl

    data = _events(5, 4_000, [f"svc-{i}" for i in range(8)], 25)
    with routes_of("tpu"), override_flag("dense_domain_limit", 16):
        eng = Engine(window_rows=4_096)
        eng.append_data("events", data)
        state = CompilerState(
            schemas={n: t.relation for n, t in eng.tables.items()},
            registry=eng.registry, now_ns=0, max_groups=slots,
        )
        eng.execute_plan(compile_pxl(RIDE_PXL % aggs, state).plan)
    folds = [s.attributes for s in eng.tracer.last().spans
             if s.name == "device.dispatch" and "fold" in s.attributes]
    assert folds and all(
        (a["fold"], a["group"], a["slots"]) == ("sorted_int", "sorted", slots)
        for a in folds)
    assert {a.get("ride") for a in folds} == {ride}
    words = 2 * ("px.max" in aggs) + 2 * ("px.any" in aggs)
    assert {a.get("max_words") for a in folds} == {words or None}


RANGED_PXL = RIDE_PXL.replace(
    "table='events'", "table='events', start_time=%d, end_time=%d")


@pytest.mark.parametrize("lo,hi,rows,ride", [
    (0, 4_000, 4_096, "payload"),  # over a half: the whole window
    (500, 2_400, 2_048, "payload"),  # a half: n = 4 g still
    (3_000, 3_900, 1_024, "index"),  # a quarter: short against 512 slots
], ids=["whole", "half", "quarter"])
def test_a_sliced_windows_ride_is_its_slices(lo, hi, rows, ride):
    """The span's ``ride`` is reckoned from the rows the program is
    handed (PR 44: a slice around the range), as the program's own trace
    reckons it; the answer is numpy's by either way."""
    from pixie_tpu.planner import CompilerState, compile_pxl

    data = _events(5, 4_000, [f"svc-{i}" for i in range(8)], 25)
    with routes_of("tpu"), override_flag("dense_domain_limit", 16):
        eng = Engine(window_rows=4_096)
        eng.append_data("events", data)
        state = CompilerState(
            schemas={n: t.relation for n, t in eng.tables.items()},
            registry=eng.registry, now_ns=0, max_groups=512,
        )
        out = eng.execute_plan(compile_pxl(
            RANGED_PXL % (lo, hi, "a=('lat', px.sum), b=('size', px.sum)"),
            state).plan)
    (fold,) = [s.attributes for s in eng.tracer.last().spans
               if s.name == "device.dispatch" and "fold" in s.attributes]
    assert (fold["rows"], fold["range_rows"], fold["ride"]) == (
        rows, hi - lo, ride)
    want = {}
    for s, p, lat, size in zip(data["svc"][lo:hi], data["path"][lo:hi],
                               data["lat"][lo:hi], data["size"][lo:hi]):
        a, b = want.get((s, p), (0, 0))
        want[s, p] = (a + int(lat), b + int(size))
    d = out["output"].to_pydict()
    got = {(s, p): (int(a), int(b))
           for s, p, a, b in zip(d["svc"], d["path"], d["a"], d["b"])}
    assert got == want


@pytest.fixture(params=[2, 3], ids=["two_pems", "three_pems"])
def cluster(request):
    """Broker, Kelvin and PEMs whose tables were appended as python
    strings: every PEM has dictionaries of its own, in its own order,
    over service sets that overlap in part."""
    from pixie_tpu.services import (
        AgentTracker, KelvinAgent, MessageBus, PEMAgent, QueryBroker,
    )

    k = request.param
    bus = MessageBus()
    tracker = AgentTracker(bus, expiry_s=60.0, check_interval_s=60.0)
    pems = [
        PEMAgent(bus, f"pem-{i}", heartbeat_interval_s=0.05,
                 engine=Engine(window_rows=1_024)).start()
        for i in range(k)
    ]
    kelvin = KelvinAgent(bus, "kelvin-0", heartbeat_interval_s=0.05).start()
    parts = []
    for i, pem in enumerate(pems):
        names = [f"svc-{(7 * i + j) % 50}" for j in range(30)][::-1 if i % 2 else 1]
        parts.append(_events(100 + i, 3_000 + 500 * i, names, 1_500))
        pem.append_data("events", parts[-1])
        pem._register()
    deadline = time.time() + 10
    # Every PEM's re-registration, not the first one's: until it lands a
    # PEM is registered with no table and is planned around.
    while len(tracker.distributed_state().pems_with_table("events")) < k:
        assert time.time() < deadline, "a PEM's schema did not reach the tracker"
        time.sleep(0.01)
    yield QueryBroker(bus, tracker), pems, kelvin, parts
    for a in pems + [kelvin]:
        a.stop()
    tracker.close()
    bus.close()


def test_pems_with_dictionaries_of_their_own_through_the_kelvin(cluster):
    """The Kelvin remaps every PEM's ids into its canonical dictionary,
    which reorders keys: its merge is handed states in no key order, and
    the answer is still numpy's over all the parts."""
    broker, pems, kelvin, parts = cluster
    with routes_of("tpu"), \
            override_flag("dense_domain_limit", 1_024):
        for _ in range(2):
            res = broker.execute_script(PXL, timeout_s=180,
                                        max_output_rows=1 << 17)
            assert not res.get("partial")
            _assert_rows(_rows_by_key(res["tables"]["output"]),
                         _events_answer(parts))
    for pem in pems:
        frag = next(t for t in pem.engine.tracer.recent()
                    if t["kind"] == "fragment")
        assert {f["fold"] for f in frag["fragments"] if "fold" in f} == {
            "sorted_int"}
