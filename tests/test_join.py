"""Device N:M join tests (kernel + engine routing).

Mirrors the reference's join coverage (``equijoin_node_test.cc``,
``end_to_end_join_test.cc``): all four join types, N:M fan-out, string
keys with divergent dictionaries, u128 keys, empty sides, and the
overflow-retry path.
"""

import numpy as np
import pytest

from pixie_tpu.exec.engine import Engine
from pixie_tpu.exec.plan import JoinOp, MemorySourceOp, Plan, ResultSinkOp


def _ref_join(lk, rk, how):
    """Reference N:M join on int key lists -> set of (l_idx, r_idx) pairs
    (r_idx None = null right, l_idx None = null left)."""
    out = []
    r_by_key = {}
    for j, k in enumerate(rk):
        r_by_key.setdefault(k, []).append(j)
    matched_r = set()
    for i, k in enumerate(lk):
        js = r_by_key.get(k, [])
        if js:
            for j in js:
                out.append((i, j))
                matched_r.add(j)
        elif how in ("left", "outer"):
            out.append((i, None))
    if how in ("right", "outer"):
        for j in range(len(rk)):
            if j not in matched_r:
                out.append((None, j))
    return sorted(out, key=lambda p: (p[0] is None, p[0], p[1] is None, p[1]))


def _run_join(lk, lv, rk, rv, how):
    e = Engine()
    e.append_data(
        "l",
        {"k": np.asarray(lk, dtype=np.int64), "lv": np.asarray(lv, dtype=np.int64)},
        time_cols=(),
    )
    e.append_data(
        "r",
        {"k": np.asarray(rk, dtype=np.int64), "rv": np.asarray(rv, dtype=np.int64)},
        time_cols=(),
    )
    p = Plan()
    s1 = p.add(MemorySourceOp(table="l"))
    s2 = p.add(MemorySourceOp(table="r"))
    j = p.add(JoinOp(left_on=("k",), right_on=("k",), how=how), [s1, s2])
    p.add(ResultSinkOp("output"), [j])
    return p, e


def _check(lk, rk, how):
    lv = [100 + i for i in range(len(lk))]
    rv = [200 + j for j in range(len(rk))]
    p, e = _run_join(lk, lv, rk, rv, how)
    out = e.execute_plan(p)["output"].to_pydict()
    got = sorted(
        zip(out["lv"].tolist(), out["rv"].tolist()),
        key=lambda t: (t[0] == 0, t[0], t[1] == 0, t[1]),
    )
    ref = _ref_join(lk, rk, how)
    want = sorted(
        (
            (0 if i is None else 100 + i, 0 if j is None else 200 + j)
            for i, j in ref
        ),
        key=lambda t: (t[0] == 0, t[0], t[1] == 0, t[1]),
    )
    assert got == want, f"{how}: {got} != {want}"


class TestDeviceJoinKernel:
    """Drive the kernel through the engine with forced-device routing."""

    @pytest.fixture(autouse=True)
    def force_device(self, monkeypatch):
        import pixie_tpu.exec.joins as eng_mod

        monkeypatch.setattr(eng_mod, "DEVICE_JOIN_MIN_ROWS", 0)

    @pytest.mark.parametrize("how", ["inner", "left", "right", "outer"])
    def test_all_types_nm(self, how):
        _check([1, 2, 2, 5, 7], [2, 2, 3, 5, 5, 9], how)

    @pytest.mark.parametrize("how", ["inner", "left", "right", "outer"])
    def test_no_overlap(self, how):
        _check([1, 2], [3, 4], how)

    @pytest.mark.parametrize("how", ["inner", "left", "right", "outer"])
    def test_full_overlap_dups_both_sides(self, how):
        _check([4, 4, 4], [4, 4], how)

    def test_randomized_vs_reference(self):
        rng = np.random.default_rng(3)
        for how in ("inner", "left", "right", "outer"):
            lk = rng.integers(0, 20, 300).tolist()
            rk = rng.integers(10, 30, 200).tolist()
            _check(lk, rk, how)

    def test_string_keys_divergent_dicts(self):
        e = Engine()
        e.append_data("l", {"s": ["a", "b", "c", "b"]}, time_cols=())
        e.append_data(
            "r", {"s": ["b", "d", "b"], "v": np.array([1, 2, 3], dtype=np.int64)},
            time_cols=(),
        )
        p = Plan()
        s1 = p.add(MemorySourceOp(table="l"))
        s2 = p.add(MemorySourceOp(table="r"))
        j = p.add(JoinOp(left_on=("s",), right_on=("s",), how="outer"), [s1, s2])
        p.add(ResultSinkOp("output"), [j])
        out = e.execute_plan(p)["output"].to_pydict()
        rows = sorted(zip(out["s"], out["v"].tolist()))
        assert rows == [
            ("a", 0), ("b", 1), ("b", 1), ("b", 3), ("b", 3), ("c", 0), ("d", 2)
        ]

    def test_u128_keys(self):
        hi = np.array([1, 1, 2], dtype=np.uint64)
        lo = np.array([5, 6, 5], dtype=np.uint64)
        e = Engine()
        e.append_data("l", {"u": np.stack([hi, lo], axis=1)}, time_cols=())
        e.append_data(
            "r",
            {"u": np.stack([hi[:2], lo[:2]], axis=1),
             "v": np.array([10, 20], dtype=np.int64)},
            time_cols=(),
        )
        p = Plan()
        s1 = p.add(MemorySourceOp(table="l"))
        s2 = p.add(MemorySourceOp(table="r"))
        j = p.add(JoinOp(left_on=("u",), right_on=("u",), how="left"), [s1, s2])
        p.add(ResultSinkOp("output"), [j])
        out = e.execute_plan(p)["output"].to_pydict()
        assert out["v"].tolist() == [10, 20, 0]

    @pytest.mark.parametrize("how", ["inner", "left", "right", "outer"])
    def test_empty_left(self, how):
        _check([], [1, 2], how)

    @pytest.mark.parametrize("how", ["inner", "left", "right", "outer"])
    def test_empty_right(self, how):
        _check([1, 2], [], how)

    @pytest.mark.parametrize("how", ["inner", "outer"])
    def test_empty_both(self, how):
        _check([], [], how)

    def test_overflow_retries_with_larger_capacity(self, monkeypatch):
        """A high-fan-out join whose output exceeds the first capacity
        guess must rebucket, not truncate."""
        # 64 probe rows x 64 build rows on one key -> 4096 pairs, far
        # beyond bucket_capacity(64 + 64) = 128.
        lk = [7] * 64
        rk = [7] * 64
        p, e = _run_join(lk, range(64), rk, range(64), "inner")
        out = e.execute_plan(p)["output"].to_pydict()
        assert len(out["k"]) == 64 * 64


class TestJoinRouting:
    def test_large_inputs_route_off_n1_host_path(self, monkeypatch):
        """Above the threshold the N:1 host dict path is skipped: the
        device kernel on TPU, the vectorized numpy N:M join on CPU (XLA
        CPU sorts make the device kernel a regression there). The build
        side has a duplicate key: a unique one is the ``host_table``
        lookup at any size (``tests/test_join_host_table.py``)."""
        import jax

        import pixie_tpu.exec.joins as eng_mod

        monkeypatch.setattr(eng_mod, "DEVICE_JOIN_MIN_ROWS", 4)
        expected = (
            "_join_device" if jax.default_backend() == "tpu"
            else "_join_host_nm"
        )
        calls = []
        orig = getattr(eng_mod, expected)

        def spy(left, right, op, *a, **kw):
            calls.append(op.how)
            return orig(left, right, op, *a, **kw)

        monkeypatch.setattr(eng_mod, expected, spy)
        _check([1, 2, 3], [2, 3, 3, 4], "inner")
        assert calls == ["inner"]

    def test_pxl_right_and_outer_merge(self):
        """The frontend accepts right/outer and routes to the device."""
        e = Engine()
        e.append_data(
            "a",
            {"k": np.array([1, 2], dtype=np.int64),
             "x": np.array([10, 20], dtype=np.int64)},
            time_cols=(),
        )
        e.append_data(
            "b",
            {"k": np.array([2, 3], dtype=np.int64),
             "y": np.array([5, 6], dtype=np.int64)},
            time_cols=(),
        )
        out = e.execute_query("""
import px
a = px.DataFrame(table='a')
b = px.DataFrame(table='b')
j = a.merge(b, how='outer', left_on=['k'], right_on=['k'], suffixes=['', '_r'])
px.display(j)
""")["output"].to_pydict()
        rows = sorted(zip(out["x"].tolist(), out["y"].tolist()))
        assert rows == [(0, 6), (10, 0), (20, 5)]


@pytest.mark.slow
class TestJoinScale:
    """Moderate-scale N:M self-join vs numpy (the 10M-row hardware case
    lives in tests/test_tpu.py::test_device_join_10m_on_tpu)."""

    def test_half_million_self_join_matches_numpy(self):
        import jax

        from pixie_tpu.ops.join import device_join
        from pixie_tpu.types.batch import bucket_capacity

        n = 500_000
        rng = np.random.default_rng(31)
        nb = bucket_capacity(n)
        bk = rng.integers(0, n // 2, nb).astype(np.int64)
        pk = rng.integers(0, n // 2, nb).astype(np.int64)
        bv = np.zeros(nb, dtype=bool)
        bv[:n] = True
        pv = np.zeros(nb, dtype=bool)
        pv[:n] = True
        cap = bucket_capacity(4 * n)
        out = device_join([jax.numpy.asarray(bk)], jax.numpy.asarray(bv),
                          [jax.numpy.asarray(pk)], jax.numpy.asarray(pv),
                          cap, "inner")
        p_idx, p_take, b_idx, b_take, out_valid, overflow = (
            np.asarray(a) for a in out
        )
        assert not bool(overflow)
        cnt = np.bincount(bk[:n], minlength=n // 2)
        assert int(out_valid.sum()) == int(cnt[pk[:n]].sum())
        sel = np.nonzero(out_valid)[0]
        # Every emitted pair joins equal keys.
        assert (pk[p_idx[sel]] == bk[b_idx[sel]]).all()
        # Per-probe-row emission count matches numpy fan-out.
        emitted = np.bincount(p_idx[sel], minlength=nb)
        np.testing.assert_array_equal(emitted[:n], cnt[pk[:n]])


class TestJoinPastTheFlatScan:
    """The single-shot kernel above ``ops.join._FLAT_CUMMAX_MAX`` output
    slots, where its ownership scan is the blocked one
    (px/perf_flamegraph's N:1 join of 0.64 M rows against 4,096): on
    both platforms' routes, against numpy."""

    @pytest.mark.parametrize("n", [1 << 17, (1 << 17) + 1, 300_001])
    def test_the_ownership_scan_is_numpys_either_side_of_the_limit(self, n):
        import jax.numpy as jnp

        from pixie_tpu.ops.join import _FLAT_CUMMAX_MAX, _cummax

        assert _FLAT_CUMMAX_MAX == 1 << 17
        x = np.random.default_rng(n).integers(0, n, n).astype(np.int32)
        x[x % 3 > 0] = 0  # mostly "no owner yet", as the markers are
        assert np.array_equal(np.asarray(_cummax(jnp.asarray(x))),
                              np.maximum.accumulate(x))

    @pytest.mark.parametrize("how", ["inner", "left"])
    @pytest.mark.parametrize("platform", ["cpu", "tpu"])
    def test_an_n_to_1_join_of_200k_rows_matches_numpy(self, platform, how):
        import jax.numpy as jnp

        from conftest import routes_of
        from pixie_tpu.ops.join import device_join

        rng = np.random.default_rng(5)
        nb, n, cap = 512, 200_000, 1 << 18
        bk = rng.permutation(nb + 64)[:nb].astype(np.int32)  # unique keys
        bv = rng.random(nb) < 0.9
        pk = rng.integers(-1, nb + 64, n).astype(np.int32)
        pv = rng.random(n) < 0.95
        with routes_of(platform):
            out = device_join([jnp.asarray(bk)], jnp.asarray(bv),
                              [jnp.asarray(pk)], jnp.asarray(pv), cap, how)
        p_idx, p_take, b_idx, b_take, out_valid, overflow = (
            np.asarray(a) for a in out)
        assert not bool(overflow)
        row_of = {int(k): i for i, k in enumerate(bk) if bv[i]}
        hit = np.asarray([int(k) in row_of for k in pk]) & pv
        want = np.flatnonzero(hit if how == "inner" else pv)
        sel = np.flatnonzero(out_valid)
        assert np.array_equal(p_idx[sel], want)  # probe order, each once
        assert p_take[sel].all()
        assert np.array_equal(b_take[sel], hit[want])
        matched = sel[b_take[sel]]
        assert np.array_equal(
            b_idx[matched], [row_of[int(k)] for k in pk[p_idx[matched]]])


class TestHostNMJoinMultiKey:
    def test_two_key_nm_join_above_threshold(self, monkeypatch):
        """Multi-plane keys route through the dense-id (np.unique) path of
        the host N:M join on the CPU backend."""
        import jax
        import numpy as np
        import pixie_tpu.exec.joins as eng_mod
        from pixie_tpu.exec.engine import Engine

        if jax.default_backend() == "tpu":  # host path is CPU-only
            return
        monkeypatch.setattr(eng_mod, "DEVICE_JOIN_MIN_ROWS", 4)
        eng = Engine(window_rows=1 << 12)
        n = 3000
        rng = np.random.default_rng(4)
        a = rng.integers(0, 8, n)
        b = rng.integers(0, 5, n)
        v = rng.integers(0, 100, n)
        eng.append_data("l", {"time_": np.arange(n, dtype=np.int64),
                              "a": a, "b": b})
        eng.append_data("r", {"time_": np.arange(n, dtype=np.int64),
                              "a": a, "b": b, "v": v})
        out = eng.execute_query(
            "import px\n"
            "l = px.DataFrame(table='l')\n"
            "r = px.DataFrame(table='r')\n"
            "g = l.merge(r, how='inner', left_on=['a', 'b'],"
            " right_on=['a', 'b'], suffixes=['', '_r'])\n"
            "s = g.groupby('a').agg(n=('v', px.count))\npx.display(s)"
        )["output"].to_pydict()
        # numpy truth: inner join on (a, b) pair counts.
        import collections

        cnt = collections.Counter(zip(a, b))
        expect = collections.Counter()
        for (ka, kb), c in cnt.items():
            expect[ka] += c * c
        got = dict(zip(out["a"].tolist(), out["n"].tolist()))
        assert got == dict(expect)
