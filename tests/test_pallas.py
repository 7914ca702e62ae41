"""Pallas dense-domain group-by kernel (interpret mode on CPU; the same
kernel compiles for the chip via mosaic)."""

import numpy as np
import pytest

from pixie_tpu.ops.pallas_groupby import (
    INT_FOLD_GROUP_BLOCK,
    INT_FOLD_MAX_GROUPS,
    dense_group_fold,
    dense_group_fold_int,
    int_fold_blocks,
    int_fold_groups,
)

I64 = np.iinfo(np.int64)


class TestDenseGroupFold:
    def test_matches_numpy(self):
        rng = np.random.default_rng(0)
        n, g = 8192, 128
        slots = rng.integers(0, g, n).astype(np.int32)
        slots[::7] = g  # masked rows land in the trash id
        vals = rng.random(n).astype(np.float32) * 100
        cnt, s, mx, mn = dense_group_fold(slots, vals, g, chunk=1024,
                                          interpret=True, want_min=True)
        live = slots < g
        ref_cnt = np.bincount(slots[live], minlength=g)
        ref_sum = np.bincount(slots[live], weights=vals[live].astype(np.float64),
                              minlength=g)
        np.testing.assert_array_equal(np.asarray(cnt), ref_cnt)
        np.testing.assert_allclose(np.asarray(s), ref_sum, rtol=1e-5)
        ref_max = np.full(g, np.nan, dtype=np.float32)
        for k in range(g):
            m = slots == k
            if m.any():
                ref_max[k] = vals[m].max()
        np.testing.assert_allclose(np.asarray(mx), ref_max, rtol=1e-6)

    def test_empty_groups_are_nan_max_zero_count(self):
        slots = np.full(2048, 64, dtype=np.int32)  # everything masked
        vals = np.ones(2048, dtype=np.float32)
        cnt, s, mx, mn = dense_group_fold(slots, vals, 64, chunk=1024,
                                          interpret=True, want_min=True)
        assert float(np.asarray(cnt).sum()) == 0.0
        assert float(np.asarray(s).sum()) == 0.0
        assert np.isnan(np.asarray(mx)).all()


def _int_fold(slots, g, sums=(), exts=(), ext_max=()):
    """``dense_group_fold_int`` in interpret mode at the engine's
    blocking, outputs cut back to g slots (as fragment.py cuts them)."""
    g_pad = int_fold_groups(g)
    chunk, g_block = int_fold_blocks(len(slots), g_pad)
    cnt, s, e = dense_group_fold_int(
        np.where(slots >= g, g_pad, slots).astype(np.int32), tuple(sums),
        tuple(exts), g=g_pad, chunk=chunk, g_block=g_block,
        ext_max=tuple(ext_max), interpret=True,
    )
    return (np.asarray(cnt)[:g], [np.asarray(x)[:g] for x in s],
            [np.asarray(x)[:g] for x in e])


def _np_sum(slots, g, v):
    """numpy's int64 sum of each group: modulo 2^64, as ``np.add`` wraps."""
    live = slots < g
    out = np.zeros(g, np.int64)
    np.add.at(out, slots[live], v[live].astype(np.int64))
    return out


def _np_ext(slots, g, v, is_max):
    live = slots < g
    out = np.full(g, I64.min if is_max else I64.max)
    (np.maximum if is_max else np.minimum).at(out, slots[live], v[live])
    return out


class TestDenseGroupFoldInt:
    """The exact integer fold against numpy int64 (interpret mode; the
    chip's compiles are in tests/test_tpu_compile.py)."""

    # 32 and 2,048 are the benchmark cells' domains; one slot above a
    # group block pads to a second block.
    @pytest.mark.parametrize("g", [32, 2048, INT_FOLD_GROUP_BLOCK + 1])
    @pytest.mark.parametrize("n", [1024, 1 << 15])
    def test_matches_numpy(self, n, g):
        rng = np.random.default_rng(n + g)
        slots = rng.integers(0, g, n).astype(np.int32)
        slots[::7] = g  # masked rows land in the trash id
        slots[slots == 3] = 4  # an empty group among live ones
        # The whole int64 range: group sums wrap, as numpy's do.
        v = rng.integers(I64.min, I64.max, n, dtype=np.int64, endpoint=True)
        v[:4] = [I64.min, I64.max, -1, 0]
        small = rng.integers(-1000, 1000, n).astype(np.int64)
        cnt, (s_v, s_small), (mx, mn, mn_small) = _int_fold(
            slots, g, sums=(v, small), exts=(v, v, small),
            ext_max=(True, False, False),
        )
        np.testing.assert_array_equal(
            cnt, np.bincount(slots[slots < g], minlength=g))
        assert cnt[3] == 0
        np.testing.assert_array_equal(s_v, _np_sum(slots, g, v))
        np.testing.assert_array_equal(s_small, _np_sum(slots, g, small))
        np.testing.assert_array_equal(mx, _np_ext(slots, g, v, True))
        np.testing.assert_array_equal(mn, _np_ext(slots, g, v, False))
        np.testing.assert_array_equal(
            mn_small, _np_ext(slots, g, small, False))

    @pytest.mark.parametrize("case", [
        "int64_min", "int64_max", "negatives", "sum_wraps", "all_masked",
        "empty_groups", "boolean", "time64ns", "same_high_word",
    ])
    def test_edge(self, case):
        n, g = 2048, 64
        rng = np.random.default_rng(1)
        slots = rng.integers(0, g, n).astype(np.int32)
        v = rng.integers(-(1 << 40), 1 << 40, n).astype(np.int64)
        if case == "int64_min":
            v[:] = I64.min  # the max's own neutral, as a value
        elif case == "int64_max":
            v[:] = I64.max
            slots[:] = np.arange(n) % g  # 32 a group: every sum wraps
        elif case == "negatives":
            v = -np.abs(v) - 1
        elif case == "sum_wraps":
            v[:] = (1 << 62) + 12345
            slots[:] = 5  # 2048 x 2^62 = 2^73: wraps many times
        elif case == "all_masked":
            slots[:] = g
        elif case == "empty_groups":
            slots[:] = 7
        elif case == "boolean":
            v = rng.random(n) < 0.3
        elif case == "time64ns":
            v = 1_700_000_000_000_000_000 + rng.integers(0, 1 << 40, n)
        elif case == "same_high_word":
            # The low u32 decides, above and below its sign bit.
            v = (np.int64(-5) << 32) + rng.integers(0, 1 << 32, n)
        exts = () if v.dtype == np.bool_ else (v, v)
        cnt, (s,), e = _int_fold(
            slots, g, sums=(v,), exts=exts, ext_max=(True, False)[:len(exts)]
        )
        np.testing.assert_array_equal(
            cnt, np.bincount(slots[slots < g], minlength=g))
        np.testing.assert_array_equal(s, _np_sum(slots, g, v))
        if exts:
            np.testing.assert_array_equal(e[0], _np_ext(slots, g, v, True))
            np.testing.assert_array_equal(e[1], _np_ext(slots, g, v, False))
        if case in ("all_masked", "empty_groups"):
            empty = cnt == 0
            assert empty.sum() >= g - 1
            assert (s[empty] == 0).all()
            assert (e[0][empty] == I64.min).all()  # the UDAs' neutrals
            assert (e[1][empty] == I64.max).all()

    def test_count_only(self):
        slots = (np.arange(4096) % 100).astype(np.int32)
        cnt, s, e = _int_fold(slots, 100)
        assert s == [] and e == []
        np.testing.assert_array_equal(cnt, np.bincount(slots, minlength=100))

    @pytest.mark.parametrize("n,g,want", [
        (1 << 21, 2048, (2048, INT_FOLD_GROUP_BLOCK)),
        (1 << 21, 128, (2048, 128)),
        (1 << 19, 2048, (2048, INT_FOLD_GROUP_BLOCK)),  # a mesh shard
        (1024, 2048, (1024, INT_FOLD_GROUP_BLOCK)),
        (5000, 2048, None),  # no row block the tiling accepts
        (1 << 24, 128, None),  # 255 x rows would pass the i32 limb sums
        (1 << 21, INT_FOLD_MAX_GROUPS, (2048, INT_FOLD_GROUP_BLOCK)),
        (1 << 21, INT_FOLD_MAX_GROUPS + INT_FOLD_GROUP_BLOCK, None),
    ])
    def test_blocks(self, n, g, want):
        assert int_fold_blocks(n, g) == want

    @pytest.mark.parametrize("g,want", [
        (1, 128), (32, 128), (2048, 2048),
        (INT_FOLD_GROUP_BLOCK, INT_FOLD_GROUP_BLOCK),
        (INT_FOLD_GROUP_BLOCK + 1, 2 * INT_FOLD_GROUP_BLOCK),
        (2049, 3 * INT_FOLD_GROUP_BLOCK),
    ])
    def test_groups_pad_to_blocks_not_off_a_cliff(self, g, want):
        assert int_fold_groups(g) == want


class TestSortedCentroidFold:
    def test_matches_bincount(self):
        """The sorted digest's reduction (``ops/pallas_tdigest.py``):
        per-slot row counts and value sums of SORTED slot ids, a slot
        count that is no whole tile, chunks that span several tiles, and
        dropped rows (ids past every tile) at the end."""
        from pixie_tpu.ops.pallas_tdigest import sorted_centroid_fold

        rng = np.random.default_rng(4)
        n, n_slots = 8192, 3000
        ids = np.sort(rng.integers(0, n_slots, n)).astype(np.int32)
        ids[-1500:] = 3072  # dropped: the padded slot count
        vals = (rng.random(n).astype(np.float32) - 0.5) * 50
        w, mw = sorted_centroid_fold(ids, vals, n_slots, interpret=True)
        live = ids < n_slots
        ref_w = np.bincount(ids[live], minlength=n_slots)
        ref_mw = np.bincount(ids[live], weights=vals[live].astype(np.float64),
                             minlength=n_slots)
        np.testing.assert_array_equal(np.asarray(w), ref_w)
        np.testing.assert_allclose(np.asarray(mw), ref_mw, rtol=1e-4,
                                   atol=1e-3)


class TestEnginePallasRouting:
    """Interpret-mode engine equivalence: the Pallas fold and the XLA
    fold must produce identical query results (VERDICT r5 item 2)."""

    QUERY = """
import px
df = px.DataFrame(table='t')
out = df.groupby('svc').agg(
    n=('v', px.count), s=('v', px.sum), mean=('v', px.mean),
    mx=('v', px.max))
px.display(out)
"""

    def _engine(self):
        from pixie_tpu.exec.engine import Engine
        from pixie_tpu.types.batch import HostBatch
        from pixie_tpu.types.dtypes import DataType
        from pixie_tpu.types.relation import Relation
        from pixie_tpu.types.strings import StringDictionary

        rng = np.random.default_rng(9)
        n = 8192
        svcs = [f"s{i}" for i in range(23)]
        d = StringDictionary(svcs)
        rel = Relation([("time_", DataType.TIME64NS),
                        ("svc", DataType.STRING),
                        ("v", DataType.FLOAT64)])
        eng = Engine(window_rows=4096)
        eng.append_data("t", HostBatch(relation=rel, cols={
            "time_": (np.arange(n, dtype=np.int64),),
            "svc": (rng.integers(0, len(svcs), n).astype(np.int32),),
            "v": (rng.random(n) * 100,),
        }, length=n, dicts={"svc": d}))
        return eng

    def test_pallas_engine_path_matches_xla(self):
        from conftest import routes_of
        from pixie_tpu.config import override_flag

        eng = self._engine()
        with override_flag("cpu_fold_threads", 1):  # the XLA fold, not the native one
            xla = eng.execute_query(self.QUERY)["output"].to_pydict()
        with routes_of("tpu"):
            pallas = eng.execute_query(self.QUERY)["output"].to_pydict()
        ox = np.argsort(xla["svc"])
        op = np.argsort(pallas["svc"])
        assert list(np.array(xla["svc"])[ox]) == list(np.array(pallas["svc"])[op])
        np.testing.assert_array_equal(xla["n"][ox], pallas["n"][op])
        np.testing.assert_allclose(xla["s"][ox], pallas["s"][op], rtol=1e-5)
        np.testing.assert_allclose(xla["mean"][ox], pallas["mean"][op],
                                   rtol=1e-5)
        np.testing.assert_allclose(xla["mx"][ox], pallas["mx"][op], rtol=1e-6)

    def test_tdigest_pallas_quantiles_close(self):
        from conftest import routes_of
        from pixie_tpu.config import override_flag

        eng = self._engine()
        q = ("import px\ndf = px.DataFrame(table='t')\n"
             "out = df.groupby('svc').agg(p=('v', px.quantiles))\n"
             "out.p50 = px.pluck_float64(out.p, 'p50')\n"
             "out = out[['svc', 'p50']]\npx.display(out)")
        with override_flag("cpu_fold_threads", 1):
            xla = eng.execute_query(q)["output"].to_pydict()
        with routes_of("tpu"):
            pal = eng.execute_query(q)["output"].to_pydict()
        ox, op = np.argsort(xla["svc"]), np.argsort(pal["svc"])
        np.testing.assert_allclose(xla["p50"][ox], pal["p50"][op], rtol=0.05)

    def test_nonfinite_values_confined_to_their_group(self):
        """NaN/inf rows must poison only their OWN group's sum — the
        one-hot contraction zeroes them and the max/min evidence
        restores them (r5 review finding)."""
        slots = np.array([0, 0, 1, 1, 2, 2, 3, 3] * 16, dtype=np.int32)
        vals = np.ones(128, dtype=np.float32)
        vals[0] = np.nan        # group 0: NaN
        vals[2] = np.inf        # group 1: +inf
        vals[4] = -np.inf       # group 2: -inf
        cnt, s, mx, mn = dense_group_fold(slots, vals, 128, chunk=64,
                                          interpret=True, want_min=True)
        s = np.asarray(s)
        assert np.isnan(s[0])
        assert s[1] == np.inf
        assert s[2] == -np.inf
        assert s[3] == 32.0  # the finite group is untouched
        assert np.asarray(mn)[3] == 1.0

    def test_neg_inf_restored_without_min_pass(self):
        """want_min=False still restores a -inf group sum (the aux
        output counts -inf rows via an MXU contraction instead)."""
        slots = np.array([0, 0, 1, 1] * 32, dtype=np.int32)
        vals = np.ones(128, dtype=np.float32)
        vals[0] = -np.inf
        cnt, s, mx, mn = dense_group_fold(slots, vals, 128, chunk=64,
                                          interpret=True, want_min=False)
        assert mn is None
        s = np.asarray(s)
        assert s[0] == -np.inf
        assert s[1] == 64.0


class TestEngineIntFoldRouting:
    """The shipped scripts reach the exact integer kernel, per
    aggregate, and answer as the XLA fold does, bit for bit."""

    MIXED = """
import px
df = px.DataFrame(table='http_events')
df.failure = df.resp_status >= 400
out = df.groupby('service').agg(
    q=('latency_ns', px.quantiles), err=('failure', px.mean),
    n=('latency_ns', px.count))
px.display(out)
"""
    INT_STATS = """
import px
df = px.DataFrame(table='http_events')
df.signed = 1000000 - df.latency_ns
out = df.groupby(['service', 'req_method']).agg(
    n=('signed', px.count), s=('signed', px.sum), mean=('signed', px.mean),
    lo=('signed', px.min), hi=('signed', px.max),
    first=('time_', px.min), last=('time_', px.max),
    failed=('resp_status', px.max))
px.display(out)
"""
    F64 = """
import px
df = px.DataFrame(table='http_events')
df.ms = df.latency_ns / 1000000.0
out = df.groupby('service').agg(n=('ms', px.count), s=('ms', px.sum))
px.display(out)
"""

    @pytest.fixture(scope="class")
    def eng(self):
        from pixie_tpu.exec.engine import Engine
        from pixie_tpu.ingest.replay import gen_http_events

        eng = Engine(window_rows=8192)
        for chunk in gen_http_events(20000, seed=3):  # three windows
            eng.append_data("http_events", chunk)
        return eng

    @staticmethod
    def _run(eng, pxl, platform):
        """(sorted output columns, the fold programs' ``fold`` span
        attributes, the /debug/queryz fragment entries' ``fold``)."""
        from conftest import routes_of
        from pixie_tpu.config import override_flag

        with routes_of(platform), \
                override_flag("cpu_fold_threads", 1):  # not the native fold
            out = eng.execute_query(pxl)["output"].to_pydict()
        trace = eng.tracer.last()
        keys = [k for k in ("service", "req_path", "req_method", "k") if k in out]
        order = np.lexsort([np.asarray(out[k]) for k in keys])
        spans = {
            sp.attributes.get("fold") for sp in trace.spans
            if sp.name == "device.dispatch"
            and sp.attributes["program"] != "fragment_finalize"
        }
        queryz = {f.get("fold") for f in trace.to_dict()["fragments"]}
        return {k: np.asarray(v)[order] for k, v in out.items()}, spans, queryz

    @staticmethod
    def _assert_bit_equal(a, b, digests=()):
        """Every column equal bit for bit, but the ``digests`` columns: a
        window's t-digest adds the same values in another order on the
        two platforms' routes (rows scattered into bins against rows
        sorted), so its quantiles agree to f32 rounding."""
        import json

        assert list(a) == list(b)
        for k in a:
            assert a[k].dtype == b[k].dtype, k
            if k not in digests:
                np.testing.assert_array_equal(a[k], b[k], err_msg=k)
                continue
            for x, y in zip(a[k], b[k]):
                x, y = (json.loads(v) if isinstance(v, str) else {"q": v}
                        for v in (x, y))
                assert list(x) == list(y), k
                np.testing.assert_allclose(
                    list(x.values()), list(y.values()), rtol=1e-5, err_msg=k)

    @pytest.mark.parametrize("script,fold", [
        ("px/http_stats", "pallas_int"),
        ("px/service_stats", "mixed:pallas_int=2,sorted_digest=2"),
    ])
    def test_shipped_script_equals_xla_bit_for_bit(self, eng, script, fold):
        from pixie_tpu.scripts import load_script

        pxl = load_script(script).pxl
        off, off_spans, off_queryz = self._run(eng, pxl, "cpu")
        on, spans, queryz = self._run(eng, pxl, "tpu")
        self._assert_bit_equal(off, on, digests=("p50", "p99"))
        assert off_spans == off_queryz == {"xla"}
        assert spans == queryz == {fold}

    def test_mixed_aggop_routes_per_aggregate(self, eng, monkeypatch):
        """``quantiles`` keeps its ``uda.update`` and vetoes nothing:
        ``mean(bool)`` and ``count`` come from ONE kernel call a window."""
        from pixie_tpu.ops import pallas_groupby

        calls = []
        real = pallas_groupby.dense_group_fold_int

        def spy(slots, sum_args, ext_args, **kw):
            calls.append(([a.dtype for a in sum_args], len(ext_args)))
            return real(slots, sum_args, ext_args, **kw)

        monkeypatch.setattr(pallas_groupby, "dense_group_fold_int", spy)
        off, _, _ = self._run(eng, self.MIXED, "cpu")
        assert not calls
        on, spans, _ = self._run(eng, self.MIXED, "tpu")
        self._assert_bit_equal(off, on, digests=("q",))
        assert spans == {"mixed:pallas_int=2,sorted_digest=1"}
        # Traced once (the three windows share one program): one call,
        # the BOOLEAN argument alone, no extreme.
        assert calls == [([np.dtype(bool)], 0)]

    def test_integer_sum_min_max_equal_xla(self, eng):
        """Negative INT64 values, TIME64NS extremes, several arguments,
        min beside max: every carry bit-equal to the XLA fold's."""
        off, _, _ = self._run(eng, self.INT_STATS, "cpu")
        on, spans, _ = self._run(eng, self.INT_STATS, "tpu")
        self._assert_bit_equal(off, on)
        assert spans == {"pallas_int"}
        assert (on["lo"] < 0).any() and on["s"].dtype == np.int64

    def test_float64_arguments_keep_the_f32_kernel(self, eng):
        _out, spans, queryz = self._run(eng, self.F64, "tpu")
        assert spans == queryz == {"pallas_f32"}

    def test_above_the_crossover_stays_on_xla(self, monkeypatch):
        """A dense domain one group block above ``INT_FOLD_MAX_GROUPS``
        keeps the sort-based fold: the kernel is never traced."""
        from pixie_tpu.exec.engine import Engine
        from pixie_tpu.ops import pallas_groupby
        from pixie_tpu.types.batch import HostBatch
        from pixie_tpu.types.dtypes import DataType
        from pixie_tpu.types.relation import Relation
        from pixie_tpu.types.strings import StringDictionary

        def refuse(*a, **kw):
            raise AssertionError("integer kernel traced above the cross-over")

        q = ("import px\ndf = px.DataFrame(table='t')\n"
             "out = df.groupby('k').agg(n=('v', px.count), s=('v', px.sum),"
             " mx=('v', px.max))\npx.display(out)")
        rng = np.random.default_rng(2)
        n = 4096
        results = {}
        for ndv in (INT_FOLD_MAX_GROUPS - 1, INT_FOLD_MAX_GROUPS + 1):
            # +1 slot for the dictionary's NULL id: ndv + 1 slots.
            d = StringDictionary([f"k{i}" for i in range(ndv)])
            rel = Relation([("time_", DataType.TIME64NS),
                            ("k", DataType.STRING), ("v", DataType.INT64)])
            eng = Engine(window_rows=4096)
            eng.append_data("t", HostBatch(relation=rel, cols={
                "time_": (np.arange(n, dtype=np.int64),),
                "k": (rng.integers(0, ndv, n).astype(np.int32),),
                "v": (rng.integers(-(1 << 50), 1 << 50, n),),
            }, length=n, dicts={"k": d}))
            if ndv > INT_FOLD_MAX_GROUPS:
                monkeypatch.setattr(
                    pallas_groupby, "dense_group_fold_int", refuse)
            off, _, _ = self._run(eng, q, "cpu")
            on, spans, _ = self._run(eng, q, "tpu")
            self._assert_bit_equal(off, on)
            results[ndv] = spans
        assert results[INT_FOLD_MAX_GROUPS - 1] == {"pallas_int"}
        assert results[INT_FOLD_MAX_GROUPS + 1] == {"xla"}


class TestRowChunk:
    """ops/pallas_groupby.row_chunk: the row block both kernels' call
    sites pick — a multiple of 1024 dividing n, or all n rows (what the
    chip's tiling of a 1-D 32-bit operand accepts; the compiles against
    it are in tests/test_tpu_compile.py)."""

    @pytest.mark.parametrize("n,cap,want", [
        (1 << 21, 2048, 2048),
        (1 << 21, 1024, 1024),
        (1024, 2048, 1024),
        (3072, 2048, 1024),
        (128, 2048, 128),     # below the tile: the whole array
        (1536, 2048, 1536),   # not a 1024 multiple, fits one block
        (1536, 1024, None),   # no block the tiling accepts: XLA path
        (5000, 2048, None),
    ])
    def test_block(self, n, cap, want):
        from pixie_tpu.ops.pallas_groupby import row_chunk

        assert row_chunk(n, cap) == want
