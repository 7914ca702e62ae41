"""Pallas dense-domain group-by kernel (interpret mode on CPU; the same
kernel compiles for the chip via mosaic)."""

import numpy as np
import pytest

from pixie_tpu.ops.pallas_groupby import dense_group_fold


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


class TestHistFold:
    def test_matches_segment_sum(self):
        from pixie_tpu.ops.pallas_tdigest import hist_fold

        rng = np.random.default_rng(4)
        n, n_slots = 8192, 3000  # non-tile-multiple slot count
        bins = rng.integers(0, n_slots, n).astype(np.int32)
        bins[::5] = 4096  # trash (>= padded range)
        vals = (rng.random(n).astype(np.float32) - 0.5) * 50
        w, mw = hist_fold(bins, vals, n_slots, chunk=1024, interpret=True)
        live = bins < n_slots
        ref_w = np.bincount(bins[live], minlength=n_slots)
        ref_mw = np.bincount(bins[live], weights=vals[live].astype(np.float64),
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
        from pixie_tpu.config import set_flag

        eng = self._engine()
        set_flag("cpu_fold_threads", 1)  # isolate the XLA/Pallas paths
        try:
            xla = eng.execute_query(self.QUERY)["output"].to_pydict()
            set_flag("pallas_dense_fold", "interpret")
            pallas = eng.execute_query(self.QUERY)["output"].to_pydict()
        finally:
            set_flag("pallas_dense_fold", "auto")
            set_flag("cpu_fold_threads", 0)
        ox = np.argsort(xla["svc"])
        op = np.argsort(pallas["svc"])
        assert list(np.array(xla["svc"])[ox]) == list(np.array(pallas["svc"])[op])
        np.testing.assert_array_equal(xla["n"][ox], pallas["n"][op])
        np.testing.assert_allclose(xla["s"][ox], pallas["s"][op], rtol=1e-5)
        np.testing.assert_allclose(xla["mean"][ox], pallas["mean"][op],
                                   rtol=1e-5)
        np.testing.assert_allclose(xla["mx"][ox], pallas["mx"][op], rtol=1e-6)

    def test_tdigest_pallas_quantiles_close(self):
        from pixie_tpu.config import set_flag

        eng = self._engine()
        q = ("import px\ndf = px.DataFrame(table='t')\n"
             "out = df.groupby('svc').agg(p=('v', px.quantiles))\n"
             "out.p50 = px.pluck_float64(out.p, 'p50')\n"
             "out = out[['svc', 'p50']]\npx.display(out)")
        set_flag("cpu_fold_threads", 1)
        try:
            xla = eng.execute_query(q)["output"].to_pydict()
            set_flag("pallas_tdigest", "interpret")
            pal = eng.execute_query(q)["output"].to_pydict()
        finally:
            set_flag("pallas_tdigest", "auto")
            set_flag("cpu_fold_threads", 0)
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

    def test_odd_window_stays_on_xla_segment_sum(self):
        """A window with no valid block keeps batch_to_digest on the
        scatter path even when the kernel is asked for by name."""
        import jax.numpy as jnp
        import numpy as np

        from pixie_tpu.config import override_flag
        from pixie_tpu.ops import pallas_tdigest
        from pixie_tpu.ops.tdigest import batch_to_digest, digest_quantile

        n = 5000
        rng = np.random.default_rng(0)
        vals = jnp.asarray(rng.lognormal(3.0, 1.0, n).astype(np.float32))
        gids = jnp.zeros(n, jnp.int32)
        mask = jnp.ones(n, bool)
        called = []
        orig = pallas_tdigest.hist_fold
        pallas_tdigest.hist_fold = lambda *a, **k: called.append(1) or orig(*a, **k)
        try:
            with override_flag("pallas_tdigest", "interpret"):
                q = digest_quantile(batch_to_digest(vals, gids, mask, 1), (0.5,))
        finally:
            pallas_tdigest.hist_fold = orig
        assert not called
        ref = np.quantile(np.asarray(vals), 0.5)
        assert abs(float(q[0, 0]) - ref) / ref < 0.05
