"""Unit tests for the type system & columnar core (SURVEY.md §7 stage 1)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pixie_tpu.types import (
    DataType,
    DeviceBatch,
    HostBatch,
    MIN_CAPACITY,
    Relation,
    StringDictionary,
    bucket_capacity,
)


class TestRelation:
    def test_basic(self):
        r = Relation({"time_": DataType.TIME64NS, "latency": DataType.FLOAT64})
        assert r.column_names == ("time_", "latency")
        assert r.col_type("latency") == DataType.FLOAT64
        assert r.col_index("latency") == 1
        assert len(r) == 2

    def test_duplicate_rejected(self):
        with pytest.raises(ValueError):
            Relation([("a", DataType.INT64), ("a", DataType.INT64)])

    def test_select_add_merge(self):
        r = Relation({"a": DataType.INT64, "b": DataType.STRING})
        assert r.select(["b"]).column_names == ("b",)
        r2 = r.add("c", DataType.FLOAT64)
        assert r2.column_names == ("a", "b", "c")
        merged = r.merge(Relation({"a": DataType.INT64, "d": DataType.BOOLEAN}))
        assert merged.column_names == ("a", "b", "a_y", "d")

    def test_hash_eq(self):
        r1 = Relation({"a": DataType.INT64})
        r2 = Relation({"a": DataType.INT64})
        assert r1 == r2 and hash(r1) == hash(r2)


class TestStringDictionary:
    def test_encode_decode_roundtrip(self):
        d = StringDictionary()
        ids = d.encode(["GET", "POST", "GET", "PUT"])
        assert ids.dtype == np.int32
        assert list(ids) == [0, 1, 0, 2]
        assert list(d.decode(ids)) == ["GET", "POST", "GET", "PUT"]

    def test_lookup_missing(self):
        d = StringDictionary(["a"])
        assert d.lookup("a") == 0
        assert d.lookup("zz") == -1

    def test_transform(self):
        d = StringDictionary(["/api/v1/users/123", "/api/v1/users/456", "/health"])
        new, remap = d.transform(lambda s: s.rsplit("/", 1)[0] if s[-1].isdigit() else s)
        assert new.strings == ["/api/v1/users", "/health"]
        assert list(remap) == [0, 0, 1]

    def test_union(self):
        a = StringDictionary(["x", "y"])
        b = StringDictionary(["y", "z"])
        merged, ra, rb = a.union(b)
        assert merged.strings == ["x", "y", "z"]
        assert list(ra) == [0, 1]
        assert list(rb) == [1, 2]


    def test_byte_lengths_follow_the_dictionary_as_it_grows(self):
        d = StringDictionary(["a", "h\u00e9", ""])
        assert d.byte_lengths().tolist() == [1, 3, 0]
        d.get_or_add("\u65e5\u672c")
        assert d.byte_lengths().tolist() == [1, 3, 0, 6]
        assert d.byte_lengths().dtype == np.int32


class TestHostBatch:
    @pytest.mark.parametrize("ids,want", [
        ([0, 1, 1, 2], 1 + 3 + 3 + 0),
        ([0, -1, 1, 7, 1], 1 + 3 + 3),  # a null id and one out of range
        ([], 0),
    ])
    def test_string_nbytes_counts_what_the_ids_stand_for(self, ids, want):
        d = StringDictionary(["a", "h\u00e9", ""])
        hb = HostBatch(
            relation=Relation({"s": DataType.STRING, "n": DataType.INT64}),
            length=len(ids), dicts={"s": d},
            cols={"s": (np.asarray(ids, np.int32),),
                  "n": (np.arange(len(ids), dtype=np.int64),)},
        )
        assert hb.string_nbytes() == want

    def test_infer_relation(self):
        hb = HostBatch.from_pydict(
            {
                "time_": np.arange(5, dtype=np.int64),
                "latency": np.linspace(0, 1, 5),
                "service": ["a", "b", "a", "c", "b"],
                "ok": np.array([True, False, True, True, False]),
            }
        )
        assert hb.relation.col_type("time_") == DataType.TIME64NS
        assert hb.relation.col_type("latency") == DataType.FLOAT64
        assert hb.relation.col_type("service") == DataType.STRING
        assert hb.relation.col_type("ok") == DataType.BOOLEAN
        assert hb.length == 5
        out = hb.to_pydict()
        assert list(out["service"]) == ["a", "b", "a", "c", "b"]

    def test_uint128(self):
        vals = [(1 << 70) + 5, 7]
        hb = HostBatch.from_pydict(
            {"upid": vals},
            relation=Relation({"upid": DataType.UINT128}),
        )
        hi, lo = hb.cols["upid"]
        assert hi.dtype == np.uint64 and lo.dtype == np.uint64
        assert int(hi[0]) == (vals[0] >> 64) and int(lo[0]) == vals[0] & ((1 << 64) - 1)
        assert int(hi[1]) == 0 and int(lo[1]) == 7


class TestDeviceBatch:
    def test_bucket_capacity(self):
        assert bucket_capacity(0) == MIN_CAPACITY
        assert bucket_capacity(1024) == 1024
        assert bucket_capacity(1025) == 2048

    def test_roundtrip(self):
        hb = HostBatch.from_pydict(
            {
                "time_": np.arange(10, dtype=np.int64),
                "latency": np.arange(10, dtype=np.float64),
                "service": ["s%d" % (i % 3) for i in range(10)],
            }
        )
        db = hb.to_device()
        assert db.capacity == MIN_CAPACITY
        assert int(db.n_valid()) == 10
        back = db.to_host(dicts=hb.dicts)
        np.testing.assert_array_equal(back.cols["time_"][0], hb.cols["time_"][0])
        assert list(back.to_pydict()["service"]) == list(hb.to_pydict()["service"])

    def test_pytree_through_jit(self):
        hb = HostBatch.from_pydict({"x": np.arange(8, dtype=np.int64)})
        db = hb.to_device()

        @jax.jit
        def double(b: DeviceBatch) -> DeviceBatch:
            return b.with_cols({"x": (b.plane("x") * 2,)}, b.relation)

        out = double(db)
        np.testing.assert_array_equal(
            np.asarray(out.plane("x"))[:8], np.arange(8) * 2
        )
        # mask survives
        assert int(out.n_valid()) == 8

    def test_mask_semantics(self):
        hb = HostBatch.from_pydict({"x": np.arange(6, dtype=np.int64)})
        db = hb.to_device()
        filtered = db.with_valid(db.valid & (db.plane("x") % 2 == 0))
        back = filtered.to_host()
        np.testing.assert_array_equal(back.cols["x"][0], [0, 2, 4])

    def test_int64_preserved(self):
        big = np.array([2**40 + 1, -(2**50)], dtype=np.int64)
        db = HostBatch.from_pydict({"t": big}, time_cols=()).to_device()
        assert db.plane("t").dtype == jnp.int64
        np.testing.assert_array_equal(np.asarray(db.plane("t"))[:2], big)


class TestReviewRegressions:
    """Regressions from the round-1 code review findings."""

    def test_shared_empty_dict_is_used(self):
        shared = StringDictionary()
        b1 = HostBatch.from_pydict({"s": ["a", "b"]}, dicts={"s": shared})
        b2 = HostBatch.from_pydict({"s": ["b", "a"]}, dicts={"s": shared})
        assert b1.dicts["s"] is shared and b2.dicts["s"] is shared
        np.testing.assert_array_equal(b1.cols["s"][0], [0, 1])
        np.testing.assert_array_equal(b2.cols["s"][0], [1, 0])

    def test_pre_encoded_int64_ids(self):
        d = StringDictionary(["x", "y"])
        hb = HostBatch.from_pydict(
            {"s": np.array([0, 1], dtype=np.int64)},
            relation=Relation({"s": DataType.STRING}),
            dicts={"s": d},
        )
        assert hb.cols["s"][0].dtype == np.int32
        assert list(hb.to_pydict()["s"]) == ["x", "y"]
        assert d.strings == ["x", "y"]  # not polluted with "0"/"1"

    def test_eos_passthrough(self):
        hb = HostBatch.from_pydict({"x": [1, 2]})
        out = hb.to_device().to_host(eow=True, eos=True)
        assert out.eow and out.eos

    def test_merge_suffix_collision(self):
        r = Relation({"a": DataType.INT64, "a_y": DataType.INT64})
        merged = r.merge(Relation({"a": DataType.INT64}))
        assert merged.column_names == ("a", "a_y", "a_y_y")

    def test_encode_generator(self):
        d = StringDictionary()
        ids = d.encode(s for s in ["a", "b", "a"])
        assert list(ids) == [0, 1, 0]

    def test_decode_vectorized_null(self):
        d = StringDictionary(["a"])
        out = d.decode(np.array([0, -1, 5], dtype=np.int32))
        assert list(out) == ["a", None, None]
