"""Device-resident table windows (HBM cold store) + analyze stats + config."""

import numpy as np
import pytest

from pixie_tpu import config
from pixie_tpu.exec import Engine
from pixie_tpu.table_store import device_cache as dc
from pixie_tpu.table_store.table import Table
from pixie_tpu.types import DataType
from pixie_tpu.types.relation import Relation

W = 1 << 10  # MIN_CAPACITY-aligned small window for tests

QUERY = """
import px
df = px.DataFrame(table='events')
df = df[df.v >= 0]
out = df.groupby('svc').agg(n=('v', px.count), s=('v', px.sum))
px.display(out)
"""


def _mk_table(n, name="events"):
    rel = Relation([
        ("time_", DataType.TIME64NS),
        ("v", DataType.INT64),
        ("svc", DataType.STRING),
    ])
    t = Table(name, rel)
    rng = np.random.default_rng(3)
    t.append({
        "time_": np.arange(n, dtype=np.int64),
        "v": rng.integers(0, 100, n).astype(np.int64),
        "svc": [f"s{i % 5}" for i in range(n)],
    })
    return t


def _mk_engine(n, window_rows=W):
    e = Engine(window_rows=window_rows)
    rng = np.random.default_rng(3)
    e.append_data("events", {
        "time_": np.arange(n, dtype=np.int64),
        "v": rng.integers(-5, 100, n).astype(np.int64),
        "svc": [f"s{i % 5}" for i in range(n)],
    })
    return e


class TestDeviceScan:
    def test_append_stages_complete_windows(self, monkeypatch):
        monkeypatch.setenv("PIXIE_TPU_WINDOW_ROWS", str(W))
        t = _mk_table(3 * W + 17)
        # Three full windows staged at append; tail not yet.
        assert t._device_cache is not None
        assert len(t._device_cache) == 3
        wins = list(t.device_scan(window_rows=W))
        assert len(wins) == 4  # incl. on-demand tail
        total = sum(hi - lo for _, lo, hi in wins)
        assert total == 3 * W + 17

    def test_scan_cache_hits(self, monkeypatch):
        monkeypatch.setenv("PIXIE_TPU_WINDOW_ROWS", str(W))
        t = _mk_table(2 * W)
        calls = []
        orig = dc.stage_window

        def counting(table, k, w):
            calls.append(k)
            return orig(table, k, w)

        monkeypatch.setattr(dc, "stage_window", counting)
        list(t.device_scan(window_rows=W))
        list(t.device_scan(window_rows=W))
        assert calls == []  # both scans served fully from the append-time cache

    def test_tail_window_grows_and_supersedes(self, monkeypatch):
        monkeypatch.setenv("PIXIE_TPU_WINDOW_ROWS", str(W))
        t = _mk_table(W + 10)
        list(t.device_scan(window_rows=W))
        n_entries = len(t._device_cache)
        t.append({
            "time_": np.arange(10, dtype=np.int64) + W + 10,
            "v": np.arange(10, dtype=np.int64),
            "svc": ["s0"] * 10,
        })
        wins = list(t.device_scan(window_rows=W))
        assert sum(hi - lo for _, lo, hi in wins) == W + 20
        # The grown tail replaced the stale partial entry (no leak).
        assert len(t._device_cache) == n_entries

    def test_time_bounds(self, monkeypatch):
        monkeypatch.setenv("PIXIE_TPU_WINDOW_ROWS", str(W))
        t = _mk_table(2 * W)
        wins = list(t.device_scan(start_time=100, stop_time=W + 50, window_rows=W))
        assert sum(hi - lo for _, lo, hi in wins) == W + 50 - 100

    def test_byte_budget_eviction(self, monkeypatch):
        monkeypatch.setenv("PIXIE_TPU_WINDOW_ROWS", str(W))
        row_bytes = 8 + 8 + 4  # time i64 + v i64 + svc id i32
        monkeypatch.setenv(
            "PIXIE_TPU_DEVICE_CACHE_BYTES", str(2 * W * row_bytes)
        )
        t = _mk_table(4 * W)
        assert len(t._device_cache) == 2  # LRU kept the newest two
        assert t._device_cache.nbytes <= 2 * W * row_bytes

    def test_expiry_evicts(self, monkeypatch):
        monkeypatch.setenv("PIXIE_TPU_WINDOW_ROWS", str(W))
        rel = Relation([("time_", DataType.TIME64NS), ("v", DataType.INT64)])
        t = Table("ring", rel, max_bytes=2 * W * 16)
        for i in range(4):
            t.append({
                "time_": np.arange(W, dtype=np.int64) + i * W,
                "v": np.arange(W, dtype=np.int64),
            })
        first = t._backend.first_row_id()
        assert first > 0  # the ring expired early batches
        wins = list(t.device_scan(window_rows=W))
        assert all(lo >= first for _, lo, hi in wins)
        assert all(w.row0 + w.n > first for w, _, _ in wins)


class TestEngineResidency:
    def test_results_match_host_path(self, monkeypatch):
        n = 2 * W + 123
        monkeypatch.setenv("PIXIE_TPU_WINDOW_ROWS", str(W))
        e1 = _mk_engine(n)
        got1 = e1.execute_query(QUERY)["output"].to_pydict()
        monkeypatch.setenv("PIXIE_TPU_DEVICE_RESIDENCY", "0")
        e2 = _mk_engine(n)
        got2 = e2.execute_query(QUERY)["output"].to_pydict()
        o1, o2 = np.argsort(got1["svc"]), np.argsort(got2["svc"])
        for k in got1:
            assert np.array_equal(got1[k][o1], got2[k][o2]), k

    def test_steady_state_no_restaging(self, monkeypatch):
        monkeypatch.setenv("PIXIE_TPU_WINDOW_ROWS", str(W))
        e = _mk_engine(3 * W)  # exact multiple: no tail
        e.execute_query(QUERY)
        calls = []
        orig = dc.stage_window

        def counting(table, k, w):
            calls.append(k)
            return orig(table, k, w)

        monkeypatch.setattr(dc, "stage_window", counting)
        e.execute_query(QUERY)
        assert calls == []


    def test_a_missed_window_is_charged_to_bytes_restaged(self, monkeypatch):
        """A window the cache lost is staged again by the scan and lands
        in ``usage.bytes_restaged`` (device bytes, padded planes), not
        in ``bytes_staged``, which admission and pxbound read."""
        monkeypatch.setenv("PIXIE_TPU_WINDOW_ROWS", str(W))
        e = _mk_engine(3 * W)  # exact multiple: no tail
        cache = e.tables["events"]._device_cache
        resident = cache.nbytes
        assert len(cache) == 3 and resident > 0
        e.execute_query(QUERY)
        u = e.tracer.last().usage
        assert (u.bytes_staged, u.bytes_restaged) == (0, 0)
        cache.clear()
        e.execute_query(QUERY)
        u = e.tracer.last().usage
        assert (u.bytes_staged, u.bytes_restaged) == (0, resident)
        assert cache.nbytes == resident
        e.execute_query(QUERY)
        assert e.tracer.last().usage.bytes_restaged == 0


class TestAnalyze:
    def test_stats_recorded(self, monkeypatch):
        monkeypatch.setenv("PIXIE_TPU_WINDOW_ROWS", str(W))
        n = 2 * W + 7
        e = _mk_engine(n)
        out = e.execute_query(QUERY, analyze=True)
        assert "output" in out
        stats = e.last_stats
        assert stats is not None and stats.total_seconds > 0
        d = stats.to_dict()
        frag = d["fragments"][-1]
        assert frag["windows"] == 3
        assert frag["rows_in"] == n
        assert frag["rows_out"] == 5  # five services
        assert "compute" in frag["stages"] and "finalize" in frag["stages"]
        assert frag["stages"]["compute"]["seconds"] > 0
        # analyze off leaves last_stats untouched from prior run
        e.execute_query(QUERY)
        assert e.last_stats is stats


class TestConfig:
    def test_env_and_override(self, monkeypatch):
        monkeypatch.setenv("PIXIE_TPU_FOLD_SCAN_WINDOWS", "8")
        assert config.get_flag("fold_scan_windows") == 8
        config.set_flag("fold_scan_windows", 4)
        assert config.get_flag("fold_scan_windows") == 4
        config.clear_flag("fold_scan_windows")
        assert config.get_flag("fold_scan_windows") == 8
        monkeypatch.delenv("PIXIE_TPU_FOLD_SCAN_WINDOWS")
        assert config.get_flag("fold_scan_windows") == 16

    def test_bool_parse(self, monkeypatch):
        monkeypatch.setenv("PIXIE_TPU_DEVICE_RESIDENCY", "false")
        assert config.get_flag("device_residency") is False
        monkeypatch.setenv("PIXIE_TPU_DEVICE_RESIDENCY", "1")
        assert config.get_flag("device_residency") is True

    def test_all_flags_listing(self):
        flags = config.all_flags()
        assert "window_rows" in flags and "device_cache_bytes" in flags
        assert all(len(v) == 2 for v in flags.values())
