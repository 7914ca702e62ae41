"""Randomized join-strategy equivalence suite (ISSUE 9).

Pits every N:M execution path — host hash (``host``), single-shot
device kernel (``single``), windowed sorted-probe (``sorted``), windowed
radix-partitioned (``radix``) — and the host-dict N:1 path against a
pure-python reference join, across ``how`` variants, null string keys,
duplicate-heavy (N:M) keys, empty sides, build-side swap and the
forced overflow-retry path. All paths must agree BIT-IDENTICALLY after
output canonicalization (the engine's join has no row-order contract;
rows are compared as multisets of value tuples).
"""

import collections

import numpy as np
import pytest

import pixie_tpu.exec.joins as joins_mod
from pixie_tpu.config import override_flag
from pixie_tpu.exec.engine import Engine
from pixie_tpu.exec.plan import JoinOp, MemorySourceOp, Plan, ResultSinkOp

STRATEGIES = ("host", "single", "sorted", "radix")
WINDOW = 64  # small windows force the multi-window drivers


def _ref_join(lk, rk, how):
    """Reference join -> multiset of (l_idx|None, r_idx|None) pairs."""
    r_by_key: dict = collections.defaultdict(list)
    for j, k in enumerate(rk):
        r_by_key[k].append(j)
    out = []
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
    return collections.Counter(out)


def _canon(out, n_l, n_r):
    """Engine output -> the reference pair multiset (values chosen so 0
    unambiguously means null: lv = i + 1, rv = j + 1)."""
    return collections.Counter(
        (int(a) - 1 if a else None, int(b) - 1 if b else None)
        for a, b in zip(out["lv"].tolist(), out["rv"].tolist())
    )


def _run_strategy(lk, rk, how, strategy, window=WINDOW, min_rows=0):
    lk = np.asarray(lk, dtype=np.int64)
    rk = np.asarray(rk, dtype=np.int64)
    e = Engine()
    e.append_data("l", {"k": lk, "lv": np.arange(1, len(lk) + 1,
                                                 dtype=np.int64)},
                  time_cols=())
    e.append_data("r", {"k": rk, "rv": np.arange(1, len(rk) + 1,
                                                 dtype=np.int64)},
                  time_cols=())
    p = Plan()
    s1 = p.add(MemorySourceOp(table="l"))
    s2 = p.add(MemorySourceOp(table="r"))
    j = p.add(JoinOp(left_on=("k",), right_on=("k",), how=how), [s1, s2])
    p.add(ResultSinkOp("output"), [j])
    old = joins_mod.DEVICE_JOIN_MIN_ROWS
    joins_mod.DEVICE_JOIN_MIN_ROWS = min_rows
    try:
        with override_flag("join_strategy", strategy), \
                override_flag("join_probe_window_rows", window):
            out = e.execute_plan(p)["output"].to_pydict()
    finally:
        joins_mod.DEVICE_JOIN_MIN_ROWS = old
    return _canon(out, len(lk), len(rk)), e


class TestStrategyEquivalence:
    @pytest.mark.parametrize("how", ["inner", "left", "right", "outer"])
    def test_randomized_all_strategies(self, how):
        rng = np.random.default_rng(11)
        for _trial in range(3):
            n_l = int(rng.integers(1, 400))
            n_r = int(rng.integers(1, 300))
            lk = rng.integers(0, 60, n_l)
            rk = rng.integers(20, 80, n_r)
            ref = _ref_join(lk.tolist(), rk.tolist(), how)
            for s in STRATEGIES:
                got, _e = _run_strategy(lk, rk, how, s)
                assert got == ref, (how, s, n_l, n_r)

    @pytest.mark.parametrize("how", ["inner", "left"])
    def test_duplicate_heavy_nm(self, how):
        rng = np.random.default_rng(13)
        lk = rng.integers(0, 5, 300)  # ~60 rows per key each side
        rk = rng.integers(0, 5, 200)
        ref = _ref_join(lk.tolist(), rk.tolist(), how)
        for s in STRATEGIES:
            got, _e = _run_strategy(lk, rk, how, s)
            assert got == ref, (how, s)

    @pytest.mark.parametrize("how", ["inner", "left", "right", "outer"])
    def test_empty_sides(self, how):
        for n_l, n_r in ((0, 5), (5, 0), (0, 0)):
            lk = np.arange(n_l)
            rk = np.arange(n_r)
            ref = _ref_join(lk.tolist(), rk.tolist(), how)
            for s in STRATEGIES:
                got, _e = _run_strategy(lk, rk, how, s)
                assert got == ref, (how, s, n_l, n_r)

    def test_build_side_swap_matches(self):
        """A heavily imbalanced inner join (build >> probe rows swapped
        to probe the big side) must emit the same pair multiset."""
        rng = np.random.default_rng(17)
        lk = rng.integers(0, 50, 60)
        rk = rng.integers(0, 50, 1200)  # >4x left -> swap candidate
        ref = _ref_join(lk.tolist(), rk.tolist(), "inner")
        for s in ("sorted", "radix"):
            got, e = _run_strategy(lk, rk, "inner", s)
            assert got == ref, s
            assert e.last_join_decision.swap, s

    def test_zone_skip_left_join_clustered(self):
        """Clustered probe keys + narrow build range: most windows are
        zone-skipped; a LEFT join must still emit their null rows."""
        lk = np.arange(1000)  # ascending: each window spans ~64 keys
        rk = np.arange(950, 980)  # only the tail windows can match
        ref = _ref_join(lk.tolist(), rk.tolist(), "left")
        for s in ("sorted", "radix"):
            got, e = _run_strategy(lk, rk, "left", s)
            assert got == ref, s
            assert e.last_join_decision.skipped_windows > 0, s
        # Inner: same skip, matching rows only.
        ref_i = _ref_join(lk.tolist(), rk.tolist(), "inner")
        got, e = _run_strategy(lk, rk, "inner", "sorted")
        assert got == ref_i
        assert e.last_join_decision.skipped_windows > 0

    def test_forced_overflow_retry_path(self, monkeypatch):
        """A deliberately wrong capacity estimate must retry doubled
        (counted) and still produce the exact join."""
        monkeypatch.setattr(
            joins_mod, "estimate_join_capacity", lambda *a, **k: 16
        )
        monkeypatch.setattr(
            joins_mod, "learned_capacity", lambda eng, k: None
        )
        rng = np.random.default_rng(19)
        lk = rng.integers(0, 10, 400)  # ~40 matches per probe row
        rk = rng.integers(0, 10, 400)
        ref = _ref_join(lk.tolist(), rk.tolist(), "inner")
        for s in ("single", "sorted", "radix"):
            got, e = _run_strategy(lk, rk, "inner", s)
            assert got == ref, s
            assert e.last_join_decision.retries > 0, s
            assert e.tracer.registry.counter(
                "pixie_join_capacity_retries_total"
            ).value() > 0

    def test_learned_capacity_skips_reclimb(self):
        """Second run of the same plan starts at the learned rung: zero
        additional retries."""
        rng = np.random.default_rng(23)
        lk = rng.integers(0, 10, 400)
        rk = rng.integers(0, 10, 400)
        e = Engine()
        e.append_data("l", {"k": lk.astype(np.int64),
                            "lv": np.arange(400, dtype=np.int64)},
                      time_cols=())
        e.append_data("r", {"k": rk.astype(np.int64),
                            "rv": np.arange(400, dtype=np.int64)},
                      time_cols=())
        q = """
import px
l = px.DataFrame(table='l')
r = px.DataFrame(table='r')
g = l.merge(r, how='inner', left_on=['k'], right_on=['k'], suffixes=['', '_r'])
px.display(g, 'j')
"""
        old = joins_mod.DEVICE_JOIN_MIN_ROWS
        joins_mod.DEVICE_JOIN_MIN_ROWS = 0
        try:
            with override_flag("join_strategy", "sorted"), \
                    override_flag("join_probe_window_rows", WINDOW):
                e.execute_query(q, max_output_rows=1 << 62)
                first = e.tracer.registry.counter(
                    "pixie_join_capacity_retries_total"
                ).value()
                e.execute_query(q, max_output_rows=1 << 62)
                second = e.tracer.registry.counter(
                    "pixie_join_capacity_retries_total"
                ).value()
        finally:
            joins_mod.DEVICE_JOIN_MIN_ROWS = old
        assert second == first  # no re-climb on the repeat run

    @pytest.mark.parametrize("strategy,route", [
        ("auto", "host_table"), ("host", "host_dict"),
    ])
    def test_host_n1_routes_agree_on_unique_build(self, strategy, route):
        """The N:1 host paths agree with every forced bulk strategy: the
        table lookup (the auto route of a unique dense build) and the
        small-input dict join, which a forced strategy still reaches
        under the row limit."""
        rng = np.random.default_rng(29)
        lk = rng.integers(0, 40, 200)
        rk = rng.permutation(40)[:30]  # unique build keys
        for how in ("inner", "left"):
            ref = _ref_join(lk.tolist(), rk.tolist(), how)
            got, e = _run_strategy(lk, rk, how, strategy,
                                   min_rows=1 << 15)
            assert got == ref
            assert e.last_join_decision.strategy == route
            for s in STRATEGIES:
                got_s, _e = _run_strategy(lk, rk, how, s)
                assert got_s == ref, (how, s)


class TestNullStringKeys:
    @pytest.mark.parametrize("strategy", ["host", "single", "sorted"])
    def test_null_ids_consistent_across_paths(self, strategy):
        """Divergent dictionaries leave unseen build strings remapped to
        NULL_ID; every path must treat those identically (bit-identical
        output multisets across strategies IS the contract here)."""
        e = Engine()
        e.append_data("l", {"s": ["a", "b", "c", "b", "e"]}, time_cols=())
        e.append_data(
            "r",
            {"s": ["b", "d", "b", "e"],
             "v": np.array([1, 2, 3, 4], dtype=np.int64)},
            time_cols=(),
        )
        p = Plan()
        s1 = p.add(MemorySourceOp(table="l"))
        s2 = p.add(MemorySourceOp(table="r"))
        j = p.add(JoinOp(left_on=("s",), right_on=("s",), how="inner"),
                  [s1, s2])
        p.add(ResultSinkOp("output"), [j])
        old = joins_mod.DEVICE_JOIN_MIN_ROWS
        joins_mod.DEVICE_JOIN_MIN_ROWS = 0
        try:
            with override_flag("join_strategy", strategy), \
                    override_flag("join_probe_window_rows", 2):
                out = e.execute_plan(p)["output"].to_pydict()
        finally:
            joins_mod.DEVICE_JOIN_MIN_ROWS = old
        rows = sorted(zip(out["s"], out["v"].tolist()))
        assert rows == [("b", 1), ("b", 1), ("b", 3), ("b", 3), ("e", 4)]
