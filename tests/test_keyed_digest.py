"""A ``quantiles`` aggregate of a KEYED group-by (PR 41): the digest
built beside the keyed integer fold by a sort of its own
(``ops/tdigest.py`` ``ordered_batch_to_digest``), group for group what
the dense route's digest holds; the merge and the read-out of ordered
digests without a sort (``merge_ordered``, ``digest_quantile``) against
the sorts they replaced; the slot numbering a digest follows its group
by (``ops/groupby.py`` ``sorted_slot_ids``); and the cell's AggOp through
an engine on both platforms' routes, windows whose groups arrive late
among them."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import routes_of
from pixie_tpu.config import override_flag
from pixie_tpu.exec.engine import Engine
from pixie_tpu.exec.plan import (
    AggExpr, AggOp, ColumnRef as C, MemorySourceOp, Plan, ResultSinkOp,
)
from pixie_tpu.ops import routes, tdigest as td
from pixie_tpu.ops.groupby import (
    lead_words, sorted_group_fold, sorted_slot_ids,
)

K = td.DEFAULT_K
U32_MAX = np.uint32(0xFFFFFFFF)


def _latencies(rng, n):
    return np.exp(rng.normal(15.0, 1.2, n)).astype(np.int64).astype(np.float32)


def _sorted_compress(means, weights, k=K):
    """What ``_compress`` did until PR 41 for centroids in any order: a
    row-wise stable sort by mean, then the ordered re-binning."""
    order = jnp.argsort(jnp.where(weights > 0, means, jnp.inf), axis=-1,
                        stable=True)
    return td._compress(jnp.take_along_axis(means, order, axis=-1),
                        jnp.take_along_axis(weights, order, axis=-1), k)


def _sorted_quantile(carry, qs):
    """``digest_quantile`` as it read a digest until PR 41."""
    means, weights = carry
    order = jnp.argsort(jnp.where(weights > 0, means, jnp.inf), axis=-1,
                        stable=True)
    m = jnp.take_along_axis(means, order, axis=-1)
    w = jnp.take_along_axis(weights, order, axis=-1)
    total = jnp.sum(w, axis=-1)
    cmid = jnp.cumsum(w, axis=-1) - w * 0.5
    fm = jax.lax.cummax(jnp.where(w > 0, m, -jnp.inf), axis=1)
    fc = jnp.where(w > 0, cmid, total[:, None])
    qs = jnp.asarray(qs, jnp.float32)
    out = jax.vmap(lambda m, c, t: jnp.interp(qs * t, c, m))(fm, fc, total)
    return jnp.where(total[:, None] > 0, out, jnp.nan)


def _digest_of(rng, groups, rows, share=0.9):
    gid = rng.integers(0, groups, rows)
    v = _latencies(rng, rows)
    keep = rng.random(rows) < share
    return td.batch_to_digest(jnp.asarray(v), jnp.asarray(gid),
                              jnp.asarray(keep), groups, K)


def _assert_ordered(digest):
    means, weights = (np.asarray(p) for p in digest)
    for m, w in zip(means, weights):
        assert np.all(np.diff(m[w > 0]) >= 0)
        assert np.all(m[w == 0] == 0)


# -- the merge and the read-out against the sorts they replaced ---------------

@pytest.mark.parametrize("groups,rows", [(1, 50), (37, 5000), (300, 2000)])
def test_merge_ordered_is_the_sorted_compress(groups, rows):
    rng = np.random.default_rng(groups)
    a, b = _digest_of(rng, groups, rows), _digest_of(rng, groups, rows)
    want = _sorted_compress(jnp.concatenate([a[0], b[0]], -1),
                            jnp.concatenate([a[1], b[1]], -1))
    got = td.merge_ordered(a, b)
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               rtol=1e-6)
    _assert_ordered(got)


def test_equal_means_go_to_the_first_side_as_the_stable_sort_had_them():
    """Single rows of equal value on both sides: the weights' places
    follow the tie rule, and the total is kept."""
    v = jnp.full((1, K), 0.0).at[0, :4].set(jnp.asarray([5.0, 5.0, 7.0, 9.0]))
    w = jnp.zeros((1, K)).at[0, :4].set(1.0)
    got = td.merge_ordered((v, w), (v, w))
    want = _sorted_compress(jnp.concatenate([v, v], -1),
                            jnp.concatenate([w, w], -1))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]))
    assert float(got[1].sum()) == 8.0


def test_a_merge_with_an_empty_side_passes_the_other_through():
    rng = np.random.default_rng(3)
    a = _digest_of(rng, 17, 900)
    empty = td.digest_init(17, K)
    for got in (td.digest_merge(empty, a), td.digest_merge(a, empty)):
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(a[0]))
        np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(a[1]))
    both = td.digest_merge(a, a)
    assert float(both[1].sum()) == 2 * float(a[1].sum())
    _assert_ordered(both)


@pytest.mark.parametrize("groups,rows", [(1, 9), (5, 40), (37, 5000)])
def test_the_read_out_is_the_sorted_interpolation(groups, rows):
    rng = np.random.default_rng(rows)
    digest = td.merge_ordered(_digest_of(rng, groups, rows),
                              _digest_of(rng, groups, rows, share=0.5))
    qs = (0.01, 0.25, 0.5, 0.9, 0.99)
    got = np.asarray(td.digest_quantile(digest, qs))
    want = np.asarray(_sorted_quantile(digest, qs))
    np.testing.assert_allclose(got, want, rtol=2e-6, equal_nan=True)


def test_an_empty_group_reads_nan_and_a_single_row_itself():
    means = jnp.zeros((3, K)).at[1, 70].set(42.0)
    weights = jnp.zeros((3, K)).at[1, 70].set(1.0)
    got = np.asarray(td.digest_quantile((means, weights), (0.01, 0.5, 0.99)))
    assert np.all(np.isnan(got[0])) and np.all(np.isnan(got[2]))
    np.testing.assert_array_equal(got[1], [42.0, 42.0, 42.0])


# -- the histogram's widths, and what happens past them -----------------------

@pytest.mark.parametrize("groups,bins", [
    (1, 8192), (33, 8192), (4096, 8192), (4097, 4096), (8192, 4096),
    (8193, 0), (1 << 17, 0),
])
def test_a_histogram_is_built_at_two_widths_and_no_narrower(groups, bins):
    assert routes.digest_hist_bins(groups) == bins
    assert routes.digest_bins(groups, False) == (bins or 1 << 32)
    assert routes.digest_bins(groups, True) == 1 << 32


@pytest.mark.parametrize("platform", ["cpu", "tpu"])
def test_past_the_histograms_widths_rows_sort_by_their_values(platform):
    """16,384 groups (a rehearsal's capacity): no 2,048-bin histogram;
    every group of a handful of rows keeps each row a centroid."""
    rng = np.random.default_rng(11)
    groups, rows = 1 << 14, 6000
    gid = rng.integers(0, 2000, rows) * 7
    v = _latencies(rng, rows)
    mask = rng.random(rows) < 0.95
    with routes_of(platform):
        digest = td.batch_to_digest(jnp.asarray(v), jnp.asarray(gid),
                                    jnp.asarray(mask), groups, K)
    weights = np.asarray(digest[1])
    counts = np.bincount(gid[mask], minlength=groups)
    np.testing.assert_array_equal(weights.sum(axis=1), counts)
    assert weights.max() == 1.0  # no two rows of a group share a centroid
    q = np.asarray(td.digest_quantile(digest, (0.5,)))[:, 0]
    for g in np.flatnonzero(counts)[:50]:
        rows_g = np.sort(v[mask & (gid == g)])
        assert rows_g[0] <= q[g] <= rows_g[-1]


# -- the keyed digest against the dense route's, group for group --------------

def _keyed_rows(rng, rows, keys, share_valid=0.97):
    key = (rng.integers(0, keys, rows).astype(np.uint32) * np.uint32(977)
           + np.uint32(5))
    valid = rng.random(rows) < share_valid
    return key, valid, _latencies(rng, rows)


@pytest.mark.parametrize("folded", [True, False])
def test_a_keyed_digest_is_the_dense_routes_digest_of_the_same_rows(folded):
    rng = np.random.default_rng(41)
    key, valid, v = _keyed_rows(rng, 30_000, 200)
    slots = 256
    lead = lead_words([jnp.asarray(key)], jnp.asarray(valid), folded)
    keyed = td.ordered_batch_to_digest(lead, folded, jnp.asarray(v), slots, K)
    # The dense route: the group ids are the keys' ranks, as the sorted
    # fold numbers them.
    distinct = np.unique(key[valid])
    gid = np.searchsorted(distinct, key)
    with routes_of("cpu"):
        dense = td.batch_to_digest(jnp.asarray(v), jnp.asarray(gid),
                                   jnp.asarray(valid), slots, K)
    _assert_ordered(keyed)
    np.testing.assert_array_equal(np.asarray(keyed[1]).sum(axis=1),
                                  np.asarray(dense[1]).sum(axis=1))
    assert float(np.asarray(keyed[1])[len(distinct):].sum()) == 0.0
    qs = (0.5, 0.9, 0.99)
    got = np.asarray(td.digest_quantile(keyed, qs))[:len(distinct)]
    binned = np.asarray(td.digest_quantile(dense, qs))[:len(distinct)]
    err = {"keyed": [], "binned": []}
    for g, k in enumerate(distinct):
        rows = np.sort(v[valid & (key == k)])
        n = len(rows)
        for j, q in enumerate(qs):
            # Within two rows of the rank q asks for (a digest of ~150
            # rows keeps nearly every row a centroid of its own).
            lo = rows[max(int(np.floor(q * n)) - 2, 0)]
            hi = rows[min(int(np.ceil(q * n)) + 1, n - 1)]
            assert lo <= got[g, j] <= hi, (k, q, got[g, j], lo, hi)
        exact = np.quantile(rows, 0.5)
        err["keyed"].append(abs(got[g, 0] - exact) / exact)
        err["binned"].append(abs(binned[g, 0] - exact) / exact)
    # The same estimate up to a bin's width, and the unbinned digest no
    # farther from the exact median than the binned one.
    assert np.max(np.abs(got[:, 0] - binned[:, 0]) / binned[:, 0]) <= 0.05
    assert np.mean(err["keyed"]) <= np.mean(err["binned"]) * 1.05


def test_a_row_that_is_no_number_keeps_its_groups_place_and_adds_nothing():
    key = np.asarray([7, 7, 3, 3, 9, 5], np.uint32)
    v = np.asarray([np.nan, np.inf, 2.0, 4.0, 8.0, -np.inf], np.float32)
    valid = np.ones(6, bool)
    lead = lead_words([jnp.asarray(key)], jnp.asarray(valid), True)
    means, weights = td.ordered_batch_to_digest(
        lead, True, jnp.asarray(v), 8, K)
    # Groups in key order: 3, 5, 7, 9. Those of 5 and 7 hold no number.
    np.testing.assert_array_equal(np.asarray(weights).sum(axis=1),
                                  [2, 0, 0, 1, 0, 0, 0, 0])
    got = np.asarray(td.digest_quantile((means, weights), (0.5,)))[:, 0]
    assert got[0] == 3.0 and got[3] == 8.0
    assert np.isnan(got[1]) and np.isnan(got[2])


def test_groups_past_the_slots_are_dropped():
    rng = np.random.default_rng(2)
    key, valid, v = _keyed_rows(rng, 4000, 100, share_valid=1.0)
    lead = lead_words([jnp.asarray(key)], jnp.asarray(valid), True)
    _means, weights = td.ordered_batch_to_digest(
        lead, True, jnp.asarray(v), 64, K)
    distinct = np.unique(key)
    kept = np.isin(key, distinct[:64])
    assert float(np.asarray(weights).sum()) == kept.sum()


# -- the slots a digest follows its group to ----------------------------------

@pytest.mark.parametrize("folded", [True, False])
def test_the_slot_of_a_row_is_the_sorted_folds_slot_of_its_key(folded):
    rng = np.random.default_rng(17)
    key, valid, _v = _keyed_rows(rng, 5000, 300)
    key2 = rng.integers(0, 3, 5000).astype(np.uint32)
    words = [jnp.asarray(key), jnp.asarray(key2)]
    g = 1024
    dest = np.asarray(sorted_slot_ids(words, jnp.asarray(valid), g, folded))
    keys_g, valid_g, _rows, _s, _m, n = sorted_group_fold(
        words, jnp.asarray(valid), [], [], g, folded_flag=folded)
    keys_g = [np.asarray(k) for k in keys_g]
    assert int(n) == len(set(zip(key[valid], key2[valid])))
    assert np.all(dest[~valid] == g)
    at = dest[valid]
    np.testing.assert_array_equal(keys_g[0][at], key[valid])
    np.testing.assert_array_equal(keys_g[1][at], key2[valid])
    assert np.all(np.asarray(valid_g)[at])


def test_an_overflowing_key_goes_to_the_trash_slot():
    key = jnp.arange(10, dtype=jnp.uint32)
    dest = np.asarray(sorted_slot_ids([key], jnp.ones(10, bool), 4, True))
    np.testing.assert_array_equal(dest, [0, 1, 2, 3, 4, 4, 4, 4, 4, 4])


# -- the cell's AggOp through an engine, on both platforms' routes ------------

def _edge_rows(rng, rows, late_share=0.0):
    """``remote_addr`` x ``pod`` x ``service`` rows; a share of the
    edges shows up only in the table's later half, so that a window's
    groups push the state's slots along."""
    addr = rng.integers(0, 40, rows)
    pod = rng.integers(0, 12, rows)
    late = (addr % 5 == 0) & (np.arange(rows) < rows * late_share)
    addr = np.where(late, addr + 1, addr)
    return {
        "time_": np.arange(rows, dtype=np.int64),
        "remote_addr": [f"10.0.0.{a}" for a in addr],
        "pod": [f"svc-{p % 3}/pod-{p}" for p in pod],
        "service": [f"svc-{p % 3}" for p in pod],
        "latency_ns": np.exp(rng.normal(15.0, 1.2, rows)).astype(np.int64),
        "failure": rng.random(rows) < 0.1,
        "resp_body_size": rng.integers(64, 1 << 20, rows),
    }


def _graph_plan(max_groups=4096, plucks=("p50", "p99"), more=()):
    p = Plan()
    src = p.add(MemorySourceOp(table="t"))
    agg = p.add(AggOp(
        ("remote_addr", "pod", "service"),
        tuple(AggExpr(q, f"_quantile_{q}", (C("latency_ns"),))
              for q in plucks) + tuple(more) +
        (AggExpr("error_rate", "mean", (C("failure"),)),
         AggExpr("n", "count", (C("latency_ns"),)),
         AggExpr("bytes", "sum", (C("resp_body_size"),))),
        max_groups=max_groups,
    ), [src])
    p.add(ResultSinkOp("output"), [agg])
    return p


def _by_edge(rows):
    out = {}
    for i in range(len(rows["time_"])):
        k = (rows["remote_addr"][i], rows["pod"][i], rows["service"][i])
        out.setdefault(k, []).append(i)
    return out


@pytest.mark.parametrize("late_share", [0.0, 0.5])
@pytest.mark.parametrize("platform", ["tpu", "cpu"])
def test_the_service_graph_through_an_engine(platform, late_share):
    rng = np.random.default_rng(4141)
    rows = _edge_rows(rng, 6000, late_share)
    with routes_of(platform), override_flag("cpu_fold_threads", 1), \
            override_flag("dense_domain_limit", 64):
        eng = Engine(window_rows=1 << 10)  # six windows, merged
        eng.append_data("t", rows)
        out = eng.execute_plan(_graph_plan())["output"].to_pydict()
        trace = eng.tracer.last()
    folds = [s.attributes for s in trace.spans
             if s.name == "device.dispatch" and "fold" in s.attributes]
    # ONE carry for the two plucked quantiles of one column.
    assert folds and all(
        (a["digests"], a["digest_outputs"]) == (1, 2) for a in folds)
    assert all(a["digest_slots"] == 4096 * K for a in folds)
    if platform == "tpu":
        assert {a["fold"] for a in folds} == {
            "mixed:sorted_int=3,keyed_digest=2"}
        assert {a["digest_bins"] for a in folds} == {1 << 32}
    else:
        assert {a["fold"] for a in folds} == {"xla"}
        assert {a["digest_bins"] for a in folds} == {8192}
    want = _by_edge(rows)
    got = {(a, p, s): i for i, (a, p, s) in enumerate(
        zip(out["remote_addr"], out["pod"], out["service"]))}
    assert set(got) == set(want) and len(got) == len(out["n"])
    lat = rows["latency_ns"]
    for k, idx in want.items():
        i = got[k]
        assert out["n"][i] == len(idx)
        assert out["bytes"][i] == int(rows["resp_body_size"][idx].sum())
        assert abs(out["error_rate"][i]
                   - rows["failure"][idx].mean()) <= 1e-6
        v = np.sort(lat[idx])
        for col, q in (("p50", 0.5), ("p99", 0.99)):
            est = out[col][i]
            # Inside a row's slack of the rank q asks for.
            under, at_or_under = np.mean(v < est), np.mean(v <= est)
            slack = (1.0 if platform == "tpu" else 2.0) / len(v) + 0.01
            assert under - slack <= q <= at_or_under + slack, (k, col, est, v)


_ALL = ("p50", "p90", "p99")


def _graph_answer(platform, plucks, more=()):
    """{edge: row} of the service graph's AggOp with these plucks,
    through an engine of six windows, and its fold dispatches."""
    rows = _edge_rows(np.random.default_rng(4242), 6000, 0.5)
    with routes_of(platform), override_flag("cpu_fold_threads", 1), \
            override_flag("dense_domain_limit", 64):
        eng = Engine(window_rows=1 << 10)
        eng.append_data("t", rows)
        out = eng.execute_plan(
            _graph_plan(plucks=plucks, more=more))["output"].to_pydict()
        trace = eng.tracer.last()
    folds = [s.attributes for s in trace.spans
             if s.name == "device.dispatch" and "fold" in s.attributes]
    cols = [c for c in out if c not in ("remote_addr", "pod", "service")]
    return {
        k: {c: out[c][i] for c in cols}
        for i, k in enumerate(zip(out["remote_addr"], out["pod"],
                                  out["service"]))
    }, folds


_shared_answers = {}


@pytest.mark.parametrize("pluck", _ALL)
@pytest.mark.parametrize("platform", ["tpu", "cpu"])
def test_a_pluck_beside_the_others_answers_as_alone(platform, pluck):
    """One digest an argument through an engine: the three plucks (and
    a second column's, with a carry of its own) in one AggOp against the
    AggOp with this pluck alone, VALUE FOR VALUE on every edge; the
    span says how many carries and how many outputs."""
    if platform not in _shared_answers:
        _shared_answers[platform] = _graph_answer(
            platform, _ALL,
            more=(AggExpr("size_p50", "_quantile_p50",
                          (C("resp_body_size"),)),))
    shared, folds = _shared_answers[platform]
    assert folds and all(
        (a["digests"], a["digest_outputs"]) == (2, 4) for a in folds)
    alone, a_folds = _graph_answer(platform, (pluck,))
    assert all((a["digests"], a["digest_outputs"]) == (1, 1)
               for a in a_folds)
    assert set(alone) == set(shared) and len(alone) > 300
    for k, row in alone.items():
        for c in ("n", "bytes", "error_rate", pluck):
            np.testing.assert_array_equal(
                np.float64(shared[k][c]), np.float64(row[c]), err_msg=str(k))


def test_the_chip_smokes_edges_phase_rehearses():
    """``chip_smoke.py``'s ``edges`` phase on the CPU under the TPU's
    routes: the cell's script over the new builder's data against the
    reference, the ``fold`` label, the digests' attributes and no
    program compiled by the warm run (its own assertions)."""
    import chip_smoke

    with routes_of("tpu"), override_flag("cpu_fold_threads", 1):
        chip_smoke.phase_edges(41, 1 << 15, chip_smoke.CompileMeter(), False)
