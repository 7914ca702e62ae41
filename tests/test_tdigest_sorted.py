"""The quantile digest's window on the TPU's routes (``ops/tdigest.py``
``_sorted_batch_to_digest``): rows reach their centroids by one
payload-carrying sort and a reduction of sorted ids
(``ops/pallas_tdigest.py`` ``sorted_centroid_fold``, interpreted here),
against the scatter route of the same rows (the CPU's: two scatters into
the [G, B] histogram, then its compress) and against ``numpy.quantile``
under the benchmark's limits."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import pixie_tpu  # noqa: F401  (x64 on)
from conftest import routes_of
from pixie_tpu.ops import tdigest

QS = (0.01, 0.10, 0.25, 0.50, 0.75, 0.90, 0.99)
#: The benchmark's limits on ``service_stats.p50_relerr`` / ``p99_relerr``
#: (``benchmark/`` reads them against ``numpy.quantile``), held here for
#: every group of at least this many live rows.
P50_LIMIT, P99_LIMIT, LIMITS_FROM_ROWS = 0.012, 0.075, 20_000


def _lognormal(rng, n):
    return np.exp(rng.normal(15, 1.2, n))


def _case(name):
    """(values f64[n], gids i32[n], mask bool[n], G) of a named window."""
    rng = np.random.default_rng(sum(map(ord, name)))
    n, g = 60_000, 33
    if name in ("one_group", "two_groups", "service_stats_33"):
        g = {"one_group": 1, "two_groups": 2, "service_stats_33": 33}[name]
    elif name == "bins_shrink_at_8192_groups":
        n, g = 200_000, 8192  # ``_hist_bins``: B = 4,096
    elif name == "shorter_than_a_kernel_chunk":
        n, g = 300, 3
    elif name == "not_a_whole_block":
        n = 5_001  # padded to 6,144 rows for the kernel's tiling
    values = _lognormal(rng, n)
    gids = rng.integers(0, g, n)
    mask = np.ones(n, dtype=bool)
    if name == "padded_window_30pct_live":
        mask = np.arange(n) < int(0.3 * n)  # a padded window: a live prefix
    elif name == "nan_and_inf_rows":
        values[rng.random(n) < 0.05] = np.nan
        values[rng.random(n) < 0.02] = np.inf
        values[rng.random(n) < 0.02] = -np.inf
    elif name == "empty_groups":
        gids = rng.choice([0, 7, 8, 31, 32], n)
    elif name == "one_value_repeated":
        values[:] = 1_234_567.0
    elif name == "spread_31_to_1":
        sizes = np.linspace(31, 1, g)
        gids = rng.choice(g, n, p=sizes / sizes.sum())
    elif name == "negative_values":
        values = rng.normal(0, 1e6, n)
    elif name == "a_chunk_spans_tiles":
        # A group of 100 rows beside one of 10^5: the chunk that holds
        # the small group's rows holds its neighbours' too.
        n = 100_100 + 900
        values = _lognormal(rng, n)
        gids = np.concatenate([np.full(100_000, 4), np.full(100, 5),
                               rng.integers(6, 12, 900)])
        order = rng.permutation(n)
        gids = gids[order]
        mask = np.ones(n, dtype=bool)
    elif name == "no_live_row":
        mask[:] = False
    elif name == "masked_rows_scattered":
        mask = rng.random(n) < 0.7
    return values, gids.astype(np.int32), mask, g


CASES = [
    "one_group", "two_groups", "service_stats_33",
    "bins_shrink_at_8192_groups", "padded_window_30pct_live",
    "nan_and_inf_rows", "empty_groups", "one_value_repeated",
    "spread_31_to_1", "negative_values", "shorter_than_a_kernel_chunk",
    "a_chunk_spans_tiles", "not_a_whole_block", "no_live_row",
    "masked_rows_scattered",
]


def _digest(platform, values, gids, mask, g):
    with routes_of(platform):
        fold = jax.jit(lambda v, i, m: tdigest.batch_to_digest(v, i, m, g))
        means, weights = fold(jnp.asarray(values, jnp.float32),
                              jnp.asarray(gids), jnp.asarray(mask))
    return means, weights


@pytest.mark.parametrize("name", CASES)
def test_sorted_route_equals_scatter_route(name):
    values, gids, mask, g = _case(name)
    scatter = _digest("cpu", values, gids, mask, g)
    sorted_ = _digest("tpu", values, gids, mask, g)
    live = mask & np.isfinite(values.astype(np.float32))
    rows = np.bincount(gids[live], minlength=g)

    # A group's weights sum exactly to its live rows, centroid by
    # centroid as the scatter route has them.
    np.testing.assert_array_equal(np.asarray(sorted_[1]).sum(axis=1), rows)
    np.testing.assert_array_equal(np.asarray(sorted_[1]),
                                  np.asarray(scatter[1]))

    got = np.asarray(tdigest.digest_quantile(sorted_, QS))
    want = np.asarray(tdigest.digest_quantile(scatter, QS))
    assert (np.isnan(got) == (rows == 0)[:, None]).all()
    assert (np.isnan(want) == (rows == 0)[:, None]).all()
    seen = rows > 0
    np.testing.assert_allclose(got[seen], want[seen], rtol=1e-3)

    for grp in np.flatnonzero(rows >= LIMITS_FROM_ROWS):
        exact = np.quantile(values[live & (gids == grp)], [0.5, 0.99])
        p50, p99 = got[grp, QS.index(0.50)], got[grp, QS.index(0.99)]
        assert abs(p50 / exact[0] - 1) <= P50_LIMIT, (name, grp)
        assert abs(p99 / exact[1] - 1) <= P99_LIMIT, (name, grp)


def test_update_and_merge_carry_the_sorted_digest():
    """``digest_update`` on the TPU's routes folds windows into a carry
    that ``digest_merge`` and ``digest_quantile`` (untouched) read: two
    windows of one group's rows against ``numpy.quantile`` of them all."""
    rng = np.random.default_rng(33)
    values = _lognormal(rng, 80_000)
    gids = np.zeros(80_000, np.int32)
    with routes_of("tpu"):
        carry = tdigest.digest_init(1)
        for half in (slice(0, 40_000), slice(40_000, None)):
            carry = jax.jit(tdigest.digest_update)(
                carry, jnp.asarray(gids[half]),
                jnp.ones(40_000, dtype=jnp.bool_),
                jnp.asarray(values[half], jnp.float32))
    assert float(np.asarray(carry[1]).sum()) == 80_000
    p50, p99 = np.asarray(tdigest.digest_quantile(carry, (0.5, 0.99)))[0]
    exact = np.quantile(values, [0.5, 0.99])
    assert abs(p50 / exact[0] - 1) <= P50_LIMIT
    assert abs(p99 / exact[1] - 1) <= P99_LIMIT


def _lowered(platform, g=33, n=4096):
    with routes_of(platform):
        return jax.jit(
            lambda v, i, m: tdigest.batch_to_digest(v, i, m, g)
        ).lower(
            jax.ShapeDtypeStruct((n,), jnp.float32),
            jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((n,), jnp.bool_),
        ).as_text()


def test_the_cpus_route_sorts_nothing_and_the_tpus_scatters_nothing():
    """The choice is the platform's alone (``ops/routes.py``): the CPU's
    lowered text of the aggregate holds the scatters and no ``sort``
    (XLA's CPU sort is ~90x its scatter), the TPU's one sort and no
    scatter of the rows. Above the reduction's slot limit no histogram
    of a width worth having fits (``routes.digest_hist_bins``): on both
    platforms' routes the rows then sort once, by (group, value), and
    their centroids are scatter-added (PR 41)."""
    from pixie_tpu.ops.routes import DIGEST_K, SORTED_DIGEST_MAX_SLOTS

    cpu, tpu = _lowered("cpu"), _lowered("tpu")
    assert "stablehlo.sort" not in cpu and "stablehlo.scatter" in cpu
    assert tpu.count("stablehlo.sort") == 1
    assert "stablehlo.scatter" not in tpu
    for platform in ("tpu", "cpu"):
        over = _lowered(platform, g=SORTED_DIGEST_MAX_SLOTS // DIGEST_K + 1)
        assert over.count("stablehlo.sort") == 1
        assert "stablehlo.scatter" in over


def test_a_served_refresh_names_the_sorted_digest():
    """``px/service_stats`` through broker, PEM and Kelvin under the
    TPU's routes: the PEM's fold dispatches carry the new route in
    ``fold`` (what ``/debug/queryz`` and the benchmark's span readers
    see), ``px/http_stats``' keep theirs, and the answers are complete."""
    from pixie_tpu.exec.engine import Engine
    from pixie_tpu.ingest.replay import gen_http_events
    from pixie_tpu.scripts import load_script
    from pixie_tpu.services import (
        AgentTracker, KelvinAgent, MessageBus, PEMAgent, QueryBroker,
    )
    from conftest import wait_until

    with routes_of("tpu"):
        bus = MessageBus()
        tracker = AgentTracker(bus, expiry_s=60.0, check_interval_s=60.0)
        pem = PEMAgent(bus, "pem-0", heartbeat_interval_s=0.05,
                       engine=Engine(window_rows=1 << 12)).start()
        kelvin = KelvinAgent(bus, "kelvin-0", heartbeat_interval_s=0.05).start()
        try:
            for hb in gen_http_events(1 << 13, seed=33):
                pem.append_data("http_events", hb)
            pem._register()
            wait_until(
                lambda: tracker.distributed_state().pems_with_table(
                    "http_events"),
                "the PEM's schema reached the tracker")
            broker = QueryBroker(bus, tracker)
            folds, traces = {}, []
            pem.engine.tracer.add_listener(traces.append)
            for script in ("px/http_stats", "px/service_stats"):
                res = broker.execute_script(load_script(script).pxl,
                                            timeout_s=300)
                assert not res.get("partial"), script
                folds[script] = {
                    sp.attributes["fold"]
                    for trace in traces for sp in trace.spans
                    if sp.name == "device.dispatch"
                    and "fold" in sp.attributes
                }
                del traces[:]
        finally:
            pem.stop()
            kelvin.stop()
            tracker.close()
            bus.close()
    assert folds["px/http_stats"] == {"pallas_int"}
    assert folds["px/service_stats"] == {"mixed:pallas_int=2,sorted_digest=2"}
