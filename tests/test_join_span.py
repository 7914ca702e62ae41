"""``px/net_flow_graph`` through broker, PEMs and Kelvin against the plain
reference (``benchmark/reference/px_net_flow_graph.py``), and the
``join`` span every ``JoinOp`` leaves on the engine's trace.

The benchmark's cell has ONE PEM and one address a pod, so its join is
N:1 over one PEM's dictionaries. Held here: two PEMs with disjoint pods
and overlapping addresses (each address is the ``src_addr`` of a pod on
either PEM, so the join fans out N:M over dictionaries that differ), a
flow to an address that is no pod's (dropped), rows outside the range,
on the CPU's routes and on the chip's.
"""

import os
import time

import numpy as np
import pytest
from conftest import routes_of

from benchmark.reference import px_net_flow_graph as reference
from pixie_tpu.exec.engine import Engine

SEED = 3_000_000_019  # the driver's seeds pass 2**31
ROWS, WINDOW = 6000, 1 << 12
PODS, ADDRS, OUTSIDE = 64, 32, 16
T_END = 1_700_000_000_000_000_000
STEP = 100_000_000  # 10 rows a second: '-5m' holds the last 3,001 rows
EVERY_ROW = 1 << 17

with open(os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "traffic", "flow_recent",
        "net_flow_graph.pxl")) as f:
    PXL = f.read()


def _data() -> dict:
    """``ROWS`` samples as the reference takes them (codes beside their
    vocabularies): pod p's address is ``p % ADDRS``, so pods p and
    p + 32 share one, and ``remote_addr`` draws from those 32 and 16
    that are no pod's."""
    rng = np.random.default_rng(SEED)
    pod = rng.integers(0, PODS, ROWS).astype(np.int32)
    return {
        "time_": T_END - STEP * np.arange(ROWS - 1, -1, -1, dtype=np.int64),
        "src_pod": pod,
        "src_addr": pod % ADDRS,
        "remote_addr": rng.integers(0, ADDRS + OUTSIDE, ROWS).astype(np.int32),
        "bytes_sent": rng.integers(64, 1 << 20, ROWS),
        "bytes_recv": rng.integers(64, 1 << 20, ROWS),
        "names": {
            "src_pod": [f"ns/pod-{i}" for i in range(PODS)],
            "src_addr": [f"10.0.0.{i}" for i in range(ADDRS)],
            # The outside addresses first: a pod's address has another
            # code here than in ``src_addr``.
            "remote_addr": [f"198.18.0.{i}" for i in range(OUTSIDE)]
                           + [f"10.0.0.{i}" for i in range(ADDRS)],
        },
    }


def _table(data: dict, keep: np.ndarray) -> dict:
    """The kept rows as ``append_data`` takes a table: strings as
    strings, so every PEM builds dictionaries of its own, in the order
    its rows bring them."""
    n = int(keep.sum())
    names = data["names"]
    out = {
        "time_": data["time_"][keep],
        "upid": np.stack([np.ones(n, np.uint64),
                          data["src_pod"][keep].astype(np.uint64)], axis=1),
        "remote_port": np.full(n, 443, np.int64),
        "trace_role": np.ones(n, np.int64),
        "addr_family": np.full(n, 2, np.int64),
        "protocol": np.ones(n, np.int64),
        "ssl": np.zeros(n, bool),
        "conn_open": np.ones(n, np.int64),
        "conn_close": np.zeros(n, np.int64),
        "conn_active": np.ones(n, np.int64),
        "bytes_sent": data["bytes_sent"][keep],
        "bytes_recv": data["bytes_recv"][keep],
    }
    for col in ("remote_addr", "src_addr", "src_pod"):
        out[col] = [names[col][i] for i in data[col][keep]]
    return out


@pytest.fixture(scope="module")
def data():
    return _data()


@pytest.fixture(scope="module", params=[1, 2], ids=["1pem", "2pems"])
def cluster(request, data):
    """Broker, Kelvin and k PEMs; PEM i holds the pods of its share
    (disjoint pods, and with k = 2 every address on both)."""
    from pixie_tpu.ingest.schemas import init_schemas
    from pixie_tpu.services import (
        AgentTracker, KelvinAgent, MessageBus, PEMAgent, QueryBroker,
    )

    k = request.param
    bus = MessageBus()
    tracker = AgentTracker(bus, expiry_s=60.0, check_interval_s=60.0)
    pems = []
    for i in range(k):
        eng = Engine(window_rows=WINDOW)
        init_schemas(eng)
        pem = PEMAgent(bus, f"pem-{i}", heartbeat_interval_s=0.05,
                       engine=eng).start()
        pem.append_data("conn_stats",
                        _table(data, data["src_pod"] * k // PODS == i))
        pem._register()
        pems.append(pem)
    kelvin = KelvinAgent(bus, "kelvin-0", heartbeat_interval_s=0.05).start()
    deadline = time.time() + 10
    while len(tracker.distributed_state().pems_with_table("conn_stats")) < k:
        assert time.time() < deadline, "a PEM's schema did not reach the tracker"
        time.sleep(0.01)
    yield QueryBroker(bus, tracker), pems, kelvin, k
    for a in pems + [kelvin]:
        a.stop()
    tracker.close()
    bus.close()


def _joins(trace) -> list:
    return [s for s in trace.spans if s.name == "join"]


@pytest.mark.parametrize("platform", ["cpu", "tpu"])
def test_the_served_path_equals_the_reference(cluster, data, platform):
    """Every edge and both sums, exactly, twice (the second request
    starts from what the first remembered); the Kelvin's trace holds one
    ``join`` span that says what ran."""
    broker, pems, kelvin, k = cluster
    want = reference.answer(data, T_END - 300 * 10**9)
    # Each flow to a pod's address reaches both pods that share it; the
    # 16 outside addresses reach none.
    assert 0 < len(want["key"]) <= PODS * PODS
    with routes_of(platform):
        for _ in range(2):
            res = broker.execute_script(PXL, timeout_s=120, now_ns=T_END,
                                        max_output_rows=EVERY_ROW)
            assert not res.get("partial")
            got = reference.rows(res["tables"]["output"].to_pydict())
            assert reference.numbers(got, want) == {
                "net_flow_graph.keys_differ": 0,
                "net_flow_graph.bytes_sent_differ": 0,
                "net_flow_graph.bytes_recv_differ": 0,
            }
    trace = kelvin.engine.tracer.last()
    (span,) = _joins(trace)
    a = span.attributes
    # An address is two pods' (N:M): never the host's unique-key lookup.
    # The bulk route is the platform's: the CPU's native hash join, the
    # chip's single-shot sort join (``routes_platform``, not the backend
    # underneath, which is a CPU here either way).
    assert a["strategy"] == {"cpu": "host_hash", "tpu": "single"}[platform]
    assert a["where"] == {"cpu": "host", "tpu": "device"}[platform]
    assert a["how"] == "inner"
    assert a["build_rows"] == PODS  # (src_addr, src_pod): one a pod
    in_range = data["time_"] >= T_END - 300 * 10**9
    flows = len(np.unique(
        data["src_pod"][in_range].astype(np.int64) * 64
        + data["remote_addr"][in_range]
    ))
    assert a["probe_rows"] == flows
    # A pod has one address, so a (src_pod, remote_addr) pair reaches a
    # pod once: the re-aggregation regroups the join's rows, none merge.
    assert a["rows_out"] == len(want["key"])
    assert span.end_ns > span.start_ns and span.parent_id == trace.root.span_id
    assert trace.usage.join_rows_in == PODS + flows
    assert trace.usage.join_rows_out == a["rows_out"]
    # The re-aggregation is told from a PEM's fold by its fragment's ops.
    ops = [s.attributes["ops"] for s in trace.spans if s.name == "fragment"]
    assert ops[-1].startswith("AggOp") and len(ops) == 3
    for pem in pems:
        t = pem.engine.tracer.last()
        assert _joins(t) == [] and t.usage.join_rows_in == 0


def test_usage_counters_reach_the_brokers_trace(cluster):
    """``join_rows_in`` / ``join_rows_out`` ride the usage record to the
    broker as its other fields do (``QueryResourceUsage.merge``)."""
    from pixie_tpu.exec.trace import QueryResourceUsage

    broker, _pems, kelvin, _k = cluster
    broker.execute_script(PXL, timeout_s=120, now_ns=T_END,
                          max_output_rows=EVERY_ROW)
    mine = kelvin.engine.tracer.last().usage
    assert mine.join_rows_in > 0 < mine.join_rows_out
    total = QueryResourceUsage()
    total.merge(mine.to_dict())
    total.merge(mine)
    assert total.join_rows_in == 2 * mine.join_rows_in
    assert total.join_rows_out == 2 * mine.join_rows_out


@pytest.mark.parametrize("forced,strategy", [
    ("auto", "host_table"), ("host", "host_dict"),
])
def test_a_unique_small_build_stays_on_the_host_and_says_so(forced, strategy):
    """The bundled script over one address a pod: the N:1 host lookup,
    on a bare engine's trace. By the table where the strategy is the
    engine's to choose (one dictionary-coded key); by the dict join
    where one is forced, which small inputs still reach."""
    from pixie_tpu.config import override_flag
    from pixie_tpu.ingest.schemas import init_schemas
    from pixie_tpu.scripts import load_script

    d = _data()
    d["src_addr"] = d["src_pod"]
    d["names"]["src_addr"] = [f"10.0.1.{i}" for i in range(PODS)]
    d["names"]["remote_addr"] = (d["names"]["remote_addr"][:OUTSIDE]
                                 + d["names"]["src_addr"])
    d["remote_addr"] = np.random.default_rng(SEED).integers(
        0, OUTSIDE + PODS, ROWS).astype(np.int32)
    eng = Engine(window_rows=WINDOW)
    init_schemas(eng)
    eng.append_data("conn_stats", _table(d, np.ones(ROWS, bool)))
    with override_flag("join_strategy", forced):
        out = eng.execute_query(load_script("px/net_flow_graph").pxl)
    got = reference.rows(out["output"].to_pydict())
    assert reference.numbers(got, reference.answer(d, None)) == {
        "net_flow_graph.keys_differ": 0,
        "net_flow_graph.bytes_sent_differ": 0,
        "net_flow_graph.bytes_recv_differ": 0,
    }
    (span,) = _joins(eng.tracer.last())
    assert span.attributes["strategy"] == strategy
    assert span.attributes["where"] == "host"
    assert span.attributes["build_rows"] == PODS
    # The union of the two address dictionaries and the null's slot.
    assert span.attributes.get("domain") == (
        {"host_table": OUTSIDE + PODS + 1, "host_dict": None}[strategy])
    assert eng.tracer.last().usage.join_rows_out == span.attributes["rows_out"]


def test_a_fused_lookup_join_leaves_a_span_of_its_build():
    """An N:1 join against a dense aggregate fuses into the probe's
    fragment: the span covers the build and counts no probe rows."""
    eng = Engine(window_rows=WINDOW)
    rng = np.random.default_rng(5)
    n = 2000
    for table, value in (("L", "b"), ("R", "v")):
        eng.append_data(table, {
            "time_": np.arange(n, dtype=np.int64),
            "k": rng.integers(0, 64, n), value: rng.integers(0, 7, n),
        })
    out = eng.execute_query(
        "import px\n"
        "l = px.DataFrame(table='L')\n"
        "r = px.DataFrame(table='R')\n"
        "g = l.merge(r, how='inner', left_on=['k'], right_on=['k'],"
        " suffixes=['', '_r'])\n"
        "px.display(g.groupby('b').agg(n=('v', px.count)))\n"
    )["output"].to_pydict()
    assert int(np.sum(out["n"])) > n  # N:M through the partial aggregate
    assert eng.last_join_decision.strategy == "fused"
    (span,) = _joins(eng.tracer.last())
    a = span.attributes
    assert (a["strategy"], a["where"], a["how"]) == ("fused", "device", "inner")
    assert a["build_rows"] >= 64 and a["probe_rows"] == a["rows_out"] == 0


@pytest.mark.parametrize("platform,strategy",
                         [("cpu", "host_hash"), ("tpu", "single")])
def test_the_bulk_join_route_follows_routes_platform(platform, strategy):
    """``choose_join_strategy`` asks ``ops/routes.py``, so a CPU test
    reaches the route the chip takes; ``exec/joins.py`` reads the backend
    nowhere."""
    from pixie_tpu.exec import joins
    from pixie_tpu.exec.plan import JoinOp
    from pixie_tpu.types.batch import HostBatch

    hb = HostBatch.from_pydict({"k": np.arange(8, dtype=np.int64)})
    op = JoinOp(how="inner", left_on=("k",), right_on=("k",))
    with routes_of(platform):
        assert joins.choose_join_strategy(hb, hb, op).strategy == strategy
    with open(joins.__file__) as f:
        assert "default_backend" not in f.read()


# -- sizing the join's tail from the rows in hand -----------------------------


@pytest.mark.parametrize("estimate,planned,want", [
    (63_000, 1 << 22, 1 << 17),   # far under the plan's: worth a program
    (5_000, 1 << 13, 1 << 13),    # near the plan's: the plan's
    (643_000, 1 << 22, 1 << 20),  # a quarter of the plan's: taken
    (330_000, 1 << 20, 1 << 20),  # half of the plan's: the plan's
    (1_200, 1 << 13, 1 << 11),    # the least plan that is given up
    (31_556, 1 << 12, 1 << 16),   # over the plan's: where a climb would end
    (10, 1 << 12, 1 << 12),
    (4, 8, 8),                    # the floor alone never grows a plan's
])
def test_a_probed_capacity_may_pass_the_plans(estimate, planned, want):
    from pixie_tpu.exec.stream import _probed_capacity

    assert _probed_capacity(estimate, planned) == want


def test_the_single_shot_join_is_sized_from_the_build_keys_in_hand():
    """A build side no ingest sketch covers (a merged aggregate's rows):
    its keys are counted, so the output is sized from probe rows x
    fan-out and not from the plan's bound, which stays the ceiling."""
    from pixie_tpu.exec import joins
    from pixie_tpu.exec.plan import JoinOp
    from pixie_tpu.types.batch import HostBatch, bucket_capacity

    rng = np.random.default_rng(SEED)
    left = HostBatch.from_pydict({"k": rng.integers(0, 80, 3000),
                                  "v": np.arange(3000)})
    right = HostBatch.from_pydict({"k": np.arange(64, dtype=np.int64),
                                   "w": np.arange(64)})
    op = JoinOp(how="inner", left_on=("k",), right_on=("k",))
    eng = Engine()
    with routes_of("tpu"):
        out = joins._join_device(left, right, op, eng, cap_key=("plan", 1),
                                 planned_capacity=1 << 22)
    assert out.length == int(np.sum(left.cols["k"][0] < 64))
    # Unique build keys: fan-out 1, times the safety factor of 2.
    assert joins.learned_capacity(eng, ("single", ("plan", 1))) == (
        bucket_capacity(2 * 3000 + 1)
    ) == 8192
    assert eng.last_join_decision.retries == 0
    stats = joins._in_hand_build_stats(None, [right.cols["k"][0]])
    assert (stats.rows, stats.origin) == (64, "scan") and 60 <= stats.ndv <= 64
    sketch = joins.JoinSideStats(rows=9, ndv=3, origin="sketch")
    assert joins._in_hand_build_stats(sketch, [right.cols["k"][0]]) is sketch
    # Two key planes are not counted: the order of before stands.
    assert joins._in_hand_build_stats(None, [right.cols["k"][0]] * 2) is None


def test_an_aggregate_over_rows_in_hand_is_probed_not_climbed():
    """The re-aggregation of a join's 11 k rows by a key with no dense
    domain: the plan has no sketch of them, so its capacity is AggOp's
    default, 4,096 slots. The rows are in hand and outnumber it, so the
    first request reads a sketch of the joint key and folds where a
    climb would have ended, without one ``rebucket``; the second finds
    it remembered."""
    from pixie_tpu.ingest.schemas import init_schemas
    from pixie_tpu.scripts import load_script

    pods, peers, rows = 1100, 10, 40_000  # 1,101^2 codes > dense_domain_limit
    rng = np.random.default_rng(SEED)
    pod = rng.integers(0, pods, rows).astype(np.int32)
    d = {
        "time_": np.arange(rows, dtype=np.int64),
        "src_pod": pod, "src_addr": pod,
        "remote_addr": ((pod * 7 + rng.integers(0, peers, rows)) % pods
                        ).astype(np.int32),
        "bytes_sent": rng.integers(64, 1 << 20, rows),
        "bytes_recv": rng.integers(64, 1 << 20, rows),
        "names": {"src_pod": [f"ns/pod-{i}" for i in range(pods)],
                  "src_addr": [f"10.1.{i >> 8}.{i & 255}" for i in range(pods)],
                  "remote_addr": [f"10.1.{i >> 8}.{i & 255}"
                                  for i in range(pods)]},
    }
    eng = Engine(window_rows=1 << 16)
    init_schemas(eng)
    eng.append_data("conn_stats", _table(d, np.ones(rows, bool)))
    want = reference.answer(d, None)
    assert 8192 < len(want["key"]) <= pods * peers
    pxl = load_script("px/net_flow_graph").pxl
    with routes_of("tpu"):
        for run in range(2):
            out = eng.execute_query(pxl, max_output_rows=EVERY_ROW)
            got = reference.rows(out["output"].to_pydict())
            assert sum(reference.numbers(got, want).values()) == 0
            trace = eng.tracer.last()
            assert trace.usage.rebuckets == 0
            probes = [s.attributes for s in trace.spans
                      if s.name == "group_probe"
                      and s.attributes["slots"] == 4096]
            if run == 0:
                (probe,) = probes
                assert abs(probe["estimate"] - len(want["key"])) < (
                    0.05 * len(want["key"]))
            else:
                assert probes == []
            folds = [s.attributes["slots"] for s in trace.spans
                     if s.name == "device.dispatch"
                     and "slots" in s.attributes]
            assert folds[-1] == 1 << 14  # 11 k edges, a quarter's head-room


def test_one_window_in_hand_is_staged_without_a_prefetch_thread():
    """A batch in hand that fits one window has no window N + 1 to stage
    ahead of: its pipeline is serial (no thread to start and hand over
    to, which is what made the Kelvin's ``window.stage`` bimodal on the
    chip); a longer one keeps the engine's depth, as a table's scan does."""
    from pixie_tpu.types.batch import HostBatch

    eng = Engine(window_rows=1 << 10)
    assert eng.pipeline_depth >= 2

    def depth_for(rows):
        hb = HostBatch.from_pydict({"k": np.arange(rows, dtype=np.int64)})
        pipe = eng._window_pipeline(eng._as_stream(hb))
        try:
            return pipe.depth, sum(int(np.sum(np.asarray(valid)))
                                   for _cols, valid in pipe)
        finally:
            pipe.close()

    assert depth_for(1 << 10) == (1, 1 << 10)
    assert depth_for(7) == (1, 7)
    assert depth_for((1 << 10) + 1) == (eng.pipeline_depth, (1 << 10) + 1)


def _join_sides(seed=SEED, nb=96, npr=700, keys=40):
    """Padded build and probe key planes with invalid rows, N:M over
    ``keys`` values, a two-plane key beside the one-plane one."""
    rng = np.random.default_rng(seed)
    bk = rng.integers(0, keys, nb).astype(np.int32)
    pk = rng.integers(0, keys + 8, npr).astype(np.int32)  # some match none
    bv, pv = rng.random(nb) < 0.9, rng.random(npr) < 0.9
    return bk, bv, pk, pv


def _pairs(out, bk, pk):
    p_idx, p_take, b_idx, b_take, valid, overflow = (np.asarray(a)
                                                     for a in out)
    assert not bool(overflow)
    rows = np.nonzero(valid)[0]
    return sorted(
        (int(p_idx[r]) if p_take[r] else -1, int(b_idx[r]) if b_take[r] else -1)
        for r in rows
    )


@pytest.mark.parametrize("how", ["inner", "left", "right", "outer"])
@pytest.mark.parametrize("planes", [1, 2], ids=["one_plane", "two_planes"])
def test_the_joins_sorted_key_ids_give_the_tables_pairs(how, planes):
    """``device_join`` takes its shared key ids by the sort on the TPU's
    routes (``ops/routes.py`` ``JOIN_SORT_IDS_MAX_ROWS``) and by the
    open-addressing table on the CPU's: the same pairs either way, for
    every ``how``, with invalid rows on both sides."""
    import jax.numpy as jnp

    from pixie_tpu.ops.join import device_join

    bk, bv, pk, pv = _join_sides()
    b_planes = [jnp.asarray(bk)] + [jnp.asarray(bk % 3)] * (planes - 1)
    p_planes = [jnp.asarray(pk)] + [jnp.asarray(pk % 3)] * (planes - 1)
    got = {}
    for platform in ("cpu", "tpu"):
        with routes_of(platform):
            got[platform] = _pairs(
                device_join(b_planes, jnp.asarray(bv), p_planes,
                            jnp.asarray(pv), 1 << 13, how), bk, pk)
    assert got["tpu"] == got["cpu"] and got["tpu"]
    if how == "inner":
        want = sorted((i, j) for i in np.nonzero(pv)[0]
                      for j in np.nonzero(bv)[0] if pk[i] == bk[j])
        assert got["tpu"] == want


@pytest.mark.parametrize("platform,limit,table", [
    ("tpu", None, False), ("tpu", 64, True), ("cpu", None, True),
], ids=["tpu", "tpu_over_the_limit", "cpu"])
def test_the_joins_time_does_not_follow_the_data_on_the_chips_routes(
        platform, limit, table, monkeypatch):
    """The table's rounds are a ``while_loop`` that ends when the data
    lets it (3 or 4 rounds by seed in the benchmark's cell: PERF.md
    section 6, PR 32); the sort has no such loop. Held on the trace:
    ``dense_group_ids_hash`` is not reached under the TPU's routes up to
    the limit, and is above it and on the CPU's."""
    import jax
    import jax.numpy as jnp

    from pixie_tpu.ops import join, routes

    if limit is not None:
        monkeypatch.setattr(routes, "JOIN_SORT_IDS_MAX_ROWS", limit)
    reached = []
    real = join.dense_group_ids_hash
    monkeypatch.setattr(
        join, "dense_group_ids_hash",
        lambda *a, **k: reached.append(1) or real(*a, **k))
    bk, bv, pk, pv = (jnp.asarray(a) for a in _join_sides())
    with routes_of(platform):
        jax.make_jaxpr(
            lambda a, b, c, d: join.device_join([a], b, [c], d, 1 << 10,
                                                "inner")
        )(bk, bv, pk, pv)
    assert bool(reached) is table


def test_the_join_program_is_cached_by_the_platform_whose_routes_run():
    """``_device_join_cache`` keys on ``routes_platform()``: a program
    traced under one platform's routes is not handed to the other's."""
    from pixie_tpu.exec import joins

    a = joins._device_join_cache(64, 64, ("int32",), 128, "inner", "cpu")
    b = joins._device_join_cache(64, 64, ("int32",), 128, "inner", "tpu")
    assert a is not b
    assert a is joins._device_join_cache(64, 64, ("int32",), 128, "inner",
                                         "cpu")
