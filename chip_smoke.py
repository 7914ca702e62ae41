"""Chip smoke: the query path, once, on the TPU it was written for.

    python chip_smoke.py            # one chip: kernel, engine, served, skew, flow, edges
    python chip_smoke.py --chips 4  # four chips: kernel, DistributedEngine, cluster

One process, no child that needs the chip. A seeded ``http_events``
replay (five columns, 32 B/row) goes in through the
table store's ingest path with device residency on; every answer is
checked against a plain numpy replay of the same semantics. Progress is
one JSON object per line; the LAST line is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

and the exit code is 0 only then. Any phase that raises, or a platform
other than ``tpu``, ends in ``"ok": false`` and a non-zero exit. On the
CPU the script only rehearses (``JAX_PLATFORMS=cpu python chip_smoke.py
--rows 65536``: Pallas kernels in interpret mode, never ``ok``).
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
import traceback

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

WINDOW = 1 << 21  # one window size: one update + one finalize per query
REHEARSAL_MAX_ROWS = 1 << 20  # what a run without a TPU may be asked for
SKEW_ROWS = 1 << 23  # the non-dense phase: four windows at ten columns
FLOW_ROWS = 1 << 22  # the join phase: two windows at conn_stats' fifteen
EDGES_ROWS = 1 << 22  # the keyed quantiles phase: two windows at ten columns
CLUSTER_ROWS = 4 << 21  # the four-node phase: 2^21 rows a node on average

SERVICES = [f"svc-{i}" for i in range(32)]
PATHS = [f"/api/v1/ep{i}" for i in range(8)]

#: FLOAT64 aggregates over a dense (dictionary-coded) key domain: the
#: shape ``exec/fragment.py`` routes through the f32 ``dense_group_fold``.
#: The shipped scripts' INT64 / BOOLEAN aggregates take the exact
#: ``dense_group_fold_int`` (``latency_ns`` is INT64).
F64_GROUPBY = """
import px
df = px.DataFrame(table='http_events')
df.lat_ms = df.latency_ns / 1000000.0
df = df.groupby(['service', 'req_path']).agg(
    n=('lat_ms', px.count),
    lat_sum=('lat_ms', px.sum),
    lat_mean=('lat_ms', px.mean),
    lat_max=('lat_ms', px.max),
    lat_min=('lat_ms', px.min),
)
px.display(df)
"""

#: Two groups (a BOOLEAN key is a dense domain of 2): the window digest
#: of a ``quantiles`` aggregate by sorting the rows (``ops/tdigest.py``),
#: at the other end of the group counts from px/service_stats' 33; its
#: program must hold the sorted reduction's kernel.
QUANTILE_BY_FAILED = """
import px
df = px.DataFrame(table='http_events')
df.failed = df.resp_status >= 400
df = df.groupby('failed').agg(
    lat_q=('latency_ns', px.quantiles),
    n=('latency_ns', px.count),
)
df.p50 = px.pluck_float64(df.lat_q, 'p50')
df.p99 = px.pluck_float64(df.lat_q, 'p99')
df = df[['failed', 'p50', 'p99', 'n']]
px.display(df)
"""


def emit(**kw) -> None:
    print(json.dumps(kw), flush=True)


# -- compile accounting -------------------------------------------------------


class CompileMeter:
    """Counts what JAX compiled (or fetched from the persistent cache),
    from JAX's own monitoring events: every jit in the process, tracked
    by the program registry or not."""

    def __init__(self):
        import jax.monitoring

        self.programs = 0  # compiled, or fetched from the persistent cache
        self.fetched = 0  # of those, fetched
        self.secs = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_kw):
        # The backend-compile timer wraps compile-or-fetch, so a
        # persistent-cache hit fires both events.
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.secs += secs
        elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
            self.fetched += 1

    def mark(self):
        return self.programs, self.fetched, self.secs

    def since(self, mark=(0, 0, 0.0)) -> dict:
        return {
            "programs": self.programs - mark[0],
            "from_persistent_cache": self.fetched - mark[1],
            "compile_secs": round(self.secs - mark[2], 3),
        }


def _registry_snapshot() -> dict:
    from pixie_tpu.exec.programs import default_program_registry

    return {
        r.program_id: r.compiles
        for r in default_program_registry().records()
    }


def _programs_since(snap: dict) -> list:
    """Registry records compiled since ``snap`` (the tracked fragment
    programs: label, compile seconds, whether a Pallas kernel is in the
    executable XLA built)."""
    from pixie_tpu.exec.programs import default_program_registry

    out = []
    for r in default_program_registry().records():
        if r.compiles > snap.get(r.program_id, 0):
            text = r.compiled.as_text() if r.compiled is not None else ""
            out.append({
                "program": f"{r.kind}:{r.label}",
                "compile_secs": round(r.compile_s_last, 3),
                "tpu_custom_call": "tpu_custom_call" in text,
                "kernels": sorted(
                    k for k in ("dense_group_fold", "dense_group_fold_int",
                              "sorted_centroid_fold")
                    if f"{k}/pallas_call" in text
                ),
            })
    return out


# -- the replay and its numpy reference --------------------------------------


class Replay:
    """``rows`` http_events of five columns:
    time_ i64, latency_ns i64, resp_status i64, service/req_path as
    dictionary codes. All of it from ``seed``."""

    def __init__(self, rows: int, seed: int):
        from pixie_tpu.types.dtypes import DataType
        from pixie_tpu.types.relation import Relation
        from pixie_tpu.types.strings import StringDictionary

        rng = np.random.default_rng(seed)
        self.rows = rows
        self.rel = Relation([
            ("time_", DataType.TIME64NS),
            ("latency_ns", DataType.INT64),
            ("resp_status", DataType.INT64),
            ("service", DataType.STRING),
            ("req_path", DataType.STRING),
        ])
        self.dicts = {
            "service": StringDictionary(SERVICES),
            "req_path": StringDictionary(PATHS),
        }
        statuses = np.array([200, 200, 200, 200, 404, 500])
        self.svc = rng.integers(0, len(SERVICES), rows).astype(np.int32)
        self.path = rng.integers(0, len(PATHS), rows).astype(np.int32)
        self.lat = rng.integers(1_000, 100_000_000, rows)
        self.status = statuses[rng.integers(0, len(statuses), rows)].astype(
            np.int64
        )
        self.bytes_per_row = 8 + 8 + 8 + 4 + 4

    def push(self, append, rows: int | None = None) -> int:
        """Append the first ``rows`` rows, a window at a time, through
        ``append(table, HostBatch)`` (the engine's / agent's ingest)."""
        from pixie_tpu.types.batch import HostBatch

        n = self.rows if rows is None else min(rows, self.rows)
        for off in range(0, n, WINDOW):
            m = min(WINDOW, n - off)
            s = slice(off, off + m)
            append("http_events", HostBatch(
                relation=self.rel,
                cols={
                    "time_": (np.arange(off, off + m, dtype=np.int64),),
                    "latency_ns": (self.lat[s],),
                    "resp_status": (self.status[s],),
                    "service": (self.svc[s],),
                    "req_path": (self.path[s],),
                },
                length=m, dicts=self.dicts,
            ))
        return n


def _by_key(got: dict):
    """Group-by output keyed service * 64 + req_path, sorted."""
    key = got["service"].astype(np.int64) * 64 + got["req_path"]
    order = np.argsort(key)
    return key[order], order


def check_http_stats(rp: Replay, n: int, got: dict) -> None:
    lat, status = rp.lat[:n], rp.status[:n]
    ok = status < 400
    key = rp.svc[:n][ok].astype(np.int64) * 64 + rp.path[:n][ok]
    uniq, inv = np.unique(key, return_inverse=True)
    cnt = np.bincount(inv)
    mean = np.bincount(inv, weights=lat[ok].astype(np.float64)) / cnt
    mx = np.full(len(uniq), -np.inf)
    np.maximum.at(mx, inv, lat[ok])
    gkey, order = _by_key(got)
    assert np.array_equal(uniq, gkey), "http_stats: group keys differ"
    assert np.array_equal(got["n"][order], cnt), "http_stats: counts differ"
    np.testing.assert_allclose(got["lat_mean"][order], mean, rtol=1e-5)
    np.testing.assert_allclose(got["lat_max"][order], mx)


def check_service_stats(rp: Replay, n: int, got: dict) -> None:
    lat, status, svc = rp.lat[:n], rp.status[:n], rp.svc[:n]
    seen = set()
    for s, p50, p99, err, thr in zip(
        got["service"], got["p50"], got["p99"], got["error_rate"],
        got["throughput"],
    ):
        m = svc == s
        seen.add(int(s))
        r50, r99 = np.quantile(lat[m], 0.5), np.quantile(lat[m], 0.99)
        assert abs(p50 - r50) / r50 < 0.15, f"p50 off: {p50} vs {r50}"
        assert abs(p99 - r99) / r99 < 0.15, f"p99 off: {p99} vs {r99}"
        np.testing.assert_allclose(err, np.mean(status[m] >= 400), rtol=1e-4)
        assert thr == int(m.sum()), "service_stats: throughput differs"
    assert seen == set(np.unique(svc).tolist()), "service_stats: services differ"


def check_f64_groupby(rp: Replay, n: int, got: dict) -> None:
    # The device holds FLOAT64 planes as f32 (types/dtypes.py) and the
    # kernel folds in f32: the repo's own tolerance for it is 1e-4
    # (tests/test_tpu.py).
    ms = rp.lat[:n].astype(np.float64) / 1e6
    key = rp.svc[:n].astype(np.int64) * 64 + rp.path[:n]
    uniq, inv = np.unique(key, return_inverse=True)
    cnt = np.bincount(inv)
    total = np.bincount(inv, weights=ms)
    mx = np.full(len(uniq), -np.inf)
    mn = np.full(len(uniq), np.inf)
    np.maximum.at(mx, inv, ms)
    np.minimum.at(mn, inv, ms)
    gkey, order = _by_key(got)
    assert np.array_equal(uniq, gkey), "f64_groupby: group keys differ"
    assert np.array_equal(got["n"][order], cnt), "f64_groupby: counts differ"
    np.testing.assert_allclose(got["lat_sum"][order], total, rtol=1e-4)
    np.testing.assert_allclose(got["lat_mean"][order], total / cnt, rtol=1e-4)
    np.testing.assert_allclose(got["lat_max"][order], mx, rtol=1e-6)
    np.testing.assert_allclose(got["lat_min"][order], mn, rtol=1e-6)


def check_quantile_by_failed(rp: Replay, n: int, got: dict) -> None:
    lat, failed = rp.lat[:n], rp.status[:n] >= 400
    assert sorted(got["failed"].tolist()) == [False, True], got["failed"]
    for f, p50, p99, cnt in zip(
        got["failed"], got["p50"], got["p99"], got["n"]
    ):
        m = failed == bool(f)
        assert cnt == int(m.sum()), "quantile_by_failed: counts differ"
        for name, val, q in (("p50", p50, 0.5), ("p99", p99, 0.99)):
            ref = np.quantile(lat[m], q)
            assert abs(val - ref) / ref < 0.15, (
                f"quantile_by_failed {name} off: {val} vs {ref}"
            )


def engine_queries() -> list:
    """(name, PxL, check, kernel the program must contain on the chip)."""
    from pixie_tpu.scripts import load_script

    return [
        ("px/http_stats", load_script("px/http_stats").pxl,
         check_http_stats, "dense_group_fold_int"),
        ("px/service_stats", load_script("px/service_stats").pxl,
         check_service_stats, "dense_group_fold_int"),
        ("inline/f64_groupby", F64_GROUPBY, check_f64_groupby,
         "dense_group_fold"),
        ("inline/quantile_by_failed", QUANTILE_BY_FAILED,
         check_quantile_by_failed, "sorted_centroid_fold"),
    ]


# -- phases -------------------------------------------------------------------


def _resident(table, sharded_over: int = 1) -> dict:
    """What of ``table`` sits in device memory, by walking the windows a
    query would scan. With ``sharded_over`` > 1 every plane must have
    its shards on that many distinct devices."""
    windows = rows = nbytes = 0
    devices = set()
    for win, _lo, _hi in table.device_scan(None, None, window_rows=WINDOW):
        windows += 1
        rows += win.n
        nbytes += win.nbytes
        for planes in win.cols.values():
            for p in planes:
                devs = {sh.device for sh in p.addressable_shards}
                assert len(devs) == sharded_over, (
                    f"window at row {win.row0}: shards on {len(devs)} "
                    f"devices, want {sharded_over}"
                )
                devices |= devs
    return {"windows": windows, "rows": rows, "bytes": nbytes,
            "devices": sorted(str(d) for d in devices)}


def _timed_query(eng, pxl: str):
    """Seconds to the host readback, and the host result."""
    t0 = time.perf_counter()
    out = eng.execute_query(pxl, materialize=False)
    host = {
        k: (v.to_host() if hasattr(v, "to_host") else v)
        for k, v in out.items()
    }
    return time.perf_counter() - t0, host


def _fold_routes(eng, attr: str = "fold") -> list:
    """The ``fold`` attributes of the last query's device.dispatch spans:
    how its window-fold programs said they fold (``attr`` ``ride``: how
    a keyed sorted fold's windows carried their sums)."""
    return sorted({
        sp.attributes[attr] for sp in eng.tracer.last().spans
        if sp.name == "device.dispatch" and attr in sp.attributes
    })


def run_queries(eng, rp: Replay, n: int, queries, meter: CompileMeter,
                on_tpu: bool, phase: str, tracked: bool = True) -> None:
    """Each query twice (cold, warm), checked; the second run may
    compile nothing, and on the chip a query that names a kernel must
    say so on its fold's spans and (``tracked``: the mesh steps are plain
    jits, not registry records) have it in the program XLA built."""
    for name, pxl, check, kernel in queries:
        snap, mark = _registry_snapshot(), meter.mark()
        cold_s, host = _timed_query(eng, pxl)
        cold = meter.since(mark)
        programs = _programs_since(snap)
        check(rp, n, host["output"].to_pydict(decode_strings=False))
        mark = meter.mark()
        warm_s, host = _timed_query(eng, pxl)
        warm = meter.since(mark)
        check(rp, n, host["output"].to_pydict(decode_strings=False))
        folds = _fold_routes(eng)
        emit(phase=phase, query=name, rows=n, checked=True,
             cold_secs=cold_s, warm_secs=warm_s, fold=folds,
             cold_compile=cold, warm_compile=warm, programs=programs)
        assert warm["programs"] == 0, (
            f"{name}: second run compiled {warm['programs']} program(s)"
        )
        if kernel == "dense_group_fold_int" and on_tpu:
            assert folds and all("pallas_int" in f for f in folds), (
                f"{name}: fold spans say {folds}, not pallas_int"
            )
        if kernel and on_tpu and tracked:
            assert any(
                p["tpu_custom_call"] and kernel in p["kernels"]
                for p in programs
            ), f"{name}: no tpu_custom_call for {kernel} in its programs"


def phase_int_kernel(seed: int, rows: int, on_tpu: bool, chips: int) -> None:
    """``dense_group_fold_int`` at the benchmark cells' shapes against
    numpy: a window of ``rows`` rows over px/http_stats' 2,048 slots
    (count, sum and max of an INT64) and px/service_stats' 32 (count and
    sum of a BOOLEAN); on four chips a quarter of the window a shard
    under ``shard_map``, as the mesh step runs it."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from pixie_tpu.ops.pallas_groupby import (
        dense_group_fold_int, int_fold_blocks, int_fold_groups,
    )
    from pixie_tpu.parallel.mesh import agent_mesh, row_sharding

    rng = np.random.default_rng(seed)
    lat = np.exp(rng.normal(15, 1.2, rows)).astype(np.int64)
    lat[:3] = [np.iinfo(np.int64).max, np.iinfo(np.int64).min, -1]
    failed = rng.random(rows) < 0.08
    mesh = agent_mesh(chips) if chips > 1 else None
    for g, sums, exts in ((2048, (lat,), (lat,)), (32, (failed,), ())):
        g_pad = int_fold_groups(g)
        chunk, g_block = int_fold_blocks(rows // chips, g_pad)
        slots = rng.integers(0, g, rows).astype(np.int32)
        slots[::9] = g_pad  # masked rows

        def fold(slots, sums, exts):
            cnt, s, e = dense_group_fold_int(
                slots, sums, exts, g=g_pad, chunk=chunk, g_block=g_block,
                ext_max=(True,) * len(exts), interpret=not on_tpu,
            )
            # A leading shard axis, so four chips' partials come back
            # side by side and numpy merges them.
            return jax.tree_util.tree_map(lambda x: x[None, :g], (cnt, s, e))

        args = (slots, sums, exts)
        if mesh is not None:
            axes = mesh.axis_names
            fold = jax.shard_map(fold, mesh=mesh, in_specs=P(axes),
                                 out_specs=P(axes), check_vma=False)
            args = jax.device_put(args, row_sharding(mesh))
        t0 = time.perf_counter()
        compiled = jax.jit(fold).lower(*args).compile()
        compile_s = time.perf_counter() - t0
        assert not on_tpu or "tpu_custom_call" in compiled.as_text()
        cnt, s, e = jax.block_until_ready(compiled(*args))
        t0 = time.perf_counter()
        jax.block_until_ready(compiled(*args))
        warm_s = time.perf_counter() - t0
        live = slots < g
        np.testing.assert_array_equal(
            np.asarray(cnt).sum(0), np.bincount(slots[live], minlength=g)
        )
        for got, v in zip(s, sums):
            want = np.zeros(g, np.int64)
            np.add.at(want, slots[live], v[live].astype(np.int64))
            np.testing.assert_array_equal(np.asarray(got).sum(0), want)
        for got, v in zip(e, exts):
            want = np.full(g, np.iinfo(np.int64).min)
            np.maximum.at(want, slots[live], v[live])
            np.testing.assert_array_equal(np.asarray(got).max(0), want)
        emit(phase="int_kernel", rows=rows, groups=g, chips=chips,
             rows_a_shard=rows // chips, blocks=[chunk, g_block],
             checked=True, compile_secs=round(compile_s, 3),
             warm_secs=round(warm_s, 5))


def phase_engine(rp: Replay, meter: CompileMeter, on_tpu: bool) -> None:
    from pixie_tpu.exec.engine import Engine

    eng = Engine(window_rows=WINDOW)
    eng.create_table("http_events")
    t0 = time.perf_counter()
    n = rp.push(eng.append_data)
    table = eng.tables["http_events"]
    res = _resident(table)
    emit(phase="engine", step="ingest", rows=n,
         table_bytes=n * rp.bytes_per_row, secs=time.perf_counter() - t0,
         table_store_backend=type(table._backend).__name__, resident=res)
    assert res["rows"] == n, f"resident rows {res['rows']} != {n}"
    run_queries(eng, rp, n, engine_queries(), meter, on_tpu, "engine")


def phase_served(rp: Replay, meter: CompileMeter, on_tpu: bool) -> None:
    """Broker + one PEM + one Kelvin on an in-process bus; three
    px/http_stats requests through the broker."""
    from pixie_tpu.exec.engine import Engine
    from pixie_tpu.scripts import load_script
    from pixie_tpu.services import (
        AgentTracker, KelvinAgent, MessageBus, PEMAgent, QueryBroker,
    )
    from pixie_tpu.services.load_tester import broker_executor

    bus = MessageBus()
    tracker = AgentTracker(bus)
    pem = PEMAgent(bus, "pem-0", engine=Engine(window_rows=WINDOW)).start()
    kelvin = KelvinAgent(bus, "kelvin-0").start()
    try:
        n = rp.push(pem.append_data, rows=WINDOW)
        pem._register()  # so the tracker sees the post-ingest schema
        deadline = time.monotonic() + 30
        while "http_events" not in tracker.schemas():
            assert time.monotonic() < deadline, "PEM schema never registered"
            time.sleep(0.01)
        emit(phase="served", step="ingest", pem_rows=n,
             note="the PEM holds one window of the same replay; the "
                  "engine phase carries the size")
        execute = broker_executor(QueryBroker(bus, tracker))
        pxl = load_script("px/http_stats").pxl
        snap = _registry_snapshot()
        for i in range(3):
            mark = meter.mark()
            t0 = time.perf_counter()
            res = execute(pxl, 1100.0)
            secs = time.perf_counter() - t0
            # The merged result carries its own string dictionary: map
            # the decoded strings back onto the replay's codes.
            got = res["tables"]["output"].to_pydict()
            got["service"] = np.array(
                [SERVICES.index(s) for s in got["service"]]
            )
            got["req_path"] = np.array(
                [PATHS.index(s) for s in got["req_path"]]
            )
            check_http_stats(rp, n, got)
            emit(phase="served", request=i, query="px/http_stats", rows=n,
                 groups=len(got["n"]), checked=True, secs=secs,
                 cache=res.get("cache", ""), compile=meter.since(mark))
        # The shipped script, served, reaches the integer kernel: its
        # fold program holds the kernel's tpu_custom_call.
        folds = [p for p in _programs_since(snap)
                 if p["program"].startswith("fragment_update")]
        emit(phase="served", fold_programs=folds)
        if on_tpu:
            assert any(
                p["tpu_custom_call"] and "dense_group_fold_int" in p["kernels"]
                for p in folds
            ), f"px/http_stats: no dense_group_fold_int in {folds}"
    finally:
        pem.stop()
        kelvin.stop()
        tracker.close()
        bus.close()


def phase_distributed(rp: Replay, meter: CompileMeter, on_tpu: bool,
                      chips: int) -> None:
    """``DistributedEngine`` over ``agent_mesh(chips)`` on the same
    replay: windows resident row-sharded across the chips, the two
    shipped scripts checked against the same numpy reference."""
    import jax

    from pixie_tpu.parallel.executor import DistributedEngine
    from pixie_tpu.parallel.mesh import agent_mesh

    assert len(jax.devices()) >= chips, (
        f"--chips {chips} needs {chips} devices, have {len(jax.devices())}"
    )
    eng = DistributedEngine(window_rows=WINDOW, mesh=agent_mesh(chips))
    eng.create_table("http_events")
    t0 = time.perf_counter()
    n = rp.push(eng.append_data)
    res = _resident(eng.tables["http_events"], sharded_over=chips)
    emit(phase="distributed", step="ingest", rows=n, chips=chips,
         secs=time.perf_counter() - t0, resident=res)
    assert res["rows"] == n and len(res["devices"]) == chips
    run_queries(eng, rp, n, engine_queries()[:2], meter, on_tpu,
                "distributed", tracked=False)


def _fold_groups(eng) -> list:
    """(group, slots) of the last query's fold dispatches: how its rows
    found their groups and at what capacity."""
    return sorted({
        (sp.attributes["group"], sp.attributes["slots"])
        for sp in eng.tracer.last().spans
        if sp.name == "device.dispatch" and "group" in sp.attributes
    })


def phase_skew(seed: int, rows: int, meter: CompileMeter,
               on_tpu: bool) -> None:
    """The non-dense group-by: configuration ``http_full_1chip``'s data
    (Zipf keys, 65,536 request paths owned by 32 services, ten columns)
    at ``rows`` rows through ``Engine``, both shipped scripts against
    the benchmark's plain numpy reference. ``service`` x ``req_path``
    has no dense domain, so px/http_stats takes the sort route with a
    keyed state, and since its aggregates are exact integer statistics
    the rows ride the sort (``fold`` = ``sorted_int``; px/service_stats
    stays dense): its first run reads a sketch of the joint key and
    folds at the capacity that gives, not at the planner's bound (the
    product of the columns' NDVs); the second compiles nothing."""
    from benchmark.builders import served_http_skew
    from benchmark.reference import px_http_stats, px_service_stats
    from pixie_tpu.exec.engine import Engine
    from pixie_tpu.scripts import load_script

    with open(os.path.join(REPO, "benchmark", "configs",
                           "http_full_1chip.json")) as f:
        cfg = json.load(f)
    t0 = time.perf_counter()
    data = served_http_skew.make_data(cfg, seed, rows)
    eng = Engine(window_rows=WINDOW)
    for hb in served_http_skew.batches(data, WINDOW):
        eng.append_data("http_events", hb)
    res = _resident(eng.tables["http_events"])
    emit(phase="skew", step="ingest", rows=rows,
         secs=time.perf_counter() - t0, resident=res)
    assert res["rows"] == rows, f"resident rows {res['rows']} != {rows}"
    for name, ref in (("px/http_stats", px_http_stats),
                      ("px/service_stats", px_service_stats)):
        want = ref.answer(data, None)
        pxl = load_script(name).pxl
        # The reference's own limits, but for the quantiles: those are set
        # at the benchmark cell's size; here the smoke's 15 % stands.
        limits = {k: 0.15 if k.endswith(("p50_relerr", "p99_relerr")) else v
                  for k, v in ref.LIMITS.items()}
        for run in ("first", "warm"):
            mark = meter.mark()
            t0 = time.perf_counter()
            # Every group: the default cut is 10,000 rows a table.
            got = eng.execute_query(pxl, max_output_rows=1 << 17)
            secs = time.perf_counter() - t0
            rows_got = ref.rows(got["output"].to_pydict())
            if "lat_mean" in rows_got:
                # A bare Engine hands back the f64 quotient; the served
                # path rounds it once into an f32 plane, which is what
                # the reference's limits are for.
                rows_got["lat_mean"] = rows_got["lat_mean"].astype(
                    np.float32
                ).astype(np.float64)
            numbers = ref.numbers(rows_got, want)
            over = sorted(k for k, v in numbers.items() if v > limits[k])
            compiled = meter.since(mark)
            probes = [dict(sp.attributes) for sp in eng.tracer.last().spans
                      if sp.name == "group_probe"]
            emit(phase="skew", query=name, run=run, rows=rows, secs=secs,
                 groups=len(want["key"]), fold=_fold_routes(eng),
                 group=_fold_groups(eng), group_probe=probes,
                 compile=compiled, numbers=numbers)
            assert not over, f"{name} ({run}): over its limit: {over}"
        assert compiled["programs"] == 0, (
            f"{name}: second run compiled {compiled['programs']} program(s)"
        )
        folds = _fold_routes(eng)
        if name == "px/http_stats":
            assert all(
                g != "dense" and slots < 4 * len(want["key"])
                for g, slots in _fold_groups(eng)
            ), (f"{name}: expected the non-dense route at the sketched "
                f"capacity, got {_fold_groups(eng)}")
            # count / mean / max of an INT64 by a keyed state: the rows
            # ride the sort (the CPU's 'auto' hashes, and keeps the id form).
            assert not on_tpu or folds == ["sorted_int"], (
                f"{name}: fold spans say {folds}, not sorted_int")
        else:
            assert "sorted_int" not in folds and all(
                g == "dense" for g, _slots in _fold_groups(eng)
            ), f"{name}: expected a dense route, got {folds} {_fold_groups(eng)}"


def phase_flow(seed: int, rows: int, meter: CompileMeter,
               on_tpu: bool) -> None:
    """The join: configuration ``conn_flow_1chip``'s ``conn_stats``
    (4,096 pods x 8,192 addresses, Zipf keys, fifteen columns) at
    ``rows`` rows through ``Engine``, the bundled px/net_flow_graph
    against the benchmark's plain numpy reference, every number exact.
    Two keyed group-bys ride the sort, their rows join on the address
    strings of two dictionaries (one address a pod, so the build side is
    unique on one dictionary-coded key: a ``host_table`` lookup on the
    host at any size, on the chip and in a rehearsal alike, and no join
    program), and the join's rows are aggregated again; the second run
    compiles nothing."""
    from benchmark.builders import served_conn
    from benchmark.reference import px_net_flow_graph as ref
    from pixie_tpu.exec.engine import Engine
    from pixie_tpu.scripts import load_script

    with open(os.path.join(REPO, "benchmark", "configs",
                           "conn_flow_1chip.json")) as f:
        cfg = json.load(f)
    t0 = time.perf_counter()
    data = served_conn.make_data(cfg, seed, rows)
    eng = Engine(window_rows=WINDOW)
    for hb in served_conn.batches(data, WINDOW):
        eng.append_data("conn_stats", hb)
    res = _resident(eng.tables["conn_stats"])
    emit(phase="flow", step="ingest", rows=rows,
         secs=time.perf_counter() - t0, resident=res)
    assert res["rows"] == rows, f"resident rows {res['rows']} != {rows}"
    want = ref.answer(data, None)
    pxl = load_script("px/net_flow_graph").pxl
    for run in ("first", "warm"):
        mark = meter.mark()
        t0 = time.perf_counter()
        # Every edge: the default cut is 10,000 rows a table.
        got = eng.execute_query(pxl, max_output_rows=1 << 17)
        secs = time.perf_counter() - t0
        numbers = ref.numbers(ref.rows(got["output"].to_pydict()), want)
        compiled = meter.since(mark)
        joins = [dict(sp.attributes) for sp in eng.tracer.last().spans
                 if sp.name == "join"]
        emit(phase="flow", query="px/net_flow_graph", run=run, rows=rows,
             secs=secs, edges=len(want["key"]), fold=_fold_routes(eng),
             group=_fold_groups(eng), ride=_fold_routes(eng, "ride"),
             join=joins, compile=compiled, numbers=numbers)
        over = sorted(k for k, v in numbers.items() if v > ref.LIMITS[k])
        assert not over, f"px/net_flow_graph ({run}): over its limit: {over}"
    assert compiled["programs"] == 0, (
        f"px/net_flow_graph: second run compiled {compiled['programs']} "
        "program(s)")
    (join,) = joins
    assert (join["strategy"], join["where"]) == ("host_table", "host"), (
        f"the join of a unique dense build was no host lookup: {join}")
    assert join["domain"] > join["build_rows"], f"no table: {join}"
    assert not on_tpu or _fold_routes(eng) == ["sorted_int"], (
        f"fold spans say {_fold_routes(eng)}, not sorted_int")
    # ``flows``' two sums at a 2^21-row window ride the key sort (PR 35);
    # the re-aggregation of the join's rows is short against its slots.
    assert not on_tpu or "payload" in _fold_routes(eng, "ride"), (
        f"ride spans say {_fold_routes(eng, 'ride')}, no payload")


def phase_edges(seed: int, rows: int, meter: CompileMeter,
                on_tpu: bool) -> None:
    """The keyed quantiles: configuration ``http_edges_1chip``'s
    ``http_events`` (a pod's twenty clients in ``remote_addr``) at
    ``rows`` rows through ``Engine``, the cell's own script (the cluster
    view's service graph: three plucked quantiles, a BOOLEAN mean, a
    count and an INT64 sum by (remote_addr, pod, service)) over the whole
    table against the benchmark's plain numpy reference, every number
    inside its limit. On the chip's routes the integer aggregates ride
    the keyed sort and the digests are built beside it
    (``mixed:sorted_int=3,keyed_digest=3``), binned at nothing; the
    second run compiles nothing."""
    from benchmark.builders import served_http_edges
    from benchmark.reference import px_service_graph as ref
    from pixie_tpu.exec.engine import Engine

    with open(os.path.join(REPO, "benchmark", "configs",
                           "http_edges_1chip.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(REPO, "benchmark", "traffic", "graph_recent",
                           "service_graph.pxl")) as f:
        pxl = f.read().replace(", start_time='-5m'", "")
    t0 = time.perf_counter()
    data = served_http_edges.make_data(cfg, seed, rows)
    eng = Engine(window_rows=WINDOW)
    for hb in served_http_edges.batches(data, WINDOW):
        eng.append_data("http_events", hb)
    res = _resident(eng.tables["http_events"])
    emit(phase="edges", step="ingest", rows=rows,
         secs=time.perf_counter() - t0, resident=res)
    assert res["rows"] == rows, f"resident rows {res['rows']} != {rows}"
    want = ref.answer(data, None)
    for run in ("first", "warm"):
        mark = meter.mark()
        t0 = time.perf_counter()
        # Every edge: the default cut is 10,000 rows a table.
        got = eng.execute_query(pxl, max_output_rows=1 << 17)
        secs = time.perf_counter() - t0
        numbers = ref.numbers(ref.rows(got["output"].to_pydict()), want)
        compiled = meter.since(mark)
        emit(phase="edges", query="px/service_graph", run=run, rows=rows,
             secs=secs, edges=len(want["key"]), fold=_fold_routes(eng),
             group=_fold_groups(eng), ride=_fold_routes(eng, "ride"),
             digests=[_fold_routes(eng, a) for a in
                      ("digests", "digest_outputs", "digest_slots",
                       "digest_bins")],
             compile=compiled, numbers=numbers)
        over = sorted(k for k, v in numbers.items() if v > ref.LIMITS[k])
        assert not over, f"px/service_graph ({run}): over its limit: {over}"
    assert compiled["programs"] == 0, (
        f"px/service_graph: second run compiled {compiled['programs']} "
        "program(s)")
    want_fold = "mixed:sorted_int=3,keyed_digest=3"
    assert not on_tpu or _fold_routes(eng) == [want_fold], (
        f"fold spans say {_fold_routes(eng)}, not {want_fold}")
    # The three plucked quantiles of one column share ONE carry.
    assert _fold_routes(eng, "digests") == [1], "no one shared digest"
    assert _fold_routes(eng, "digest_outputs") == [3], "no three outputs"
    assert _fold_routes(eng, "digest_bins") == [1 << 32], (
        f"a window's rows were binned: {_fold_routes(eng, 'digest_bins')}")


def phase_cluster(seed: int, rows: int, meter: CompileMeter,
                  on_tpu: bool) -> None:
    """Four nodes answered as the deployment answers them: configuration
    ``http_cluster_4chip``'s stack at ``rows`` rows over the cluster (a
    PEM a node, each an ``Engine`` on a chip of its own holding its
    node's rows under dictionaries of its own, one Kelvin, one broker),
    the cell's two scripts over the whole table three times, against the
    benchmark's plain references over the union: every number inside its
    limit. Every PEM's programs run on its own chip, the Kelvin merges
    four payloads a script through remaps that are not empty, and the
    third run compiles nothing (the second's merge may: the Kelvin has
    seen the union fit a smaller bucket than the payloads' sum)."""
    from benchmark import harness
    from benchmark.builders import served_http_nodes

    spec = harness.load_cell("http_cluster_4chip.cluster_recent")
    cfg = spec["config"]
    requests = [
        {**r, "pxl": r["pxl"].replace(", start_time='-5m'", "")}
        for r in harness.requests_of(spec)
    ]
    t0 = time.perf_counter()
    data = served_http_nodes.make_data(cfg, seed, rows)
    stack = served_http_nodes.build(cfg, WINDOW)
    try:
        stack.ingest(data)
        res = stack.resident()
        emit(phase="cluster", step="ingest", rows=rows,
             secs=time.perf_counter() - t0, resident=res)
        assert (res["rows"], res["devices"]) == (rows, cfg["nodes"]), (
            f"resident {res}, want {rows} rows on {cfg['nodes']} devices")
        log = harness.SpanLog(stack.tracers)
        for run in ("first", "again", "warm"):
            for req in requests:
                ref = harness.module("reference", req["reference"])
                mark = meter.mark()
                t0 = time.perf_counter()
                got = stack.execute(req["pxl"], 600.0, cfg["t_end_ns"])
                secs = time.perf_counter() - t0
                assert not got["partial"], f"{req['name']}: partial"
                numbers = ref.numbers(ref.rows(got["rows"]),
                                      ref.answer(data, None))
                compiled = meter.since(mark)
                time.sleep(0.1)  # the agents' traces close after eos
                spans = log.cut()
                merge = [s.attributes for t in spans["kelvin"]
                         for s in t.spans if s.name == "device.dispatch"]
                # (PR 47) what the k-way fold joined, on the merge's wait.
                joined = [
                    {k: s.attributes[k] for k in ("contended_slots", "rebins")}
                    for t in spans["kelvin"] for s in t.spans
                    if s.name == "device.wait" and "rebins" in s.attributes]
                devices = sorted({
                    s.attributes["device"] for k, traces in spans.items()
                    if k.startswith("pem") for t in traces for s in t.spans
                    if s.name == "device.dispatch"})
                emit(phase="cluster", query=req["name"], run=run, rows=rows,
                     secs=secs, answer_rows=len(next(iter(
                         got["rows"].values()))),
                     pem_devices=devices, merge=merge, joined=joined,
                     compile=compiled, numbers=numbers)
                over = sorted(k for k, v in numbers.items()
                              if v > ref.LIMITS[k])
                assert not over, (
                    f"{req['name']} ({run}): over its limit: {over}")
                assert len(devices) == cfg["nodes"], (
                    f"the PEMs' programs ran on devices {devices}")
                assert merge and merge[-1]["payloads"] == cfg["nodes"], merge
                assert merge[-1].get("remap_entries", 0) > 0, (
                    "the nodes' dictionaries were equal")
                # Four keyed payloads fold ONCE; the graph's edges are
                # disjoint by node (nothing re-binned), the (service,
                # req_path) groups overlap and hold no digest.
                assert joined and joined[-1]["rebins"] == 0, joined
                assert (joined[-1]["contended_slots"] > 0) == (
                    req["name"] == "px/http_stats"), (req["name"], joined)
                if run == "warm":
                    assert compiled["programs"] == 0, (
                        f"{req['name']}: third run compiled "
                        f"{compiled['programs']} program(s)")
    finally:
        stack.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=16 << 20,
                    help="http_events rows (default 16 Mi = 512 MiB)")
    ap.add_argument("--seed", type=int, default=22)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the DistributedEngine phase and the four-node "
                         "cluster phase, and nothing else")
    args = ap.parse_args(argv)

    from pixie_tpu import native
    from pixie_tpu.utils.cache import configure_jax_cache

    cache_dir = configure_jax_cache()
    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    on_tpu = dev.platform == "tpu"
    emit(device=device, cache_dir=cache_dir, rows=args.rows, seed=args.seed,
         window_rows=WINDOW, jax=jax.__version__)
    if not on_tpu and args.rows > REHEARSAL_MAX_ROWS:
        emit(error=f"no TPU (platform {dev.platform}); without one only a "
                   f"rehearsal at --rows <= {REHEARSAL_MAX_ROWS} runs")
        emit(ok=False, device=device)
        return 1

    ok = False
    try:
        emit(native_rebuilt=native.rebuild_all(), libraries=native.LIBRARIES)
        from unittest import mock

        from pixie_tpu.ops import routes

        meter = CompileMeter()
        rp = Replay(args.rows, args.seed)
        with contextlib.ExitStack() as rehearsal:
            if not on_tpu:
                # A rehearsal of the control flow: the chip's routes on
                # this backend (sorts, the kernels interpreted, the scan
                # fold, no native fold), by substituting the one function
                # every route choice asks. On the chip nothing is set.
                rehearsal.enter_context(mock.patch.object(
                    routes, "routes_platform", lambda: "tpu"))
            phase_int_kernel(args.seed, min(args.rows, WINDOW), on_tpu,
                             args.chips)
            if args.chips == 4:
                phase_distributed(rp, meter, on_tpu, 4)
                phase_cluster(args.seed, min(args.rows, CLUSTER_ROWS),
                              meter, on_tpu)
            else:
                phase_engine(rp, meter, on_tpu)
                phase_served(rp, meter, on_tpu)
                phase_skew(args.seed, min(args.rows, SKEW_ROWS), meter,
                           on_tpu)
                phase_flow(args.seed, min(args.rows, FLOW_ROWS), meter,
                           on_tpu)
                phase_edges(args.seed, min(args.rows, EDGES_ROWS), meter,
                            on_tpu)
        emit(total_compile=meter.since())
        ok = on_tpu
        if not ok:
            emit(error=f"rehearsal on {dev.platform}: not a chip run")
    except BaseException:
        traceback.print_exc()
        emit(error=traceback.format_exc(limit=3).splitlines()[-1])
    emit(ok=ok, device=device)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
