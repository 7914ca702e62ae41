"""The lowered text of the shipped scripts' fold programs, for the chip's
routes, with no chip: what a refactor of ``exec/fragment.py`` compares
against its parent before it asks for one.

Phase 1 runs ``px/http_stats`` and ``px/service_stats`` through a broker,
one PEM and a Kelvin on the CPU at a tiny size, over the dense cells'
data (32 services x 64 paths: 2,145 slots) and over ``http_full_1chip``'s
(65,536 paths: the keyed route), and records every aggregate fragment the
PEM and the Kelvin compile. Phase 2 compiles the same chains again as the
chip would see them (the backend answers ``tpu``, the devices are a
described ``v5e:2x2``'s) and lowers ``update``, ``update_all`` (three
windows), ``merge_states``, ``finalize`` and (PR 48) ``init_state``, the
one program of a fold's empty state, at the benchmark's 2^21-row
window; a keyed chain at the 131,072 slots ``http_full_1chip`` settles on.
It also prepares the Kelvin's merge of each script as a request does
(``exec/bridge.py`` ``_prepare_merge``, from the payloads the served run
shipped: the three configurations have two sets of dictionaries, the
four-chip one serves the one-chip one's) and lowers AND compiles its one
program, ``merge_finalize``, at the capacity the cell's live groups give
(2,048 / 1,024 / 65,536 / 1,024 slots), printing the compile's seconds.
The ``flow`` case is ``px/net_flow_graph`` over ``conn_flow_1chip``'s
``conn_stats``: every program a cold run of its cell compiles for the
chip, lowered AND compiled at the cell's shapes (``FLOW``): the PEM's two
keyed folds of one window and their joint-key sketches, the Kelvin's two
keyed ``merge_finalize``, the single-shot device join, and the
re-aggregation's fold and finalize of the join's rows. The ``digest``
case (PR 33) is ``px/service_stats``' fold, the one chain whose
``quantiles`` sort their rows (``ops/tdigest.py``): ``update`` and
``update_all`` at the 2^21-row window on one chip, and the four-chip
cell's ``shard_map`` step over the described 2x2's four devices (2^19 rows
a chip), each lowered AND compiled. The ``sql`` case (PR 34) is
``px/sql_stats`` over ``sql_stats_1chip``'s ``mysql_events``, served on TWO
seeds (``sql`` and ``sql2``): the PEM's keyed fold of one window and its
joint-key sketch, and the Kelvin's unpacked ``merge_finalize``, at the
cell's capacities (``SQL``), lowered AND compiled. The two seeds' strings
differ and their texts must not: the remap of ``px.normalize_mysql`` is
an operand of the programs (``exec/expr.py``), so the last line says
``two_seeds_one_text`` and the exit code is 1 if it is false. (The
operand's shape follows the served run's small dictionary, not the
cell's 2^22-entry bucket.)

The ``flame`` case (PR 39) is ``px/perf_flamegraph`` over
``stack_flame_1chip``'s ``stack_traces.beta``: what a cold run of its cell
compiles, lowered AND compiled at the cell's shapes (``FLAME``): the PEM's
keyed fold of the two windows in range with ``any`` of a string beside a
sum at 2^20 slots (``update_all``) and its joint-key sketch, the dense
fold by pod, the Kelvin's two ``merge_finalize``, the single-shot device
join of 4,096 + 2^20 rows into 2^21 output slots.

The ``cluster`` case (PR 46) is ``http_cluster_4chip.cluster_recent``:
its two scripts served by FOUR PEMs, a node's rows and dictionaries each
(``benchmark/builders/served_http_nodes.py``), so the Kelvin's prepared
merges hold four payloads and remaps that are not empty. What a cold run
of the cell compiles, lowered AND compiled at the cell's shapes
(``CLUSTER``): a PEM's keyed folds of one window (``update``; with
``--rows 1048576`` the slice the cell's five minutes take) at 2^15 and
2^16 slots with their joint-key sketches, and ``merge_finalize`` of four
states at the bucket of their sum (2^17, 2^18) and at the one the union
was seen to fit (2^16); the line carries ``remap_entries``.

``--rows N`` (PR 44) lowers the window programs alone (``update``,
``update_all``) as the engine calls them for a range short against its
window: handed N rows of each 2^21-row plane from a start that is an
argument (``exec/fragment.py`` ``RowSlice``), under names that end
``.rows<N>``. Without it every text is a whole window's, which is what a
full window, the mesh step and every Kelvin program stay.

    JAX_PLATFORMS=cpu python tools/fold_hlo.py --out DIR

writes one ``<case>.<program>.txt`` a program and prints one line a
program with the text's sha256: two trees agree when their lines do
(``diff`` the two outputs, or the directories). A Pallas kernel sits in the
text as serialized MLIR that carries its call site's file and line, which a
refactor moves: it is replaced by the sha256 of its assembly printed
without locations. Nothing runs on a device.
"""

import argparse
import base64
import dataclasses
import hashlib
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
os.environ.setdefault("TPU_LOG_DIR", "disabled")

WINDOW = 1 << 21
KEYED_SLOTS = 1 << 17
ROWS, SMALL_WINDOW = 1 << 14, 1 << 12
#: The ``flow`` case's shapes, by chain label: (the fold's capacity, the
#: Kelvin's merge bucket, the fold's window), as
#: ``conn_flow_1chip.flow_recent`` settles on them (56.7 k live pairs,
#: 4,096 pods, 45.4 k edges). The third chain is the Kelvin's
#: re-aggregation of the join's rows: no merge, a window of their bucket.
FLOW = {
    "sum+sum_by_src_pod_remote_addr": (1 << 17, 1 << 16, WINDOW),
    "_by_src_addr_src_pod": (1 << 13, 1 << 12, WINDOW),  # its count is pruned
    "sum+sum_by_src_pod_src_pod_dst": (1 << 16, None, 1 << 16),
}
#: The single-shot join's (build, probe, output) buckets.
FLOW_JOIN = (1 << 12, 1 << 16, 1 << 17)
#: The ``sql`` case's shapes, as ``FLOW``'s: ``sql_stats_1chip.sql_recent``
#: settles on 2^17 slots for its 74 k (shape, second) groups, which the
#: Kelvin merges in a 2^17 bucket.
SQL = {"count+mean_by_query_norm_window": (1 << 17, 1 << 17, WINDOW)}
SQL_ROWS = 1 << 16  # every one of the 290 shapes has rows at this size
#: The ``flame`` case's shapes, as ``FLOW``'s:
#: ``stack_flame_1chip.flame_recent`` settles on 2^20 slots for its 0.64 M
#: (pod, stack_trace_id) groups, merged in a 2^20 bucket; the chain by pod
#: is dense (its capacity is its dictionary's) and merges in 4,096.
FLAME = {
    "any+sum_by_pod_stack_trace_id": (1 << 20, 1 << 20, WINDOW),
    "sum_by_pod": (None, 1 << 12, WINDOW),
}
#: (The join's output is sized at twice the probe's 0.64 M rows,
#: ``join_capacity_safety``: 2^21 slots.)
FLAME_JOIN = (1 << 12, 1 << 20, 1 << 21)
FLAME_WINDOWS = 2  # the windows ``-5m`` holds
#: The ``edges`` case's shapes, as ``FLOW``'s:
#: ``http_edges_1chip.graph_recent`` settles on 2^17 slots for its 80 k
#: (remote_addr, pod, service) edges (ONE [2^17, 128] digest, shared by
#: the three plucked quantiles of the one column, beside the integer
#: carries), merged in a 2^17 bucket; three windows in range.
EDGES = {
    "mean+count+sum+_quantile_p50+_quantile_p90+_quantile_p99"
    "_by_remote_addr_pod_service": (1 << 17, 1 << 17, WINDOW),
}
#: The ``cluster`` case's shapes, as ``FLOW``'s, with FOUR payloads a
#: merge: a PEM of ``http_cluster_4chip.cluster_recent`` settles on 2^15
#: slots for its node's 18.8 k live edges and on 2^16 for its 47-48 k
#: (service, req_path) groups, and folds one sliced window a script (run
#: with ``--rows 1048576``); the Kelvin merges four states of that bucket
#: each at the bucket of the live groups' SUM (2^17 for 75 k edges,
#: disjoint; 2^18 for 190 k groups) and, once it has seen the union fit a
#: smaller one, there (``CLUSTER_KNOWN``: 63 k groups in 2^16).
CLUSTER = {
    "mean+count+sum+_quantile_p50+_quantile_p90+_quantile_p99"
    "_by_remote_addr_pod_service": (1 << 15, 1 << 15, WINDOW),
    "count+mean+max_by_service_req_path": (1 << 16, 1 << 16, WINDOW),
}
CLUSTER_KNOWN = {"count+mean+max_by_service_req_path": 1 << 16}


def _sized(case):
    """The chains' (capacity, merge bucket, window) where the case runs a
    cell's one script at the cell's shapes; None otherwise."""
    if case.startswith("flow"):
        return FLOW
    if case.startswith("flame"):
        return FLAME
    if case.startswith("edges"):
        return EDGES
    if case.startswith("cluster"):
        return CLUSTER
    return SQL if case.startswith("sql") else None


def _capture(batches, table="http_events",
             scripts=("px/http_stats", "px/service_stats"), more_pems=()):
    """Every (who, ops, relation, dicts, allow_dense, col_stats) of an
    aggregate fragment compiled while the scripts are served.
    ``more_pems``: the batches of each further PEM (a node a PEM, with
    dictionaries of its own: the Kelvin then merges k payloads)."""
    from pixie_tpu.exec import fragment
    from pixie_tpu.exec.engine import Engine
    from pixie_tpu.scripts import load_script
    from pixie_tpu.services import (
        AgentTracker, KelvinAgent, MessageBus, PEMAgent, QueryBroker,
    )

    from pixie_tpu.exec import bridge

    seen, real = [], fragment.compile_fragment
    merges, real_prepare = [], bridge._prepare_merge

    def spy_prepare(engine, payloads, tail, slots, key):
        merges.append((list(payloads), list(tail)))
        return real_prepare(engine, payloads, tail, slots, key)

    def spy(ops, relation, dicts, registry, allow_dense=True, col_stats=None):
        frag = real(ops, relation, dicts, registry, allow_dense,
                    col_stats=col_stats)
        if frag.is_agg:
            seen.append((list(ops), relation, dict(dicts), allow_dense,
                         col_stats))
        return frag

    fragment.compile_fragment = spy
    bridge._prepare_merge = spy_prepare
    fragment._FRAGMENT_CACHE.clear()
    bus = MessageBus()
    tracker = AgentTracker(bus, expiry_s=60.0, check_interval_s=60.0)
    pem = PEMAgent(bus, "pem-0", heartbeat_interval_s=0.05,
                   engine=Engine(window_rows=SMALL_WINDOW)).start()
    kelvin = KelvinAgent(bus, "kelvin-0", heartbeat_interval_s=0.05).start()
    pems = [pem] + [
        PEMAgent(bus, f"pem-{n}", heartbeat_interval_s=0.05,
                 engine=Engine(window_rows=SMALL_WINDOW)).start()
        for n in range(1, 1 + len(more_pems))
    ]
    try:
        for agent, its in zip(pems, (batches, *more_pems)):
            for hb in its:
                agent.append_data(table, hb)
            agent._register()
        _wait_for_table(tracker, table, len(pems))
        broker = QueryBroker(bus, tracker)
        for script in scripts:
            # A bundled script's name, or the text of a traffic's own.
            pxl = script if "\n" in script else load_script(script).pxl
            res = broker.execute_script(pxl,
                                        timeout_s=300, max_output_rows=1 << 17)
            assert not res.get("partial"), script
    finally:
        fragment.compile_fragment = real
        bridge._prepare_merge = real_prepare
        for agent in pems:
            agent.stop()
        kelvin.stop()
        tracker.close()
        bus.close()
    return seen, merges


def _wait_for_table(tracker, table, pems=1):
    import time

    deadline = time.time() + 30
    while len(tracker.distributed_state().pems_with_table(table)) < pems:
        assert time.time() < deadline, "the PEM's schema did not reach the tracker"
        time.sleep(0.01)


def _agg_label(ops):
    from pixie_tpu.exec.plan import AggOp

    agg = next(op for op in ops if isinstance(op, AggOp))
    return "+".join(a.uda_name for a in agg.aggs) + "_by_" + "_".join(
        agg.group_cols)


def _without_kernel_locations(text):
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    ctx = mlir.make_ir_context()
    ctx.allow_unregistered_dialects = True

    def digest(m):
        cfg = json.loads(m.group(1).replace("\\22", '"'))
        with ctx:
            asm = ir.Module.parse(
                base64.b64decode(cfg["custom_call_config"]["body"])
            ).operation.get_asm(enable_debug_info=False)
        cfg["custom_call_config"]["body"] = hashlib.sha256(
            asm.encode()).hexdigest()
        return "backend_config = " + json.dumps(cfg, sort_keys=True)

    return re.sub(
        r'backend_config = "(\{\\22custom_call_config.*?\})"', digest, text
    )


def _record(lines, out_dir, name, program, fold, text, **more):
    """One program's line (printed, kept) and its text (written)."""
    lines.append({"case": name, "program": program, "fold": fold,
                  "sha256": hashlib.sha256(text.encode()).hexdigest()[:16],
                  "bytes": len(text), **more})
    print(json.dumps(lines[-1]), flush=True)
    if out_dir:
        with open(os.path.join(out_dir, f"{name}.{program}.txt"), "w") as f:
            f.write(text)


def _lower(case, captured, topo_device, out_dir, lines, rows=None):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from pixie_tpu.exec.fragment import (
        OperandProgram, RowSlice, compile_fragment,
    )
    from pixie_tpu.exec.plan import AggOp
    from pixie_tpu.types.dtypes import device_dtypes
    from pixie_tpu.udf.registry import default_registry

    chip = SingleDeviceSharding(topo_device)

    def on(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
            tree,
        )

    for ops, relation, dicts, allow_dense, col_stats in captured:
        window, flow = WINDOW, _sized(case) is not None
        digest = case == "digest"
        if digest and not (allow_dense and _folds_quantiles(ops)):
            continue
        if case.startswith("keyed"):
            slots = KEYED_SLOTS
        elif flow:
            if not allow_dense:
                continue  # the Kelvin's merge of a chain: ``_lower_merges``
            slots, merged_at, window = _sized(case)[_agg_label(ops)]
        else:
            slots = None
        if slots is not None:
            ops = [dataclasses.replace(op, max_groups=slots)
                   if isinstance(op, AggOp) else op for op in ops]
        # (A node's dictionaries hold the strings of its rows alone: at
        # the served run's size their product is a dense domain, at the
        # cell's 6.6 k addresses x 1,024 pods, 65 k paths x 32 services,
        # it is not: the cluster's PEM chains are compiled keyed.)
        frag = compile_fragment(ops, relation, dicts, default_registry(),
                                allow_dense and case != "cluster",
                                col_stats=col_stats)
        # The flow case's unmerged chain is the Kelvin's re-aggregation.
        on_kelvin = not allow_dense or (flow and merged_at is None)
        who = "kelvin" if on_kelvin else "pem"
        name = f"{case}.{who}.{_agg_label(ops)}.{frag.group}{frag.slots}"
        if rows:
            if on_kelvin or rows >= window:
                continue  # no resident window, or the whole one
            name += f".rows{rows}"
        if any(line["case"] == name for line in lines):
            continue  # compiled twice (the probe, then the capacity)
        state = on(jax.eval_shape(frag.init_state))
        cols = {
            c: tuple(jax.ShapeDtypeStruct((window,), dt, sharding=chip)
                     for dt in device_dtypes(t))
            for c, t in relation.items()
        }
        scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)
        n_windows = FLAME_WINDOWS if case == "flame" else 3
        bounds = jax.ShapeDtypeStruct((n_windows,), jnp.int32, sharding=chip)
        valid = jax.ShapeDtypeStruct((window,), jnp.bool_, sharding=chip)
        programs = {
            "merge_states": lambda: jax.jit(frag.merge_states).lower(
                state, state),
            "finalize": lambda: frag.finalize.lower(state),
            # ``--rows``: the programs of a range short against its
            # window, handed that many rows of each plane.
            "update": lambda: frag.update.lower(
                state, cols, (scalar, scalar),
                *([RowSlice(scalar, rows)] if rows else [])),
            "update_all": lambda: frag.update_all.lower(
                state, (cols,) * n_windows, bounds, bounds,
                *([RowSlice(bounds, rows)] if rows else [])),
            # (A program that takes operand tables lowers itself, with
            # the tables' shapes: ``fragment.OperandProgram``.)
            "group_sketch": lambda: (
                frag.group_sketch
                if isinstance(frag.group_sketch, OperandProgram)
                else jax.jit(frag.group_sketch)
            ).lower(on(jax.eval_shape(frag.init_sketch)), cols, valid),
            # The fold's empty state (PR 48: ``frag.init_program``, one
            # program of no argument; here its function with the
            # described device named, where the engine's scope names it).
            "init_state": lambda: jax.jit(
                frag.init_state, out_shardings=chip).lower(),
        }
        if case in ("flame", "edges"):  # windows in range: one scan program
            wanted = ("update_all",) + (
                ("group_sketch",) if frag.group_sketch else ())
        elif flow:  # what the cell runs: one window in range a chain
            wanted = ("update", "group_sketch" if who == "pem" else "finalize")
        elif digest:
            wanted = ("update", "update_all", "mesh_agg_step")
            programs["mesh_agg_step"] = lambda: _mesh_step(frag, relation)
        elif allow_dense:
            wanted = ("finalize", "merge_states", "update", "update_all")
        else:  # the Kelvin folds no window: it merges and finalizes
            wanted = ("finalize", "merge_states")
        if rows:
            wanted = [p for p in wanted if p in ("update", "update_all")]
        elif {"update", "update_all"} & set(wanted):
            wanted = (*wanted, "init_state")  # whoever folds windows
        for program in sorted(wanted):
            lowered = programs[program]()
            more = (
                {"compile_s": _compile_s(lowered)} if flow or digest else {}
            )
            _record(lines, out_dir, name, program, frag.fold,
                    _without_kernel_locations(lowered.as_text()), **more)


def _folds_quantiles(ops) -> bool:
    """The chain is ``px/service_stats``': its AggOp holds a t-digest."""
    from pixie_tpu.exec.plan import AggOp

    agg = next(op for op in ops if isinstance(op, AggOp))
    return any(a.uda_name.startswith("_quantile_") for a in agg.aggs)


def _mesh_step(frag, relation):
    """The four-chip cell's window step (``parallel/executor.py``
    ``distributed_agg_step``, the device-resident form), lowered over
    the described topology's four devices: a 2^21-row window row-sharded,
    2^19 rows a chip, the state replicated."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pixie_tpu.parallel.executor import distributed_agg_step
    from pixie_tpu.parallel.mesh import agent_mesh, row_sharding
    from pixie_tpu.types.dtypes import device_dtypes

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = agent_mesh(4, devices=topo.devices)
    whole = NamedSharding(mesh, P())
    state = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=whole),
        jax.eval_shape(frag.init_state),
    )
    cols = {
        c: tuple(jax.ShapeDtypeStruct((WINDOW,), dt,
                                      sharding=row_sharding(mesh))
                 for dt in device_dtypes(t))
        for c, t in relation.items()
    }
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=whole)
    return distributed_agg_step(frag, mesh, range_valid=True).lower(
        state, cols, {}, (scalar, scalar))


def _compile_s(lowered) -> float:
    import time

    t0 = time.perf_counter()
    lowered.compile()
    return round(time.perf_counter() - t0, 2)


def _lower_join(case, topo_device, out_dir, lines):
    """The single-shot device join at the case's buckets (``flow``: the
    merged ``addrs`` build, the merged ``flows`` probe; ``flame``: the
    pods' totals, the merged stacks), int32 string codes aligned to one
    dictionary."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from pixie_tpu.ops.join import device_join

    chip = SingleDeviceSharding(topo_device)
    nb, npr, cap = FLAME_JOIN if case == "flame" else FLOW_JOIN

    def plane(n, dt):
        return jax.ShapeDtypeStruct((n,), dt, sharding=chip)

    lowered = jax.jit(
        lambda bk, bv, pk, pv: device_join(bk, bv, pk, pv, cap, "inner")
    ).lower([plane(nb, jnp.int32)], plane(nb, jnp.bool_),
            [plane(npr, jnp.int32)], plane(npr, jnp.bool_))
    _record(lines, out_dir, f"{case}.kelvin.join.nb{nb}.np{npr}.cap{cap}",
            "join_single_shot", "-", lowered.as_text(),
            compile_s=_compile_s(lowered))


def _lower_merges(case, merges, topo_device, out_dir, lines):
    """The Kelvin's ``merge_finalize`` of each script: the payloads the
    served run shipped, their states compacted as a request compacts
    them, at the capacity the cell's live groups give (a keyed chain:
    ``http_full_1chip``'s 63 k groups in a 65,536-slot bucket). Lowered
    and COMPILED for the described device: the line carries the
    seconds."""
    import types

    import jax
    from jax.sharding import SingleDeviceSharding

    from pixie_tpu.exec import bridge
    from pixie_tpu.types.batch import bucket_capacity
    from pixie_tpu.udf.registry import default_registry

    from pixie_tpu.exec import placement

    chip = SingleDeviceSharding(topo_device)
    engine = types.SimpleNamespace(registry=default_registry(),
                                   _put=placement.put)
    for payloads, tail in merges:
        slots = [bridge._live_slots(p.state) for p in payloads]
        caps = [cap for _idx, _live, cap in slots]
        if case.startswith("keyed") and not payloads[0].dense_domains:
            caps = [KEYED_SLOTS // 2] * len(payloads)
        elif _sized(case) is not None:
            caps = [_sized(case)[_agg_label(payloads[0].chain)][1]] * len(
                payloads)
        buckets = [bucket_capacity(sum(caps))]
        if case == "cluster":  # and the bucket the union was seen to fit
            known = CLUSTER_KNOWN.get(_agg_label(payloads[0].chain))
            buckets += [known] if known else []
        for g in buckets:
            rec = bridge._prepare_merge(engine, payloads, tail, g, None)
            name = (f"{case}.kelvin.{_agg_label(payloads[0].chain)}"
                    f".k{len(payloads)}.{rec.frag.group}{g}")
            if any(line["case"] == name for line in lines):
                continue
            states = [
                jax.tree_util.tree_map(
                    lambda a, have=have, cap=cap: jax.ShapeDtypeStruct(
                        (cap,) + a.shape[1:] if a.ndim and a.shape[0] == have
                        else a.shape, a.dtype, sharding=chip),
                    bridge._explicit_state(p, idx, rec.key_types),
                )
                for p, (idx, _live, have), cap in zip(payloads, slots, caps)
            ]
            # (The remaps are arrays of the host's backend here: their
            # shapes, on the described device.)
            remaps = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=chip), rec.remaps)
            lowered = rec.program.lower(states, remaps)
            _record(lines, out_dir, name, "merge_finalize", rec.frag.fold,
                    _without_kernel_locations(lowered.as_text()),
                    compile_s=_compile_s(lowered),
                    remap_entries=sum(int(t.shape[0]) for remap in rec.remaps
                                      for t in remap.values()))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=None, help="directory for the texts")
    ap.add_argument("--cases", default="dense,keyed,flow",
                    help="comma-separated, of dense, keyed, flow, digest, "
                         "sql, flame, edges, cluster")
    ap.add_argument("--rows", type=int, default=None,
                    help="lower the window programs alone, handed this many "
                         "rows of each 2^21-row plane (a fragment.RowSlice: "
                         "a range short against its window)")
    args = ap.parse_args()
    if args.out:
        os.makedirs(args.out, exist_ok=True)

    import jax

    import pixie_tpu  # noqa: F401
    from benchmark.builders import (
        served_conn, served_http_edges, served_http_nodes, served_http_skew,
        served_sql, served_stacks,
    )
    from pixie_tpu.ingest.replay import gen_http_events

    def config(name):
        with open(os.path.join(ROOT, "benchmark", "configs",
                               f"{name}.json")) as f:
            return json.load(f)

    def keyed():
        skew = served_http_skew.make_data(
            config("http_full_1chip"), 3_000_000_019, ROWS)
        return _capture(list(
            served_http_skew.batches(skew, SMALL_WINDOW, 0, ROWS)))

    def flow():
        conn = served_conn.make_data(
            config("conn_flow_1chip"), 3_000_000_019, ROWS)
        return _capture(
            list(served_conn.batches(conn, SMALL_WINDOW, 0, ROWS)),
            table="conn_stats", scripts=("px/net_flow_graph",))

    def sql(seed):
        data = served_sql.make_data(config("sql_stats_1chip"), seed, SQL_ROWS)
        return _capture(
            list(served_sql.batches(data, SMALL_WINDOW, 0, SQL_ROWS)),
            table="mysql_events", scripts=("px/sql_stats",))

    def flame():
        data = served_stacks.make_data(
            config("stack_flame_1chip"), 3_900_000_019, SQL_ROWS)
        return _capture(
            list(served_stacks.batches(data, SMALL_WINDOW, 0, SQL_ROWS)),
            table="stack_traces.beta", scripts=("px/perf_flamegraph",))

    def edges():
        cfg = {**config("http_edges_1chip"), "requires": {}}
        data = served_http_edges.make_data(cfg, 4_100_000_019, SQL_ROWS)
        with open(os.path.join(ROOT, "benchmark", "traffic", "graph_recent",
                               "service_graph.pxl")) as f:
            pxl = f.read()
        return _capture(
            list(served_http_edges.batches(data, SMALL_WINDOW, 0, SQL_ROWS)),
            scripts=(pxl,))

    def cluster():
        cfg = {**config("http_cluster_4chip"), "requires": {}}
        data = served_http_nodes.make_data(cfg, 4_600_000_019, SQL_ROWS)
        pxl = []
        for name in ("service_graph", "http_stats"):
            with open(os.path.join(ROOT, "benchmark", "traffic",
                                   "cluster_recent", f"{name}.pxl")) as f:
                pxl.append(f.read())
        first, *rest = (
            list(served_http_skew.batches(part, SMALL_WINDOW))
            for part in data["parts"])
        return _capture(first, scripts=tuple(pxl), more_pems=rest)

    cases = {
        "dense": lambda: _capture(list(gen_http_events(ROWS, seed=3))),
        "keyed": keyed, "flow": flow,
        "digest": lambda: _capture(list(gen_http_events(ROWS, seed=3))),
        "sql": lambda: sql(3_400_000_019),
        "sql2": lambda: sql(3_400_000_023),
        "flame": flame, "edges": edges, "cluster": cluster,
    }
    wanted = args.cases.split(",")
    if "sql" in wanted:
        wanted.append("sql2")
    captured = {case: cases[case]() for case in wanted}

    # From here on the code sees the chip: the one read of the backend
    # (ops/routes.py) answers "tpu", and every shape sits on a described
    # v5e device, so the kernels lower through Mosaic, uninterpreted.
    from jax.experimental import topologies

    from pixie_tpu.exec import fragment

    jax.default_backend = lambda: "tpu"
    fragment._FRAGMENT_CACHE.clear()
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    lines = []
    for case, (seen, merges) in captured.items():
        _lower(case, seen, topo.devices[0], args.out, lines, args.rows)
        if args.rows:
            continue
        _lower_merges(case, merges, topo.devices[0], args.out, lines)
        if case in ("flow", "flame"):
            _lower_join(case, topo.devices[0], args.out, lines)
    if "sql" in captured:
        texts = {
            case: sorted((line["case"].split(".", 1)[1], line["program"],
                          line["sha256"]) for line in lines
                         if line["case"].split(".")[0] == case)
            for case in ("sql", "sql2")
        }
        same = bool(texts["sql"]) and texts["sql"] == texts["sql2"]
        print(json.dumps({"case": "sql", "programs": len(texts["sql"]),
                          "two_seeds_one_text": same}), flush=True)
        return 0 if same else 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
