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
windows), ``merge_states`` and ``finalize`` at the benchmark's 2^21-row
window; a keyed chain at the 131,072 slots ``http_full_1chip`` settles on.
It also prepares the Kelvin's merge of each script as a request does
(``exec/bridge.py`` ``_prepare_merge``, from the payloads the served run
shipped: the three configurations have two sets of dictionaries, the
four-chip one serves the one-chip one's) and lowers AND compiles its one
program, ``merge_finalize``, at the capacity the cell's live groups give
(2,048 / 1,024 / 65,536 / 1,024 slots), printing the compile's seconds.

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


def _capture(batches):
    """Every (who, ops, relation, dicts, allow_dense, col_stats) of an
    aggregate fragment compiled while the two scripts are served."""
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
    try:
        for hb in batches:
            pem.append_data("http_events", hb)
        pem._register()
        _wait_for_table(tracker)
        broker = QueryBroker(bus, tracker)
        for script in ("px/http_stats", "px/service_stats"):
            res = broker.execute_script(load_script(script).pxl,
                                        timeout_s=300, max_output_rows=1 << 17)
            assert not res.get("partial"), script
    finally:
        fragment.compile_fragment = real
        bridge._prepare_merge = real_prepare
        pem.stop()
        kelvin.stop()
        tracker.close()
        bus.close()
    return seen, merges


def _wait_for_table(tracker):
    import time

    deadline = time.time() + 30
    while not tracker.distributed_state().pems_with_table("http_events"):
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


def _lower(case, captured, topo_device, out_dir, lines):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from pixie_tpu.exec.fragment import compile_fragment
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
        if case.startswith("keyed"):
            ops = [dataclasses.replace(op, max_groups=KEYED_SLOTS)
                   if isinstance(op, AggOp) else op for op in ops]
        frag = compile_fragment(ops, relation, dicts, default_registry(),
                                allow_dense, col_stats=col_stats)
        who = "pem" if allow_dense else "kelvin"
        name = f"{case}.{who}.{_agg_label(ops)}.{frag.group}{frag.slots}"
        if any(line["case"] == name for line in lines):
            continue  # compiled twice (the probe, then the capacity)
        state = on(jax.eval_shape(frag.init_state))
        cols = {
            c: tuple(jax.ShapeDtypeStruct((WINDOW,), dt, sharding=chip)
                     for dt in device_dtypes(t))
            for c, t in relation.items()
        }
        scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)
        bounds = jax.ShapeDtypeStruct((3,), jnp.int32, sharding=chip)
        programs = {
            "merge_states": lambda: jax.jit(frag.merge_states).lower(
                state, state),
            "finalize": lambda: frag.finalize.lower(state),
        }
        if allow_dense:  # the Kelvin folds no window: it merges and finalizes
            programs["update"] = lambda: frag.update.lower(
                state, cols, (scalar, scalar))
            programs["update_all"] = lambda: frag.update_all.lower(
                state, (cols,) * 3, bounds, bounds)
        for program, lower in sorted(programs.items()):
            _record(lines, out_dir, name, program, frag.fold,
                    _without_kernel_locations(lower().as_text()))


def _lower_merges(case, merges, topo_device, out_dir, lines):
    """The Kelvin's ``merge_finalize`` of each script: the payloads the
    served run shipped, their states compacted as a request compacts
    them, at the capacity the cell's live groups give (a keyed chain:
    ``http_full_1chip``'s 63 k groups in a 65,536-slot bucket). Lowered
    and COMPILED for the described device: the line carries the
    seconds."""
    import time
    import types

    import jax
    from jax.sharding import SingleDeviceSharding

    from pixie_tpu.exec import bridge
    from pixie_tpu.types.batch import bucket_capacity
    from pixie_tpu.udf.registry import default_registry

    chip = SingleDeviceSharding(topo_device)
    engine = types.SimpleNamespace(registry=default_registry())
    for payloads, tail in merges:
        slots = [bridge._live_slots(p.state) for p in payloads]
        caps = [cap for _idx, _live, cap in slots]
        if case.startswith("keyed") and not payloads[0].dense_domains:
            caps = [KEYED_SLOTS // 2] * len(payloads)
        g = bucket_capacity(sum(caps))
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
        lowered = rec.program.lower(states, rec.remaps)
        t0 = time.perf_counter()
        lowered.compile()
        _record(lines, out_dir, name, "merge_finalize", rec.frag.fold,
                _without_kernel_locations(lowered.as_text()),
                compile_s=round(time.perf_counter() - t0, 2))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=None, help="directory for the texts")
    args = ap.parse_args()
    if args.out:
        os.makedirs(args.out, exist_ok=True)

    import jax

    import pixie_tpu  # noqa: F401
    from benchmark.builders import served_http_skew
    from pixie_tpu.ingest.replay import gen_http_events

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "http_full_1chip.json")) as f:
        cfg = json.load(f)
    skew = served_http_skew.make_data(cfg, 3_000_000_019, ROWS)
    captured = {
        "dense": _capture(list(gen_http_events(ROWS, seed=3))),
        "keyed": _capture(list(
            served_http_skew.batches(skew, SMALL_WINDOW, 0, ROWS))),
    }

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
        _lower(case, seen, topo.devices[0], args.out, lines)
        _lower_merges(case, merges, topo.devices[0], args.out, lines)
    return 0


if __name__ == "__main__":
    sys.exit(main())
