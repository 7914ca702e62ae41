"""Compile the chip's kernels for a v5e that is described, not attached.

The TPU compiler is installed on machines without a chip; lowering a
kernel against a described ``v5e:2x2`` topology raises what the chip's
compiler would raise (Mosaic dot lowering, index-map types, tiling,
scoped vmem) — everything interpret mode cannot see. The shapes are the
ones the engine passes: the bench's 2^21-row window, each call site's
smallest window, group counts up to the call site's gate.

A compile that passes is not a chip run: ``chip_smoke.py`` is.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

WINDOW = 1 << 21


@pytest.fixture(scope="module")
def topo():
    # Only a test of this file may load the TPU's library: one process
    # at a time holds it, and every xdist worker imports every file.
    import os

    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs to /tmp

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one (the next run would warn and
    # compile again), so the cache is off around these tests.
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _rows(n, dtype, sharding):
    return jax.ShapeDtypeStruct((n,), dtype, sharding=sharding)


def _compile(fn, *shapes, **static):
    compiled = fn.lower(*shapes, **static).compile()
    return compiled.as_text()


# g = 2048 is the top of the f32 kernel's gate (fragment.py ``g <= 2048``).
@pytest.mark.parametrize("want_min", [False, True])
@pytest.mark.parametrize("g", [128, 512, 2048])
def test_dense_group_fold_at_bench_window(one_chip, g, want_min):
    from pixie_tpu.ops.pallas_groupby import dense_group_fold, fold_row_chunk

    text = _compile(
        dense_group_fold,
        _rows(WINDOW, jnp.int32, one_chip),
        _rows(WINDOW, jnp.float32, one_chip),
        g=g, chunk=fold_row_chunk(WINDOW, g), want_min=want_min,
    )
    assert "tpu_custom_call" in text


def test_dense_group_fold_at_smallest_window(one_chip):
    """MIN_CAPACITY rows (types/batch.py) at the gate's top group count:
    the block is the 1024-row floor the tiling sets."""
    from pixie_tpu.ops.pallas_groupby import dense_group_fold, fold_row_chunk
    from pixie_tpu.types.batch import MIN_CAPACITY

    text = _compile(
        dense_group_fold,
        _rows(MIN_CAPACITY, jnp.int32, one_chip),
        _rows(MIN_CAPACITY, jnp.float32, one_chip),
        g=2048, chunk=fold_row_chunk(MIN_CAPACITY, 2048), want_min=True,
    )
    assert "tpu_custom_call" in text


def _int_fold_text(sharding, n, g, sums, exts, wrap=None):
    """Compile ``dense_group_fold_int`` at the engine's blocking for
    [n] rows and g groups; ``sums`` / ``exts`` are the arguments' dtypes
    (every extreme a max and a min: both fills, both compares)."""
    from pixie_tpu.ops.pallas_groupby import (
        dense_group_fold_int,
        int_fold_blocks,
        int_fold_groups,
    )

    g_pad = int_fold_groups(g)
    chunk, g_block = int_fold_blocks(n, g_pad)

    def fold(slots, sum_args, ext_args):
        return dense_group_fold_int(
            slots, sum_args, ext_args, g=g_pad, chunk=chunk,
            g_block=g_block, ext_max=(True, False)[: len(exts)],
        )

    total = n * 4 if wrap else n  # ``wrap`` shards the rows over 2x2
    return _compile(
        jax.jit(wrap(fold) if wrap else fold),
        _rows(total, jnp.int32, sharding),
        tuple(_rows(total, dt, sharding) for dt in sums),
        tuple(_rows(total, dt, sharding) for dt in exts),
    )


# The cells' AggOps (PERF.md section 4): px/http_stats is count, mean and
# max of one INT64 column over 2,048 slots; px/service_stats' integer
# part is count and mean of one BOOLEAN over 32. Then one slot above a
# group block, the top of the gate, and min beside max.
@pytest.mark.parametrize("g,sums,exts", [
    (2048, (jnp.int64,), (jnp.int64,)),
    (32, (jnp.bool_,), ()),
    (2049, (jnp.int64,), (jnp.int64, jnp.int64)),
    ("max", (jnp.int64, jnp.bool_), (jnp.int64, jnp.int64)),
])
def test_dense_group_fold_int_at_bench_window(one_chip, g, sums, exts):
    import pixie_tpu  # noqa: F401  (x64 on: int64 stays int64)
    from pixie_tpu.ops.pallas_groupby import INT_FOLD_MAX_GROUPS

    g = INT_FOLD_MAX_GROUPS if g == "max" else g
    assert "tpu_custom_call" in _int_fold_text(one_chip, WINDOW, g, sums, exts)


def test_dense_group_fold_int_at_smallest_window(one_chip):
    import pixie_tpu  # noqa: F401
    from pixie_tpu.types.batch import MIN_CAPACITY

    text = _int_fold_text(
        one_chip, MIN_CAPACITY, 2048, (jnp.int64,), (jnp.int64,)
    )
    assert "tpu_custom_call" in text


def test_dense_group_fold_int_under_shard_map(topo):
    """The mesh step (``parallel/executor.py``) runs the window fold
    inside ``shard_map`` over the 2x2 mesh: 2^19 rows a shard of the
    four-chip cell's 2^21-row windows."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import pixie_tpu  # noqa: F401

    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("kelvin", "agents"))
    axes = mesh.axis_names

    def wrap(fold):
        def step(slots, sum_args, ext_args):
            cnt, sums, exts = fold(slots, sum_args, ext_args)
            return jax.lax.psum((cnt, sums, exts), axes)

        return jax.shard_map(
            step, mesh=mesh, in_specs=(P(axes), P(axes), P(axes)),
            out_specs=P(), check_vma=False,
        )

    text = _int_fold_text(
        NamedSharding(mesh, P(axes)), WINDOW // 4, 2048,
        (jnp.int64,), (jnp.int64,), wrap=wrap,
    )
    assert "tpu_custom_call" in text


# The sorted digest's reduction (``ops/tdigest.py``'s route on the TPU):
# px/service_stats' 33 x 128 centroid slots at the benchmark's window and
# at the four-chip cell's 2^19-row shard, the smallest block, and the
# route's slot limit (two 4 MiB accumulators resident in VMEM).
@pytest.mark.parametrize("rows,n_slots", [
    (WINDOW, 33 * 128), (WINDOW // 4, 33 * 128), (1024, 128),
    (WINDOW, 1 << 20),
])
def test_sorted_centroid_fold_at_bench_window(one_chip, rows, n_slots):
    from pixie_tpu.ops.pallas_tdigest import sorted_centroid_fold
    from pixie_tpu.ops.routes import SORTED_DIGEST_MAX_SLOTS

    assert n_slots <= SORTED_DIGEST_MAX_SLOTS
    text = _compile(
        sorted_centroid_fold,
        _rows(rows, jnp.int32, one_chip),
        _rows(rows, jnp.float32, one_chip),
        n_slots=n_slots,
    )
    assert "tpu_custom_call" in text


def _service_stats_fragment():
    """px/service_stats' aggregate over its one dictionary key (33 dense
    slots) as the chip compiles it: the count and the mean in the integer
    kernel, the two quantiles' window digest by sorting the rows. The
    backend underneath answers ``tpu`` while it is compiled and lowered,
    so the kernels go through Mosaic, uninterpreted."""
    import pixie_tpu  # noqa: F401
    from pixie_tpu.exec.fragment import compile_fragment
    from pixie_tpu.exec.plan import AggExpr, AggOp, ColumnRef
    from pixie_tpu.types.dtypes import DataType
    from pixie_tpu.types.relation import Relation
    from pixie_tpu.types.strings import StringDictionary
    from pixie_tpu.udf.registry import default_registry

    rel = Relation([("latency_ns", DataType.INT64),
                    ("failure", DataType.BOOLEAN),
                    ("service", DataType.STRING)])
    dicts = {"service": StringDictionary(f"s{i}" for i in range(32))}
    lat = (ColumnRef("latency_ns"),)
    frag = compile_fragment(
        [AggOp(("service",),
               (AggExpr("p50", "_quantile_p50", lat),
                AggExpr("p99", "_quantile_p99", lat),
                AggExpr("error_rate", "mean", (ColumnRef("failure"),)),
                AggExpr("throughput", "count", lat)))],
        rel, dicts, default_registry(),
    )
    assert (frag.group, frag.fold, frag.slots) == (
        "dense", "mixed:pallas_int=2,sorted_digest=2", 33)
    return frag


# Compile seconds here, for the described v5e (PR 33): 12 each (25 / 29 /
# 15 while the position scans were flat ``lax.cummax``: they are blocked).
@pytest.mark.parametrize("program", ["update", "update_all", "mesh_agg_step"])
def test_sorted_digest_programs_at_bench_window(topo, one_chip, program):
    """The programs of px/service_stats' fold that the dashboard cells
    dispatch, with the window digest on the sorted route: one 2^21-row
    window, the three-window scan, and ``DistributedEngine``'s step over
    the four chips (2^19 rows a chip, inside ``shard_map``). One sort of
    the rows (the two quantiles share it), the reduction's kernel, and no
    scatter of a window's rows: what scatters are left are the digests'
    [33, 256]-centroid compress."""
    from unittest import mock

    from jax.sharding import NamedSharding, PartitionSpec as P

    from pixie_tpu.exec import fragment
    from pixie_tpu.ops import routes
    from pixie_tpu.parallel.executor import distributed_agg_step
    from pixie_tpu.parallel.mesh import agent_mesh

    def cols_on(sharding):
        return {"latency_ns": (_rows(WINDOW, jnp.int64, sharding),),
                "failure": (_rows(WINDOW, jnp.bool_, sharding),),
                "service": (_rows(WINDOW, jnp.int32, sharding),)}

    with mock.patch.object(routes, "_backend", lambda: "tpu"):
        fragment._FRAGMENT_CACHE.clear()
        try:
            frag = _service_stats_fragment()
            state = jax.eval_shape(frag.init_state)
            if program == "mesh_agg_step":
                mesh = agent_mesh(4, devices=topo.devices)
                everywhere = NamedSharding(mesh, P())
                scalar = jax.ShapeDtypeStruct((), jnp.int32,
                                              sharding=everywhere)
                text = _compile(
                    distributed_agg_step(frag, mesh, range_valid=True),
                    _on(state, everywhere),
                    cols_on(NamedSharding(mesh, P(mesh.axis_names))),
                    {}, (scalar, scalar))
                assert "all-gather" in text
            else:
                scalar = jax.ShapeDtypeStruct((), jnp.int32,
                                              sharding=one_chip)
                cols = cols_on(one_chip)
                if program == "update":
                    text = _compile(frag.update, _on(state, one_chip), cols,
                                    (scalar, scalar))
                else:
                    bounds = jax.ShapeDtypeStruct((3,), jnp.int32,
                                                  sharding=one_chip)
                    text = _compile(frag.update_all, _on(state, one_chip),
                                    (cols,) * 3, bounds, bounds)
        finally:
            fragment._FRAGMENT_CACHE.clear()
    rows = WINDOW // 4 if program == "mesh_agg_step" else WINDOW
    assert "sorted_centroid_fold" in text and "dense_group_fold_int" in text
    assert f"u32[{rows}]" in text and " sort(" in text
    for line in text.splitlines():
        if " scatter(" in line:
            assert f"[{rows}]" not in line, line


def test_row_chunk_refuses_what_the_tiling_refuses(one_chip):
    """A 512-row block of a 2^21-row operand is what the seed's call
    site picked at g = 2048; Mosaic refuses it (XLA tiles the operand
    T(1024)), and ``row_chunk`` never offers it."""
    from pixie_tpu.ops.pallas_groupby import dense_group_fold, row_chunk

    assert row_chunk(WINDOW, 1024) == 1024
    assert row_chunk(1536, 1024) is None  # no block: the XLA path
    assert row_chunk(1536, 2048) == 1536  # the whole array as one block
    with pytest.raises(Exception, match="layout"):
        _compile(
            dense_group_fold,
            _rows(WINDOW, jnp.int32, one_chip),
            _rows(WINDOW, jnp.float32, one_chip),
            g=2048, chunk=512, want_min=False,
        )


def test_blocked_cumsum_int64_at_bench_window(one_chip):
    """The round-5 scoped-vmem fix: a flat 2^21-row i64 cumsum stages
    its whole operand in vmem and fails; the blocked form compiles."""
    import pixie_tpu  # noqa: F401  (x64 on: int64 stays int64)
    from pixie_tpu.ops.scan import blocked_cumsum

    _compile(jax.jit(blocked_cumsum), _rows(WINDOW, jnp.int64, one_chip))


@pytest.mark.parametrize("scan_fn", ["blocked_cumsum", "blocked_cummax"])
def test_blocked_scan_inside_a_loop_at_bench_window(one_chip, scan_fn):
    """The engine's scan-fold program runs the window fold inside a
    ``lax.scan``. There XLA:TPU gave the blocked scan's 256-element
    chunk-totals ``cumsum`` a 19 MiB scoped-vmem stack and refused every
    sort-based scan-fold program (PR 22); the totals are now scanned by
    shifted combines (``ops/scan.py`` ``_totals_scan``)."""
    import pixie_tpu  # noqa: F401
    from pixie_tpu.ops import scan

    fn = getattr(scan, scan_fn)

    def body(carry, window):
        return carry + fn(window)[-1], None

    fold = jax.jit(lambda ws: jax.lax.scan(body, jnp.int64(0), ws)[0])
    _compile(fold, jax.ShapeDtypeStruct((2, WINDOW), jnp.int64,
                                        sharding=one_chip))


def _http_stats_keyed_fragment(slots, allow_dense=True):
    """px/http_stats' aggregate over its two dictionary keys, whose
    domains (33 x 65,537 codes) pass ``dense_domain_limit``: the keyed
    route, compiled at ``slots``, as configuration ``http_full_1chip``
    folds it: every aggregate an exact integer statistic, so the rows
    ride the sort (``fold`` = ``sorted_int``), the two keys packed into
    one word; ``allow_dense=False`` is the Kelvin's fragment, which
    sorts the key planes as they are. The routes are the TPU's,
    whatever the backend the test runs on."""
    import pixie_tpu  # noqa: F401
    from conftest import routes_of
    from pixie_tpu.exec.fragment import compile_fragment
    from pixie_tpu.exec.plan import AggExpr, AggOp, ColumnRef
    from pixie_tpu.types.dtypes import DataType
    from pixie_tpu.types.relation import Relation
    from pixie_tpu.types.strings import StringDictionary
    from pixie_tpu.udf.registry import default_registry

    rel = Relation([("latency_ns", DataType.INT64),
                    ("service", DataType.STRING),
                    ("req_path", DataType.STRING)])
    dicts = {"service": StringDictionary(f"s{i}" for i in range(32)),
             "req_path": StringDictionary(f"p{i}" for i in range(65_536))}
    lat = (ColumnRef("latency_ns"),)
    with routes_of("tpu"):
        frag = compile_fragment(
            [AggOp(("service", "req_path"),
                   (AggExpr("n", "count", lat), AggExpr("lat_mean", "mean", lat),
                    AggExpr("lat_max", "max", lat)),
                   max_groups=slots)],
            rel, dicts, default_registry(), allow_dense=allow_dense,
        )
    assert (frag.group, frag.fold, frag.slots) == ("sorted", "sorted_int", slots)
    return frag


def _window_cols(one_chip):
    cols = {"latency_ns": (_rows(WINDOW, jnp.int64, one_chip),),
            "service": (_rows(WINDOW, jnp.int32, one_chip),),
            "req_path": (_rows(WINDOW, jnp.int32, one_chip),)}
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    return cols, (scalar, scalar)


def _on(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree,
    )


def test_joint_key_sketch_at_bench_window(one_chip):
    """The probe a keyed aggregate runs before its first fold
    (``CompiledFragment.group_sketch``: px/http_stats' two dictionary
    keys mixed into one u64 a row, a 2^21-row ``segment_max`` into 2^12
    registers), at the benchmark's window."""
    frag = _http_stats_keyed_fragment(1 << 17)
    _compile(frag.group_sketch,
             _on(jax.eval_shape(frag.init_sketch), one_chip),
             *_window_cols(one_chip))


# Compile seconds here, for the described v5e (PR 29): merge_states 33-41,
# finalize 0.6, update 34, update_all (three windows) 35, the Kelvin's
# merge_states 50, the four-chip step ~70. What takes over half a minute
# is opt-in (``-m slow``); ``chip_smoke.py``'s ``skew`` phase runs the
# window fold on the chip.
@pytest.mark.parametrize("program", [
    "merge_states", "finalize",
    pytest.param("update", marks=pytest.mark.slow),
    pytest.param("update_all", marks=pytest.mark.slow),
    pytest.param("kelvin_merge_states", marks=pytest.mark.slow),
])
def test_keyed_state_programs_at_the_full_cells_capacity(one_chip, program):
    """The sorted-fold programs at the state ``http_full_1chip`` settles
    on, 2^17 slots: the merge of two keyed states (a 2^18-row sort, the
    carries riding a batched one), with the keys packed (the PEM's
    fragment) and as planes (the Kelvin's), its finalize, and the
    2^21-row window fold alone and as the three-window scan the cell
    dispatches. No window-long scatter is left in any of them."""
    frag = _http_stats_keyed_fragment(
        1 << 17, allow_dense=program != "kelvin_merge_states")
    state = _on(jax.eval_shape(frag.init_state), one_chip)
    if program.endswith("merge_states"):
        text = _compile(jax.jit(frag.merge_states), state, state)
    elif program == "finalize":
        text = _compile(frag.finalize, state)
    elif program == "update":
        text = _compile(frag.update, state, *_window_cols(one_chip))
    else:
        cols, _range = _window_cols(one_chip)
        bounds = jax.ShapeDtypeStruct((3,), jnp.int32, sharding=one_chip)
        text = _compile(frag.update_all, state, (cols,) * 3, bounds, bounds)
    assert " scatter(" not in text
    if program != "finalize":
        assert " sort(" in text


@pytest.mark.parametrize("where", ["one_chip", "mesh"])
def test_a_folds_empty_state_is_one_program_of_fills(topo, one_chip, where):
    """A fold's empty state (PR 48: ``CompiledFragment.init_program``, the
    mesh engine's ``_init_program``) for the described chip, and
    replicated over the 2x2's four: a program of no argument whose text
    holds no literal of the state's size (every plane a broadcast fill)
    and whose outputs are the state's, at the keyed state's 2^17 slots."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pixie_tpu.parallel.mesh import agent_mesh

    frag = _http_stats_keyed_fragment(1 << 17)
    sharding = one_chip if where == "one_chip" else NamedSharding(
        agent_mesh(4, devices=topo.devices), P())
    compiled = jax.jit(frag.init_state, out_shardings=sharding).lower(
    ).compile()
    state = jax.eval_shape(frag.init_state)
    leaves = jax.tree_util.tree_leaves(state)
    assert len(jax.tree_util.tree_leaves(compiled.out_info)) == len(leaves)
    assert len(compiled.as_text()) < 64 * 1024
    want = sum(a.size * a.dtype.itemsize for a in leaves)
    assert want > 4 << 20
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes == 0
    assert want <= memory.output_size_in_bytes < want + (1 << 16)


@pytest.mark.slow
def test_keyed_fold_step_under_shard_map(topo):
    """``DistributedEngine``'s step for a keyed chain on the four chips
    (``parallel/executor.py`` ``distributed_agg_step`` over
    ``agent_mesh(4)``): the sorted window fold of a 2^19-row shard, the
    all-gathered partial states merged pairwise by the same function,
    then into the accumulated state. No cell reaches it (the four-chip
    cell's keys are dense); this is what says it would compile."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pixie_tpu.parallel.executor import distributed_agg_step
    from pixie_tpu.parallel.mesh import agent_mesh

    mesh = agent_mesh(4, devices=topo.devices)
    frag = _http_stats_keyed_fragment(1 << 17)
    everywhere = NamedSharding(mesh, P())
    rows = NamedSharding(mesh, P(mesh.axis_names))
    cols = {"latency_ns": (_rows(WINDOW, jnp.int64, rows),),
            "service": (_rows(WINDOW, jnp.int32, rows),),
            "req_path": (_rows(WINDOW, jnp.int32, rows),)}
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=everywhere)
    step = distributed_agg_step(frag, mesh, range_valid=True)
    text = _compile(step, _on(jax.eval_shape(frag.init_state), everywhere),
                    cols, {}, (scalar, scalar))
    assert "all-gather" in text and " scatter(" not in text
