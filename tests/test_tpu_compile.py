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


# g = 2048 is the top of the call site's gate (fragment.py ``g <= 2048``).
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


# 8192 and 1 << 15 slots: G = 1 and 4 groups at B = 8192 bins, the whole
# range ``ops/tdigest.py`` admits to the kernel.
@pytest.mark.parametrize("n_slots", [8192, 1 << 15])
def test_hist_fold_at_bench_window(one_chip, n_slots):
    from pixie_tpu.ops.pallas_groupby import row_chunk
    from pixie_tpu.ops.pallas_tdigest import hist_fold

    text = _compile(
        hist_fold,
        _rows(WINDOW, jnp.int32, one_chip),
        _rows(WINDOW, jnp.float32, one_chip),
        n_slots=n_slots, chunk=row_chunk(WINDOW, 2048),
    )
    assert "tpu_custom_call" in text


def test_hist_fold_at_smallest_window(one_chip):
    """128 rows, the floor of ``batch_to_digest``'s gate: one block."""
    from pixie_tpu.ops.pallas_groupby import row_chunk
    from pixie_tpu.ops.pallas_tdigest import hist_fold

    text = _compile(
        hist_fold,
        _rows(128, jnp.int32, one_chip),
        _rows(128, jnp.float32, one_chip),
        n_slots=8192, chunk=row_chunk(128, 2048),
    )
    assert "tpu_custom_call" in text


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
