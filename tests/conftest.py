"""Test configuration: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's "fake the distributed system without a cluster"
strategy (SURVEY.md §4): instead of LocalResultSinkServer + synthetic
DistributedState, we stand up 8 XLA host-platform devices so shard_map
programs compile and run without TPU hardware. Hardware-tagged tests use
@pytest.mark.requires_tpu (the reference's ``requires_bpf`` pattern).
"""

import os

if not os.environ.get("PIXIE_TPU_RUN_TPU_TESTS"):
    # requires_tpu runs (PIXIE_TPU_RUN_TPU_TESTS=1) keep the machine's
    # own backend: there jax finds the chip by default.
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from pixie_tpu.utils.cache import jax_cache_dir  # noqa: E402

# Persist XLA compiles across test runs, in JAX_COMPILATION_CACHE_DIR when
# the caller set it and <checkout>/.jax_cache otherwise. Through the
# environment, not jax.config: the import above has already imported jax
# in THIS process, so what this sets is read by the processes started
# from it — the xdist workers, which run the tests, and the agents and
# CLIs the tests spawn.
os.environ["JAX_COMPILATION_CACHE_DIR"] = jax_cache_dir()
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")

import contextlib  # noqa: E402
import time  # noqa: E402
from unittest import mock  # noqa: E402

import pytest  # noqa: E402


@contextlib.contextmanager
def routes_of(platform):
    """The fold routes of ``platform`` (``tpu`` / ``cpu``) on whatever
    backend the tests run on, by substituting the one function every
    route choice asks (``ops/routes.py``): under ``tpu`` here, rows sort,
    the Pallas kernels run interpreted (the backend underneath is not a
    TPU) and windows scan-fold; under ``cpu`` on a chip, the CPU's XLA
    routes run there. Process-wide, like a flag: agents' threads see it."""
    from pixie_tpu.ops import routes

    with mock.patch.object(routes, "routes_platform", lambda: platform):
        yield


def wait_until(cond, what, ceiling_s=120.0):
    """Poll ``cond`` until it holds: for a test that waits on an EVENT of
    the system (an agent registered, a cancel delivered, a cache dropped)
    and not on the clock. The ceiling is generous because a loaded box
    is slow, not wrong; a healthy run never comes near it."""
    deadline = time.monotonic() + ceiling_s
    while not cond():
        assert time.monotonic() < deadline, what
        time.sleep(0.01)

# Runtime lock-order validation (pxlock's dynamic half): with
# PIXIE_TPU_LOCKDEP=1 (./run_tests.sh --locks), every lock created from
# here on is order-tracked and the first acquisition that would close a
# cycle raises with both stack pairs. Enabled at conftest import — i.e.
# before any test module (and the engines/brokers/agents they build)
# creates its locks. The autouse guard below also FAILS the owning test
# on violations product code swallowed (bus handlers catch Exception).
_LOCKDEP = None
if os.environ.get("PIXIE_TPU_LOCKDEP"):
    from pixie_tpu.analysis import lockdep as _lockdep_mod  # noqa: E402

    _LOCKDEP = _lockdep_mod.enable()


@pytest.fixture(autouse=True)
def _lockdep_guard():
    if _LOCKDEP is None:
        yield
        return
    before = len(_LOCKDEP.violations)
    yield
    fresh = _LOCKDEP.violations[before:]
    assert not fresh, (
        "lockdep recorded lock-order violation(s) during this test "
        "(possibly swallowed by a handler):\n"
        + "\n---\n".join(str(v) for v in fresh)
    )


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "requires_tpu: needs real TPU hardware (excluded by default)"
    )
    config.addinivalue_line(
        "markers", "slow: multi-process / long-running tests"
    )
    config.addinivalue_line(
        "markers",
        "stress: concurrency/thread-hammer tests (skipped by "
        "./run_tests.sh --fast)",
    )


#: Tests of ``tests/benchmark/`` (the benchmark's own files: only a
#: ``benchmark`` PR may edit them) that hold a number a later PR moved on
#: purpose, with the test that holds the new one. Strict: the PR that
#: updates the assertion there deletes the entry here.
_SUPERSEDED = {
    "tests/benchmark/test_span_readers.py::"
    "test_head_tail_and_device_interval_make_up_the_brokers_root": (
        "asserts device_dispatches == 2 * (1 + 2); since PR 31 the Kelvin's "
        "merge is one program a request, 2 * (1 + 1): held, with this test's "
        "other assertions, by tests/test_bridge_merge.py::"
        "test_served_refresh_span_shape"
    ),
    "tests/benchmark/test_host_path_metrics.py::"
    "test_benchmark_json_files_the_seven_at_the_end": (
        "asserts the LAST seven per-layer metrics are PR 37's; PR 39 "
        "appended its cell's three after them (new entries go last): held, "
        "with this test's other assertions, by tests/benchmark/"
        "test_stack_flame.py::test_the_three_follow_the_host_paths_seven"
    ),
    "tests/benchmark/test_stack_flame.py::"
    "test_the_file_agrees_with_benchmark_json_and_the_programs_split": (
        "asserts stack_flame_1chip is the LAST configuration; PR 41 "
        "appended http_edges_1chip after it (new entries go last): every "
        "other assertion of this test is held by tests/benchmark/"
        "test_http_edges.py::test_stack_flames_file_agrees_with_benchmark_"
        "json_and_the_programs_split, the order (relatively, so that the "
        "next PR supersedes nothing) by ::test_what_was_filed_is_a_prefix_"
        "of_the_list"
    ),
    "tests/benchmark/test_stack_flame.py::"
    "test_the_three_follow_the_host_paths_seven": (
        "asserts the LAST ten per-layer metrics are PR 37's seven and PR "
        "39's three; PR 41 appended its cell's three after them: the "
        "order is held by tests/benchmark/test_http_edges.py::"
        "test_what_was_filed_is_a_prefix_of_the_list, the seven's entries "
        "by ::test_the_host_paths_seven_are_as_they_were_filed"
    ),
    **{
        "tests/benchmark/test_stack_flame.py::"
        f"test_the_new_metrics_are_filed_under_their_layers[{metric}]": (
            "asserts PR 39's three per-layer metrics are the LAST three; "
            "PR 41 appended its cell's three after them: this test's "
            "other assertions are held by tests/benchmark/"
            "test_http_edges.py::test_stack_flames_metrics_are_filed_"
            f"under_their_layers[{metric}]"
        )
        for metric in ("answer_rows", "answer_string_mb",
                       "perf_flamegraph_p50_ms")
    },
    # PR 42: one digest an argument. The cell's three plucked quantiles
    # of one column share ONE [slots, 128] carry where these four assert
    # three; every other assertion of theirs is held, with the new
    # numbers, by the case of the same name in
    # tests/test_shared_digest_cell.py.
    "tests/benchmark/test_http_edges.py::"
    "test_one_served_requests_span_shape": (
        'asserts "digests": 3 and digest_bytes == 3 * 2 * slots * 128 * 4 '
        "on the PEM's fold dispatch and payload; since PR 42 one carry "
        "(digests 1, digest_outputs 3, a third of the bytes): held by "
        "tests/test_shared_digest_cell.py::"
        "test_one_served_requests_span_shape"
    ),
    "tests/benchmark/test_http_edges.py::"
    "test_the_new_readers_read_the_spans_and_the_counter": (
        "asserts digest_states == 3 and digest_mb at six planes; since PR "
        "42 1 and two planes: held by tests/test_shared_digest_cell.py::"
        "test_the_new_readers_read_the_spans_and_the_counter"
    ),
    **{
        "tests/benchmark/test_http_edges.py::"
        f"test_a_rehearsal_of_the_cell_is_sound[{platform}]": (
            f"asserts digest_states == {states} and digest_mb at six "
            "planes; since PR 42 a third of each: held by tests/"
            "test_shared_digest_cell.py::"
            f"test_a_rehearsal_of_the_cell_is_sound[{platform}]"
        )
        for platform, states in (("tpu", 3), ("cpu", 12))
    },
    "tests/benchmark/test_fold_fill.py::"
    "test_the_metric_is_filed_under_the_engine": (
        "asserts fold_fill_pct is the LAST per-layer metric; PR 46 appended "
        "its cell's five after it (new entries go last): the entry is held, "
        "by name, by tests/benchmark/test_http_cluster.py::"
        "test_fold_fill_pct_is_as_it_was_filed, the order by ::"
        "test_what_was_filed_is_a_prefix_of_the_list"
    ),
    # PR 46: a second four-chip cell (http_cluster_4chip.cluster_recent:
    # four PEMs, a chip each, behind one broker).
    "tests/benchmark/test_span_readers.py::"
    "test_benchmark_json_has_the_span_metrics_and_the_four_chip_cell": (
        "asserts len(cells4) == 1; PR 46 appended a second four-chip cell "
        "(of nine: the limit is four): every other assertion of this test, "
        "and len(cells4) == 2 <= len(workloads) // 2, is held by "
        "tests/benchmark/test_http_cluster.py::"
        "test_benchmark_json_has_the_span_metrics_and_two_four_chip_cells"
    ),
    # PR 47: the k-way merge's counter as a per-layer metric.
    "tests/benchmark/test_http_cluster.py::"
    "test_what_was_filed_is_a_prefix_of_the_list[per_layer]": (
        "asserts the per-layer list ends with PR 46's five; PR 47 appended "
        "merge_rebins after them (new entries go last): that what was "
        "filed is a prefix of the list is held, relatively, by "
        "tests/benchmark/test_merge_rebins.py::"
        "test_the_metric_is_filed_under_the_engine_after_what_was_there"
    ),
    "tests/benchmark/test_http_cluster.py::"
    "test_nothing_the_benchmark_had_lists_the_new_cell": (
        "asserts that PR 46's five per-layer metrics alone list the "
        "four-node cell; PR 47's merge_rebins lists it too (a new entry, "
        "no accepted one edited): held, with that one more name, by "
        "tests/benchmark/test_merge_rebins.py::"
        "test_nothing_that_was_filed_before_lists_the_four_node_cell"
    ),
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        reason = _SUPERSEDED.get(item.nodeid)
        if reason is not None:
            item.add_marker(pytest.mark.xfail(reason=reason, strict=True))
    if os.environ.get("PIXIE_TPU_RUN_TPU_TESTS"):
        return
    skip = pytest.mark.skip(reason="requires real TPU (set PIXIE_TPU_RUN_TPU_TESTS=1)")
    for item in items:
        if "requires_tpu" in item.keywords:
            item.add_marker(skip)
