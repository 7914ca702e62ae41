"""``http_edges_1chip.graph_recent`` with one digest an argument (PR 42):
the cell's three plucked quantiles of ``latency_ns`` share ONE
[slots, 128] carry through fold, merge, transfer, Kelvin and read-out.

Four cases of ``tests/benchmark/test_http_edges.py`` assert three states
(``"digests": 3``, ``digest_states == 3``, ``digest_mb`` at six planes)
and that directory is the benchmark's: they are entered in
``tests/conftest.py`` ``_SUPERSEDED`` and every OTHER assertion of theirs
is held here, with the new numbers (1 state, a third of the bytes), on
that file's own rehearsed window and rehearsal (read, not edited). On the
CPU (the TPU's routes by substituting ``ops/routes.py``
``routes_platform``): never a device number from here."""

import importlib.util
import os
import sys

import pytest


def _http_edges():
    """``tests/benchmark/test_http_edges.py`` as a module of its own: its
    rehearsed ``window`` fixture, readers and rehearsal."""
    here = os.path.join(os.path.dirname(__file__), "benchmark")
    if here not in sys.path:
        sys.path.insert(0, here)  # it imports its neighbour's helpers
    spec = importlib.util.spec_from_file_location(
        "_http_edges_cell", os.path.join(here, "test_http_edges.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


edges = _http_edges()
window = edges.window  # the module-scoped fixture, rehearsed once here
_read = edges._read


def _fold_and_payload(window):
    pem = window["spans"]["pem"][-1]
    (fold,) = [s.attributes for s in pem.spans
               if s.name == "device.dispatch" and "fold" in s.attributes]
    (payload,) = [s for s in pem.spans if s.name == "payload"]
    return pem, fold, payload


def test_one_served_requests_span_shape(window):
    """The PEM folds the three windows in range in one scan program
    whose integer aggregates ride the keyed sort and whose ONE digest
    (three outputs read it) is built beside it; the Kelvin's one
    ``merge_finalize`` reads it."""
    from benchmark.reference import px_service_graph as ref

    pem, fold, payload = _fold_and_payload(window)
    slots = fold["slots"]
    assert fold == {
        "program": "fragment_scan_fold", "windows": fold["windows"],
        "device": 0,  # (PR 46) an engine's spans name its device
        "fold": "mixed:sorted_int=3,keyed_digest=3", "group": "sorted",
        "slots": slots, "digests": 1, "digest_outputs": 3,
        "digest_slots": slots * 128, "digest_bins": 1 << 32,
        "ride": "index",
        # (PR 44) one length a run of resident windows, the rows in range.
        "rows": fold["rows"], "range_rows": fold["range_rows"],
    }
    assert fold["windows"] in (3, 4)
    assert 0 < fold["range_rows"] <= fold["rows"]
    assert fold["rows"] % fold["windows"] == 0
    assert not [s for t in window["spans"]["pem"] + window["spans"]["kelvin"]
                for s in t.spans if s.name == "rebucket"]
    assert payload.attributes == {
        "kind": "agg_state", "digest_bytes": 1 * 2 * slots * 128 * 4}
    assert pem.usage.digest_bytes == payload.attributes["digest_bytes"]
    assert pem.usage.digest_bytes < pem.usage.wire_bytes
    kelvin = window["spans"]["kelvin"][-1]
    assert [s.attributes["program"] for s in kelvin.spans
            if s.name == "device.dispatch"] == ["merge_finalize"]
    assert kelvin.usage.digest_bytes == 0
    want = ref.answer(window["data"], edges.LO_NS)
    assert kelvin.usage.answer_rows == len(want["key"])


def test_the_new_readers_read_the_spans_and_the_counter(window):
    slots = _read("group_slots", window)
    assert _read("digest_slots", window) == slots * 128
    assert _read("digest_states", window) == 1
    assert _read("digest_mb", window) == pytest.approx(
        1 * 2 * slots * 128 * 4 / 1e6)
    assert _read("digest_mb", window) < _read("wire_mb", window)


def test_the_shipped_state_is_the_integer_planes_and_one_digest(window):
    """What ``fetch_mb`` and ``wire_mb`` hold once the digest is shared:
    the state's key, validity and integer planes and ONE pair of
    [slots, 128] f32 planes: ``digest_mb`` parts from ``fetch_mb`` by
    more than the integer planes (it was ``fetch_mb`` less a constant
    while each pluck kept a state)."""
    pem, fold, payload = _fold_and_payload(window)
    slots = fold["slots"]
    digest = payload.attributes["digest_bytes"]
    assert digest == 2 * slots * 128 * 4
    # Beside the digest: the packed key's three id planes, validity, the
    # mean's (sum, count), the count, the sum and the overflow flag.
    rest = pem.usage.wire_bytes - digest
    assert 0 < rest <= slots * (3 * 4 + 1 + 4 * 8) + 8
    # Three states would be two more of them.
    assert pem.usage.wire_bytes < 2 * digest


@pytest.mark.parametrize("name", ["device_dispatches", "group_refolds",
                                  "staged_mb"])
def test_an_accepted_reader_reads_what_it_read(window, name):
    """The sharing moves no count the accepted span readers take: two
    dispatches a request (a PEM fold, a merge), no re-fold, nothing
    staged."""
    assert _read(name, window) == {
        "device_dispatches": 2, "group_refolds": 0, "staged_mb": 0}[name]


@pytest.mark.parametrize("platform", ["tpu", "cpu"])
def test_a_rehearsal_of_the_cell_is_sound(platform):
    """The served stack's answer against the plain reference on both
    platforms' routes: exact keys, counts and byte sums, every edge's
    quantiles inside the limits, with one digest state a fold
    program."""
    from benchmark.reference.px_service_graph import LIMITS

    result = edges._rehearse(platform)
    assert result["rehearsal"] is True and result["failed"] == 0
    assert result["correct"] is True, result["numbers"]
    numbers = result["numbers"]
    assert set(numbers) == set(LIMITS)
    assert {k: numbers[k] for k in edges.EXACT} == {
        k: [0.0, 0] for k in edges.EXACT}
    relerr, limit = numbers["service_graph.error_rate_relerr"]
    assert 0 < relerr < 1.2e-7 < limit  # an f32 plane, one rounding
    for k in edges.RANK:
        assert numbers[k][0] <= numbers[k][1] / 2, k
    metrics = result["metrics"]
    # One scan-folded program a request on the TPU's routes; on the
    # CPU's a dispatch a window, each holding the one carry.
    assert metrics["digest_states"]["value"] == (
        1 if platform == "tpu" else 1 * 4)
    slots = metrics["group_slots"]["value"]
    assert slots >= 8_192
    assert metrics["digest_slots"]["value"] == slots * 128
    assert metrics["digest_mb"]["value"] == pytest.approx(
        2 * slots * 128 * 4 / 1e6)
    assert metrics["window_compiles"]["value"] == 0
    assert metrics["group_refolds"]["value"] == 0
    assert metrics["staged_mb"]["value"] == 0
    assert "service_stats_p50_ms" not in metrics and "join_ms" not in metrics
