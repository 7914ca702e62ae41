"""The Kelvin's merge (``exec/bridge.py`` ``merge_agg_bridge``) against a
plain numpy union-and-reduce: k = 1, 2, 4 payloads x equal, overlapping
and disjoint dictionaries x a dense PEM state, a keyed one, a digest
carry. Every benchmark cell has ONE PEM, so that the remap is the
identity and the fold empty there: what a cluster of PEMs, each with its
own dictionaries, needs of the merge is held here. Each case also holds
what the merge prepares once (``_PreparedMerge``): the first request
misses, the second hits, calls ``StringDictionary.get_or_add`` never and
compiles nothing.
"""

import contextlib
import threading

import numpy as np
import pytest

from conftest import routes_of
from pixie_tpu.exec.engine import Engine, QueryError
from pixie_tpu.exec.plan import (
    AggExpr, AggOp, ColumnRef as C, LimitOp, MapOp, MemorySourceOp, Plan,
    ResultSinkOp,
)
from pixie_tpu.planner.distributed.splitter import Splitter
from pixie_tpu.types.batch import bucket_capacity
from pixie_tpu.types.strings import StringDictionary

KS = (1, 2, 4)
DICTS = ("equal", "overlapping", "disjoint")
LAYOUTS = ("dense", "keyed", "digest")
ROWS = 400
#: Integer keys too far apart for a dense domain: a keyed PEM state.
CODES = np.array([3, 10**6 + 1, 10**9 + 7, 10**11 + 3, 10**12 + 9])

_compiles = [0]


def _on_compile(event, _secs, **_kw):
    if event == "/jax/core/compile/backend_compile_duration":
        _compiles[0] += 1


@pytest.fixture(scope="module", autouse=True)
def _compile_meter():
    import jax.monitoring

    jax.monitoring.register_event_duration_secs_listener(_on_compile)
    yield
    _drop_programs()


def _drop_programs():
    """Let go of every executable this process holds. An executable of
    the CPU backend maps its code, some 300 mappings a keyed merge, and
    the process's caches pin them: this file's cases (each compiles a
    merge of its own, by design) would walk an xdist worker into
    ``vm.max_map_count`` (65,530) and the compiler into a segfault."""
    from pixie_tpu.exec import fragment, programs

    programs.default_program_registry().clear()
    fragment._FRAGMENT_CACHE.clear()


@pytest.fixture
def fresh_programs():
    yield
    _drop_programs()


@contextlib.contextmanager
def _counted_get_or_add():
    """Calls of ``StringDictionary.get_or_add`` while the block runs."""
    calls = [0]
    real = StringDictionary.get_or_add

    def counted(d, s):
        calls[0] += 1
        return real(d, s)

    StringDictionary.get_or_add = counted
    try:
        yield calls
    finally:
        StringDictionary.get_or_add = real


def _names(a: int, dicts: str) -> list:
    """Agent ``a``'s services, in the order its dictionary learns them."""
    if dicts == "equal":
        return [f"svc-{i}" for i in range(12)]
    if dicts == "overlapping":
        return [f"svc-{i}" for i in range(3 * a, 3 * a + 6)]
    return [f"a{a}-svc-{i}" for i in range(6)]


def _rows(a: int, dicts: str, seed: int = 0, rows: int = ROWS) -> dict:
    rng = np.random.default_rng(1000 * seed + a)
    names = _names(a, dicts)
    # Every name once, in order, so that equal name lists give equal
    # dictionaries; then a skewed draw.
    svc = names + [names[i] for i in
                   rng.zipf(1.5, rows - len(names)) % len(names)]
    return {
        "time_": np.arange(rows, dtype=np.int64),
        "svc": svc,
        "code": CODES[rng.integers(0, len(CODES), rows)],
        "lat": rng.integers(1, 10**9, rows).astype(np.int64),
    }


def _agent(rows: dict) -> Engine:
    eng = Engine(window_rows=1 << 10)
    eng.append_data("t", rows)
    return eng


def _split(layout: str, limit: int = 10_000, plucks=None):
    keys = ("svc", "code") if layout == "keyed" else ("svc",)
    if plucks is not None:
        aggs = (AggExpr("n", "count", (C("lat"),)),) + tuple(
            AggExpr(q, f"_quantile_{q}", (C("lat"),)) for q in plucks)
    elif layout == "digest":
        aggs = (AggExpr("n", "count", (C("lat"),)),
                AggExpr("p50", "_quantile_p50", (C("lat"),)))
    else:
        aggs = (AggExpr("n", "count", (C("lat"),)),
                AggExpr("total", "sum", (C("lat"),)),
                AggExpr("worst", "max", (C("lat"),)))
    p = Plan()
    src = p.add(MemorySourceOp(table="t"))
    agg = p.add(AggOp(keys, aggs), [src])
    # The plan's ops after the finalize node ride the merge's program.
    out = p.add(MapOp(exprs=tuple(
        [("service", C("svc"))] + [(k, C(k)) for k in keys[1:]]
        + [(a.out_name, C(a.out_name)) for a in aggs]
    )), [agg])
    lim = p.add(LimitOp(n=limit), [out])
    p.add(ResultSinkOp("output"), [lim])
    return Splitter().split(p)


def _payloads(split, agents):
    return [e.execute_plan(split.before_blocking)[("bridge", 0)]
            for e in agents]


def _merge(kelvin, split, payloads) -> tuple:
    """(rows by key, the merge's trace)."""
    out = kelvin.execute_plan(
        split.after_blocking, bridge_inputs={0: payloads}
    )["output"].to_pydict()
    cols = [c for c in out if c not in ("service", "code")]
    keys = zip(out["service"], out["code"]) if "code" in out else out["service"]
    got = {k: tuple(out[c][i] for c in cols) for i, k in enumerate(keys)}
    assert len(got) == len(out["service"])  # a group appears once
    return got, kelvin.tracer.last()


def _reference(all_rows: list, layout: str) -> dict:
    """The union of the agents' rows, reduced group by group in numpy."""
    groups: dict = {}
    for rows in all_rows:
        for i, s in enumerate(rows["svc"]):
            k = (s, int(rows["code"][i])) if layout == "keyed" else s
            groups.setdefault(k, []).append(int(rows["lat"][i]))
    if layout == "digest":
        return {k: (len(v), float(np.median(v))) for k, v in groups.items()}
    return {k: (len(v), sum(v), max(v)) for k, v in groups.items()}


def _assert_equal(got: dict, want: dict, layout: str) -> None:
    assert set(got) == set(want)
    for k, w in want.items():
        g = got[k]
        if layout == "digest":
            assert g[0] == w[0]
            # A digest of a few rows interpolates between neighbours.
            assert abs(g[1] - w[1]) <= 0.35 * w[1] or w[0] < 8, (k, g, w)
        else:
            assert tuple(int(x) for x in g) == w, k


def _dispatches(trace) -> list:
    return [s for s in trace.spans if s.name == "device.dispatch"]


def _assert_prepared(trace, want: str, rebuckets: int = 0) -> None:
    """One ``merge_finalize`` an attempt, the last one ``want``; one
    ``device.wait`` each; nothing staged; the usage counters agree."""
    spans = _dispatches(trace)
    assert [s.attributes["program"] for s in spans] == (
        ["merge_finalize"] * (1 + rebuckets)
    )
    assert spans[-1].attributes["prepared"] == want
    assert spans[-1].attributes["slots"] >= 1024
    names = [s.name for s in trace.spans]
    assert names.count("device.wait") == 1 + rebuckets
    assert "window.stage" not in names
    assert names.count("rebucket") == rebuckets == trace.usage.rebuckets
    u = trace.usage
    assert u.merge_prepared_hits + u.merge_prepared_misses == 1 + rebuckets
    assert (u.merge_prepared_hits if want == "hit"
            else u.merge_prepared_misses) >= 1


@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("dicts", DICTS)
@pytest.mark.parametrize("k", KS)
def test_merge_equals_union_and_reduce(k, dicts, layout):
    split = _split(layout)
    all_rows = [_rows(a, dicts) for a in range(k)]
    payloads = _payloads(split, [_agent(r) for r in all_rows])
    assert bool(payloads[0].dense_domains) == (layout != "keyed")
    want = _reference(all_rows, layout)
    kelvin = Engine()
    got, trace = _merge(kelvin, split, payloads)
    _assert_equal(got, want, layout)
    _assert_prepared(trace, "miss")
    # The second identical request: everything but the values is
    # remembered.
    before = _compiles[0]
    with _counted_get_or_add() as calls:
        got, trace = _merge(kelvin, split, payloads)
    _assert_equal(got, want, layout)
    _assert_prepared(trace, "hit")
    assert calls[0] == 0
    assert _compiles[0] == before
    assert len(kelvin._prepared_merges) == 1


@pytest.mark.parametrize("platform", ["cpu", "tpu"])
@pytest.mark.parametrize("layout", ["dense", "keyed"])
@pytest.mark.parametrize("k", [2, 4])
def test_a_shared_digest_through_the_bridge(k, layout, platform):
    """One digest an argument, PEM to Kelvin: three plucked quantiles of
    one column ship ONE [slots, 128] carry from each of k PEMs with
    dictionaries of their own (``digest_bytes`` counts its planes once),
    the Kelvin's fragment of the same chain derives the same owner, and
    every plucked quantile of the merged answer equals, value for value,
    the merged answer of the chain with that pluck alone."""
    all_rows = [_rows(a, "overlapping", seed=3) for a in range(k)]
    plucks = ("p50", "p90", "p99")

    def served(plucks):
        split = _split(layout, plucks=plucks)
        agents = [_agent(r) for r in all_rows]
        payloads = _payloads(split, agents)
        got, _trace = _merge(Engine(), split, payloads)
        return got, payloads, [e.tracer.last() for e in agents]

    with routes_of(platform):
        got, payloads, traces = served(plucks)
        for p, trace in zip(payloads, traces):
            assert set(p.state["carries"]) == {"n", "p50"}
            planes = p.state["carries"]["p50"]
            slots = len(p.state["valid"])
            assert [a.shape for a in planes] == [(slots, 128)] * 2
            (payload,) = [s for s in trace.spans if s.name == "payload"]
            assert payload.attributes["digest_bytes"] == (
                1 * 2 * slots * 128 * 4) == trace.usage.digest_bytes
            folds = [s.attributes for s in trace.spans
                     if s.name == "device.dispatch"
                     and "digests" in s.attributes]
            # (The CPU's native dense fold dispatches no fold program.)
            assert folds or (layout, platform) == ("dense", "cpu")
            assert all(
                (a["digests"], a["digest_outputs"]) == (1, 3) for a in folds)
        counts = _reference(all_rows, layout if layout == "keyed" else "digest")
        assert {key: row[0] for key, row in got.items()} == {
            key: row[0] for key, row in counts.items()}
        for at, pluck in enumerate(plucks):
            alone, _payloads_, _traces = served((pluck,))
            assert set(alone) == set(got)
            for key, (n, q) in alone.items():
                assert got[key][0] == n
                np.testing.assert_array_equal(
                    np.float64(got[key][1 + at]), np.float64(q),
                    err_msg=f"{key} {pluck}")


def test_equal_dictionaries_are_not_remapped_and_others_are():
    """Equal ``content_key``s: the canonical dictionary IS the first
    payload's and no remap is applied; disjoint ones: every payload but
    the first carries one."""
    split = _split("dense")
    for dicts, remapped in (("equal", [False, False]),
                            ("disjoint", [False, True])):
        agents = [_agent(_rows(a, dicts)) for a in range(2)]
        payloads = _payloads(split, agents)
        kelvin = Engine()
        _merge(kelvin, split, payloads)
        (rec,) = kelvin._prepared_merges.values()
        assert [bool(r) for r in rec.remaps] == remapped
        canon = next(m.dict for m in rec.meta if m.name == "service")
        first = payloads[0].input_dicts["svc"]
        assert (canon is first) == (dicts == "equal")


def test_payloads_from_the_wire_hit_without_a_dictionary_loop():
    """Decoded payloads bring fresh dictionary objects every request:
    the record is found by content, and the answer's dictionary is the
    record's own object."""
    from pixie_tpu.services.wire import decode, encode

    split = _split("dense")
    all_rows = [_rows(a, "overlapping") for a in range(2)]
    sent = [encode(p) for p in
            _payloads(split, [_agent(r) for r in all_rows])]
    kelvin = Engine()
    want = _reference(all_rows, "dense")
    dicts = []
    for expect in ("miss", "hit", "hit"):
        payloads = [decode(b) for b in sent]
        with _counted_get_or_add() as calls:
            out = kelvin.execute_plan(
                split.after_blocking, bridge_inputs={0: payloads}
            )["output"]
        _assert_prepared(kelvin.tracer.last(), expect)
        assert (calls[0] == 0) == (expect == "hit")
        dicts.append(out.dicts["service"])
        got = dict(zip(out.to_pydict()["service"],
                       out.to_pydict()["n"].tolist()))
        assert got == {k: v[0] for k, v in want.items()}
    assert dicts[0] is dicts[1] is dicts[2]


def test_a_dictionary_that_grows_misses_once():
    split = _split("dense")
    rows = _rows(0, "equal")
    agent = _agent(rows)
    kelvin = Engine()
    _merge(kelvin, split, _payloads(split, [agent]))
    _got, trace = _merge(kelvin, split, _payloads(split, [agent]))
    _assert_prepared(trace, "hit")
    more = {"time_": np.arange(ROWS, ROWS + 3, dtype=np.int64),
            "svc": ["svc-new"] * 3, "code": CODES[:3],
            "lat": np.array([5, 6, 7], dtype=np.int64)}
    agent.append_data("t", more)
    got, trace = _merge(kelvin, split, _payloads(split, [agent]))
    _assert_prepared(trace, "miss")
    assert got["svc-new"] == (3, 18, 7)
    _assert_equal(got, _reference([rows, more], "dense"), "dense")
    _got, trace = _merge(kelvin, split, _payloads(split, [agent]))
    _assert_prepared(trace, "hit")


def test_an_empty_payload_merges_as_nothing():
    split = _split("dense")
    rows = _rows(0, "equal")
    empty = {"time_": np.empty(0, np.int64), "svc": np.empty(0, dtype=str),
             "code": np.empty(0, np.int64), "lat": np.empty(0, np.int64)}
    payloads = _payloads(split, [_agent(rows), _agent(empty)])
    assert not payloads[1].state["valid"].any()
    kelvin = Engine()
    got, trace = _merge(kelvin, split, payloads)
    _assert_equal(got, _reference([rows], "dense"), "dense")
    _assert_prepared(trace, "miss")
    got, trace = _merge(kelvin, split, payloads)
    _assert_equal(got, _reference([rows], "dense"), "dense")
    _assert_prepared(trace, "hit")


def _wide(a: int, shared: bool) -> dict:
    """1,000 services an agent: the same ones, or its own."""
    names = [f"{'s' if shared else f'a{a}'}-{i}" for i in range(1000)]
    return {"time_": np.arange(1000, dtype=np.int64), "svc": names,
            "code": np.zeros(1000, np.int64),
            "lat": np.arange(1, 1001, dtype=np.int64) * (a + 1)}


def test_a_union_that_overflows_the_remembered_capacity_refolds_once():
    """Two agents with the same 1,000 groups: the merge runs at the
    bucket of what they hold (2,048) and remembers the bucket the union
    fit (1,024). The same chain over disjoint groups then starts there,
    spills, doubles once (one ``rebucket`` span) and is right; the third
    request starts at what the climb settled on."""
    split = _split("dense")
    kelvin = Engine()
    same = [_wide(a, True) for a in range(2)]
    got, trace = _merge(kelvin, split, _payloads(split, map(_agent, same)))
    _assert_equal(got, _reference(same, "dense"), "dense")
    assert _dispatches(trace)[0].attributes["slots"] == 2048
    apart = [_wide(a, False) for a in range(2)]
    payloads = _payloads(split, map(_agent, apart))
    got, trace = _merge(kelvin, split, payloads)
    _assert_equal(got, _reference(apart, "dense"), "dense")
    _assert_prepared(trace, "miss", rebuckets=1)
    assert [s.attributes["slots"] for s in _dispatches(trace)] == [1024, 2048]
    got, trace = _merge(kelvin, split, payloads)
    _assert_equal(got, _reference(apart, "dense"), "dense")
    _assert_prepared(trace, "hit")
    assert _dispatches(trace)[0].attributes["slots"] == 2048


@pytest.mark.parametrize("platform", ["cpu", "tpu"])
def test_a_string_carry_over_disagreeing_dictionaries_is_refused(platform):
    """``any`` of a string carries dictionary ids, which the merge does
    not realign (only group keys are): from k = 2 agents whose
    dictionaries differ in content it is refused loudly, on the CPU's
    routes and on the TPU's (where the ``any`` rides the keyed sort as a
    maximum of ids); over equal dictionaries it merges to the greatest
    id's string a group."""
    with routes_of(platform):
        _a_string_carry_across_agents(platform)


def _a_string_carry_across_agents(platform):
    p = Plan()
    src = p.add(MemorySourceOp(table="t"))
    agg = p.add(AggOp(("code",), (AggExpr("some", "any", (C("svc"),)),)),
                [src])
    p.add(ResultSinkOp("output"), [agg])
    split = Splitter().split(p)
    kelvin = Engine()
    payloads = _payloads(
        split, [_agent(_rows(a, "disjoint")) for a in range(2)]
    )
    for _ in range(2):  # nothing of a refused merge is remembered
        with pytest.raises(QueryError, match="string ids"):
            kelvin.execute_plan(
                split.after_blocking, bridge_inputs={0: payloads}
            )
    assert len(kelvin._prepared_merges) == 0
    # ... and the same carry over equal dictionaries merges.
    payloads = _payloads(
        split, [_agent(_rows(a, "equal")) for a in range(2)]
    )
    assert {p.chain[-1].aggs[0].uda_name for p in payloads} == {"any"}
    out = kelvin.execute_plan(
        split.after_blocking, bridge_inputs={0: payloads}
    )["output"].to_pydict()
    names = _names(0, "equal")
    want = {}
    for a in range(2):
        rows = _rows(a, "equal")
        for code, svc in zip(rows["code"].tolist(), rows["svc"]):
            want[code] = max(want.get(code, -1), names.index(svc))
    assert dict(zip(out["code"].tolist(), out["some"])) == {
        code: names[i] for code, i in want.items()}
    fold = [s.attributes.get("fold") for s in kelvin.tracer.last().spans
            if s.name == "device.dispatch"]
    assert fold == [None]  # the merge's one program


def test_an_overflowed_payload_is_refused():
    split = _split("dense")
    (payload,) = _payloads(split, [_agent(_rows(0, "equal"))])
    payload.state["overflow"] = np.asarray(True)
    with pytest.raises(QueryError, match="group overflow"):
        Engine().execute_plan(
            split.after_blocking, bridge_inputs={0: [payload]}
        )


def test_the_limit_cuts_the_same_rows_in_the_same_order():
    """The closing Limit is applied after the finalize, to the rows in
    slot order, as the fragment over the merged rows applied it."""
    rows = _rows(0, "equal")
    (payload,) = _payloads(_split("dense"), [_agent(rows)])
    kelvin = Engine()
    every = kelvin.execute_plan(
        _split("dense").after_blocking, bridge_inputs={0: [payload]}
    )["output"].to_pydict()
    cut = kelvin.execute_plan(
        _split("dense", limit=5).after_blocking,
        bridge_inputs={0: [payload]},
    )["output"].to_pydict()
    assert len(every["service"]) == 12
    assert list(cut["service"]) == list(every["service"][:5])
    assert list(cut["total"]) == list(every["total"][:5])


def test_two_chains_merge_concurrently_on_one_kelvin():
    kelvin = Engine()
    jobs = []
    for layout in ("dense", "keyed"):
        split = _split(layout)
        all_rows = [_rows(a, "overlapping", seed=7) for a in range(2)]
        jobs.append((layout, split,
                     _payloads(split, [_agent(r) for r in all_rows]),
                     _reference(all_rows, layout)))
    start = threading.Barrier(len(jobs))
    errors = []

    def run(layout, split, payloads, want):
        try:
            start.wait(timeout=60)
            for _ in range(4):
                got, _trace = _merge(kelvin, split, payloads)
                _assert_equal(got, want, layout)
        except BaseException as e:  # surfaced on the main thread
            errors.append(e)

    threads = [threading.Thread(target=run, args=j) for j in jobs]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not errors, errors
    assert len(kelvin._prepared_merges) == 2


def _span_readers():
    """``tests/benchmark/test_span_readers.py``'s rehearsed window and
    reader (that directory is the benchmark's: read, not edited)."""
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(__file__), "benchmark",
                        "test_span_readers.py")
    spec = importlib.util.spec_from_file_location("_span_readers", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_served_refresh_span_shape():
    """A served refresh of ``http_pem_1chip.dash_recent``, rehearsed: the
    Kelvin's trace of each request holds exactly one ``device.dispatch``
    (``merge_finalize``, ``prepared`` = ``hit``) and one ``device.wait``
    and stages nothing; ``device_dispatches`` reads 4 a refresh (a PEM
    fold and a merge a script); and head + device interval + tail still
    make up the broker's root, with the parts inside what holds them."""
    readers = _span_readers()
    ctx = readers._window(0.05)
    ctx["window"]["refreshes"] = ctx["window"]["refreshes"][:1]
    (refresh,) = ctx["window"]["refreshes"]
    merges = {t.qid: t for t in ctx["spans"]["kelvin"] if t.kind == "merge"}
    for rec in refresh:
        trace = merges[rec["qid"]]
        _assert_prepared(trace, "hit")
        assert trace.usage.merge_prepared_hits == 1
    read = readers._read
    assert read("device_dispatches", ctx) == 2 * (1 + 1)
    roots = {t.qid: t for t in ctx["spans"]["broker"]}
    root_ms = sum((roots[r["qid"]].end_ns - roots[r["qid"]].start_ns) / 1e6
                  for r in refresh)
    head, tail, interval = (read(m, ctx) for m in (
        "head_ms", "tail_ms", "device_interval_ms"
    ))
    assert min(head, tail, interval) > 0
    assert head + tail + interval == pytest.approx(root_ms, abs=1e-6)
    client_ms = sum((r["t1"] - r["t0"]) * 1e3 for r in refresh)
    assert root_ms < client_ms < root_ms + 25
    assert read("device_wait_ms", ctx) + read("dispatch_ms", ctx) <= interval
    assert read("merge_ms", ctx) < tail
    assert read("broker_self_ms", ctx) < head + tail


# -- the k-way fold (PR 47) ---------------------------------------------------
# k >= 2 payloads of a keyed sort fold merge in ONE fold at their own
# sizes (``exec/fragment.py`` ``merge_many``): on the chip's routes, which
# these cases run under. The cases above run the CPU's, whose merge
# fragment is the id-form fold: the scan of pairwise merges.

MANY_KS = (2, 3, 4)
KEY_SETS = ("disjoint", "overlapping", "mixed")
BUCKETS = ("equal", "differ", "empty")
MANY_DICTS = ("equal", "differ")
SVCS = [f"svc-{i}" for i in range(6)]


def _many_rows(a: int, key_set: str, size: str, dicts: str) -> dict:
    """Agent ``a``'s rows: groups by (svc, code), a handful of rows each.
    ``key_set``: the agents' groups are their own, the same, or half and
    half; ``size``: ``small`` (72 live groups: the least bucket, 1,024),
    ``large`` (1,560: the next one) or ``empty`` (no row); ``dicts``:
    every agent learns the services in one order, or in an order of its
    own with a service of its own ahead of them."""
    if size == "empty":
        return {"time_": np.empty(0, np.int64), "svc": np.empty(0, dtype=str),
                "code": np.empty(0, np.int64), "lat": np.empty(0, np.int64),
                "tag": np.empty(0, np.int64)}
    rng = np.random.default_rng(4700 + a)
    codes = 260 if size == "large" else 12
    shared = {"disjoint": 0, "overlapping": codes, "mixed": codes // 2}[key_set]
    code = np.array([10**9 * (0 if j < shared else a + 1) + 7 * j
                     for j in range(codes)])
    names = SVCS if dicts == "equal" else (
        [f"only-a{a}"] + SVCS[a:] + SVCS[:a])
    first = [(s, c) for s in names for c in code
             if not s.startswith("only")]
    if key_set != "overlapping" and dicts == "differ":
        first = [(names[0], int(code[-1]))] + first
    elif dicts == "differ":  # (its own service learnt, no group under it)
        names = names[1:]
        first = [(s, c) for s in names for c in code]
    more = [first[i] for i in rng.integers(0, len(first), 4 * len(first))]
    groups = first + more
    return {
        "time_": np.arange(len(groups), dtype=np.int64),
        "svc": [s for s, _c in groups],
        "code": np.array([c for _s, c in groups], dtype=np.int64),
        "lat": rng.integers(1, 10**9, len(groups)).astype(np.int64),
        # One value a group, so that ``any`` has one answer.
        "tag": np.array([c % 1000 + len(s) for s, c in groups],
                        dtype=np.int64),
    }


def _many_split():
    aggs = (AggExpr("n", "count", (C("lat"),)),
            AggExpr("total", "sum", (C("lat"),)),
            AggExpr("worst", "max", (C("lat"),)),
            AggExpr("some", "any", (C("tag"),)),
            AggExpr("p50", "_quantile_p50", (C("lat"),)))
    p = Plan()
    src = p.add(MemorySourceOp(table="t"))
    agg = p.add(AggOp(("svc", "code"), aggs), [src])
    out = p.add(MapOp(exprs=tuple(
        [("service", C("svc")), ("code", C("code"))]
        + [(a.out_name, C(a.out_name)) for a in aggs]
    )), [agg])
    p.add(ResultSinkOp("output"), [out])
    return Splitter().split(p)


_many_payloads: dict = {}


def _many_payload(a, key_set, size, dicts):
    """(rows, payload) of one agent, made once a module run: the same
    agent serves every k."""
    key = (a, key_set, size, dicts)
    if key not in _many_payloads:
        rows = _many_rows(a, key_set, size, dicts)
        (payload,) = _payloads(_many_split(), [_agent(rows)])
        _many_payloads[key] = (rows, payload)
    return _many_payloads[key]


def _sizes(k: int, buckets: str) -> list:
    """``equal``: every agent's live groups share a bucket; ``differ``:
    agent 1's take the next one; ``empty``: the last agent has no row."""
    sizes = ["small"] * k
    if buckets == "differ":
        sizes[1] = "large"
    elif buckets == "empty":
        sizes[-1] = "empty"
    return sizes


def _many_reference(all_rows) -> dict:
    groups: dict = {}
    for a, rows in enumerate(all_rows):
        for s, c, lat, tag in zip(rows["svc"], rows["code"].tolist(),
                                  rows["lat"].tolist(), rows["tag"].tolist()):
            g = groups.setdefault((s, c), {"lat": [], "tag": set(),
                                           "agents": set()})
            g["lat"].append(lat)
            g["tag"].add(tag)
            g["agents"].add(a)
    return groups


def _arrived(rec, payloads) -> list:
    """The states as ``merge_finalize`` hands them to the fold: compacted,
    explicit keys, string ids remapped into the canonical dictionary."""
    from pixie_tpu.exec import bridge
    from pixie_tpu.types.strings import NULL_ID

    states = []
    for p, remap in zip(payloads, rec.remaps):
        idx, _live, _cap = bridge._live_slots(p.state)
        s = bridge._explicit_state(p, idx, rec.key_types)
        keys = list(s["keys"])
        for pi, table in remap.items():
            table = np.asarray(table)
            ids = np.asarray(keys[pi])
            keys[pi] = np.where(
                ids >= 0, table[np.clip(ids, 0, len(table) - 1)], NULL_ID
            ).astype(np.int32)
        states.append({**s, "keys": tuple(keys)})
    return states


def _bits(plane):
    return np.ascontiguousarray(plane, dtype=np.float32).view(np.uint32)


def _assert_digests_moved_or_rebinned(rec, payloads, contended_want: int):
    """The k-way fold's digests, slot for slot: the one state's row bit
    for bit where one state fills the slot; ``merge_ordered`` folded over
    the states that fill it, in payload order, where several do."""
    import jax

    from pixie_tpu.ops.tdigest import merge_ordered

    states = _arrived(rec, payloads)
    merged, told = jax.jit(rec.frag.merge_many)(states)
    merged = jax.tree_util.tree_map(np.asarray, merged)
    fills: dict = {}  # key -> [(state, slot)], in payload order
    for j, s in enumerate(states):
        for slot in np.nonzero(np.asarray(s["valid"]))[0]:
            fills.setdefault(
                tuple(int(k[slot]) for k in s["keys"]), []
            ).append((j, int(slot)))
    live = np.nonzero(merged["valid"])[0]
    assert len(live) == len(fills)
    assert int(told["contended_slots"]) == contended_want == sum(
        len(f) > 1 for f in fills.values())
    by_pattern: dict = {}  # the states that fill a slot -> its slots
    for slot in live:
        key = tuple(int(k[slot]) for k in merged["keys"])
        by_pattern.setdefault(
            tuple(j for j, _s in fills[key]), []
        ).append((int(slot), [s for _j, s in fills[key]]))
    got = merged["carries"]["p50"]
    for pattern, slots in by_pattern.items():
        at = np.array([slot for slot, _src in slots])
        sides = [
            tuple(np.asarray(states[j]["carries"]["p50"][plane])[
                np.array([src[n] for _slot, src in slots])]
                for plane in (0, 1))
            for n, j in enumerate(pattern)
        ]
        want = sides[0]
        for side in sides[1:]:
            want = jax.jit(merge_ordered)(want, side)
        for plane in (0, 1):
            if len(pattern) == 1:  # as it was shipped, bit for bit
                np.testing.assert_array_equal(
                    _bits(got[plane][at]), _bits(want[plane]))
            else:
                np.testing.assert_allclose(
                    got[plane][at], np.asarray(want[plane]), rtol=1e-6)
    # A slot no state fills holds the empty digest.
    dead = ~merged["valid"]
    assert not got[0][dead].any() and not got[1][dead].any()


@pytest.mark.parametrize("dicts", MANY_DICTS)
@pytest.mark.parametrize("buckets", BUCKETS)
@pytest.mark.parametrize("key_set", KEY_SETS)
@pytest.mark.parametrize("k", MANY_KS)
def test_k_payloads_fold_once(k, key_set, buckets, dicts, fresh_programs):
    """k >= 2 keyed payloads under the chip's routes: keys, counts, sums,
    ``max`` and ``any`` equal the union-and-reduce reference exactly; a
    digest no other payload joins is the shipped one bit for bit, a
    joined one ``merge_ordered`` over its contributors; the wait says
    what the fold joined."""
    with routes_of("tpu"):
        made = [_many_payload(a, key_set, size, dicts)
                for a, size in enumerate(_sizes(k, buckets))]
        all_rows = [rows for rows, _p in made]
        payloads = [p for _rows, p in made]
        caps = [_cap(p) for p in payloads]
        assert (len(set(caps)) > 1) == (buckets == "differ"), caps
        assert payloads[-1].state["valid"].any() == (buckets != "empty")
        full = k - (buckets == "empty")  # (an empty agent learnt nothing)
        same = len({p.input_dicts["svc"].content_key()
                    for p in payloads[:full]}) == 1
        assert same == (dicts == "equal" or full < 2)
        kelvin = Engine()
        out = kelvin.execute_plan(
            _many_split().after_blocking, bridge_inputs={0: payloads}
        )["output"].to_pydict()
        trace = kelvin.tracer.last()
        (rec,) = kelvin._prepared_merges.values()
        want = _many_reference(all_rows)
        got = {(s, int(c)): i for i, (s, c) in
               enumerate(zip(out["service"], out["code"]))}
        assert set(got) == set(want) and len(got) == len(out["service"])
        for key, w in want.items():
            i = got[key]
            assert (int(out["n"][i]), int(out["total"][i]),
                    int(out["worst"][i])) == (
                len(w["lat"]), sum(w["lat"]), max(w["lat"])), key
            assert {int(out["some"][i])} == w["tag"], key
        contended = sum(len(w["agents"]) > 1 for w in want.values())
        assert (contended == 0) == (key_set == "disjoint" or full < 2)
        assert rec.frag.merge_many is not None
        assert bool(any(rec.remaps[:full])) == (not same)
        _assert_prepared(trace, "miss")
        (wait,) = [s for s in trace.spans if s.name == "device.wait"]
        rebins = (k - 1) if contended else 0
        assert (wait.attributes["contended_slots"],
                wait.attributes["rebins"]) == (contended, rebins)
        assert trace.usage.merge_rebins == rebins
        (dispatch,) = _dispatches(trace)
        assert (dispatch.attributes["payloads"],
                dispatch.attributes["merges"]) == (k, k - 1)
        assert dispatch.attributes["slots"] == max(
            bucket_capacity(sum(_live(p) for p in payloads)), 1024)
        _assert_digests_moved_or_rebinned(rec, payloads, contended)


def _live(p) -> int:
    return int(np.count_nonzero(p.state["valid"]))


def _cap(p) -> int:
    from pixie_tpu.exec import bridge

    return bridge._live_slots(p.state)[2]


def test_a_k_way_union_that_overflows_refolds_once():
    """The k-way fold under a remembered capacity the union outgrows:
    two agents with the same 1,000 (svc, code) groups teach the Kelvin
    1,024 slots; four with groups of their own start there (the largest
    payload's bucket), overflow, double ONCE (one ``rebucket`` span) and
    are right; the next request starts at what the climb settled on."""
    def wide(a: int, shared: bool) -> dict:
        return {"time_": np.arange(1000, dtype=np.int64),
                "svc": [SVCS[i % 6] for i in range(1000)],
                "code": np.arange(1000, dtype=np.int64) * 7 + (
                    0 if shared else 10**9 * (a + 1)),
                "lat": np.arange(1, 1001, dtype=np.int64) * (a + 1),
                "tag": np.zeros(1000, np.int64)}

    def merged(kelvin, all_rows):
        payloads = _payloads(split, map(_agent, all_rows))
        out = kelvin.execute_plan(
            split.after_blocking, bridge_inputs={0: payloads}
        )["output"].to_pydict()
        want = _many_reference(all_rows)
        assert len(out["service"]) == len(want)
        for s, c, n, total in zip(out["service"], out["code"], out["n"],
                                  out["total"]):
            w = want[s, int(c)]
            assert (int(n), int(total)) == (len(w["lat"]), sum(w["lat"]))
        return kelvin.tracer.last()

    with routes_of("tpu"):
        split = _many_split()
        kelvin = Engine()
        trace = merged(kelvin, [wide(a, True) for a in range(2)])
        assert _dispatches(trace)[0].attributes["slots"] == 2048
        apart = [wide(a, False) for a in range(4)]
        trace = merged(kelvin, apart)
        _assert_prepared(trace, "miss", rebuckets=2)
        assert [s.attributes["slots"] for s in _dispatches(trace)] == [
            1024, 2048, 4096]
        assert [(s.attributes["from"], s.attributes["to"])
                for s in trace.spans if s.name == "rebucket"] == [
            (1024, 2048), (2048, 4096)]
        trace = merged(kelvin, apart)
        _assert_prepared(trace, "hit")
        assert _dispatches(trace)[0].attributes["slots"] == 4096
        (wait,) = [s for s in trace.spans if s.name == "device.wait"]
        assert (wait.attributes["contended_slots"],
                wait.attributes["rebins"]) == (0, 0)


@pytest.mark.parametrize("platform", ["cpu", "tpu"])
def test_one_payload_folds_nothing(platform):
    """One payload pads, finalizes and applies the tail: its program
    holds no sort, no scatter, no conditional and no loop on either
    platform's routes (``tools/fold_hlo.py`` holds its text to the
    parent's byte for byte), and its wait says nothing of a fold."""
    with routes_of(platform):
        split = _many_split()
        rows, payload = _many_payload(0, "disjoint", "small", "equal")
        kelvin = Engine()
        out = kelvin.execute_plan(
            split.after_blocking, bridge_inputs={0: [payload]}
        )["output"].to_pydict()
        assert len(out["service"]) == len(_many_reference([rows]))
        trace = kelvin.tracer.last()
        (wait,) = [s for s in trace.spans if s.name == "device.wait"]
        assert not {"rebins", "contended_slots"} & set(wait.attributes)
        assert trace.usage.merge_rebins == 0
        (rec,) = kelvin._prepared_merges.values()
        (state,) = _arrived(rec, [payload])
        text = rec.program.fn.lower([state], rec.remaps).as_text()
        for op in ("stablehlo.sort", "stablehlo.scatter", "stablehlo.case",
                   "stablehlo.while"):
            assert op not in text, op
