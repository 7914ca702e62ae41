"""pxlint rule-engine tests: each rule on synthetic sources, the
suppression + baseline machinery, and the shipped-tree green gate
(``run_tests.sh --analyze``). See docs/ANALYSIS.md."""

from __future__ import annotations

import os
import textwrap

from pixie_tpu.analysis.lint import (
    load_baseline,
    run_lint,
    save_baseline,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _lint_src(tmp_path, name, src, rules=None, extra_files=()):
    p = tmp_path / name
    p.write_text(textwrap.dedent(src))
    for fname, fsrc in extra_files:
        (tmp_path / fname).parent.mkdir(parents=True, exist_ok=True)
        (tmp_path / fname).write_text(textwrap.dedent(fsrc))
    report = run_lint(
        [str(tmp_path)], rules=rules,
        baseline_path=str(tmp_path / "no_baseline.json"),
        repo_root=str(tmp_path),
    )
    return report


# -- host-sync-hot-path -------------------------------------------------------

_HOT_DECL = """
    PXLINT_HOT_REGIONS = (
        "hot_mod.py:Runner._loop*",
    )
"""


def test_host_sync_rule_flags_registered_regions(tmp_path):
    report = _lint_src(
        tmp_path, "hot_mod.py",
        """
        import numpy as np

        PXLINT_HOT_REGIONS = (
            "hot_mod.py:Runner._loop*",
        )

        class Runner:
            def _loop(self, xs):
                for x in xs:
                    x.block_until_ready()
                    v = float(x.item())
                    a = np.asarray(x)
                return a

            def cold(self, x):
                return np.asarray(x)  # not a hot region
        """,
        rules={"host-sync-hot-path"},
    )
    msgs = [f.message for f in report.findings]
    assert len(msgs) == 3
    assert any("block_until_ready" in m for m in msgs)
    assert any(".item()" in m for m in msgs)
    assert any("np.asarray" in m for m in msgs)
    assert all(f.symbol == "Runner._loop" for f in report.findings)


def test_host_sync_rule_knows_the_batched_get(tmp_path):
    # exec/stream.py's ``_fetch_tree`` (one jax.device_get of a pytree)
    # is a readback by name: a hot region that calls it says why.
    report = _lint_src(
        tmp_path, "hot_mod.py",
        """
        import jax

        from stream import _fetch_tree

        PXLINT_HOT_REGIONS = (
            "hot_mod.py:Runner._loop*",
        )

        class Runner:
            def _loop(self, outs):
                a = jax.device_get(outs)
                b = _fetch_tree(outs)
                c = _fetch_tree(outs)  # pxlint: disable=host-sync-hot-path
                return a, b, c
        """,
        rules={"host-sync-hot-path"},
    )
    msgs = sorted(f.message for f in report.findings)
    assert len(msgs) == 2
    assert "_fetch_tree()" in msgs[0] and "jax.device_get()" in msgs[1]


def test_host_sync_nested_def_reports_once(tmp_path):
    report = _lint_src(
        tmp_path, "hot_mod.py",
        """
        import numpy as np

        PXLINT_HOT_REGIONS = (
            "hot_mod.py:Runner._loop*",
        )

        class Runner:
            def _loop(self, xs):
                def stage(x):
                    return np.asarray(x)  # one violation, one finding
                return [stage(x) for x in xs]
        """,
        rules={"host-sync-hot-path"},
    )
    assert len(report.findings) == 1
    assert report.findings[0].symbol == "Runner._loop"


def test_host_sync_registration_is_cross_module(tmp_path):
    # pipeline-style module registers a region in ANOTHER file.
    report = _lint_src(
        tmp_path, "registrar.py",
        """
        PXLINT_HOT_REGIONS = ("worker.py:fold",)
        """,
        rules={"host-sync-hot-path"},
        extra_files=[(
            "worker.py",
            """
            import numpy as np

            def fold(xs):
                return [np.asarray(x) for x in xs]
            """,
        )],
    )
    assert len(report.findings) == 1
    assert report.findings[0].path == "worker.py"


# -- jit-recompile-hazard -----------------------------------------------------

def test_jit_recompile_rule(tmp_path):
    report = _lint_src(
        tmp_path, "jitted.py",
        """
        import jax
        from functools import partial

        @jax.jit
        def bad(x, n):
            if n > 3:          # traced arg -> flagged
                return x
            return x * n

        @partial(jax.jit, static_argnums=0)
        def also_checked(n, x):
            while n:           # flagged (rule is decorator-level)
                n -= 1
            return x

        @jax.jit
        def good(x, flags):
            if x.shape[0] > 4:     # static: shape attr
                return x
            if len(flags) > 1:     # static: len()
                return x
            closure_const = 3
            if closure_const:      # not an argument
                return x
            return x

        def not_jitted(x, n):
            if n:
                return x
        """,
        rules={"jit-recompile-hazard"},
    )
    assert [f.symbol for f in report.findings] == ["bad", "also_checked"]
    assert "retraces and recompiles" in report.findings[0].message


# -- thread-shared-state ------------------------------------------------------

_THREADY = """
    import threading

    class Svc:
        def __init__(self, bus):
            self._lock = threading.Lock()
            self.jobs = {}
            self.done = []
            threading.Thread(target=self._worker, daemon=True).start()
            bus.subscribe("x", self._on_msg)

        def _worker(self):
            self.jobs["w"] = 1

        def _on_msg(self, m):
            self.done.append(m)

        def submit(self, j):
            self.jobs[j.id] = j

        def drain(self):
            with self._lock:
                self.done = []
"""


def test_thread_shared_state_rule(tmp_path):
    report = _lint_src(
        tmp_path, "svc.py", _THREADY, rules={"thread-shared-state"},
    )
    by_attr = {
        f.message.split("self.")[1].split(" ")[0]: f
        for f in report.findings
    }
    # jobs: thread write + public write, both unlocked -> flagged.
    assert "jobs" in by_attr
    # done: thread append unlocked + public write locked -> flagged
    # (one side holding the lock protects nothing).
    assert "done" in by_attr


def test_thread_shared_state_two_dispatcher_threads(tmp_path):
    report = _lint_src(
        tmp_path, "two.py",
        """
        import threading

        class Two:
            def __init__(self, bus):
                self.state = {}
                bus.subscribe("a", self._on_a)
                bus.subscribe("b", self._on_b)

            def _on_a(self, m):
                self.state["a"] = m

            def _on_b(self, m):
                self.state["b"] = m
        """,
        rules={"thread-shared-state"},
    )
    # One finding PER unlocked write site (suppressing one site must
    # not hide the other).
    assert [f.symbol for f in report.findings] == [
        "Two._on_a", "Two._on_b",
    ]
    assert "two different dispatcher threads" in report.findings[0].message


def test_thread_shared_state_lock_discipline_is_clean(tmp_path):
    report = _lint_src(
        tmp_path, "clean.py",
        """
        import threading

        class Clean:
            def __init__(self, bus):
                self._lock = threading.Lock()
                self.state = {}
                bus.subscribe("a", self._on_a)

            def _on_a(self, m):
                with self._lock:
                    self.state["a"] = m

            def reset(self):
                with self._lock:
                    self.state = {}
        """,
        rules={"thread-shared-state"},
    )
    assert report.findings == []


# -- metrics-naming -----------------------------------------------------------

def test_metrics_naming_rule(tmp_path):
    report = _lint_src(
        tmp_path, "metrics.py",
        """
        def setup(reg):
            reg.counter("pixie_good_total", "ok")
            reg.counter("Bad-Name", "nope")
            reg.gauge("pixie_thing_count", "reserved suffix")
            reg.histogram("pixie_lat_seconds", "histograms may _count")
        """,
        rules={"metrics-naming"},
    )
    msgs = [f.message for f in report.findings]
    assert len(msgs) == 2
    assert any("'Bad-Name' violates" in m for m in msgs)
    assert any(
        "'pixie_thing_count' ends in a reserved" in m for m in msgs
    )


def test_metrics_tenant_label_cardinality(tmp_path):
    """ISSUE 13 satellite: {tenant}-labeled metrics are bounded-
    cardinality — a ``.labels(tenant=...)`` value must visibly derive
    from resolve_tenant() (or be DEFAULT_TENANT); raw client strings
    and unresolved names are findings."""
    report = _lint_src(
        tmp_path, "tenantlbl.py",
        """
        from pixie_tpu.services.tenancy import DEFAULT_TENANT, resolve_tenant

        def ok_direct(reg, raw):
            reg.counter("pixie_x_total").labels(
                tenant=resolve_tenant(raw)).inc()

        def ok_bound(reg, raw):
            tenant = resolve_tenant(raw)
            reg.counter("pixie_x_total").labels(tenant=tenant).inc()

        def ok_default(reg):
            reg.counter("pixie_x_total").labels(
                tenant=DEFAULT_TENANT).inc()

        def bad_raw(reg, msg):
            reg.counter("pixie_x_total").labels(
                tenant=msg.get("tenant")).inc()

        def bad_passthrough(reg, tenant):
            reg.counter("pixie_x_total").labels(tenant=tenant).inc()

        def bad_constant(reg):
            reg.counter("pixie_x_total").labels(tenant="rando").inc()
        """,
        rules={"metrics-naming"},
    )
    bad = sorted(f.symbol for f in report.findings)
    assert bad == ["bad_constant", "bad_passthrough", "bad_raw"], \
        "\n".join(f.render() for f in report.findings)
    assert all("resolve_tenant" in f.message for f in report.findings)


def test_metrics_tenant_label_assignment_forms(tmp_path):
    """Annotated and walrus assignments from resolve_tenant() bind the
    name just like a plain assignment — correct code must not need a
    baseline entry (false positives teach people to baseline)."""
    report = _lint_src(
        tmp_path, "tenantforms.py",
        """
        from pixie_tpu.services.tenancy import resolve_tenant

        def ok_annotated(reg, raw):
            tenant: str = resolve_tenant(raw)
            reg.counter("pixie_x_total").labels(tenant=tenant).inc()

        def ok_walrus(reg, raw):
            if (t := resolve_tenant(raw)):
                reg.counter("pixie_x_total").labels(tenant=t).inc()
        """,
        rules={"metrics-naming"},
    )
    assert report.findings == [], \
        "\n".join(f.render() for f in report.findings)


def test_metrics_tenant_label_module_scope_binding(tmp_path):
    """A module-level resolved binding covers module-level label calls,
    but one function's binding does NOT leak into another function
    (scope boundaries are real, not whole-file grep)."""
    report = _lint_src(
        tmp_path, "tenantscope.py",
        """
        from pixie_tpu.services.tenancy import resolve_tenant

        TEN = resolve_tenant("boot")
        COUNTER.labels(tenant=TEN).inc()

        def resolver_elsewhere(raw):
            t = resolve_tenant(raw)
            return t

        def bad_other_scope(reg, t):
            reg.counter("pixie_x_total").labels(tenant=t).inc()
        """,
        rules={"metrics-naming"},
    )
    bad = sorted(f.symbol for f in report.findings)
    assert bad == ["bad_other_scope"], \
        "\n".join(f.render() for f in report.findings)


def test_lock_assigned_in_later_method_still_counts(tmp_path):
    # _worker is defined textually BEFORE the __init__ that creates the
    # lock; the class-wide lock pass must still see it.
    report = _lint_src(
        tmp_path, "order.py",
        """
        import threading

        class Ordered:
            def _worker(self):
                with self._lock:
                    self.state = 1

            def __init__(self, bus):
                self._lock = threading.Lock()
                self.state = 0
                threading.Thread(target=self._worker).start()

            def reset(self):
                with self._lock:
                    self.state = 0
        """,
        rules={"thread-shared-state"},
    )
    assert report.findings == [], \
        "\n".join(f.render() for f in report.findings)


# -- lock-order ---------------------------------------------------------------

_ABBA = """
    import threading

    class Svc:
        def __init__(self):
            self._la = threading.Lock()
            self._lb = threading.Lock()

        def fwd(self):
            with self._la:
                with self._lb:
                    pass

        def rev(self):
            with self._lb:
                with self._la:
                    pass
"""


def test_lock_order_detects_abba_cycle(tmp_path):
    report = _lint_src(tmp_path, "svc.py", _ABBA, rules={"lock-order"})
    assert len(report.findings) == 1
    msg = report.findings[0].message
    assert "lock-order cycle" in msg
    # Both acquisition chains are in the diagnostic.
    assert "Svc.fwd" in msg and "Svc.rev" in msg
    assert "Svc._la" in msg and "Svc._lb" in msg


def test_lock_order_transitive_same_class_calls(tmp_path):
    report = _lint_src(
        tmp_path, "tr.py",
        """
        import threading

        class Tr:
            def __init__(self):
                self._la = threading.Lock()
                self._lb = threading.Lock()

            def fwd(self):
                with self._la:
                    self._takes_b()

            def _takes_b(self):
                with self._lb:
                    pass

            def rev(self):
                with self._lb:
                    self._takes_a()

            def _takes_a(self):
                with self._la:
                    pass
        """,
        rules={"lock-order"},
    )
    assert len(report.findings) == 1
    msg = report.findings[0].message
    assert "Tr.fwd -> Tr._takes_b" in msg
    assert "Tr.rev -> Tr._takes_a" in msg


def test_lock_order_cross_module_chain(tmp_path):
    """The graph is interprocedural ACROSS modules: Holder holds its
    lock and calls into Other (attr type from the annotated ctor
    param); Other holds its lock and calls back. Neither file alone has
    a cycle."""
    report = _lint_src(
        tmp_path, "x1.py",
        """
        import threading
        from .x2 import Other

        class Holder:
            def __init__(self):
                self._hlock = threading.Lock()
                self.other = Other(self)

            def go(self):
                with self._hlock:
                    self.other.poke()

            def back(self):
                with self._hlock:
                    pass
        """,
        rules={"lock-order"},
        extra_files=[(
            "x2.py",
            """
            import threading

            class Other:
                def __init__(self, holder: "Holder"):
                    self._olock = threading.Lock()
                    self.holder = holder

                def poke(self):
                    with self._olock:
                        pass

                def reverse(self):
                    with self._olock:
                        self.holder.back()
            """,
        )],
    )
    assert len(report.findings) == 1
    msg = report.findings[0].message
    assert "Holder._hlock" in msg and "Other._olock" in msg
    assert "Holder.go -> Other.poke" in msg
    assert "Other.reverse -> Holder.back" in msg


def test_lock_order_self_deadlock_nonreentrant(tmp_path):
    report = _lint_src(
        tmp_path, "sd.py",
        """
        import threading

        class Dead:
            def __init__(self):
                self._lock = threading.Lock()

            def outer(self):
                with self._lock:
                    self._inner()

            def _inner(self):
                with self._lock:
                    pass

        class Fine:
            def __init__(self):
                self._lock = threading.RLock()

            def outer(self):
                with self._lock:
                    self._inner()

            def _inner(self):
                with self._lock:
                    pass
        """,
        rules={"lock-order"},
    )
    assert len(report.findings) == 1
    f = report.findings[0]
    assert f.symbol == "Dead.outer"
    assert "certain self-deadlock" in f.message
    assert "Dead.outer -> Dead._inner" in f.message


def test_lock_order_consistent_order_is_clean(tmp_path):
    """Nesting the same two locks in ONE consistent order everywhere
    (incl. via subclass inheritance of the lock attr) is fine."""
    report = _lint_src(
        tmp_path, "ok.py",
        """
        import threading

        class Base:
            def __init__(self):
                self._la = threading.Lock()
                self._lb = threading.Lock()

            def one(self):
                with self._la:
                    with self._lb:
                        pass

        class Sub(Base):
            def two(self):
                with self._la:
                    with self._lb:
                        pass
        """,
        rules={"lock-order"},
    )
    assert report.findings == [], \
        "\n".join(f.render() for f in report.findings)


def test_lock_order_condition_aliases_its_wrapped_lock(tmp_path):
    """``Condition(self._lock)`` shares _lock's underlying lock: the
    two attrs are ONE node, so nesting them is a self-deadlock, not a
    two-node cycle — and a bare Condition() (RLock inside) nested under
    itself through a helper stays clean."""
    report = _lint_src(
        tmp_path, "cond.py",
        """
        import threading

        class Shares:
            def __init__(self):
                self._lock = threading.Lock()
                self._changed = threading.Condition(self._lock)

            def bad(self):
                with self._lock:
                    with self._changed:
                        pass

        class BareCond:
            def __init__(self):
                self._cond = threading.Condition()

            def outer(self):
                with self._cond:
                    self._inner()

            def _inner(self):
                with self._cond:
                    pass
        """,
        rules={"lock-order"},
    )
    assert len(report.findings) == 1
    f = report.findings[0]
    assert f.symbol == "Shares.bad"
    assert "self-deadlock" in f.message


def test_lock_order_condition_alias_across_inheritance(tmp_path):
    """A subclass Condition wrapping a BASE-class Lock collapses onto
    the base lock's node with the base lock's (non-)reentrancy — the
    self-nest is a self-deadlock, not a clean two-node nesting."""
    report = _lint_src(
        tmp_path, "inh.py",
        """
        import threading

        class Base:
            def __init__(self):
                self._lock = threading.Lock()

        class Sub(Base):
            def __init__(self):
                super().__init__()
                self._cv = threading.Condition(self._lock)

            def bad(self):
                with self._lock:
                    with self._cv:
                        pass
        """,
        rules={"lock-order"},
    )
    assert len(report.findings) == 1, \
        "\n".join(f.render() for f in report.findings)
    f = report.findings[0]
    assert f.symbol == "Sub.bad" and "self-deadlock" in f.message


def test_lock_order_suppression_and_baseline(tmp_path):
    # Inline suppression silences the finding at its reported line.
    sup = _ABBA.replace(
        "with self._lb:\n                with self._la:",
        "with self._lb:  # pxlint: disable=lock-order\n"
        "                with self._la:",
    )
    # The finding anchors at the FIRST edge's acquisition site, so
    # suppress there instead: cycle findings land on the smallest
    # node's edge (Svc._la acquired in fwd).
    sup2 = _ABBA.replace(
        "def fwd(self):\n            with self._la:",
        "def fwd(self):\n"
        "            with self._la:  # pxlint: disable=lock-order",
    )
    r2 = _lint_src(tmp_path, "sup2.py", sup2, rules={"lock-order"})
    assert r2.findings == [] and r2.suppressed == 1
    # Baseline roundtrip: line drift keeps the key.
    import textwrap
    p = tmp_path / "legacy.py"
    p.write_text(textwrap.dedent(_ABBA))
    bl = tmp_path / "bl.json"
    r3 = run_lint([str(p)], rules={"lock-order"}, baseline_path=str(bl),
                  repo_root=str(tmp_path))
    assert len(r3.findings) == 1
    save_baseline(r3.findings, str(bl))
    p.write_text("\n\n" + textwrap.dedent(_ABBA))
    r4 = run_lint([str(p)], rules={"lock-order"}, baseline_path=str(bl),
                  repo_root=str(tmp_path))
    assert r4.ok and len(r4.baselined) == 1


# -- request-from-handler -----------------------------------------------------

def test_request_from_handler_direct_and_transitive(tmp_path):
    report = _lint_src(
        tmp_path, "handlers.py",
        """
        class Svc:
            def __init__(self, bus):
                self.bus = bus
                bus.subscribe("a", self._on_a)
                bus.subscribe("b", self._on_b)
                bus.subscribe("c", self._on_c)

            def _on_a(self, msg):
                return self.bus.request("status", {})  # direct

            def _on_b(self, msg):
                self._helper(msg)

            def _helper(self, msg):
                self.bus.request("other", {})  # transitive

            def _on_c(self, msg):
                self.bus.publish("ok", msg)  # publish never blocks
        """,
        rules={"request-from-handler"},
    )
    syms = sorted(f.symbol for f in report.findings)
    assert syms == ["Svc._helper", "Svc._on_a"], \
        "\n".join(f.render() for f in report.findings)
    assert all("dispatcher thread" in f.message for f in report.findings)


def test_request_from_handler_nested_def_and_wrapped(tmp_path):
    """serve()-style registration: a nested def subscribed through a
    wrapper call still runs on the dispatcher thread; sibling nested
    defs it calls are followed."""
    report = _lint_src(
        tmp_path, "served.py",
        """
        class Broker:
            def serve(self, bus):
                def _lookup(msg):
                    return bus.request("mds.lookup", msg)

                def _on_execute(msg):
                    _lookup(msg)

                bus.subscribe("broker.execute", _guarded(_on_execute))

            def off_thread(self, bus):
                # Not subscribed: requesting from a caller thread is
                # fine (the client API does exactly this).
                return bus.request("broker.execute", {})
        """,
        rules={"request-from-handler"},
    )
    assert len(report.findings) == 1
    f = report.findings[0]
    assert f.symbol == "Broker.serve._lookup"
    assert "_on_execute" in f.message


def test_request_from_handler_uncalled_nested_def_is_clean(tmp_path):
    """A nested def containing a request that the handler merely
    DEFINES (handed to a worker thread, never invoked on the
    dispatcher) is not a dispatcher-thread site; calling it is."""
    report = _lint_src(
        tmp_path, "defer.py",
        """
        import threading

        class Defer:
            def __init__(self, bus):
                self.bus = bus
                bus.subscribe("a", self._on_a)
                bus.subscribe("b", self._on_b)

            def _on_a(self, msg):
                def lookup():
                    return self.bus.request("mds.x", msg)

                threading.Thread(target=lookup).start()  # off-thread

            def _on_b(self, msg):
                def lookup():
                    return self.bus.request("mds.y", msg)

                return lookup()  # ON the dispatcher thread
        """,
        rules={"request-from-handler"},
    )
    syms = [f.symbol for f in report.findings]
    assert syms == ["Defer._on_b.lookup"], \
        "\n".join(f.render() for f in report.findings)


# -- blocking-call-under-lock: sleep + queue extension ------------------------

def test_blocking_rule_flags_sleep_and_bare_queue_ops(tmp_path):
    report = _lint_src(
        tmp_path, "blk.py",
        """
        import threading
        import time

        class Blk:
            def __init__(self):
                self._lock = threading.Lock()
                self._q = make_queue()

            def bad_sleep(self):
                with self._lock:
                    time.sleep(0.1)

            def bad_get(self):
                with self._lock:
                    return self._q.get()

            def bad_put(self, item):
                with self._lock:
                    self._q.put(item)
        """,
        rules={"blocking-call-under-lock"},
    )
    msgs = sorted(f.message for f in report.findings)
    assert len(msgs) == 3, "\n".join(f.render() for f in report.findings)
    assert any("time.sleep" in m for m in msgs)
    assert any("_q.get() without a timeout" in m for m in msgs)
    assert any("_q.put() without a timeout" in m for m in msgs)


def test_blocking_rule_queue_timeout_forms_are_clean(tmp_path):
    report = _lint_src(
        tmp_path, "blkok.py",
        """
        import threading
        import time

        class BlkOk:
            def __init__(self):
                self._lock = threading.Lock()
                self._q = make_queue()

            def ok(self, item, d):
                with self._lock:
                    a = self._q.get(timeout=1.0)   # bounded wait
                    b = self._q.get_nowait()       # non-blocking
                    c = self._q.put(item, block=False)
                    f = self._q.put(item, False)   # positional block
                    g = self._q.put(item, True, 5) # positional timeout
                    e = d.get("key")               # dict.get: not a queue
                    return a, b, c, e, f, g

            def unlocked(self):
                time.sleep(0.1)        # no lock held
                return self._q.get()   # no lock held
        """,
        rules={"blocking-call-under-lock"},
    )
    assert report.findings == [], \
        "\n".join(f.render() for f in report.findings)


# -- suppression + baseline machinery ----------------------------------------

def test_inline_suppression(tmp_path):
    report = _lint_src(
        tmp_path, "sup.py",
        """
        def setup(reg):
            reg.counter("Bad-One", "x")  # pxlint: disable=metrics-naming
            # pxlint: disable=metrics-naming
            reg.counter("Bad-Two", "x")
            reg.counter("Bad-Three", "x")
        """,
        rules={"metrics-naming"},
    )
    assert len(report.findings) == 1
    assert "Bad-Three" in report.findings[0].message
    assert report.suppressed == 2


def test_baseline_roundtrip(tmp_path):
    src = """
        def setup(reg):
            reg.counter("Legacy-Metric", "grandfathered")
    """
    p = tmp_path / "legacy.py"
    p.write_text(textwrap.dedent(src))
    bl = tmp_path / "baseline.json"
    r1 = run_lint([str(p)], rules={"metrics-naming"},
                  baseline_path=str(bl), repo_root=str(tmp_path))
    assert len(r1.findings) == 1
    save_baseline(r1.findings, str(bl))
    assert len(load_baseline(str(bl))) == 1
    r2 = run_lint([str(p)], rules={"metrics-naming"},
                  baseline_path=str(bl), repo_root=str(tmp_path))
    assert r2.ok and len(r2.baselined) == 1
    # Baseline keys ignore line numbers: shifting the file keeps it.
    p.write_text("\n\n\n" + textwrap.dedent(src))
    r3 = run_lint([str(p)], rules={"metrics-naming"},
                  baseline_path=str(bl), repo_root=str(tmp_path))
    assert r3.ok
    # Occurrence counts are enforced: a SECOND identical violation in
    # the same symbol exceeds the baselined count and fails.
    p.write_text(textwrap.dedent(src)
                 + '    reg.counter("Legacy-Metric", "again")\n')
    r4 = run_lint([str(p)], rules={"metrics-naming"},
                  baseline_path=str(bl), repo_root=str(tmp_path))
    assert len(r4.findings) == 1 and len(r4.baselined) == 1


# -- the shipped tree is green ------------------------------------------------

def test_repo_lints_clean_with_baseline():
    report = run_lint(
        [os.path.join(REPO, "pixie_tpu")], repo_root=REPO,
    )
    assert report.ok, "\n".join(f.render() for f in report.findings)


def test_repo_metrics_naming_has_no_findings_at_all():
    # The migrated metrics lint must hold with NO baseline escape:
    # every statically-registered metric name is convention-clean.
    report = run_lint(
        [os.path.join(REPO, "pixie_tpu")], rules={"metrics-naming"},
        baseline_path=os.devnull, repo_root=REPO,
    )
    assert report.findings == [], \
        "\n".join(f.render() for f in report.findings)
