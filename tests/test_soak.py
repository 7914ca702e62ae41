"""Opt-in cluster soak: operator-managed roles under sustained load
with a mid-run role kill.

Run with ``PIXIE_TPU_SOAK=1 ./run_tests.sh tests/test_soak.py -s``
(~2 min). Skipped by default to keep the suite fast. This is the
system-level complement to test_stress (in-process races) and
test_operator (reconciler mechanics): a real broker/PEM/Kelvin process
tree, queried continuously over the netbus while a PEM is SIGKILLed,
must recover through the operator with zero post-recovery failures.
"""

from __future__ import annotations

import os
import subprocess
import time

import pytest

pytestmark = pytest.mark.skipif(
    not os.environ.get("PIXIE_TPU_SOAK"),
    reason="soak is opt-in: set PIXIE_TPU_SOAK=1",
)

PORT = 6230


def _role_env():
    return (
        ("PIXIE_TPU_NETBUS_PORT", str(PORT)),
        ("PIXIE_TPU_BROKER", f"127.0.0.1:{PORT}"),
        ("PIXIE_TPU_OBS_PORT", "0"),
        ("PIXIE_TPU_SEQGEN", "1"),
        ("JAX_PLATFORMS", "cpu"),
    )


QUERY = (
    "import px\ndf = px.DataFrame(table='sequences')\n"
    "s = df.groupby('modulo10').agg(n=('x', px.count))\npx.display(s)"
)


def test_soak_query_through_role_kill():
    from pixie_tpu.api import Client, ScriptExecutionError
    from pixie_tpu.services.operator import Reconciler, RoleSpec

    specs = {
        r: RoleSpec(name=r, replicas=1, env=_role_env())
        for r in ("broker", "pem", "kelvin")
    }
    rec = Reconciler(specs, base_backoff_s=0.2, max_backoff_s=1.0)
    rec.run_as_thread()
    results = []  # (t, ok, err)
    stream_updates = []

    def one_query():
        try:
            with Client("127.0.0.1", PORT) as c:
                out = c.execute_script(QUERY, timeout_s=15)
            rows = out.get("output", {})
            n = int(sum(rows.get("n", []))) if rows else 0
            return n > 0, None
        except (ScriptExecutionError, ConnectionError, OSError,
                TimeoutError) as e:
            return False, f"{type(e).__name__}: {e}"

    try:
        # Phase 0: wait for first success (roles boot, PEM registers).
        deadline = time.time() + 60
        ok = False
        while time.time() < deadline and not ok:
            ok, _err = one_query()
            if not ok:
                time.sleep(2)
        assert ok, "cluster never served a query"

        # Live stream rides along for the whole soak.
        stream_client = Client("127.0.0.1", PORT)
        sub = stream_client.stream_script(
            QUERY, on_update=stream_updates.append, poll_interval_s=0.5
        )

        kill_at = time.time() + 20
        killed = {"pid": None, "t": None}
        end = time.time() + 90
        while time.time() < end:
            t0 = time.time()
            ok, err = one_query()
            results.append((t0, ok, err))
            if killed["pid"] is None and time.time() >= kill_at:
                (st,) = [
                    s for s in rec.status()
                    if s["role"] == "pem" and s["alive"]
                ]
                subprocess.run(["kill", "-9", str(st["pid"])], check=True)
                killed = {"pid": st["pid"], "t": time.time()}
            time.sleep(2)
        # The killed PEM's restart aborts the stream with a visible
        # error (its new incarnation can't rejoin the old plan); a
        # fresh stream against the new topology must then deliver.
        stream_errs = [u for u in stream_updates if "error" in u]
        assert stream_errs, "data-agent restart never surfaced to stream"
        ups2 = []
        sub2 = stream_client.stream_script(
            QUERY, on_update=ups2.append, poll_interval_s=0.5
        )
        deadline = time.time() + 30
        while len([u for u in ups2 if "rows" in u]) < 2 and \
                time.time() < deadline:
            time.sleep(0.5)
        assert len([u for u in ups2 if "rows" in u]) >= 2
        sub2.cancel()
        sub.cancel()
        stream_client.close()
    finally:
        rec.stop()

    assert killed["pid"] is not None, "never reached the kill phase"
    # Recovery: everything from 30s after the kill must succeed.
    tail = [r for r in results if r[0] > killed["t"] + 30]
    assert tail, "soak too short to observe recovery"
    failures = [r for r in tail if not r[1]]
    assert not failures, f"post-recovery failures: {failures[:3]}"
    # The operator recorded the crash and restarted the role.
    kinds = [e[1] for e in rec.events]
    assert "crashed" in kinds and "restarted" in kinds
    # The live stream delivered before the kill and errored cleanly at
    # the restart (never a silent partial view).
    assert len([u for u in stream_updates if "rows" in u]) >= 3
    # Overall availability: the only tolerated failures sit inside the
    # 30s recovery window.
    pre_kill = [r for r in results if r[0] <= killed["t"]]
    assert all(r[1] for r in pre_kill), [r for r in pre_kill if not r[1]][:3]
