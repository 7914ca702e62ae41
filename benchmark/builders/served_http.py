"""The served stack over one ``http_events`` table: broker, PEM, Kelvin
and tracker on an in-process bus, the table made from the seed and
appended through the PEM's ingest path with device residency on.

Copied in shape from ``chip_smoke.py``'s ``Replay`` and ``phase_served``
(PR 22), with real event times in ``time_`` where the smoke had row ids,
and all ten columns of the table where it had five.
"""

from __future__ import annotations

import contextlib
import time

import numpy as np


#: ``http_events`` as the program's own schema has it
#: (``ingest/replay.py`` ``HTTP_EVENTS_RELATION``): 68 B a row.
COLUMNS = (
    ("time_", "TIME64NS"), ("upid", "UINT128"), ("remote_addr", "STRING"),
    ("req_method", "STRING"), ("req_path", "STRING"),
    ("resp_status", "INT64"), ("resp_body_size", "INT64"),
    ("latency_ns", "INT64"), ("service", "STRING"), ("pod", "STRING"),
)


def make_data(cfg: dict, seed: int, rows: int) -> dict:
    """``rows`` events at all ten columns, every value from ``seed``,
    drawn as ``ingest/replay.py`` ``gen_http_events`` draws them (the
    repo's stand-in for upstream's protocol loadtest), strings as
    dictionary codes beside their vocabularies. Times are evenly spaced
    over ``span_s`` and end at ``t_end_ns``, so a range of the last r
    seconds holds rows * r / span_s rows whatever the seed."""
    rng = np.random.default_rng(seed)
    dist = cfg["values"]
    step = cfg["span_s"] * 1_000_000_000 // rows
    n_svc, n_pods, n_paths = dist["services"], dist["pods"], dist["paths"]
    methods = sorted(set(dist["methods"]))
    method_code = np.asarray([methods.index(m) for m in dist["methods"]],
                             np.int32)
    statuses = np.repeat(
        np.asarray([s for s, _n in dist["statuses"]], np.int64),
        [n for _s, n in dist["statuses"]],
    )
    mu, sigma = dist["latency_ns_lognormal"]
    lo, hi = dist["resp_body_size"]
    svc = rng.integers(0, n_svc, rows).astype(np.int32)
    return {
        "time_": cfg["t_end_ns"] - step * np.arange(rows - 1, -1, -1,
                                                    dtype=np.int64),
        "upid": (rng.integers(1, 1 << 30, rows).astype(np.uint64),
                 rng.integers(1, 1 << 62, rows).astype(np.uint64)),
        "remote_addr": svc,  # one address a service, as the replay has it
        "req_method": method_code[rng.integers(0, len(method_code), rows)],
        "req_path": rng.integers(0, n_paths, rows).astype(np.int32),
        "resp_status": statuses[rng.integers(0, len(statuses), rows)],
        "resp_body_size": rng.integers(lo, hi, rows),
        "latency_ns": np.exp(rng.normal(mu, sigma, rows)).astype(np.int64),
        "service": svc,
        "pod": svc * n_pods + rng.integers(0, n_pods, rows).astype(np.int32),
        "names": {
            "remote_addr": [f"10.0.{i % 256}.{i % 251}" for i in range(n_svc)],
            "req_method": methods,
            "req_path": [f"/api/v1/ep{i}" for i in range(n_paths)],
            "service": [f"svc-{i}" for i in range(n_svc)],
            "pod": [f"svc-{i}/pod-{j}" for i in range(n_svc)
                    for j in range(n_pods)],
        },
    }


def _planes(col) -> tuple:
    return col if isinstance(col, tuple) else (col,)


class Stack:
    """What a traffic driver needs of the deployment: ``execute`` and
    the tracers the per-layer readers listen to."""

    def __init__(self, cfg: dict, window_rows: int):
        import jax

        from pixie_tpu.exec.engine import Engine
        from pixie_tpu.services import (
            AgentTracker, KelvinAgent, MessageBus, PEMAgent, QueryBroker,
        )
        from pixie_tpu.services.load_tester import broker_executor

        from pixie_tpu.config import override_flag

        self.cfg = cfg
        self.window_rows = window_rows
        self.table = cfg["table"]
        # Deployment settings the configuration states (``flags``), for
        # the life of the stack; every other flag stays at its default.
        self._flags = contextlib.ExitStack()
        for name, value in cfg["flags"].items():
            self._flags.enter_context(override_flag(name, value))
        if cfg["engine"] == "DistributedEngine":
            from pixie_tpu.parallel.executor import DistributedEngine
            from pixie_tpu.parallel.mesh import agent_mesh

            engine = DistributedEngine(
                window_rows=window_rows,
                mesh=agent_mesh(cfg["chips"],
                                devices=jax.devices()[:cfg["chips"]]),
            )
        elif cfg["engine"] == "Engine":
            engine = Engine(window_rows=window_rows)
        else:
            raise ValueError(f"engine {cfg['engine']!r}")
        self.bus = MessageBus()
        self.tracker = AgentTracker(self.bus)
        self.pem = PEMAgent(self.bus, "pem-0", engine=engine).start()
        self.kelvin = KelvinAgent(self.bus, "kelvin-0").start()
        self.broker = QueryBroker(self.bus, self.tracker)
        self._execute = broker_executor(self.broker)
        self.ingest_s = 0.0
        self.rows = 0

    @property
    def tracers(self) -> dict:
        return {"broker": self.broker.tracer,
                "pem": self.pem.engine.tracer,
                "kelvin": self.kelvin.engine.tracer}

    def ingest(self, data: dict) -> None:
        """Append every row, a window at a time, and wait until the
        tracker has the table's schema: the broker plans against it."""
        from pixie_tpu.types.batch import HostBatch
        from pixie_tpu.types.dtypes import DataType
        from pixie_tpu.types.relation import Relation
        from pixie_tpu.types.strings import StringDictionary

        rel = Relation([(c, DataType[t]) for c, t in COLUMNS])
        dicts = {c: StringDictionary(names)
                 for c, names in data["names"].items()}
        rows = len(data["time_"])
        t0 = time.perf_counter()
        for off in range(0, rows, self.window_rows):
            s = slice(off, min(off + self.window_rows, rows))
            self.pem.append_data(self.table, HostBatch(
                relation=rel,
                cols={c: tuple(p[s] for p in _planes(data[c]))
                      for c in rel.column_names},
                length=s.stop - s.start, dicts=dicts,
            ))
        self.ingest_s = time.perf_counter() - t0
        self.rows = rows
        self.pem._register()  # the tracker learns the post-ingest schema
        deadline = time.monotonic() + 30
        while self.table not in self.tracker.schemas():
            if time.monotonic() > deadline:
                raise RuntimeError("the PEM's schema never reached the tracker")
            time.sleep(0.01)

    def resident(self) -> dict:
        """Rows and bytes of the table in device memory, and the devices
        holding them, by walking the windows a query would scan."""
        table = self.pem.engine.tables[self.table]
        rows = nbytes = 0
        devices = set()
        for win, _lo, _hi in table.device_scan(
            None, None, window_rows=self.window_rows
        ):
            rows += win.n
            nbytes += win.nbytes
            for planes in win.cols.values():
                for p in planes:
                    devices |= {sh.device for sh in p.addressable_shards}
        return {"rows": rows, "bytes": nbytes, "devices": len(devices)}

    def execute(self, pxl: str, timeout_s: float, now_ns: int) -> dict:
        """One script through ``QueryBroker.execute_script``; the rows
        decoded, as a client has them in hand."""
        res = self._execute(pxl, timeout_s, now_ns=now_ns)
        return {"qid": res.get("qid"), "partial": bool(res.get("partial")),
                "rows": res["tables"]["output"].to_pydict()}

    def close(self) -> None:
        self.pem.stop()
        self.kelvin.stop()
        self.tracker.close()
        self.bus.close()
        self._flags.close()


def build(cfg: dict, window_rows: int) -> Stack:
    return Stack(cfg, window_rows)
