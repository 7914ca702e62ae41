"""Four nodes behind one broker, as the deployment answers them: a PEM a
node (upstream's DaemonSet), each an ``Engine`` on a chip of its own
holding its node's ``http_events``, one Kelvin, one ``QueryBroker``,
``AgentTracker`` and in-process ``MessageBus``. Every script fans out to
the four PEMs and the Kelvin merges their four states.

The cluster's rows are ``served_http_edges.make_data``'s (one stream,
the cluster-wide ``names``: what the plain references answer over), parted
by node: a service's pods are spread round robin over the nodes in the
order of their load, each service starting a little further round than
the one before it (``node_of_pod``; ``load_ranks`` replays the law's
rank -> code permutations), and a row belongs to the node of its ``pod``,
since upstream traces a request on the node that served it. So a node runs
32 of every service's 128 pods, the nodes hold unequal shares (26.0 / 26.2
/ 24.6 / 23.1 % of the rows) that are the law's whatever the seed, and
which pods (codes, names, addresses) a node runs is the seed's.

**Each node's dictionaries are its own.** A PEM interns a string when it
first sees it, so node n's dictionary of a STRING column holds only the
strings of node n's rows, in the order of their first appearance there,
and its rows carry ids of THAT dictionary (``part_by_node``). No two PEMs
are handed one dictionary object: the Kelvin unites four dictionaries a
string key column and remaps every payload's key planes.

``python3 -m benchmark.builders.served_http_nodes --seeds 1,2,3`` counts
what the configuration's file states: the nodes' shares by the law and as
drawn, the budget the fullest node asks for, the live edges and the
(service, req_path) groups of the traffic's range, by node and over the
cluster.
"""

from __future__ import annotations

import functools
import inspect
import math
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from . import served_conn, served_http_edges, served_http_skew
from .served_http_skew import _zipf_cdf

COLUMNS = served_http_skew.COLUMNS
STRING_COLUMNS = tuple(c for c, t in COLUMNS if t == "STRING")


def _an_engine_lives_on_a_named_device() -> bool:
    from pixie_tpu.exec.engine import Engine

    return "device" in inspect.signature(Engine.__init__).parameters


#: What this configuration's ``requires`` may name, and how it is looked
#: for: ``served_http_edges``'s, and an engine that can be told its device.
CAPABILITIES = {
    **served_http_edges.CAPABILITIES,
    "engine_device": _an_engine_lives_on_a_named_device,
}


def require_capabilities(cfg: dict) -> None:
    """Exit at once, with the configuration's own reason, on a program
    that lacks something ``cfg["requires"]`` names (as
    ``served_http_skew.require_capabilities``, over this module's
    ``CAPABILITIES``): before a row is made."""
    for name, why in cfg.get("requires", {}).items():
        if not CAPABILITIES[name]():
            raise SystemExit(f"{cfg['name']}: the program lacks {name}: {why}")


def load_ranks(cfg: dict, seed: int) -> tuple:
    """(rank of a service by load [services], rank of a pod by load among
    its service's pods [services * pods]; 0 the hottest), as
    ``served_http_skew.make_data`` maps ranks to codes: child 0 of
    ``SeedSequence(seed)`` draws the permutation over the services, then a
    service's over its paths, then over its pods."""
    dist = cfg["values"]
    n_svc, n_pods = dist["services"], dist["pods"]
    rng = np.random.default_rng(np.random.SeedSequence(seed).spawn(1)[0])
    svc_of_rank = rng.permutation(n_svc)
    rng.permuted(np.tile(np.arange(dist["paths_per_service"], dtype=np.int32),
                         (n_svc, 1)), axis=1)
    pod_of_rank = rng.permuted(
        np.tile(np.arange(n_pods, dtype=np.int32), (n_svc, 1)), axis=1
    )
    svc_rank = np.empty(n_svc, np.int32)
    svc_rank[svc_of_rank] = np.arange(n_svc, dtype=np.int32)
    pod_rank = np.empty((n_svc, n_pods), np.int32)
    np.put_along_axis(
        pod_rank, pod_of_rank,
        np.broadcast_to(np.arange(n_pods, dtype=np.int32), pod_rank.shape),
        axis=1,
    )
    return svc_rank, pod_rank.reshape(-1)  # pod code = service * pods + j


def first_node(svc_rank, nodes: int):
    """The node a service's hottest pod runs on: the scheduler starts the
    hottest service at node 0 and every pair of services after it one node
    further, so that the services' hottest pods do not pile on one node."""
    return ((svc_rank + 1) // 2) % nodes


def node_of_pod(cfg: dict, seed: int) -> np.ndarray:
    """node[p]: the node pod p runs on. A service's pods are spread round
    robin over the nodes in the order of their load (rank r on node
    ``first_node`` + r mod ``nodes``): 32 of its 128 pods a node."""
    svc_rank, pod_rank = load_ranks(cfg, seed)
    first = np.repeat(first_node(svc_rank, cfg["nodes"]),
                      cfg["values"]["pods"])
    return (first + pod_rank) % cfg["nodes"]


def part_by_node(data: dict, node_of_pod: np.ndarray, nodes: int) -> list:
    """``data``'s rows by the node of their ``pod`` (``node_of_pod``: a
    node a pod code), each node's in their order in the stream: a list of
    ``data``-shaped dicts whose STRING columns carry ids of the node's OWN
    dictionary (its strings in the order of their first appearance
    there), beside that dictionary's strings under ``names``."""
    node = node_of_pod[data["pod"]]

    def part(n: int) -> dict:
        idx = np.flatnonzero(node == n)
        out, names = {}, {}
        for col, _type in COLUMNS:
            planes = data[col]
            if isinstance(planes, tuple):
                out[col] = tuple(p[idx] for p in planes)
                continue
            out[col] = planes[idx]
            if col in STRING_COLUMNS:
                seen, first, code = np.unique(
                    out[col], return_index=True, return_inverse=True
                )
                order = np.argsort(first)  # by first appearance
                rank = np.empty(len(seen), np.int32)
                rank[order] = np.arange(len(seen), dtype=np.int32)
                out[col] = rank[code]
                strings = data["names"][col]
                names[col] = [strings[i] for i in seen[order].tolist()]
        out["names"] = names
        return out

    with ThreadPoolExecutor(nodes) as pool:
        return list(pool.map(part, range(nodes)))


def make_data(cfg: dict, seed: int, rows: int) -> dict:
    """The cluster's one stream (``served_http_edges.make_data``: the
    union, with the cluster-wide ``names``, which the references answer
    over as they stand) and, under ``parts``, its rows by node."""
    require_capabilities(cfg)
    data = served_http_edges.make_data({**cfg, "requires": {}}, seed, rows)
    data["parts"] = part_by_node(data, node_of_pod(cfg, seed), cfg["nodes"])
    return data


def node_shares(cfg: dict) -> list:
    """The share of the cluster's rows each node holds by the law: the
    services' shares (Zipf over their ranks) times their pods' (Zipf over
    a service's ranks), summed by ``node_of_pod``'s rule."""
    dist = cfg["values"]
    nodes, c = cfg["nodes"], dist["skew"]["constant"]
    p_svc = np.diff(_zipf_cdf(dist["services"], c), prepend=0.0)
    p_pod = np.diff(_zipf_cdf(dist["pods"], c), prepend=0.0)
    node = (first_node(np.arange(dist["services"]), nodes)[:, None]
            + np.arange(dist["pods"])[None, :]) % nodes
    weight = p_svc[:, None] * p_pod[None, :]
    return [float(weight[node == n].sum()) for n in range(nodes)]


#: Room over the law's share for the draw: a node's rows are a sum of
#: multinomial counts (some 3 k rows of standard deviation on the fullest
#: node's 8.3 M); 1 % is 28 of them.
SHARE_HEADROOM = 1.01


def least_data_limit_mb(cfg: dict) -> int:
    """The least ``table_store_data_limit_mb`` whose 40 % share for
    ``http_events`` holds the fullest node's rows: its share by the law,
    with ``SHARE_HEADROOM`` for the draw."""
    rows = math.ceil(max(node_shares(cfg)) * SHARE_HEADROOM * cfg["rows"])
    return math.ceil(rows * cfg["bytes_per_row"] / 0.4 / (1 << 20))


class NodesStack:
    """What a traffic driver needs of the deployment: ``execute`` and
    the tracers the per-layer readers listen to. ``pem`` is node 0's
    (the readers that list no cell read node 0's fragment here);
    ``pem.1`` .. ``pem.3`` are the other nodes', for the readers that
    know of them."""

    def __init__(self, cfg: dict, window_rows: int):
        import contextlib

        import jax

        from pixie_tpu.config import override_flag
        from pixie_tpu.exec.engine import Engine
        from pixie_tpu.services import (
            AgentTracker, KelvinAgent, MessageBus, PEMAgent, QueryBroker,
        )
        from pixie_tpu.services.load_tester import broker_executor

        if cfg["engine"] != "Engine":
            raise ValueError(f"engine {cfg['engine']!r}")
        self.cfg = cfg
        self.window_rows = window_rows
        self.table = cfg["table"]
        devices = jax.devices()[:cfg["nodes"]]
        if len(devices) < cfg["nodes"]:
            raise RuntimeError(
                f"{cfg['nodes']} nodes need a device each, have {len(devices)}"
            )
        # Deployment settings the configuration states (``flags``), for
        # the life of the stack; every other flag stays at its default.
        self._flags = contextlib.ExitStack()
        for name, value in cfg["flags"].items():
            self._flags.enter_context(override_flag(name, value))
        self.bus = MessageBus()
        self.tracker = AgentTracker(self.bus)
        self.pems = [
            PEMAgent(self.bus, f"pem-{n}", engine=Engine(
                window_rows=window_rows, device=device)).start()
            for n, device in enumerate(devices)
        ]
        # Upstream's Kelvin is a pod on one of the nodes: node 0's chip.
        self.kelvin = KelvinAgent(
            self.bus, "kelvin-0", engine=Engine(device=devices[0])
        ).start()
        self.broker = QueryBroker(self.bus, self.tracker)
        self._execute = functools.partial(
            broker_executor(self.broker),
            max_output_rows=cfg["max_output_rows"],
        )
        self.ingest_s = 0.0
        self.rows = 0

    @property
    def tracers(self) -> dict:
        out = {"broker": self.broker.tracer,
               "kelvin": self.kelvin.engine.tracer,
               "pem": self.pems[0].engine.tracer}
        for n, pem in enumerate(self.pems[1:], 1):
            out[f"pem.{n}"] = pem.engine.tracer
        return out

    def ingest(self, data: dict) -> None:
        """Append every node's rows to its PEM, a window at a time, the
        nodes side by side, and wait until the tracker has the table from
        every PEM: the broker plans the fan-out against it."""
        def append(pem, part) -> None:
            for batch in served_http_skew.batches(part, self.window_rows):
                pem.append_data(self.table, batch)

        t0 = time.perf_counter()
        with ThreadPoolExecutor(len(self.pems)) as pool:
            list(pool.map(append, self.pems, data["parts"]))
        self.ingest_s = time.perf_counter() - t0
        self.rows = len(data["time_"])
        for pem in self.pems:
            pem._register()  # the tracker learns the post-ingest schema
        want = {pem.agent_id for pem in self.pems}
        deadline = time.monotonic() + 30
        while True:
            have = {a.agent_id for a in self.tracker.distributed_state().pems
                    if a.tables is not None and self.table in a.tables}
            if want <= have:
                return
            if time.monotonic() > deadline:
                raise RuntimeError(
                    f"the tracker has {self.table} from {sorted(have)} only")
            time.sleep(0.01)

    def resident(self) -> dict:
        """Rows and bytes of the four tables in device memory, and the
        distinct devices holding them, by walking the windows a query
        would scan; ``by_node`` is each PEM's rows and its devices."""
        rows = nbytes = 0
        devices, by_node = set(), []
        for pem in self.pems:
            table = pem.engine.tables[self.table]
            node_rows, node_devices = 0, set()
            for win, _lo, _hi in table.device_scan(
                None, None, window_rows=self.window_rows
            ):
                node_rows += win.n
                nbytes += win.nbytes
                for planes in win.cols.values():
                    for p in planes:
                        node_devices |= {
                            sh.device for sh in p.addressable_shards}
            rows += node_rows
            devices |= node_devices
            by_node.append(
                {"rows": node_rows, "devices": sorted(
                    d.id for d in node_devices)})
        return {"rows": rows, "bytes": nbytes, "devices": len(devices),
                "by_node": by_node}

    def execute(self, pxl: str, timeout_s: float, now_ns: int) -> dict:
        """One script through ``QueryBroker.execute_script``; the rows
        decoded, as a client has them in hand, the number columns as the
        client's own copies (``served_conn.ConnStack.execute``, for its
        reason). An answer that any agent missed is ``partial``."""
        res = self._execute(pxl, timeout_s, now_ns=now_ns)
        rows = res["tables"]["output"].to_pydict()
        return {"qid": res.get("qid"), "partial": bool(res.get("partial")),
                "rows": {c: v if v.dtype == object else v.copy()
                         for c, v in rows.items()}}

    def close(self) -> None:
        for pem in self.pems:
            pem.stop()
        self.kelvin.stop()
        self.tracker.close()
        self.bus.close()
        self._flags.close()


def build(cfg: dict, window_rows: int) -> NodesStack:
    served_conn.keep_the_heap()
    return NodesStack(cfg, window_rows)


def count_cluster(cfg: dict, traffic: dict, seed: int) -> dict:
    """What the configuration's file states of one seed's data: the
    nodes' rows, the rows, live edges and (service, req_path) groups with
    resp_status < 400 of the traffic's range a node and over the cluster,
    and the strings a node's dictionaries hold."""
    data = make_data({**cfg, "requires": {}}, seed, cfg["rows"])
    lo_ns = cfg[traffic["now"]] - traffic["range_s"] * 1_000_000_000
    n_paths = len(data["names"]["req_path"])

    def counts(d: dict) -> dict:
        keep = d["time_"] >= lo_ns
        edge = (d["remote_addr"][keep].astype(np.int64) << 32) | d["pod"][keep]
        ok = keep & (d["resp_status"] < 400)
        group = d["service"][ok].astype(np.int64) * n_paths + d["req_path"][ok]
        return {"rows": len(d["time_"]), "rows_in_range": int(keep.sum()),
                "live_edges": len(np.unique(edge)),
                "http_stats_groups": len(np.unique(group))}

    nodes = [counts(p) for p in data["parts"]]
    for node, part in zip(nodes, data["parts"]):
        node["strings"] = {c: len(v) for c, v in part["names"].items()}
    whole = counts(data)
    return {
        "seed": seed, **whole,
        "node_rows": [n["rows"] for n in nodes],
        "node_share": [round(n["rows"] / whole["rows"], 4) for n in nodes],
        "node_rows_in_range": [n["rows_in_range"] for n in nodes],
        "node_live_edges": [n["live_edges"] for n in nodes],
        "node_http_stats_groups": [n["http_stats_groups"] for n in nodes],
        "node_strings": [n["strings"] for n in nodes],
    }


def main(argv=None) -> int:
    import argparse
    import json

    ap = argparse.ArgumentParser(description=count_cluster.__doc__)
    ap.add_argument("--workload",
                    default="http_cluster_4chip.cluster_recent")
    ap.add_argument("--seeds", default="1,2,3")
    args = ap.parse_args(argv)
    from benchmark import harness

    spec = harness.load_cell(args.workload)
    cfg = spec["config"]
    print(json.dumps({
        "node_shares_by_the_law": node_shares(cfg),
        "least_table_store_data_limit_mb": least_data_limit_mb(cfg),
    }), flush=True)
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(count_cluster(cfg, spec["traffic"], seed)),
              flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
