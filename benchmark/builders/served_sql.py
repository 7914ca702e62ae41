"""The served stack of ``served_http`` over a PEM's ``mysql_events``
table: the program's own seven columns (``ingest/schemas.py``
``MYSQL_EVENTS_RELATION``, upstream's ``kMySQLTable``), made from the
seed and appended through the PEM's ingest path with device residency
on. ``px/sql_stats`` reads it.

The cluster is ``http_full_1chip``'s 32 services. The service of rank r
runs one sysbench ``oltp_read_write`` client at its defaults against a
table of its own, ``sbtest<r>``; a transaction is twenty statements in
sysbench's order (``STATEMENTS``), every literal filled in, as
Stirling's MySQL tracer records a ``COM_QUERY`` and a
``COM_STMT_EXECUTE`` with its parameters. A transaction draws its
service by rank with p(r) proportional to 1 / r^c (YCSB's core zipfian
generator, ``values.skew.constant``), its ids uniformly over the
table's rows. Two of the twenty statements carry a random ``c`` value
and are new strings every time; the others repeat by id. So
``query_str`` is a STRING column whose dictionary holds a quarter as
many strings as the table has rows (``make_data`` counts them), under
290 shapes once the literals are taken out. The dictionary is in
arrival order, as an ingest path meets the strings.

Every request passes the configuration's ``max_output_rows`` (the
answer has a row a shape and second), the number columns of an answer
are handed to the harness as copies and ``build`` tells malloc to keep
its heap, as ``served_conn`` does and for its reasons.
"""

from __future__ import annotations

import os
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from . import served_conn, served_http_skew
from .served_http_skew import _ranks, _zipf_cdf

#: ``mysql_events`` as the program's own schema has it: 56 B a row.
COLUMNS = (
    ("time_", "TIME64NS"), ("upid", "UINT128"), ("req_cmd", "INT64"),
    ("query_str", "STRING"), ("resp_status", "INT64"),
    ("latency_ns", "INT64"), ("service", "STRING"),
)

#: One transaction of sysbench 1.0's ``oltp_read_write`` at its defaults
#: (``point_selects`` 10, the four range selects 1 each,
#: ``index_updates`` 1, ``non_index_updates`` 1, ``delete_inserts`` 1),
#: in ``oltp_common.lua``'s order: (kind, template). ``{t}`` is the
#: table's number, ``{id}`` a row id, ``{a}`` / ``{b}`` a range's ends
#: (``range_size`` rows), ``{k}`` a key, ``{c}`` / ``{pad}`` the random
#: digit groups of ``c_value_template`` / ``pad_value_template``.
POINT = "SELECT c FROM sbtest{t} WHERE id={id}"
STATEMENTS = (
    ("begin", "BEGIN"),
    *((("point", POINT),) * 10),
    ("range", "SELECT c FROM sbtest{t} WHERE id BETWEEN {a} AND {b}"),
    ("sum", "SELECT SUM(k) FROM sbtest{t} WHERE id BETWEEN {a} AND {b}"),
    ("order", "SELECT c FROM sbtest{t} WHERE id BETWEEN {a} AND {b} "
              "ORDER BY c"),
    ("distinct", "SELECT DISTINCT c FROM sbtest{t} WHERE id BETWEEN {a} "
                 "AND {b} ORDER BY c"),
    ("index_update", "UPDATE sbtest{t} SET k=k+1 WHERE id={id}"),
    ("non_index_update", "UPDATE sbtest{t} SET c='{c}' WHERE id={id}"),
    ("delete", "DELETE FROM sbtest{t} WHERE id={id}"),
    ("insert", "INSERT INTO sbtest{t} (id, k, c, pad) VALUES "
               "({id}, {k}, '{c}', '{pad}')"),
    ("commit", "COMMIT"),
)
#: Statements whose text is new every time (a random ``c``).
FRESH = ("non_index_update", "insert")
#: Digit groups of sysbench's ``c`` (119 characters) and ``pad`` (59).
C_GROUPS, PAD_GROUPS, GROUP_DIGITS = 10, 5, 11

#: Transactions a stream of the seed: chunk k of the table is drawn from
#: child k + 1 of ``SeedSequence(seed)`` (child 0 draws the permutation),
#: so the data is the seed's whatever the number of threads that draw it.
CHUNK_TX = 1 << 17


def shapes(cfg: dict) -> list:
    """The query shapes of the deployment, with ``?`` for every literal:
    what ``NormalizeMySQLUDF``'s rule makes of ``STATEMENTS`` (written
    out by hand here, statement for statement: the tests hold the
    program's normaliser and the reference's scanner to it)."""
    marks = {"id": "?", "a": "?", "b": "?", "k": "?"}
    out = []
    for _kind, text in dict(STATEMENTS).items():
        text = text.replace("k=k+1", "k=k+?")
        text = text.replace("'{c}'", "?").replace("'{pad}'", "?")
        if "{t}" not in text:
            out.append(text)
            continue
        for t in range(1, cfg["values"]["services"] + 1):
            out.append(text.format(t=t, **marks))
    return out


def _has_a_memo_of_dictionary_udfs() -> bool:
    from pixie_tpu.types.strings import StringDictionary

    return hasattr(StringDictionary, "image")


def _sizes_an_aggregate_on_computed_keys() -> bool:
    from pixie_tpu.exec import stream

    return hasattr(stream, "_computed_group_keys")


#: What this configuration's ``requires`` may name, and how it is looked
#: for: ``served_http_skew``'s, the joint-key sketch for a chain whose
#: group keys are computed, and the memo of a dictionary-side UDF's
#: image with the remap as a program operand.
CAPABILITIES = {
    **served_http_skew.CAPABILITIES,
    "computed_key_sizing": _sizes_an_aggregate_on_computed_keys,
    "dictionary_udf_memo": _has_a_memo_of_dictionary_udfs,
}


def require_capabilities(cfg: dict) -> None:
    """Exit at once, with the configuration's own reason, on a program
    that lacks something ``cfg["requires"]`` names: before a row is
    made."""
    for name, why in cfg.get("requires", {}).items():
        if not CAPABILITIES[name]():
            raise SystemExit(f"{cfg['name']}: the program lacks {name}: {why}")


def _digit_groups(rng, rows: int, groups: int) -> list:
    """``rows`` strings of ``groups`` groups of eleven random digits,
    joined by '-' (sysbench's ``#`` templates)."""
    g = rng.integers(0, 10 ** GROUP_DIGITS, (rows, groups))
    fmt = "-".join(["%011d"] * groups)
    return [fmt % tuple(r) for r in g.tolist()]


def make_data(cfg: dict, seed: int, rows: int) -> dict:
    """``rows`` statements at all seven columns, every value from
    ``seed``. Rows [20 i, 20 i + 20) are transaction i's statements in
    order (the last transaction is cut where the rows end). Times are
    evenly spaced over ``span_s`` and end at ``t_end_ns``, so a range of
    the last r seconds holds the same rows whatever the seed. Chunks of
    ``CHUNK_TX`` transactions are drawn side by side, chunk k from child
    k + 1 of ``SeedSequence(seed)``, as ``served_http_skew`` draws.

    ``query_str`` comes as codes into ``names["query_str"]``, the
    distinct statements in the order the rows first use them."""
    require_capabilities(cfg)
    dist = cfg["values"]
    if dist["skew"]["distribution"] != "zipfian":
        raise ValueError(f"skew {dist['skew']!r}")
    n_svc, size = dist["services"], dist["table_size"]
    span = dist["range_size"] - 1
    per_tx = len(STATEMENTS)
    kinds = [k for k, _t in STATEMENTS]
    text = dict(STATEMENTS)
    n_tx = -(-rows // per_tx)
    step = cfg["span_s"] * 1_000_000_000 // rows
    offsets = range(0, n_tx, CHUNK_TX)
    head, *streams = np.random.SeedSequence(seed).spawn(len(offsets) + 1)
    svc_of_rank = np.random.default_rng(head).permutation(n_svc).astype(
        np.int32)
    svc_cdf = _zipf_cdf(n_svc, dist["skew"]["constant"])
    lat_lo, lat_hi = (np.log(v) for v in dist["latency_ns_loguniform"])

    svc = np.empty(n_tx, np.int32)
    ids = np.empty((n_tx, per_tx), np.int32)  # the statement's id or a
    lat = np.empty((n_tx, per_tx), np.int64)
    # The statements that are new every time, a transaction: their text.
    fresh = {k: [None] * len(offsets) for k in FRESH}

    def draw(j: int, off: int, stream) -> None:
        s = slice(off, min(off + CHUNK_TX, n_tx))
        n = s.stop - s.start
        rng = np.random.default_rng(stream)
        svc[s] = svc_of_rank[_ranks(rng, svc_cdf, n)]
        ids[s] = rng.integers(1, size + 1, (n, per_tx))
        # sysbench deletes a row and inserts one of the same id.
        ids[s, kinds.index("insert")] = ids[s, kinds.index("delete")]
        lat[s] = np.exp(rng.uniform(lat_lo, lat_hi, (n, per_tx)))
        t = (svc[s] + 1).tolist()
        upd = ids[s, kinds.index("non_index_update")].tolist()
        ins = ids[s, kinds.index("insert")].tolist()
        ks = rng.integers(1, size + 1, n).tolist()
        c1 = _digit_groups(rng, n, C_GROUPS)
        c2 = _digit_groups(rng, n, C_GROUPS)
        pad = _digit_groups(rng, n, PAD_GROUPS)
        fresh["non_index_update"][j] = [
            f"UPDATE sbtest{a} SET c='{c}' WHERE id={i}"
            for a, c, i in zip(t, c1, upd)
        ]
        fresh["insert"][j] = [
            f"INSERT INTO sbtest{a} (id, k, c, pad) VALUES "
            f"({i}, {k}, '{c}', '{p}')"
            for a, i, k, c, p in zip(t, ins, ks, c2, pad)
        ]

    with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
        list(pool.map(draw, range(len(offsets)), offsets, streams))

    # A statement's identity as one integer: a repeating one is its
    # (kind, table, id); a fresh one its own row. In arrival order they
    # become the dictionary's codes.
    kind_no = {k: i for i, k in enumerate(dict(STATEMENTS))}
    col_kind = np.asarray([kind_no[k] for k in kinds], np.int64)
    ident = (col_kind[None, :] * n_svc + svc[:, None]) * (size + 1) + ids
    for k in ("begin", "commit"):
        ident[:, kinds.index(k)] = kind_no[k] * n_svc * (size + 1)
    base = len(kind_no) * n_svc * (size + 1)
    row_no = np.arange(n_tx * per_tx, dtype=np.int64).reshape(n_tx, per_tx)
    for k in FRESH:
        c = kinds.index(k)
        ident[:, c] = base + row_no[:, c]
    ident = ident.reshape(-1)[:rows]
    uniq, first, inverse = np.unique(ident, return_index=True,
                                     return_inverse=True)
    arrival = np.argsort(first, kind="stable")
    code_of_uniq = np.empty(len(uniq), np.int32)
    code_of_uniq[arrival] = np.arange(len(uniq), dtype=np.int32)
    codes = code_of_uniq[inverse]

    # The text of each distinct statement, from the row that first uses it.
    rows_first = first[arrival]
    tx, col = rows_first // per_tx, rows_first % per_tx
    t_of, id_of = (svc[tx] + 1).tolist(), ids[tx, col].tolist()
    fresh_flat = {k: [s for chunk in v for s in chunk]
                  for k, v in fresh.items()}
    names = []
    for x, c, t, i in zip(tx.tolist(), col.tolist(), t_of, id_of):
        kind = kinds[c]
        if kind in fresh_flat:
            names.append(fresh_flat[kind][x])
        elif kind in ("begin", "commit"):
            names.append(text[kind])
        else:
            names.append(text[kind].format(t=t, id=i, a=i, b=i + span))
    service = np.repeat(svc, per_tx)[:rows]
    return {
        "time_": cfg["t_end_ns"] - step * np.arange(rows - 1, -1, -1,
                                                    dtype=np.int64),
        "upid": (np.ones(rows, np.uint64), service.astype(np.uint64)),
        "req_cmd": np.full(rows, dist["req_cmd"], np.int64),
        "query_str": codes,
        "resp_status": np.full(rows, dist["resp_status"], np.int64),
        "latency_ns": lat.reshape(-1)[:rows].copy(),
        "service": service,
        "names": {
            "query_str": names,
            "service": [f"svc-{i}" for i in range(n_svc)],
        },
    }


def batches(data: dict, window_rows: int, lo: int = 0, hi: int | None = None):
    """``data``'s rows [lo, hi) as the ingest path takes them: one
    ``HostBatch`` a window, every batch over the same dictionaries."""
    from pixie_tpu.types.batch import HostBatch
    from pixie_tpu.types.dtypes import DataType
    from pixie_tpu.types.relation import Relation
    from pixie_tpu.types.strings import StringDictionary

    rel = Relation([(c, DataType[t]) for c, t in COLUMNS])
    dicts = {c: StringDictionary(v) for c, v in data["names"].items()}
    hi = len(data["time_"]) if hi is None else hi
    for off in range(lo, hi, window_rows):
        s = slice(off, min(off + window_rows, hi))
        yield HostBatch(
            relation=rel, length=s.stop - s.start, dicts=dicts,
            cols={c: tuple(p[s] for p in (
                data[c] if isinstance(data[c], tuple) else (data[c],)
            )) for c in rel.column_names},
        )


class SqlStack(served_conn.ConnStack):
    """``ConnStack`` (every request asks for all of its rows and gets
    copies of its number columns; the ingest waits for the schema) whose
    table is ``mysql_events``."""

    def ingest(self, data: dict) -> None:
        t0 = time.perf_counter()
        for batch in batches(data, self.window_rows):
            self.pem.append_data(self.table, batch)
        self.ingest_s = time.perf_counter() - t0
        self.rows = len(data["time_"])
        self.pem._register()  # the tracker learns the post-ingest schema
        deadline = time.monotonic() + 30
        while self.table not in self.tracker.schemas():
            if time.monotonic() > deadline:
                raise RuntimeError("the PEM's schema never reached the tracker")
            time.sleep(0.01)


def build(cfg: dict, window_rows: int) -> SqlStack:
    served_conn.keep_the_heap()
    return SqlStack(cfg, window_rows)
