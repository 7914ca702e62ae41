"""Agent-mode bridge payloads + merge: partial-agg state shipping.

Reference parity: GRPCSinkNode/GRPCSourceNode pairs plus the UDA
``Serialize``/``DeSerialize`` contract (``src/carnot/exec/
grpc_sink_node.h:54``, ``udf/udf.h:99-100``). The TPU redesign ships the
fragment's carry pytree itself — the merge tier recompiles the identical
fragment and folds states through its associative merge, instead of
streaming serialized row batches.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from ..types.batch import HostBatch, bucket_capacity
from ..types.dtypes import DataType
from ..types.strings import NULL_ID, StringDictionary
from .fragment import ColumnMeta, compile_fragment_cached as compile_fragment
from .joins import learned_capacity
from .plan import AggOp
from .stream import (
    _device_wait,
    _dispatch,
    _fetch_result,
    _timed,
    _NO_STATS,
    QueryError,
    _agg_capacity_key,
    _double_agg_groups,
    _rebucket,
    _remember_climb,
    _Stream,
    _with_agg_groups,
    _stream_col_stats,
    _to_host_batch,
)


@dataclass
class AggStatePayload:
    """Partial-agg state shipped across a bridge (agent mode).

    The UDA ``Serialize``/``DeSerialize`` analog (``udf.h:99-100``): the
    serialized form IS the carry pytree plus enough metadata for the
    merge tier to recompile the identical fragment and realign string
    dictionary ids. String-valued *carries* (e.g. ``any`` over a string
    column) are not realigned — only group keys are; such UDAs need a
    shared dictionary to cross agents.
    """

    chain: tuple  # fragment ops [pre..., AggOp]
    input_relation: object  # Relation at fragment input
    input_dicts: dict  # {col: StringDictionary} at fragment input
    state: dict  # group-state pytree (numpy leaves)
    # Dense-domain states ship no key planes (slot index IS the packed
    # key); the producing fragment's domains let the merge side expand
    # them back to explicit keys (dictionaries may differ per agent).
    # ``dense_offsets`` shifts stats-derived integer codes back to values;
    # ``dense_strides`` scales step-indexed codes (binned time keys).
    dense_domains: tuple = ()
    dense_offsets: tuple = ()
    dense_strides: tuple = ()


@dataclass
class RowsPayload:
    """Materialized rows shipped across a bridge (plain GRPCSink analog)."""

    batch: HostBatch


@dataclass
class _PendingAggBridge:
    """Agg-bridge payloads awaiting their finalize AggOp."""

    payloads: list  # list[AggStatePayload]


def _expand_dense_payload(p, group_rel, key_plane_index):
    """Expand a dense-domain AggStatePayload to explicit key planes.

    Dense states carry no keys (slot index IS the packed key); the merge
    tier reconstructs them with the same unpack arithmetic the producing
    fragment's finalize uses, so the generic realign/merge path applies.
    """
    import dataclasses

    from .fragment import unpack_dense_slots

    doms = getattr(p, "dense_domains", ())
    if not doms:
        return p
    gd = len(p.state["valid"])
    keys = unpack_dense_slots(
        np.arange(gd, dtype=np.int64),
        doms,
        [group_rel.col_type(c) for c, _i in key_plane_index],
        np,
        offsets=getattr(p, "dense_offsets", ()),
        strides=getattr(p, "dense_strides", ()),
    )
    return dataclasses.replace(
        p, state={**p.state, "keys": tuple(keys)}, dense_domains=(),
        dense_offsets=(), dense_strides=(),
    )


def _compact_payload(p):
    """Shrink an expanded dense-domain payload to its live slots.

    A dense state is domain-sized (up to ``dense_domain_limit`` slots)
    however few groups are live; merging every payload at that capacity
    is a large avoidable cost for small aggregates. Live slots compact to
    the front (padded to a power-of-two bucket with neutral invalid
    slots, so merge-fragment compiles stay shape-bucketed).
    """
    import dataclasses

    import jax

    valid = np.asarray(p.state["valid"])
    g = len(valid)
    live = int(valid.sum())
    cap = bucket_capacity(max(live, 1))
    if cap >= g:
        return p
    idx = np.nonzero(valid)[0]
    if len(idx) < cap:
        # Invalid slots hold uda-neutral carries by construction, so any
        # one of them is safe padding.
        fill = int(np.nonzero(~valid)[0][0])
        idx = np.concatenate(
            [idx, np.full(cap - len(idx), fill, dtype=np.int64)]
        )

    def take(leaf):
        a = np.asarray(leaf)
        return a[idx] if a.ndim and a.shape[0] == g else a

    return dataclasses.replace(p, state={
        "keys": tuple(take(k) for k in p.state["keys"]),
        "valid": valid[idx],
        "carries": jax.tree_util.tree_map(take, p.state["carries"]),
        "overflow": p.state["overflow"],
    })


def payload_nbytes(p) -> int:
    """Approximate wire size of one bridge payload: the plane bytes the
    transport actually moves (metadata/dicts excluded). Host numpy
    arithmetic only — feeds ``QueryResourceUsage.wire_bytes``."""
    if isinstance(p, RowsPayload):
        return p.batch.nbytes
    if isinstance(p, AggStatePayload):
        import jax

        return int(sum(
            np.asarray(leaf).nbytes
            for leaf in jax.tree_util.tree_leaves(p.state)
        ))
    return 0


def bridge_payload(engine, res):
    """Produce a BridgeSink payload: partial-agg state for agg chains,
    materialized rows otherwise (GRPCSinkNode's two modes)."""
    if isinstance(res, _Stream) and any(
        isinstance(o, AggOp) for o in res.chain
    ):
        import jax

        # The agent-mode agg fold records onto the query's trace spine
        # like any other fragment (rows/windows/stage/compute feed the
        # per-agent QueryResourceUsage attribution).
        qstats = getattr(engine, "_query_stats", None)
        res, frag = engine._sized_agg_fragment(res)
        climbed = False
        retry = _NO_STATS  # a ``rebucket`` span from the 2nd attempt on
        while True:
            with retry:
                if frag is None:
                    frag = compile_fragment(
                        res.chain, res.relation, res.dicts, engine.registry,
                        col_stats=_stream_col_stats(res),
                    )
                stats = (
                    qstats.new_fragment(res.chain) if qstats is not None
                    else None
                )
                state = engine._fold_agg_state(res, frag, stats)
                # The fragment's sync: the fold has run when its overflow
                # flag is on the host; then the state that ships, a copy
                # a leaf (as ever: see stream._fetch_result).
                with _device_wait(stats):
                    overflowed = bool(np.asarray(state["overflow"]))
                    if not overflowed:
                        state = jax.tree_util.tree_map(np.asarray, state)
            if not overflowed:
                break
            res = _double_agg_groups(res)  # rebucket before shipping
            climbed = True
            retry = _rebucket(stats, frag.slots, frag.slots * 2, "pem")
            frag = None
        if climbed:
            _remember_climb(engine, res.chain, res.source, "pem", frag)
        return AggStatePayload(
            chain=tuple(res.chain),
            input_relation=res.relation,
            input_dicts=dict(res.dicts),
            state=state,
            dense_domains=frag.dense_domains,
            dense_offsets=frag.dense_offsets,
            dense_strides=frag.dense_strides,
        )
    return RowsPayload(batch=engine._materialize(res))


def bind_bridge(payloads):
    from .joins import _union_host

    payloads = payloads if isinstance(payloads, list) else [payloads]
    if not payloads:
        raise QueryError("bridge received no payloads")
    if all(isinstance(p, RowsPayload) for p in payloads):
        return _union_host([p.batch for p in payloads])
    if all(isinstance(p, AggStatePayload) for p in payloads):
        return _PendingAggBridge(payloads)
    raise QueryError("mixed payload kinds on one bridge")


def merge_agg_bridge(engine, pending: _PendingAggBridge) -> HostBatch:
    """Merge shipped partial-agg states and finalize.

    The agent-mode replacement for the on-mesh collective: states from
    k agents fold through the fragment's associative merge, after the
    group-key string ids of every agent are remapped into one
    canonical dictionary (the reference ships raw strings over GRPC,
    so alignment is implicit there; here ids must be reconciled).
    """
    from .fragment import _bind_pre_stage, _split_chain
    from ..types.dtypes import device_dtypes

    p0 = pending.payloads[0]
    # The merge-and-finalize half records onto the query's trace spine
    # as a fragment of its own: its programs and its wait are spans.
    qstats = getattr(engine, "_query_stats", None)
    stats = qstats.new_fragment(p0.chain) if qstats is not None else None
    # The merge fragment is compiled WITHOUT dense mode: agents encode
    # against their own dictionaries, so dense slot spaces are not
    # comparable across payloads — expand each dense state to explicit
    # key planes (then compact to live slots: a dense state is
    # domain-sized regardless of how few groups are live, and the
    # merge must not inherit that capacity) and realign through the
    # generic (sort-space) path. The group relation / key planes come
    # from binding the pre-stage directly — no compile needed before
    # the payload sizes are known.
    pre0, agg0, _post0, _limit0 = _split_chain(list(p0.chain))
    _, rel1, _ = _bind_pre_stage(
        pre0, p0.input_relation, dict(p0.input_dicts), engine.registry
    )
    key_plane_index = tuple(
        (c, i)
        for c in agg0.group_cols
        for i in range(len(device_dtypes(rel1.col_type(c))))
    )
    group_rel = rel1
    pending = _PendingAggBridge(payloads=[
        _compact_payload(_expand_dense_payload(p, rel1, key_plane_index))
        for p in pending.payloads
    ])
    p0 = pending.payloads[0]
    # Merge at the largest payload capacity (smaller states pad with
    # neutral slots below); overflow rebucketing grows it if the
    # union of live groups spills.
    g = max(
        op.max_groups
        for p in pending.payloads
        for op in p.chain
        if isinstance(op, AggOp)
    )
    g = max([g] + [len(p.state["valid"]) for p in pending.payloads])
    # ... or the rung an earlier merge of this chain settled on: the
    # union of the agents' groups is the Kelvin's to observe.
    cap_key = _agg_capacity_key(p0.chain, "(bridge)", "kelvin")
    g = max(g, learned_capacity(engine, cap_key) or 0)
    chain = _with_agg_groups(p0.chain, g)
    frag = compile_fragment(
        chain, p0.input_relation, dict(p0.input_dicts), engine.registry,
        allow_dense=False,
    )
    if frag.string_carry_sources and len(pending.payloads) > 1:
        # String ids inside a CARRY (not a group key) cannot be
        # realigned after the fact; reject unless every agent encoded
        # from the very same dictionary objects (keys only are realigned
        # here — reference ships raw strings over GRPC instead).
        for out_name, src_cols in frag.string_carry_sources:
            for c in src_cols:
                d0 = pending.payloads[0].input_dicts.get(c)
                s0 = list(d0.strings) if d0 is not None else None
                for p in pending.payloads[1:]:
                    d = p.input_dicts.get(c)
                    same = (
                        d is d0
                        or (d is not None and s0 is not None
                            and list(d.strings) == s0)
                    )
                    if not same:
                        raise QueryError(
                            f"aggregate {out_name!r} carries string ids "
                            f"of column {c!r} across agents whose "
                            "dictionaries disagree; results would be "
                            "garbage. Share one dictionary or aggregate "
                            "after merge."
                        )
    # Per-agent post-pre-stage dictionaries for the group columns.
    per_agent_dicts = []
    for p in pending.payloads:
        _, rel1_a, dicts1 = _bind_pre_stage(
            pre0, p.input_relation, dict(p.input_dicts), engine.registry
        )
        if tuple(rel1_a.items()) != tuple(group_rel.items()):
            raise QueryError(
                f"bridge schema mismatch: {rel1_a} vs {group_rel}"
            )
        per_agent_dicts.append(dicts1)
    # Canonical dictionary + id remap per string group column.
    canonical: dict[str, StringDictionary] = {}
    states = []
    for p, dicts1 in zip(pending.payloads, per_agent_dicts):
        keys = list(p.state["keys"])
        for pi, (c, i) in enumerate(key_plane_index):
            if group_rel.col_type(c) != DataType.STRING or i != 0:
                continue
            src = dicts1.get(c)
            if src is None:
                continue
            dst = canonical.setdefault(c, StringDictionary())
            remap = np.fromiter(
                (dst.get_or_add(s) for s in src.strings),
                dtype=np.int32,
                count=len(src),
            )
            ids = np.asarray(keys[pi])
            if len(remap) == 0:
                # Empty dictionary (agent had no rows): every slot is
                # already the null id — nothing to remap.
                keys[pi] = np.full_like(ids, NULL_ID, dtype=np.int32)
            else:
                keys[pi] = np.where(
                    ids >= 0, remap[np.clip(ids, 0, None)], NULL_ID
                ).astype(np.int32)
        if bool(np.asarray(p.state["overflow"])):
            # Lost groups at the source cannot be recovered here; the
            # producing agent rebuckets before shipping (bridge_payload).
            raise QueryError(
                "bridge payload arrived with group overflow; producing "
                "agent failed to rebucket"
            )
        states.append({**p.state, "keys": tuple(keys)})
    climbed = False
    retry = _NO_STATS  # a ``rebucket`` span from the 2nd attempt on
    while True:
        # Pad smaller states into g neutral slots, fold-merge, and on
        # merged-distinct overflow double g and retry from the (still
        # intact) original states.
        with retry:
            cols, valid, overflowed = _merge_padded(frag, states, stats)
        if not overflowed:
            break
        from ..config import get_flag

        if g * 2 > get_flag("max_groups_limit"):
            raise QueryError(
                f"group-by overflow merging bridge states at "
                f"max_groups={g}; rebucketing past the "
                f"{get_flag('max_groups_limit')} cap refused "
                "(PIXIE_TPU_MAX_GROUPS_LIMIT)"
            )
        retry = _rebucket(stats, g, g * 2, "kelvin")
        g *= 2
        climbed = True
        chain = _with_agg_groups(chain, g)
        frag = compile_fragment(
            chain, p0.input_relation, dict(p0.input_dicts), engine.registry,
            allow_dense=False,  # states carry explicit key planes
        )
    meta = [
        (
            ColumnMeta(m.name, m.dtype, dict=canonical[m.name])
            if m.name in canonical
            else m
        )
        for m in frag.out_meta
    ]
    with _device_wait(stats):
        cols, valid = _fetch_result(meta, cols, valid)
    if climbed:
        _remember_climb(engine, p0.chain, "(bridge)", "kelvin", frag)
    with _timed(stats, "materialize"):
        return _to_host_batch(meta, cols, valid)


def _merge_padded(frag, states, stats):
    """One attempt of the Kelvin's merge at ``frag``'s capacity: smaller
    states padded into its neutral slots and folded through its
    associative merge. Returns (finalized cols, valid, overflowed)."""
    import jax
    import jax.numpy as jnp

    init = frag.init_state()

    def pad(a, i):
        a = jnp.asarray(a)
        if a.ndim == 0 or a.shape[0] >= i.shape[0]:
            return a
        return jnp.concatenate([a, i[a.shape[0]:]])

    merge = jax.jit(frag.merge_states)
    padded = [jax.tree_util.tree_map(pad, s, init) for s in states]
    acc = padded[0]
    for s in padded[1:]:
        with _dispatch(stats, frag.merge_states):
            acc = merge(acc, s)
    with _dispatch(stats, frag.finalize, "finalize"):
        cols, valid, overflow = frag.finalize(acc)
    with _device_wait(stats):
        overflowed = bool(overflow)
    return cols, valid, overflowed
