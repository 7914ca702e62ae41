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
from ..types.dtypes import DataType, device_dtypes
from ..types.strings import NULL_ID, StringDictionary
from .fragment import ColumnMeta, compile_fragment_cached as compile_fragment
from .joins import learned_capacity
from .plan import AggOp
from .stream import (
    _apply_limit,
    _device_fetch,
    _device_wait,
    _dispatch,
    _fetch_tree,
    _note_fetched,
    _query_trace,
    _timed,
    _NO_STATS,
    QueryError,
    _agg_capacity_key,
    _double_agg_groups,
    _rebucket,
    _remember_climb,
    _root_span,
    _start_fetch,
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
    shared dictionary to cross agents, and the merge refuses them loudly
    where the agents' dictionaries differ in content (``_prepare_merge``,
    ``CompiledFragment.string_carry_sources``).
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


def _live_slots(state):
    """(idx, live, cap) of a shipped state: its live groups' count, the
    bucket that holds them, and the slots to take so that they sit at
    the front of ``cap`` slots (None: the state is that small already).

    A dense state is domain-sized (up to ``dense_domain_limit`` slots)
    and a keyed one as large as the PEM's fold, however few groups are
    live; the merge must not inherit that capacity. Live slots compact
    to the front, padded to a power-of-two bucket with one of the
    state's own invalid slots (they hold uda-neutral carries by
    construction), so the merge program's shapes stay bucketed."""
    valid = np.asarray(state["valid"])
    g = len(valid)
    live = int(np.count_nonzero(valid))
    cap = bucket_capacity(max(live, 1))
    if cap >= g:
        return None, live, g
    idx = np.nonzero(valid)[0]
    fill = int(np.nonzero(~valid)[0][0])
    idx = np.concatenate([idx, np.full(cap - live, fill, dtype=idx.dtype)])
    return idx, live, cap


def _explicit_state(p, idx, key_types):
    """A payload's state at the slots ``idx`` (``_live_slots``), with
    explicit key planes. Dense states carry no keys (slot index IS the
    packed key); the merge tier reconstructs them with the same unpack
    arithmetic the producing fragment's finalize uses, for the slots it
    keeps, so the generic realign/merge path applies."""
    import jax

    from .fragment import unpack_dense_slots

    state = p.state
    g = len(state["valid"])

    def take(leaf):
        a = np.asarray(leaf)
        return a[idx] if idx is not None and a.ndim and a.shape[0] == g else a

    if p.dense_domains:
        slots = np.arange(g, dtype=np.int64) if idx is None else idx
        keys = tuple(unpack_dense_slots(
            slots.astype(np.int64, copy=False), p.dense_domains, key_types,
            np, offsets=p.dense_offsets, strides=p.dense_strides,
        ))
    else:
        keys = tuple(take(k) for k in state["keys"])
    return {
        "keys": keys,
        "valid": take(state["valid"]),
        "carries": jax.tree_util.tree_map(take, state["carries"]),
        "overflow": np.asarray(state["overflow"]),
    }


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


def _count_wire(engine, payload):
    """Wire accounting (``QueryResourceUsage.wire_bytes``): bridge
    egress is what a fragment ships to the merge tier."""
    trace = _query_trace(engine)
    if trace is not None:
        trace.add_wire_bytes(payload_nbytes(payload))
    return payload


def bridge_payload(engine, res):
    """Produce a BridgeSink payload: partial-agg state for agg chains,
    materialized rows otherwise (GRPCSinkNode's two modes). What follows
    the fragment's last ``device.wait`` (the payload built, its wire
    bytes counted) is the trace's ``payload`` span."""
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
                # The fold's last program is enqueued: the state that
                # ships starts for the host behind it. The fragment's
                # sync: the fold has run when its overflow flag is on
                # the host; then the state by one batched get, the
                # wait's ``device.fetch`` (an overflowed state is dropped
                # with its copies).
                _start_fetch(state)
                with _device_wait(stats) as wait:
                    overflowed = bool(np.asarray(state["overflow"]))
                    if not overflowed:
                        with _device_fetch(stats, wait) as fetch:
                            state = _fetch_tree(state)
                            _note_fetched(
                                fetch, jax.tree_util.tree_leaves(state)
                            )
            if not overflowed:
                break
            res = _double_agg_groups(res)  # rebucket before shipping
            climbed = True
            retry = _rebucket(stats, frag.slots, frag.slots * 2, "pem")
            frag = None
        with _root_span(engine, "payload", kind="agg_state") as span:
            if climbed:
                _remember_climb(engine, res.chain, res.source, "pem", frag)
            if span is not None and frag.plan.digests:
                # The [slots, K] planes of the ``quantiles`` aggregates
                # among what ships (``usage.digest_bytes``): one pair a
                # carry, however many outputs read it.
                span.attributes["digest_bytes"] = sum(
                    leaf.nbytes
                    for owner in {own for _o, own in frag.plan.digest_owners}
                    for leaf in jax.tree_util.tree_leaves(
                        state["carries"][owner])
                )
            return _count_wire(engine, AggStatePayload(
                chain=tuple(res.chain),
                input_relation=res.relation,
                input_dicts=dict(res.dicts),
                state=state,
                dense_domains=frag.dense_domains,
                dense_offsets=frag.dense_offsets,
                dense_strides=frag.dense_strides,
            ))
    batch = engine._materialize(res)
    with _root_span(engine, "payload", kind="rows"):
        return _count_wire(engine, RowsPayload(batch=batch))


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


# -- the Kelvin's merge -------------------------------------------------------
# Per request the merge tier does only what depends on the values the
# PEMs sent: which slots are live, one upload of the compacted states,
# one program, one fetch. What depends on the plan and the dictionaries
# alone is prepared once and remembered by content (``_PreparedMerge``,
# an LRU on the engine): the canonical dictionaries and each payload's
# remap into them, the merge fragment, the plan's ops after the finalize
# node bound against the canonical dictionaries, and the one program
# that pads, remaps, folds, finalizes and applies them. One path for
# every number of payloads and every layout; what it does follows what
# it observes: equal ``content_key``s, no remap; live counts, the
# capacity; k >= 2 payloads of a keyed sort fold, ONE k-way fold at their
# own sizes (``fragment.py`` ``merge_many``), of another fold k - 1
# merges.

#: Prepared merges an engine keeps (a Kelvin serves a handful of
#: scripts; a record pins a fragment, a program and its dictionaries).
_PREPARED_MAX = 64


@dataclass(frozen=True)
class _PreparedMerge:
    """What a merge of one chain's payloads needs that the values in
    them do not change. Keyed by the chain, the ops after the finalize
    node, the capacity, and a payload's relation, state shapes, dense
    layout and dictionaries' ``content_key``s, in payload order: a
    dictionary that grows has a new key (it is append-only), the record
    misses and is built again, and the old one ages out."""

    frag: object  # the merge fragment (explicit keys), at the capacity
    program: object  # (states, remaps) -> (planes, valid, overflow, live)
    # A payload's remaps into the canonical dictionaries, {key plane:
    # int32 ids on the device}; a remap that is the identity is left out
    # (always so where the payloads' dictionaries are equal).
    remaps: tuple
    meta: tuple  # ColumnMeta of the answer; canonical dictionaries
    limit: object  # the closing LimitOp's n, or None
    key_types: tuple  # the group columns' types, a key plane


def _dict_keys(p) -> tuple:
    """A payload's input relation and dictionaries, by content."""
    return (p.input_relation.items_tuple(), tuple(sorted(
        (n, d.content_key()) for n, d in p.input_dicts.items()
    )))


def _prepared_merge(engine, payloads, tail, sigs, slots: int, chain_key):
    """(record, "hit" | "miss") for these payloads at ``slots``;
    ``chain_key`` is the chain's capacity key (its ops with the AggOp's
    capacity taken out: ``_agg_capacity_key``)."""
    from ..ops.routes import routes_platform

    key = (
        chain_key, tuple(tail), slots, routes_platform(),
        tuple(
            (_dict_keys(p), sig, p.dense_domains, p.dense_offsets,
             p.dense_strides)
            for p, sig in zip(payloads, sigs)
        ),
    )
    try:
        if chain_key is None:
            raise TypeError
        hash(key)
    except TypeError:  # a chain that does not hash is prepared each time
        return _prepare_merge(engine, payloads, tail, slots, None), "miss"
    cache, lock = engine._prepared_merges, engine._prepared_merges_lock
    with lock:
        rec = cache.get(key)
        if rec is not None:
            cache.move_to_end(key)
            return rec, "hit"
    # Built outside the lock (merges of different queries run
    # concurrently on one Kelvin, and a build compiles); the loser of a
    # duplicate miss adopts the winner's record.
    rec = _prepare_merge(engine, payloads, tail, slots, key)
    with lock:
        raced = cache.get(key)
        if raced is not None:
            return raced, "miss"
        cache[key] = rec
        while len(cache) > _PREPARED_MAX:
            cache.popitem(last=False)
    return rec, "miss"


def _prepare_merge(engine, payloads, tail, slots: int, key) -> _PreparedMerge:
    import jax

    from .fragment import _bind_post_stage, _bind_pre_stage, _split_chain
    from .programs import default_program_registry

    p0 = payloads[0]
    pre = _split_chain(list(p0.chain))[0]
    post, _agg, _post, limit = _split_chain(list(tail))
    # The merge fragment is compiled WITHOUT dense mode: agents encode
    # against their own dictionaries, so dense slot spaces are not
    # comparable across payloads; states arrive here with explicit key
    # planes (``_explicit_state``) and realign through the keyed path.
    frag = compile_fragment(
        _with_agg_groups(p0.chain, slots), p0.input_relation,
        dict(p0.input_dicts), engine.registry, allow_dense=False,
    )
    group_rel = frag.group_relation
    # String ids inside a CARRY (not a group key) cannot be realigned
    # after the fact; refuse unless every agent encoded from equal
    # dictionaries (keys only are realigned here: the reference ships
    # raw strings over GRPC instead).
    for out_name, src_cols in frag.string_carry_sources:
        for c in src_cols:
            if len({
                d.content_key() if d is not None else None
                for d in (p.input_dicts.get(c) for p in payloads)
            }) > 1:
                raise QueryError(
                    f"aggregate {out_name!r} carries string ids "
                    f"of column {c!r} across agents whose "
                    "dictionaries disagree; results would be "
                    "garbage. Share one dictionary or aggregate "
                    "after merge."
                )
    # Per-agent post-pre-stage dictionaries of the group columns (bound
    # once a distinct set of input dictionaries).
    bound: dict = {}
    agent_dicts = []
    for p in payloads:
        k = _dict_keys(p)
        if k not in bound:
            _, rel1, dicts1 = _bind_pre_stage(
                pre, p.input_relation, dict(p.input_dicts), engine.registry
            )
            if tuple(rel1.items()) != tuple(group_rel.items()):
                raise QueryError(
                    f"bridge schema mismatch: {rel1} vs {group_rel}"
                )
            bound[k] = dicts1
        agent_dicts.append(bound[k])
    # The canonical dictionary of each string group column, and each
    # payload's remap into it. Where every payload's dictionary has the
    # same content the canonical dictionary IS the first one, an object
    # that lives as long as this record: its digest is computed once and
    # every cache key downstream that holds it hits without hashing.
    canonical: dict[str, StringDictionary] = {}
    remaps = [dict() for _ in payloads]
    for pi, (c, i) in enumerate(frag.key_plane_index):
        if group_rel.col_type(c) != DataType.STRING or i != 0:
            continue
        srcs = [dicts1.get(c) for dicts1 in agent_dicts]
        if srcs[0] is None:
            continue
        if len({d.content_key() for d in srcs}) == 1:
            canonical[c] = srcs[0]
            continue
        dst = canonical[c] = StringDictionary()
        for remap, src in zip(remaps, srcs):
            ids = np.fromiter(
                (dst.get_or_add(s) for s in src.strings),
                dtype=np.int32, count=len(src),
            )
            if len(ids) and np.array_equal(ids, np.arange(len(ids))):
                continue  # the first payload's, and any prefix of it
            # Padded to a bucket with the null id, so that a dictionary
            # that grows inside its bucket asks for no new program; an
            # empty dictionary (an agent with no rows) maps all to null.
            padded = np.full(bucket_capacity(len(ids)), NULL_ID, np.int32)
            padded[:len(ids)] = ids
            remap[pi] = engine._put(padded)
    apply_tail, meta, _rel = _bind_post_stage(
        post,
        [
            ColumnMeta(m.name, m.dtype, dict=canonical[m.name])
            if m.name in canonical else m
            for m in frag.out_meta
        ],
        engine.registry,
    )
    program = _merge_program(frag, apply_tail, meta)
    if key is not None:
        # (The registry's identity is part of the key: its UDAs' finalize
        # is in the program, and two engines of one process may differ.)
        program = default_program_registry().wrap(
            program, "merge_finalize",
            (key, id(engine.registry), "merge_finalize"),
            ",".join(type(o).__name__ for o in (*p0.chain, *tail)),
            pins=(tuple(canonical.values()), engine.registry),
        )
    return _PreparedMerge(
        frag=frag, program=program, remaps=tuple(remaps),
        meta=tuple(meta), limit=limit,
        key_types=tuple(
            group_rel.col_type(c) for c, _i in frag.key_plane_index
        ),
    )


def _merge_program(frag, apply_tail, meta):
    """The one program of a prepared merge: each state as it arrived
    (compacted, explicit keys), string key ids remapped where there is a
    remap; k >= 2 states folded ONCE at their own sizes where the merge
    fragment's fold offers that (``frag.merge_many``: the keyed sort
    fold's), else padded into ``frag``'s neutral slots and the k - 1
    merges folded (nothing to fold for one); the finalize, the plan's
    ops after it. Returns the planes the host batch reads, their
    validity, the overflow flag and the union's live groups; after a
    k-way fold also its ``contended_slots`` and ``rebins``."""
    import jax
    import jax.numpy as jnp

    def cast(a, i):
        return jnp.asarray(a, i.dtype)

    def pad(a, i):
        a = cast(a, i)
        if a.ndim == 0 or a.shape[0] >= i.shape[0]:
            return a
        return jnp.concatenate([a, i[a.shape[0]:]])

    def merge_finalize(states, remaps):
        init = frag.init_state()
        many = len(states) > 1 and frag.merge_many is not None
        arrived = []
        for s, remap in zip(states, remaps):
            keys = list(s["keys"])
            for pi, table in remap.items():
                ids = keys[pi]
                keys[pi] = jnp.where(
                    ids >= 0, table[jnp.clip(ids, 0, table.shape[0] - 1)],
                    NULL_ID,
                ).astype(jnp.int32)
            arrived.append(jax.tree_util.tree_map(
                cast if many else pad, {**s, "keys": tuple(keys)}, init
            ))
        counts = ()
        if many:
            # One fold of the states' N slots as they came: no pad to the
            # union's capacity, no stack, no merge a state.
            acc, told = frag.merge_many(arrived)
            counts = (told["contended_slots"], told["rebins"])
        else:
            # The fold of the k - 1 merges as a scan over the stacked
            # states: one merge body in the program whatever k is (a
            # keyed merge takes the chip's compiler most of a minute),
            # and an empty scan for one payload.
            stacked = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *arrived)
            acc, _ = jax.lax.scan(
                lambda acc, s: (frag.merge_states(acc, s), None),
                jax.tree_util.tree_map(lambda x: x[0], stacked),
                jax.tree_util.tree_map(lambda x: x[1:], stacked),
            )
        live = jnp.sum(acc["valid"], dtype=jnp.int32)
        cols, valid, overflow = frag.finalize_state(acc)
        cols, valid = apply_tail(cols, valid)
        # The planes the host batch reads, each in its column's device
        # dtype, as a batch staged for a fragment of the plan's ops has
        # them (a FLOAT64 plane is f32 on the device: the mean is
        # rounded once, into its result plane).
        planes = {
            m.name: (cols[m.name][0],) if m.struct_fields is not None
            else tuple(
                p.astype(dt)
                for p, dt in zip(cols[m.name], device_dtypes(m.dtype))
            )
            for m in meta
        }
        return (planes, valid, overflow, live, *counts)

    return jax.jit(merge_finalize)


def merge_agg_bridge(engine, pending: _PendingAggBridge,
                     tail=()) -> HostBatch:
    """Merge shipped partial-agg states, finalize, and apply ``tail``:
    the plan's Map / Filter ops and closing Limit after the finalize
    node.

    The agent-mode replacement for the on-mesh collective: states from
    k agents fold through the fragment's associative merge, after the
    group-key string ids of every agent are remapped into one
    canonical dictionary (the reference ships raw strings over GRPC,
    so alignment is implicit there; here ids must be reconciled).
    """
    import jax

    from ..config import get_flag
    from .programs import shape_signature
    from .joins import remember_capacity

    payloads = pending.payloads
    p0 = payloads[0]
    # The merge records onto the query's trace spine as ONE fragment:
    # one program's dispatch, one wait.
    qstats = getattr(engine, "_query_stats", None)
    stats = (
        qstats.new_fragment((*p0.chain, *tail)) if qstats is not None
        else None
    )
    # ``merge.compact``: the states' live slots found here, and taken
    # below once the prepared merge has said what the key planes hold.
    with _root_span(engine, "merge.compact", payloads=len(payloads)):
        for p in payloads:
            if bool(np.asarray(p.state["overflow"])):
                # Lost groups at the source cannot be recovered here;
                # the producing agent rebuckets before shipping
                # (bridge_payload).
                raise QueryError(
                    "bridge payload arrived with group overflow; "
                    "producing agent failed to rebucket"
                )
        parts = [_live_slots(p.state) for p in payloads]
        sigs = [
            (shape_signature(p.state), cap)
            for p, (_idx, _live, cap) in zip(payloads, parts)
        ]
    # The capacity: the bucket of what the payloads hold live (their
    # union cannot spill it), or the bucket an earlier merge of this
    # chain saw the union fit where that is smaller: the union of the
    # agents' groups is the Kelvin's to observe. A union that outgrows
    # a remembered bucket overflows, doubles and refolds.
    cap_key = _agg_capacity_key(p0.chain, "(bridge)", "kelvin")
    known = learned_capacity(engine, cap_key)
    g = bucket_capacity(sum(live for _idx, live, _cap in parts))
    if known is not None and known < g:
        g = max(known, max(cap for _idx, _live, cap in parts))
    climbed = False
    retry = _NO_STATS  # a ``rebucket`` span from the 2nd attempt on
    while True:
        # The merge is one program: its one boundary a cancel can stop
        # it at is before an attempt's dispatch.
        engine._check_cancel()
        with retry:
            with _root_span(engine, "fragment.bind") as bind:
                rec, prepared = _prepared_merge(
                    engine, payloads, tail, sigs, g, cap_key
                )
                if bind is not None:
                    bind.attributes["cached"] = prepared
            with _root_span(engine, "merge.compact",
                            payloads=len(payloads), slots=g):
                states = [
                    _explicit_state(p, idx, rec.key_types)
                    for p, (idx, _live, _cap) in zip(payloads, parts)
                ]
            with _dispatch(stats, rec.program, "finalize") as span:
                if span is not None:
                    # What k agents cost the merge beyond one: the
                    # states uploaded, the merges folded, the padded
                    # entries of the remaps read (none where the
                    # payloads' dictionaries are equal).
                    span.attributes.update(
                        prepared=prepared, slots=g,
                        payloads=len(payloads), merges=len(payloads) - 1,
                        upload_bytes=sum(
                            int(a.nbytes)
                            for a in jax.tree_util.tree_leaves(states)
                        ),
                    )
                    remapped = sum(
                        int(t.shape[0])
                        for remap in rec.remaps for t in remap.values()
                    )
                    if remapped:  # absent without a remap, as a fold's
                        span.attributes["remap_entries"] = remapped
                out = rec.program(engine._put(states), rec.remaps)
            with _device_wait(stats) as wait:
                out = jax.device_get(out)
                _note_fetched(wait, jax.tree_util.tree_leaves(out))
                cols, valid, overflowed, live, *folded = out
                if wait is not None and folded:
                    # What the k-way fold saw (they ride the one fetch):
                    # the merged slots two or more states filled, and
                    # the ``merge_ordered`` runs their digests took.
                    wait.attributes.update(
                        contended_slots=int(folded[0]),
                        rebins=int(folded[1]),
                    )
        if not overflowed:
            break
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
    held = bucket_capacity(int(live))
    if climbed:
        _remember_climb(engine, p0.chain, "(bridge)", "kelvin", rec.frag)
    elif held > (known or 0):
        remember_capacity(engine, cap_key, held)
    with _timed(stats, "materialize"):
        out = _apply_limit(_to_host_batch(rec.meta, cols, valid), rec.limit)
    if stats is not None:
        stats.rows_in = sum(n for _idx, n, _cap in parts)
        stats.rows_out = out.length
    return out
