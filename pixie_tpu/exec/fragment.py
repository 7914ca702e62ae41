"""Fragment compiler: a linear operator chain -> one jitted XLA program.

Reference contrast: Carnot instantiates an ExecutionGraph of exec nodes and
pushes RowBatches through virtual ConsumeNext calls
(``src/carnot/exec/exec_graph.cc:295``). Here the whole chain
{Map/Filter -> BlockingAgg -> Map/Filter/Limit} is traced into TWO
functions:

- ``update(state, cols, valid)``: folds one staged window into the group
  state (or, for non-aggregating chains, produces the window's output
  batch). Runs once per window under jit — XLA fuses projections, filter
  masks, group-id sorts and UDA segment updates into one program.
- ``finalize(state)``: UDA finalize + post-agg ops -> output columns.

Group state is a pytree {keys, valid, carries, overflow}; windows merge
via the regroup machinery (``pixie_tpu.ops.groupby``), the same path a
multi-device partial-agg merge uses.
"""

from __future__ import annotations

import functools
import threading
from dataclasses import dataclass, field
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..config import get_flag
from ..ops import hll
from ..ops import routes as _routes
from ..ops.groupby import (
    _to_bits,
    dense_group_ids,
    dense_group_ids_hash,
    join_u32,
    lead_words,
    regroup_pair,
    scatter_carry,
    sorted_group_fold,
    sorted_slot_ids,
    split_u32,
)
from ..ops.tdigest import (
    digest_merge,
    digest_quantile,
    merge_ordered,
    ordered_batch_to_digest,
)
from ..types.dtypes import DataType, device_dtypes, pad_values
from ..types.relation import Relation
from ..udf.builtins.math_sketches import quantile_points
from ..udf.registry import Registry
from ..udf.udf import UDADef, apply_cast
from .expr import BindError, bind_expr, collect_operands, operands_bound
from .fold_plan import INT_KEY_TYPES, FoldPlan, is_digest, plan_fold
from .plan import (
    AggOp,
    ColumnRef,
    FilterOp,
    FuncCall,
    LimitOp,
    Literal,
    LookupJoinOp,
    MapOp,
)


@dataclass
class ColumnMeta:
    """Host-side metadata for one output column."""

    name: str
    dtype: DataType
    dict: object = None  # StringDictionary for STRING columns
    struct_fields: Optional[tuple] = None  # sketch JSON struct (quantiles)


@dataclass
class CompiledFragment:
    relation: Relation  # device-visible output relation
    out_meta: list  # list[ColumnMeta] incl. struct columns
    is_agg: bool
    update: object = None  # jitted
    update_all: object = None  # jitted scan-fold over stacked windows (agg)
    finalize: object = None  # jitted (agg only)
    init_state: object = None  # callable -> state pytree (agg only)
    # ``init_state`` as ONE program of no argument (jitted; agg only):
    # what a fold starts from, made anew every request. The plain
    # function above stays for what calls it inside a trace (the merge
    # tier's ``merge_finalize``, the mesh step) or reads its shapes.
    init_program: object = None
    limit: Optional[int] = None  # host-enforced row cap (non-agg chains)
    # Unjitted building blocks, traceable inside shard_map (the distributed
    # partial-agg path, ``pixie_tpu.parallel``):
    window_state: object = None  # (cols, valid) -> per-window group state
    merge_states: object = None  # (state_a, state_b) -> merged state
    finalize_state: object = None  # ``finalize`` before jit (the merge tier)
    # [k >= 2 states as they arrived] -> (merged state, {counts}) in one
    # fold (the keyed sort fold's ``merge_many``); None: the merge tier
    # folds ``merge_states`` over them.
    merge_many: object = None
    # Dense fragments whose aggregates are all count/sum/mean/min/max
    # expose the native-fold seam: {"inputs_jit": (cols, valid) ->
    # (gids, an argument a carry, oob), "plan": ((out_name, uda_name,
    # init), ...) of the aggregates that hold a carry}.
    # The engine's CPU backend runs the scatter passes in the native
    # multi-core kernel (native/seg_fold.cc) — XLA:CPU scatters are
    # single-threaded. None = not eligible.
    native_fold: object = None
    apply_rows: object = None  # (cols, valid) -> (cols, valid), non-agg chain
    # (col, plane_i) per entry of state["keys"], and the post-pre-stage
    # relation the group columns are typed against (agg only) — consumed by
    # the agent-mode bridge merge to realign string key dictionaries.
    key_plane_index: tuple = ()
    group_relation: Relation = None
    # Agg outputs whose CARRY holds string-dictionary ids (e.g. ``any``
    # over a string column) mapped to the input columns those ids encode.
    # Group keys realign across agents; carries do not — the bridge merge
    # rejects such payloads unless every agent shares the dictionaries.
    string_carry_sources: tuple = ()  # tuple[(out_name, tuple[col, ...])]
    # Dense-domain mode: per-group-col static domain sizes (the packed key
    # IS the group id; state["keys"] is empty). () = not dense.
    # ``dense_offsets`` shifts stats-derived integer keys to zero base
    # (0 for dictionary/bool columns).
    dense_domains: tuple = ()
    dense_offsets: tuple = ()
    # Per-key value stride (1 except binned/affine integer keys, where
    # slot codes count stride steps: value = code * stride + offset).
    dense_strides: tuple = ()
    # Which fold the programs run, decided once at compile time (agg
    # only; ``fold_plan.py``), and three of its fields under the names the
    # fold programs' device.dispatch spans and the fragment's
    # /debug/queryz entry carry: ``fold`` = the aggregates' route
    # (``pallas_int`` / ``pallas_f32`` / ``sorted_digest`` / ``xla`` /
    # ``mixed:<route>=<n>,...`` / ``sorted_int``), ``group`` = the layout
    # (``dense`` / ``sorted`` / ``hashed``), ``slots`` = the capacity g.
    plan: Optional[FoldPlan] = None
    fold: str = ""
    group: str = ""
    slots: int = 0
    # Under ``sorted_int``: n -> how an n-row window's sum planes reach
    # group order (``ops/routes.py`` ``sorted_fold_ride``), the ``ride``
    # attribute of the window programs' dispatch spans. None otherwise.
    ride: object = None
    # A keyed fold's probe (agg only, None on a dense domain): jitted
    # (registers, cols, valid) -> registers, the window's rows folded
    # into ONE HyperLogLog row over the JOINT group key (``ops/hll.py``),
    # after the same pre-stage the fold runs. ``init_sketch()`` gives the
    # empty row. The engine reads it where no capacity is remembered yet
    # (``Engine._sized_agg_fragment``).
    group_sketch: object = None
    init_sketch: object = None
    # The operand tables the chain's expressions gather from
    # (``exec/expr.py``, "operand tables": a dictionary-side UDF's remap),
    # {name: Operand} in bind order. The jitted entry points take them as
    # an argument of their own (``OperandProgram``): callers call
    # ``update(state, cols, valid)`` as ever. ``remap_entries`` is the
    # largest table's length (its bucket), 0 without one: the
    # ``remap_entries`` attribute of the fold programs' dispatch spans.
    operands: dict = field(default_factory=dict)
    remap_entries: int = 0


class OperandProgram:
    """A jitted entry point of a fragment whose expressions read operand
    tables: the program's first argument is the tables, filled in here,
    so it is called (and lowered) with the arguments it always had. The
    tables' device copies are made once and shared
    (``expr.Operand.device``)."""

    __slots__ = ("fn", "operands")

    def __init__(self, fn, operands: dict):
        self.fn = fn  # the jit stage, or its TrackedProgram
        self.operands = operands

    def __call__(self, *args):
        return self.fn(
            {n: o.device() for n, o in self.operands.items()}, *args
        )

    def lower(self, *args):
        return self.fn.lower(
            {n: jax.ShapeDtypeStruct(o.host.shape, o.host.dtype)
             for n, o in self.operands.items()}, *args
        )

    @property
    def kind(self):
        return getattr(self.fn, "kind", None)

    @property
    def __name__(self):
        return getattr(self.fn, "__name__", "program")


def _program(fn, operands: dict):
    """``jax.jit(fn)``; where the fragment binds operand tables, the
    program takes them as its first argument and traces ``fn`` with
    them bound, under ``fn``'s own name (the device trace's
    ``jit_update*``)."""
    if not operands:
        return jax.jit(fn)

    @functools.wraps(fn)
    def run(tables, *args):
        with operands_bound(tables):
            return fn(*args)

    return OperandProgram(jax.jit(run), operands)


_FRAGMENT_CACHE: dict = {}
_FRAGMENT_CACHE_MAX = 128
# Guards insert/evict (concurrent queries compile concurrently; two
# threads evicting the same oldest key would KeyError, and the loser of
# a duplicate-miss race must adopt the winner's fragment so id()-keyed
# downstream caches — the distributed step cache — stay canonical).
_FRAGMENT_CACHE_LOCK = threading.Lock()


def _struct_key(x):
    """Canonical hashable form of a plan-op / expr tree (class names keep
    e.g. ColumnRef('x') distinct from a bare string)."""
    import dataclasses

    if dataclasses.is_dataclass(x) and not isinstance(x, type):
        return (type(x).__name__,) + tuple(
            _struct_key(getattr(x, f.name)) for f in dataclasses.fields(x)
        )
    if isinstance(x, (list, tuple)):
        return tuple(_struct_key(v) for v in x)
    if isinstance(x, dict):
        return tuple(sorted((k, _struct_key(v)) for k, v in x.items()))
    return x


def _stats_cache_key(ops, col_stats):
    """The col_stats facts that can influence compilation: rounded bounds
    of columns reaching the chain's agg group keys. Keying on anything
    more (e.g. time_ bounds, which move every append) would defeat the
    fragment cache."""
    if not col_stats:
        return ()
    try:
        pre, agg, _post, _limit = _split_chain(list(ops))
    except BindError:
        return tuple(sorted(col_stats.items()))
    if agg is None:
        return ()
    stats = _propagate_stats(pre, col_stats)
    return tuple(
        (c, _round_stat_bounds(*stats[c]))
        for c in agg.group_cols
        if c in stats
    )


def compile_fragment_cached(ops, input_relation, input_dicts, registry,
                            allow_dense: bool = True, col_stats=None,
                            note=None):
    """``compile_fragment`` memoized on plan structure.

    A fragment's jitted ``update``/``finalize`` closures hold the XLA
    executables; rebuilding them per query forces a re-trace + compile
    every ``execute_query`` (Carnot similarly reuses compiled plan
    state, ``src/carnot/carnot.cc:122``). Keyed on the op chain, input schema,
    the CONTENT identity of every string dictionary
    (``StringDictionary.content_key``: an append-only dictionary's
    compile-time behavior — literal ``lookup`` ids, out_meta decode —
    is a pure function of its ordered contents, and growth re-encodes
    string literals under a new key), and the registry identity.
    ``note``, when given, is called with ``"hit"`` or ``"miss"``: what
    the lookup found (a ``fragment.bind`` span's ``cached``).
    Content- rather than id()-keyed because the merge tier's bridge
    payloads decode FRESH dictionary objects from the wire on every
    distributed query: identity keying missed the cache (and recompiled
    the merge/limit XLA programs) once per run. Unhashable chains (not
    produced by the planner today) fall back to uncached compilation.
    """
    try:
        key = (
            _struct_key(tuple(ops)),
            input_relation.items_tuple(),
            tuple(sorted(
                (n, d.content_key()) for n, d in input_dicts.items()
            )),
            id(registry),
            _routes.routes_platform(),
            get_flag("dense_domain_limit") if allow_dense else -1,
            get_flag("int_dense_domain_limit") if allow_dense else -1,
            _stats_cache_key(ops, col_stats),
        )
        hash(key)
    except TypeError:
        if note is not None:
            note("miss")
        return compile_fragment(
            ops, input_relation, input_dicts, registry, allow_dense,
            col_stats=col_stats,
        )
    hit = _FRAGMENT_CACHE.get(key)
    if note is not None:
        note("miss" if hit is None else "hit")
    if hit is None:
        # Compile OUTSIDE the cache lock (compiles are slow and must
        # not serialize concurrent queries' unrelated misses); a
        # duplicate-miss race costs one redundant compile and the
        # loser adopts the winner's fragment below.
        frag = compile_fragment(
            ops, input_relation, input_dicts, registry, allow_dense,
            col_stats=col_stats,
        )
        _track_fragment_programs(frag, ops, key, input_dicts, registry)
        with _FRAGMENT_CACHE_LOCK:
            raced = _FRAGMENT_CACHE.get(key)
            if raced is not None:
                return raced[0]
            while len(_FRAGMENT_CACHE) >= _FRAGMENT_CACHE_MAX:
                _FRAGMENT_CACHE.pop(next(iter(_FRAGMENT_CACHE)))
            # The entry pins the registry (still id()-keyed: a freed
            # registry's address could be recycled into a false hit) and
            # the compile-time dictionaries (the fragment's out_meta
            # resolves ids through them; content-equal callers may
            # outlive their own copies).
            _FRAGMENT_CACHE[key] = (
                frag, tuple(input_dicts.values()), registry
            )
    else:
        frag = hit[0]
    return frag


def _track_fragment_programs(frag, ops, cache_key, input_dicts,
                             registry) -> None:
    """Wrap a fresh fragment's jit entry points in the process program
    registry (exec/programs.py): per-shape compile wall-time + XLA
    cost/memory analysis, hit/miss counts, /debug/programz and the
    ``__programs__`` telemetry table. Keyed by the fragment cache key —
    the same structural identity that keys THIS cache — so a repeated
    plan's second run is a registry hit, and a fragment-cache eviction
    can still reuse the registry's executable instead of recompiling
    (the registry pins the id()-keyed objects exactly like the entry
    above)."""
    from .programs import default_program_registry

    preg = default_program_registry()
    label = ",".join(type(o).__name__ for o in ops) or "(scan)"
    pins = (tuple(input_dicts.values()), registry)

    def wrap(fn, kind, which):
        if isinstance(fn, OperandProgram):  # the jit stage inside it
            fn.fn = preg.wrap(fn.fn, kind, (cache_key, which), label,
                              pins=pins)
            return fn
        return preg.wrap(fn, kind, (cache_key, which), label, pins=pins)

    frag.update = wrap(frag.update, "fragment_update", "update")
    frag.update_all = wrap(frag.update_all, "fragment_scan_fold",
                           "update_all")
    frag.finalize = wrap(frag.finalize, "fragment_finalize", "finalize")
    frag.init_program = wrap(frag.init_program, "fragment_init_state",
                             "init_state")
    if frag.native_fold is not None:
        frag.native_fold["inputs_jit"] = wrap(
            frag.native_fold["inputs_jit"], "native_fold_inputs",
            "native_inputs",
        )


def window_rows(cols) -> int:
    """The length of a staged window's planes."""
    return next(p for c, p in cols.items() if c != "__side__")[0].shape[0]


@jax.tree_util.register_pytree_node_class
class RowSlice:
    """Where a fold program cuts a resident window's planes before it
    folds them: ``rows`` rows of each plane from row ``start`` (a scalar;
    a vector, one a window, under ``update_all``). ``start`` is an
    argument of the program (no retrace an offset); ``rows`` is part of
    the tree's structure, so a program is traced once a length. The
    (lo, hi) pair beside it counts from ``start``. The engine names the
    slice (``exec/engine.py`` ``_fold_agg_state``): the rows in range of
    a window padded to its capacity."""

    __slots__ = ("start", "rows")

    def __init__(self, start, rows: int):
        self.start, self.rows = start, rows

    def tree_flatten(self):
        return (self.start,), self.rows

    @classmethod
    def tree_unflatten(cls, rows, children):
        return cls(children[0], rows)


def _sliced(cols, at):
    """``cols`` with every window plane cut to the rows ``at`` names
    (a ``RowSlice``; None = whole), inside the program that folds them."""
    if at is None:
        return cols
    return {
        c: planes if c == "__side__" else tuple(
            jax.lax.dynamic_slice_in_dim(p, at.start, at.rows)
            for p in planes
        )
        for c, planes in cols.items()
    }


def _range_valid(cols, valid):
    """Materialize ``valid`` when it arrives as a (lo, hi) row-range pair
    (device-resident windows carry no mask; rather than a separate
    dispatch per window, the mask is built INSIDE the fragment program
    from two scalars)."""
    if isinstance(valid, tuple):
        lo, hi = valid
        iota = jnp.arange(window_rows(cols), dtype=jnp.int32)
        return (iota >= lo) & (iota < hi)
    return valid


def _bind_pre_stage(ops, relation, dicts, registry):
    """Bind leading Map/Filter/LookupJoin ops; returns
    (apply_fn, relation, dicts)."""
    steps = []  # ("map", [(name, BoundExpr)]) | ("filter", BoundExpr)
    #           | ("lookup", LookupJoinOp)
    for op in ops:
        if isinstance(op, MapOp):
            bound = [(name, bind_expr(e, relation, dicts, registry)) for name, e in op.exprs]
            steps.append(("map", bound))
            relation = Relation([(n, b.dtype) for n, b in bound])
            dicts = {n: b.dict for n, b in bound if b.dict is not None}
        elif isinstance(op, FilterOp):
            b = bind_expr(op.predicate, relation, dicts, registry)
            if b.dtype != DataType.BOOLEAN:
                raise BindError(f"filter predicate has type {b.dtype}, want BOOLEAN")
            steps.append(("filter", b))
        elif isinstance(op, LookupJoinOp):
            if not relation.has_column(op.key_col):
                raise BindError(f"lookup key {op.key_col!r} not in {relation}")
            steps.append(("lookup", op))
            relation = Relation(
                list(relation.items())
                + [(n, dt) for n, dt, _np in op.out_cols]
            )
        else:
            raise AssertionError(op)

    def apply_lookup(op, cols, valid, side):
        if side is None:
            raise BindError(
                "LookupJoinOp fragment ran without its side-input tables "
                "(cols['__side__'] missing — engine-internal op misuse)"
            )
        k = cols[op.key_col][0]
        idx = k - op.lo
        inb = (idx >= 0) & (idx < op.dom)
        slot = jnp.clip(idx, 0, op.dom - 1).astype(jnp.int32)
        found = inb & side[f"{op.prefix}:found"][slot]
        cols = dict(cols)
        for name, _dt, n_planes in op.out_cols:
            planes = []
            for j in range(n_planes):
                t = side[f"{op.prefix}:{name}:{j}"]
                v = t[slot]
                if op.how == "left":
                    # Unmatched probe rows stay valid with null values.
                    # Inner joins skip the select: not-found rows become
                    # invalid below, so their gathered garbage is masked
                    # everywhere downstream.
                    v = jnp.where(found, v, jnp.zeros((), v.dtype))
                planes.append(v)
            cols[name] = tuple(planes)
        if op.how == "inner":
            valid = valid & found
        return cols, valid

    def apply(cols, valid):
        cols = dict(cols)
        side = cols.pop("__side__", None)
        for kind, payload in steps:
            if kind == "map":
                # Broadcast so literal-only expressions yield full planes.
                new_cols = {}
                for name, b in payload:
                    v = b.fn(cols)
                    planes = v if isinstance(v, tuple) else (v,)
                    new_cols[name] = tuple(
                        jnp.broadcast_to(p, valid.shape) for p in planes
                    )
                cols = new_cols
            elif kind == "lookup":
                cols, valid = apply_lookup(payload, cols, valid, side)
            else:
                valid = valid & jnp.broadcast_to(payload.fn(cols), valid.shape)
        return cols, valid

    return apply, relation, dicts


def _split_chain(ops):
    """[pre(map/filter)...] [agg]? [post(map/filter)...] [limit at end]?

    A LimitOp may only terminate a fragment — the engine splits chains at
    interior limits so the cap applies at its plan position (Carnot's
    LimitNode aborts upstream sources the same way,
    ``src/carnot/exec/limit_node.h``).
    """
    pre, agg, post, limit = [], None, [], None
    for i, op in enumerate(ops):
        if isinstance(op, LimitOp):
            if i != len(ops) - 1:
                raise BindError(
                    "LimitOp must terminate a fragment (engine splits chains)"
                )
            limit = op.n
        elif isinstance(op, AggOp):
            if agg is not None:
                raise BindError("multiple aggregates in one fragment")
            agg = op
        elif agg is None:
            pre.append(op)
        else:
            post.append(op)
    return pre, agg, post, limit


def _expr_stats(e, stats):
    """(min, max, stride) bounds of an integer expression, or None.

    Interval + stride arithmetic over the affine expressions the planner
    emits for time windowing: ``bin(t, d)`` yields multiples of ``d``,
    and +/-/*-by-literal keep the lattice. The invariant maintained is
    "every value ≡ min (mod stride)", which is exactly what the dense
    packing needs: code = (v - min) // stride is exact. Constants carry
    stride 0 (gcd identity)."""
    import math

    if isinstance(e, ColumnRef):
        s = stats.get(e.name)
        if s is None:
            return None
        return (int(s[0]), int(s[1]), int(s[2]) if len(s) > 2 else 1)
    if isinstance(e, Literal):
        v = e.value
        if isinstance(v, bool) or not isinstance(v, int):
            return None
        return (v, v, 0)
    if not isinstance(e, FuncCall):
        return None
    args = [_expr_stats(a, stats) for a in e.args]
    if any(a is None for a in args):
        return None
    if e.name == "bin" and len(args) == 2 and args[1][0] == args[1][1]:
        d = args[1][0]
        lo, hi, _s = args[0]
        if d <= 0 or lo < 0:
            # jnp's floor-mod and this arithmetic agree for non-negative
            # values; negative time bases don't occur, so just decline.
            return None
        return (lo - lo % d, hi - hi % d, d)
    if e.name in ("add", "subtract") and len(args) == 2:
        (la, ha, sa), (lb, hb, sb) = args
        st = math.gcd(sa, sb)
        if e.name == "add":
            return (la + lb, ha + hb, st)
        return (la - hb, ha - lb, st)
    if e.name == "multiply" and len(args) == 2:
        (la, ha, sa), (lb, hb, sb) = args
        const = None
        var = None
        if lb == hb:
            const, var = lb, (la, ha, sa)
        elif la == ha:
            const, var = la, (lb, hb, sb)
        if const is None or const <= 0:
            return None
        lo, hi, st = var
        return (lo * const, hi * const, st * const)
    return None


def _propagate_stats(ops, stats):
    """Carry input-column (min, max[, stride]) bounds through leading
    Map/Filter ops. Pass-through ColumnRefs keep their source bounds;
    affine integer expressions (time binning) get derived strided
    bounds via ``_expr_stats``; filters narrow, so bounds stay valid."""
    if not stats:
        return stats
    for op in ops:
        if isinstance(op, MapOp):
            nxt = {}
            for name, e in op.exprs:
                s = _expr_stats(e, stats)
                if s is not None and s[2] != 0:
                    nxt[name] = s
            stats = nxt
    return stats


def compile_fragment(ops, input_relation, input_dicts, registry: Registry,
                     allow_dense: bool = True, col_stats=None) -> CompiledFragment:
    # Every bind of the chain happens inside: the tables its expressions
    # gather from become the programs' operands.
    with collect_operands() as operands:
        frag = _compile_fragment(
            ops, input_relation, input_dicts, registry, allow_dense,
            col_stats, operands,
        )
    frag.operands = operands
    frag.remap_entries = max(
        (len(o.host) for o in operands.values()), default=0
    )
    return frag


def _compile_fragment(ops, input_relation, input_dicts, registry,
                      allow_dense, col_stats, operands) -> CompiledFragment:
    pre, agg, post, limit = _split_chain(ops)
    apply_pre, rel1, dicts1 = _bind_pre_stage(pre, input_relation, dict(input_dicts), registry)

    if agg is None:
        if post:
            raise AssertionError("post ops without agg should be in pre")
        out_meta = [
            ColumnMeta(name=n, dtype=t, dict=dicts1.get(n)) for n, t in rel1.items()
        ]

        def update(cols, valid):
            return apply_pre(cols, _range_valid(cols, valid))

        return CompiledFragment(
            relation=rel1, out_meta=out_meta, is_agg=False,
            update=_program(update, operands),
            limit=limit, apply_rows=apply_pre,
        )

    return _compile_agg(
        agg, post, limit, apply_pre, rel1, dicts1, registry,
        allow_dense=allow_dense, col_stats=_propagate_stats(pre, col_stats),
        pre_ops=pre, operands=operands,
    )


def _dict_code(p, dom):
    """A dictionary-id or boolean key plane as its code in [0, dom):
    NULL_ID (-1) takes the last sub-slot (``unpack_dense_slots`` is the
    inverse)."""
    return jnp.clip(jnp.where(p < 0, dom - 1, p).astype(jnp.int32), 0, dom - 1)


def unpack_dense_slots(iota, doms, col_types, xp, offsets=None, strides=None):
    """Dense slot indices -> per-group-col key planes.

    The single source of the unpack arithmetic, shared by the traced
    finalize (xp=jnp) and the bridge-payload expansion (xp=np) so the
    packing order / NULL encoding can never diverge between them.
    ``offsets`` shifts stats-derived integer codes back to their values;
    ``strides`` scales step-indexed codes (binned time keys) back.
    """
    import numpy as np

    planes = []
    pack = 1
    for d in doms:
        pack *= d
    offsets = offsets or (0,) * len(doms)
    strides = strides or (1,) * len(doms)
    for dt, dom, off, st in zip(col_types, doms, offsets, strides):
        pack //= dom
        code = (iota // pack) % dom
        if dt == DataType.BOOLEAN:
            planes.append(code.astype(np.bool_))
        elif dt in INT_KEY_TYPES:
            planes.append((code * st + off).astype(np.int64))
        else:  # STRING: last sub-slot decodes back to NULL_ID (-1)
            planes.append(
                xp.where(code == dom - 1, -1, code).astype(np.int32)
            )
    return planes


# Stats bounds round outward to this grain so ordinary appends (which
# nudge a column's min/max) neither change the compiled domain nor churn
# the fragment cache; only growth past the grain recompiles.
_STATS_Q = 4096


def _round_stat_bounds(lo: int, hi: int, stride: int = 1) -> tuple:
    """Round bounds outward to the _STATS_Q grain IN STRIDE STEPS, so the
    rounded lo keeps the values' residue class (the dense packing divides
    by the stride exactly)."""
    if stride <= 1:
        return (lo - lo % _STATS_Q, hi - hi % _STATS_Q + _STATS_Q - 1, 1)
    lo_r = lo - ((lo // stride) % _STATS_Q) * stride
    hi_r = hi + (_STATS_Q - 1 - (hi // stride) % _STATS_Q) * stride
    return (lo_r, hi_r, stride)


def _static_key_domains(rel1, dicts1, group_cols, col_stats=None):
    """Per-column (domain size, value offset, value stride) triples, or
    None when any column's domain is not known at compile time.

    Dictionary-encoded STRING columns have exactly ``len(dict) + 1``
    possible device codes (ids 0..len-1 plus NULL_ID), BOOLEANs two.
    Integer/time keys are dense when the table store's append-time
    min/max stats (``Table.col_stats``) bound them: the domain is
    [min, max] and the offset shifts values to zero-based codes. Rows
    outside a stats-derived domain (appends racing the query) flag
    overflow, and the engine's rebucket retry recompiles against fresh
    stats. Float keys have no dense form -> None.
    """
    doms = []
    for c in group_cols:
        dt = rel1.col_type(c)
        if dt == DataType.STRING and dicts1.get(c) is not None:
            doms.append((len(dicts1[c]) + 1, 0, 1))  # last slot = NULL_ID
        elif dt == DataType.BOOLEAN:
            doms.append((2, 0, 1))
        elif (
            dt in (DataType.INT64, DataType.TIME64NS)
            and col_stats
            and c in col_stats
        ):
            lo, hi, stride = _round_stat_bounds(*col_stats[c])
            if hi - lo + 1 <= 0:
                return None
            doms.append(((hi - lo) // stride + 1, lo, stride))
        else:
            return None
    return doms


def _pure_select_map(pre):
    """out col -> source table col when the pre-stage is only pure
    column-select/rename Maps (the shape column pruning emits); None when
    any real computation or filtering happens before the aggregate."""
    mapping = None  # None = identity so far
    for op in pre:
        if not isinstance(op, MapOp) or not all(
            isinstance(e, ColumnRef) for _n, e in op.exprs
        ):
            return None
        new = {}
        for n2, e in op.exprs:
            src = e.name if mapping is None else mapping.get(e.name)
            if src is None:
                return None
            new[n2] = src
        mapping = new
    return mapping if mapping is not None else {}


#: Registers of a keyed fold's joint-key sketch: 2^12, ~1.6 % error.
_SKETCH_P = 12


class _Fold(NamedTuple):
    """What differs between the three folds (``fold_plan.py``), built by
    ``_dense_fold`` / ``_sorted_fold`` / ``_id_fold``; they share no state
    format beyond the {keys, valid, carries, overflow} pytree."""

    init_keys: object  # () -> ``state["keys"]`` of an empty state
    window: object  # (cols, valid), pre-stage applied -> the window's state
    merge: object  # (state_a, state_b) -> merged state
    key_planes: object  # state -> [g] key planes, ``key_plane_index`` order
    ride: object = None  # ``CompiledFragment.ride`` (the sorted fold's)
    # (state, cols, valid), pre-stage applied -> the state with the window
    # folded in; None: ``merge(state, window(cols, valid))``.
    absorb: object = None
    # [k >= 2 states, each at its own length] -> (merged state, {counts}):
    # the Kelvin's k-way fold (``exec/bridge.py``); None: ``merge`` folded
    # over the states padded to g.
    merge_many: object = None


def _dense_slot_ids(plan, rel1, key_plane_index, cols, valid):
    """Packed key code per row + out-of-domain flag.

    slot = sum(code_i * stride_i); NULL_ID (-1) string codes land in
    each column's last sub-slot and masked rows in the trash slot g.
    Stats-derived integer codes are offset to zero base; a row whose
    value escaped the compile-time [min, max] (an append racing the
    query) goes to the trash slot and raises ``oob`` so the engine's
    rebucket retry recompiles against fresh stats.
    """
    slot = None
    oob = None
    for (c, _i), dom, off, st in zip(
        key_plane_index, plan.domains, plan.offsets, plan.strides
    ):
        p = cols[c][0]
        if rel1.col_type(c) in INT_KEY_TYPES:
            raw = p - off
            if st > 1:
                # Strided domain (binned time keys): the slot is the
                # step index; off-grid values (appends racing the
                # stats) are out-of-domain, not silently misbinned.
                out = (raw < 0) | (raw >= dom * st) | (raw % st != 0)
                raw = raw // st
            else:
                out = (raw < 0) | (raw >= dom)
            oob = out if oob is None else (oob | out)
            code = jnp.clip(raw, 0, dom - 1).astype(jnp.int32)
        else:
            code = _dict_code(p, dom)
        slot = code if slot is None else slot * jnp.int32(dom) + code
    if oob is None:
        oob_any = jnp.zeros((), dtype=jnp.bool_)
        keep = valid
    else:
        oob = oob & valid
        oob_any = jnp.any(oob)
        keep = valid & ~oob
    # ONE select to the trash slot (several chained wheres over [n]
    # i64 planes cost real memory bandwidth at window scale).
    return jnp.where(keep, slot, plan.slots).astype(jnp.int32), oob_any


def _uda_window_carries(aggs_bound, g, gids, cols, valid, carries_w):
    """``uda.update`` of a fresh carry, for every aggregate that
    ``carries_w`` does not hold yet (the XLA route)."""
    for ae, uda, arg_bound, casts in aggs_bound:
        if ae.out_name in carries_w:
            continue
        args = [
            apply_cast(b.fn(cols), have, want)
            for b, (have, want) in zip(arg_bound, casts)
        ]
        args = [jnp.broadcast_to(a, valid.shape) for a in args]
        carries_w[ae.out_name] = uda.update(uda.init(g), gids, valid, *args)
    return carries_w


def _dense_fold(plan, aggs_bound, rel1, key_plane_index) -> _Fold:
    """Static dense key domain: when every group column's device code has
    a statically-known small domain, the PACKED CODE is the group id —
    no per-window sort or hash, and state merges are slot-aligned
    (regroup-free), the shape XLA/TPU executes best. Carnot has no
    analog (its RowTuple hash map is domain-oblivious,
    ``src/carnot/exec/agg_node.h:66``); this is the TPU-first design.
    Integer keys qualify through the table store's append-time min/max
    stats (a bincount-class scatter replaces hash probing); they get a
    larger domain budget because a single int column can't suffer the
    multi-key packing blowup the base limit protects against.

    count/sum/mean/max/min route PER AGGREGATE (``plan.routes``) through
    the hand-scheduled kernels of ops/pallas_groupby.py — one-hot
    contractions with VMEM-resident [G] accumulators instead of per-UDA
    sorts, gathers and scatters. INT64 / BOOLEAN / TIME64NS arguments
    take the exact integer kernel (limb contractions, carries stay i64),
    FLOAT64 arguments the f32 kernel; anything else (``quantiles``...)
    keeps its ``uda.update`` and vetoes nothing. The row block is the
    window's to decide."""
    g = plan.slots
    routes = dict(plan.routes)
    count_route = plan.count_route
    g_pad = -(-g // 128) * 128  # f32 kernel's lane alignment
    g_int = _routes.int_fold_groups(g)  # integer kernel's lanes and group blocks
    kernels = (set(routes.values()) | {count_route}) & {
        "pallas_int", "pallas_f32"
    }
    if kernels:
        # Imported only here: pulling in Pallas costs a second, which a
        # process that never runs the kernels must not pay mid-query.
        from ..ops.pallas_groupby import (
            dense_group_fold,
            dense_group_fold_int,
            fold_row_chunk,
            int_fold_blocks,
        )

        interpret = _routes.kernels_interpreted()

    def _pallas_window_carries(gids, cols, valid):
        """Carries of the aggregates the kernels take on this window, as
        ``uda.update`` of a fresh state would give them, and the window's
        per-slot row count (None when no kernel ran: a window with no row
        block the chip's tiling accepts stays whole on the XLA fold)."""
        n = valid.shape[0]
        by_route = {"pallas_int": [], "pallas_f32": []}
        for ab in aggs_bound:
            if ab[0].uda_name != "count" and routes[ab[0].out_name] in by_route:
                by_route[routes[ab[0].out_name]].append(ab)
        f32_chunk = (
            fold_row_chunk(n, g_pad)
            if by_route["pallas_f32"] or count_route == "pallas_f32" else None
        )
        int_blocks = (
            int_fold_blocks(n, g_int)
            if by_route["pallas_int"] or count_route == "pallas_int" else None
        )

        def arg_plane(ae, arg_bound, casts):
            a = apply_cast(arg_bound[0].fn(cols), *casts[0])
            return jnp.broadcast_to(a, valid.shape)

        carries_w = {}
        cnt = None
        if f32_chunk is not None:
            # Trash rows must match NO kernel column, incl. the pad range.
            gids_p = jnp.where(gids >= g, jnp.int32(g_pad), gids)
            need_min = any(
                ae.uda_name == "min" for ae, _u, _b, _c in by_route["pallas_f32"]
            )
            # One kernel pass per distinct ARG EXPRESSION (sum+mean+max
            # over the same column share a single sweep — the kernel
            # returns all three statistics anyway).
            folds: dict = {}
            for ae, uda, arg_bound, casts in by_route["pallas_f32"]:
                fkey = (_struct_key(ae.args), casts[0])
                if fkey not in folds:
                    c, s, mx, mn = dense_group_fold(
                        gids_p, arg_plane(ae, arg_bound, casts), g_pad,
                        chunk=f32_chunk, interpret=interpret,
                        want_min=need_min,
                    )
                    folds[fkey] = (
                        c[:g], s[:g], mx[:g], mn[:g] if mn is not None else None
                    )
                cnt, s, mx, mn = folds[fkey]
                init_leaf = uda.init(g)
                if ae.uda_name == "sum":
                    carries_w[ae.out_name] = s.astype(init_leaf.dtype)
                elif ae.uda_name == "mean":
                    carries_w[ae.out_name] = (
                        s.astype(init_leaf[0].dtype),
                        cnt.astype(init_leaf[1].dtype),
                    )
                else:  # max/min: empty slots keep the UDA's neutral fill
                    ext = mx if ae.uda_name == "max" else mn
                    carries_w[ae.out_name] = jnp.where(
                        cnt > 0, ext.astype(init_leaf.dtype), init_leaf
                    )
        if int_blocks is not None and (by_route["pallas_int"] or cnt is None):
            # ONE kernel call for the AggOp: every distinct argument's
            # sum and extreme, and the count, off the same one-hot.
            # (insertion-ordered: key -> position among the kernel's
            # sum / extreme arguments).
            sum_at, ext_at, planes = {}, {}, {}
            for ae, _uda, arg_bound, casts in by_route["pallas_int"]:
                fkey = (_struct_key(ae.args), casts[0])
                if fkey not in planes:
                    planes[fkey] = arg_plane(ae, arg_bound, casts)
                if ae.uda_name in ("sum", "mean"):
                    sum_at.setdefault(fkey, len(sum_at))
                else:
                    ext_at.setdefault((fkey, ae.uda_name == "max"), len(ext_at))
            cnt, sums, exts = dense_group_fold_int(
                jnp.where(gids >= g, jnp.int32(g_int), gids),
                tuple(planes[k] for k in sum_at),
                tuple(planes[k] for k, _mx in ext_at),
                g=g_int, chunk=int_blocks[0], g_block=int_blocks[1],
                ext_max=tuple(mx for _k, mx in ext_at),
                interpret=interpret,
            )
            cnt = cnt[:g]
            for ae, uda, _b, casts in by_route["pallas_int"]:
                fkey = (_struct_key(ae.args), casts[0])
                if ae.uda_name == "sum":
                    carries_w[ae.out_name] = sums[sum_at[fkey]][:g]
                elif ae.uda_name == "mean":
                    carries_w[ae.out_name] = (
                        sums[sum_at[fkey]][:g],
                        cnt.astype(uda.init(g)[1].dtype),
                    )
                else:  # empty slots already read the UDA's neutral fill
                    carries_w[ae.out_name] = exts[
                        ext_at[fkey, ae.uda_name == "max"]
                    ][:g]
        if cnt is not None:
            for ae, uda, _b, _c in aggs_bound:
                if ae.uda_name == "count":
                    carries_w[ae.out_name] = cnt.astype(uda.init(g).dtype)
        return carries_w, cnt

    def window(cols, valid):
        gids, oob = _dense_slot_ids(plan, rel1, key_plane_index, cols, valid)
        # Dense slots cannot overflow by count; stats-derived integer
        # domains overflow only when a row's key escapes the
        # compile-time bounds (oob flags it for the rebucket retry).
        n_w = jnp.where(oob, g + 1, 0).astype(jnp.int32)
        carries_w = {}
        valid_w = None  # a kernel's count, or a count carry, gives it free
        if kernels:
            carries_w, cnt_w = _pallas_window_carries(gids, cols, valid)
            if cnt_w is not None:
                valid_w = cnt_w > 0
        _uda_window_carries(aggs_bound, g, gids, cols, valid, carries_w)
        if valid_w is None:
            # A count aggregate's fresh carry already says which slots
            # saw rows — reuse it instead of paying a third scatter pass
            # over the window.
            cnt_name = next(
                (ae.out_name for ae, uda, _b, _c in aggs_bound
                 if ae.uda_name == "count"),
                None,
            )
            if cnt_name is not None:
                valid_w = carries_w[cnt_name] > 0
            else:
                valid_w = (
                    jnp.zeros(g + 1, dtype=jnp.bool_).at[gids].set(True)[:g]
                )
        return {
            "keys": (),  # implicit: slot index IS the packed key
            "valid": valid_w,
            "carries": carries_w,
            "overflow": n_w > g,
        }

    def merge(sa, sb):
        """Slot-for-slot — no regroup sort at all."""
        carries = {
            ae.out_name: uda.merge(
                sa["carries"][ae.out_name], sb["carries"][ae.out_name]
            )
            for ae, uda, _, _ in aggs_bound
        }
        return {
            "keys": (),
            "valid": sa["valid"] | sb["valid"],
            "carries": carries,
            "overflow": sa["overflow"] | sb["overflow"],
        }

    def key_planes(state):
        """Reconstruct the [g] key planes from the slot index (traced)."""
        return unpack_dense_slots(
            jnp.arange(g, dtype=jnp.int64),
            plan.domains,
            [rel1.col_type(c) for c, _i in key_plane_index],
            jnp,
            offsets=plan.offsets,
            strides=plan.strides,
        )

    return _Fold(lambda: (), window, merge, key_planes)


def _keyed_init_keys(g, rel1, key_plane_index):
    """An empty keyed state's key planes: [g] of each plane's pad value."""
    return lambda: tuple(
        jnp.full(
            g,
            pad_values(rel1.col_type(c))[i],
            dtype=device_dtypes(rel1.col_type(c))[i],
        )
        for c, i in key_plane_index
    )


def _sorted_fold(plan, aggs_bound, rel1, key_plane_index) -> _Fold:
    """Keyed integer fold: a NON-dense key whose aggregates are all in the
    integer set folds by sorting the rows themselves, keys, values and
    carries riding one ``lax.sort`` (ops/groupby.py sorted_group_fold):
    no argsort, no window-long gather or scatter, and the same function
    is the window fold and the merge of two keyed states.

    A ``quantiles`` aggregate beside them (route ``keyed_digest``) rides
    nothing: its [g, K] digest is built by a sort of its own under the
    same key words (``ops/tdigest.py`` ``ordered_batch_to_digest``, slot
    for slot the integer fold's groups), and in a merge each side's
    digests are moved to their groups' new slots (``sorted_slot_ids``, a
    gather of rows) and merged there. ``aggs_bound`` holds the
    aggregates with a carry: the digests of one argument are one."""
    g = plan.slots
    pack_doms = plan.pack_doms
    folded = pack_doms is not None or plan.lead_id
    digest_aggs = [ab for ab in aggs_bound if is_digest(ab[0].uda_name)]
    int_aggs = [ab for ab in aggs_bound if not is_digest(ab[0].uda_name)]
    key_dtypes = [
        device_dtypes(rel1.col_type(c))[i] for c, i in key_plane_index
    ]

    def _key_words(planes):
        """[n] key planes (``state['keys']`` order) -> u32 sort words."""
        if pack_doms is None:
            words = [w for p in planes for w in split_u32(_to_bits(p))]
            if plan.lead_id:
                words[0] = words[0] + jnp.uint32(1)
            return words
        code = None
        for p, dom in zip(planes, pack_doms):
            c = _dict_code(p, dom)
            code = c if code is None else code * jnp.int32(dom) + c
        return [code.astype(jnp.uint32)]

    def _key_planes(words):
        """Inverse of ``_key_words`` on the [g] slots of a folded state."""
        if pack_doms is not None:
            return unpack_dense_slots(
                words[0].astype(jnp.int32), pack_doms,
                [rel1.col_type(c) for c, _i in key_plane_index], jnp,
            )
        if plan.lead_id:
            words = [words[0] - jnp.uint32(1)] + list(words[1:])
        planes, at = [], 0
        for dt in key_dtypes:
            k = 2 if jnp.dtype(dt).itemsize == 8 else 1
            planes.append(join_u32(words[at:at + k], dt))
            at += k
        return planes

    def _counted_state(key_planes, valid, leaves):
        """``sorted_group_fold`` over N partial groups. ``leaves`` maps an
        aggregate's out_name to its statistic planes in carry order, each
        ("sum" | "max" | "min" | "rows", int64[N] or None): (the new [g]
        state, int32[g] the partial groups each slot folded). ``rows`` is
        a window's count (every valid row counts one)."""
        sums, maxes = [], []
        for kinds in leaves.values():
            for kind, v in kinds:
                if kind == "sum" and not any(v is s for s in sums):
                    sums.append(v)
                elif kind in ("max", "min"):
                    v = v if kind == "max" else ~v
                    maxes.append(v)
        with jax.named_scope("sorted_fold"):
            words, valid_g, rows, sums_g, maxes_g, n_groups = sorted_group_fold(
                _key_words(key_planes), valid, sums, maxes, g,
                folded_flag=folded,
            )
        carries = {}
        n_max = 0
        for ae, uda, _b, _c in int_aggs:
            init = uda.init(g)
            init_leaves = init if isinstance(init, tuple) else (init,)
            out = []
            for (kind, v), init_leaf in zip(leaves[ae.out_name], init_leaves):
                if kind == "rows":
                    leaf = rows
                elif kind == "sum":
                    leaf = sums_g[next(i for i, s in enumerate(sums) if s is v)]
                else:
                    leaf = maxes_g[n_max] if kind == "max" else ~maxes_g[n_max]
                    n_max += 1
                out.append(leaf.astype(init_leaf.dtype))
            carries[ae.out_name] = tuple(out) if isinstance(init, tuple) else out[0]
        return {
            "keys": tuple(_key_planes(words)),
            "valid": valid_g,
            "carries": carries,
            "overflow": n_groups > g,
        }, rows

    def _sorted_state(key_planes, valid, leaves):
        """``_counted_state``'s [g] state alone: a window's, a pairwise
        merge's."""
        return _counted_state(key_planes, valid, leaves)[0]

    # A window's statistic planes by aggregate, each (kind, which distinct
    # argument expression): static, so the span can say how they ride.
    # ``any`` is a maximum (``fold_plan._sort_max``); of a STRING it is
    # one of int32 dictionary ids, whose plane keeps its one word.
    spec, plane_dtype = {}, {}
    for ae, _uda, _b, casts in int_aggs:
        if ae.uda_name == "count":
            spec[ae.out_name] = (("rows", None),)
            continue
        fkey = (_struct_key(ae.args), casts[0])
        plane_dtype[fkey] = (
            jnp.int32 if casts[0][1] == DataType.STRING else jnp.int64
        )
        kind = "max" if ae.uda_name == "any" else ae.uda_name
        spec[ae.out_name] = (
            (("sum", fkey), ("rows", None)) if kind == "mean"
            else ((kind, fkey),)
        )

    # The first maximum is a sort key; a sum of its own plane is read off
    # it (a minimum's plane is ``~v``: another plane). The other distinct
    # sum planes ride, by the window's length (``sorted_fold_ride``).
    stats = [s for kinds in spec.values() for s in kinds]
    primary = next((s for s in stats if s[0] in ("max", "min")), None)
    ride_planes = {fkey for kind, fkey in stats if kind == "sum"}
    if primary is not None and primary[0] == "max":
        ride_planes.discard(primary[1])
    n_lead = 1 if pack_doms is not None else sum(
        2 if jnp.dtype(dt).itemsize == 8 else 1 for dt in key_dtypes
    ) + (0 if plan.lead_id else 1)
    ride = functools.partial(
        _routes.sorted_fold_ride, g=g, planes=len(ride_planes),
        key_words=n_lead + (
            0 if primary is None
            else 1 if plane_dtype[primary[1]] == jnp.int32 else 2
        ),
    )

    def arg_planes(cols, valid):
        planes = {}  # one plane a distinct argument expression
        for ae, _uda, arg_bound, casts in int_aggs:
            for _kind, fkey in spec[ae.out_name]:
                if fkey is not None and fkey not in planes:
                    a = apply_cast(arg_bound[0].fn(cols), *casts[0])
                    planes[fkey] = jnp.broadcast_to(
                        a, valid.shape).astype(plane_dtype[fkey])
        return planes

    def window_digests(key_planes, cols, valid):
        """{out_name: the window's [g, K] digest} of the digests that
        hold a carry (one a distinct argument): one sort each, under the
        integer fold's own lead words."""
        lead = lead_words(_key_words(key_planes), valid, folded)
        carries = {}
        for ae, _uda, arg_bound, casts in digest_aggs:
            values = jnp.broadcast_to(
                apply_cast(arg_bound[0].fn(cols), *casts[0]), valid.shape
            )
            with jax.named_scope("keyed_digest"):
                carries[ae.out_name] = ordered_batch_to_digest(
                    lead, folded, values, g
                )
        return carries

    def window(cols, valid):
        planes = arg_planes(cols, valid)
        leaves = {
            out: tuple((kind, planes.get(fkey)) for kind, fkey in kinds)
            for out, kinds in spec.items()
        }
        key_planes = [cols[c][i] for c, i in key_plane_index]
        state = _sorted_state(key_planes, valid, leaves)
        if digest_aggs:
            state["carries"].update(window_digests(key_planes, cols, valid))
        return state

    def merge_digests(sa, sb, key_planes, valid):
        """{out_name: merged digest}: where each side's slots go in the
        merged state, their digests' rows taken there (a slot no side
        fills reads the empty digest), then the digests' own merge."""
        with jax.named_scope("digest_slots"):
            dest = sorted_slot_ids(_key_words(key_planes), valid, g, folded)
        ga = sa["valid"].shape[0]

        def sources(dest):
            """The source slot of each merged slot (its own count where
            this side fills it not: the empty row below)."""
            n = dest.shape[0]
            return jnp.full(g + 1, n, jnp.int32).at[dest].set(
                jnp.arange(n, dtype=jnp.int32)
            )[:g]

        def placed(carry, src):
            return tuple(
                jnp.take(jnp.concatenate([p, jnp.zeros_like(p[:1])]), src,
                         axis=0)
                for p in carry
            )

        src_a, src_b = sources(dest[:ga]), sources(dest[ga:])
        carries = {}
        for ae, _uda, _b, _c in digest_aggs:
            with jax.named_scope("digest_merge"):
                carries[ae.out_name] = digest_merge(
                    placed(sa["carries"][ae.out_name], src_a),
                    placed(sb["carries"][ae.out_name], src_b),
                )
        return carries

    def merge(sa, sb):
        # Slots are partial groups like rows are: one concatenation,
        # one fold. Neither side need be key-sorted (the Kelvin's
        # arrive remapped into its canonical dictionary).
        def cat(a, b):
            return jnp.concatenate([jnp.asarray(a), jnp.asarray(b)])

        leaves = {}
        for ae, uda, _b, _c in int_aggs:
            ca, cb = sa["carries"][ae.out_name], sb["carries"][ae.out_name]
            if ae.uda_name == "mean":
                leaves[ae.out_name] = (
                    ("sum", cat(ca[0], cb[0])), ("sum", cat(ca[1], cb[1])),
                )
            else:
                (kind, _fkey), = spec[ae.out_name]
                kind = kind if kind in ("max", "min") else "sum"
                leaves[ae.out_name] = ((kind, cat(ca, cb)),)
        key_planes = [cat(a, b) for a, b in zip(sa["keys"], sb["keys"])]
        valid = cat(sa["valid"], sb["valid"])
        merged = _sorted_state(key_planes, valid, leaves)
        if digest_aggs:
            merged["carries"].update(merge_digests(sa, sb, key_planes, valid))
        merged["overflow"] = (
            merged["overflow"] | sa["overflow"] | sb["overflow"]
        )
        return merged

    def moved_digests(states, key_planes, valid, contended):
        """({out_name: merged digest}, ``merge_ordered`` runs) of a k-way
        fold: ONE ``sorted_slot_ids`` over the N partial groups says where
        each goes. A merged slot one state fills takes that state's [K]
        rows as they came, bit for bit: one move of N rows. A slot two or
        more fill (``contended``, bool[g]) is re-binned by
        ``merge_ordered`` folded over the states that fill it, in their
        order, and the program runs that fold only when such a slot
        exists: the rule is a slot's own."""
        with jax.named_scope("digest_slots"):
            dest = sorted_slot_ids(_key_words(key_planes), valid, g, folded)
        n = dest.shape[0]
        iota = jnp.arange(n, dtype=jnp.int32)

        def sources(lo, hi):
            """The row of [lo, hi) that fills each merged slot (n, the
            empty row below, where none does)."""
            return jnp.full(g + 1, n, jnp.int32).at[dest[lo:hi]].set(
                iota[lo:hi])[:g]

        rows = {
            ae.out_name: tuple(
                jnp.concatenate([*planes, jnp.zeros_like(planes[0][:1])])
                for planes in zip(*(s["carries"][ae.out_name] for s in states))
            )
            for ae, _uda, _b, _c in digest_aggs
        }

        def placed(src):
            return {out: tuple(jnp.take(p, src, axis=0) for p in carry)
                    for out, carry in rows.items()}

        def rebinned(moved):
            ends = [0]  # where each state's rows lie among the N
            for s in states:
                ends.append(ends[-1] + s["valid"].shape[0])
            src = jnp.stack(
                [sources(lo, hi) for lo, hi in zip(ends, ends[1:])])

            def fold(acc, src_b):
                b, out = placed(src_b), {}
                for name, (ma, wa) in acc.items():
                    mb, wb = b[name]
                    has_a = jnp.any(wa > 0, axis=-1, keepdims=True)
                    has_b = jnp.any(wb > 0, axis=-1, keepdims=True)
                    with jax.named_scope("digest_merge"):
                        merged = merge_ordered((ma, wa), (mb, wb))
                    out[name] = tuple(
                        jnp.where(has_a & has_b, m, jnp.where(has_b, pb, pa))
                        for m, pa, pb in zip(merged, (ma, wa), (mb, wb))
                    )
                return out, None

            acc, _ = jax.lax.scan(fold, placed(src[0]), src[1:])
            return {
                name: tuple(jnp.where(contended[:, None], a, m)
                            for a, m in zip(acc[name], moved[name]))
                for name in moved
            }

        any_contended = jnp.any(contended)
        carries = jax.lax.cond(
            any_contended, rebinned, lambda moved: moved, placed(sources(0, n)))
        runs = (len(states) - 1) * len(rows)
        return carries, jnp.where(any_contended, runs, 0).astype(jnp.int32)

    def merge_many(states):
        """(merged state, {``contended_slots``, ``rebins``}) of k >= 2
        states, each at its own length, in ONE fold: their slots are N
        partial groups, concatenated as they came and folded by one set
        of sorts into the g slots (no pad to g, no k - 1 pairwise merges
        at 2 g); the digests follow by ``moved_digests``. What the
        pairwise ``merge`` folded over the states gives, but for a digest
        no other state joins, which is the one that was shipped."""
        def cat(*xs):
            return jnp.concatenate([jnp.asarray(x) for x in xs])

        leaves = {}
        for ae, _uda, _b, _c in int_aggs:
            cs = [s["carries"][ae.out_name] for s in states]
            if ae.uda_name == "mean":
                leaves[ae.out_name] = (
                    ("sum", cat(*(c[0] for c in cs))),
                    ("sum", cat(*(c[1] for c in cs))),
                )
            else:
                (kind, _fkey), = spec[ae.out_name]
                kind = kind if kind in ("max", "min") else "sum"
                leaves[ae.out_name] = ((kind, cat(*cs)),)
        key_planes = [cat(*ks) for ks in zip(*(s["keys"] for s in states))]
        valid = cat(*(s["valid"] for s in states))
        merged, filled = _counted_state(key_planes, valid, leaves)
        contended = filled > 1
        rebins = jnp.zeros((), jnp.int32)
        if digest_aggs:
            digests, rebins = moved_digests(
                states, key_planes, valid, contended)
            merged["carries"].update(digests)
        for s in states:
            merged["overflow"] = merged["overflow"] | s["overflow"]
        return merged, {
            "contended_slots": jnp.sum(contended, dtype=jnp.int32),
            "rebins": rebins,
        }

    def absorb(state, cols, valid):
        """The state with a window folded in. A window long against the
        slots folds alone and merges (``_front``'s rule, n >= 4 g). One
        short against them (2^21 rows into 2^20 slots) would sort as a
        merge does, twice: its rows are partial groups as the state's
        slots are, so they are lifted to carries (a count of one, a sum,
        an extreme or an ``any`` of the row's own value) and folded WITH
        the state, one concatenation and one set of sorts for the two."""
        if valid.shape[0] >= 4 * g:
            return merge(state, window(cols, valid))
        planes = arg_planes(cols, valid)
        one = jnp.ones(valid.shape, jnp.int64)
        carries = {}
        for ae, _uda, _b, _c in aggs_bound:
            lifted = tuple(
                one if fkey is None else planes[fkey]
                for _kind, fkey in spec[ae.out_name]
            )
            have = state["carries"][ae.out_name]
            carries[ae.out_name] = jax.tree_util.tree_map(
                lambda row, slot: row.astype(slot.dtype),
                lifted if isinstance(have, tuple) else lifted[0], have,
            )
        return merge(state, {
            "keys": tuple(cols[c][i] for c, i in key_plane_index),
            "valid": valid, "carries": carries,
            "overflow": jnp.zeros((), jnp.bool_),
        })

    # A digest is no row's carry: with one in the state a window folds
    # alone and merges, whatever its length.
    return _Fold(
        _keyed_init_keys(g, rel1, key_plane_index), window, merge,
        lambda state: state["keys"], ride, None if digest_aggs else absorb,
        merge_many,
    )


def _id_fold(plan, aggs_bound, rel1, key_plane_index) -> _Fold:
    """Keyed fold with group ids in row order (what a ``quantiles`` or a
    FLOAT64 sum needs): per-window ids by the platform's own algorithm —
    XLA's TPU sort is fast while its CPU sort is ~90x slower than
    scatter, so the TPU sorts and the CPU hashes — then ``uda.update``.
    The small [2G] regroup merges always sort."""
    g = plan.slots
    window_group_ids = (
        dense_group_ids_hash if plan.layout == "hashed" else dense_group_ids
    )

    def window(cols, valid):
        key_planes = [cols[c][i] for c, i in key_plane_index]
        with jax.named_scope("group_ids"):
            gids, keys_w, valid_w, n_w = window_group_ids(
                key_planes, valid, g
            )
        return {
            "keys": tuple(keys_w),
            "valid": valid_w,
            "carries": _uda_window_carries(
                aggs_bound, g, gids, cols, valid, {}
            ),
            "overflow": n_w > g,
        }

    def merge(sa, sb):
        with jax.named_scope("regroup"):
            ids_a, ids_b, m_keys, m_valid, n_tot = regroup_pair(
                sa["keys"], sa["valid"], sb["keys"], sb["valid"], g
            )
        carries = {}
        for ae, uda, _, _ in aggs_bound:
            # Neutral carries materialize DURING tracing (never precompute
            # them eagerly): no concrete jax Array may be captured as a
            # jit-closure constant.
            neutral = uda.init(g)
            with jax.named_scope("scatter_carry"):
                ca = scatter_carry(
                    sa["carries"][ae.out_name], ids_a, sa["valid"], g, neutral
                )
                cb = scatter_carry(
                    sb["carries"][ae.out_name], ids_b, sb["valid"], g, neutral
                )
            carries[ae.out_name] = uda.merge(ca, cb)
        overflow = sa["overflow"] | sb["overflow"] | (n_tot > g)
        return {
            "keys": tuple(m_keys),
            "valid": m_valid,
            "carries": carries,
            "overflow": overflow,
        }

    return _Fold(
        _keyed_init_keys(g, rel1, key_plane_index), window, merge,
        lambda state: state["keys"],
    )


def _bind_post_stage(post, out_meta, registry):
    """Bind the Map / Filter ops that follow an aggregate against the
    non-struct view of its output (``out_meta``: group columns, then
    aggregates). Returns (apply, final_meta, relation): ``apply(cols,
    valid)`` takes the aggregate's finalized planes through the ops.

    Struct planes never flow through device post-ops (the planner fuses
    pluck(quantiles(...)) into _quantile_* UDAs instead). Post filters
    keep all columns, so struct columns survive them; a post MapOp is a
    full projection and cannot reference struct columns (binding against
    the non-struct view raises). Shared by a fragment's own finalize and
    the Kelvin's merge program (``exec/bridge.py``), which binds the
    plan's ops after the finalize node against the canonical
    dictionaries of the merged keys."""
    struct_cols = {m.name for m in out_meta if m.struct_fields is not None}
    post_rel = Relation(
        [(m.name, m.dtype) for m in out_meta if m.name not in struct_cols]
    )
    post_dicts = {m.name: m.dict for m in out_meta if m.dict is not None}
    apply_post, post_rel_out, post_dicts_out = _bind_pre_stage(
        post, post_rel, post_dicts, registry
    )
    if post:
        final_meta = [
            ColumnMeta(n, post_rel_out.col_type(n), dict=post_dicts_out.get(n))
            for n in post_rel_out.column_names
        ]
        if not any(isinstance(op, MapOp) for op in post):
            final_meta += [m for m in out_meta if m.struct_fields is not None]
        out_rel = post_rel_out
    else:
        final_meta = out_meta
        out_rel = Relation([(m.name, m.dtype) for m in out_meta])

    def apply(cols, valid):
        device_cols = {n: p for n, p in cols.items() if n not in struct_cols}
        device_cols, valid = apply_post(device_cols, valid)
        for s in struct_cols:
            device_cols[s] = cols[s]
        return device_cols, valid

    return apply, final_meta, out_rel


def _compile_agg(agg: AggOp, post, limit, apply_pre, rel1, dicts1, registry,
                 allow_dense=True, col_stats=None, pre_ops=(), operands=None):
    for c in agg.group_cols:
        if not rel1.has_column(c):
            raise BindError(f"group column {c!r} not in {rel1}")

    # Bind aggregate input expressions and resolve UDAs.
    aggs_bound = []  # (AggExpr, UDADef, [BoundExpr], [cast pairs])
    for ae in agg.aggs:
        arg_bound = [bind_expr(a, rel1, dicts1, registry) for a in ae.args]
        uda: UDADef = registry.get_uda(ae.uda_name, [b.dtype for b in arg_bound])
        casts = list(zip([b.dtype for b in arg_bound], uda.arg_types))
        aggs_bound.append((ae, uda, arg_bound, casts))

    group_cols = list(agg.group_cols)
    key_plane_index = []  # (col, plane_i) per key plane
    for c in group_cols:
        for i in range(len(device_dtypes(rel1.col_type(c)))):
            key_plane_index.append((c, i))

    # The one decision (fold_plan.py), and the one fold it names.
    plan = plan_fold(
        tuple((c, rel1.col_type(c)) for c in group_cols),
        _static_key_domains(rel1, dicts1, group_cols, col_stats),
        tuple(
            (ae.out_name, ae.uda_name, tuple(want for _have, want in casts),
             _struct_key(ae.args))
            for ae, _uda, _b, casts in aggs_bound
        ),
        max_groups=agg.max_groups,
        allow_dense=allow_dense,
        dense_limit=get_flag("dense_domain_limit"),
        int_dense_limit=get_flag("int_dense_domain_limit"),
        platform=_routes.routes_platform(),
    )
    g = plan.slots
    if plan.layout == "dense":
        build = _dense_fold
    elif plan.payload_sort:
        build = _sorted_fold
    else:
        build = _id_fold
    # The aggregates that hold a carry: all but a digest that reads
    # another's (``fold_plan.digest_owners``: the digests of one argument
    # share one). The folds, the state and what ships know these alone;
    # ``finalize`` reads every output off them.
    owner = dict(plan.digest_owners)  # a digest output -> its carry's name
    carried = [
        ab for ab in aggs_bound
        if owner.get(ab[0].out_name, ab[0].out_name) == ab[0].out_name
    ]
    fold = build(plan, carried, rel1, key_plane_index)

    def init_state():
        keys = fold.init_keys()
        carries = {ae.out_name: uda.init(g) for ae, uda, _, _ in carried}
        return {
            "keys": keys,
            "valid": jnp.zeros(g, dtype=jnp.bool_),
            "carries": carries,
            "overflow": jnp.zeros((), dtype=jnp.bool_),
        }

    def window_state(cols, valid):
        """Fold one window of rows into a fresh [G]-slot group state.

        ``valid`` is a bool[n] mask or a (lo, hi) row-range scalar pair
        (the device-resident-window form)."""
        valid = _range_valid(cols, valid)
        cols, valid = apply_pre(cols, valid)
        return fold.window(cols, valid)

    def merge_states(sa, sb):
        """Associative merge of two group states (slot orders may differ).

        This single function is both the window accumulator and the
        distributed finalize: per-device partial states gathered over the
        mesh merge through it, replacing Carnot's UDA Serialize -> GRPC ->
        finalize-agg pipeline (``planner/distributed/splitter/partial_op_mgr``).
        """
        return fold.merge(sa, sb)

    # ``update``, ``update_all``, ``group_sketch`` and ``finalize`` become
    # programs at the end, once every expression of the chain is bound
    # and the operand tables they read are known (``_program``).
    def update(state, cols, valid, at=None):
        cols = _sliced(cols, at)
        valid = _range_valid(cols, valid)
        cols, valid = apply_pre(cols, valid)
        if fold.absorb is not None:
            return fold.absorb(state, cols, valid)
        return fold.merge(state, fold.window(cols, valid))

    def update_all(state, cols_list, los, his, at=None):
        """Fold MANY equal-capacity windows in ONE program: stack the
        per-window planes on device and lax.scan the window fold. One
        dispatch replaces W of them; XLA overlaps the scan iterations'
        memory traffic.

        ``cols_list`` is a tuple of per-window cols dicts; ``los``/``his``
        are i32[W] row-range bounds (the mask builds in-program); ``at``
        (a ``RowSlice`` of i32[W] starts) cuts every window to one length
        before the stack, so the stack and the scan carry that many rows.
        Query-constant side inputs (``__side__``, the fused-lookup-join
        build tables) are identical across windows and must NOT be
        stacked W times — they lift out and rejoin inside the scan body.
        """
        side = None
        stripped = []
        for w, c in enumerate(cols_list):
            c = dict(c)
            s = c.pop("__side__", None)
            side = side if side is not None else s
            if at is not None:
                c = _sliced(c, RowSlice(at.start[w], at.rows))
            stripped.append(c)
        stacked = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *stripped
        )

        def body(st, xs):
            c, lo, hi = xs
            if side is not None:
                c = {**c, "__side__": side}
            return update(st, c, (lo, hi)), None

        out, _ = jax.lax.scan(body, state, (stacked, los, his))
        return out

    group_sketch = None
    if plan.layout != "dense" and group_cols:
        def group_sketch(registers, cols, valid):
            valid = _range_valid(cols, valid)
            cols, valid = apply_pre(cols, valid)
            joint = None  # one u64 a row, a bijective mix a key plane
            for c, i in key_plane_index:
                bits = _to_bits(cols[c][i]).astype(jnp.uint64)
                joint = bits if joint is None else hll._splitmix64(joint) ^ bits
            with jax.named_scope("group_sketch"):
                return hll.hll_update(
                    registers, jnp.zeros(valid.shape, jnp.int32), valid,
                    jnp.broadcast_to(joint, valid.shape), p=_SKETCH_P,
                )

    # Output: group cols then agg outputs (struct sketches keep a [G, k]
    # plane; they are host-materialized and opaque to post ops).
    out_meta = [
        ColumnMeta(name=c, dtype=rel1.col_type(c), dict=dicts1.get(c))
        for c in group_cols
    ]
    for ae, uda, arg_bound, _ in aggs_bound:
        if uda.struct_fields:
            out_meta.append(
                ColumnMeta(
                    name=ae.out_name, dtype=uda.return_type,
                    struct_fields=uda.struct_fields,
                )
            )
        else:
            d = arg_bound[0].dict if (
                uda.return_type == DataType.STRING and arg_bound
            ) else None
            out_meta.append(ColumnMeta(name=ae.out_name, dtype=uda.return_type, dict=d))

    apply_post, final_meta, out_rel = _bind_post_stage(post, out_meta, registry)

    # A digest's outputs by the carry they read, each with the points its
    # read-out asks for: a shared digest is read ONCE, at all of them.
    readers: dict = {}
    for ae, uda, _b, _c in aggs_bound:
        if ae.out_name in owner:
            readers.setdefault(owner[ae.out_name], []).append(
                (ae.out_name, quantile_points(ae.uda_name),
                 bool(uda.struct_fields))
            )

    def digest_reads(carries):
        """{out_name: a digest output's plane} ([g], or [g, points] for
        an unplucked ``quantiles``): a column a point of one
        ``digest_quantile`` a carry, as ``uda.finalize`` reads each."""
        reads = {}
        for own, outs in readers.items():
            q = digest_quantile(
                carries[own], tuple(p for _o, pts, _s in outs for p in pts)
            )
            at = 0
            for out, pts, struct in outs:
                reads[out] = q[:, at:at + len(pts)] if struct else q[:, at]
                at += len(pts)
        return reads

    def finalize(state):
        cols = {}
        key_planes = fold.key_planes(state)
        for c in group_cols:
            cols[c] = tuple(
                kp for kp, (kc, _i) in zip(key_planes, key_plane_index)
                if kc == c
            )
        reads = digest_reads(state["carries"])
        for ae, uda, _, _ in aggs_bound:
            out = reads[ae.out_name] if ae.out_name in reads else (
                uda.finalize(state["carries"][ae.out_name]))
            cols[ae.out_name] = (out,)
        device_cols, valid = apply_post(cols, state["valid"])
        return device_cols, valid, state["overflow"]

    string_carry_sources = []
    for ae, uda, arg_bound, _ in aggs_bound:
        if (
            uda.return_type == DataType.STRING
            and not uda.struct_fields
            and any(b.dtype == DataType.STRING for b in arg_bound)
        ):
            string_carry_sources.append(
                (ae.out_name, tuple(_expr_columns(ae.args)))
            )

    # Native-fold seam: dense-domain fragments whose aggregates all have
    # associative scalar carries hand the scatter passes to the CPU
    # multi-core kernel; XLA keeps the elementwise pre-stage + slot-id
    # packing (engine._fold_agg_state_native).
    native_fold = None
    if plan.layout == "dense" and all(
        (
            ae.uda_name in ("count", "sum", "mean", "min", "max")
            or ae.uda_name == "quantiles"
            or ae.uda_name.startswith("_quantile_")
        )
        and len(arg_bound) == 1
        for ae, _uda, arg_bound, _casts in aggs_bound
    ):
        def fold_inputs(cols, valid):
            valid = _range_valid(cols, valid)
            cols2, valid2 = apply_pre(cols, valid)
            gids, oob = _dense_slot_ids(
                plan, rel1, key_plane_index, cols2, valid2
            )
            args = []
            for ae, _uda, arg_bound, casts in carried:
                if ae.uda_name == "count":
                    args.append(None)  # count reads no value column
                    continue
                b, (have, want) = arg_bound[0], casts[0]
                a = apply_cast(b.fn(cols2), have, want)
                args.append(jnp.broadcast_to(a, valid2.shape))
            return gids, tuple(args), oob

        # Raw mode: when the pre-stage is a pure column select and every
        # key/arg is a direct table column, the kernel reads the STAGED
        # PLANES themselves — zero device work in the fold path.
        raw = None
        sel = _pure_select_map(pre_ops)
        if sel is not None:
            def _src(c):
                return c if not sel else sel.get(c)

            key_specs, key_srcs = [], []
            for (c, pi), dom, off, st in zip(
                key_plane_index, plan.domains, plan.offsets, plan.strides
            ):
                dt = rel1.col_type(c)
                src = _src(c)
                if src is None or pi != 0 or len(device_dtypes(dt)) != 1:
                    key_srcs = None
                    break
                if dt == DataType.STRING:
                    kind = 0
                elif dt == DataType.BOOLEAN:
                    kind = 1
                else:
                    kind = 2
                key_specs.append((kind, dom, off, st))
                key_srcs.append(src)
            arg_srcs = []
            if key_srcs is not None:
                for ae, _uda, _b, _c in carried:
                    if ae.uda_name == "count":
                        arg_srcs.append(None)
                        continue
                    e = ae.args[0] if ae.args else None
                    src = _src(e.name) if isinstance(e, ColumnRef) else None
                    if src is None or len(device_dtypes(rel1.col_type(e.name))) != 1:
                        arg_srcs = None
                        break
                    arg_srcs.append(src)
            if key_srcs is not None and arg_srcs is not None:
                raw = {
                    "key_cols": tuple(key_srcs),
                    "key_specs": tuple(key_specs),
                    "arg_cols": tuple(arg_srcs),
                }

        native_fold = {
            "inputs_jit": _program(fold_inputs, operands),
            "plan": tuple(
                (ae.out_name, ae.uda_name, uda.init)
                for ae, uda, _b, _c in carried
            ),
            "raw": raw,
        }

    return CompiledFragment(
        relation=out_rel,
        out_meta=final_meta,
        is_agg=True,
        update=_program(update, operands),
        update_all=_program(update_all, operands),
        finalize=_program(finalize, operands),
        finalize_state=finalize,
        init_state=init_state,
        init_program=_program(init_state, {}),
        limit=limit,
        window_state=window_state,
        merge_states=merge_states,
        merge_many=fold.merge_many,
        native_fold=native_fold,
        apply_rows=apply_pre,
        key_plane_index=tuple(key_plane_index),
        group_relation=rel1,
        string_carry_sources=tuple(string_carry_sources),
        dense_domains=plan.domains,
        dense_offsets=plan.offsets,
        dense_strides=plan.strides,
        plan=plan,
        fold=plan.fold,
        group=plan.layout,
        slots=g,
        ride=fold.ride,
        group_sketch=(
            _program(group_sketch, operands) if group_sketch else None
        ),
        init_sketch=(
            (lambda: hll.hll_init(1, _SKETCH_P)) if group_sketch else None
        ),
    )


def _expr_columns(exprs):
    """Column names referenced anywhere in a tuple of Expr trees."""
    from .plan import ColumnRef, FuncCall

    out: list[str] = []

    def walk(e):
        if isinstance(e, ColumnRef):
            if e.name not in out:
                out.append(e.name)
        elif isinstance(e, FuncCall):
            for a in e.args:
                walk(a)

    for e in exprs:
        walk(e)
    return out
