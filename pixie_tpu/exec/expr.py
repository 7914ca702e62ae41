"""Expression binder: Expr tree x Relation x dictionaries -> device closure.

Reference parity: ``src/carnot/exec/expression_evaluator.{h,cc}`` — but
where Carnot walks the expression tree per RowBatch (vector- or
arrow-native, ``expression_evaluator.h:89-91``), here the whole tree is
bound ONCE into a jnp closure that XLA fuses into the fragment program.

Binding rules:
- DEVICE UDFs: recursive bind, implicit casts from the lattice, traced.
- HOST_DICT UDFs: the string argument's dictionary is transformed
  host-side at bind time; the device sees an int32 gather (lookup table
  for scalar returns, id-remap for string returns). A string result's
  image of the dictionary is remembered (``StringDictionary.image``:
  a second bind runs the UDF on no string, a dictionary that grew by k
  on k), and its remap reaches a fragment's programs as an OPERAND, not
  as a literal of their text ("operand tables" below): a column with
  millions of distinct strings neither costs a bind seconds each nor
  writes its dictionary into the program.
- STRING literals are encoded against the sibling argument's dictionary
  (equality filters on unseen literals become id==-1: always false).
"""

from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass
from typing import Callable, Optional

import jax.numpy as jnp
import numpy as np

from ..types.batch import bucket_capacity
from ..types.dtypes import DataType
from ..types.strings import NULL_ID, StringDictionary
from ..udf.registry import Registry
from ..udf.udf import Executor, apply_cast
from . import placement
from . import trace as _trace
from .plan import ColumnRef, Expr, FuncCall, Literal


class BindError(TypeError):
    pass


# -- operand tables ------------------------------------------------------------
# A table a bound expression gathers from and that follows the DATA (a
# dictionary-side UDF's remap: 4 B a distinct string of the column) is
# an operand of the fragment's programs, not a literal in their text:
# the text then depends on the table's bucket alone, so two dictionaries
# of one bucket share a compiled program (the persistent cache hits on
# another table's strings), and the table is uploaded once, not folded
# into every program that reads it.
#
# Bind time: ``compile_fragment`` binds inside ``collect_operands()``;
# a binder with such a table registers it (``_operand``) and gets a name.
# Trace time: the fragment's jitted entry points take the tables as one
# more argument and trace their bodies inside ``operands_bound(tables)``;
# the bound closure asks ``_operand_value(name)`` and gathers from the
# traced argument. Outside both (an expression bound on its own, a
# fragment's unjitted pieces inside another program) the closure falls
# back to the literal, as it always was.

_operands = threading.local()


class Operand:
    """One operand table: the host array (padded to its bucket) and its
    copy on a device, made at the first dispatch there that needs it and
    kept: one a device, for a fragment is shared through the process's
    fragment cache by every engine whose dictionaries read alike, and
    each engine's programs read the copy on ITS device (the scope the
    call runs in: ``exec/placement.py``). Remembered on the image it was
    made from (``DictImage.derived``), so every fragment over one image
    shares the copies."""

    __slots__ = ("host", "_copies", "_lock")

    def __init__(self, host: np.ndarray):
        self.host = host
        self._copies: dict = {}  # device (None: JAX's own) -> array
        self._lock = threading.Lock()

    def device(self):
        where = placement.current()
        copy = self._copies.get(where)
        if copy is None:
            with self._lock:
                copy = self._copies.get(where)
                if copy is None:
                    copy = self._copies[where] = placement.put(self.host)
        return copy


@contextlib.contextmanager
def collect_operands():
    """Bind time: the operand tables registered inside the block, by the
    name their closures ask for ({name: Operand}, in bind order)."""
    prev = getattr(_operands, "collecting", None)
    found: dict = {}
    _operands.collecting = found
    try:
        yield found
    finally:
        _operands.collecting = prev


@contextlib.contextmanager
def operands_bound(values: dict):
    """Trace time: ``values`` ({name: array}) are the tables the closures
    traced inside the block gather from."""
    prev = getattr(_operands, "values", None)
    _operands.values = values
    try:
        yield
    finally:
        _operands.values = prev


def _operand(kind: str, make):
    """Register the table ``make()`` gives with the collecting fragment
    and return its name; None where nothing collects. Names count up in
    bind order, so equal chains name their tables alike."""
    found = getattr(_operands, "collecting", None)
    if found is None:
        return None
    name = f"{kind}:{len(found)}"
    found[name] = make()
    return name


def _operand_value(name):
    values = getattr(_operands, "values", None)
    return None if values is None or name is None else values.get(name)


@dataclass
class BoundExpr:
    """fn(cols: dict[str, planes-tuple]) -> plane array (broadcastable)."""

    fn: Callable
    dtype: DataType
    # For STRING-typed results: the dictionary its int32 ids refer to.
    dict: Optional[StringDictionary] = None


def bind_expr(expr: Expr, relation, dicts, registry: Registry) -> BoundExpr:
    if isinstance(expr, ColumnRef):
        if not relation.has_column(expr.name):
            raise BindError(f"unknown column {expr.name!r} in {relation}")
        dt = relation.col_type(expr.name)
        name = expr.name
        if dt == DataType.UINT128:
            fn = lambda cols: cols[name]  # (hi, lo) tuple
        else:
            fn = lambda cols: cols[name][0]
        return BoundExpr(fn=fn, dtype=dt, dict=dicts.get(name))

    if isinstance(expr, Literal):
        if expr.dtype == DataType.STRING:
            # Encoded later, in FuncCall context (needs a sibling dict).
            raise BindError(
                f"string literal {expr.value!r} outside a function context"
            )
        val = expr.value
        return BoundExpr(fn=lambda cols: jnp.asarray(val), dtype=expr.dtype)

    if isinstance(expr, FuncCall):
        return _bind_func(expr, relation, dicts, registry)

    raise BindError(f"cannot bind expression {expr!r}")


def _bind_func(expr: FuncCall, relation, dicts, registry: Registry) -> BoundExpr:
    # Bind non-string-literal args first to learn types and dictionaries.
    bound: list = [None] * len(expr.args)
    str_literals: list = []
    for i, a in enumerate(expr.args):
        if isinstance(a, Literal) and a.dtype == DataType.STRING:
            str_literals.append(i)
        else:
            bound[i] = bind_expr(a, relation, dicts, registry)

    arg_types = [
        DataType.STRING if i in str_literals else bound[i].dtype
        for i in range(len(expr.args))
    ]
    udf = registry.get_scalar(expr.name, arg_types)

    if udf.executor == Executor.HOST_DICT:
        return _bind_host_dict(expr, udf, bound, str_literals, relation, dicts, registry)

    # DEVICE: ids from different dictionaries are not comparable — align
    # every STRING arg onto one shared dictionary (id-preserving union;
    # later args get a remap gather). The union snapshots the dictionaries
    # at bind time: queries assume no concurrent appends to the source
    # table while executing (the service shell serializes these).
    sibling_dict = None
    for i, b in enumerate(bound):
        if b is None or b.dict is None:
            continue
        if sibling_dict is None:
            sibling_dict = b.dict
        elif b.dict is not sibling_dict:
            merged, _, remap = sibling_dict.union(b.dict)
            remap_j = np.asarray(remap)
            prev_fn = b.fn
            bound[i] = BoundExpr(
                fn=(
                    lambda _f, _r: (
                        lambda cols: jnp.where(
                            (ids := _f(cols)) >= 0,
                            jnp.asarray(_r)[jnp.clip(ids, 0)],
                            NULL_ID,
                        )
                    )
                )(prev_fn, remap_j),
                dtype=DataType.STRING,
                dict=merged,
            )
            sibling_dict = merged

    # Encode string literals against the shared dictionary.
    for i in str_literals:
        lit = expr.args[i]
        if sibling_dict is None:
            raise BindError(
                f"string literal {lit.value!r} in {expr.name} has no sibling "
                "dictionary to encode against"
            )
        lit_id = sibling_dict.lookup(lit.value)
        bound[i] = BoundExpr(
            fn=(lambda _id: (lambda cols: jnp.asarray(_id, dtype=jnp.int32)))(lit_id),
            dtype=DataType.STRING,
            dict=sibling_dict,
        )

    casts = list(zip(arg_types, udf.arg_types))
    arg_fns = [b.fn for b in bound]
    fn_udf = udf.fn

    def fn(cols):
        vals = [apply_cast(f(cols), have, want) for f, (have, want) in zip(arg_fns, casts)]
        return fn_udf(*vals)

    out_dict = None
    if udf.return_type == DataType.STRING:
        out_dict = udf.out_dict if udf.out_dict is not None else sibling_dict
    return BoundExpr(fn=fn, dtype=udf.return_type, dict=out_dict)


def _bind_host_dict(expr, udf, bound, str_literals, relation, dicts, registry) -> BoundExpr:
    """Run the UDF over the dictionary host-side; device applies a gather."""
    d_i = udf.dict_arg
    if d_i in str_literals or bound[d_i] is None or bound[d_i].dict is None:
        raise BindError(
            f"{udf.name}: argument {d_i} must be a string column/expression "
            "with a dictionary"
        )
    src = bound[d_i]
    src_dict = src.dict

    # All other args must be literals (reference: these are Init() args of
    # the C++ UDFs — compile-time constants).
    literal_vals: dict[int, object] = {}
    for i, a in enumerate(expr.args):
        if i == d_i:
            continue
        if not isinstance(a, Literal):
            raise BindError(
                f"{udf.name}: argument {i} must be a literal (host-dict UDF)"
            )
        literal_vals[i] = a.value

    def call_one(s: str):
        args = [literal_vals.get(i) if i != d_i else s for i in range(len(expr.args))]
        return udf.fn(*args)

    src_fn = src.fn
    if udf.return_type == DataType.STRING:
        # The UDF's image of the dictionary, remembered by (the UDF, its
        # literal arguments, the dictionary's content): inside a
        # ``dict_udf`` span on the query's trace.
        key = (udf.fn, tuple(sorted(literal_vals.items())))
        with _trace.dict_udf_span(udf.name, len(src_dict)) as note:
            img, memo, ran = src_dict.image(call_one, key)
            note(strings=ran, memo=memo)
        new_dict, remap = img.dict, img.remap

        def padded() -> Operand:
            # To a bucket, with the null id: ids past the image (rows
            # appended since) read null, and a dictionary that grows
            # inside its bucket asks for no new program.
            if "operand" not in img.derived:
                host = np.full(
                    bucket_capacity(len(remap) + 1), NULL_ID, np.int32
                )
                host[:len(remap)] = remap
                img.derived["operand"] = Operand(host)
            return img.derived["operand"]

        name = _operand("dict_udf", padded)

        def fn(cols):
            ids = src_fn(cols)
            table = _operand_value(name)
            if table is None:
                # jnp.asarray at TRACE time: no concrete jax Array is
                # captured as a jit constant.
                table = jnp.asarray(remap)
            return jnp.where(
                ids >= 0,
                table[jnp.clip(ids, 0, table.shape[0] - 1)], NULL_ID,
            )

        return BoundExpr(fn=fn, dtype=DataType.STRING, dict=new_dict)

    null_value = {
        DataType.BOOLEAN: False,
        DataType.INT64: 0,
        DataType.FLOAT64: float("nan"),
        DataType.TIME64NS: 0,
    }[udf.return_type]
    np_dt = {
        DataType.BOOLEAN: np.bool_,
        DataType.INT64: np.int64,
        DataType.FLOAT64: np.float32,
        DataType.TIME64NS: np.int64,
    }[udf.return_type]
    table = np.asarray([call_one(s) for s in src_dict.strings] + [null_value], dtype=np_dt)
    table_j = table
    k = len(src_dict.strings)

    def fn(cols):
        ids = src_fn(cols)
        safe = jnp.where((ids >= 0) & (ids < k), ids, k)
        return jnp.asarray(table_j)[safe]

    return BoundExpr(fn=fn, dtype=udf.return_type)
