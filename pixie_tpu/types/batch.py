"""Columnar batches: host-side staging form and device-resident form.

Reference parity: ``src/table_store/schema/row_batch.h:40`` (RowBatch =
vector of Arrow arrays + eow/eos markers). TPU-first redesign:

- A ``DeviceBatch`` is a *fixed-capacity* set of column planes plus a
  validity mask. Filters flip mask bits instead of producing
  data-dependent shapes (XLA needs static shapes); compaction happens
  only at shard/window boundaries.
- Capacities are bucketed to powers of two (min 1024 = 8 sublanes x 128
  lanes) so streaming windows reuse compiled programs instead of
  recompiling per batch size.
- A logical column is 1-2 physical planes (UINT128 -> hi/lo uint64).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .dtypes import DataType, device_dtypes, from_numpy_dtype, host_dtypes, pad_values
from .relation import Relation
from .strings import StringDictionary

# 8 float32 sublanes x 128 lanes — the minimum TPU tile.
MIN_CAPACITY = 1024


def bucket_capacity(n: int) -> int:
    """Round up to a power of two, at least MIN_CAPACITY."""
    cap = MIN_CAPACITY
    while cap < n:
        cap *= 2
    return cap


Planes = tuple  # tuple of np.ndarray | jnp.ndarray, one per physical plane


@dataclass
class HostBatch:
    """Host-side columnar batch (numpy planes; strings already dict-encoded)."""

    relation: Relation
    cols: dict[str, Planes]
    length: int
    dicts: dict[str, StringDictionary] = field(default_factory=dict)
    # Stream markers (reference: eow/eos on RowBatch).
    eow: bool = False
    eos: bool = False

    @classmethod
    def from_pydict(
        cls,
        data: Mapping[str, Sequence],
        relation: Relation | None = None,
        time_cols: Sequence[str] = ("time_",),
        dicts: Mapping[str, StringDictionary] | None = None,
    ) -> "HostBatch":
        """Build from {col: values}; infers the relation when not given."""
        cols: dict[str, Planes] = {}
        out_dicts: dict[str, StringDictionary] = {}
        rel_items: list[tuple[str, DataType]] = []
        length = None
        for name, values in data.items():
            arr = np.asarray(values)
            if length is None:
                length = len(arr)
            elif len(arr) != length:
                raise ValueError(f"column {name!r} length {len(arr)} != {length}")
            if relation is not None:
                dt = relation.col_type(name)
            else:
                if arr.ndim == 2 and arr.shape[1] == 2 and arr.dtype == np.uint64:
                    dt = DataType.UINT128  # (n, 2) [hi, lo] UPID layout
                else:
                    dt = from_numpy_dtype(arr.dtype, is_time=name in time_cols)
                rel_items.append((name, dt))
            if dt == DataType.STRING:
                if dicts is not None and name in dicts:
                    d = dicts[name]
                else:
                    d = StringDictionary()
                if np.issubdtype(arr.dtype, np.integer):
                    ids = arr.astype(np.int32)  # already dict-encoded
                else:
                    ids = d.encode([str(v) for v in arr])
                out_dicts[name] = d
                cols[name] = (ids,)
            elif dt == DataType.UINT128:
                if arr.ndim == 2 and arr.shape[1] == 2:  # (n, 2) [hi, lo]
                    cols[name] = (
                        arr[:, 0].astype(np.uint64),
                        arr[:, 1].astype(np.uint64),
                    )
                else:  # python ints
                    hi = np.fromiter(((int(v) >> 64) & (2**64 - 1) for v in values), np.uint64, length)
                    lo = np.fromiter((int(v) & (2**64 - 1) for v in values), np.uint64, length)
                    cols[name] = (hi, lo)
            else:
                (hdt,) = host_dtypes(dt)
                cols[name] = (arr.astype(hdt),)
        rel = relation if relation is not None else Relation(rel_items)
        return cls(relation=rel, cols=cols, length=length or 0, dicts=out_dicts)

    @property
    def nbytes(self) -> int:
        """Total plane bytes (the resource-accounting unit for staging
        and bridge-wire costs; dictionary strings not included)."""
        return int(sum(
            p.nbytes for planes in self.cols.values() for p in planes
        ))

    def string_nbytes(self) -> int:
        """UTF-8 bytes of the values the STRING columns' ids stand for
        (what ``to_pydict`` hands a client by reference); null and
        out-of-range ids count nothing."""
        total = 0
        for name, dt in self.relation.items():
            d = self.dicts.get(name)
            if dt != DataType.STRING or d is None or not len(d):
                continue
            lengths = d.byte_lengths()
            ids = self.cols[name][0]
            if not len(ids):
                continue
            if ids.min() < 0 or ids.max() >= len(lengths):
                ids = ids[(ids >= 0) & (ids < len(lengths))]
            total += int(lengths.take(ids).sum(dtype=np.int64))
        return total

    def to_pydict(self, decode_strings: bool = True) -> dict[str, np.ndarray]:
        out: dict[str, np.ndarray] = {}
        for name, dt in self.relation.items():
            planes = self.cols[name]
            if dt == DataType.STRING and decode_strings and name in self.dicts:
                out[name] = self.dicts[name].decode(planes[0])
            elif dt == DataType.UINT128:
                out[name] = np.stack(planes, axis=1)
            else:
                out[name] = planes[0]
        return out

    def to_device(self, capacity: int | None = None, sharding=None) -> "DeviceBatch":
        """Pad to a fixed capacity and place on device.

        ``sharding`` (a jax.sharding.Sharding) places planes row-sharded
        over a mesh — the distributed staging path; None keeps the default
        single-device placement.
        """
        cap = capacity if capacity is not None else bucket_capacity(self.length)
        if cap < self.length:
            raise ValueError(f"capacity {cap} < batch length {self.length}")
        put = (lambda a: jax.device_put(a, sharding)) if sharding is not None else jnp.asarray
        cols: dict[str, Planes] = {}
        for name, dt in self.relation.items():
            pads = pad_values(dt)
            ddts = device_dtypes(dt)
            planes = []
            for plane, pad, ddt in zip(self.cols[name], pads, ddts):
                padded = np.full(cap, pad, dtype=np.dtype(ddt))
                padded[: self.length] = plane
                planes.append(put(padded))
            cols[name] = tuple(planes)
        valid = np.zeros(cap, dtype=np.bool_)
        valid[: self.length] = True
        return DeviceBatch(relation=self.relation, cols=cols, valid=put(valid))


@jax.tree_util.register_pytree_node_class
class DeviceBatch:
    """Fixed-capacity device-resident columnar batch with validity mask.

    Pytree: children = (cols, valid); aux = relation. Safe to pass through
    jit/shard_map; the relation is static metadata.
    """

    __slots__ = ("relation", "cols", "valid")

    def __init__(self, relation: Relation, cols: dict[str, Planes], valid):
        self.relation = relation
        self.cols = cols
        self.valid = valid

    @property
    def capacity(self) -> int:
        return self.valid.shape[-1]

    def n_valid(self):
        return jnp.sum(self.valid.astype(jnp.int32), axis=-1)

    def plane(self, name: str, i: int = 0):
        return self.cols[name][i]

    def with_cols(self, new_cols: Mapping[str, Planes], relation: Relation) -> "DeviceBatch":
        return DeviceBatch(relation=relation, cols=dict(new_cols), valid=self.valid)

    def with_valid(self, valid) -> "DeviceBatch":
        return DeviceBatch(relation=self.relation, cols=self.cols, valid=valid)

    def select(self, names: Sequence[str]) -> "DeviceBatch":
        return DeviceBatch(
            relation=self.relation.select(names),
            cols={n: self.cols[n] for n in names},
            valid=self.valid,
        )

    def to_host(
        self,
        dicts: Mapping[str, StringDictionary] | None = None,
        eow: bool = False,
        eos: bool = False,
    ) -> HostBatch:
        """Copy back to host, compacting to valid rows.

        eow/eos are host-plane stream markers (they never ride the device
        pytree — that would fork compiled programs per marker combination);
        the streaming layer threads them around the device hop.
        """
        valid = np.asarray(self.valid)
        idx = np.nonzero(valid)[0]
        cols: dict[str, Planes] = {}
        for name, dt in self.relation.items():
            hdts = host_dtypes(dt)
            cols[name] = tuple(
                np.asarray(p)[idx].astype(hdt) for p, hdt in zip(self.cols[name], hdts)
            )
        return HostBatch(
            relation=self.relation,
            cols=cols,
            length=len(idx),
            dicts=dict(dicts) if dicts else {},
            eow=eow,
            eos=eos,
        )

    # -- pytree protocol ---------------------------------------------------
    def tree_flatten(self):
        names = self.relation.column_names
        children = (tuple(self.cols[n] for n in names), self.valid)
        return children, self.relation

    @classmethod
    def tree_unflatten(cls, relation: Relation, children):
        col_planes, valid = children
        cols = {n: p for n, p in zip(relation.column_names, col_planes)}
        return cls(relation=relation, cols=cols, valid=valid)

    def __repr__(self) -> str:
        return f"DeviceBatch(capacity={self.capacity}, relation={self.relation})"
