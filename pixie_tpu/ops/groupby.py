"""Group-by machinery: exact groups with static shapes.

Reference parity: Carnot's BlockingAggNode builds an absl flat_hash_map
keyed by RowTuple (``src/carnot/exec/agg_node.h:66``,
``src/carnot/exec/row_tuple.h``). Three exact device strategies for a key
with no dense domain (``exec/fragment.py`` picks; a dense domain needs
none of them, the packed key code is the slot):

- ``sorted_group_fold`` — **the rows ride the sort** (PR 29): keys, values
  and carries are operands of ``lax.sort``, so groups come out adjacent
  with their data; sums are a cumsum differenced at the group ends, an
  extreme is a group's last row, one row a group is compacted into the G
  slots. No row ever gets a group id, so nothing is gathered or scattered
  at window length. It is both the window fold and the merge of two [G]
  states, for aggregates that are exact integer statistics (count / sum /
  mean / max / min of INT64, TIME64NS, BOOLEAN): what ``px/http_stats``
  runs on the TPU when its keys have no dense domain.
- ``dense_group_ids`` — **multi-key lexicographic argsort +
  first-occurrence cumsum**, the ids scattered back to row order: no
  hashing at all. The id form, for aggregates that need a row's group id
  in row order (``quantiles``, FLOAT64 sums, ``any``): their
  ``uda.update`` takes the ids. Also regroups two [G] states
  (``regroup_pair``) for those aggregates and under ``hashed``.
- ``dense_group_ids_hash`` — **bounded-probe open-addressing insert on
  device**: rows claim slots in a 2G-slot table via scatter-min rounds,
  then slot ranks give dense ids. Exact (full keys are compared, the hash
  only picks probe order); O(rounds * n) elementwise work instead of
  O(key_planes) full-window stable sorts — the per-window path on the
  CPU, where XLA's sort is ~90x its scatter. Probe exhaustion reports
  overflow, which the engine's rebucketing doubles away (Carnot's growing
  hash map, ``agg_node.cc``).

Plus the regroup layer of the id form: align two group states (different
slot orders, e.g. accumulated-state x new-window, or per-device partials)
onto a shared dense id space so UDA carries can be merged slot-wise. This
is the TPU replacement for Carnot's partial-agg-serialize -> GRPC ->
finalize-agg pipeline (``planner/distributed/splitter/partial_op_mgr``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .routes import sorted_fold_ride
from .scan import _CHUNK, blocked_cumsum


def _sortable(plane):
    """Map a key plane to its sortable bit view.

    Sorting/grouping happens on bit patterns (``_to_bits``), not values,
    so float keys group exactly by payload — bit-identical NaNs form ONE
    group — matching the hash path. (Value order for negative floats
    differs from numeric order; group *membership* is unaffected and
    callers never rely on group emission order.)
    """
    if plane.dtype == jnp.bool_:
        return plane.astype(jnp.int8)
    if jnp.issubdtype(plane.dtype, jnp.floating):
        return _to_bits(plane)
    return plane


def dense_group_ids(key_planes, mask, max_groups: int):
    """Assign dense group ids by multi-key sort.

    Args:
      key_planes: list of [n] arrays (a UINT128 key contributes two).
      mask: [n] bool; masked rows get id ``max_groups`` (trash slot).
      max_groups: static group capacity G.

    Returns:
      gids: int32[n] in [0, G) for valid rows, G for invalid.
      group_keys: list of [G] arrays — key values per dense id.
      group_valid: bool[G] — slots actually occupied.
      n_groups: int32 scalar — true distinct count (may exceed G; caller
        checks ``n_groups > max_groups`` to detect overflow).
    """
    n = mask.shape[0]
    planes = [_sortable(p) for p in key_planes]

    # Lexicographic stable sort: secondary keys first, primary last, with
    # invalid rows forced to the end via a final sort on ~mask.
    order = jnp.arange(n, dtype=jnp.int32)
    for p in reversed(planes):
        order = order[jnp.argsort(p[order], stable=True)]
    order = order[jnp.argsort(~mask[order], stable=True)]

    sorted_mask = mask[order]
    is_new = jnp.zeros(n, dtype=jnp.bool_)
    for p in planes:
        sp = p[order]
        diff = jnp.concatenate([jnp.ones(1, jnp.bool_), sp[1:] != sp[:-1]])
        is_new = is_new | diff
    is_new = is_new & sorted_mask

    # blocked: a flat window-length i32 cumsum overflows TPU scoped vmem
    # at multi-million-row windows (see ops/scan.py).
    sorted_gid = blocked_cumsum(is_new.astype(jnp.int32)) - 1
    n_groups = jnp.sum(is_new.astype(jnp.int32))
    # Clamp overflowing groups into the last slot; invalid rows -> G.
    sorted_gid_c = jnp.where(
        sorted_mask, jnp.clip(sorted_gid, 0, max_groups - 1), max_groups
    )
    gids = jnp.zeros(n, dtype=jnp.int32).at[order].set(sorted_gid_c)

    # First occurrence (in original row order) of each group -> key values.
    first_idx = jax.ops.segment_min(
        jnp.arange(n, dtype=jnp.int32), gids, num_segments=max_groups + 1
    )[:-1]
    group_valid = first_idx < n
    safe_idx = jnp.where(group_valid, first_idx, 0)
    group_keys = [p[safe_idx] for p in key_planes]
    return gids, group_keys, group_valid, n_groups


def _to_bits(p):
    """Bit-exact unsigned view of a key plane (u32 or u64).

    Comparing bit patterns (not values) makes float keys well-defined for
    NaNs (bit-identical NaNs group together) and costs nothing for ints;
    -0.0 canonicalizes to +0.0 first so both zeros stay one group
    (value-equality semantics, Carnot's RowTuple ==).
    """
    if p.dtype == jnp.bool_:
        return p.astype(jnp.uint32)
    if jnp.issubdtype(p.dtype, jnp.floating):
        p = jnp.where(p == 0, jnp.zeros_like(p), p)
    nbits = p.dtype.itemsize * 8
    if nbits < 32:
        return jax.lax.bitcast_convert_type(
            p.astype(jnp.int32), jnp.uint32
        )
    target = jnp.uint32 if nbits == 32 else jnp.uint64
    return jax.lax.bitcast_convert_type(p, target)


def _from_bits(bits, dtype):
    if dtype == jnp.bool_:
        return bits != 0
    nbits = jnp.dtype(dtype).itemsize * 8
    if nbits < 32:
        return jax.lax.bitcast_convert_type(bits, jnp.int32).astype(dtype)
    return jax.lax.bitcast_convert_type(bits, dtype)


def _mix32(x):
    """32-bit finalizer (lowbias32); wrapping uint32 arithmetic."""
    x ^= x >> jnp.uint32(16)
    x = x * jnp.uint32(0x7FEB352D)
    x ^= x >> jnp.uint32(15)
    x = x * jnp.uint32(0x846CA68B)
    x ^= x >> jnp.uint32(16)
    return x


def _hash_bits(bit_planes):
    h = jnp.full(bit_planes[0].shape, jnp.uint32(0x9E3779B9))
    for b in bit_planes:
        if b.dtype == jnp.uint64:
            h = _mix32(h ^ (b & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32))
            h = _mix32(h ^ (b >> jnp.uint64(32)).astype(jnp.uint32))
        else:
            h = _mix32(h ^ b)
    return h


def _table_size(max_groups: int) -> int:
    size = 16
    while size < 2 * max_groups:
        size *= 2
    return size


def dense_group_ids_hash(key_planes, mask, max_groups: int,
                         max_rounds: int = 32):
    """``dense_group_ids`` via a device-built open-addressing table.

    Same contract as ``dense_group_ids`` except group ids are in hash
    (arbitrary) order rather than key-sorted order. Rows linear-probe a
    2G-slot table: each round, rows whose candidate slot is free race to
    claim it (scatter-min on row index), the winner publishes its key,
    and every row whose candidate slot now holds its exact key resolves.
    Unresolved rows after ``max_rounds`` report overflow (n_groups >
    max_groups) so the caller rebuckets larger.
    """
    n = mask.shape[0]
    if not key_planes:
        # No-group aggregation: every valid row lands in slot 0 (matches
        # the sort path's degenerate behavior).
        gids = jnp.where(mask, 0, max_groups).astype(jnp.int32)
        group_valid = (
            jnp.zeros(max_groups, dtype=jnp.bool_).at[0].set(jnp.any(mask))
        )
        return gids, [], group_valid, jnp.int32(0)
    size = _table_size(max_groups)
    bit_planes = [_to_bits(p) for p in key_planes]
    base = (_hash_bits(bit_planes) & jnp.uint32(size - 1)).astype(jnp.int32)
    iota = jnp.arange(n, dtype=jnp.int32)

    slot_bits0 = tuple(
        jnp.zeros(size + 1, dtype=b.dtype) for b in bit_planes
    )
    occupied0 = jnp.zeros(size + 1, dtype=jnp.bool_)

    def round_body(carry):
        r, active, row_slot, occupied, slot_bits = carry
        cand = (base + r) & jnp.int32(size - 1)
        free = ~occupied[cand]
        contender = active & free
        claim_idx = jnp.where(contender, cand, size)
        claims = (
            jnp.full(size + 1, n, dtype=jnp.int32).at[claim_idx].min(iota)
        )
        winner = contender & (claims[cand] == iota)
        win_idx = jnp.where(winner, cand, size)
        occupied = occupied.at[win_idx].set(True)
        occupied = occupied.at[size].set(False)
        slot_bits = tuple(
            sb.at[win_idx].set(b) for sb, b in zip(slot_bits, bit_planes)
        )
        # Resolve rows whose candidate slot now holds their exact key.
        match = active & occupied[cand]
        for sb, b in zip(slot_bits, bit_planes):
            match = match & (sb[cand] == b)
        row_slot = jnp.where(match, cand, row_slot)
        active = active & ~match
        return r + 1, active, row_slot, occupied, slot_bits

    def round_cond(carry):
        r, active, *_ = carry
        return (r < max_rounds) & jnp.any(active)

    init = (
        jnp.int32(0),
        mask,
        jnp.full(n, -1, dtype=jnp.int32),
        occupied0,
        slot_bits0,
    )
    _, active, row_slot, occupied, slot_bits = jax.lax.while_loop(
        round_cond, round_body, init
    )
    probe_failed = jnp.any(active)

    occ = occupied[:size]
    rank = blocked_cumsum(occ.astype(jnp.int32)) - 1  # [size]
    n_occupied = jnp.sum(occ.astype(jnp.int32))
    n_groups = jnp.where(
        probe_failed, jnp.int32(max_groups + 1), n_occupied
    )
    # Row ids: rank of the row's slot, clamped into [0, G) for valid rows
    # (overflowing ranks land in the last slot, like the sort path);
    # invalid/unresolved rows get the trash slot G.
    resolved = mask & (row_slot >= 0)
    raw_gid = rank[jnp.clip(row_slot, 0, size - 1)]
    gids = jnp.where(
        resolved, jnp.clip(raw_gid, 0, max_groups - 1), max_groups
    ).astype(jnp.int32)

    # Dense [G] key values from occupied slots (rank < G).
    dense_idx = jnp.where(occ & (rank < max_groups), rank, max_groups)
    group_keys = []
    for sb, p in zip(slot_bits, key_planes):
        dense = (
            jnp.zeros(max_groups + 1, dtype=sb.dtype)
            .at[dense_idx]
            .set(sb[:size])[:max_groups]
        )
        group_keys.append(_from_bits(dense, p.dtype))
    group_valid = jnp.arange(max_groups, dtype=jnp.int32) < jnp.minimum(
        n_occupied, max_groups
    )
    return gids, group_keys, group_valid, n_groups


def scatter_rows(arr, ids, valid, capacity: int, fill):
    """Scatter [n]-leading arr rows to slots ``ids`` (unique among valid)."""
    pad_shape = (capacity + 1,) + arr.shape[1:]
    out = jnp.full(pad_shape, fill, dtype=arr.dtype)
    out = out.at[jnp.where(valid, ids, capacity)].set(arr)
    return out[:capacity]


def regroup_pair(keys_a, valid_a, keys_b, valid_b, max_groups: int):
    """Compute a shared dense-id space for two [G]-slot group states.

    Returns (ids_a, ids_b, merged_keys, merged_valid, n_groups): slot i of
    side A maps to merged slot ids_a[i], likewise for B; merged_keys/valid
    describe the union. Carries are then aligned with ``scatter_rows`` /
    ``scatter_carry`` and combined with the UDA's associative merge
    (merge(init, x) == x makes empty slots neutral).
    """
    cat_keys = [jnp.concatenate([a, b]) for a, b in zip(keys_a, keys_b)]
    cat_valid = jnp.concatenate([valid_a, valid_b])
    ids, merged_keys, merged_valid, n_groups = dense_group_ids(
        cat_keys, cat_valid, max_groups
    )
    g = valid_a.shape[0]
    return ids[:g], ids[g:], merged_keys, merged_valid, n_groups


def scatter_carry(carry, ids, valid, capacity: int, init_carry):
    """Align a [G]-leading carry pytree onto merged slots (empty = init)."""
    return jax.tree_util.tree_map(
        lambda arr, init: jnp.concatenate(
            [init, jnp.zeros((1,) + arr.shape[1:], arr.dtype)]
        )
        .at[jnp.where(valid, ids, capacity)]
        .set(arr)[:capacity],
        carry,
        init_carry,
    )


# -- the keyed fold as a payload-carrying sort ---------------------------------
#
# ``dense_group_ids`` sorts an INDEX (argsort) and fetches every plane
# through it: a window-long gather a plane, a window-long scatter for the
# ids, and again for each UDA. On the TPU the sorts are cheap and those
# gathers are not (ledger, PR 28: ``sort`` 0.38 s of 7.55 s busy, the
# fusion around them 7.01 s). ``sorted_group_fold`` lets the rows ride the
# sort instead: ``lax.sort`` takes several operands, the leading ones as
# keys, so after the sort every plane is in group order and what is left
# is elementwise work, scans, and moving one row a group to the front
# (``_front``). No argsort-and-fetch, no window-long gather or scatter.
#
# What shapes the sorts is the TPU compiler's time beside the chip's: at
# 2^21 rows the sums' words as payload operands of the key sort take 5-13
# ms where a row index, an inverse sort and a BATCHED two-operand sort
# ([P, N] along N, the key row repeated) take 25-38, but every operand and
# every key adds seconds to the sort's compile, and at 2^18 rows there are
# 2 ms to win (the sweep: ``ops/routes.py`` ``SORT_PAYLOAD_MAX_OPERANDS``).
# So what rides which sort follows the rows against the slots and the
# operand count (``sorted_fold_ride`` there, static at trace time):
#
# - The group key's words and the primary maximum's are the keys of the
#   one sort, always; a sum of the primary maximum's own plane is read
#   off it.
# - A WINDOW's other sum planes (n >= 4 g: 2^21 rows into 2^17 slots)
#   ride that sort as payload operands (``payload``), where keys and
#   words together stay within ``SORT_PAYLOAD_MAX_OPERANDS``.
# - A MERGE of two states (n = 2 g: short, and a mean's two sums and a
#   count beside the key would make a 7-to-9-operand sort) carries the
#   row index instead: an inverse sort, then the planes
#   follow under that unique key in one batched sort (``index``,
#   ``_batched_sort``). So does a window past the operand limit.
# - One row a group moves to the front by ``_front``, on the same rule.

_U32_MAX = 0xFFFFFFFF
_I32_MAX = 0x7FFFFFFF
_SIGN = 0x80000000


def split_u32(bits):
    """A u32 / u64 bit plane as one / two u32 planes, high word first."""
    if bits.dtype == jnp.uint32:
        return [bits]
    return [(bits >> jnp.uint64(32)).astype(jnp.uint32),
            bits.astype(jnp.uint32)]


def join_u32(words, dtype):
    """Inverse of ``_to_bits`` + ``split_u32`` for a key plane of ``dtype``."""
    if len(words) == 1:
        return _from_bits(words[0], dtype)
    hi, lo = words
    bits = (hi.astype(jnp.uint64) << jnp.uint64(32)) | lo.astype(jnp.uint64)
    return _from_bits(bits, dtype)


def _i64_words(v):
    """INT64 as [hi, lo] u32 whose unsigned lexicographic order is the
    signed order of ``v`` (the sign bit flipped)."""
    u = jax.lax.bitcast_convert_type(v, jnp.uint64)
    hi = (u >> jnp.uint64(32)).astype(jnp.uint32) ^ jnp.uint32(_SIGN)
    return [hi, u.astype(jnp.uint32)]


def _i64_from_words(hi, lo):
    u = ((hi ^ jnp.uint32(_SIGN)).astype(jnp.uint64) << jnp.uint64(32)) | (
        lo.astype(jnp.uint64)
    )
    return jax.lax.bitcast_convert_type(u, jnp.int64)


def _order_words(v):
    """An INT64 / int32 plane as u32 words whose unsigned lexicographic
    order is the signed order of ``v``: two, or one (a maximum of
    dictionary ids is one word)."""
    if v.dtype == jnp.int64:
        return _i64_words(v)
    return [jax.lax.bitcast_convert_type(v, jnp.uint32) ^ jnp.uint32(_SIGN)]


def _max_plane(words):
    """A maximum's sorted order words as the one plane ``_front`` moves
    (u32 or int64): an INT64 as itself, an int32 as its order word."""
    return _i64_from_words(*words) if len(words) == 2 else words[0]


def _max_value(plane):
    """Inverse of ``_max_plane`` on the [g] slots."""
    if plane.dtype == jnp.int64:
        return plane
    return jax.lax.bitcast_convert_type(plane ^ jnp.uint32(_SIGN), jnp.int32)


def _batched_sort(key, words):
    """u32[N] ``words`` in ascending order of the unique int32 ``key``:
    one two-operand sort along the rows of [P, N], the key row repeated.
    Returns (the sorted key, the sorted words)."""
    stacked = jnp.stack(words)
    s_key, out = jax.lax.sort(
        [jnp.broadcast_to(key[None, :], stacked.shape), stacked],
        dimension=1, is_stable=False, num_keys=1,
    )
    return s_key[0], list(out)


def _front(pos, planes, g):
    """The rows whose ``pos`` (unique int32, INT32_MAX where a row is not
    wanted) is smallest, in its order, as g slots: (pos[g], planes[g]);
    ``planes`` are u32 or int64. Slots past the wanted rows hold junk.

    A long window into few slots (N >= 4 g) sorts ``pos`` alone and
    fetches g rows a plane: on the v5e a one-operand 2^21-row sort is 2.2
    ms and a g-long gather 1.9-2.6 ms at g = 2^17, where the batched sort
    of five planes is 24 ms (tools/fold_sweep.py --micro, PR
    29). Otherwise (a merge of two states, N = 2 g) the planes ride one
    batched sort: 3 ms at 2^18 rows.
    """
    n = pos.shape[0]
    if n >= 4 * g:
        pos_g = jnp.sort(pos)[:g]
        at = jnp.minimum(pos_g, n - 1)
        return pos_g, [p[at] for p in planes]
    words = [w for p in planes
             for w in (_i64_words(p) if p.dtype == jnp.int64 else [p])]
    pos_s, words = _batched_sort(pos, words)
    if n >= g:
        pos_g, words = pos_s[:g], [w[:g] for w in words]
    else:
        pos_g = jnp.pad(pos_s, (0, g - n), constant_values=_I32_MAX)
        words = [jnp.pad(w, (0, g - n)) for w in words]
    out = []
    for p in planes:
        if p.dtype == jnp.int64:
            out.append(_i64_from_words(words[0], words[1]))
            words = words[2:]
        else:
            out.append(words[0])
            words = words[1:]
    return pos_g, out


def lead_words(keys, valid, folded_flag: bool) -> list:
    """The u32 words a keyed fold's rows sort by, ahead of anything they
    carry: ``keys`` with "not valid" folded into the first as 0xFFFFFFFF
    (``folded_flag``: a valid row never reads that) or as a flag word of
    its own ahead of them. Valid rows sort first, equal words are one
    group, and the g-th distinct run is slot g: ``sorted_group_fold``,
    ``sorted_slot_ids`` and ``ops/tdigest.py`` ``ordered_batch_to_digest``
    number groups by these words alike."""
    if folded_flag:
        return [jnp.where(valid, keys[0], jnp.uint32(_U32_MAX))] + list(keys[1:])
    return [(~valid).astype(jnp.uint32)] + list(keys)


def sorted_lead_runs(s_lead, folded_flag: bool):
    """(valid, differs) of rows SORTED by their lead words: which rows
    are valid, and bool[N - 1] whether row i + 1 opens a new group (a
    word differs from the row before)."""
    s_valid = ((s_lead[0] != jnp.uint32(_U32_MAX)) if folded_flag
               else (s_lead[0] == 0))
    differs = jnp.zeros(s_lead[0].shape[0] - 1, dtype=jnp.bool_)
    for p in s_lead:
        differs = differs | (p[1:] != p[:-1])
    return s_valid, differs


def sorted_slot_ids(keys, valid, max_groups: int, folded_flag: bool = False):
    """int32[N]: the slot ``sorted_group_fold`` gives each of N partial
    groups' key (the rank of its key among the distinct valid keys), in
    the rows' own order; ``max_groups`` for a row that is not valid or
    whose group overflows. A sort by the lead words with the row index
    riding, a count of the runs, and the inverse sort: what a carry that
    cannot ride the sort (a [g, K] digest) follows its slot by."""
    n = valid.shape[0]
    lead = lead_words(keys, valid, folded_flag)
    iota = jnp.arange(n, dtype=jnp.int32)
    out = jax.lax.sort(lead + [iota], dimension=0, is_stable=False,
                       num_keys=len(lead))
    s_lead, s_row = out[:-1], out[-1]
    s_valid, differs = sorted_lead_runs(s_lead, folded_flag)
    starts = jnp.concatenate([jnp.ones(1, jnp.bool_), differs]) & s_valid
    gid = blocked_cumsum(starts.astype(jnp.int32), force=n > _CHUNK) - 1
    gid = jnp.where(s_valid & (gid < max_groups), gid, max_groups)
    return jax.lax.sort([s_row, gid], dimension=0, is_stable=False,
                        num_keys=1)[1]


def sorted_group_fold(keys, valid, sums, maxes, max_groups: int,
                      folded_flag: bool = False):
    """Fold N partial groups into ``max_groups`` slots by sorting the rows.

    A row is a partial group: a window's row (count 1, sum = max = its
    value) or a slot of an accumulated state (its carries), so the one
    function is the window fold and the associative merge of two states,
    whatever order their slots are in.

    Args:
      keys: list of u32[N] key bit planes (``_to_bits`` + ``split_u32``,
        or one packed code); equal keys are one group.
      valid: bool[N].
      sums: list of int64[N] planes to add up a group (wrapping, exact).
        One that is also (``is``) the first of ``maxes`` is carried once,
        as that key. The others reach group order as ``ops/routes.py``
        ``sorted_fold_ride`` says for these N, g and operand counts: a
        window's (N >= 4 g) as payload operands of the key sort, up to
        ``SORT_PAYLOAD_MAX_OPERANDS`` in all; a merge's (N = 2 g), and
        past that limit, through the row index, an inverse sort and one
        batched sort.
      maxes: list of int64[N] or int32[N] planes to take the greatest
        of a group (a minimum is the maximum of ``~v``; ``any`` of a
        string is the maximum of its int32 dictionary ids: one word).
        The first rides the sort as its last key; each further maximum
        costs a sort of its own.
      max_groups: static slot count g.
      folded_flag: the caller guarantees ``keys[0]`` of a valid row is
        never 0xFFFFFFFF, so "not valid" needs no operand of its own.

    Returns (keys[g], valid[g], rows[g], sums[g], maxes[g], n_groups):
    slot k is the k-th group in key order; ``rows`` (int32) is its count
    of valid rows; empty slots read zero sums and the least value of a
    maximum's dtype;
    n_groups may exceed g (the caller's overflow).
    """
    g = max_groups
    n = valid.shape[0]
    u32 = jnp.uint32
    iota = jnp.arange(n, dtype=jnp.int32)
    lead = lead_words(keys, valid, folded_flag)
    n_lead = len(lead)
    primary = maxes[0] if maxes else None
    ride = [s for s in sums if s is not primary]
    operands = lead + (_order_words(primary) if primary is not None else [])
    n_keys = len(operands)
    way = sorted_fold_ride(n, g, n_keys, len(ride))

    def ride_words():
        return [w for s in ride for w in _i64_words(jnp.where(valid, s, 0))]

    # A group's rows may come out in any order. Beside the keys ride a
    # window's sum words (``payload``) or, where the planes follow
    # through it, the row index (``index``).
    carried = (ride_words() if way == "payload"
               else [iota] if way == "index" else [])
    out = jax.lax.sort(operands + carried, dimension=0,
                       is_stable=False, num_keys=n_keys)
    s_lead = list(out[:n_lead])
    s_valid = (s_lead[0] != u32(_U32_MAX)) if folded_flag else (s_lead[0] == 0)
    s_primary = (
        _max_plane(out[n_lead:n_keys]) if primary is not None else None
    )
    if way == "payload":
        rode = list(out[n_keys:])
    elif way == "index":
        # Where each row went: the inverse of the order it came out in.
        dest = jax.lax.sort([out[n_keys], iota], dimension=0,
                            is_stable=False, num_keys=1)[1]
        rode = _batched_sort(dest, ride_words())[1]
    sorted_sums = []
    for s in sums:
        if s is primary:
            v = jnp.where(s_valid, s_primary, 0)
        else:
            v, rode = _i64_from_words(rode[0], rode[1]), rode[2:]
        sorted_sums.append(v)

    # Neighbour compares: a row starts a group when a key plane differs
    # from the row before; it ends one when the next row starts one or is
    # not valid (invalid rows sort last).
    one = jnp.ones(1, dtype=jnp.bool_)
    differs = jnp.zeros(n - 1, dtype=jnp.bool_)
    for p in s_lead:
        differs = differs | (p[1:] != p[:-1])
    starts = jnp.concatenate([one, differs])
    is_last = s_valid & jnp.concatenate([differs | ~s_valid[1:], one])
    n_groups = jnp.sum((s_valid & starts).astype(jnp.int32))

    # One row a group to the front: the position of a group's last row is
    # a unique ascending key, every other row sorts behind; the planes
    # follow. The position doubles as the count (rows before it).
    pos = jnp.where(is_last, iota, jnp.int32(_I32_MAX))
    pay = s_lead + ([s_primary] if primary is not None else [])
    # Always the blocked scan past one chunk: a flat 2^18-row INT64 cumsum
    # takes the TPU compiler 17 s, and 119 s inside the fold's scan loop
    # (a described v5e, PR 29), where the blocked one takes 2 s.
    pay = pay + [blocked_cumsum(v, force=n > _CHUNK) for v in sorted_sums]
    pos_g, packed = _front(pos, pay, g)
    slot_valid = jnp.arange(g, dtype=jnp.int32) < jnp.minimum(n_groups, g)
    rows = pos_g - jnp.concatenate([jnp.full(1, -1, jnp.int32), pos_g[:-1]])
    rows = jnp.where(slot_valid, rows, 0)
    keys_g = packed[:n_lead] if folded_flag else packed[1:n_lead]
    at = n_lead
    maxes_g = []
    if primary is not None:
        mx = _max_value(packed[at])
        maxes_g.append(jnp.where(slot_valid, mx, jnp.iinfo(mx.dtype).min))
        at += 1
    sums_g = []
    for cs in packed[at:]:
        # Inclusive prefix at a group's end less the one at the end of
        # the group before: wrap-around differences are exact.
        tot = cs - jnp.concatenate([jnp.zeros(1, jnp.int64), cs[:-1]])
        sums_g.append(jnp.where(slot_valid, tot, 0))
    # Every further maximum: the same keys sort to the same sequence, so
    # ``is_last`` marks the same rows; only the order inside a group
    # changes.
    for extra in maxes[1:]:
        words = _order_words(extra)
        words = jax.lax.sort(lead + words, dimension=0, is_stable=False,
                             num_keys=n_lead + len(words))[n_lead:]
        mx = _max_value(_front(pos, [_max_plane(words)], g)[1][0])
        maxes_g.append(jnp.where(slot_valid, mx, jnp.iinfo(mx.dtype).min))
    return list(keys_g), slot_valid, rows, sums_g, maxes_g, n_groups
