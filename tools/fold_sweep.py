"""Where the one-hot integer fold and the sort-based fold cross over,
and (``--keyed``) what the keyed fold costs, old route against new.

    python tools/fold_sweep.py                 # on a chip: the sweep
    python tools/fold_sweep.py --keyed         # on a chip: the keyed fold
    python tools/fold_sweep.py --micro         # on a chip: sorts, gathers, scans
    python tools/fold_sweep.py --digest        # on a chip: the quantile digest
    JAX_PLATFORMS=cpu python tools/fold_sweep.py --rows 4096 --groups 128
    JAX_PLATFORMS=cpu python tools/fold_sweep.py --keyed --rows 4096 --groups 256
    JAX_PLATFORMS=cpu python tools/fold_sweep.py --digest --rows 4096 --groups 2 33

Times one window's fold of ``px/http_stats``' aggregates (``count``,
``mean`` and ``max`` of one INT64 column) two ways over a range of group
counts: ``ops/pallas_groupby.py`` ``dense_group_fold_int`` (cost: rows x
groups) and the UDAs' own ``update`` (``udf/builtins/math_ops.py``: on
the TPU the argsort / cumsum / gather form, about the same whatever the
group count). ``INT_FOLD_MAX_GROUPS`` is set from what this prints on
the chip; the run it was set from is cited in PERF.md (PR 26). One JSON
object a line; both forms are checked against each other bit for bit.
On the CPU it only rehearses (kernel in interpret mode, no time means
anything).

``--keyed``: one window of ``px/http_stats`` over its two dictionary keys
with NO dense domain (33 x 65,537 codes), folded into a keyed state of g
slots and merged into an accumulated one, two ways: the id form
(``ops/groupby.py`` ``dense_group_ids``, the UDAs' ``update``,
``regroup_pair`` + ``scatter_carry``: an argsort and a window-long gather
or scatter a step) and ``sorted_group_fold`` (the rows ride the sort).
The pieces are timed apart, and the two states are compared bit for bit.
PERF.md section 6 (PR 29) holds the chip's output.

``--digest``: one window of ``px/service_stats``' ``quantiles`` aggregate
(``ops/tdigest.py`` ``digest_update`` of a [G, 128] carry, then the
window's merge into the accumulated state), the scatter route (the CPU's:
two row scatters into the [G, B] histogram, then its compress) against
the sorted route (the TPU's: one payload-carrying sort, positions, a
reduction of sorted ids), at 2^19 and 2^21 rows and G = 1, 2, 4, 33,
8,192, the two routes' quantiles compared, the sorted route's pieces
timed apart. PERF.md section 6 (PR 33) holds the chip's output.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _time(fn, *args, reps: int) -> float:
    """Median milliseconds of ``reps`` calls, each fenced."""
    import jax

    jax.block_until_ready(fn(*args))  # compile + warm
    out = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        out.append((time.perf_counter() - t0) * 1e3)
    return sorted(out)[len(out) // 2]


_DOMS = (33, 65_537)  # px/http_stats' key columns in `http_full_1chip`


def keyed_sweep(args) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import pixie_tpu  # noqa: F401  (x64 on)
    from pixie_tpu.ops import groupby as gb
    from pixie_tpu.ops.scan import blocked_cumsum
    from pixie_tpu.types.dtypes import DataType
    from pixie_tpu.udf.registry import default_registry

    dev = jax.devices()[0]
    print(json.dumps({"device": dev.device_kind, "platform": dev.platform,
                      "rows": args.rows, "keyed": True}), flush=True)
    reg = default_registry()
    udas = [reg.get_uda("count", [DataType.FLOAT64]),
            reg.get_uda("mean", [DataType.INT64]),
            reg.get_uda("max", [DataType.INT64])]
    rng = np.random.default_rng(args.seed)
    n = args.rows
    lat = jnp.asarray(np.exp(rng.normal(15, 1.2, n)).astype(np.int64))
    valid = jnp.asarray(rng.random(n) < 0.92)

    def code_of(svc, path):
        return (svc * jnp.int32(_DOMS[1]) + path).astype(jnp.uint32)

    for g in args.groups or [1 << 13, 1 << 15, 1 << 17, 1 << 19]:
        live = max(1, g // 2)  # groups a window holds: half the slots
        c = rng.integers(0, live, (2, n))
        svc = [jnp.asarray((x % 32).astype(np.int32)) for x in c]
        path = [jnp.asarray((x // 32).astype(np.int32)) for x in c]

        # -- the id form: what window_state / merge_states ran until PR 29
        def old_window(svc, path, valid, lat):
            gids, keys, kvalid, n_w = gb.dense_group_ids([svc, path], valid, g)
            carries = tuple(u.update(u.init(g), gids, valid, lat) for u in udas)
            return keys, kvalid, carries, n_w > g

        def old_merge(sa, sb):
            ids_a, ids_b, keys, kvalid, n_tot = gb.regroup_pair(
                sa[0], sa[1], sb[0], sb[1], g)
            carries = tuple(
                u.merge(gb.scatter_carry(ca, ids_a, sa[1], g, u.init(g)),
                        gb.scatter_carry(cb, ids_b, sb[1], g, u.init(g)))
                for u, ca, cb in zip(udas, sa[2], sb[2]))
            return keys, kvalid, carries, sa[3] | sb[3] | (n_tot > g)

        # -- the rows ride the sort
        def new_window(svc, path, valid, lat):
            (code,), kvalid, rows, (s,), (mx,), n_w = gb.sorted_group_fold(
                [code_of(svc, path)], valid, [lat], [lat], g, folded_flag=True)
            rows = rows.astype(jnp.int64)
            return code, kvalid, (rows, (s, rows), mx), n_w > g

        def new_merge(sa, sb):
            cat = lambda a, b: jnp.concatenate([a, b])
            (code,), kvalid, _r, (cn, s, cm), (mx,), n_tot = gb.sorted_group_fold(
                [cat(sa[0], sb[0])], cat(sa[1], sb[1]),
                [cat(sa[2][0], sb[2][0]), cat(sa[2][1][0], sb[2][1][0]),
                 cat(sa[2][1][1], sb[2][1][1])],
                [cat(sa[2][2], sb[2][2])], g, folded_flag=True)
            return code, kvalid, (cn, (s, cm), mx), sa[3] | sb[3] | (n_tot > g)

        def by_code(state, packed):
            """{code: (n, sum, count, max)} of a state's live slots."""
            keys, kvalid, (cn, (s, cm), mx), over = jax.device_get(state)
            assert not over
            code = keys if packed else np.asarray(
                keys[0].astype(np.int64) * _DOMS[1] + keys[1])
            live_ = np.flatnonzero(kvalid)
            order = live_[np.argsort(np.asarray(code)[live_])]
            return tuple(np.asarray(a)[order] for a in (code, cn, s, cm, mx))

        line = {"groups": g, "live": live}
        states = {}
        for name, win, mrg in (("old", old_window, old_merge),
                               ("new", new_window, new_merge)):
            win_j, mrg_j = jax.jit(win), jax.jit(mrg)
            w0 = (svc[0], path[0], valid, lat)
            w1 = (svc[1], path[1], valid, lat)
            st0, st1 = jax.block_until_ready((win_j(*w0), win_j(*w1)))
            states[name] = by_code(mrg_j(st0, st1), name == "new")
            line[f"{name}_window_ms"] = round(_time(win_j, *w0, reps=args.reps), 3)
            line[f"{name}_merge_ms"] = round(
                _time(mrg_j, st0, st1, reps=args.reps), 3)
        for a, b in zip(states["old"], states["new"]):
            np.testing.assert_array_equal(a, b)
        line["equal"] = True

        # -- the pieces, apart
        code = code_of(svc[0], path[0])
        hi, lo = gb._i64_words(lat)
        gids = jnp.asarray(rng.integers(0, g, n).astype(np.int32))

        def scans(code, valid, v):
            differs = code[1:] != code[:-1]
            last = valid & jnp.concatenate([differs, jnp.ones(1, jnp.bool_)])
            return last, blocked_cumsum(jnp.where(valid, v, 0))

        pieces = {
            "main_sort": (lambda c, h, l: jax.lax.sort(
                [c, h, l], dimension=0, is_stable=False, num_keys=3),
                (code, hi, lo)),
            "scans": (scans, (jnp.sort(code), valid, lat)),
            "compaction": (lambda k, *p: gb._front(k, list(p), g),
                           (jnp.asarray(rng.permutation(n).astype(np.int32)),
                            code, lat, lat)),
            "old_group_ids": (lambda s, p, m: gb.dense_group_ids([s, p], m, g),
                              (svc[0], path[0], valid)),
            "old_uda_updates": (lambda gi, m, v: tuple(
                u.update(u.init(g), gi, m, v) for u in udas),
                (gids, valid, lat)),
        }
        for name, (fn, a) in pieces.items():
            line[f"{name}_ms"] = round(_time(jax.jit(fn), *a, reps=args.reps), 3)
        print(json.dumps(line), flush=True)
    return 0


def digest_sweep(args) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import pixie_tpu  # noqa: F401  (x64 on)
    from pixie_tpu.ops import routes, tdigest
    from pixie_tpu.ops.pallas_tdigest import sorted_centroid_fold

    dev = jax.devices()[0]
    print(json.dumps({"device": dev.device_kind, "platform": dev.platform,
                      "digest": True}), flush=True)
    qs = (0.01, 0.10, 0.25, 0.50, 0.75, 0.90, 0.99)
    k = tdigest.DEFAULT_K
    real_platform = routes.routes_platform

    def on(platform, fn):
        """``fn`` jitted and traced under ``platform``'s routes."""
        def traced(*a):
            routes.routes_platform = lambda: platform
            try:
                return fn(*a)
            finally:
                routes.routes_platform = real_platform
        return jax.jit(traced)

    rng = np.random.default_rng(args.seed)
    for n in ([args.rows] if args.rows != 1 << 21 else [1 << 19, 1 << 21]):
        lat = jnp.asarray(np.exp(rng.normal(15, 1.2, n)).astype(np.float32))
        valid = jnp.asarray(rng.random(n) < 0.92)
        for g in args.groups or [1, 2, 4, 33, 8192]:
            gids = jnp.asarray(rng.integers(0, g, n).astype(np.int32))
            carry = jax.block_until_ready(tdigest.digest_init(g, k))

            def window(carry, gids, valid, lat):
                # What a fold pays a window: the UDA's update of a fresh
                # carry, then the merge into the accumulated state.
                fresh = tdigest.digest_update(
                    tdigest.digest_init(g, k), gids, valid, lat)
                return tdigest.digest_merge(carry, fresh)

            line = {"rows": n, "groups": g, "bins": tdigest._hist_bins(g)}
            out = {}
            for name, platform in (("scatter", "cpu"), ("sorted", "tpu")):
                fold = on(platform, window)
                out[name] = np.asarray(tdigest.digest_quantile(
                    fold(carry, gids, valid, lat), qs))
                line[f"{name}_ms"] = round(
                    _time(fold, carry, gids, valid, lat, reps=args.reps), 3)
            live = ~np.isnan(out["scatter"])
            assert (live == ~np.isnan(out["sorted"])).all()
            line["quantiles_max_relerr"] = float(np.max(
                np.abs(out["sorted"][live] / out["scatter"][live] - 1.0)))

            # -- the sorted route's pieces, apart
            v, m, gi, bins, b = tdigest._row_bins(lat, gids, valid, g)
            key = jnp.where(m & (gi < g), (gi * b + bins).astype(jnp.uint32),
                            jnp.uint32(0xFFFFFFFF))
            iota = jnp.arange(n, dtype=jnp.int32)
            s_key, s_val = jax.block_until_ready(jax.lax.sort(
                (key, v), num_keys=1, is_stable=False))
            ids = jnp.sort(jnp.asarray(
                rng.integers(0, g * k, n).astype(np.int32)))
            scatter_rows = on("cpu", lambda *a: tdigest.batch_to_digest(
                *a, g, k))
            digest = jax.block_until_ready(scatter_rows(lat, gids, valid))
            pieces = {
                "sort": (lambda a, c: jax.lax.sort(
                    (a, c), num_keys=1, is_stable=False), (key, v)),
                "two_scans": (lambda a: tdigest._span_bounds(
                    a[1:] != a[:-1], iota), (s_key,)),
                "reduction": (lambda i, c: sorted_centroid_fold(
                    i, c, g * k, interpret=routes.kernels_interpreted()),
                    (ids, s_val)),
                "two_merges": (lambda c, f: tdigest.digest_merge(
                    c, tdigest.digest_merge(tdigest.digest_init(g, k), f)),
                    (carry, digest)),
                "scatter_rows": (scatter_rows, (lat, gids, valid)),
            }
            for name, (fn, a) in pieces.items():
                line[f"{name}_ms"] = round(
                    _time(jax.jit(fn), *a, reps=args.reps), 3)
            print(json.dumps(line), flush=True)
    return 0


def micro_sweep(args) -> int:
    """What the keyed fold is built from, one primitive a line, at the
    window's and the merge's lengths: sorts by operand and key count, the
    payload sort against the index way's three, the batched sort, gathers,
    a scatter, the scans (``first_s`` is the first call: compile, or the
    cache)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import pixie_tpu  # noqa: F401  (x64 on)
    from pixie_tpu.ops.scan import blocked_cumsum

    dev = jax.devices()[0]
    print(json.dumps({"device": dev.device_kind, "platform": dev.platform,
                      "micro": True}), flush=True)
    rng = np.random.default_rng(args.seed)
    g = 1 << 17

    def bench(name, fn, *a):
        if args.only and not name.startswith(tuple(args.only)):
            return
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*a))
        first = time.perf_counter() - t0
        print(json.dumps({"name": name, "first_s": round(first, 2),
                          "ms": round(_time(fn, *a, reps=args.reps), 3)}),
              flush=True)

    for n in (args.rows, args.rows >> 3):
        u = [jnp.asarray(rng.integers(0, 1 << 32, n, dtype=np.uint64)
                         .astype(np.uint32)) for _ in range(8)]
        code = jnp.asarray(rng.integers(0, 1 << 16, n).astype(np.uint32))
        i64 = jnp.asarray(rng.integers(-1 << 62, 1 << 62, n).astype(np.int64))
        perm = jnp.asarray(rng.permutation(n).astype(np.int32))
        for ops, keys in ((1, 1), (2, 1), (3, 1), (3, 3), (4, 3), (6, 1)):
            bench(f"sort n={n} operands={ops} keys={keys}", jax.jit(
                lambda *o, k=keys: jax.lax.sort(
                    list(o), dimension=0, is_stable=False, num_keys=k)),
                code, *u[:ops - 1])
        # How a window's sum words reach group order (``ops/groupby.py``
        # ``sorted_group_fold``, ``ops/routes.py`` ``sorted_fold_ride``):
        # as payload operands of the key sort, against the index way's
        # three sorts (keys + the row index, the inverse, the batched
        # [P, N]). ``SORT_PAYLOAD_MAX_OPERANDS`` is set from these rows.
        for keys in (1, 3):
            for pay in (2, 4, 6):
                ks, ps = [code] + u[:keys - 1], u[2:2 + pay]
                bench(f"payload_sort n={n} keys={keys} payload={pay}", jax.jit(
                    lambda *o, k=keys: jax.lax.sort(
                        list(o), dimension=0, is_stable=False, num_keys=k)),
                    *ks, *ps)

                def index_way(*o, k=keys):
                    iota = jnp.arange(o[0].shape[0], dtype=jnp.int32)
                    out = jax.lax.sort(list(o[:k]) + [iota], dimension=0,
                                       is_stable=False, num_keys=k)
                    dest = jax.lax.sort([out[k], iota], dimension=0,
                                        is_stable=False, num_keys=1)[1]
                    p = jnp.stack(o[k:])
                    return out[:k], jax.lax.sort(
                        [jnp.broadcast_to(dest[None, :], p.shape), p],
                        dimension=1, is_stable=False, num_keys=1)[1]

                bench(f"index_way n={n} keys={keys} payload={pay}",
                      jax.jit(index_way), *ks, *ps)
        for planes in (1, 4, 8):
            bench(f"batched_sort n={n} planes={planes}", jax.jit(
                lambda k, p: jax.lax.sort(
                    [jnp.broadcast_to(k[None, :], p.shape), p], dimension=1,
                    is_stable=False, num_keys=1)[1]),
                perm, jnp.stack(u[:planes]))
        take = jax.jit(lambda a, i: a[i])
        for name, a in (("u32", u[0]), ("i64", i64)):
            bench(f"gather_{name} n={n} full", take, a, perm)
            bench(f"gather_{name} n={n} {min(g, n)}-long", take, a, perm[:g])
        bench(f"scatter_i32 n={n}", jax.jit(
            lambda a, i: jnp.zeros(a.shape, jnp.int32).at[i].set(a)), perm, perm)
        bench(f"blocked_cumsum_i64 n={n}", jax.jit(
            lambda a: blocked_cumsum(a, force=True)), i64)
        bench(f"searchsorted {min(g, n)} edges into n={n}", jax.jit(
            lambda a, q: jnp.searchsorted(a, q, side="right")),
            jnp.sort(perm), perm[:g])
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=1 << 21)
    ap.add_argument("--keyed", action="store_true",
                    help="the keyed fold: the id form against the payload sort")
    ap.add_argument("--micro", action="store_true",
                    help="the primitives the keyed fold is built from")
    ap.add_argument("--digest", action="store_true",
                    help="the quantile digest: the scatter route against "
                         "the sorted route")
    ap.add_argument("--only", nargs="*", default=[],
                    help="--micro: only the rows whose name starts so")
    ap.add_argument("--groups", type=int, nargs="*", default=None)
    ap.add_argument("--blocks", nargs="*", default=[],
                    help="extra kernel blockings chunk,g_block")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=26)
    args = ap.parse_args(argv)
    if args.micro:
        return micro_sweep(args)
    if args.digest:
        return digest_sweep(args)
    if args.keyed:
        return keyed_sweep(args)
    if args.groups is None:
        args.groups = [32, 2048, 4096, 8192, 16384, 32768]

    import jax
    import jax.numpy as jnp
    import numpy as np

    import pixie_tpu  # noqa: F401  (x64 on)
    from pixie_tpu.ops import pallas_groupby as pg
    from pixie_tpu.types.dtypes import DataType
    from pixie_tpu.udf.registry import default_registry

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    print(json.dumps({"device": dev.device_kind, "platform": dev.platform,
                      "rows": args.rows}), flush=True)
    reg = default_registry()
    udas = [reg.get_uda("count", [DataType.FLOAT64]),
            reg.get_uda("mean", [DataType.INT64]),
            reg.get_uda("max", [DataType.INT64])]
    rng = np.random.default_rng(args.seed)
    n = args.rows
    lat = np.exp(rng.normal(15, 1.2, n)).astype(np.int64)
    keep = rng.random(n) < 0.92

    for g in args.groups:
        g_pad = pg.int_fold_groups(g)
        slots_np = rng.integers(0, g, n).astype(np.int32)
        gids = jnp.asarray(slots_np)
        mask = jnp.asarray(keep)
        v = jnp.asarray(lat)

        @jax.jit
        def xla_fold(gids, mask, v):
            cnt = udas[0].update(udas[0].init(g), gids, mask, v)
            s, c = udas[1].update(udas[1].init(g), gids, mask, v)
            mx = udas[2].update(udas[2].init(g), gids, mask, v)
            return cnt, s, mx

        def kernel_fold(chunk, gb):
            @jax.jit
            def fold(gids, mask, v):
                slots = jnp.where(mask, gids, jnp.int32(g_pad))
                cnt, (s,), (mx,) = pg.dense_group_fold_int(
                    slots, (v,), (v,), g=g_pad, chunk=chunk, g_block=gb,
                    ext_max=(True,), interpret=not on_tpu,
                )
                return cnt[:g], s[:g], mx[:g]
            return fold

        line = {"groups": g, "g_pad": g_pad}
        want = jax.block_until_ready(xla_fold(gids, mask, v))
        line["xla_ms"] = round(_time(xla_fold, gids, mask, v, reps=args.reps), 3)
        blockings = []
        chunk = pg.row_chunk(n, 2048)
        if chunk is not None:  # the engine's blocking, whatever the gate
            blockings.append((chunk, min(g_pad, pg.INT_FOLD_GROUP_BLOCK)))
        for b in args.blocks:
            chunk, gb = (int(x) for x in b.split(","))
            if n % chunk == 0 and g_pad % gb == 0:
                blockings.append((chunk, gb))
        for chunk, gb in blockings:
            name = f"pallas_ms[{chunk},{gb}]"
            try:
                fold = kernel_fold(chunk, gb)
                got = jax.block_until_ready(fold(gids, mask, v))
                for a, b_ in zip(got, want):
                    np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))
                line[name] = round(_time(fold, gids, mask, v, reps=args.reps), 3)
            except Exception as e:  # a blocking the chip refuses: say so
                line[name] = f"{type(e).__name__}: {str(e)[:200]}"
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
