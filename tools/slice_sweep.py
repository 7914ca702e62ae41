"""What a fold program costs handed the rows in range and not the padded
window (PR 44: ``exec/fragment.py`` ``RowSlice``, ``exec/stream.py``
``_fold_rows``), by the program alone, on a chip.

    python tools/slice_sweep.py                       # on a chip
    JAX_PLATFORMS=cpu python tools/slice_sweep.py --window 4096 --reps 1

Three of the cells' fold shapes, compiled by hand at the cells' slots
over random rows of a 2^21-row resident window: ``dense`` (``px/http_stats``
on 33 x 65 codes: the integer Pallas kernel), ``keyed`` (``px/net_flow_graph``'s
two sums by two dictionary keys at 2^17 slots: the payload-carrying sort)
and ``any`` (``px/perf_flamegraph``'s ``any`` of a string beside a sum by a
dictionary key and an INT64 at 2^20 slots: ``absorb``, two windows a run).
Each is timed (median ms of ``--reps`` fenced calls, compile seconds
beside it) folding the same rows in range:

- ``whole``: the window at its capacity, the mask from (lo, hi);
- ``half``: a half of it from ``lo`` (as the engine cuts it, wherever
  ``lo`` falls) and ``half_aligned``: from ``lo`` rounded down to 1,024
  rows, to see what an unaligned start of the slice costs;
- ``quarter``: a quarter, for a range that fits one;
- ``any``, the run of two windows of 432,374 and 582,178 rows:
  ``run_whole``, ``run_half`` (ONE ``update_all`` at the longer window's
  length, as the engine runs it) and ``split`` (``update`` at a quarter,
  then ``update`` at a half: two programs, two dispatches).

Every sliced state's answer is compared with the whole window's.
One JSON object a line. PERF.md section 6 (PR 44) holds the chip's
output; on the CPU it only rehearses (no time means anything).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from unittest import mock

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _timed(fn, *args, reps: int):
    """(median ms of ``reps`` fenced calls, seconds of the first call,
    which compiles, the result)."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    first = time.perf_counter() - t0
    ms = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        ms.append((time.perf_counter() - t0) * 1e3)
    return sorted(ms)[len(ms) // 2], first, out


def _same(frag, a, b) -> bool:
    """Two states answer alike: the live slots of every output plane
    (a dead slot of a sorted state holds what its sort left there)."""
    import jax
    import numpy as np

    (ca, va, oa), (cb, vb, ob) = (
        jax.device_get(frag.finalize(s)) for s in (a, b))
    return bool(oa) == bool(ob) and np.array_equal(va, vb) and all(
        np.array_equal(x[va], y[vb], equal_nan=True)
        for x, y in zip(jax.tree_util.tree_leaves(ca),
                        jax.tree_util.tree_leaves(cb))
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--window", type=int, default=1 << 21)
    ap.add_argument("--reps", type=int, default=7)
    ap.add_argument("--seed", type=int, default=44)
    ap.add_argument("--only", nargs="*", default=[],
                    help="of dense, keyed, any")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    import pixie_tpu  # noqa: F401  (x64 on)
    from pixie_tpu.exec.fragment import RowSlice, compile_fragment
    from pixie_tpu.exec.plan import AggExpr, AggOp, ColumnRef
    from pixie_tpu.exec.stream import _fold_rows
    from pixie_tpu.ops import routes
    from pixie_tpu.types.dtypes import DataType
    from pixie_tpu.types.relation import Relation
    from pixie_tpu.types.strings import StringDictionary
    from pixie_tpu.udf.registry import default_registry

    w = args.window
    scale = w / (1 << 21)
    rng = np.random.default_rng(args.seed)
    rel = Relation([
        ("lat", DataType.INT64), ("bytes", DataType.INT64),
        ("id", DataType.INT64), ("svc", DataType.STRING),
        ("path", DataType.STRING), ("pod", DataType.STRING),
        ("addr", DataType.STRING), ("stack", DataType.STRING),
    ])
    sizes = {"svc": 32, "path": 64, "pod": 4_096, "addr": 8_192,
             "stack": max(int(262_144 * scale), 8)}
    dicts = {c: StringDictionary(f"{c}{i}" for i in range(n))
             for c, n in sizes.items()}

    def window():
        cols = {
            "lat": rng.integers(0, 1 << 40, w).astype(np.int64),
            "bytes": rng.integers(0, 1 << 20, w).astype(np.int64),
            "id": rng.integers(0, max(int(3_600_000 * scale), 8), w).astype(
                np.int64),
        }
        for c, n in sizes.items():
            cols[c] = rng.integers(0, n, w).astype(np.int32)
        return {c: (jnp.asarray(v),) for c, v in cols.items()}

    #: name -> (keys, aggregates, slots, the (lo, hi) a window in range).
    at = lambda rows, end: (int((end - rows) * scale), int(end * scale))  # noqa: E731
    forms = {
        "dense": (("svc", "path"),
                  (("n", "count", "lat"), ("m", "mean", "lat"),
                   ("mx", "max", "lat")), 4_096,
                  [at(658_000, 1_603_704)]),
        "keyed": (("pod", "addr"),
                  (("a", "sum", "lat"), ("b", "sum", "bytes")),
                  max(int((1 << 17) * scale), 64), [at(614_366, 679_650)]),
        "any": (("pod", "id"),
                (("st", "any", "stack"), ("c", "sum", "bytes")),
                max(int((1 << 20) * scale), 64),
                [at(432_374, 1 << 21), at(582_178, 582_178)]),
    }
    platform = "tpu"  # the chip's routes; interpreted kernels on a CPU
    device = jax.devices()[0]
    print(json.dumps({"device": device.platform,
                      "device_kind": device.device_kind, "window": w}),
          flush=True)
    i32 = np.int32
    for name, (keys, aggs, slots, ranges) in forms.items():
        if args.only and name not in args.only:
            continue
        with mock.patch.object(routes, "routes_platform", lambda: platform):
            frag = compile_fragment(
                [AggOp(keys, tuple(AggExpr(o, u, (ColumnRef(c),))
                                   for o, u, c in aggs), max_groups=slots)],
                rel, dicts, default_registry(), allow_dense=True,
            )
            wins = [window() for _ in ranges]
            state = frag.init_state()

            def say(way, ms, first, rows, **more):
                print(json.dumps({
                    "form": name, "fold": frag.fold, "slots": frag.slots,
                    "way": way, "rows": rows, "ms": round(ms, 3),
                    "first_call_s": round(first, 2), **more,
                }), flush=True)

            lo, hi = ranges[0]
            one = len(ranges) == 1  # a run is timed as a run alone
            if one:
                ms, first, whole = _timed(
                    frag.update, state, wins[0], (i32(lo), i32(hi)),
                    reps=args.reps)
                say("whole", ms, first, w, range_rows=hi - lo)
            for way, rows, align in (("half", w // 2, 1),
                                     ("half_aligned", w // 2, 1_024),
                                     ("quarter", w // 4, 1)) if one else ():
                lo2 = lo
                start = min(lo - lo % align, w - rows)
                if hi - start > rows:  # a range that fits this length
                    lo2 = start = -(-(hi - rows) // align) * align
                ms, first, got = _timed(
                    frag.update, state, wins[0],
                    (i32(lo2 - start), i32(hi - start)),
                    RowSlice(i32(start), rows), reps=args.reps)
                want = whole if lo2 == lo else frag.update(
                    state, wins[0], (i32(lo2), i32(hi)))
                say(way, ms, first, rows, range_rows=hi - lo2, start=start,
                    same=_same(frag, got, want))
            if one:
                continue
            los = np.array([r[0] for r in ranges], i32)
            his = np.array([r[1] for r in ranges], i32)
            ms, first, run_whole = _timed(
                frag.update_all, state, tuple(wins), los, his, reps=args.reps)
            say("run_whole", ms, first, w * len(wins))
            lengths = [_fold_rows(w, int(h - l)) for l, h in ranges]
            rows = max(lengths)
            starts = np.minimum(los, w - rows).astype(i32)
            ms, first, got = _timed(
                frag.update_all, state, tuple(wins), los - starts,
                his - starts, RowSlice(starts, rows), reps=args.reps)
            say("run_half", ms, first, rows * len(wins),
                same=_same(frag, got, run_whole))

            def split(state):
                for cols, (l, h), rows in zip(wins, ranges, lengths):
                    start = min(l, w - rows)
                    state = frag.update(
                        state, cols, (i32(l - start), i32(h - start)),
                        RowSlice(i32(start), rows))
                return state

            ms, first, got = _timed(split, state, reps=args.reps)
            say("split", ms, first, sum(lengths), same=_same(frag, got, run_whole))
    return 0


if __name__ == "__main__":
    sys.exit(main())
