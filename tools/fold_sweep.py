"""Where the one-hot integer fold and the sort-based fold cross over.

    python tools/fold_sweep.py                 # on a chip: the sweep
    JAX_PLATFORMS=cpu python tools/fold_sweep.py --rows 4096 --groups 128

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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=1 << 21)
    ap.add_argument("--groups", type=int, nargs="*",
                    default=[32, 2048, 4096, 8192, 16384, 32768])
    ap.add_argument("--blocks", nargs="*", default=[],
                    help="extra kernel blockings chunk,g_block")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=26)
    args = ap.parse_args(argv)

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
