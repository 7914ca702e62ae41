"""Run one cell of ``BENCHMARK.json`` once, in a new process.

    python3 benchmark/run.py --workload http_pem_1chip.dash_full \\
        --seed 7 --seconds 51 --trace 0

Loads, warms up, measures for ``--seconds``, compares every answer with
the plain reference, and prints one JSON object as its last line. A
machine without the cell's TPU chips is an error (exit 2, no result):
there is no fallback. ``--rehearse-rows N`` walks the same flow on
whatever JAX finds (the CPU) at N rows, marks the line a rehearsal and
exits 1; it reports no device number.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def main(argv=None) -> int:
    t0 = time.time()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-rows", type=int, default=None)
    args = ap.parse_args(argv)

    # One hash seed for every run, so that dict and set order, and with
    # them the host path's allocation pattern, repeat from run to run.
    # execve replaces this process: no parent is left holding the chip.
    if os.environ.get("PYTHONHASHSEED") != "0":
        env = dict(os.environ, PYTHONHASHSEED="0", PXBENCH_T0=repr(t0))
        os.execve(sys.executable, [sys.executable, *sys.argv], env)
    t0 = float(os.environ.get("PXBENCH_T0", t0))

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    from benchmark import harness

    try:
        result = harness.run_cell(
            args.workload, args.seed, args.seconds, bool(args.trace), t0,
            rehearse_rows=args.rehearse_rows,
        )
    except harness.NoChip as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    if result.get("rehearsal"):
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
