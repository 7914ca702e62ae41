"""The control of "How correct is decided": the plain reference with
its sums one precision down (32-bit floats, summed pairwise, for the
exact i64 sum the configurations state), put in the program's place at
the cell's own size, and compared as a run's answers are. It has to
come out NOT correct. Needs no chip and is no part of a benchmark run:

    python3 benchmark/control.py --workload http_pem_1chip.dash_full \\
        --seeds 1,2,3
"""

from __future__ import annotations

import argparse
import os
import sys


def control_numbers(workload: str, seed: int) -> tuple:
    """(numbers, limits) of the lower-precision reference against the
    exact one, on the data of ``seed``."""
    from benchmark import harness

    spec = harness.load_cell(workload)
    cfg, traffic = spec["config"], spec["traffic"]
    data = harness.module("builders", cfg["builder"]).make_data(
        cfg, seed, cfg["rows"]
    )
    lo_ns, _now = harness.range_lo_ns(cfg, traffic)
    numbers, limits = {}, {}
    for s in traffic["scripts"]:
        ref = harness.module("reference", s["reference"])
        exact = ref.answer(data, lo_ns)
        numbers.update(ref.numbers(ref.answer(data, lo_ns, sums="f32"), exact))
        limits.update(ref.LIMITS)
    return numbers, limits


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    )))
    from benchmark import harness

    caught = True
    for seed in (int(s) for s in args.seeds.split(",")):
        numbers, limits = control_numbers(args.workload, seed)
        over = sorted(k for k in limits if numbers[k] > limits[k])
        harness.say(workload=args.workload, seed=seed,
                    control="f32 pairwise sums",
                    numbers={k: [numbers[k], limits[k]] for k in limits},
                    over_limit=over, correct=not over)
        caught = caught and bool(over)
    return 0 if caught else 1


if __name__ == "__main__":
    sys.exit(main())
