"""The controls of ``px_perf_flamegraph``'s comparison, put in the
program's place at a cell's own size and compared as a run's answers
are. Both have to come out NOT correct:

- ``percent`` one precision down. The configuration states it in the
  program's FLOAT64, an f32 plane on the device; the precision below is
  half: the float64 quotient rounded into a float16 plane.
- the exact answer cut at the broker's default 10,000 rows a table.

A third line is no control and says why: the sums one precision down
(f32, for the exact INT64 sums the configuration states) are EXACT on
this data (a count is at most a few thousand, a pod's samples of five
minutes under 2^24), so they read as a sound run and the line is
printed with ``is_control: false``. Needs no chip and is no part of a
benchmark run:

    python3 benchmark/control_perf_flamegraph.py \\
        --workload stack_flame_1chip.flame_recent --seeds 1,2,3
"""

from __future__ import annotations

import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.control_net_flow import BROKER_DEFAULT_CUT  # noqa: E402

#: The line that is printed beside the controls and is none.
NO_CONTROL = "f32 sums"


def as_rows(answer: dict, cut: int | None = None) -> dict:
    """A reference's answer as the program would hand it back (the
    first ``cut`` rows of it): what ``numbers`` takes for ``got``."""
    key = answer["key"][:cut]
    return {
        "pod": np.asarray(answer["pods"], object)[key[:, 0]],
        "stack_trace_id": key[:, 1],
        "stack_trace": answer["stack_trace"][:cut],
        "count": answer["count"][:cut],
        "percent": answer["percent"][:cut],
    }


def control_numbers(workload: str, seed: int, rows: int | None = None,
                    cut: int = BROKER_DEFAULT_CUT) -> tuple:
    """({control: numbers}, limits) of each control (and ``NO_CONTROL``)
    against the exact reference, on the data of ``seed`` (``rows``: a
    rehearsal's size)."""
    from benchmark import harness

    spec = harness.load_cell(workload)
    cfg, traffic = spec["config"], spec["traffic"]
    data = harness.module("builders", cfg["builder"]).make_data(
        cfg, seed, cfg["rows"] if rows is None else rows
    )
    lo_ns, _now = harness.range_lo_ns(cfg, traffic)
    (script,) = traffic["scripts"]
    ref = harness.module("reference", script["reference"])
    exact = ref.answer(data, lo_ns)
    return {
        "percent in float16": ref.numbers(
            as_rows(ref.answer(data, lo_ns, percent="float16")), exact),
        f"cut at {cut} rows": ref.numbers(as_rows(exact, cut), exact),
        NO_CONTROL: ref.numbers(
            as_rows(ref.answer(data, lo_ns, sums="f32")), exact),
    }, dict(ref.LIMITS)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--rehearse-rows", type=int, default=None)
    ap.add_argument("--cut", type=int, default=BROKER_DEFAULT_CUT)
    args = ap.parse_args(argv)
    from benchmark import harness

    caught = True
    for seed in (int(s) for s in args.seeds.split(",")):
        controls, limits = control_numbers(
            args.workload, seed, args.rehearse_rows, args.cut
        )
        for control, numbers in controls.items():
            over = sorted(k for k in limits if numbers[k] > limits[k])
            harness.say(workload=args.workload, seed=seed, control=control,
                        numbers={k: [numbers[k], limits[k]] for k in limits},
                        over_limit=over, correct=not over,
                        **({"is_control": False} if control == NO_CONTROL
                           else {}))
            caught = caught and (bool(over) or control == NO_CONTROL)
    return 0 if caught else 1


if __name__ == "__main__":
    sys.exit(main())
