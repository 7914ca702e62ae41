"""The controls of ``px_net_flow_graph``'s comparison, put in the
program's place at a cell's own size and compared as a run's answers
are: the plain reference with its sums one precision down (32-bit
floats, summed pairwise, for the exact INT64 sums the configuration
states), and the exact answer cut at the broker's default 10,000 rows
a table. Both have to come out NOT correct. Needs no chip and is no
part of a benchmark run (``control.py`` is the dashboards' scripts'):

    python3 benchmark/control_net_flow.py \\
        --workload conn_flow_1chip.flow_recent --seeds 1,2,3
"""

from __future__ import annotations

import argparse
import os
import sys

#: ``QueryBroker.execute_script``'s default ``max_output_rows``.
BROKER_DEFAULT_CUT = 10_000


def control_numbers(workload: str, seed: int, rows: int | None = None,
                    cut: int = BROKER_DEFAULT_CUT) -> tuple:
    """({control: numbers}, limits) of each control against the exact
    reference, on the data of ``seed`` (``rows``: a rehearsal's size)."""
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
    # The cut as the broker makes it: the first ``cut`` rows of a table.
    kept = {k: v[:cut] for k, v in exact.items()}
    return {
        "f32 pairwise sums": ref.numbers(
            ref.answer(data, lo_ns, sums="f32"), exact),
        f"cut at {cut} rows": ref.numbers(kept, exact),
    }, dict(ref.LIMITS)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--rehearse-rows", type=int, default=None)
    ap.add_argument("--cut", type=int, default=BROKER_DEFAULT_CUT)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    )))
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
                        over_limit=over, correct=not over)
            caught = caught and bool(over)
    return 0 if caught else 1


if __name__ == "__main__":
    sys.exit(main())
