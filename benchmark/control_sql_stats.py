"""The controls of ``px_sql_stats``'s comparison, put in the program's
place at a cell's own size and compared as a run's answers are: the
plain reference with its sums one precision down (32-bit floats, summed
pairwise, for the exact INT64 sums the configuration states), and the
exact answer cut at the broker's default 10,000 rows a table. Both have
to come out NOT correct. Needs no chip and is no part of a benchmark
run. The controls themselves are ``control_net_flow``'s, which asks the
cell's own traffic for its one script's reference:

    python3 benchmark/control_sql_stats.py \\
        --workload sql_stats_1chip.sql_recent --seeds 1,2,3
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.control_net_flow import (  # noqa: E402,F401
    BROKER_DEFAULT_CUT, control_numbers, main,
)

if __name__ == "__main__":
    sys.exit(main())
