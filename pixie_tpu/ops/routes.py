"""What a fold's route is chosen from besides the AggOp itself: the
platform whose routes run, and the kernels' measured size limits.

Kernel-free (no Pallas import): ``exec/fold_plan.py`` decides from these,
``ops/pallas_groupby.py`` / ``ops/tdigest.py`` / ``udf/builtins/math_ops.py``
obey the same values, so the decision and the kernels cannot disagree.
"""

from __future__ import annotations

import jax


def _backend() -> str:
    """The backend underneath (the fold's one read of it)."""
    return jax.default_backend()


def routes_platform() -> str:
    """Which platform's fold routes run: ``tpu`` sorts, contracts one-hots
    in Pallas kernels and scan-folds windows in one program; ``cpu``
    hashes, scatters and hands dense folds to the native library (XLA's
    CPU sort is ~90x slower than its scatter, the inverse of the TPU).
    Every route choice asks THIS function, and the fragment cache keys
    on its value, so a test or ``chip_smoke.py``'s rehearsal reaches the
    chip's routes on the CPU by substituting it alone."""
    return _backend()


def kernels_interpreted() -> bool:
    """A Pallas kernel a route asks for is interpreted exactly when the
    backend underneath is not a TPU (derived, never set)."""
    return _backend() != "tpu"


#: Largest (padded) group count the integer fold is routed to; above it a
#: window keeps the sort-based XLA fold (``udf/builtins/math_ops.py``).
#: The one-hot costs rows x G while the sort costs about the same
#: whatever G is, so there is a cross-over, and it is measured
#: (``tools/fold_sweep.py``, one 2^21-row window of px/http_stats'
#: count + mean + max on a TPU v5e; my chip run, PR 26, PERF.md section
#: 6), kernel ms against sort-based ms: 32 slots 5.1 / 157.9; 2,048
#: 11.6 / 134.5; 4,096 21.6 / 135.2; 8,192 41.4 / 136.6; 16,384 80.7 /
#: 139.7; 24,576 120.1 / 142.2; 32,768 159.1 / 145.1. The kernel is
#: 2.2 ms + 4.8 ms a 1,024-slot group block, so the lines cross near
#: 30 Ki slots; the gate is the largest measured size that still wins.
INT_FOLD_MAX_GROUPS = 24576
#: Columns of the one-hot a grid step builds (the G axis of the grid).
#: Same run, 2,048 slots, [2048 rows, block] a step: 128 columns 59.8 ms
#: (1,024-row steps), 256 29.9, 512 15.9, 1,024 11.6, 2,048 11.7.
INT_FOLD_GROUP_BLOCK = 1024
#: Largest group count the f32 kernel takes: its [chunk, G] one-hot must
#: fit VMEM.
F32_FOLD_MAX_GROUPS = 2048
#: Centroids a group's t-digest keeps (``ops/tdigest.py``).
DIGEST_K = 128
#: Largest groups x centroids of a DENSE (or id-form) group-by whose window
#: digest is built by SORTING the rows by (slot, bin)
#: (``ops/tdigest.py`` ``_sorted_batch_to_digest``): the reduction's two
#: f32 accumulators stay in VMEM (2 x 4 MiB here). Above it, and on the
#: CPU, such a fold's rows scatter into the [G, B] histogram. The limit
#: says nothing of a KEYED group-by on the TPU's routes (PR 41): there
#: the rows sort by (packed key, value) beside the keyed integer fold's
#: own sort, whatever the slot count (``ordered_batch_to_digest``: 2^24
#: slots in ``http_edges_1chip``), and no histogram exists.
SORTED_DIGEST_MAX_SLOTS = 1 << 20
#: What ``digest_bins`` reads where a window's rows are not binned at all:
#: the keyed digest orders a group's rows by the value's whole 32-bit
#: pattern.
KEYED_DIGEST_BINS = 1 << 32


def digest_route(platform: str, slots: int) -> str:
    """The route of a ``quantiles`` aggregate's window digest over
    ``slots`` = groups x centroids where the rows carry group ids
    (dense, or the id form): ``sorted_digest`` or ``xla``."""
    if platform == "tpu" and slots <= SORTED_DIGEST_MAX_SLOTS:
        return "sorted_digest"
    return "xla"


def digest_hist_bins(num_groups: int) -> int:
    """Histogram width B of the binned routes (``sorted_digest``,
    ``xla``), 0 where a window's rows are not binned.

    B=8192 gives positive values 4 mantissa bits of resolution (bins are
    ~4.4% wide in value; the within-bin weighted mean recovers most of
    that), and the [G, B] f32 scratch is kept near 2^25 slots: 4,096
    bins at 8,192 groups. Past that the width would go on halving (256
    bins at 2^17 groups, a factor of four wide in value: most of a
    small group's rows in one bin), so no histogram is built: the rows
    sort by (group, value) and are not binned at all
    (``ops/tdigest.py`` ``ordered_batch_to_digest``)."""
    b = 8192
    while b > 4096 and num_groups * b > (1 << 25):
        b //= 2
    return b if num_groups * b <= (1 << 25) else 0


def digest_bins(num_groups: int, keyed_sort: bool) -> int:
    """What ``digest_bins`` reads on a fold program's dispatch: the
    histogram's width, or ``KEYED_DIGEST_BINS`` where the rows sort by
    their values (the keyed fold's digests, and any past the widths a
    histogram is built at)."""
    return (KEYED_DIGEST_BINS if keyed_sort
            else digest_hist_bins(num_groups) or KEYED_DIGEST_BINS)


#: Largest build + probe rows whose shared key-id space ``ops/join.py``
#: ``device_join`` takes by SORTING on the TPU's routes; above it, and on
#: the CPU's, the open-addressing table. The table's rounds are a
#: ``while_loop`` that ends when every row is placed, so the kernel's time
#: follows the data: px/net_flow_graph's join (4,096 + 65,536 rows, a
#: 262,144-slot table) places all but 3-4 rows in three rounds and takes
#: a fourth for them on 52 of 60 seeds, three on 8, at 3.55 ms a round
#: (scatters: kernel wait 32.3 against 28.75 ms; my chip run, PR 32,
#: PERF.md section 6), a step of 2.4 % of the refresh from seed to seed.
#: The sort is the fold's own rule on this platform (``exec/fold_plan.py``:
#: rows sort on the TPU, hash on the CPU) and its time does not follow
#: the data. The limit is one window's rows, the size PR 29's pieces were
#: measured at (a 2^21-row ``lax.sort`` 2.15-3.55 ms, a full scatter 10.5,
#: a full gather 18.8); larger single-shot joins keep the table until
#: they are measured. Read after it (same runs): the kernel ~30 -> 19.4 ms
#: a refresh, ``refresh_p50_ms`` 148.4 -> 136.2 on every seed.
JOIN_SORT_IDS_MAX_ROWS = 1 << 21


#: Most u32 operands (key words + payload words) of the keyed fold's one
#: ``lax.sort`` (``ops/groupby.py`` ``sorted_group_fold``): up to it a
#: window's sum planes ride the key sort as payload, past it they follow
#: through a row index as a merge's do. What bounds it is the compiler's
#: seconds in a script's first request (120 s to answer), against 20 ms a
#: window ever after. ``tools/fold_sweep.py --micro --only payload_sort
#: index_way`` on the chip's host (a v5e, PR 35), run ms / compile s of
#: the payload sort against the index way's three sorts, 2^21 rows:
#:
#:   key words  payload words  operands   payload       index
#:       1            2            3      5.0 / 16.8   24.9 / 10.7
#:       1            4            5      7.2 / 25.5   27.5 /  8.5
#:       1            6            7      9.6 / 38.0   35.3 /  9.6
#:       3            2            5      7.6 / 44.7   27.7 / 30.4
#:       3            4            7     10.0 / 76.8   30.0 / 33.2
#:       3            6            9     13.1 / 89.6   38.4 / 30.9
#:
#: (2^18 rows: 1.3-2.2 against 3.2-4.0 ms at the same compile seconds.)
#: The payload sort is 20-25 ms a window faster wherever it was tried and
#: 6-17 s dearer to compile up to five operands, 28-59 s from seven: with
#: three key words a first request that compiles ~35 s beside its window
#: program would pass 100 s. One sort a plane under the same keys (what a
#: further maximum takes) was reckoned as a step between and is none: two
#: or three five-operand sorts compile in 89 / 134 s and run in 15 / 23
#: ms where one sort carries the same planes in 77 / 90 s and 10 / 13 ms.
SORT_PAYLOAD_MAX_OPERANDS = 5


def sorted_fold_ride(n: int, g: int, key_words: int, planes: int) -> str:
    """How the sum planes that are not the primary maximum reach group
    order in ``sorted_group_fold``, from what is static at trace time: n
    rows into g slots, ``key_words`` sort keys (the group key's and the
    primary maximum's), ``planes`` INT64 planes of two words each. ``""``
    with no such plane; else ``payload`` (operands of the key sort) or
    ``index`` (the row index rides, an inverse sort, a batched [P, N]
    sort). The ``ride`` attribute of a ``sorted_int`` window's
    ``device.dispatch``."""
    if not planes:
        return ""
    # ``_front``'s rule: a window is long against its slots, a merge of
    # two states is N = 2 g.
    if n >= 4 * g and key_words + 2 * planes <= SORT_PAYLOAD_MAX_OPERANDS:
        return "payload"
    return "index"


def int_fold_groups(g: int) -> int:
    """g padded for ``dense_group_fold_int``: to whole 128-lane tiles,
    and above one group block to whole blocks (so a dictionary one entry
    larger than a block costs one more block, not the kernel)."""
    step = 128 if g <= INT_FOLD_GROUP_BLOCK else INT_FOLD_GROUP_BLOCK
    return -(-g // step) * step
