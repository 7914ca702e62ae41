"""The broker's own work on a request: its ``snapshot``, ``plan``,
``admit``, ``register`` and ``finish`` spans, and the root's time that
no child span covers (``compile``, ``dispatch`` and ``await`` are the
children left out). Summed over a refresh's requests, median over the
window's refreshes."""

from ..span_readers import by_qid, covered_ns, median_per_refresh, total_ms

OWN = ("snapshot", "plan", "admit", "register", "finish")


def read(ctx):
    out = {}
    for qid, t in by_qid(ctx, "broker", "distributed").items():
        root = t.root
        if not root.end_ns:
            continue
        children = [(s.start_ns, s.end_ns) for s in t.spans
                    if s.parent_id == root.span_id and s.end_ns]
        bare = (root.end_ns - root.start_ns) - covered_ns(
            children, root.start_ns, root.end_ns
        )
        out[qid] = sum(total_ms(t, n) for n in OWN) + bare / 1e6
    return median_per_refresh(ctx, out)
