"""What a request costs outside the broker's root, on the one clock: the
client's ``t0`` to the root's start (the call, the tracer's begin) plus
the root's end to the client's ``t1`` (the broker trace's own sinks, the
result's decode and the builder's copies). Summed over a refresh's
requests, median over the window's refreshes."""

from ..span_readers import by_qid, median_per_refresh


def read(ctx):
    roots = {qid: t.root
             for qid, t in by_qid(ctx, "broker", "distributed").items()
             if t.root.end_ns}
    return median_per_refresh(ctx, {
        r["qid"]: ((roots[r["qid"]].start_ns - r["t0"] * 1e9)
                   + (r["t1"] * 1e9 - roots[r["qid"]].end_ns)) / 1e6
        for recs in ctx["window"]["refreshes"] for r in recs
        if r["qid"] in roots
    })
