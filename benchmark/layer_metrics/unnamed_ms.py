"""What is left of the broker's root once every span that says what was
done is laid over it: the root's length minus the union, clipped to the
root, of the spans of the request's three traces (the broker's, the
PEM's ``fragment``, the Kelvin's ``merge``), their ``bus.deliver`` hops
among them. Left out are the spans that only hold others or only say
that someone waited: the roots, the ``fragment`` containers, ``await*``
and ``merge.wait``. Summed over a refresh's requests, median over the
window's refreshes."""

from ..span_readers import by_qid, covered_ns, median_per_refresh


def request_traces(ctx) -> dict:
    """{qid: [the broker's trace, then the PEM's and the Kelvin's where
    they are in hand]} of the requests whose broker root has ended."""
    out = {qid: [b]
           for qid, b in by_qid(ctx, "broker", "distributed").items()
           if b.root.end_ns}
    for tracer, kind in (("pem", "fragment"), ("kelvin", "merge")):
        for qid, t in by_qid(ctx, tracer, kind).items():
            if qid in out:
                out[qid].append(t)
    return out


def says_what_was_done(trace, span) -> bool:
    return bool(
        span.end_ns and span.span_id != trace.root.span_id
        and span.name not in ("fragment", "merge.wait")
        and not span.name.startswith("await")
    )


def named_intervals(traces) -> list:
    return [(s.start_ns, s.end_ns) for t in traces for s in t.spans
            if says_what_was_done(t, s)]


def read(ctx):
    out = {}
    for qid, traces in request_traces(ctx).items():
        root = traces[0].root
        out[qid] = ((root.end_ns - root.start_ns) - covered_ns(
            named_intervals(traces), root.start_ns, root.end_ns
        )) / 1e6
    return median_per_refresh(ctx, out)
