"""What the Kelvin waits for the slowest node: per request, the end of
the last PEM's ``publish`` span (its payload, stats and eos on the bus)
less the end of the first's, over every PEM tracer of the stack (``pem``
and ``pem.<n>``). Summed over a refresh's requests, median over the
window's refreshes. A request that fewer than two PEMs published for is
left out; nothing where the stack has one PEM."""

from ..span_readers import by_qid, median_per_refresh, named


def pem_tracers(ctx) -> list:
    return [k for k in ctx["spans"] if k == "pem" or k.startswith("pem.")]


def read(ctx):
    ends: dict = {}
    for tracer in pem_tracers(ctx):
        for qid, t in by_qid(ctx, tracer, "fragment").items():
            published = [s.end_ns for s in named(t, "publish")]
            if published:
                ends.setdefault(qid, []).append(max(published))
    return median_per_refresh(ctx, {
        qid: (max(e) - min(e)) / 1e6 for qid, e in ends.items() if len(e) > 1
    })
