"""Device idle time (the gaps between device operations) that falls inside
the host spans named in ``args.span_names``, or with ``args.outside``
outside every one of them, over the number of ``args.per_span`` spans, in
ms: which phase of the host loop the device waits in."""

from perfbench.readers._intervals import overlap, spans_named, total
from perfbench.tracered import merge


def read(ctx, metric):
    a, trace = metric["args"], ctx["trace"]
    spans = spans_named(trace, a["span_names"])
    n = len(spans_named(trace, a["per_span"]))
    if not spans or not n:
        return None
    idle = trace.idle_gaps()
    inside = overlap(idle, merge(spans))
    return 1e3 * (total(idle) - inside if a.get("outside") else inside) / n
