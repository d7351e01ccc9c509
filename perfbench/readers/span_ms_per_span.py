"""Host time in the spans named ``args.span_name``, over the number of
``args.per_span`` spans, in ms: what a phase that runs several times a
step (or not in every step) costs one step of the loop."""

from perfbench.readers._intervals import spans_named, total


def read(ctx, metric):
    a = metric["args"]
    part = spans_named(ctx["trace"], a["span_name"])
    n = len(spans_named(ctx["trace"], a["per_span"]))
    if not part or not n:
        return None
    return 1e3 * total(part) / n
