"""Time in the host spans named ``args.span_name`` as a share of the
time in those named ``args.of``, in % (the first nest inside the second)."""

from perfbench.readers._intervals import spans_named, total


def read(ctx, metric):
    a = metric["args"]
    part = spans_named(ctx["trace"], a["span_name"])
    whole = total(spans_named(ctx["trace"], a["of"]))
    if not part or whole <= 0.0:
        return None
    return 100.0 * total(part) / whole
