"""Device time in which an operation ran inside the host spans named
``args.span_name``, over the number of those spans, in ms: what one such
phase of the program costs the device."""

from perfbench.readers._intervals import busy_intervals, overlap, spans_named
from perfbench.tracered import merge


def read(ctx, metric):
    spans = spans_named(ctx["trace"], metric["args"]["span_name"])
    if not spans:
        return None
    return 1e3 * overlap(merge(spans), busy_intervals(ctx["trace"])) / len(spans)
