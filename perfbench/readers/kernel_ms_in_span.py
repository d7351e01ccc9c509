"""Device time, per span named ``args.span_name``, in the operations
whose name matches ``args.pattern`` and that ran inside such a span, in
ms (``kernel_ms_per_step`` with the step told apart by the program's own
span, for a loop whose phases run ops of one name in several shapes)."""

from perfbench.readers._ops_in_span import seconds_in_spans


def read(ctx, metric):
    a = metric["args"]
    s, n = seconds_in_spans(ctx["trace"], a["pattern"], a["span_name"])
    if not n or s <= 0.0:
        return None
    return 1e3 * s / n
