"""A statistic of the durations of a named host span in the trace, ms."""

from perfbench.readers._common import stat


def read(ctx, metric):
    a = metric["args"]
    v = stat(ctx["trace"].host_span_durations(a["span_name"]), a["stat"])
    return None if v is None else 1e3 * v
