"""Compiled programs on the device (the trace's module events) whose
name matches ``args.pattern``: their share of device busy time in %
(``stat: share``) or a statistic of their durations in ms."""

from perfbench.readers._common import stat


def read(ctx, metric):
    a, t = metric["args"], ctx["trace"]
    durations = t.program_durations(a["pattern"])
    if not durations:
        return None
    if a["stat"] == "share":
        total = sum(t.program_durations(".*"))
        return 100.0 * sum(durations) / total if total > 0 else None
    return 1e3 * stat(durations, a["stat"])
