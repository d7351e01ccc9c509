"""A statistic (p50, p95, max, mean) of one of the run's span series."""

from perfbench.readers._common import stat


def read(ctx, metric):
    a = metric["args"]
    values = ctx["run"].get("spans", {}).get(a["span"], [])
    v = stat(values, a["stat"])
    return None if v is None else v * float(a.get("scale", 1.0))
