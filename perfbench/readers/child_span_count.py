"""A statistic of how many ``args.child`` spans lie inside each
``args.parent`` span (a parent with none counts as 0)."""

from perfbench.readers._common import stat
from perfbench.readers._intervals import spans_named


def read(ctx, metric):
    a = metric["args"]
    parents = spans_named(ctx["trace"], a["parent"])
    children = spans_named(ctx["trace"], a["child"])
    if not parents or not children:
        return None
    counts = [sum(1 for lo, hi in children if p_lo <= lo and hi <= p_hi) for p_lo, p_hi in parents]
    return stat(counts, a["stat"])
