"""Device time per step in the operations whose name matches
``args.pattern`` (whatever implements them), in ms."""

from perfbench.readers._common import steps_in_trace


def read(ctx, metric):
    n = steps_in_trace(ctx, metric["args"])
    s = ctx["trace"].seconds(metric["args"]["pattern"])
    if not n or s <= 0.0:
        return None
    return 1e3 * s / n
