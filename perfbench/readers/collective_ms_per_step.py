"""Device time per step in collective operations, or (``args.exposed``)
the part of it during which no compute ran on that device, in ms."""

from perfbench.readers._common import steps_in_trace


def read(ctx, metric):
    n = steps_in_trace(ctx, metric["args"])
    total, exposed = ctx["trace"].collective_seconds()
    if not n or total <= 0.0:
        return None
    return 1e3 * (exposed if metric["args"].get("exposed") else total) / n
