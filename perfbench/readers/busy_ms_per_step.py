"""Device time per step of the traced window in which an operation ran,
in ms. Set beside the window's own time per step it says how much of a
step the device works, also where the profiler disturbs the host side
of the traced steps (as it does in the ResNet cells, PERF.md)."""

from perfbench.readers._common import steps_in_trace


def read(ctx, metric):
    n = steps_in_trace(ctx, metric["args"])
    if not n:
        return None
    return 1e3 * ctx["trace"].busy_s / n
