"""Device idle time per step of the traced window, in ms: the gaps
between device operations, which is where the device waits for the host."""

from perfbench.readers._common import steps_in_trace


def read(ctx, metric):
    n = steps_in_trace(ctx, metric["args"])
    if not n:
        return None
    t = ctx["trace"]
    return 1e3 * (t.window_s - t.busy_s) / n
