"""Share of the traced window in which no operation ran on the device."""


def read(ctx, metric):
    return 100.0 * ctx["trace"].idle_share
