"""Device time a run of a compiled program spends in one named scope's
ops (``args.scope`` in the programs matching ``args.program``;
``_scoped.py``), in ms."""

from perfbench.readers._scoped import scope_seconds


def read(ctx, metric):
    a = metric["args"]
    got = scope_seconds(ctx, a["scope"], a["program"])
    if got is None or got[0] <= 0.0:
        return None
    return 1e3 * got[0] / got[1]
