"""A number the driver already holds (a counter of the program, a count
of the run): ``args.path`` is the keys to follow in the run's result."""


def read(ctx, metric):
    v = ctx["run"]
    for k in metric["args"]["path"]:
        if not isinstance(v, dict) or k not in v:
            return None
        v = v[k]
    return float(v) * float(metric["args"].get("scale", 1.0))
