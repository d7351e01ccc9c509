"""Helpers the readers share. A reader is ``read(ctx, metric) -> float |
None``: ``ctx`` holds the run's own numbers (``run``), the reduced trace
(``trace``), the cell, its configuration and traffic, and the chip's
peaks; ``metric`` is the metric's file, whose ``args`` parametrise the
reader. A reader that finds nothing to read returns None."""

from __future__ import annotations

import numpy as np


def steps_in_trace(ctx, args) -> float | None:
    """How many steps the traced window held: by the host span the step
    loop writes (``step_span``), else the driver's count."""
    name = args.get("step_span")
    if name:
        n = len(ctx["trace"].host_span_durations(name))
        if n:
            return float(n)
    n = ctx["run"].get("steps_traced")
    return float(n) if n else None


def stat(values, which: str) -> float | None:
    if len(values) == 0:
        return None
    v = np.asarray(values, np.float64)
    if which == "max":
        return float(v.max())
    if which == "mean":
        return float(v.mean())
    if which.startswith("p"):
        return float(np.percentile(v, float(which[1:])))
    raise ValueError(f"unknown statistic {which!r}")
