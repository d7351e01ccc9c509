"""Helpers of the readers that lay the program's host spans against the
device's operations: both are on the profiler's one clock, so a span's
share of the device's busy and idle time is an intersection of intervals
(``tracered``'s ``merge`` and ``subtract``; the idle intervals are
``Trace.idle_gaps()``: idle time begins after the traced stretch's first
operation and ends before its last). A reader that finds no span of the
name it is given returns None."""

from __future__ import annotations

from perfbench.tracered import merge, subtract


def spans_named(trace, names) -> list[tuple[float, float]]:
    """(start, end) of every host span whose name is in ``names``."""
    names = {names} if isinstance(names, str) else set(names)
    return [(s[1], s[2]) for s in trace.host_spans if s[0] in names]


def busy_intervals(trace) -> list[tuple[float, float]]:
    """Merged intervals in which an operation ran on the first device."""
    return merge((e[2], e[3]) for e in trace.device_ops[min(trace.device_ops)])


def total(intervals) -> float:
    return sum(hi - lo for lo, hi in intervals)


def overlap(intervals, cover) -> float:
    """Length of ``intervals`` (merged) that ``cover`` (merged) covers."""
    return total(intervals) - subtract(intervals, cover)
