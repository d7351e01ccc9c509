"""Device time of the operations a program's named scope holds. The
trace names each op by its instruction in the compiled module and
carries no ``op_name``; the driver leaves, under ``counts.scopes``,
{module: {instruction: scope}} of the compiled programs that ran (read
from their text, ``op_name`` metadata), and this joins the two: the ops
that start inside a run of a module whose name matches ``program``, on
the first device, whose instruction the map puts under ``scope``, each
with its own time (an op nested in another, a loop's body in its
``while``, keeps its time and the enclosing op the remainder, as the
train steps' phases are read). Where the run holds no map, or the trace
no run of such a module, there is nothing to read."""

from __future__ import annotations

import bisect
import re

from perfbench.readers.phase_ms_per_step import exclusive, instruction


def scope_seconds(ctx, scope: str, program: str) -> tuple[float, int] | None:
    """(device seconds in the ops of ``scope``, runs of the programs
    matching ``program``), or None."""
    maps = (ctx["run"].get("counts") or {}).get("scopes")
    trace = ctx["trace"]
    if not maps or not trace.device_programs:
        return None
    rx = re.compile(program)
    d = min(trace.device_ops)
    runs = sorted(
        (lo, hi, name.partition("(")[0]) for name, lo, hi in trace.device_programs.get(d, [])
        if rx.search(name) and name.partition("(")[0] in maps
    )
    if not runs:
        return None
    starts = [r[0] for r in runs]
    inside, ours = [], []
    for e in trace.device_ops[d]:
        i = bisect.bisect_right(starts, e[2]) - 1
        if i >= 0 and e[2] < runs[i][1]:
            inside.append((e[2], e[3]))
            ours.append(maps[runs[i][2]].get(instruction(e[0])) == scope)
    return sum(s for s, mine in zip(exclusive(inside), ours) if mine), len(runs)
