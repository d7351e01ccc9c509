"""Device time per step in one phase of the train step (``args.phase``:
``augment``, ``fwd``, ``bwd``, ``optimizer``, ``telemetry``, ``sync``,
``unscoped``), in ms.

The trace names each op by its HLO line and carries no ``op_name``, so
the program's ``graftscope/*`` scopes do not reach it. The program maps
each instruction of its compiled step to a phase before it opens the
capture (``utils/profiling.py``: ``step_phases()``, {module: {instruction:
phase}}, the rules in ``docs/observability.md``). This reader takes the
ops that start inside the step module's intervals (``args.program``, a
pattern on the trace's module names) on each device, gives each instant
of them to the op that started last among those running (an op nested in
another keeps its own time, the enclosing op the remainder, so the
phases sum to the module's busy time), and sums by the phase of each
op's instruction; then it averages over chips and divides by the traced
steps. It reads nothing where the program keeps no map (a program before
the map existed) or where less than 99% of the module's op time found
its instruction in the map."""

from __future__ import annotations

import bisect
import heapq
import re
from collections import defaultdict

from perfbench.readers._common import steps_in_trace

COVERAGE = 0.99
UNMAPPED = "_unmapped"


def program_phases():
    """The program's phase maps, or None where it keeps none."""
    try:
        from cs744_pytorch_distributed_tutorial_tpu.utils import profiling
    except ImportError:
        return None
    get = getattr(profiling, "step_phases", None)
    return get() if get is not None else None


def instruction(op_name: str) -> str:
    """``%fusion.71 = bf16[...] fusion(...)`` -> ``fusion.71``."""
    return op_name.partition(" = ")[0].strip().lstrip("%")


def exclusive(ops) -> list[float]:
    """Each op's own time, for ops given as (start, end): every instant
    goes to the op that started last among those running then (the
    innermost, where ops nest), so the times sum to the ops' union."""
    order = sorted(range(len(ops)), key=lambda i: ops[i][0])
    points = sorted({t for lo, hi in ops for t in (lo, hi)})
    own = [0.0] * len(ops)
    running: list[tuple[float, float, int]] = []
    k = 0
    for a, b in zip(points, points[1:]):
        while k < len(order) and ops[order[k]][0] <= a:
            i = order[k]
            heapq.heappush(running, (-ops[i][0], ops[i][1], i))
            k += 1
        while running and running[0][1] <= a:
            heapq.heappop(running)
        if running:
            own[running[0][2]] += b - a
    return own


def phase_seconds(trace, program: str, phases) -> tuple[dict[str, float], int]:
    """({phase: device seconds summed over chips}, number of chips that
    ran the module) for the ops inside modules matching ``program``;
    ops whose instruction the map lacks count under ``_unmapped``."""
    rx = re.compile(program)
    out: dict[str, float] = defaultdict(float)
    chips = 0
    for d, programs in trace.device_programs.items():
        spans = sorted((lo, hi, name.partition("(")[0]) for name, lo, hi in programs if rx.search(name))
        if not spans:
            continue
        chips += 1
        starts = [s[0] for s in spans]
        inside, names = [], []
        for e in trace.device_ops.get(d, []):
            i = bisect.bisect_right(starts, e[2]) - 1
            if i >= 0 and e[2] < spans[i][1]:
                inside.append((e[2], e[3]))
                names.append(phases.get(spans[i][2], {}).get(instruction(e[0]), UNMAPPED))
        for phase, s in zip(names, exclusive(inside)):
            out[phase] += s
    return dict(out), chips


def read(ctx, metric):
    args = metric["args"]
    phases = program_phases()
    n = steps_in_trace(ctx, args)
    if not phases or not n:
        return None
    by, chips = phase_seconds(ctx["trace"], args["program"], phases)
    total = sum(by.values())
    if not chips or total <= 0.0 or by.get(UNMAPPED, 0.0) > (1.0 - COVERAGE) * total:
        return None
    return 1e3 * by.get(args["phase"], 0.0) / chips / n
