"""Device operations that ran inside a named host span: the decode
step's ops are those under ``serve/decode`` (the step and its blocking
fetch), whatever a prefill chunk's ops of the same name and another shape
do under ``serve/prefill_chunk``. Spans and ops share the profiler's
clock; an op belongs to the span that holds its start."""

from __future__ import annotations

import bisect

from perfbench.readers._intervals import spans_named


def seconds_in_spans(trace, pattern: str, span_name: str) -> tuple[float, int]:
    """(device seconds, on the first device, in ops matching ``pattern``
    that started inside a span named ``span_name``; number of such
    spans)."""
    spans = sorted(spans_named(trace, span_name))
    if not spans:
        return 0.0, 0
    starts = [lo for lo, _ in spans]
    total = 0.0
    for _, e in trace.ops(pattern, device=min(trace.device_ops)):
        i = bisect.bisect_right(starts, e[2]) - 1
        if i >= 0 and e[2] < spans[i][1]:
            total += e[3] - e[2]
    return total, len(spans)
