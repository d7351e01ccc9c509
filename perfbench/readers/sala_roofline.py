"""A MiniCPM-SALA kernel's share of its roofline: the least time the chip
could take for what the traced steps' counters say the algorithm needed
(``perfbench/work_minicpm_sala.py``: ``args.work`` names the kernel),
over the device time of the operations matching ``args.pattern``, inside
``args.span_name`` spans where it is given (the decode step's kernels),
else anywhere in the trace (the chunk's, which run under the admission's
spans); or, for work that XLA spreads over many ops, those of the named
scope ``args.scope`` in the programs matching ``args.program``
(``_scoped.py``). The driver leaves the counters of the traced steps
under ``counts.traced``; where the program has no such counter, or the
trace no such operation, the metric is left out."""

from perfbench import work, work_minicpm_sala as wms
from perfbench.readers._ops_in_span import seconds_in_spans
from perfbench.readers._scoped import scope_seconds


def read(ctx, metric):
    a = metric["args"]
    c = (ctx["run"].get("counts") or {}).get("traced")
    if not c:
        return None
    try:
        flops, nbytes = wms.needed(a["work"], c, ctx["config"])
    except KeyError:
        return None
    if flops <= 0.0 and nbytes <= 0.0:
        return None
    trace = ctx["trace"]
    if a.get("scope"):
        s = (scope_seconds(ctx, a["scope"], a["program"]) or (0.0, 0))[0]
    elif a.get("span_name"):
        s, _ = seconds_in_spans(trace, a["pattern"], a["span_name"])
    else:
        s = sum(e[3] - e[2] for _, e in trace.ops(a["pattern"], device=min(trace.device_ops)))
    if s <= 0.0:
        return None
    return 100.0 * work.roofline_seconds(flops, nbytes, ctx["peaks"]) / s
