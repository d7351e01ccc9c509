"""A paged-attention kernel's share of its roofline in a model whose
layers differ by kind: the least time the chip could take to read the K
and V rows of the keys the traced decode steps attended on the layers of
``args.kind`` (``window`` or ``full``; the engine's ``window_tokens_read``
/ ``full_tokens_read``, left by the driver under ``counts.traced``) and to
score them (``perfbench/work_window_moe.py``), over the device time of
the operations matching ``args.pattern`` inside ``args.span_name`` spans.
Where the program has no such counters the driver leaves none, and the
metric is left out."""

from perfbench import work, work_window_moe as wwm
from perfbench.readers._ops_in_span import seconds_in_spans


def read(ctx, metric):
    a = metric["args"]
    c = (ctx["run"].get("counts") or {}).get("traced")
    keys = (c or {}).get(f"{a['kind']}_tokens_read")
    if not keys or not c.get("decode_steps"):
        return None
    s, n = seconds_in_spans(ctx["trace"], a["pattern"], a["span_name"])
    if not n or s <= 0.0:
        return None
    cfg = ctx["config"]
    least = work.roofline_seconds(wwm.attention_flops(keys, cfg), wwm.attention_bytes(keys, cfg), ctx["peaks"])
    return 100.0 * (least / c["decode_steps"]) / (s / n)
