"""The latent page walk's share of its roofline in the ``deepseek_v2``
family (the accepted ``latent_roofline`` reads the ``longcat_flash``
family's keys): the least time the chip could take to read the latent
rows the traced decode steps attended (the engine's
``latent_tokens_read``, left by the driver under ``counts.traced``), each
ONCE at its unpadded width, and to make the absorbed products over them
(``perfbench/work_deepseek_v2.py``: 278,528 FLOPs a row at 128 heads),
whichever bound is nearer, over the device time of the operations
matching ``args.pattern`` inside ``args.span_name`` spans. Where the
program has no such counter the driver leaves none, and the metric is
left out."""

from perfbench import work, work_deepseek_v2 as wd2
from perfbench.readers._ops_in_span import seconds_in_spans


def read(ctx, metric):
    a = metric["args"]
    c = (ctx["run"].get("counts") or {}).get("traced")
    rows = (c or {}).get("latent_tokens_read")
    if not rows or not c.get("decode_steps"):
        return None
    s, n = seconds_in_spans(ctx["trace"], a["pattern"], a["span_name"])
    if not n or s <= 0.0:
        return None
    cfg = ctx["config"]
    least = work.roofline_seconds(
        wd2.absorbed_attention_flops(rows, cfg), wd2.latent_attention_bytes(rows, cfg), ctx["peaks"]
    )
    return 100.0 * (least / c["decode_steps"]) / (s / n)
