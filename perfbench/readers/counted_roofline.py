"""A kernel's share of its roofline where the work is counted by the
program itself: the least time the chip could take for what the traced
decode steps' counters say the algorithm needed
(``perfbench/work_sparse_moe.py``), over the device time of the
operations matching ``args.pattern`` inside ``args.span_name`` spans.
The driver leaves the counters of the traced steps under
``counts.traced``; where the program has no such counters it leaves
none, and the metric is left out."""

from perfbench import work, work_sparse_moe as wsm
from perfbench.readers._ops_in_span import seconds_in_spans


def needed(kind: str, c, cfg) -> tuple[float, float]:
    if kind == "sparse_attn":
        return wsm.attention_flops(c["selected_tokens"], cfg), wsm.attention_bytes(c["selected_tokens"], cfg)
    if kind == "indexer":
        return wsm.indexer_flops(c["scored_tokens"], cfg), wsm.indexer_bytes(c["scored_tokens"], cfg)
    if kind == "moe_gmm":
        return wsm.moe_flops(c["token_expert_pairs"], cfg), wsm.moe_bytes(c["experts_hit"], cfg)
    raise ValueError(f"unknown work {kind!r}")


def read(ctx, metric):
    a = metric["args"]
    c = (ctx["run"].get("counts") or {}).get("traced")
    if not c or not c.get("decode_steps"):
        return None
    s, n = seconds_in_spans(ctx["trace"], a["pattern"], a["span_name"])
    if not n or s <= 0.0:
        return None
    flops, nbytes = needed(a["work"], c, ctx["config"])
    least = work.roofline_seconds(flops, nbytes, ctx["peaks"]) / c["decode_steps"]
    return 100.0 * least / (s / n)
