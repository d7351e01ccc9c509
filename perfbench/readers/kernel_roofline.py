"""A kernel's share of its roofline: the least time the chip could take
for the work the algorithm needs (from shapes, perfbench/work.py) over
the device time of the operations matching ``args.pattern``."""

from perfbench import work
from perfbench.readers._common import steps_in_trace


def needed(ctx, kind: str) -> tuple[float, float]:
    run, cfg = ctx["run"], ctx["config"]
    if kind == "flash_train":
        b, t = run["batch"], run["seq_len"]
        return work.flash_train_flops_per_step(b, t, cfg), work.flash_train_bytes_per_step(b, t, cfg)
    if kind == "paged_decode":
        live = run["counts"]["mean_live_tokens_traced"]
        return work.paged_decode_attn_flops(live, cfg), work.paged_decode_attn_bytes(live, cfg)
    raise ValueError(f"unknown work {kind!r}")


def read(ctx, metric):
    a = metric["args"]
    n = steps_in_trace(ctx, a)
    s = ctx["trace"].seconds(a["pattern"])
    if not n or s <= 0.0:
        return None
    flops, nbytes = needed(ctx, a["work"])
    return 100.0 * work.roofline_seconds(flops, nbytes, ctx["peaks"]) / (s / n)
