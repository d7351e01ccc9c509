"""The whole serving step's share of the chip's peak for a sparse-
attention mixture of experts: 2 FLOPs an ACTIVE matmul parameter a token
(attention and indexer projections, router, the experts a token is
routed to, head) over the window's prompt and answer tokens, plus
attention over the selected tokens and the indexer over the scored ones
(the driver's ``attention_flops_in_window``: prefill from the prompts'
lengths, decode from the engine's counters), over the window and the
bf16 peak."""

from perfbench import work_sparse_moe as wsm


def read(ctx, metric):
    run, cfg = ctx["run"], ctx["config"]
    if "sa_config" not in cfg or "num_experts" not in cfg:
        return None
    c = run["counts"]
    flops = 2.0 * wsm.active_matmul_params(cfg) * (c["prompt_tokens_in_window"] + c["tokens_in_window"])
    flops += c["attention_flops_in_window"]
    return 100.0 * flops / run["window_s"] / ctx["peaks"]["bf16_flops_per_s"]
