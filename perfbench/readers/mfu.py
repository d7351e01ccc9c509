"""The whole step's share of the chip's peak: the FLOPs the forward and
backward passes need (perfbench/work.py; recomputation not counted) times
the run's own end-to-end rate, over the bf16 peak."""

from perfbench import work


def read(ctx, metric):
    run, cfg, kind = ctx["run"], ctx["config"], metric["args"]["kind"]
    peak = ctx["peaks"]["bf16_flops_per_s"]
    if kind == "resnet18_train":
        rate = run["end_to_end"]["train_samples_per_s_per_chip"]
        per = work.resnet18_cifar_train_flops_per_sample(
            stages=tuple(cfg["stage_sizes"]), widths=tuple(cfg["widths"]),
            image=cfg["image_size"], classes=cfg["num_classes"],
        )
        return 100.0 * rate * per / peak
    if kind == "transformer_train":
        rate = run["end_to_end"]["train_tokens_per_s_per_chip"]
        return 100.0 * rate * work.transformer_train_flops_per_token(cfg, run["seq_len"]) / peak
    if kind == "transformer_serve":
        c = run["counts"]
        flops = 2.0 * work.transformer_matmul_params(cfg) * (c["prompt_tokens_in_window"] + c["tokens_in_window"])
        flops += c["attention_flops_in_window"]
        return 100.0 * flops / run["window_s"] / peak
    raise ValueError(f"unknown mfu kind {kind!r}")
