"""CLI for the long-context LM family: train, then optionally generate.

The image classifiers have ``cli.py`` (the reference's part presets);
this is the transformer counterpart — no analog exists in the reference
(its only model is conv VGG-11, ``master/part1/model.py:30-46``):

    # train on synthetic tokens over a data x seq mesh:
    python -m cs744_pytorch_distributed_tutorial_tpu.lm_cli \
        --data-parallel 2 --seq-parallel 4 --steps 100

    # byte-level LM on any local file, then sample from it:
    python -m cs744_pytorch_distributed_tutorial_tpu.lm_cli \
        --text-file README.md --steps 200 --generate 128 \
        --prompt "The reference" --temperature 0.8 --top-k 40
"""

from __future__ import annotations

import argparse
import json
import math as _math


def _json_loss(loss):
    """A loss value safe for json.dumps: non-finite floats become null
    (bare NaN is invalid JSON; the 'finite' key carries the signal)."""
    return loss if loss is not None and _math.isfinite(loss) else None


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="cs744-tpu-lm",
        description="TPU-native long-context LM training + generation",
    )
    # model
    p.add_argument("--vocab-size", type=int, default=1024,
                   help="ignored with --text-file (byte vocab = 256)")
    p.add_argument("--num-layers", type=int, default=4)
    p.add_argument("--num-heads", type=int, default=8)
    p.add_argument("--num-kv-heads", type=int, default=None,
                   help="grouped-query attention KV head count (1 = MQA)")
    p.add_argument("--d-model", type=int, default=256)
    p.add_argument("--d-ff", type=int, default=1024)
    p.add_argument("--max-seq-len", type=int, default=2048)
    from cs744_pytorch_distributed_tutorial_tpu.models.transformer import (
        ATTENTION_IMPLS,
    )

    p.add_argument("--attention-impl", default="ring",
                   choices=list(ATTENTION_IMPLS))
    p.add_argument("--compute-dtype", default="float32",
                   choices=["float32", "bfloat16"])
    p.add_argument("--remat", action="store_true")
    p.add_argument("--remat-policy", default="none", choices=["none", "dots"],
                   help="remat granularity: recompute everything, or keep "
                        "matmul outputs and recompute elementwise only")
    p.add_argument("--scan-layers", action="store_true",
                   help="run the homogeneous blocks as one nn.scan body "
                        "instead of L unrolled copies — identical numerics, "
                        "O(L) smaller traced program (the compile-wall "
                        "lever for deep/big-batch configs); params carry a "
                        "leading layer axis")
    p.add_argument("--tie-embeddings", action="store_true",
                   help="share the token embedding with the output head")
    p.add_argument("--norm", default="layernorm",
                   choices=["layernorm", "rmsnorm"],
                   help="block normalization (rmsnorm = llama-family: no "
                        "mean subtraction, no bias)")
    p.add_argument("--mlp", default="gelu", choices=["gelu", "swiglu"],
                   help="block MLP (swiglu = silu(gate(x)) * up(x) with a "
                        "third column-parallel projection)")
    p.add_argument("--use-rope", action="store_true",
                   help="rotary position embeddings instead of the learned "
                        "absolute table")
    p.add_argument("--fused-xent", action="store_true",
                   help="Pallas fused softmax cross-entropy (ops/fused_xent.py)")
    # MoE
    p.add_argument("--moe-experts", type=int, default=0)
    p.add_argument("--moe-top-k", type=int, default=2)
    p.add_argument("--moe-groups", type=int, default=1,
                   help="token groups for MoE routing/capacity (GShard "
                        "dispatch-cost lever; 0 = auto ~1024 tokens/group)")
    p.add_argument("--moe-dispatch",
                   choices=("einsum", "scatter", "dropless"),
                   default="scatter",
                   help="token movement: GShard one-hot einsums, "
                        "scatter-add/gather (round 5 — same routing and "
                        "drop semantics), or dropless (no capacity — "
                        "ragged grouped matmuls; rejects "
                        "--moe-expert-parallel)")
    p.add_argument("--moe-gmm-impl", choices=("auto", "ragged", "pallas"),
                   default="auto",
                   help="grouped-matmul backend for --moe-dispatch "
                        "dropless: auto (fused-epilogue Pallas kernels "
                        "on TPU, ragged_dot elsewhere), ragged, or "
                        "pallas")
    p.add_argument("--moe-expert-parallel", action="store_true")
    # mesh
    p.add_argument("--data-parallel", type=int, default=1)
    p.add_argument("--seq-parallel", type=int, default=1)
    p.add_argument("--tensor-parallel", type=int, default=1)
    p.add_argument("--pipeline-parallel", type=int, default=1,
                   help="stage the block stack over a pipe mesh axis "
                        "(PipelineLMTrainer; composes with data/tensor "
                        "parallelism, rope/GQA/flash/remat, MoE, the "
                        "optimizer registry, checkpointing and eval — "
                        "seq parallelism and generation stay on the "
                        "shard_map engine)")
    p.add_argument("--pipeline-schedule", default="gpipe",
                   choices=["gpipe", "1f1b", "interleaved"],
                   help="gpipe: AD-derived reverse pipeline; 1f1b: "
                        "hand-scheduled backward with a fixed 2S-1 "
                        "activation stash; interleaved: virtual-stage "
                        "schedule cutting the bubble by "
                        "1/num-virtual-stages")
    p.add_argument("--num-virtual-stages", type=int, default=None,
                   help="model chunks per device for "
                        "--pipeline-schedule interleaved (default 2); "
                        "rejected on other schedules")
    p.add_argument("--num-microbatches", type=int, default=2)
    # optimization
    p.add_argument("--global-batch-size", type=int, default=8)
    p.add_argument("--seq-len", type=int, default=256)
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--optimizer", default="adamw",
                   choices=["adamw", "sgd", "lion"])
    p.add_argument("--lr-schedule", default="constant",
                   choices=["constant", "cosine", "warmup_cosine"])
    p.add_argument("--warmup-steps", type=int, default=0)
    p.add_argument("--weight-decay", type=float, default=1e-4)
    p.add_argument("--grad-clip-norm", type=float, default=None)
    p.add_argument("--grad-compress", choices=["none", "int8"],
                   default="none",
                   help="compress the data-parallel gradient sync: int8 "
                        "bucket quantization with error feedback (~3.9x "
                        "fewer gradient bytes; pure-DP layouts only)")
    p.add_argument("--sync-bucket-mb", type=float, default=4.0,
                   help="bucket size (MiB) for the compressed sync's "
                        "coalesced buffers")
    p.add_argument("--sync-overlap", choices=["off", "bucket", "bucket+int8"],
                   default="off",
                   help="overlapped gradient sync (parallel/overlap.py, "
                        "parallel/zero.py): reverse-layer-order buckets, "
                        "per-bucket collective + per-bucket optimizer "
                        "apply. Pure DP needs --optimizer sgd with "
                        "constant lr; --zero1/--fsdp admit any registry "
                        "optimizer and schedule (per-bucket scatter -> "
                        "chunk apply -> gather). 'bucket+int8' overlaps "
                        "the int8+EF wire (--grad-compress int8; pure DP "
                        "or --zero1)")
    p.add_argument("--label-smoothing", type=float, default=0.0)
    p.add_argument("--dropout-rate", type=float, default=0.0,
                   help="residual dropout on each block's sublayer "
                        "outputs; masks are keyed by the step index")
    p.add_argument("--no-halt-on-nonfinite", dest="halt_on_nonfinite",
                   action="store_false", default=True,
                   help="keep training through NaN/inf losses (and emit "
                        "'finite': false in --json) instead of raising "
                        "NonFiniteLossError")
    p.add_argument("--accum-steps", type=int, default=1)
    p.add_argument("--zero1", action="store_true",
                   help="ZeRO-1: shard the optimizer moments over the "
                        "data axis (optimizer memory / data_parallel); "
                        "composes with --tensor-parallel, "
                        "--grad-clip-norm and all --optimizer rules "
                        "(adamw/lion/sgd); no expert parallelism")
    p.add_argument("--fsdp", action="store_true",
                   help="ZeRO-3/FSDP: params AND optimizer moments "
                        "persist as data-axis-sharded chunks, gathered "
                        "just-in-time per step (3x-params state / "
                        "data_parallel); same compositions and "
                        "restrictions as --zero1")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--log-every", type=int, default=20)
    p.add_argument("--metrics-dir", default=None,
                   help="write manifest.json + per-step metrics.jsonl here "
                        "(obs/; rank 0 only)")
    p.add_argument("--metrics-every", type=int, default=None,
                   help="metric emission cadence in steps (default 1; the "
                        "LM loop fetches every step already)")
    p.add_argument("--checkpoint-dir", default=None)
    p.add_argument("--checkpoint-every", type=int, default=0)
    p.add_argument("--snapshot-every", type=int, default=0,
                   help="keep in-memory replicated state snapshots every N "
                        "steps (utils/memstore.py) — restart recovery with "
                        "zero filesystem reads (0 disables)")
    p.add_argument("--snapshot-keep", type=int, default=2,
                   help="in-memory snapshots retained (default 2)")
    p.add_argument("--max-restarts", type=int, default=0,
                   help="restart from the newest recoverable state on "
                        "detected training failures (needs --checkpoint-dir "
                        "or --snapshot-every)")
    p.add_argument("--restart-backoff-s", type=float, default=0.0,
                   help="exponential backoff base between restarts "
                        "(attempt n sleeps backoff * 2^(n-1), capped 60s)")
    p.add_argument("--restart-jitter", choices=("none", "decorrelated"),
                   default="none",
                   help="decorrelate restart backoff across ranks "
                        "(seeded per process/generation) so survivors "
                        "don't stampede the re-elected coordinator")
    # data
    p.add_argument("--text-file", default=None,
                   help="byte-level corpus from a local file (vocab 256); "
                        "default is the synthetic cyclic token stream")
    p.add_argument("--num-seqs", type=int, default=512,
                   help="synthetic stream size / corpus window cap")
    p.add_argument("--eval-frac", type=float, default=0.0,
                   help="hold out this fraction of sequences and report "
                        "final loss/perplexity on them")
    # generation
    p.add_argument("--generate", type=int, default=0, metavar="N",
                   help="after training, sample N tokens")
    p.add_argument("--prompt", default=None,
                   help="generation prompt (bytes with --text-file); "
                        "default: the first training sequence's prefix")
    p.add_argument("--prompt-len", type=int, default=16)
    p.add_argument("--temperature", type=float, default=1.0)
    p.add_argument("--top-k", type=int, default=None)
    p.add_argument("--top-p", type=float, default=None)
    p.add_argument("--int8-decode", nargs="?", const="head", default=None,
                   choices=["head", "all"], metavar="SCOPE",
                   help="generate with weight-only int8 kernels "
                        "(ops/quant.py): stored int8 + per-channel scale, "
                        "dequantized inside the Pallas matmul. SCOPE 'head' "
                        "(default) quantizes only the wide lm_head matmul — "
                        "the measured decode-throughput win; 'all' also "
                        "quantizes the per-layer projections (halves weight "
                        "memory, but per-call dispatch cost loses wall-clock "
                        "on small models)")
    p.add_argument("--int8-kv-cache", action="store_true",
                   help="store the decode KV cache int8 with per-row "
                        "scales (ops/quant.py::quantize_kv) — the "
                        "long-context decode bandwidth lever; composes "
                        "with --int8-decode")
    p.add_argument("--beam", type=int, default=0, metavar="K",
                   help="beam-search decode with K beams instead of sampling")
    p.add_argument("--speculative-k", type=int, default=0, metavar="K",
                   help="speculative greedy decoding: train a shallow "
                        "draft on the same data, propose K tokens per "
                        "target verification chunk "
                        "(infer/speculative.py; needs --temperature 0, "
                        "no --beam)")
    p.add_argument("--draft-layers", type=int, default=1,
                   help="layer count of the speculative draft model "
                        "(same width/heads as the target)")
    p.add_argument("--json", action="store_true")
    return p


def _split_eval(eval_frac: float, tokens, batch_size: int):
    """Hold out the leading ``eval_frac`` of ``tokens`` (at least one
    batch) for post-training evaluation. Returns ``(eval_tokens | None,
    train_tokens)``; any nonzero out-of-range fraction is rejected (a
    negative value is a typo, not a request for no eval)."""
    if eval_frac == 0:
        return None, tokens
    if not 0.0 < eval_frac < 1.0:
        raise SystemExit(f"--eval-frac must be in (0, 1), got {eval_frac}")
    n_eval = max(int(len(tokens) * eval_frac), batch_size)
    if n_eval >= len(tokens):
        raise SystemExit(
            f"--eval-frac {eval_frac} leaves no training data "
            f"({n_eval} of {len(tokens)} sequences held out)"
        )
    return tokens[:n_eval], tokens[n_eval:]


def _print_eval(trainer, params, eval_tokens):
    """Shared post-fit holdout report; returns the metrics dict (None
    when no holdout was requested)."""
    if eval_tokens is None:
        return None
    metrics = trainer.evaluate(params, eval_tokens)
    print(
        f"eval loss:  {metrics['loss']:f}  "
        f"perplexity:  {metrics['perplexity']:f}"
    )
    return metrics


def _run_pipeline(args, tokens, vocab: int) -> int:
    """Pipeline-parallel training route (``--pipeline-parallel > 1``):
    the real ``TransformerLM`` block stack stages over a
    ``data x pipe x tensor`` mesh (``parallel/pipeline.py``), GPipe or
    hand-scheduled 1F1B backward. Since the round-3 promotion the engine
    composes with tensor parallelism, RoPE, GQA, flash, remat, MoE
    expert parallelism, the optimizer/schedule registry, bfloat16,
    checkpoint/resume, and held-out eval; round 5 adds --zero1
    (data-sharded AdamW moments chunked per (pipe, tensor) coordinate),
    --fsdp (params AND moments chunked — just-in-time all_gather in the
    step) and --grad-clip-norm (spec-aware exact global norm). The
    remaining rejections below are the features the pipeline schedules
    genuinely cannot express."""
    import math

    # Flags the pipeline engine cannot express are rejected — a silently
    # dropped option would train a different configuration than asked.
    for flag, val, default, why in (
        ("--generate", args.generate, 0,
         "decode runs on the shard_map engine (export params instead)"),
        ("--beam", args.beam, 0,
         "decode runs on the shard_map engine"),
        ("--accum-steps", args.accum_steps, 1,
         "microbatching IS the pipeline's accumulation"),
        ("--label-smoothing", args.label_smoothing, 0.0,
         "the pipeline tail computes plain CE"),
        ("--fused-xent", args.fused_xent, False,
         "the pipeline tail computes plain CE"),
        ("--tie-embeddings", args.tie_embeddings, False,
         "the tied embedding would live in two 1F1B param groups"),
        ("--grad-compress", args.grad_compress, "none",
         "stage grads cross the pipe axis per 1F1B group, not as one "
         "flat data-parallel bucket sync"),
        ("--sync-overlap", args.sync_overlap, "off",
         "the overlapped bucket schedule models the shard_map engines' "
         "pure data-parallel sync, not per-stage pipeline grads"),
        ("--metrics-dir", args.metrics_dir, None,
         "PipelineLMConfig has no telemetry fields; the obs/ sinks wire "
         "through the shard_map engines only"),
        ("--metrics-every", args.metrics_every, None,
         "PipelineLMConfig has no telemetry fields"),
    ):
        if val != default:
            raise SystemExit(
                f"{flag} does not compose with --pipeline-parallel ({why})"
            )
    if (
        args.num_virtual_stages is not None
        and args.pipeline_schedule != "interleaved"
    ):
        # Same reject-don't-drop rule as above: a virtual-stage request
        # on a non-interleaved schedule would silently train with the
        # full (S-1) bubble. The parser default is None so an EXPLICIT
        # "--num-virtual-stages 2" is still caught.
        raise SystemExit(
            "--num-virtual-stages only applies to --pipeline-schedule "
            f"interleaved (got schedule={args.pipeline_schedule!r})"
        )
    num_virtual = 2 if args.num_virtual_stages is None else args.num_virtual_stages
    if args.seq_parallel > 1:
        # Sequence parallelism inside the stages (round 4): ring/Ulysses
        # attention over a "seq" mesh axis; the impl must be one of the
        # sequence-parallel variants (PipelineLMTrainer validates too).
        attn = args.attention_impl
        if attn not in ("ring", "ring_flash", "ulysses", "ulysses_flash"):
            raise SystemExit(
                f"--attention-impl {attn} does not compose with "
                "--seq-parallel (use ring|ring_flash|ulysses|ulysses_flash)"
            )
    else:
        # "ring" is the parser's LM-engine default, meaningless on one
        # sequence shard — map it to the pipeline engine's dense path;
        # everything else must be chosen deliberately.
        attn = "dense" if args.attention_impl == "ring" else args.attention_impl
        if attn not in ("dense", "flash"):
            raise SystemExit(
                f"--attention-impl {args.attention_impl} does not compose "
                "with --pipeline-parallel without --seq-parallel (the "
                "pipeline engine supports dense|flash per full-sequence "
                "stage)"
            )
    from cs744_pytorch_distributed_tutorial_tpu.parallel.pipeline import (
        PipelineLMConfig,
        PipelineLMTrainer,
    )

    cfg = PipelineLMConfig(
        vocab_size=vocab,
        num_layers=args.num_layers,
        num_heads=args.num_heads,
        d_model=args.d_model,
        d_ff=args.d_ff,
        max_seq_len=args.max_seq_len,
        compute_dtype=args.compute_dtype,
        use_rope=args.use_rope,
        norm=args.norm,
        mlp=args.mlp,
        num_kv_heads=args.num_kv_heads,
        moe_experts=args.moe_experts,
        moe_top_k=args.moe_top_k,
        moe_groups=args.moe_groups,
        moe_dispatch=args.moe_dispatch,
        moe_gmm_impl=args.moe_gmm_impl,
        moe_expert_parallel=args.moe_expert_parallel,
        data_parallel=args.data_parallel,
        pipeline_parallel=args.pipeline_parallel,
        tensor_parallel=args.tensor_parallel,
        seq_parallel=args.seq_parallel,
        num_microbatches=args.num_microbatches,
        schedule=args.pipeline_schedule,
        num_virtual_stages=num_virtual,
        attention_impl=attn,
        remat=args.remat,
        remat_policy=args.remat_policy,
        global_batch_size=args.global_batch_size,
        seq_len=args.seq_len,
        learning_rate=args.lr,
        seed=args.seed,
        dropout_rate=args.dropout_rate,
        optimizer=args.optimizer,
        lr_schedule=args.lr_schedule,
        warmup_steps=args.warmup_steps,
        total_steps=args.steps,
        weight_decay=args.weight_decay,
        grad_clip_norm=args.grad_clip_norm,
        zero1=args.zero1,
        fsdp=args.fsdp,
        checkpoint_dir=args.checkpoint_dir,
        checkpoint_every=args.checkpoint_every,
        halt_on_nonfinite=args.halt_on_nonfinite,
    )
    trainer = PipelineLMTrainer(cfg)
    eval_tokens, tokens = _split_eval(
        args.eval_frac, tokens, cfg.global_batch_size
    )
    params, _, losses = trainer.fit(tokens, steps=args.steps)
    for i, loss in enumerate(losses):
        if i % args.log_every == 0 or i == len(losses) - 1:
            print(f"{i} loss:  {loss:f}")
    eval_metrics = _print_eval(trainer, params, eval_tokens)
    if args.json:
        print(
            json.dumps(
                {
                    "engine": "pipeline",
                    "schedule": cfg.schedule,
                    "pipeline_parallel": cfg.pipeline_parallel,
                    "data_parallel": cfg.data_parallel,
                    "tensor_parallel": cfg.tensor_parallel,
                    "seq_parallel": cfg.seq_parallel,
                    "num_microbatches": cfg.num_microbatches,
                    "final_loss": _json_loss(losses[-1]) if losses else None,
                    # null when the run executed zero steps (checkpoint
                    # already at --steps) — a gating script must not
                    # read a no-op resume as a healthy training signal.
                    "finite": (
                        bool(math.isfinite(losses[-1])) if losses else None
                    ),
                    "steps_run": len(losses),
                    "eval": eval_metrics,
                }
            )
        )
    return 0


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)
    from cs744_pytorch_distributed_tutorial_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    configure_compile_cache()

    if (
        args.int8_decode == "head"
        and args.tie_embeddings
        and not args.int8_kv_cache
    ):
        # Fail BEFORE training: tied embeddings have no lm_head, so the
        # default weight scope would silently quantize nothing
        # (LMTrainer.quantized_decode_model raises the same way). With
        # --int8-kv-cache the request is NOT a no-op — the cache is the
        # quantization lever and the weight scope degrades to a no-op
        # pass-through.
        raise SystemExit(
            "--int8-decode head is a no-op with --tie-embeddings (no "
            "lm_head exists; the attend path stays float) — use "
            "'--int8-decode all', or --int8-kv-cache which needs no "
            "weight scope"
        )

    import jax
    import numpy as np

    # Under the graftelastic supervisor (launch.py) the multi-process
    # coordinates arrive via the GRAFT_ELASTIC_* environment — attach
    # before any device use (rendezvous + heartbeats + identity labels).
    from cs744_pytorch_distributed_tutorial_tpu.parallel.multihost import (
        attach,
        env_context,
    )

    elastic_ctx = env_context()
    if elastic_ctx is not None:
        attach(elastic_ctx)

    from cs744_pytorch_distributed_tutorial_tpu.data import (
        BYTE_VOCAB,
        byte_corpus,
        synthetic_tokens,
    )
    from cs744_pytorch_distributed_tutorial_tpu.train import LMConfig, LMTrainer

    if args.text_file:
        vocab = BYTE_VOCAB
        tokens = byte_corpus(
            args.text_file, args.seq_len, max_seqs=args.num_seqs, seed=args.seed
        )
    else:
        vocab = args.vocab_size
        tokens = synthetic_tokens(
            args.num_seqs, args.seq_len, vocab, seed=args.seed
        )

    # Route BEFORE constructing the shard_map engine's config: pipeline
    # runs must not be subject to (or pay for) LMConfig's validation.
    if args.pipeline_parallel <= 1 and args.num_virtual_stages is not None:
        # Reject-don't-drop on BOTH routes: without a pipe axis the
        # virtual-stage request would be silently ignored here.
        raise SystemExit(
            "--num-virtual-stages requires --pipeline-parallel > 1 "
            "(virtual stages interleave over the pipe axis)"
        )
    if args.pipeline_parallel > 1:
        if args.scan_layers:
            # The pipeline engine already stacks its per-stage blocks
            # under a scan — the flag would be silently ignored.
            raise SystemExit(
                "--scan-layers is the shard_map engine's compile lever; "
                "the pipeline engine already runs stacked stages (drop "
                "--scan-layers or --pipeline-parallel)"
            )
        return _run_pipeline(args, tokens, vocab)

    cfg = LMConfig(
        vocab_size=vocab,
        num_layers=args.num_layers,
        num_heads=args.num_heads,
        num_kv_heads=args.num_kv_heads,
        d_model=args.d_model,
        d_ff=args.d_ff,
        max_seq_len=args.max_seq_len,
        attention_impl=args.attention_impl,
        compute_dtype=args.compute_dtype,
        remat=args.remat,
        remat_policy=args.remat_policy,
        scan_layers=args.scan_layers,
        tie_embeddings=args.tie_embeddings,
        use_rope=args.use_rope,
        norm=args.norm,
        mlp=args.mlp,
        fused_xent=args.fused_xent,
        moe_experts=args.moe_experts,
        moe_top_k=args.moe_top_k,
        moe_groups=args.moe_groups,
        moe_dispatch=args.moe_dispatch,
        moe_gmm_impl=args.moe_gmm_impl,
        moe_expert_parallel=args.moe_expert_parallel,
        data_parallel=args.data_parallel,
        seq_parallel=args.seq_parallel,
        tensor_parallel=args.tensor_parallel,
        global_batch_size=args.global_batch_size,
        seq_len=args.seq_len,
        learning_rate=args.lr,
        optimizer=args.optimizer,
        lr_schedule=args.lr_schedule,
        warmup_steps=args.warmup_steps,
        # Cosine schedules decay over the full requested run by default.
        total_steps=args.steps if args.lr_schedule != "constant" else None,
        weight_decay=args.weight_decay,
        grad_clip_norm=args.grad_clip_norm,
        grad_compress=args.grad_compress,
        sync_bucket_mb=args.sync_bucket_mb,
        sync_overlap=args.sync_overlap,
        label_smoothing=args.label_smoothing,
        dropout_rate=args.dropout_rate,
        accum_steps=args.accum_steps,
        zero1=args.zero1,
        fsdp=args.fsdp,
        seed=args.seed,
        checkpoint_dir=args.checkpoint_dir,
        checkpoint_every=args.checkpoint_every,
        snapshot_every=args.snapshot_every,
        snapshot_keep=args.snapshot_keep,
        halt_on_nonfinite=args.halt_on_nonfinite,
        metrics_dir=args.metrics_dir,
        metrics_every=1 if args.metrics_every is None else args.metrics_every,
    )
    eval_tokens, tokens = _split_eval(
        args.eval_frac, tokens, cfg.global_batch_size
    )

    trainer = LMTrainer(cfg)
    if args.max_restarts > 0:
        from cs744_pytorch_distributed_tutorial_tpu.utils.failure import (
            run_with_recovery,
        )

        params, _, losses, restarts = run_with_recovery(
            trainer,
            max_restarts=args.max_restarts,
            backoff_s=args.restart_backoff_s,
            backoff_jitter=args.restart_jitter,
            jitter_seed=args.seed,
            fit_args=(tokens,),
            fit_kwargs={"steps": args.steps},
        )
        if restarts:
            print(f"recovered after {restarts} restart(s)")
    else:
        params, _, losses = trainer.fit(tokens, steps=args.steps)
    for i, loss in enumerate(losses):
        if i % args.log_every == 0 or i == len(losses) - 1:
            print(f"{i} loss:  {loss:f}")

    eval_metrics = _print_eval(trainer, params, eval_tokens)

    sample_text = None
    sample_ids = None
    if args.beam > 0 and (
        args.top_k is not None or args.top_p is not None or args.temperature != 1.0
    ):
        raise SystemExit(
            "--beam is deterministic highest-likelihood decoding; it cannot "
            "combine with --temperature/--top-k/--top-p (drop --beam to sample)"
        )
    if args.generate > 0:
        from cs744_pytorch_distributed_tutorial_tpu.infer import make_generator

        if args.prompt is not None and args.text_file:
            prompt_ids = np.frombuffer(
                args.prompt.encode("utf-8"), dtype=np.uint8
            ).astype(np.int32)[None, :]
        elif args.prompt is not None:
            prompt_ids = np.asarray(
                [[int(t) for t in args.prompt.split()]], dtype=np.int32
            )
        else:
            prompt_ids = tokens[:1, : args.prompt_len]
        # FSDP params persist as [dp, chunk] shards — unshard for the
        # decode tree; other layouts fetch the global arrays directly.
        host_params = (
            trainer.gather_for_decode(params)
            if args.fsdp
            else jax.device_get(params)
        )
        prompt_arr = np.asarray(prompt_ids, dtype=np.int32)
        if args.int8_decode is not None:
            decode_model = trainer.quantized_decode_model(
                args.int8_decode, kv_cache=args.int8_kv_cache
            )
            host_params = trainer.quantize_for_decode(
                host_params, args.int8_decode
            )
        elif args.int8_kv_cache:
            decode_model = trainer.decode_model().clone(quant_kv_cache=True)
        else:
            decode_model = trainer.decode_model()
        if args.speculative_k > 0:
            # temperature 0 = greedy verify; temperature > 0 =
            # rejection-sampling mode (distribution-exact). top-k/top-p
            # truncation would break the exactness identity; beam is a
            # different decoder entirely.
            if args.beam > 0:
                raise SystemExit(
                    "--speculative-k does not combine with --beam"
                )
            if args.top_k is not None or args.top_p is not None:
                raise SystemExit(
                    "--speculative-k supports temperature-only sampling "
                    "(top-k/top-p truncation re-normalizes the target "
                    "distribution, breaking the rejection-sampling "
                    "exactness identity)"
                )
            if args.int8_decode is not None or args.int8_kv_cache:
                raise SystemExit(
                    "--speculative-k does not combine with the int8 decode "
                    "paths (verify in float; quantize separately)"
                )
            import dataclasses

            from cs744_pytorch_distributed_tutorial_tpu.infer import (
                make_speculative_generator,
            )

            # Shallow draft: same width/heads/vocab, fewer layers,
            # trained on the same data stream.
            draft_cfg = dataclasses.replace(
                trainer.cfg, num_layers=args.draft_layers
            )
            draft_tr = LMTrainer(draft_cfg)
            draft_params, _, _ = draft_tr.fit(tokens, args.steps)
            # The draft inherits fsdp via the cfg replace — its chunked
            # params unshard the same way the target's did.
            draft_host = (
                draft_tr.gather_for_decode(draft_params)
                if args.fsdp
                else jax.device_get(draft_params)
            )
            spec = make_speculative_generator(
                decode_model,
                draft_tr.decode_model(),
                max_new_tokens=args.generate,
                k=args.speculative_k,
                temperature=args.temperature,
                return_stats=True,
            )
            spec_args = (host_params, draft_host, prompt_arr[:1])
            if args.temperature > 0.0:
                # Rejection-sampling mode draws from the target
                # distribution — it needs the run's rng key.
                out, target_calls = spec(*spec_args, jax.random.key(args.seed))
            else:
                out, target_calls = spec(*spec_args)
            from cs744_pytorch_distributed_tutorial_tpu.obs.metrics import (
                speculative_accept_rate,
            )

            target_calls = int(target_calls)
            accept_rate = speculative_accept_rate(
                args.generate, target_calls, args.speculative_k
            )
            print(
                f"speculative: {target_calls} target calls for "
                f"{args.generate} tokens (k={args.speculative_k}, "
                f"accept rate {accept_rate:.3f})"
            )
            if args.metrics_dir is not None:
                # Append to the training run's stream — one timeline per
                # run, decode stats alongside the step records.
                from cs744_pytorch_distributed_tutorial_tpu.obs.metrics import (
                    Telemetry,
                )

                _t = Telemetry(args.metrics_dir, run="lm")
                _t.emit_event(
                    "speculative_decode",
                    new_tokens=args.generate,
                    target_calls=target_calls,
                    k=args.speculative_k,
                    accept_rate=accept_rate,
                    draft_layers=args.draft_layers,
                    temperature=args.temperature,
                )
                _t.close()
        elif args.beam > 0:
            from cs744_pytorch_distributed_tutorial_tpu.infer import (
                make_beam_searcher,
            )

            search = make_beam_searcher(
                decode_model,
                beam_size=args.beam,
                max_new_tokens=args.generate,
            )
            out, _ = search(host_params, prompt_arr)
        else:
            generate = make_generator(
                decode_model,
                max_new_tokens=args.generate,
                temperature=args.temperature,
                top_k=args.top_k,
                top_p=args.top_p,
            )
            out = generate(host_params, prompt_arr, jax.random.key(args.seed))
        sample_ids = np.asarray(out)[0].tolist()
        if args.text_file:
            sample_text = bytes(sample_ids).decode("utf-8", errors="replace")
            print(f"sample: {sample_text!r}")
        else:
            print(f"sample ids: {sample_ids}")

    if args.json:
        print(
            json.dumps(
                {
                    "vocab_size": vocab,
                    "mesh": {
                        "data": args.data_parallel,
                        "seq": args.seq_parallel,
                        "tensor": args.tensor_parallel,
                    },
                    "steps": args.steps,
                    # Non-finite floats would make the document invalid
                    # JSON (json.dumps emits bare NaN) — null them and
                    # let "finite" carry the divergence signal.
                    "first_loss": _json_loss(losses[0]) if losses else None,
                    "final_loss": _json_loss(losses[-1]) if losses else None,
                    "finite": (
                        bool(_math.isfinite(losses[-1])) if losses else None
                    ),
                    "steps_run": len(losses),
                    "eval": eval_metrics,
                    "sample": sample_text or sample_ids,
                }
            )
        )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
