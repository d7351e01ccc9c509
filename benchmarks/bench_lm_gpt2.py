"""Realistic LM benchmark: GPT-2-small-ish training with MFU.

VERDICT r2 weak #5: the 4L/512d bench_lm.py config is embedding-
dominated and can't show whether kernel wins survive depth, and
fused_xent had never been benched on-chip in training. This bench runs
a GPT-2-small-shaped model (12 layers, d_model 768, 12 heads, d_ff
3072, seq 1024, vocab 50304) in bf16 with remat on the measured path,
and ablates flash attention and the fused softmax-CE kernel each
on/off. Reports tokens/sec AND MFU (FLOPs = 2*MACs, train = 3x
forward; remat recompute NOT counted, per the standard convention — the
hardware does ~1 extra forward of block FLOPs on top).

Run on the TPU: python benchmarks/bench_lm_gpt2.py
Prints one JSON line per configuration; headline = flash + fused_xent.

Measured 2026-07-31 (one TPU v5e chip, batch 8; re-run later same day
in parens):
  dense           135.7 ms/step   60.4k tok/s  MFU 0.262  (61.0k/0.265)
  flash            84.4 ms/step   97.1k tok/s  MFU 0.421  (98.8k/0.429)
  dense+fxent     145.6 ms/step   56.3k tok/s  MFU 0.244  (56.0k/0.243)
  flash+fxent      96.2 ms/step   85.2k tok/s  MFU 0.370  (83.5k/0.362)
The flash win SURVIVES depth (1.61x at 12L vs 1.62x at 4L);
fused_xent LOSES 12-14% wall-clock in training at this vocab (also at
batch 16) — its value is the absent [N, V] log-softmax buffer when
memory binds, and its off-by-default is now measured, not assumed.

Remat ablation (measured): at batch 8 the activations FIT without
remat, and turning it off buys the dots-policy recompute back:
  flash + remat=dots  84.5 ms/step   96.9k tok/s  MFU 0.421
  flash + remat=off   78.3 ms/step  104.6k tok/s  MFU 0.454  (+8%)
The headline when memory allows is remat=off; remat remains the
long-context/major-batch memory lever it was built as.

Batch scaling, round-4 re-measurement (the round-3 "b16 no better"
was a dots-only artifact):
  flash + remat=OFF + b16  144.9 ms/step  113.0k tok/s  MFU 0.490  <- headline
                           (first probe same day: 110.9k / 0.481)
  flash + remat=off + b20  192.9 ms/step  106.2k tok/s  MFU 0.461  (late r5)
  flash + remat=off + b24  239.7 ms/step  102.5k tok/s  MFU 0.445
                           (late-r5 re-measure: 101.4k / 0.440)
  flash + remat=dots + b16  (round 3)      94.5k tok/s  MFU 0.41
The late-round-5 b20 point pins the shape: throughput turns over
MONOTONICALLY past b16 (113.0 -> 106.2 -> 101.4k), the same
pre-compile-wall degradation medium-T2048 shows past b4 — the b16
headline is a measured local optimum, not a wall-truncated curve.
Remat does NOT rescue it (b24-dots 94.4k / 0.410 < b24-off), so the
turnover is not activation capacity; it tracks the matmul/layout
regime at those batch shapes.
Batch 32 did not compile on the development setup of the time in ANY
variant tried round 4 — unrolled/scan_layers x dots/off x fused_xent
on/off. scan_layers shrinks the traced program by 12x and fused_xent
removes the 6.6 GB f32 logit buffer, so the wall was that setup's, not
program size or planned memory. Not re-probed on this installation.

scan_layers on the chip (measured, negative for THIS regime): at b8
remat-off the scanned stack is 81.7k tok/s (MFU 0.354) vs 104.6k
unrolled — the layer loop costs ~22% (lost cross-layer fusion +
while-loop overhead at d768); at b16 remat=dots it is 90.3k vs 94.5k
unrolled. scan_layers' value is COMPILE scalability (24L+ configs,
probe_gpt2_medium.py) and O(L)-smaller programs, not single-chip
throughput at 12L; the bench keeps the unrolled path.

Scoped-vmem compiler option (measured, negative for the LM):
xla_tpu_scoped_vmem_limit_kib=65536 — the CIFAR bench's +7% lever —
gives 107.1k on the b16 remat-off config vs 110.9k default-compiled.
The LM step's Pallas flash kernels manage their own VMEM; the larger
scoped budget only perturbs XLA's fusion choices here.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

from cs744_pytorch_distributed_tutorial_tpu.data import synthetic_tokens
from cs744_pytorch_distributed_tutorial_tpu.parallel import make_mesh
from cs744_pytorch_distributed_tutorial_tpu.train import LMConfig, LMTrainer

BATCH = 8
SEQ = 1024
LAYERS = 12
D_MODEL = 768
HEADS = 12
D_FF = 3072
VOCAB = 50304  # GPT-2's 50257 padded to a 128-lane multiple
STEPS = 12
WARMUP = 8
V5E_PEAK_FLOPS = 197e12


def gpt2ish_train_flops_per_token() -> float:
    """Analytic model FLOPs per token for one training step.

    Per-layer forward matmuls: q/k/v/o projections (4 * d^2 MACs) + MLP
    (2 * d * d_ff) + attention score/value contractions (2 * T * d MACs
    per token, causal masking NOT discounted — flash skips masked
    blocks, so its measured MFU is conservatively understated). Plus the
    embedding-tied-scale LM head (d * V). FLOPs = 2*MACs, train = 3x
    forward (dgrad + wgrad)."""
    per_layer = 4 * D_MODEL**2 + 2 * D_MODEL * D_FF + 2 * SEQ * D_MODEL
    fwd = LAYERS * 2.0 * per_layer + 2.0 * D_MODEL * VOCAB
    return 3.0 * fwd


def bench_config(attention_impl: str, fused_xent: bool, batch: int = BATCH,
                 remat: bool = True, scan_layers: bool = False) -> dict:
    cfg = LMConfig(
        vocab_size=VOCAB,
        num_layers=LAYERS,
        num_heads=HEADS,
        d_model=D_MODEL,
        d_ff=D_FF,
        max_seq_len=SEQ,
        seq_len=SEQ,
        global_batch_size=batch,
        attention_impl=attention_impl,
        compute_dtype="bfloat16",
        remat=remat,
        remat_policy="dots" if remat else "none",
        scan_layers=scan_layers,
        use_rope=True,
        fused_xent=fused_xent,
    )
    mesh = make_mesh({"data": 1, "seq": 1})
    tr = LMTrainer(cfg, mesh=mesh)
    params, opt = tr.init()
    tokens = synthetic_tokens(batch, SEQ, VOCAB, seed=0)
    x, y = tr.shard_batch(tokens)

    params, opt, m = tr.train_step(params, opt, x, y)  # compile
    float(m["loss"])
    for _ in range(WARMUP):
        params, opt, m = tr.train_step(params, opt, x, y)
    float(m["loss"])  # fence: value fetch, not block_until_ready
    t0 = time.perf_counter()
    for _ in range(STEPS):
        params, opt, m = tr.train_step(params, opt, x, y)
    float(m["loss"])
    dt = (time.perf_counter() - t0) / STEPS
    tok_s = batch * SEQ / dt
    flops = gpt2ish_train_flops_per_token()
    return {
        "metric": "gpt2small_train_tokens_per_sec_per_chip",
        "attention_impl": attention_impl,
        "fused_xent": fused_xent,
        "ms_per_step": round(dt * 1e3, 2),
        "tokens_per_sec": round(tok_s, 0),
        "flops_per_token": flops,
        "mfu": (
            round(tok_s * flops / V5E_PEAK_FLOPS, 4)
            if jax.default_backend() != "cpu"
            else None
        ),
        "config": f"{LAYERS}L/{D_MODEL}d/{HEADS}h/T{SEQ}/V{VOCAB}"
                  f"/b{batch}/bf16/remat={'dots' if remat else 'off'}/rope"
                  + ("/scan" if scan_layers else ""),
    }


def main() -> None:
    for impl, fused in (
        ("dense", False),
        ("flash", False),
        ("dense", True),
        ("flash", True),
    ):
        print(json.dumps(bench_config(impl, fused)), flush=True)
    # Batch scaling: batch 8 under-fills the MXU on d768 matmuls; larger
    # batches raise MFU until memory binds. At batch 32 the f32 logit
    # buffer alone is ~6.6 GB — the regime fused_xent's absent [N, V]
    # log-softmax buffer targets, so it is ablated again here where its
    # memory saving (not wall-clock) is the question.
    # Remat ablation: at batch 8 the activations FIT without remat —
    # measures what the dots-policy recompute costs when memory allows
    # turning it off.
    print(json.dumps(bench_config("flash", False, BATCH, remat=False)),
          flush=True)
    # Round-4 headline: batch 16 with remat OFF (round 3 only measured
    # b16 under remat=dots and concluded "no better" — wrongly).
    for batch, fused, remat in (
        (16, False, False), (32, False, True), (32, True, True),
    ):
        try:
            print(json.dumps(bench_config("flash", fused, batch, remat=remat)),
                  flush=True)
        except Exception as e:
            print(json.dumps({
                "attention_impl": "flash", "fused_xent": fused,
                "batch": batch, "error": f"{type(e).__name__}: {str(e)[:120]}",
            }), flush=True)


if __name__ == "__main__":
    main()
