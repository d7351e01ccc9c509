"""Ablation timings for the scored ResNet-18 step on the real chip.

Times variants of the training step to locate the bottleneck:
  full        — the scored configuration (augment + fwd/bwd + SGD)
  no_augment  — normalize only (is the one-hot crop/flip material?)
  fwd_only    — loss forward pass, no grad/update
  fwd_bwd     — value_and_grad, no optimizer update
Run on the TPU: python benchmarks/ablate.py

Measured 2026-07-30, one TPU v5e chip, batch 4096 bf16:
  aug_only        6.75 ms   (5% of the step — the one-hot MXU rewrite paid off)
  fwd_only       41.79 ms   (~28% of bf16 MXU peak: stage-1's 64-channel
                             convs half-fill the 128-wide MXU lanes, and BN
                             stats passes re-read ~0.5 GB stage-1 activations)
  fwd_bwd       123.26 ms   (backward ~2x forward, the standard ratio)
  full          127.22 ms   (optimizer ~4 ms; 32.2k sps at this batch)
  full_no_aug   125.92 ms   (augmentation nearly free after overlap)

Round-2 device-trace breakdown (the per-op numbers below are device
time from a jax.profiler trace, fwd+bwd = 117.9 ms at batch 4096 bf16 —
time kernels either in-graph or from the trace, not with a host timer
around one dispatch):
  - backward convs ~78 ms, the top block being stage-1 (4 convs x ~8 ms:
    wgrad ~5.6 via XLA's EmitAllBatchInSublanes at ~55 TF/s + dgrad ~2.4);
  - XLA lays stage-1 activations out BATCH-minor ({0,3,2,1}) so its
    forward convs get full 128-lane tiles from the batch dim — the naive
    "64 channels half-fill lanes" read was wrong for fwd, right for wgrad;
  - BatchNorm's full in-step cost is ~19.7 ms (117.9 vs 98.2 norm-free):
    HBM stat passes + backward reduces, only removable by fusing stats
    into conv epilogues (i.e. owning the convs);
  - the Pallas wgrad kernel (ops/fused_conv.py) hits 3.15 ms on stage-1
    shapes and 1.88 ms on stage-2 in isolation — at/above XLA's isolated
    emitter — but IN-graph the layout mismatch (custom calls pin dense
    row-major operands vs XLA's batch-minor choice) inserts 2x ~3.1 ms
    relayout copies per conv and the end-to-end step got SLOWER
    (117.9 -> 159.5). Hence cfg.fast_conv defaults off.
  - xla_tpu_scoped_vmem_limit_kib=65536 (v5e has 128 MiB physical VMEM
    vs the 16 MiB scoped default) lets XLA fuse deeper: step 125.6 ->
    117.3 ms; bench.py compiles with it. Fused SGD and the in-graph
    multi-step scan are each within noise of the default at this batch
    (the scan is not faster than per-step dispatch, whose overhead
    hides under the 117 ms step).
Round-2 follow-up experiments (both measured, both closed):
  - a LOGICAL transpose [B,H,W,C] -> [H,W,C,B] feeding a pallas call IS
    free when the producer's layout is batch-minor (verified: 0
    transpose ops, 40 bitcasts in the compiled module) — so a
    batch-minor kernel avoids the relayout copies entirely;
  - but the batch-minor wgrad formulation itself is slow: contraction
    over the batch LANES forces per-x-position dots ([576, BB] x
    [K, BB]^T with 9 sublane-concat builds per position) and measured
    13.4 ms on the stage-1 shape (23 TF/s) vs XLA's in-step 5.6 ms.
    The two constraints — dense-layout kernels pay relayout copies,
    batch-minor kernels pay lane-contraction inefficiency — bracket
    XLA's emitter as genuinely near the achievable envelope for these
    shapes on this chip generation.
Remaining unexplored lever: own the ENTIRE stem+stage1 subgraph
(fwd conv+BN-stats+ReLU and the fused backward) in a C-minor layout so
the only boundary relayouts are the stem input (tiny) and the stage-2
entry — the owned region is ~63 ms of XLA time with a ~45 ms kernel-side
ceiling estimate; high effort, and the margin would still not reach the
round-1 verdict's 45k sps target (the norm-free step alone measures
98.2 ms = 41.7k sps at batch 4096).

ROUND-3 MEASUREMENTS (2026-07-31, closing the owned-subgraph question):
  Sharper region map first (benchmarks/breakdown_r3.py, device trace of
  the exact bench step, batch 4096 bf16, vmem 64 MiB — step now 112.2 ms
  device / 35.8k sps):
    stem+stage1   54.2 ms   (region MFU ~35%: fwd conv+stat fusions
                             3.5-4.8 ms x5, wgrad+SGD fusions 3.2 x4,
                             dgrad+reduce 2.06 x4, BN-apply 2.3 x2, rest)
    stage2        23.3 ms   stage3 18.9 ms   stage4 15.2 ms
  The non-stage1 remainder (58 ms) runs at ~86% MFU — there is nothing
  left to win outside the region, and XLA's in-step stage-1 ops are
  already conv+stats/conv+SGD FUSED with no relayout copies (the copies
  only appear when a foreign-layout custom call is inserted).
  The owned-region kernel bet then requires Pallas kernels that BEAT
  those fused ops. Measured attempt (benchmarks/probe_fwd_hpair.py):
  the one formulation that breaks the 64-channel half-lane ceiling packs
  two output rows into 128 lanes via a FREE paired reshape
  [B,32,32,64]->[B,16,64,64] (K=768 full, N=128 full, 75% useful MACs,
  2.1 ms matmul floor):
    hpair fwd kernel, best block:   13.39 ms   (numerics exact vs ref)
    XLA conv isolated (same I/O):    8.48 ms   (pays boundary relayouts)
    XLA conv+stats IN-step:         ~3.5  ms   (batch-minor, fused)
  The kernel is im2col-BUILD-bound: 12 tap shifts + 6-tile lane concat
  per h-pair move ~3 MB of VPU traffic against a 1 us matmul — the same
  tax that killed the batch-minor wgrad in round 2 (13.4 ms / 23 TF/s).
  Build-free formulations were derived and all cap at <= 50% useful
  MACs (w-pair/quad K-packing: the j x dh sparsity patterns multiply),
  i.e. no better than the naive half-lane form XLA already beats.
  VERDICT-r2 #1 resolution: the ceiling is LOWER than the roadmap
  estimate — at today's 112.2 ms step, even the estimate's own 45 ms
  region ceiling gives 103 ms = 39.8k sps < 40k, and the measured
  kernel floor (~4x off XLA in-step) puts the real owned-region result
  far above that ceiling. The scored bench therefore stays on XLA's
  emitters; stage-1's ~35% region MFU is the price of 64-channel convs
  on a 128-lane MXU, not of a missing kernel. Overall step MFU 0.605
  (FLOPs = 2*MACs, bench.py accounting).
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import optax

from cs744_pytorch_distributed_tutorial_tpu.config import TrainConfig
from cs744_pytorch_distributed_tutorial_tpu.data import (
    augment_train_batch,
    eval_batch,
    synthetic_cifar10,
)
from cs744_pytorch_distributed_tutorial_tpu.models import get_model
from cs744_pytorch_distributed_tutorial_tpu.train.state import make_optimizer

BATCH = 4096
STEPS = 20


def build_full_step(batch: int = BATCH):
    """The scored train step WITHOUT buffer donation, for measurement
    loops that call it repeatedly on one state (donated inputs would be
    invalidated after the first call). Single source for ablate.py and
    breakdown_r3.py — keep in sync with ``Trainer.train_step``.

    Returns ``(full, args)`` where ``full(p, stats, opt, key, x, y)``
    performs augment + fwd/bwd + optimizer update.
    """
    cfg = TrainConfig(model="resnet18", compute_dtype="bfloat16")
    model = get_model(cfg.model, num_classes=10, dtype=jnp.bfloat16)
    tx = make_optimizer(cfg)
    ds = synthetic_cifar10(batch, 16, seed=0)
    x = jnp.asarray(ds.train_images)
    y = jnp.asarray(ds.train_labels)
    key = jax.random.key(0)
    variables = model.init(
        jax.random.key(cfg.seed), jnp.zeros((1, 32, 32, 3)), train=False
    )
    params, stats = variables["params"], variables["batch_stats"]
    opt_state = tx.init(params)

    def loss_fn(p, st, xb, yb):
        logits, mut = model.apply(
            {"params": p, "batch_stats": st}, xb, train=True,
            mutable=["batch_stats"],
        )
        return (
            optax.softmax_cross_entropy_with_integer_labels(logits, yb).mean(),
            mut,
        )

    def full(p, st, o, k, xb, yb):
        (_, mut), g = jax.value_and_grad(loss_fn, has_aux=True)(
            p, st, augment_train_batch(k, xb), yb
        )
        upd, o2 = tx.update(g, o, p)
        return optax.apply_updates(p, upd), mut["batch_stats"], o2

    return full, (params, stats, opt_state, key, x, y)


def bench(fn, *args):
    out = fn(*args)  # compile
    jax.block_until_ready(out)
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(STEPS):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / STEPS


def main():
    cfg = TrainConfig(model="resnet18", compute_dtype="bfloat16")
    model = get_model(cfg.model, num_classes=10, dtype=jnp.bfloat16)
    tx = make_optimizer(cfg)
    ds = synthetic_cifar10(BATCH, 16, seed=0)
    x = jnp.asarray(ds.train_images)
    y = jnp.asarray(ds.train_labels)
    key = jax.random.key(0)
    variables = model.init(jax.random.key(cfg.seed), jnp.zeros((1, 32, 32, 3)), train=False)
    params, stats = variables["params"], variables["batch_stats"]
    opt_state = tx.init(params)

    def loss_fn(p, st, xb, yb):
        logits, mut = model.apply(
            {"params": p, "batch_stats": st}, xb, train=True,
            mutable=["batch_stats"],
        )
        return optax.softmax_cross_entropy_with_integer_labels(logits, yb).mean(), mut

    @jax.jit
    def aug_only(k, xb):
        return augment_train_batch(k, xb)

    @jax.jit
    def fwd_only(p, st, k, xb, yb):
        return loss_fn(p, st, aug_only(k, xb), yb)[0]

    @jax.jit
    def fwd_bwd(p, st, k, xb, yb):
        (l, _), g = jax.value_and_grad(loss_fn, has_aux=True)(p, st, aug_only(k, xb), yb)
        return g

    @jax.jit
    def full(p, st, o, k, xb, yb):
        (l, mut), g = jax.value_and_grad(loss_fn, has_aux=True)(p, st, aug_only(k, xb), yb)
        upd, o2 = tx.update(g, o, p)
        return optax.apply_updates(p, upd), mut["batch_stats"], o2

    @jax.jit
    def full_no_aug(p, st, o, xb, yb):
        (l, mut), g = jax.value_and_grad(loss_fn, has_aux=True)(p, st, eval_batch(xb), yb)
        upd, o2 = tx.update(g, o, p)
        return optax.apply_updates(p, upd), mut["batch_stats"], o2

    for name, t in [
        ("aug_only", bench(aug_only, key, x)),
        ("fwd_only", bench(fwd_only, params, stats, key, x, y)),
        ("fwd_bwd", bench(fwd_bwd, params, stats, key, x, y)),
        ("full", bench(full, params, stats, opt_state, key, x, y)),
        ("full_no_aug", bench(full_no_aug, params, stats, opt_state, x, y)),
    ]:
        print(f"{name:14s} {t * 1e3:8.2f} ms  {BATCH / t:10.0f} sps")


if __name__ == "__main__":
    main()
