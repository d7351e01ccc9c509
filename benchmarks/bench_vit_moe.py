"""On-chip numbers for the two families that had none (VERDICT r3 #6).

ViT: train ViT-Ti/4 and ViT-S/4 on CIFAR shapes under the same
data-parallel Trainer as VGG/ResNet (bf16, flash attention) — ms/step,
samples/sec, analytic MFU, plus a short loss-descent window on a
learnable synthetic set so the number is a TRAINING number, not a
forward benchmark.

MoE: LMTrainer step with a routed Switch FFN (E=8, top-2, d_ff=F)
against the FLOPs-MATCHED dense model (d_ff=2F — top-2 routing
computes two F-wide expert FFNs per token, so per-token matmul FLOPs
are equal up to the router). Reports tokens/sec for both, the MoE
utilization tax (dispatch/combine einsums + router), and the measured
drop rate / aux loss from the new fit-history metrics.

MFU accounting: FLOPs = 2*MACs, train = 3x forward, remat off; ViT
attention FLOPs counted at full (non-causal) N^2.

Measured 2026-07-31, one TPU v5e chip:
  vit_tiny  b1024: 57.6 ms/step  17.8k samples/sec  MFU 0.099
  vit_small b512:  77.8 ms/step   6.6k samples/sec  MFU 0.190
  vit_tiny descent (3 epochs, learnable synthetic): loss 2.52 -> 0.60,
  test accuracy 80.7% — a training capability, not a forward demo.
  (Low MFU is the small-model regime: d192/d384 matmuls over 65 tokens
  underfill the 128-lane MXU; the table exists to make that measured.)

  moe e8/top2 G=1:   230.1 ms  71.2k tok/s   drop 0.1%  (the negative
                     that motivated grouping: 4.2x slower than dense)
  moe e8/top2 G=16:   77.8 ms  210.5k tok/s  drop 12.7% at init
  dense d_ff 2048:    55.2 ms  297.1k tok/s  (FLOPs-matched oracle)
  GShard grouping cuts the O(N*E*C*D) dispatch by G: 2.96x step
  speedup, leaving a 1.41x routed-vs-dense tax (router + dispatch/
  combine einsums + the all-to-all-free single-chip layout). Init-time
  drop rises at per-group capacity (random router, cf 1.25); training
  balances it: the 60-step fit trajectory measured drop 8.7% -> 0.7%
  (G=1) with aux 4.62 -> 4.09.

Round 5 — ViT MXU geometry lever (vit_wide_p8: patch 8, d384, 3 heads
-> head_dim 128 = one MXU tile; FLOPs-matched to vit_tiny within 1%):
  vit_tiny    b1024: 59.8 ms  17.1k sps  MFU 0.095  (same-session)
  vit_wide_p8 b1024: 39.2 ms  26.2k sps  MFU 0.145  (1.53x at equal FLOPs)
  vit_wide_p8 b2048: 76.0 ms  26.9k sps  MFU 0.149  (saturated)
  descent (3 epochs, learnable synthetic): loss 2.76 -> 1.51,
  accuracy 35.9% vs vit_tiny's 80.7% — the honest trade: 8x8 patches
  on 32px inputs buy tile-aligned matmuls at the cost of spatial
  resolution; the lever demonstrates WHERE the tiny-ViT MFU went
  (geometry), it is not a free accuracy upgrade.

Round 5 — scatter dispatch (same chip, same session re-measurement):
  einsum  G=1:   232.5 ms   70.5k tok/s  drop 0.1%
  einsum  G=16:   81.6 ms  200.7k tok/s  drop 12.7% (init)
  scatter G=16:   87.1 ms  188.2k tok/s  drop 13.4% (init)
  scatter G=1:    79.6 ms  206.0k tok/s  drop 0.2%   <- new default
  scatter G=1 cf=1.0: 76.9 ms  213.2k tok/s  drop 3.2% (init)
  dense oracle:   55.3 ms  296.4k tok/s
Scatter is group-size-invariant, so G=1 (einsum's pathology) is its
best point: 2.9x over einsum at iso-drop, no grouping/drop trade.
The 1.44x residual vs dense is bandwidth, not FLOPs: cf 1.25 -> 1.0
deletes the whole 1.25x slot-padding FLOPs term but buys only 3.5%,
and the device profile shows the time spread across per-layer
movement/router fusions with no hot op.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

V5E_PEAK_FLOPS = 197e12
STEPS, WARMUP = 12, 8


def vit_flops_per_sample(d, layers, d_ff, n_tokens) -> float:
    """Per-sample forward MACs*2*3: qkv/o projections + MLP + full
    (non-causal) attention contractions, patch embed + head ignored
    (<2%)."""
    per_layer = n_tokens * (4 * d * d + 2 * d * d_ff) + 2 * n_tokens**2 * d
    return 3.0 * 2.0 * layers * per_layer


def bench_vit(model: str, batch: int) -> dict:
    from cs744_pytorch_distributed_tutorial_tpu.config import TrainConfig
    from cs744_pytorch_distributed_tutorial_tpu.data import synthetic_cifar10
    from cs744_pytorch_distributed_tutorial_tpu.parallel import make_mesh
    from cs744_pytorch_distributed_tutorial_tpu.parallel.mesh import (
        shard_global_batch,
    )
    from cs744_pytorch_distributed_tutorial_tpu.train import Trainer

    cfg = TrainConfig(
        model=model,
        # ring (explicit collectives): flash can't trace under the
        # 'auto' strategy's check_vma (see engine guard).
        sync="ring",
        num_devices=1,
        global_batch_size=batch,
        compute_dtype="bfloat16",
        synthetic_data=True,
        vit_attention="flash",
    )
    mesh = make_mesh({"data": 1})
    tr = Trainer(cfg, mesh=mesh)
    state = tr.init()
    ds = synthetic_cifar10(batch, 16, seed=0)
    x, y = shard_global_batch(mesh, ds.train_images, ds.train_labels)
    key = jax.random.key(0)
    state, m = tr.train_step(state, x, y, key)
    float(m["loss"])
    for _ in range(WARMUP):
        state, m = tr.train_step(state, x, y, key)
    float(m["loss"])
    t0 = time.perf_counter()
    for _ in range(STEPS):
        state, m = tr.train_step(state, x, y, key)
    float(m["loss"])
    dt = (time.perf_counter() - t0) / STEPS
    dims, patch = {
        "vit_tiny": ((192, 6, 768), 4),
        "vit_small": ((384, 8, 1536), 4),
        # Round-5 geometry lever: FLOPs-matched to vit_tiny (4x fewer
        # tokens x 4x the d^2 terms), head_dim 128 = one MXU tile.
        "vit_wide_p8": ((384, 6, 1536), 8),
    }[model]
    n_tokens = (32 // patch) ** 2 + 1
    flops = vit_flops_per_sample(dims[0], dims[1], dims[2], n_tokens)
    sps = batch / dt
    return {
        "metric": f"cifar10_{model}_train_samples_per_sec_per_chip",
        "ms_per_step": round(dt * 1e3, 2),
        "samples_per_sec": round(sps),
        "mfu": (
            round(sps * flops / V5E_PEAK_FLOPS, 4)
            if jax.default_backend() != "cpu" else None
        ),
        "config": f"{model}/32px/b{batch}/bf16/flash",
    }


def vit_descends(model: str = "vit_tiny") -> dict:
    """Short training window on the learnable synthetic set: the ViT
    number is a training capability, not a kernel demo."""
    from cs744_pytorch_distributed_tutorial_tpu.config import TrainConfig
    from cs744_pytorch_distributed_tutorial_tpu.train import Trainer

    cfg = TrainConfig(
        model=model,
        sync="ring",
        num_devices=1,
        global_batch_size=512,
        compute_dtype="bfloat16",
        synthetic_data=True,
        synthetic_train_size=4096,
        synthetic_test_size=1024,
        epochs=3,
        learning_rate=1e-3,
        optimizer="adamw",
        vit_attention="flash",
    )
    tr = Trainer(cfg)
    state, history = tr.fit()
    return {
        "metric": f"{model}_synthetic_descent",
        "first_loss": round(history["train_loss"][0][2], 4),
        "final_loss": round(history["train_loss"][-1][2], 4),
        "final_eval": history["eval"][-1],
    }


def _timed_lm_steps(tr, params, opt, x, y):
    """Shared LM timing protocol: compile step, WARMUP steps, then
    STEPS timed (each phase fenced by a loss fetch). Returns
    (seconds/step, last metrics)."""
    params, opt, m = tr.train_step(params, opt, x, y)
    float(m["loss"])
    for _ in range(WARMUP):
        params, opt, m = tr.train_step(params, opt, x, y)
    float(m["loss"])
    t0 = time.perf_counter()
    for _ in range(STEPS):
        params, opt, m = tr.train_step(params, opt, x, y)
    float(m["loss"])
    return (time.perf_counter() - t0) / STEPS, m


def bench_moe(batch: int = 32, seq: int = 512) -> list[dict]:
    from cs744_pytorch_distributed_tutorial_tpu.data import synthetic_tokens
    from cs744_pytorch_distributed_tutorial_tpu.parallel import make_mesh
    from cs744_pytorch_distributed_tutorial_tpu.train import LMConfig, LMTrainer

    base = dict(
        vocab_size=50304, num_layers=6, num_heads=8, d_model=512,
        max_seq_len=seq, seq_len=seq, global_batch_size=batch,
        attention_impl="flash", compute_dtype="bfloat16", use_rope=True,
    )
    rows = []
    for name, kw in (
        # top-2 of E=8 F-wide experts vs the FLOPs-matched 2F dense MLP.
        # Ungrouped (G=1) measured 4.8x slower than dense — the
        # O(N*E*C*D) dispatch at N=16k tokens; GShard grouping (G=16,
        # 1024 tokens/group) divides that cost by G.
        # moe_dispatch pinned: LMConfig's default flipped to "scatter"
        # in round 5, and these two are the einsum BASELINE rows.
        ("moe_e8_top2_g1", dict(d_ff=1024, moe_experts=8, moe_top_k=2,
                                moe_dispatch="einsum")),
        ("moe_e8_top2_g16", dict(d_ff=1024, moe_experts=8, moe_top_k=2,
                                 moe_groups=16, moe_dispatch="einsum")),
        # Round 5 (VERDICT r4 #6): scatter-add/gather token movement —
        # O(N*K*D) instead of the O(N*E*C*D) one-hot einsums, same
        # routing/drop semantics (parity-tested). Rows at the grouped
        # AND ungrouped settings: scatter's cost does not grow with the
        # group size, so G=1's per-group capacity overhead vanishes.
        ("moe_e8_top2_g16_scatter",
         dict(d_ff=1024, moe_experts=8, moe_top_k=2, moe_groups=16,
              moe_dispatch="scatter")),
        ("moe_e8_top2_g1_scatter",
         dict(d_ff=1024, moe_experts=8, moe_top_k=2,
              moe_dispatch="scatter")),
        # Capacity-floor probe: at cf=1.25 the slot padding ALONE costs
        # 1.25x vs the FLOPs-matched dense (E*C = k*cf*N slot-tokens);
        # cf=1.0 removes the padding term and isolates the router +
        # token-movement overhead.
        ("moe_e8_top2_g1_scatter_cf1",
         dict(d_ff=1024, moe_experts=8, moe_top_k=2,
              moe_dispatch="scatter", moe_capacity_factor=1.0)),
        # Dropless (late round 5): NO capacity slots — argsort by
        # expert + two ragged grouped matmuls (ops/gmm.py); expert
        # FLOPs are exactly k*N rows (the cf=1.0 scatter row's compute
        # without its drops). Both gmm backends measured.
        ("moe_e8_top2_dropless_ragged",
         dict(d_ff=1024, moe_experts=8, moe_top_k=2,
              moe_dispatch="dropless")),
        ("moe_e8_top2_dropless_pallas",
         dict(d_ff=1024, moe_experts=8, moe_top_k=2,
              moe_dispatch="dropless", moe_gmm_impl="pallas")),
        ("dense_matched", dict(d_ff=2048)),
    ):
        cfg = LMConfig(**base, **kw)
        tr = LMTrainer(cfg, mesh=make_mesh({"data": 1, "seq": 1}))
        params, opt = tr.init()
        x, y = tr.shard_batch(synthetic_tokens(batch, seq, 50304, seed=0))
        dt, m = _timed_lm_steps(tr, params, opt, x, y)
        row = {
            "metric": f"moe_vs_dense_{name}",
            "ms_per_step": round(dt * 1e3, 2),
            "tokens_per_sec": round(batch * seq / dt),
            "config": f"6L/512d/{kw.get('d_ff')}ff/b{batch}/T{seq}",
        }
        if "moe_experts" in kw:
            row["moe_drop"] = round(float(m["moe_drop"]), 4)
            row["moe_aux"] = round(float(m["moe_aux"]), 4)
        rows.append(row)
    return rows


def bench_moe_expert_sweep(batch: int = 32, seq: int = 512) -> list[dict]:
    """Where dropless pays: high expert counts. Capacity-slot compute
    scales with E*C = k*cf*N regardless of E, but the DROP RATE at
    fixed cf grows with routing imbalance, which grows with E (an
    untrained router over E=32 experts is far from uniform per group);
    covering the skew with cf costs proportional compute. Dropless
    computes exactly k*N rows at any E and any skew — this sweep
    measures both sides of that trade at E=8/32 with top-2."""
    from cs744_pytorch_distributed_tutorial_tpu.data import synthetic_tokens
    from cs744_pytorch_distributed_tutorial_tpu.parallel import make_mesh
    from cs744_pytorch_distributed_tutorial_tpu.train import LMConfig, LMTrainer

    base = dict(
        vocab_size=50304, num_layers=6, num_heads=8, d_model=512,
        d_ff=1024, max_seq_len=seq, seq_len=seq, global_batch_size=batch,
        attention_impl="flash", compute_dtype="bfloat16", use_rope=True,
        moe_top_k=2,
    )
    rows = []
    for name, kw in (
        ("e8_scatter_cf125", dict(moe_experts=8, moe_dispatch="scatter")),
        ("e8_dropless", dict(moe_experts=8, moe_dispatch="dropless")),
        ("e32_scatter_cf125", dict(moe_experts=32, moe_dispatch="scatter")),
        # cf covering the observed e32 init drop rate costs slots.
        ("e32_scatter_cf2", dict(moe_experts=32, moe_dispatch="scatter",
                                 moe_capacity_factor=2.0)),
        ("e32_dropless", dict(moe_experts=32, moe_dispatch="dropless")),
    ):
        cfg = LMConfig(**base, **kw)
        tr = LMTrainer(cfg, mesh=make_mesh({"data": 1, "seq": 1}))
        params, opt = tr.init()
        x, y = tr.shard_batch(synthetic_tokens(batch, seq, 50304, seed=0))
        dt, m = _timed_lm_steps(tr, params, opt, x, y)
        rows.append({
            "metric": f"moe_expert_sweep_{name}",
            "ms_per_step": round(dt * 1e3, 2),
            "tokens_per_sec": round(batch * seq / dt),
            "moe_drop": round(float(m["moe_drop"]), 4),
            "config": f"6L/512d/1024ff/top2/b{batch}/T{seq}",
        })
    return rows


def moe_training_trajectory() -> dict:
    """A short real fit() so drop-rate/aux-loss are shown as measured
    TRAJECTORIES (the test pins the plumbing; this pins the numbers)."""
    from cs744_pytorch_distributed_tutorial_tpu.data import synthetic_tokens
    from cs744_pytorch_distributed_tutorial_tpu.parallel import make_mesh
    from cs744_pytorch_distributed_tutorial_tpu.train import LMConfig, LMTrainer

    cfg = LMConfig(
        vocab_size=512, num_layers=4, num_heads=8, d_model=256, d_ff=512,
        max_seq_len=256, seq_len=256, global_batch_size=32,
        attention_impl="flash", compute_dtype="bfloat16", use_rope=True,
        moe_experts=8, moe_top_k=2, learning_rate=3e-4,
    )
    tr = LMTrainer(cfg, mesh=make_mesh({"data": 1, "seq": 1}))
    tokens = synthetic_tokens(256, 256, 512, seed=0)
    tr.fit(tokens, steps=60)
    h = tr.history
    return {
        "metric": "moe_fit_trajectory",
        "loss_first_last": [round(h["loss"][0], 3), round(h["loss"][-1], 3)],
        "drop_first_last": [
            round(h["moe_drop"][0], 4), round(h["moe_drop"][-1], 4),
        ],
        "aux_first_last": [
            round(h["moe_aux"][0], 4), round(h["moe_aux"][-1], 4),
        ],
    }


def main() -> None:
    which = set(sys.argv[1:]) or {"vit", "vit_descent", "moe", "moe_fit"}
    if "vit" in which:
        for model, batch in (
            ("vit_tiny", 1024), ("vit_small", 512), ("vit_wide_p8", 1024),
        ):
            print(json.dumps(bench_vit(model, batch)), flush=True)
    if "vit_descent" in which:
        for model in ("vit_tiny", "vit_wide_p8"):
            print(json.dumps(vit_descends(model)), flush=True)
    if "moe" in which:
        for row in bench_moe():
            print(json.dumps(row), flush=True)
    if "moe_sweep" in which:
        for row in bench_moe_expert_sweep():
            print(json.dumps(row), flush=True)
    if "moe_fit" in which:
        print(json.dumps(moe_training_trajectory()), flush=True)


if __name__ == "__main__":
    main()
