"""GPT-2-medium-class depth point (round 4, VERDICT r3 #4).

24L / 1024d / 16h / d_ff 4096 / T=1024 / vocab 50304 (~350M params),
bf16, RoPE, flash attention, one v5e chip — the first training number
above 12L/768d in this repo, the scale remat/scan_layers/ZeRO exist
for. Ablates scan_layers x remat to answer two questions at once:

1. does the 24L unrolled program still compile (12L b32 did not on the
   development setup of the time), and
2. what do scan_layers and remat cost/buy at depth.

MFU accounting matches bench_lm_gpt2.py (2*MACs, 3x-forward train,
remat recompute NOT counted, causal masking not discounted).

Measured 2026-07-31 (one TPU v5e chip):
  unroll + remat=off  b8   197.7 ms  41.4k tok/s  MFU 0.510  <- headline
  unroll + remat=dots b8   230.3 ms  35.6k tok/s  MFU 0.438
  scan   + remat=dots b8   240.8 ms  34.0k tok/s  MFU 0.418
  unroll + remat=off  b12  320.3 ms  38.4k tok/s  MFU 0.472
  b16: did not compile in any variant (unroll/scan x dots/off) on
       that setup — the same wall as 12L/b32; it tracked total program
       footprint, not layer count alone (24L b8 compiled where 12L b32
       did not). Not re-probed on this installation.
Findings: (1) the 24L/b8 UNROLLED program compiles and remat-off FITS
(~0.7 GB bf16 params + 2.8 GB f32 adam + activations < 16 GB HBM) —
at 1024d the bigger matmuls lift MFU past the 12L model's (0.510 vs
0.481); (2) the scan_layers penalty collapses from ~22% at 12L/768d
to ~4.3% at 24L/1024d (the loop overhead amortizes as the block body
grows) — scan remains the compile-scalability option, unrolled remains
the throughput choice while programs still compile.
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

SEQ, LAYERS, D_MODEL, HEADS, D_FF = 1024, 24, 1024, 16, 4096
VOCAB = 50304
STEPS, WARMUP = 8, 5
V5E_PEAK_FLOPS = 197e12


def flops_per_token() -> float:
    per_layer = 4 * D_MODEL**2 + 2 * D_MODEL * D_FF + 2 * SEQ * D_MODEL
    return 3.0 * (LAYERS * 2.0 * per_layer + 2.0 * D_MODEL * VOCAB)


def run(label: str, batch: int, scan_layers: bool, remat: bool) -> None:
    try:
        cfg = LMConfig(
            vocab_size=VOCAB, num_layers=LAYERS, num_heads=HEADS,
            d_model=D_MODEL, d_ff=D_FF, max_seq_len=SEQ, seq_len=SEQ,
            global_batch_size=batch, attention_impl="flash",
            compute_dtype="bfloat16", remat=remat,
            remat_policy="dots" if remat else "none",
            scan_layers=scan_layers, use_rope=True,
        )
        tr = LMTrainer(cfg, mesh=make_mesh({"data": 1, "seq": 1}))
        params, opt = tr.init()
        x, y = tr.shard_batch(synthetic_tokens(batch, SEQ, VOCAB, seed=0))
        params, opt, m = tr.train_step(params, opt, x, y)
        float(m["loss"])
        for _ in range(WARMUP):
            params, opt, m = tr.train_step(params, opt, x, y)
        float(m["loss"])
        t0 = time.perf_counter()
        for _ in range(STEPS):
            params, opt, m = tr.train_step(params, opt, x, y)
        float(m["loss"])
        dt = (time.perf_counter() - t0) / STEPS
        tok_s = batch * SEQ / dt
        print(json.dumps({
            "metric": "gpt2medium_train_tokens_per_sec_per_chip",
            "probe": label,
            "ms_per_step": round(dt * 1e3, 2),
            "tokens_per_sec": round(tok_s),
            "mfu": (
                round(tok_s * flops_per_token() / V5E_PEAK_FLOPS, 4)
                if jax.default_backend() != "cpu" else None
            ),
            "config": f"{LAYERS}L/{D_MODEL}d/{HEADS}h/T{SEQ}/V{VOCAB}"
                      f"/b{batch}/bf16/remat={'dots' if remat else 'off'}"
                      f"/rope" + ("/scan" if scan_layers else ""),
        }), flush=True)
    except Exception as e:
        print(json.dumps({
            "probe": label, "batch": batch, "scan_layers": scan_layers,
            "remat": remat, "error": f"{type(e).__name__}: {str(e)[:200]}",
        }), flush=True)


def main() -> None:
    only = sys.argv[1:] or None
    for label, b, sc, rm in (
        ("unroll-nomat", 8, False, False),
        ("unroll-dots", 8, False, True),
        ("scan-dots", 8, True, True),
        ("unroll-dots-b16", 16, False, True),
        ("scan-dots-b16", 16, True, True),
        ("scan-nomat-b16", 16, True, False),
        ("unroll-nomat-b12", 12, False, False),
    ):
        if only and label not in only:
            continue
        run(label, b, scan_layers=sc, remat=rm)


if __name__ == "__main__":
    main()
