"""GPT-2-large-class + long-context medium points (round 5, VERDICT
r4 #7) — the next perf rungs past the 24L/1024d MFU-0.510 point.

Two regimes on one v5e chip, bf16 + RoPE + Pallas flash, MFU accounting
identical to bench_lm_gpt2.py / probe_gpt2_medium.py (2*MACs,
3x-forward train, remat recompute NOT counted, causal masking not
discounted — flash MFU is understated):

1. **large**: 36L / 1280d / 20h / d_ff 5120 / T=1024 / vocab 50304
   (~770M params). f32 params ~3.1 GB + f32 adam moments ~6.2 GB leave
   ~6 GB for activations on the 16 GB chip — remat and small batches
   are load-bearing here, not optional. On the development setup of
   the time, compiles failed past a total program footprint (12L b32
   and 24L b16 both did), so the sweep leads with scan_layers variants (the
   ~4.3%-at-24L compile-scalability trade measured round 4; expected
   to amortize further at 36L).
2. **medium-T2048**: 24L / 1024d at T=2048 — the long-context regime
   where flash and remat matter more (attention is 2*S*D of the
   per-layer FLOPs: 17% at T=2048/1024d vs 9% at T=1024).

Measured 2026-08-01 (one TPU v5e chip on that setup; wall-clock over
STEPS after warmup; not re-measured on this installation):

  medium-T2048 unroll+nomat b4   226.3 ms  36.2k tok/s  MFU 0.5006
  medium-T2048 b8 (unroll/scan x nomat/dots): compile failed
  large scan+dots  b1   114.2 ms   9.0k tok/s  MFU 0.237
  large scan+dots  b2   160.8 ms  12.7k tok/s  MFU 0.336
  large scan+dots  b3:  compile failed
  large scan+nomat b2:  compile failed
  large b4..b16, unroll b8 (every variant): compile failed

Findings:
- **Context doubles at constant MFU**: medium at T=2048/b4 (the same
  8192 tokens/step as the T=1024/b8 row) lands at 0.5006 vs 0.510 —
  the flash path's S-scaling costs ~2% MFU, and the long-context
  regime keeps the 1024d efficiency. The b8/T2048 point that would
  test for a 0.52+ peak is COMPILE-WALLED (below); the late-round-5
  session filled the gap from the compiling side: b5 = 0.4847,
  b6 = 0.4678 — MFU DEGRADES monotonically past b4 (T=2048 remat-off
  activations push the working set into a worse HBM regime well
  before the wall), so **b4/0.5006 is a measured local optimum**,
  not a truncated curve, and the 0.52+ hope is dead on this chip
  regardless of the compile wall.
- **The compile wall's boundary on that setup, pinned from both sides**:
  medium-T2048 compiles at b4 and walls at b8 (= the b16/T1024
  footprint that walled round 4); large compiles at scan+dots b2 and
  walls at b3-dots AND b2-nomat. The wall tracks TOTAL footprint
  (activations + 9.3 GB of large's persistent f32 params+moments),
  not traced-program size — scan_layers (12x smaller program) moves
  it not at all at 36L.
- **GPT-2-large was therefore activation-starved there**: the only
  compiling configs (b1/b2 + dots recompute) underfill the MXU
  (0.237/0.336) exactly as small batches always do. The d-model trend
  (0.454@768d -> 0.510@1024d) predicts >=0.51 for 1280d at b8
  remat-off; that remains a prediction until it is re-probed on this
  installation.
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

VOCAB = 50304
STEPS, WARMUP = 6, 4
V5E_PEAK_FLOPS = 197e12

SHAPES = {
    "large": dict(layers=36, d_model=1280, heads=20, d_ff=5120, seq=1024),
    "medium-T2048": dict(layers=24, d_model=1024, heads=16, d_ff=4096,
                         seq=2048),
}


def flops_per_token(layers, d_model, d_ff, seq) -> float:
    per_layer = 4 * d_model**2 + 2 * d_model * d_ff + 2 * seq * d_model
    return 3.0 * (layers * 2.0 * per_layer + 2.0 * d_model * VOCAB)


def run(shape: str, batch: int, scan_layers: bool, remat: bool) -> None:
    sh = SHAPES[shape]
    label = (
        f"{shape}-{'scan' if scan_layers else 'unroll'}-"
        f"{'dots' if remat else 'nomat'}-b{batch}"
    )
    try:
        cfg = LMConfig(
            vocab_size=VOCAB, num_layers=sh["layers"], num_heads=sh["heads"],
            d_model=sh["d_model"], d_ff=sh["d_ff"], max_seq_len=sh["seq"],
            seq_len=sh["seq"], global_batch_size=batch,
            attention_impl="flash", compute_dtype="bfloat16", remat=remat,
            remat_policy="dots" if remat else "none",
            scan_layers=scan_layers, use_rope=True,
        )
        tr = LMTrainer(cfg, mesh=make_mesh({"data": 1, "seq": 1}))
        params, opt = tr.init()
        x, y = tr.shard_batch(
            synthetic_tokens(batch, sh["seq"], VOCAB, seed=0)
        )
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
        tok_s = batch * sh["seq"] / dt
        fpt = flops_per_token(sh["layers"], sh["d_model"], sh["d_ff"],
                              sh["seq"])
        print(json.dumps({
            "metric": "gpt2large_train_tokens_per_sec_per_chip",
            "probe": label,
            "ms_per_step": round(dt * 1e3, 2),
            "tokens_per_sec": round(tok_s),
            "mfu": (
                round(tok_s * fpt / V5E_PEAK_FLOPS, 4)
                if jax.default_backend() != "cpu" else None
            ),
            "config": f"{sh['layers']}L/{sh['d_model']}d/{sh['heads']}h"
                      f"/T{sh['seq']}/V{VOCAB}/b{batch}/bf16"
                      f"/remat={'dots' if remat else 'off'}/rope"
                      + ("/scan" if scan_layers else ""),
        }), flush=True)
    except Exception as e:
        print(json.dumps({
            "probe": label,
            "error": f"{type(e).__name__}: {str(e)[:200]}",
        }), flush=True)


def main() -> None:
    only = sys.argv[1:] or None
    for shape, b, sc, rm in (
        ("large", 4, True, False),
        ("large", 4, True, True),
        ("large", 8, True, False),
        ("large", 8, True, True),
        ("large", 8, False, False),   # expected: compile-helper wall
        ("large", 16, True, False),
        ("large", 16, True, True),
        ("medium-T2048", 4, False, False),
        ("medium-T2048", 8, False, False),
        ("medium-T2048", 8, False, True),
    ):
        label = (
            f"{shape}-{'scan' if sc else 'unroll'}-"
            f"{'dots' if rm else 'nomat'}-b{b}"
        )
        if only and not any(o in label for o in only):
            continue
        run(shape, b, scan_layers=sc, remat=rm)


if __name__ == "__main__":
    main()
