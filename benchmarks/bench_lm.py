"""LM training throughput on the real chip: tokens/sec, flash vs dense.

Single-chip companion to the scored CIFAR bench: a GPT-style block stack
at seq_len 2048 in bf16, comparing the Pallas flash-attention kernel
(ops/flash_attention.py) against dense attention. Run: python
benchmarks/bench_lm.py

Measured 2026-07-30 (one TPU v5e chip, this config):
  round 1:  dense  91.9 ms/step  178.3k tok/s; flash 58.1 ms  282.0k (1.58x)
  round 2:  dense  80.3 ms/step  204.1k tok/s; flash 49.5 ms  330.9k (1.62x)
(not re-measured on this installation.)
History: the kernel started 2x SLOWER than dense (f32-cast dots +
128x128 tiles); native-dtype MXU feeds and 512x1024 blocks made the
forward 2.5x faster (4.3 vs 10.7 ms), and the Pallas FA-2 backward
(dq/dkv kernels, no [T, T] materialization) delivered the full-step
1.58x above. Parity vs dense verified on-chip at 'highest' matmul
precision (maxabs ~1e-4 grads, 5e-7 forward).
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from cs744_pytorch_distributed_tutorial_tpu.data import synthetic_tokens
from cs744_pytorch_distributed_tutorial_tpu.parallel import make_mesh
from cs744_pytorch_distributed_tutorial_tpu.train import LMConfig, LMTrainer

BATCH = 8
SEQ = 2048
STEPS = 10


def main() -> None:
    mesh = make_mesh({"data": 1, "seq": 1})
    tokens = synthetic_tokens(BATCH * 2, SEQ, 32768, seed=0)
    for impl in ("dense", "flash"):
        cfg = LMConfig(
            vocab_size=32768,
            num_layers=4,
            num_heads=8,
            d_model=512,
            d_ff=2048,
            max_seq_len=SEQ,
            seq_len=SEQ,
            global_batch_size=BATCH,
            attention_impl=impl,
            compute_dtype="bfloat16",
        )
        tr = LMTrainer(cfg, mesh=mesh)
        params, opt = tr.init()
        x, y = tr.shard_batch(tokens[:BATCH])

        # Warm up past the compiled call so the measurement is the
        # steady state.
        params, opt, m = tr.train_step(params, opt, x, y)  # compile
        float(m["loss"])
        for _ in range(8):
            params, opt, m = tr.train_step(params, opt, x, y)
        float(m["loss"])
        t0 = time.perf_counter()
        for _ in range(STEPS):
            params, opt, m = tr.train_step(params, opt, x, y)
        float(m["loss"])  # fence
        dt = (time.perf_counter() - t0) / STEPS
        print(
            f"{impl:6s} {dt * 1e3:8.2f} ms/step  "
            f"{BATCH * SEQ / dt:12.0f} tokens/sec"
        )


if __name__ == "__main__":
    main()
