"""Round-3 probe: per-op device breakdown of the scored bench step.

Times the non-donating mirror of the scored train step
(``benchmarks/ablate.py::build_full_step`` — augment + fwd/bwd + SGD on
ResNet-18/CIFAR, batch 4096 bf16) compiled with bench.py's vmem option,
and prints the top device ops. This produced the round-3 region map in
``ablate.py`` (stem+stage1 54.2 ms of 112.2 at ~35% MFU; the rest at
~86%). Run on the TPU: python benchmarks/breakdown_r3.py
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

from bench import COMPILER_OPTIONS
from benchmarks.ablate import build_full_step
from cs744_pytorch_distributed_tutorial_tpu.utils.profiling import (
    device_op_breakdown,
)


def main() -> None:
    full, args = build_full_step()
    fn = jax.jit(full).lower(*args).compile(compiler_options=COMPILER_OPTIONS)

    # Warm up before tracing.
    out = None
    for _ in range(8):
        out = fn(*args)
    float(jax.tree.leaves(out)[0].ravel()[0])

    total, rows = device_op_breakdown(lambda: fn(*args), iters=4, top=40)
    print(f"total device ms/iter: {total:.2f}")
    for ms, name in rows:
        print(f"  {ms:8.3f} ms  {name}")


if __name__ == "__main__":
    main()
