"""ResNet-50 at ImageNet shape on the real chip — the scale-out model.

BASELINE.json's north star names ResNet-50/ImageNet scale-out alongside
the scored CIFAR ResNet-18 metric; `tests/test_imagenet.py` pins the
model shapes (7x7/s2 stem + maxpool, torchvision-matching param
counts), and this records single-chip training throughput at 224 px on
synthetic data (real ImageNet bytes are not available in this
environment). Run: python benchmarks/bench_imagenet.py

Not measured on this installation.
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

from cs744_pytorch_distributed_tutorial_tpu.config import TrainConfig
from cs744_pytorch_distributed_tutorial_tpu.data import synthetic_images
from cs744_pytorch_distributed_tutorial_tpu.parallel import make_mesh
from cs744_pytorch_distributed_tutorial_tpu.parallel.mesh import (
    shard_global_batch,
)
from cs744_pytorch_distributed_tutorial_tpu.train import Trainer

BATCH = 256
WARMUP = 8
STEPS = 15


def main() -> None:
    n = len(jax.devices())
    for model in ("resnet50", "resnet18"):
        cfg = TrainConfig(
            model=model,
            sync="auto",
            num_devices=n,
            global_batch_size=BATCH,
            compute_dtype="bfloat16",
            synthetic_data=True,
            image_size=224,
            num_classes=1000,
        )
        mesh = make_mesh({"data": n})
        tr = Trainer(cfg, mesh=mesh)
        state = tr.init()
        ds = synthetic_images(BATCH, 16, image_size=224, num_classes=1000,
                              seed=0)
        x, y = shard_global_batch(mesh, ds.train_images, ds.train_labels)
        key = jax.random.key(cfg.seed)
        if jax.default_backend() != "cpu":
            # Compile failures must surface, not silently fall back — a
            # default-compiled number would not be comparable to the
            # documented vmem-option configuration (same policy as
            # bench.py).
            step = tr.train_step.lower(state, x, y, key).compile(
                compiler_options={"xla_tpu_scoped_vmem_limit_kib": "65536"}
            )
        else:  # CPU smoke runs: the TPU option doesn't exist there
            step = tr.train_step
        for _ in range(WARMUP):
            state, m = step(state, x, y, key)
        float(jax.tree.leaves(state.params)[0].ravel()[0])
        t0 = time.perf_counter()
        for _ in range(STEPS):
            state, m = step(state, x, y, key)
        float(jax.tree.leaves(state.params)[0].ravel()[0])
        dt = (time.perf_counter() - t0) / STEPS
        print(
            f"{model:9s} 224px b{BATCH}: {dt * 1e3:8.1f} ms/step  "
            f"{BATCH / dt / n:8.1f} samples/sec/chip"
        )


if __name__ == "__main__":
    main()
