"""``jax.block_until_ready`` is a completion fence on this device.

The timers (``utils/timing.py``, ``utils/profiling.py``'s
``capture_device_profile``) close their regions with it. This test times the same 30 training steps at
the smoke's full width twice per round — closed once by
``block_until_ready`` and once by fetching a scalar that depends on the
last step (a host round-trip cannot return before the work it reads) —
and requires the two to agree. An enqueue-only "fence" would read an
order of magnitude short.
"""

import statistics
import time

import jax

from cs744_pytorch_distributed_tutorial_tpu.config import TrainConfig
from cs744_pytorch_distributed_tutorial_tpu.data import synthetic_cifar10
from cs744_pytorch_distributed_tutorial_tpu.parallel.mesh import (
    shard_global_batch,
)
from cs744_pytorch_distributed_tutorial_tpu.train import Trainer

STEPS, ROUNDS = 30, 3


def test_block_until_ready_agrees_with_scalar_fetch():
    n = len(jax.devices())
    batch = 4096 * n
    cfg = TrainConfig(
        model="resnet18", compute_dtype="bfloat16", sync="auto",
        num_devices=n, global_batch_size=batch, synthetic_data=True,
    )
    trainer = Trainer(cfg)
    state = trainer.init()
    ds = synthetic_cifar10(batch, 16, seed=0)
    x, y = shard_global_batch(trainer.mesh, ds.train_images, ds.train_labels)
    key = jax.random.key(cfg.seed)

    def window(fence):
        nonlocal state
        t0 = time.perf_counter()
        for _ in range(STEPS):
            state, metrics = trainer.train_step(state, x, y, key)
        fence(state, metrics)
        return (time.perf_counter() - t0) / STEPS

    fences = {
        "block_until_ready": lambda s, m: jax.block_until_ready(s),
        "scalar_fetch": lambda s, m: float(m["loss"]),
    }
    window(fences["scalar_fetch"])  # compile + warm up
    times = {name: [] for name in fences}
    for _ in range(ROUNDS):
        for name, fence in fences.items():
            times[name].append(window(fence))
    medians = {k: statistics.median(v) for k, v in times.items()}
    print("fence s/step:", times, "medians:", medians)
    ratio = medians["block_until_ready"] / medians["scalar_fetch"]
    assert 0.9 < ratio < 1.1, (times, ratio)
