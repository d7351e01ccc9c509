"""Test harness: multi-device-without-a-cluster.

The reference's verification strategy was "run on 4 CloudLab nodes and
eyeball the loss" (SURVEY §4). Here every collective path runs
single-process in CI on 8 virtual CPU devices via
``--xla_force_host_platform_device_count`` — set BEFORE the XLA backend
initializes. This directory is the CPU harness wherever it runs, so the
platform is pinned here too; what needs the chip lives in ``tests_chip/``.
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8"
    # The concurrency-optimized thunk scheduler issues data-independent
    # collectives in per-device nondeterministic order; the in-process
    # CPU communicator's rendezvous then deadlocks (observed on
    # 1F1B x seq-parallel, where a tick's fwd and bwd halves are
    # independent). TPU hardware is indifferent (channel-keyed DMAs) —
    # this is a CPU-harness setting, not a model requirement.
    + " --xla_cpu_enable_concurrency_optimized_scheduler=false"
)

import jax

jax.config.update("jax_platforms", "cpu")
# Tier-1 counts backend compiles (obs/system.py::CompileCounter). The CLI
# mains point JAX at a persistent compile cache; a test that calls one
# in-process must not turn later tests' compiles into cache hits.
jax.config.update("jax_enable_compilation_cache", False)

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def strict_jax_guard(request):
    """Opt-in strictness: tests marked ``@pytest.mark.strict_jax`` run
    under ``jax.checking_leaks()`` (tracer leaks raise at the leak site)
    and ``jax.transfer_guard("disallow")`` (any IMPLICIT host<->device
    transfer raises). Under the guard, fetch results with an explicit
    ``jax.device_get`` rather than ``float()``/``np.asarray`` — which is
    exactly the discipline graftlint GL001 enforces statically."""
    if request.node.get_closest_marker("strict_jax") is None:
        yield
        return
    with jax.checking_leaks(), jax.transfer_guard("disallow"):
        yield


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected >=8 forced CPU devices, got {devs}"
    return devs


@pytest.fixture(scope="session")
def mesh4():
    from cs744_pytorch_distributed_tutorial_tpu.parallel import make_mesh

    return make_mesh({"data": 4}, devices=jax.devices()[:4])


@pytest.fixture(scope="session")
def mesh8():
    from cs744_pytorch_distributed_tutorial_tpu.parallel import make_mesh

    return make_mesh({"data": 8})


# Shared tiny-CNN harness for the sharded-optimizer parity suites
# (test_zero1.py, test_fsdp.py): same config, same synthetic batches.
TINY_DP4_CFG = dict(
    model="tiny_cnn",
    num_devices=4,
    global_batch_size=32,
    synthetic_data=True,
    synthetic_train_size=128,
    synthetic_test_size=64,
)


def run_tiny_dp4_steps(
    sync: str,
    mesh,
    steps: int = 4,
    cfg_overrides: dict | None = None,
    data_seed: int = 0,
):
    """Train ``steps`` repeats of one fixed synthetic batch under strategy
    ``sync``; returns (losses, trainer, final_state). The ONE canonical
    step-driving discipline for the parity/golden suites — per-step
    randomness comes from the trainer folding cfg.seed with the step."""
    import jax

    from cs744_pytorch_distributed_tutorial_tpu.config import TrainConfig
    from cs744_pytorch_distributed_tutorial_tpu.data import synthetic_cifar10
    from cs744_pytorch_distributed_tutorial_tpu.parallel.mesh import (
        shard_global_batch,
    )
    from cs744_pytorch_distributed_tutorial_tpu.train import Trainer

    cfg = TrainConfig(**TINY_DP4_CFG, sync=sync, **(cfg_overrides or {}))
    tr = Trainer(cfg, mesh=mesh)
    state = tr.init()
    ds = synthetic_cifar10(TINY_DP4_CFG["global_batch_size"], 8, seed=data_seed)
    x, y = shard_global_batch(mesh, ds.train_images, ds.train_labels)
    key = jax.random.key(cfg.seed)
    losses = []
    for _ in range(steps):
        state, m = tr.train_step(state, x, y, key)
        losses.append(float(m["loss"]))
    return losses, tr, state
