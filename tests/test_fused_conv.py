"""Pallas conv3x3 wgrad kernel vs jax.vjp reference (interpret mode).

The kernel replaces XLA's conv-backprop-filter emitter for the scored
ResNet step's hottest backward ops (``ops/fused_conv.py``); these tests
pin its numerics — both strides, k-tiling, and the full custom_vjp
(dx via XLA, dw via the kernel) — against autodiff of the XLA conv.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from cs744_pytorch_distributed_tutorial_tpu.ops.fused_conv import (

    conv3x3,
    conv3x3_wgrad,
)

# CPU-interpret Pallas conv parity: minutes of XLA compile per case.
pytestmark = pytest.mark.slow


def _ref_wgrad(x, g, stride):
    def f(w):
        return lax.conv_general_dilated(
            x, w, (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )

    w0 = jnp.zeros((3, 3, x.shape[-1], g.shape[-1]), x.dtype)
    return jax.vjp(f, w0)[1](g)[0]


@pytest.mark.parametrize(
    "stride,b,h,c,k,bb",
    [
        (1, 8, 8, 16, 32, 2),
        (1, 4, 16, 8, 8, 2),
        (1, 6, 8, 8, 8, 3),  # batch chunk that doesn't divide evenly -> 3
        (2, 8, 8, 16, 32, 2),
        (2, 4, 16, 8, 16, 4),
    ],
)
def test_wgrad_matches_autodiff(stride, b, h, c, k, bb):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((b, h, h, c)), jnp.float32)
    g = jnp.asarray(
        rng.standard_normal((b, h // stride, h // stride, k)), jnp.float32
    )
    dw = conv3x3_wgrad(x, g, stride=stride, block_batch=bb, interpret=True)
    dw_ref = _ref_wgrad(x, g, stride)
    np.testing.assert_allclose(dw, dw_ref, rtol=1e-4, atol=1e-4)


def test_custom_vjp_full_path():
    """dx rides XLA's transposed conv, dw the Pallas kernel — both must
    match plain autodiff of the XLA conv."""
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((4, 8, 8, 8)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((3, 3, 8, 16)) * 0.1, jnp.float32)

    def loss_ours(x, w):
        return (conv3x3(x, w, 1, True) ** 2).sum()

    def loss_ref(x, w):
        y = lax.conv_general_dilated(
            x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC")
        )
        return (y**2).sum()

    go = jax.grad(loss_ours, argnums=(0, 1))(x, w)
    gr = jax.grad(loss_ref, argnums=(0, 1))(x, w)
    np.testing.assert_allclose(go[0], gr[0], rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(go[1], gr[1], rtol=1e-4, atol=1e-4)


def test_fast_conv_resnet_grads_match():
    """ResNet-18 with fast_conv routes wide 3x3s through the kernel; the
    full model's gradients must match the nn.Conv build (same params)."""
    from cs744_pytorch_distributed_tutorial_tpu.models.resnet import resnet18

    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((2, 32, 32, 3)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 10, 2), jnp.int32)

    ref = resnet18(num_classes=10)
    fast = resnet18(num_classes=10, fast_conv=True, kernel_interpret=True)
    vs = ref.init(jax.random.key(0), x, train=False)

    def loss(model, p):
        import optax

        logits, _ = model.apply(
            {"params": p, "batch_stats": vs["batch_stats"]},
            x, train=True, mutable=["batch_stats"],
        )
        return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()

    # identical param trees: fast_conv preserves nn.Conv naming
    fast_vs = fast.init(jax.random.key(0), x, train=False)
    assert jax.tree.structure(vs["params"]) == jax.tree.structure(
        fast_vs["params"]
    )

    g_ref = jax.grad(lambda p: loss(ref, p))(vs["params"])
    g_fast = jax.grad(lambda p: loss(fast, p))(vs["params"])
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4),
        g_ref, g_fast,
    )


@pytest.mark.parametrize("sync", ["auto", "allreduce"])
def test_fast_conv_engine_trajectory_parity(sync, mesh4):
    """cfg.fast_conv through the REAL engine (check_vma shard_map, both
    the framework-inserted and manual sync families) must reproduce the
    nn.Conv trajectory: the custom VJP aligns its outputs' varying axes
    with the primals (psum for replicated params under 'auto', no-op for
    the pcast-varying manual strategies)."""
    from cs744_pytorch_distributed_tutorial_tpu.config import TrainConfig
    from cs744_pytorch_distributed_tutorial_tpu.data import synthetic_cifar10
    from cs744_pytorch_distributed_tutorial_tpu.parallel.mesh import (
        shard_global_batch,
    )
    from cs744_pytorch_distributed_tutorial_tpu.train import Trainer

    losses = {}
    for fast in (False, True):
        cfg = TrainConfig(
            model="resnet18", sync=sync, num_devices=4,
            global_batch_size=16, synthetic_data=True, fast_conv=fast,
        )
        tr = Trainer(cfg, mesh=mesh4)
        state = tr.init()
        ds = synthetic_cifar10(16, 8, seed=0)
        x, y = shard_global_batch(
            mesh4, ds.train_images[:16], ds.train_labels[:16]
        )
        key = jax.random.key(cfg.seed)
        run = []
        for _ in range(2):
            state, m = tr.train_step(state, x, y, key)
            run.append(float(m["loss"]))
        losses[fast] = run
    np.testing.assert_allclose(losses[True], losses[False], rtol=1e-5)
