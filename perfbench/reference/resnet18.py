"""Plain ResNet-18 (He et al. 2015) with the CIFAR 3x3 stem: forward,
loss, gradients and the torch-style SGD step in jax.numpy, float32,
convolutions at "highest". BatchNorm uses the batch's own mean and biased
variance (training mode); each data-parallel replica normalises over its
own rows, as the configuration states (``sync_bn: false``).

The input transform is the configuration's: RandomCrop(32, padding 4),
horizontal flip with probability 1/2, normalisation by the CIFAR mean and
standard deviation. Its random draws follow the configuration's stated
keying (run key folded with the step, then the replica index; three
sub-keys for row offset, column offset and flip), so the reference sees
the rows the program saw.
"""

from __future__ import annotations

from typing import Any, Mapping

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from perfbench.reference.common import HIGHEST, fake_quant, matmul

MEAN = np.array([125.3, 123.0, 113.9], np.float32) / 255.0
STD = np.array([63.0, 62.1, 66.7], np.float32) / 255.0
PAD = 4


def augment(key, images_u8):
    """uint8 [N,32,32,3] -> normalised float32 crops, flipped at random."""
    n, h, w, _ = images_u8.shape
    k_h, k_w, k_f = jax.random.split(key, 3)
    off_h = jax.random.randint(k_h, (n,), 0, 2 * PAD + 1)
    off_w = jax.random.randint(k_w, (n,), 0, 2 * PAD + 1)
    flip = jax.random.bernoulli(k_f, shape=(n,))
    padded = jnp.pad(images_u8, ((0, 0), (PAD, PAD), (PAD, PAD), (0, 0)))

    def one(img, oh, ow, f):
        crop = lax.dynamic_slice(img, (oh, ow, 0), (h, w, 3))
        return jnp.where(f, crop[:, ::-1], crop)

    x = jax.vmap(one)(padded, off_h, off_w, flip).astype(jnp.float32) / 255.0
    return (x - MEAN) / STD


def _conv(x, k, stride, quant):
    x = fake_quant(x, quant, axis=-1)
    k = fake_quant(k, quant, axis=(0, 1, 2))
    return lax.conv_general_dilated(
        x, k.astype(jnp.float32), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST,
    )


def _bn(x, scale, bias, eps=1e-5):
    m = jnp.mean(x, (0, 1, 2))
    v = jnp.mean(jnp.square(x - m), (0, 1, 2))
    return (x - m) * lax.rsqrt(v + eps) * scale + bias


def _block(p, name, x, stride, quant):
    y = _conv(x, p[f"{name}/Conv_0/kernel"], stride, quant)
    y = jax.nn.relu(_bn(y, p[f"{name}/BatchNorm_0/scale"], p[f"{name}/BatchNorm_0/bias"]))
    y = _conv(y, p[f"{name}/Conv_1/kernel"], 1, quant)
    y = _bn(y, p[f"{name}/BatchNorm_1/scale"], p[f"{name}/BatchNorm_1/bias"])
    r = x
    if f"{name}/Conv_2/kernel" in p:
        r = _conv(x, p[f"{name}/Conv_2/kernel"], stride, quant)
        r = _bn(r, p[f"{name}/BatchNorm_2/scale"], p[f"{name}/BatchNorm_2/bias"])
    return jax.nn.relu(y + r)


def forward(params: Mapping[str, Any], x, cfg: Mapping[str, Any], quant=None):
    """normalised float32 [N,32,32,3] -> logits [N, classes]."""
    p = params
    x = _conv(x, p["Conv_0/kernel"], 1, quant)
    x = jax.nn.relu(_bn(x, p["BatchNorm_0/scale"], p["BatchNorm_0/bias"]))
    blk = 0
    for s, n in enumerate(cfg["stage_sizes"]):
        for b in range(n):
            stride = 2 if (s > 0 and b == 0) else 1
            # Recompute each block in the backward pass: float32
            # activations of 4096 rows would not fit the chip otherwise.
            x = jax.checkpoint(
                lambda p_, x_, name=f"BasicBlock_{blk}", st=stride: _block(p_, name, x_, st, quant)
            )(p, x)
            blk += 1
    x = jnp.mean(x, (1, 2))
    return matmul(x, p["Dense_0/kernel"], quant) + p["Dense_0/bias"]


def replica_loss(params, images_u8, labels, key, cfg, quant=None):
    x = augment(key, images_u8)
    logits = forward(params, x, cfg, quant)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - picked)




def loss_and_grads(params, images_u8, labels, step_key, cfg, replicas, quant=None, exchange=True, devices=None):
    """Mean over replicas of each replica's loss and gradient; a replica is
    a contiguous share of the rows with its own augmentation key and its
    own BatchNorm statistics. ``exchange=False`` plants the fault of a
    missing gradient exchange: replica 0's gradient alone is applied.
    With ``devices``, replica r is computed on ``devices[r]`` (the same
    arithmetic, side by side, so four replicas take the time of one) and
    the mean is taken on ``devices[0]``."""
    n = images_u8.shape[0] // replicas
    fn = jax.jit(jax.value_and_grad(lambda p, x, y, k: replica_loss(p, x, y, k, cfg, quant)))
    parts = []
    for r in range(replicas):
        args = (params, images_u8[r * n:(r + 1) * n], labels[r * n:(r + 1) * n], jax.random.fold_in(step_key, r))
        if devices:
            args = jax.device_put(args, devices[r % len(devices)])
        parts.append(fn(*args))
    if devices:
        parts = jax.device_put(parts, devices[0])
    total = sum(l for l, _ in parts) / replicas
    if not exchange:
        return total, parts[0][1]
    return total, jax.tree.map(lambda *g: sum(g) / replicas, *[g for _, g in parts])


def sgd_init(params):
    return {k: jnp.zeros(p.shape, jnp.float32) for k, p in params.items()}


def _sgd(params, grads, trace, opt):
    lr, mu, wd = opt
    new_p, new_t = {}, {}
    for k, p in params.items():
        t = mu * trace[k] + grads[k] + wd * p
        new_t[k] = t
        new_p[k] = p - lr * t
    return new_p, new_t


_sgd_jit = jax.jit(_sgd, static_argnums=3)


def sgd_step(params, grads, trace, opt: Mapping[str, float]):
    """torch.optim.SGD: decay joins the gradient before the momentum."""
    return _sgd_jit(params, grads, trace, (opt["learning_rate"], opt["momentum"], opt["weight_decay"]))
