"""ResNet-{18,34,50} — the benchmark model family.

The reference's only model is VGG-11, but the driver's scored metric is
CIFAR-10 ResNet-18 samples/sec/chip and ResNet-50/ImageNet scale-out
(``BASELINE.json``; SURVEY §6 notes the build needs both). Standard
pre-activation-free ("v1.5") residual networks, written NHWC for the
TPU's native conv layout, with a ``dtype`` knob for bfloat16 MXU compute
(params/BN stats stay float32).

Two stems:
- ``cifar_stem=True`` (default for 32x32): single 3x3 conv, no maxpool —
  the standard CIFAR ResNet adaptation;
- ``cifar_stem=False``: ImageNet 7x7/stride-2 conv + 3x3 maxpool.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Sequence

import flax.linen as nn
import jax.numpy as jnp


class FastConv3x3(nn.Module):
    """3x3 SAME conv whose backward runs the Pallas wgrad kernel
    (``ops/fused_conv.py``) instead of XLA's wgrad emitter — the scored
    training step's hottest backward ops. Parameter name/shape match
    ``nn.Conv`` (kernel [3,3,C,K], HWIO), so checkpoints and param-tree
    tests are oblivious to which implementation produced them."""

    features: int
    strides: int = 1
    dtype: Any = jnp.float32
    interpret: bool = False

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        from cs744_pytorch_distributed_tutorial_tpu.ops.fused_conv import conv3x3

        kernel = self.param(
            "kernel",
            nn.initializers.lecun_normal(),
            (3, 3, x.shape[-1], self.features),
            jnp.float32,
        ).astype(self.dtype)
        return conv3x3(
            x.astype(self.dtype), kernel, self.strides, self.interpret
        )


class BasicBlock(nn.Module):
    """Two 3x3 convs + identity/projection shortcut (ResNet-18/34)."""

    features: int
    strides: int = 1
    dtype: Any = jnp.float32
    bn_axis: str | None = None
    fast_conv: bool = False
    kernel_interpret: bool = False

    def _conv3(self, feats: int, strides: int, x, name: str,
               min_ch: int = 128, max_ch: int = 256):
        """3x3 conv; routes to the Pallas-backward FastConv3x3 where it
        wins (stride 1, channels wide enough that the kernel's dense
        layout matches XLA's choice — below 128 XLA lays activations out
        batch-minor and a relayout copy eats the gain — and narrow
        enough that the k-tiled accumulator still streams well; the
        512-channel 4x4 stage measured 3x slower than XLA's emitter).
        Explicit ``name`` keeps the param tree identical to the nn.Conv
        auto-naming, so checkpoints don't care which path produced them."""
        if (self.fast_conv and strides == 1
                and min_ch <= x.shape[-1] <= max_ch
                and min_ch <= feats <= max_ch):
            return FastConv3x3(feats, strides, dtype=self.dtype,
                               interpret=self.kernel_interpret, name=name)(x)
        return nn.Conv(feats, (3, 3), strides=(strides, strides),
                       padding="SAME", use_bias=False, dtype=self.dtype,
                       name=name)(x)

    @nn.compact
    def __call__(self, x: jnp.ndarray, *, train: bool = False) -> jnp.ndarray:
        norm = partial(
            nn.BatchNorm, use_running_average=not train, momentum=0.9,
            epsilon=1e-5, dtype=self.dtype, axis_name=self.bn_axis,
        )

        residual = x
        y = self._conv3(self.features, self.strides, x, "Conv_0")
        y = norm()(y)
        y = nn.relu(y)
        y = self._conv3(self.features, 1, y, "Conv_1")
        y = norm(scale_init=nn.initializers.zeros)(y)  # zero-init last BN gamma

        if residual.shape != y.shape:
            residual = nn.Conv(self.features, (1, 1),
                               strides=(self.strides, self.strides),
                               use_bias=False, dtype=self.dtype,
                               name="Conv_2")(residual)
            residual = norm()(residual)
        return nn.relu(y + residual)


class BottleneckBlock(nn.Module):
    """1x1 -> 3x3 -> 1x1 bottleneck with 4x expansion (ResNet-50+)."""

    features: int
    strides: int = 1
    dtype: Any = jnp.float32
    bn_axis: str | None = None
    fast_conv: bool = False  # accepted for block-interface parity; the
    # bottleneck's 3x3 sits between 1x1s whose layouts XLA reshuffles
    # freely, so the Pallas wgrad routing currently targets BasicBlock.
    kernel_interpret: bool = False

    @nn.compact
    def __call__(self, x: jnp.ndarray, *, train: bool = False) -> jnp.ndarray:
        norm = partial(
            nn.BatchNorm, use_running_average=not train, momentum=0.9,
            epsilon=1e-5, dtype=self.dtype, axis_name=self.bn_axis,
        )
        conv = partial(nn.Conv, use_bias=False, dtype=self.dtype)

        residual = x
        y = conv(self.features, (1, 1))(x)
        y = norm()(y)
        y = nn.relu(y)
        y = conv(self.features, (3, 3), strides=(self.strides, self.strides),
                 padding="SAME")(y)
        y = norm()(y)
        y = nn.relu(y)
        y = conv(self.features * 4, (1, 1))(y)
        y = norm(scale_init=nn.initializers.zeros)(y)

        if residual.shape != y.shape:
            residual = conv(self.features * 4, (1, 1),
                            strides=(self.strides, self.strides))(residual)
            residual = norm()(residual)
        return nn.relu(y + residual)


class ResNet(nn.Module):
    stage_sizes: Sequence[int]
    block: Callable[..., nn.Module]
    num_classes: int = 10
    cifar_stem: bool = True
    dtype: Any = jnp.float32
    bn_axis: str | None = None  # SyncBN mesh axis; None = per-replica BN
    fast_conv: bool = False  # Pallas wgrad backward for wide 3x3 convs
    # Run that kernel through the Pallas interpreter (a mesh that is not
    # TPU — parallel/mesh.py::interpret_kernels decides, as for flash).
    kernel_interpret: bool = False

    @nn.compact
    def __call__(self, x: jnp.ndarray, *, train: bool = False) -> jnp.ndarray:
        x = x.astype(self.dtype)
        norm = partial(
            nn.BatchNorm, use_running_average=not train, momentum=0.9,
            epsilon=1e-5, dtype=self.dtype, axis_name=self.bn_axis,
        )
        if self.cifar_stem:
            x = nn.Conv(64, (3, 3), padding="SAME", use_bias=False,
                        dtype=self.dtype)(x)
            x = norm()(x)
            x = nn.relu(x)
        else:
            x = nn.Conv(64, (7, 7), strides=(2, 2), padding=[(3, 3), (3, 3)],
                        use_bias=False, dtype=self.dtype)(x)
            x = norm()(x)
            x = nn.relu(x)
            x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=[(1, 1), (1, 1)])

        for stage, n_blocks in enumerate(self.stage_sizes):
            for b in range(n_blocks):
                strides = 2 if stage > 0 and b == 0 else 1
                x = self.block(features=64 * 2 ** stage, strides=strides,
                               dtype=self.dtype, bn_axis=self.bn_axis,
                               fast_conv=self.fast_conv,
                               kernel_interpret=self.kernel_interpret,
                               )(x, train=train)

        x = jnp.mean(x, axis=(1, 2))  # global average pool
        x = nn.Dense(self.num_classes, dtype=self.dtype)(x)
        return x.astype(jnp.float32)


def resnet18(**kw: Any) -> ResNet:
    return ResNet(stage_sizes=(2, 2, 2, 2), block=BasicBlock, **kw)


def resnet34(**kw: Any) -> ResNet:
    return ResNet(stage_sizes=(3, 4, 6, 3), block=BasicBlock, **kw)


def resnet50(**kw: Any) -> ResNet:
    return ResNet(stage_sizes=(3, 4, 6, 3), block=BottleneckBlock, **kw)
