"""Model zoo and registry.

The reference's zoo is one file exporting one factory
(``master/part1/model.py:49-50``). Here: the full VGG table it defines
plus the ResNet family the benchmark targets, behind a string registry
so configs/CLI select models by name. ``tiny_cnn`` exists for fast CI on
the forced-host CPU mesh.
"""

from __future__ import annotations

from typing import Any, Callable

import flax.linen as nn
import jax.numpy as jnp

from cs744_pytorch_distributed_tutorial_tpu.models.resnet import (
    ResNet,
    resnet18,
    resnet34,
    resnet50,
)
from cs744_pytorch_distributed_tutorial_tpu.models.moe import MoEFFN, moe_aux_loss
from cs744_pytorch_distributed_tutorial_tpu.models.transformer import (
    TransformerLM,
    stack_block_params,
    transformer_lm,
    unstack_block_params,
)
from cs744_pytorch_distributed_tutorial_tpu.models.vgg import (
    VGG,
    VGG_CFGS,
    vgg11,
    vgg13,
    vgg16,
    vgg19,
)
from cs744_pytorch_distributed_tutorial_tpu.models.hf_interop import (
    deepseek_v2_model_config,
    gpt2_model_config,
    keye_model_config,
    llama_model_config,
    longcat_flash_model_config,
    mellum_model_config,
    minicpm_sala_model_config,
    model_config_from_hf,
    lm_params_from_hf_gpt2,
    lm_params_from_hf_llama,
)
from cs744_pytorch_distributed_tutorial_tpu.models.torch_interop import (
    torch_state_dict_from_vgg_variables,
    vgg_variables_from_torch_state_dict,
)
from cs744_pytorch_distributed_tutorial_tpu.models.vit import (
    ViT,
    vit_small,
    vit_tiny,
    vit_wide_p8,
)


class TinyCNN(nn.Module):
    """Small conv net with the same structural elements as VGG
    (conv+BN+ReLU, pool, linear head) for fast tests."""

    num_classes: int = 10
    dtype: Any = jnp.float32
    bn_axis: str | None = None  # SyncBN mesh axis; None = per-replica BN

    @nn.compact
    def __call__(self, x: jnp.ndarray, *, train: bool = False) -> jnp.ndarray:
        x = x.astype(self.dtype)
        for feat in (8, 16):
            x = nn.Conv(feat, (3, 3), padding="SAME", dtype=self.dtype)(x)
            x = nn.BatchNorm(use_running_average=not train, momentum=0.9,
                             epsilon=1e-5, dtype=self.dtype,
                             axis_name=self.bn_axis)(x)
            x = nn.relu(x)
            x = nn.max_pool(x, (2, 2), strides=(2, 2))
        x = x.reshape((x.shape[0], -1))
        x = nn.Dense(self.num_classes, dtype=self.dtype)(x)
        return x.astype(jnp.float32)


def tiny_cnn(**kw: Any) -> TinyCNN:
    return TinyCNN(**kw)


MODEL_REGISTRY: dict[str, Callable[..., nn.Module]] = {
    "vgg11": vgg11,
    "vgg13": vgg13,
    "vgg16": vgg16,
    "vgg19": vgg19,
    "resnet18": resnet18,
    "resnet34": resnet34,
    "resnet50": resnet50,
    "vit_tiny": vit_tiny,
    "vit_small": vit_small,
    "vit_wide_p8": vit_wide_p8,
    "tiny_cnn": tiny_cnn,
}
# TransformerLM is deliberately NOT in MODEL_REGISTRY: the registry's
# contract is image classifiers constructed as f(num_classes=, dtype=)
# by the CIFAR Trainer; the LM family is driven by train/lm.py's
# LMTrainer instead.


def get_model(name: str, **kw: Any) -> nn.Module:
    try:
        factory = MODEL_REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown model {name!r}; choose from {sorted(MODEL_REGISTRY)}"
        ) from None
    return factory(**kw)


__all__ = [
    "MODEL_REGISTRY",
    "get_model",
    "MoEFFN",
    "moe_aux_loss",
    "ResNet",
    "TinyCNN",
    "TransformerLM",
    "transformer_lm",
    "stack_block_params",
    "unstack_block_params",
    "ViT",
    "vit_small",
    "vit_tiny",
    "vit_wide_p8",
    "VGG",
    "VGG_CFGS",
    "resnet18",
    "resnet34",
    "resnet50",
    "tiny_cnn",
    "deepseek_v2_model_config",
    "gpt2_model_config",
    "keye_model_config",
    "longcat_flash_model_config",
    "mellum_model_config",
    "minicpm_sala_model_config",
    "model_config_from_hf",
    "llama_model_config",
    "lm_params_from_hf_gpt2",
    "lm_params_from_hf_llama",
    "torch_state_dict_from_vgg_variables",
    "vgg_variables_from_torch_state_dict",
    "vgg11",
    "vgg13",
    "vgg16",
    "vgg19",
]
