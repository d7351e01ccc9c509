"""The small Mellum-like configuration the window-layer tests share: two
periods of three sliding-window layers and a full one, d 64, 4 heads
over 2 KV heads, 8 gated experts top-2 of width 32, window 8; YaRN on
the full layers with its parameters scaled down so that the blend lies
inside the head (``low`` 0, ``high`` 3 of 8 frequencies at head width
16); float32. It is a published-style ``config.json`` (``model_type:
mellum``), so the model is built by ``mellum_model_config`` from the
keys the benchmark's configuration has; weights come from the
benchmark's recipe (``perfbench/weights_mellum2.py``) and go to the model
and to the plain reference (``perfbench/reference/mellum2.py``) alike."""

import jax
import jax.numpy as jnp

from cs744_pytorch_distributed_tutorial_tpu.models import (
    TransformerLM,
    mellum_model_config,
)
from perfbench import weights as W
from perfbench import weights_mellum2 as WM

MAX_LEN = 128
WINDOW = 8
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]


def tiny_config(head_dim: int = 16, periods: int = 2) -> dict:
    layers = 4 * periods
    return dict(
        model_type="mellum", vocab_size=256, num_hidden_layers=layers,
        layer_types=PERIOD * periods, mlp_layer_types=["sparse"] * layers,
        num_attention_heads=4, num_key_value_heads=2, head_dim=head_dim,
        hidden_size=64, moe_intermediate_size=32, num_experts=8,
        num_experts_per_tok=2, norm_topk_prob=True, rms_norm_eps=1e-6,
        attention_bias=False, tie_word_embeddings=False,
        max_position_embeddings=MAX_LEN, sliding_window=WINDOW,
        use_sliding_window=True,
        rope_parameters=dict(
            full_attention=dict(
                rope_type="yarn", rope_theta=100.0, factor=4.0,
                original_max_position_embeddings=32, beta_fast=4,
                beta_slow=1,
            ),
            sliding_attention=dict(rope_type="default", rope_theta=100.0),
        ),
        weights=dict(qk_gain=2.0),
    )


def build(cfg: dict, seed: int = 5, **overrides):
    """(model, params, flat weights) of ``cfg`` in float32."""
    model = TransformerLM(
        **{**mellum_model_config(cfg, max_seq_len=MAX_LEN), **overrides},
        dtype=jnp.float32,
    )
    flat = WM.make_weights(cfg, seed, "float32")
    template = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    )["params"]
    return model, W.fill_tree(template, flat), flat
