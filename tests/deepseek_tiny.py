"""The small DeepSeek-V2-like configuration the tests share: 3 layers
(the first dense), d 64, 4 heads of 16 + 8 / 16 over a latent of 32 (a
cached row of 40, in a pool of 128 lanes), a dense MLP of 96, a router
of 16 routed experts in 4 groups of 4, the top 2 groups kept and the top
3 experts among them, weights times 16 not renormalised, experts 0-3
(group 0) held here, expert width 16 and two shared experts; YaRN on the
rope dimensions as DeepSeek-V2 publishes it (factor 40 over 32 original
positions, mscale 0.707 twice); float32. It is a published-style
``config.json`` (``model_type: deepseek_v2``), so the model is built by
``deepseek_v2_model_config`` from the keys the benchmark's configuration
has; weights come from the benchmark's recipe
(``perfbench/weights_deepseek_v2.py``) and go to the model and to the
plain reference (``perfbench/reference/deepseek_v2.py``) alike."""

import jax
import jax.numpy as jnp

from cs744_pytorch_distributed_tutorial_tpu.models import (
    TransformerLM,
    deepseek_v2_model_config,
)
from perfbench import weights as W
from perfbench import weights_deepseek_v2 as WD
from perfbench.work_deepseek_v2 import as_published

MAX_LEN = 128
YARN = dict(
    type="yarn", factor=40, original_max_position_embeddings=32, beta_fast=32, beta_slow=1,
    mscale=0.707, mscale_all_dim=0.707,
)


def tiny_config(held: int = 4, routed: int = 16, layers: int = 3) -> dict:
    return dict(
        model_type="deepseek_v2", vocab_size=256, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=16, num_hidden_layers=layers, num_attention_heads=4,
        num_key_value_heads=4, kv_lora_rank=32, q_lora_rank=48, qk_rope_head_dim=8,
        v_head_dim=16, qk_nope_head_dim=16, first_k_dense_replace=1, moe_layer_freq=1,
        n_routed_experts=held, published=dict(n_routed_experts=routed), n_shared_experts=2,
        n_group=4, topk_group=2, num_experts_per_tok=3, topk_method="group_limited_greedy",
        norm_topk_prob=False, routed_scaling_factor=16, scoring_func="softmax",
        max_position_embeddings=MAX_LEN, rms_norm_eps=1e-6, rope_theta=1e4, rope_scaling=dict(YARN),
        attention_bias=False, hidden_act="silu", tie_word_embeddings=False,
        weights=dict(q_gain=1.25, router_gain=2.0, held_gain=2.0),
    )


def model_kwargs(cfg: dict) -> dict:
    """The builder's kwargs for a file cut as the benchmark's is: the
    router's width from ``published``, the held ids said apart."""
    published, held = as_published(cfg)
    return deepseek_v2_model_config(published, max_seq_len=MAX_LEN, held_experts=held)


def build(cfg: dict, seed: int = 5, **overrides):
    """(model, params, flat weights) of ``cfg`` in float32."""
    model = TransformerLM(**{**model_kwargs(cfg), **overrides}, dtype=jnp.float32)
    flat = WD.make_weights(cfg, seed, "float32")
    template = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    )["params"]
    return model, W.fill_tree(template, flat), flat
