"""The small LongCat-like configuration the latent-attention tests share:
2 layers (4 latent sublayers), d 64, 4 heads of 16 + 8 / 16 over a latent
of 32 (a cached row of 40, in a pool of 128 lanes), dense MLPs of 96, a
router of 8 routed + 4 zero-compute outputs top-3 of which experts 0-1
are held here, expert width 32; float32. It is a published-style
``config.json`` (``model_type: longcat_flash``), so the model is built by
``longcat_flash_model_config`` from the keys the benchmark's
configuration has; weights come from the benchmark's recipe
(``perfbench/weights_longcat.py``) and go to the model and to the plain
reference (``perfbench/reference/longcat_flash.py``) alike."""

import jax
import jax.numpy as jnp

from cs744_pytorch_distributed_tutorial_tpu.models import (
    TransformerLM,
    longcat_flash_model_config,
)
from perfbench import weights as W
from perfbench import weights_longcat as WL
from perfbench.work_latent_moe import as_published

MAX_LEN = 128


def tiny_config(held: int = 2, routed: int = 8, layers: int = 2) -> dict:
    return dict(
        model_type="longcat_flash", vocab_size=256, hidden_size=64,
        ffn_hidden_size=96, expert_ffn_hidden_size=32, num_layers=layers,
        num_attention_heads=4, kv_lora_rank=32, q_lora_rank=48,
        qk_rope_head_dim=8, v_head_dim=16, qk_nope_head_dim=16,
        mla_scale_q_lora=True, mla_scale_kv_lora=True,
        routed_scaling_factor=6, n_routed_experts=held,
        published=dict(n_routed_experts=routed),
        max_position_embeddings=MAX_LEN, rms_norm_eps=1e-5, rope_theta=1e4,
        zero_expert_num=4, zero_expert_type="identity", moe_topk=3,
        attention_bias=False,
        weights=dict(q_gain=0.5, router_gain=2.0, choice_bias_std=0.02),
    )


def model_kwargs(cfg: dict) -> dict:
    """The builder's kwargs for a file cut as the benchmark's is: the
    router's width from ``published``, the held ids said apart."""
    published, held = as_published(cfg)
    return longcat_flash_model_config(published, max_seq_len=MAX_LEN, held_experts=held)


def build(cfg: dict, seed: int = 5, **overrides):
    """(model, params, flat weights) of ``cfg`` in float32."""
    model = TransformerLM(**{**model_kwargs(cfg), **overrides}, dtype=jnp.float32)
    flat = WL.make_weights(cfg, seed, "float32")
    template = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    )["params"]
    return model, W.fill_tree(template, flat), flat
