"""The small Keye-like configuration the sparse-attention and serving
tests share: 2 layers, d 64, 4 heads of 16 over 2 KV heads, 8 gated
experts top-2 of width 32, an indexer of 2 heads of 8 keeping 16 tokens;
float32. Weights come from the benchmark's recipe
(``perfbench/weights_keye.py``) and go to the model and to the plain
reference (``perfbench/reference/keye.py``) alike."""

import jax
import jax.numpy as jnp

from cs744_pytorch_distributed_tutorial_tpu.models import (
    TransformerLM,
    keye_model_config,
)
from perfbench import weights as W
from perfbench import weights_keye as WK

MAX_LEN = 128


def tiny_config(topk: int = 16) -> dict:
    return dict(
        vocab_size=256, num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, hidden_size=64,
        moe_intermediate_size=32, max_position_embeddings=MAX_LEN,
        rope_theta=1e7, rms_norm_eps=1e-6, num_experts=8,
        num_experts_per_tok=2, norm_topk_prob=True,
        tie_word_embeddings=False,
        sa_config=dict(
            indexer_head_dim=8, indexer_num_heads=2, indexer_num_kv_heads=1,
            topk=topk,
        ),
        weights=dict(qk_gain=2.0),
    )


def build(cfg: dict, seed: int = 5, **overrides):
    """(model, params, flat weights) of ``cfg`` in float32."""
    model = TransformerLM(
        **{**keye_model_config(cfg, max_seq_len=MAX_LEN), **overrides},
        dtype=jnp.float32,
    )
    flat = WK.make_weights(cfg, seed, "float32")
    template = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    )["params"]
    if model.indexer_heads == 0:  # the dense twin: no indexer leaves
        flat_m = {k: v for k, v in flat.items() if "/idx_" not in k}
    else:
        flat_m = flat
    return model, W.fill_tree(template, flat_m), flat
