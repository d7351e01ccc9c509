"""The small MiniCPM-SALA-like configuration the tests share: a
published-style ``config.json`` (``model_type: minicpm_sala``) of 8
layers, of which 4 are kept here (``kept_layers`` 0, 1, 4, 5: a
block-sparse layer and a lightning layer, twice), d 64, 4 query heads
of 32 over 2 KV heads, a SwiGLU of 96, vocabulary 256, the muP scalars
as published; the selection scaled down so that it selects within a
few hundred positions: kernels of 8 at stride 4 (the page size the
tests serve with), blocks of 8, a window of 16, the top 6 blocks, dense
below 48. Float32. The model is built by ``minicpm_sala_model_config``
from these keys; weights come from the benchmark's recipe
(``perfbench/weights_minicpm_sala.py``) and go to the model and to the
plain reference (``perfbench/reference/minicpm_sala.py``) alike."""

import jax
import jax.numpy as jnp

from cs744_pytorch_distributed_tutorial_tpu.models import (
    TransformerLM,
    minicpm_sala_model_config,
)
from perfbench import weights as W
from perfbench import weights_minicpm_sala as WS
from perfbench.work_minicpm_sala import as_published

MAX_LEN = 512
PUBLISHED_MIXERS = ["minicpm4", "lightning-attn", "lightning-attn", "minicpm4"] * 2
KEPT = [0, 1, 4, 5]
SPARSE = dict(
    kernel_size=8, kernel_stride=4, block_size=8, window_size=16, topk=6,
    init_blocks=1, dense_len=48,
)


def tiny_config(**change) -> dict:
    cfg = dict(
        model_type="minicpm_sala", vocab_size=256, hidden_size=64, intermediate_size=96,
        num_hidden_layers=len(KEPT), mixer_types=[PUBLISHED_MIXERS[i] for i in KEPT],
        published=dict(num_hidden_layers=len(PUBLISHED_MIXERS), mixer_types=PUBLISHED_MIXERS),
        kept_layers=KEPT, num_attention_heads=4, num_key_value_heads=2, head_dim=32,
        lightning_nh=4, lightning_nkv=4, lightning_head_dim=32, lightning_scale="1/sqrt(d)",
        lightning_use_rope=True, attn_use_rope=False, qk_norm=True, use_output_gate=True,
        use_output_norm=True, attn_use_output_gate=True, hidden_act="silu", attention_bias=False,
        max_position_embeddings=MAX_LEN, rms_norm_eps=1e-6, rope_theta=10000, scale_emb=12,
        scale_depth=1.4, mup_denominator=32, dim_model_base=16, tie_word_embeddings=False,
        sparse_config=dict(SPARSE), weights=dict(embed_gain=1 / 12, qk_gain=1.5, head_gain=4.0),
    )
    cfg.update(change)
    return cfg


def model_kwargs(cfg: dict) -> dict:
    """The config builder's kwargs for a file cut as the benchmark's is: the
    published layers back, the kept ones said apart."""
    published, kept = as_published(cfg)
    return minicpm_sala_model_config(published, max_seq_len=MAX_LEN, layer_ids=kept)


def build(cfg: dict, seed: int = 5, **overrides):
    """(model, params, flat weights) of ``cfg`` in float32."""
    model = TransformerLM(**{**model_kwargs(cfg), **overrides}, dtype=jnp.float32)
    flat = WS.make_weights(cfg, seed, "float32")
    template = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    )["params"]
    return model, W.fill_tree(template, flat), flat
