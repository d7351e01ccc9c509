"""Latent attention (MLA) and the two-attention shortcut-MoE layer
(models/latent.py) against the plain reference
(perfbench/reference/longcat_flash.py) on seeded weights: float32,
``mode="train"``, keys and values built a head on both sides; and every
combination that is not built raises with its reason.

Tolerance 1e-4 on logits of magnitude ~1: float32 on the CPU, the two
sides differing in the order of their sums only.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cs744_pytorch_distributed_tutorial_tpu.models import (
    TransformerLM,
    longcat_flash_model_config,
    model_config_from_hf,
)
from cs744_pytorch_distributed_tutorial_tpu.models.latent import (
    LatentAttention,
    LatentDims,
    rope_interleaved,
)
from cs744_pytorch_distributed_tutorial_tpu.serve import ServeConfig, ServingEngine
from perfbench.reference import longcat_flash as R

from longcat_tiny import MAX_LEN, build, model_kwargs, tiny_config


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config()
    return (cfg, *build(cfg))


def test_the_latent_attention_against_the_reference_s(tiny):
    """One ``LatentAttention`` over a normalised input against the
    reference's ``attention`` and output projection, with the first
    sublayer's weights."""
    cfg, model, params, flat = tiny
    p = params["block_0"]["attn_0"]
    flat_p = {k[len("block_0/attn_0/"):]: v for k, v in flat.items() if k.startswith("block_0/attn_0/")}
    x = jax.random.normal(jax.random.key(3), (1, 40, 64))
    got = LatentAttention(
        num_heads=4, dims=model.latent, rope_base=model.rope_base, norm_eps=model.norm_eps
    ).apply({"params": p}, x)[0]
    heads = R.attention(flat_p, x[0], jnp.arange(40), cfg)
    want = heads.reshape(40, -1) @ flat_p["attn_out/kernel"]
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4


@pytest.mark.parametrize("length", [1, 17, 40])
def test_the_whole_model_s_logits_against_the_reference_s(tiny, length):
    cfg, model, params, flat = tiny
    toks = np.asarray(jax.random.randint(jax.random.key(length), (length,), 0, 256))
    got = model.apply({"params": params}, toks[None])[0]
    want = R.forward(flat, toks, cfg)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4
    assert float(jnp.mean(jnp.abs(want))) > 0.1  # not a comparison of zeros


@pytest.mark.parametrize("fault", R.FAULTS)
def test_every_planted_fault_moves_the_reference_s_logits(tiny, fault):
    """What the benchmark plants to set its limits is a different
    function at this size too (else a fault could pass as sound)."""
    cfg, _, _, flat = tiny
    toks = np.asarray(jax.random.randint(jax.random.key(1), (40,), 0, 256))
    assert float(jnp.max(jnp.abs(R.forward(flat, toks, cfg, fault=fault) - R.forward(flat, toks, cfg)))) > 0.05


def test_rope_pairs_neighbours_and_stores_them_de_interleaved():
    """``rope_interleaved`` turns the pairs (2j, 2j+1) and returns the
    even dimensions first: a permutation of the reference's in-place
    rotation, so q . k is the interleaved one's."""
    x = jax.random.normal(jax.random.key(0), (1, 9, 2, 8))
    pos = jnp.arange(9)
    got = rope_interleaved(x, pos, 100.0)[0]
    want = R._rope_pairs(x[0], pos, 100.0)
    assert float(jnp.max(jnp.abs(got - jnp.concatenate([want[..., 0::2], want[..., 1::2]], -1)))) < 1e-6
    # and the half-split pairing (apply_rope on the vector as it is) is another function
    assert float(jnp.max(jnp.abs(R._rope_pairs(x[0], pos, 100.0, half_split=True) - want))) > 0.1


def test_the_builder_reads_the_published_keys():
    kw = model_kwargs({**tiny_config(), "q_lora_rank": 16})
    assert kw["latent"] == LatentDims(16, 32, 16, 8, 16, scale_q=2.0, scale_kv=2.0 ** 0.5)
    assert (kw["num_experts"], kw["moe_held_experts"], kw["moe_zero_experts"], kw["moe_top_k"]) == (8, (0, 1), 4, 3)
    assert (kw["d_ff"], kw["dense_d_ff"], kw["moe_routed_scale"], kw["moe_renormalize"]) == (32, 96, 6.0, False)
    assert kw["max_seq_len"] == MAX_LEN and kw["moe_choice_bias"] is True
    # the published file alone holds every expert; nothing but the
    # argument says which a chip holds
    whole = longcat_flash_model_config({**tiny_config(held=8), "published": {"n_routed_experts": 64}})
    assert whole["moe_held_experts"] is None and whole["num_experts"] == 8
    assert model_config_from_hf(tiny_config(held=8), held_experts=(5, 2))["moe_held_experts"] == (5, 2)
    with pytest.raises(TypeError, match="held_experts"):
        model_config_from_hf({"model_type": "mellum"}, held_experts=(0,))
    with pytest.raises(ValueError, match="zero_expert_type"):
        longcat_flash_model_config({**tiny_config(), "zero_expert_type": "copy"})
    with pytest.raises(ValueError, match="rope_scaling"):
        longcat_flash_model_config({**tiny_config(), "rope_scaling": {"rope_type": "yarn"}})


# ---- what is not built raises, with its reason ---------------------------------

@pytest.mark.parametrize("overrides, reason", [
    (dict(attention_impl="flash"), "one head width"),
    (dict(quant_kv_cache=True), "no int8 rows"),
    (dict(quant_dense=True), "float kernels"),
    (dict(tensor_axis="model", tensor_axis_size=2), "do not shard the pool"),
    (dict(scan_layers=True), "built unrolled"),
    (dict(layer_types=("full_attention", "sliding_attention"), window=8), "no window"),
    (dict(indexer_heads=2, sparse_topk=4), "no indexer"),
    (dict(expert_axis="data", expert_axis_size=2), "moe_held_experts"),
    (dict(dense_d_ff=None), "two dense MLPs"),
    (dict(norm="layernorm"), "RMSNorm"),
])
def test_each_unbuilt_combination_raises_with_its_reason(overrides, reason):
    model = TransformerLM(**{**model_kwargs(tiny_config()), **overrides})
    with pytest.raises(ValueError, match=reason):
        model.init(jax.random.key(0), jnp.zeros((1, 4), jnp.int32))


@pytest.mark.parametrize("mode", ["prefill", "decode"])
def test_the_dense_cache_modes_raise(tiny, mode):
    _, model, params, _ = tiny
    with pytest.raises(ValueError, match="dense cache holds keys and values a head"):
        model.apply(
            {"params": params}, jnp.zeros((1, 4), jnp.int32), mode=mode,
            decode_pos=jnp.zeros((), jnp.int32), mutable=["cache"],
        )


def test_the_engine_refuses_the_one_shot_prefill(tiny):
    _, model, params, _ = tiny
    with pytest.raises(ValueError, match="served by chunks"):
        ServingEngine(model, params, ServeConfig(num_slots=2, page_size=8, num_pages=9, max_pages_per_slot=4))


def test_the_share_s_options_belong_to_the_shortcut_layer():
    model = TransformerLM(
        vocab_size=32, num_layers=1, num_heads=2, d_model=16, d_ff=32, num_experts=4,
        moe_dispatch="dropless", moe_held_experts=(0, 1), attention_impl="dense",
    )
    with pytest.raises(ValueError, match="built in the shortcut-MoE layer"):
        model.init(jax.random.key(0), jnp.zeros((1, 4), jnp.int32))
