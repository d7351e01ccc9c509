"""HF GPT-2 checkpoint import (models/hf_interop.py).

Pins logit parity between an ACTUAL ``transformers`` ``GPT2LMHeadModel``
(random-init from config — no download, zero egress) and the converted
``TransformerLM``, plus greedy-decode agreement and config inference.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

transformers = pytest.importorskip("transformers")
torch = pytest.importorskip("torch")

from cs744_pytorch_distributed_tutorial_tpu.models import TransformerLM  # noqa: E402
from cs744_pytorch_distributed_tutorial_tpu.models.hf_interop import (  # noqa: E402
    gpt2_model_config,
    lm_params_from_hf_gpt2,
)


@pytest.fixture(scope="module")
def hf_model():
    cfg = transformers.GPT2Config(
        vocab_size=256,
        n_positions=64,
        n_embd=128,
        n_layer=2,
        n_head=2,
        resid_pdrop=0.0,
        embd_pdrop=0.0,
        attn_pdrop=0.0,
    )
    torch.manual_seed(11)
    m = transformers.GPT2LMHeadModel(cfg)
    m.eval()
    return m


def test_config_inference(hf_model):
    cfg = gpt2_model_config(hf_model.state_dict())
    assert cfg["vocab_size"] == 256
    assert cfg["num_layers"] == 2
    assert cfg["d_model"] == 128
    assert cfg["num_heads"] == 2  # head_dim fixed at 64
    assert cfg["d_ff"] == 512
    assert cfg["max_seq_len"] == 64
    assert cfg["tie_embeddings"] and cfg["attn_bias"]
    assert cfg["norm_eps"] == 1e-5


def test_logit_parity_vs_transformers(hf_model):
    sd = hf_model.state_dict()
    model = TransformerLM(**gpt2_model_config(sd), flash_interpret=True)
    params = lm_params_from_hf_gpt2(sd)
    # The converted tree must match what the model expects, exactly.
    ref = model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))[
        "params"
    ]
    assert jax.tree_util.tree_structure(ref) == jax.tree_util.tree_structure(
        params
    ), (jax.tree_util.tree_structure(ref), jax.tree_util.tree_structure(params))
    tokens = np.random.default_rng(0).integers(0, 256, (2, 16))
    logits = np.asarray(
        model.apply({"params": params}, jnp.asarray(tokens, jnp.int32))
    )
    with torch.no_grad():
        hf_logits = hf_model(torch.from_numpy(tokens)).logits.numpy()
    np.testing.assert_allclose(logits, hf_logits, rtol=1e-4, atol=1e-4)


def test_greedy_decode_matches_transformers_generate(hf_model):
    from cs744_pytorch_distributed_tutorial_tpu.infer import make_generator

    sd = hf_model.state_dict()
    model = TransformerLM(**gpt2_model_config(sd), flash_interpret=True)
    params = lm_params_from_hf_gpt2(sd)
    prompt = np.random.default_rng(1).integers(0, 256, (1, 8))
    gen = make_generator(model, max_new_tokens=6, temperature=0.0)
    ours = np.asarray(
        gen(params, jnp.asarray(prompt, jnp.int32), jax.random.key(0))
    )
    with torch.no_grad():
        hf = hf_model.generate(
            torch.from_numpy(prompt),
            max_new_tokens=6,
            do_sample=False,
            pad_token_id=0,
        ).numpy()[:, 8:]
    np.testing.assert_array_equal(ours, hf)


def test_non_gpt2_state_dict_rejected():
    with pytest.raises(ValueError, match="no transformer.h"):
        lm_params_from_hf_gpt2({"transformer.wte.weight": np.zeros((8, 4))})


def test_bf16_checkpoint_converts(hf_model):
    sd = {k: v.to(torch.bfloat16) if v.is_floating_point() else v
          for k, v in hf_model.state_dict().items()}
    params = lm_params_from_hf_gpt2(sd)
    assert params["tok_embed"]["embedding"].dtype == np.float32


def test_custom_head_count_override(hf_model):
    sd = hf_model.state_dict()
    cfg = gpt2_model_config(sd, num_heads=4)
    assert cfg["num_heads"] == 4
    with pytest.raises(ValueError, match="does not divide"):
        gpt2_model_config(sd, num_heads=3)
    with pytest.raises(ValueError, match="no transformer.h"):
        gpt2_model_config({"transformer.wte.weight": np.zeros((8, 4))})


from cs744_pytorch_distributed_tutorial_tpu.models.hf_interop import (  # noqa: E402
    llama_model_config,
    lm_params_from_hf_llama,
)


@pytest.fixture(scope="module")
def hf_llama():
    cfg = transformers.LlamaConfig(
        vocab_size=128,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        max_position_embeddings=64,
        rope_theta=10000.0,
        attention_dropout=0.0,
    )
    torch.manual_seed(13)
    m = transformers.LlamaForCausalLM(cfg)
    m.eval()
    return m


def test_llama_config_inference(hf_llama):
    cfg = llama_model_config(
        hf_llama.state_dict(), num_heads=4, max_seq_len=64
    )
    assert cfg["vocab_size"] == 128 and cfg["d_model"] == 64
    assert cfg["num_layers"] == 2 and cfg["num_kv_heads"] == 2
    assert cfg["d_ff"] == 128
    assert cfg["norm"] == "rmsnorm" and cfg["mlp"] == "swiglu"
    assert cfg["use_rope"] and not cfg["tie_embeddings"]
    with pytest.raises(ValueError, match="wrong num_heads"):
        llama_model_config(hf_llama.state_dict(), num_heads=1)
    with pytest.raises(ValueError, match="no model.layers"):
        llama_model_config({"model.embed_tokens.weight": np.zeros((4, 4))},
                           num_heads=2)


def test_llama_logit_parity_vs_transformers(hf_llama):
    sd = hf_llama.state_dict()
    model = TransformerLM(
        **llama_model_config(sd, num_heads=4, max_seq_len=64),
        flash_interpret=True,
    )
    params = lm_params_from_hf_llama(sd)
    ref = model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))[
        "params"
    ]
    assert jax.tree_util.tree_structure(ref) == jax.tree_util.tree_structure(
        params
    )
    tokens = np.random.default_rng(2).integers(0, 128, (2, 16))
    logits = np.asarray(
        model.apply({"params": params}, jnp.asarray(tokens, jnp.int32))
    )
    with torch.no_grad():
        hf_logits = hf_llama(torch.from_numpy(tokens)).logits.numpy()
    np.testing.assert_allclose(logits, hf_logits, rtol=2e-4, atol=2e-4)


def test_llama_tied_embeddings_checkpoint(hf_llama):
    # safetensors drops tensors shared with embed_tokens: simulate a
    # tied checkpoint by removing lm_head.weight.
    sd = {k: v for k, v in hf_llama.state_dict().items()
          if k != "lm_head.weight"}
    cfg = llama_model_config(sd, num_heads=4, max_seq_len=64)
    assert cfg["tie_embeddings"] is True
    params = lm_params_from_hf_llama(sd)
    assert "lm_head" not in params
    model = TransformerLM(**cfg, flash_interpret=True)
    ref = model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))[
        "params"
    ]
    assert jax.tree_util.tree_structure(ref) == jax.tree_util.tree_structure(
        params
    )


def test_llama_greedy_decode_matches_transformers(hf_llama):
    from cs744_pytorch_distributed_tutorial_tpu.infer import make_generator

    sd = hf_llama.state_dict()
    model = TransformerLM(
        **llama_model_config(sd, num_heads=4, max_seq_len=64),
        flash_interpret=True,
    )
    params = lm_params_from_hf_llama(sd)
    prompt = np.random.default_rng(3).integers(0, 128, (1, 8))
    gen = make_generator(model, max_new_tokens=6, temperature=0.0)
    ours = np.asarray(
        gen(params, jnp.asarray(prompt, jnp.int32), jax.random.key(0))
    )
    with torch.no_grad():
        hf = hf_llama.generate(
            torch.from_numpy(prompt),
            max_new_tokens=6,
            do_sample=False,
            pad_token_id=0,
        ).numpy()[:, 8:]
    np.testing.assert_array_equal(ours, hf)


def test_keye_model_config_maps_the_published_keys():
    """The language model of a KeyeVL2 config.json, read from its keys
    alone: the fields TransformerLM needs for the layer (GQA with its
    own head width, q/k norm, gated dropless experts, the indexer), and
    a model built from them has the parameter tree the layer implies."""
    import jax
    import jax.numpy as jnp

    from cs744_pytorch_distributed_tutorial_tpu.models import (
        TransformerLM,
        keye_model_config,
    )

    hf = {
        "model_type": "KeyeVL2", "vocab_size": 96, "hidden_size": 32,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16,  # 4 x 16 != hidden 32
        "moe_intermediate_size": 24, "intermediate_size": 96,
        "num_experts": 8, "num_experts_per_tok": 2, "norm_topk_prob": True,
        "mlp_only_layers": [], "decoder_sparse_step": 1,
        "rms_norm_eps": 1e-6, "rope_theta": 10000000,
        "max_position_embeddings": 4096, "tie_word_embeddings": False,
        "attention_bias": False, "use_sliding_window": False,
        "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 2,
                      "indexer_num_kv_heads": 1, "topk": 16},
    }
    cfg = keye_model_config(hf, max_seq_len=64)
    assert cfg["head_dim"] == 16 and cfg["num_kv_heads"] == 2 and cfg["qk_norm"]
    assert cfg["d_ff"] == 24 and cfg["num_experts"] == 8 and cfg["moe_top_k"] == 2
    assert cfg["mlp"] == "swiglu" and cfg["moe_dispatch"] == "dropless" and not cfg["moe_bias"]
    assert (cfg["indexer_heads"], cfg["indexer_head_dim"], cfg["sparse_topk"]) == (2, 8, 16)
    assert cfg["rope_base"] == 1e7 and cfg["use_rope"] and cfg["norm"] == "rmsnorm"
    assert cfg["max_seq_len"] == 64 and keye_model_config(hf)["max_seq_len"] == 4096
    model = TransformerLM(**cfg)
    params = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    )["params"]
    attn, moe = params["block_1"]["attn"], params["block_1"]["moe"]
    assert attn["q"]["kernel"].shape == (32, 64) and attn["k"]["kernel"].shape == (32, 32)
    assert attn["attn_out"]["kernel"].shape == (64, 32)
    assert attn["q_norm"]["scale"].shape == (16,) and attn["idx_q"]["kernel"].shape == (32, 16)
    assert attn["idx_k"]["kernel"].shape == (32, 8) and attn["idx_w"]["kernel"].shape == (32, 2)
    assert {k: v.shape for k, v in moe.items() if k != "router"} == {
        "w_in": (8, 32, 24), "w_gate": (8, 32, 24), "w_out": (8, 24, 32),
    }
    assert "pos_embed" not in params and "lm_head" in params
    for key, bad in (("mlp_only_layers", [0]), ("norm_topk_prob", False), ("attention_bias", True)):
        with pytest.raises(ValueError):
            keye_model_config({**hf, key: bad})
