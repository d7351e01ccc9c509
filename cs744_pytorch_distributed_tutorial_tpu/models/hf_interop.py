"""HuggingFace GPT-2 checkpoint import — the LM switching path.

The VGG converter (``models/torch_interop.py``) moves the reference's
own model across; this moves the ecosystem's most common LM checkpoint
family: a ``transformers`` GPT-2 ``state_dict`` (``GPT2LMHeadModel``)
converts into a ``TransformerLM`` variables tree with logit parity.
No counterpart exists in the reference (its only model is conv VGG-11,
``master/part1/model.py:30-46``).

Architecture mapping (GPT-2 -> this framework's ``TransformerLM``):

- pre-LN residual blocks, learned absolute positions (``wpe``), tied
  embeddings (``lm_head = wte``) — the model is constructed via
  ``gpt2_model_config`` with ``use_rope=False, tie_embeddings=True,
  norm="layernorm", mlp="gelu"`` (HF's ``gelu_new`` is the tanh
  approximation, flax's ``nn.gelu`` default), ``norm_eps=1e-5`` (HF's
  ``layer_norm_epsilon``), and ``attn_bias=True`` (GPT-2 keeps biases
  on every projection);
- HF's fused ``c_attn`` [d, 3d] Conv1D splits column-wise into the
  separate q/k/v kernels (HF ``Conv1D.weight`` is already
  [in, out] — flax ``Dense`` kernel orientation, NO transpose);
- ``c_proj`` -> ``attn_out``; ``mlp.c_fc`` -> ``mlp_in``;
  ``mlp.c_proj`` kernel -> ``mlp_out`` + its bias -> the post-residual
  ``mlp_out_bias`` (this framework separates the row-parallel bias;
  algebraically identical placement);
- ``ln_1``/``ln_2``/``ln_f`` -> ``ln1``/``ln2``/``ln_f``;
  ``wte`` -> ``tok_embed`` (the ``attend`` path IS the tied head),
  ``wpe`` -> ``pos_embed``.

Tensors are accepted as anything ``np.asarray`` understands (torch
tensors get ``.detach().cpu()`` first) — no hard transformers/torch
dependency; the parity test builds a RANDOM-INIT ``GPT2LMHeadModel``
from a config (no download, zero egress) and pins logits to 1e-4.
"""

from __future__ import annotations

from typing import Any, Mapping, Sequence

import numpy as np


from cs744_pytorch_distributed_tutorial_tpu.models._torch_np import (
    torch_to_np as _np,
)


def _require_layout(state_dict: Mapping[str, Any], sentinel: str, family: str):
    if sentinel not in state_dict:
        raise ValueError(
            f"no {sentinel.rsplit('.0.', 1)[0]}.{{i}} blocks found — not a "
            f"{family} state_dict (expected transformers' key layout)"
        )


def gpt2_model_config(
    state_dict: Mapping[str, Any], num_heads: int | None = None
) -> dict:
    """Infer the ``TransformerLM`` constructor kwargs that match a GPT-2
    ``state_dict`` (dims read from the tensors; conventions fixed by the
    architecture). Pass to ``TransformerLM(**gpt2_model_config(sd))``,
    optionally overriding ``dtype`` / ``attention_impl``.

    ``num_heads`` is NOT recoverable from tensor shapes (the fused
    ``c_attn`` is [d, 3d] for any head count); by default the GPT-2
    family's fixed head_dim of 64 is assumed — pass ``num_heads``
    explicitly for custom-headed configs, or the converted model will
    silently attend with the wrong head grouping."""
    _require_layout(
        state_dict, "transformer.h.0.ln_1.weight", "GPT2LMHeadModel"
    )
    wte = _np(state_dict["transformer.wte.weight"])
    wpe = _np(state_dict["transformer.wpe.weight"])
    c_fc = _np(state_dict["transformer.h.0.mlp.c_fc.weight"])
    n_layers = 0
    while f"transformer.h.{n_layers}.ln_1.weight" in state_dict:
        n_layers += 1
    d_model = wte.shape[1]
    if num_heads is None:
        # GPT-2 family fixes head_dim = 64 (see docstring).
        if d_model % 64:
            raise ValueError(
                f"d_model {d_model} is not a GPT-2-family width (expected "
                "a multiple of the fixed head_dim 64); pass num_heads "
                "explicitly"
            )
        num_heads = d_model // 64
    elif d_model % num_heads:
        raise ValueError(
            f"num_heads {num_heads} does not divide d_model {d_model}"
        )
    return dict(
        vocab_size=wte.shape[0],
        num_layers=n_layers,
        num_heads=num_heads,
        d_model=d_model,
        d_ff=c_fc.shape[1],
        max_seq_len=wpe.shape[0],
        use_rope=False,
        tie_embeddings=True,
        norm="layernorm",
        mlp="gelu",
        norm_eps=1e-5,
        attn_bias=True,
        attention_impl="dense",
    )


def lm_params_from_hf_gpt2(state_dict: Mapping[str, Any]) -> dict:
    """Convert a ``GPT2LMHeadModel.state_dict()`` into the ``params``
    tree of the matching ``TransformerLM`` (see ``gpt2_model_config``).
    The tied ``lm_head.weight`` is ignored (it aliases ``wte``)."""
    _require_layout(
        state_dict, "transformer.h.0.ln_1.weight", "GPT2LMHeadModel"
    )
    params: dict = {
        "tok_embed": {"embedding": _np(state_dict["transformer.wte.weight"])},
        "pos_embed": {"embedding": _np(state_dict["transformer.wpe.weight"])},
        "ln_f": {
            "scale": _np(state_dict["transformer.ln_f.weight"]),
            "bias": _np(state_dict["transformer.ln_f.bias"]),
        },
    }
    i = 0
    while f"transformer.h.{i}.ln_1.weight" in state_dict:
        pre = f"transformer.h.{i}"
        d = _np(state_dict[f"{pre}.ln_1.weight"]).shape[0]
        ca_w = _np(state_dict[f"{pre}.attn.c_attn.weight"])  # [d, 3d]
        ca_b = _np(state_dict[f"{pre}.attn.c_attn.bias"])  # [3d]
        if ca_w.shape != (d, 3 * d):
            raise ValueError(
                f"{pre}.attn.c_attn.weight has shape {ca_w.shape}, "
                f"expected {(d, 3 * d)} — not a GPT-2 checkpoint?"
            )
        params[f"block_{i}"] = {
            "ln1": {
                "scale": _np(state_dict[f"{pre}.ln_1.weight"]),
                "bias": _np(state_dict[f"{pre}.ln_1.bias"]),
            },
            "ln2": {
                "scale": _np(state_dict[f"{pre}.ln_2.weight"]),
                "bias": _np(state_dict[f"{pre}.ln_2.bias"]),
            },
            "attn": {
                "q": {"kernel": ca_w[:, :d], "bias": ca_b[:d]},
                "k": {"kernel": ca_w[:, d : 2 * d], "bias": ca_b[d : 2 * d]},
                "v": {"kernel": ca_w[:, 2 * d :], "bias": ca_b[2 * d :]},
                "attn_out": {
                    "kernel": _np(state_dict[f"{pre}.attn.c_proj.weight"]),
                    "bias": _np(state_dict[f"{pre}.attn.c_proj.bias"]),
                },
            },
            "mlp_in": {
                "kernel": _np(state_dict[f"{pre}.mlp.c_fc.weight"]),
                "bias": _np(state_dict[f"{pre}.mlp.c_fc.bias"]),
            },
            "mlp_out": {
                "kernel": _np(state_dict[f"{pre}.mlp.c_proj.weight"]),
            },
            # This framework applies the mlp output bias AFTER the
            # (potential) tensor psum as a separate parameter — for the
            # unsharded import the placement is algebraically identical.
            "mlp_out_bias": _np(state_dict[f"{pre}.mlp.c_proj.bias"]),
        }
        i += 1
    return params


def llama_model_config(
    state_dict: Mapping[str, Any],
    num_heads: int,
    max_seq_len: int = 2048,
    rope_base: float = 10000.0,
    rms_norm_eps: float = 1e-6,
) -> dict:
    """``TransformerLM`` kwargs matching a ``transformers``
    ``LlamaForCausalLM`` ``state_dict``: RMSNorm + SwiGLU + RoPE + GQA —
    every piece maps onto this framework's llama-family block options.

    ``num_heads`` is required (llama head_dim is not recoverable from
    tensor shapes; the KV head count IS derived — from the k_proj
    width). ``max_seq_len``, ``rope_base`` and ``rms_norm_eps`` come
    from the HF config (``max_position_embeddings`` / ``rope_theta`` /
    ``rms_norm_eps``; the 1e-6 default here matches LlamaConfig's), not
    the weights. Tied-embedding checkpoints (no ``lm_head.weight`` —
    safetensors drops tensors shared with ``embed_tokens``) come out
    with ``tie_embeddings=True``."""
    _require_layout(
        state_dict, "model.layers.0.input_layernorm.weight",
        "LlamaForCausalLM",
    )
    embed = _np(state_dict["model.embed_tokens.weight"])
    d_model = embed.shape[1]
    if d_model % num_heads:
        raise ValueError(
            f"num_heads {num_heads} does not divide d_model {d_model}"
        )
    head_dim = d_model // num_heads
    kv_width = _np(state_dict["model.layers.0.self_attn.k_proj.weight"]).shape[0]
    if kv_width % head_dim:
        raise ValueError(
            f"k_proj width {kv_width} is not a multiple of head_dim "
            f"{head_dim} (d_model {d_model} / num_heads {num_heads}) — "
            "wrong num_heads?"
        )
    d_ff = _np(state_dict["model.layers.0.mlp.gate_proj.weight"]).shape[0]
    n_layers = 0
    while f"model.layers.{n_layers}.input_layernorm.weight" in state_dict:
        n_layers += 1
    return dict(
        vocab_size=embed.shape[0],
        num_layers=n_layers,
        num_heads=num_heads,
        num_kv_heads=kv_width // head_dim,
        d_model=d_model,
        d_ff=d_ff,
        max_seq_len=max_seq_len,
        use_rope=True,
        rope_base=rope_base,
        tie_embeddings="lm_head.weight" not in state_dict,
        norm="rmsnorm",
        mlp="swiglu",
        norm_eps=rms_norm_eps,
        attn_bias=False,
        attention_impl="dense",
    )


def keye_model_config(hf_config: Mapping[str, Any], max_seq_len: int | None = None) -> dict:
    """``TransformerLM`` kwargs for the language model of a
    ``KeyeVL2`` ``config.json`` (Kwai-Keye/Keye-VL-2.0-30B-A3B): RMSNorm,
    RoPE, GQA with a head width of its own and per-head q/k RMSNorm,
    gated experts on the dropless path with renormalised top-k weights
    (``norm_topk_prob``), and the sparse-attention indexer of
    ``sa_config``. Read from the published keys alone, no tensor: the
    language model only (no vision tower), text positions (the three
    position streams of ``mrope_section`` are equal for text, which is
    plain RoPE). ``max_seq_len`` defaults to
    ``max_position_embeddings``."""
    if hf_config.get("mlp_only_layers") or hf_config.get("decoder_sparse_step", 1) != 1:
        raise ValueError(
            "dense layers among the routed ones (mlp_only_layers, "
            "decoder_sparse_step != 1) are not supported: every block "
            "of TransformerLM is alike"
        )
    if not hf_config.get("norm_topk_prob", True):
        raise ValueError(
            "norm_topk_prob=false is not supported: MoEFFN renormalises "
            "the top-k weights"
        )
    if hf_config.get("attention_bias") or hf_config.get("use_sliding_window"):
        raise ValueError("attention_bias / sliding windows are not supported")
    sa = hf_config.get("sa_config") or {}
    if sa and sa.get("indexer_num_kv_heads", 1) != 1:
        raise ValueError("the indexer caches one key head a token")
    return dict(
        vocab_size=hf_config["vocab_size"],
        num_layers=hf_config["num_hidden_layers"],
        num_heads=hf_config["num_attention_heads"],
        num_kv_heads=hf_config["num_key_value_heads"],
        head_dim=hf_config["head_dim"],
        d_model=hf_config["hidden_size"],
        d_ff=hf_config["moe_intermediate_size"],
        max_seq_len=max_seq_len or hf_config["max_position_embeddings"],
        use_rope=True,
        rope_base=float(hf_config["rope_theta"]),
        tie_embeddings=bool(hf_config.get("tie_word_embeddings", False)),
        norm="rmsnorm",
        norm_eps=hf_config["rms_norm_eps"],
        mlp="swiglu",
        qk_norm=True,
        num_experts=hf_config["num_experts"],
        moe_top_k=hf_config["num_experts_per_tok"],
        moe_dispatch="dropless",
        moe_bias=False,
        indexer_heads=sa.get("indexer_num_heads", 0),
        indexer_head_dim=sa.get("indexer_head_dim", 64),
        sparse_topk=sa.get("topk", 0),
        attn_bias=False,
        attention_impl="dense",
    )


def _rope_scaling(entry: Mapping[str, Any], what: str):
    """A ``rope_parameters`` entry as ``RopeScaling`` (None: default)."""
    kind = entry.get("rope_type", "default")
    if kind == "default":
        return None
    if kind != "yarn":
        raise ValueError(
            f"rope_type {kind!r} on the {what} layers is not supported: "
            "apply_rope knows the default rotation and YaRN"
        )
    from cs744_pytorch_distributed_tutorial_tpu.models.transformer import (
        RopeScaling,
    )

    return RopeScaling(
        factor=float(entry["factor"]),
        original_max_position=int(entry["original_max_position_embeddings"]),
        beta_fast=float(entry.get("beta_fast", 32)),
        beta_slow=float(entry.get("beta_slow", 1)),
        attention_factor=entry.get("attention_factor"),
    )


def mellum_model_config(hf_config: Mapping[str, Any], max_seq_len: int | None = None) -> dict:
    """``TransformerLM`` kwargs for a ``mellum`` ``config.json``
    (JetBrains/Mellum2-12B-A2.5B-Instruct): RMSNorm, GQA with a head
    width of its own and per-head q/k RMSNorm, every layer routed (gated
    experts on the dropless path, top-k weights renormalised), and
    layers that differ by ``layer_types``: a ``sliding_attention`` layer
    sees ``sliding_window`` keys and rotates by the ``sliding_attention``
    entry of ``rope_parameters`` (default RoPE), a ``full_attention``
    layer sees every key and rotates by the ``full_attention`` entry
    (YaRN as published). Read from the published keys alone, no tensor.
    ``max_seq_len`` defaults to ``max_position_embeddings``."""
    layers = hf_config["num_hidden_layers"]
    # A file cut in depth keeps the published lists whole: the first
    # num_hidden_layers entries are the layers that run.
    kinds = tuple(hf_config.get("layer_types") or ("full_attention",) * layers)
    if len(kinds) < layers:
        raise ValueError(
            f"layer_types names {len(kinds)} layers, num_hidden_layers is "
            f"{layers}"
        )
    kinds = kinds[:layers]
    if any(k != "sparse" for k in (hf_config.get("mlp_layer_types") or ())[:layers]):
        raise ValueError(
            "dense layers among the routed ones (mlp_layer_types) are not "
            "supported: every block of TransformerLM has the same MLP"
        )
    if not hf_config.get("norm_topk_prob", True):
        raise ValueError(
            "norm_topk_prob=false is not supported: MoEFFN renormalises "
            "the top-k weights"
        )
    if hf_config.get("attention_bias"):
        raise ValueError("attention_bias is not supported")
    sliding = "sliding_attention" in kinds
    if sliding and not (
        hf_config.get("use_sliding_window", True) and hf_config.get("sliding_window")
    ):
        raise ValueError(
            "sliding_attention layers need use_sliding_window and a "
            "sliding_window"
        )
    rope = hf_config.get("rope_parameters") or {}
    full = rope.get("full_attention", rope)
    window = rope.get("sliding_attention", {})
    if _rope_scaling(window, "sliding_attention") is not None:
        raise ValueError(
            "the sliding_attention layers rotate by default RoPE: a scaled "
            "rope_type there is not supported"
        )
    return dict(
        vocab_size=hf_config["vocab_size"],
        num_layers=layers,
        num_heads=hf_config["num_attention_heads"],
        num_kv_heads=hf_config["num_key_value_heads"],
        head_dim=hf_config["head_dim"],
        d_model=hf_config["hidden_size"],
        d_ff=hf_config["moe_intermediate_size"],
        max_seq_len=max_seq_len or hf_config["max_position_embeddings"],
        use_rope=True,
        rope_base=float(full.get("rope_theta", hf_config.get("rope_theta", 1e4))),
        rope_scaling=_rope_scaling(full, "full_attention"),
        tie_embeddings=bool(hf_config.get("tie_word_embeddings", False)),
        norm="rmsnorm",
        norm_eps=hf_config["rms_norm_eps"],
        mlp="swiglu",
        qk_norm=True,
        num_experts=hf_config["num_experts"],
        moe_top_k=hf_config["num_experts_per_tok"],
        moe_dispatch="dropless",
        moe_bias=False,
        layer_types=kinds if sliding else None,
        window=int(hf_config["sliding_window"]) if sliding else None,
        window_rope_base=float(window["rope_theta"]) if "rope_theta" in window else None,
        attn_bias=False,
        attention_impl="dense",
    )


def longcat_flash_model_config(
    hf_config: Mapping[str, Any], max_seq_len: int | None = None,
    held_experts: Sequence[int] | None = None,
) -> dict:
    """``TransformerLM`` kwargs for a ``longcat_flash`` ``config.json``
    (meituan-longcat/LongCat-Flash-Chat; transformers'
    ``modular_longcat_flash.py`` reads the same keys): every layer two
    latent attentions (MLA: ``q_lora_rank``, ``kv_lora_rank``, heads of
    ``qk_nope_head_dim + qk_rope_head_dim`` / ``v_head_dim``, the
    ``mla_scale_*_lora`` factors ``sqrt(hidden / rank)``, interleaved
    RoPE on the rope dimensions), two dense SwiGLU MLPs of
    ``ffn_hidden_size`` and one shortcut MoE: a softmax router over
    ``n_routed_experts + zero_expert_num`` outputs, ``moe_topk`` chosen
    by ``scores + e_score_correction_bias``, weighted by the scores
    times ``routed_scaling_factor``, NOT renormalised; the zero-compute
    experts are the identity. Read from the published keys alone.

    ``held_experts`` is one chip's share of the experts (the
    ``model-configs`` guide's section 4): the ids, of the
    ``n_routed_experts`` the router routes over, whose matrices the model
    holds; None holds them all. ``max_seq_len`` defaults to
    ``max_position_embeddings``."""
    if hf_config.get("attention_bias") or hf_config.get("router_bias"):
        raise ValueError("attention_bias / router_bias are not supported")
    if hf_config.get("hidden_act", "silu") != "silu":
        raise ValueError("hidden_act other than silu is not supported")
    if hf_config.get("zero_expert_type", "identity") != "identity":
        raise ValueError(
            f"zero_expert_type {hf_config['zero_expert_type']!r} is not "
            "supported: a zero-compute expert is the identity"
        )
    if hf_config.get("rope_scaling"):
        raise ValueError(
            "rope_scaling on a longcat_flash config is not supported: the "
            "latent layer rotates by default RoPE"
        )
    from cs744_pytorch_distributed_tutorial_tpu.models.latent import LatentDims

    hidden = hf_config["hidden_size"]
    q_rank, kv_rank = hf_config["q_lora_rank"], hf_config["kv_lora_rank"]
    return dict(
        vocab_size=hf_config["vocab_size"],
        num_layers=hf_config["num_layers"],
        num_heads=hf_config["num_attention_heads"],
        d_model=hidden,
        d_ff=hf_config["expert_ffn_hidden_size"],
        dense_d_ff=hf_config["ffn_hidden_size"],
        max_seq_len=max_seq_len or hf_config["max_position_embeddings"],
        use_rope=True,
        rope_base=float(hf_config["rope_theta"]),
        tie_embeddings=False,
        norm="rmsnorm",
        norm_eps=hf_config["rms_norm_eps"],
        mlp="swiglu",
        latent=LatentDims(
            q_lora_rank=q_rank,
            kv_lora_rank=kv_rank,
            qk_nope_head_dim=hf_config["qk_nope_head_dim"],
            qk_rope_head_dim=hf_config["qk_rope_head_dim"],
            v_head_dim=hf_config["v_head_dim"],
            scale_q=(hidden / q_rank) ** 0.5 if hf_config.get("mla_scale_q_lora") else 1.0,
            scale_kv=(hidden / kv_rank) ** 0.5 if hf_config.get("mla_scale_kv_lora") else 1.0,
        ),
        num_experts=hf_config["n_routed_experts"],
        moe_top_k=hf_config["moe_topk"],
        moe_dispatch="dropless",
        moe_bias=False,
        moe_held_experts=None if held_experts is None else tuple(held_experts),
        moe_zero_experts=int(hf_config.get("zero_expert_num") or 0),
        moe_renormalize=False,
        moe_routed_scale=float(hf_config.get("routed_scaling_factor", 1.0)),
        moe_choice_bias=True,
        attn_bias=False,
        attention_impl="dense",
    )


def _deepseek_yarn(rope_scaling: Mapping[str, Any] | None):
    """DeepSeek-V2's ``rope_scaling`` (keys ``type``, ``factor``,
    ``original_max_position_embeddings``, ``beta_fast``, ``beta_slow``,
    ``mscale``, ``mscale_all_dim``) as (``RopeScaling`` whose
    ``attention_factor`` is the factor on cos and sin, the factor on the
    whole score): with ``m(a) = 0.1 a ln(factor) + 1`` (1 where factor
    <= 1), cos and sin carry ``m(mscale) / m(mscale_all_dim)`` and the
    score ``m(mscale_all_dim)^2`` (1 where ``mscale_all_dim`` is 0 or
    absent), as the published ``DeepseekV2YarnRotaryEmbedding`` and
    ``softmax_scale`` have it. (None, 1.0) without scaling."""
    if not rope_scaling:
        return None, 1.0
    kind = rope_scaling.get("type", rope_scaling.get("rope_type"))
    if kind != "yarn":
        raise ValueError(
            f"rope_scaling of type {kind!r} is not supported: the latent "
            "layer knows the plain rotation and DeepSeek's YaRN"
        )
    import math

    from cs744_pytorch_distributed_tutorial_tpu.models.transformer import (
        RopeScaling,
    )

    factor = float(rope_scaling["factor"])

    def m(a: float) -> float:
        return 0.1 * a * math.log(factor) + 1.0 if factor > 1 else 1.0

    mscale = float(rope_scaling.get("mscale", 1))
    all_dim = float(rope_scaling.get("mscale_all_dim", 0))
    return RopeScaling(
        factor=factor,
        original_max_position=int(rope_scaling["original_max_position_embeddings"]),
        beta_fast=float(rope_scaling.get("beta_fast", 32)),
        beta_slow=float(rope_scaling.get("beta_slow", 1)),
        attention_factor=m(mscale) / m(all_dim),
    ), (m(all_dim) ** 2 if all_dim else 1.0)


def deepseek_v2_model_config(
    hf_config: Mapping[str, Any], max_seq_len: int | None = None,
    held_experts: Sequence[int] | None = None,
) -> dict:
    """``TransformerLM`` kwargs for a ``deepseek_v2`` ``config.json``
    (deepseek-ai/DeepSeek-V2; its ``modeling_deepseek.py`` reads the same
    keys): every layer the plain pre-norm block over latent attention
    (MLA: ``q_lora_rank``, ``kv_lora_rank``, heads of ``qk_nope_head_dim +
    qk_rope_head_dim`` / ``v_head_dim``, interleaved RoPE on the rope
    dimensions, DeepSeek's YaRN from ``rope_scaling``); the FFN a dense
    SwiGLU of ``intermediate_size`` on the first ``first_k_dense_replace``
    layers, on the others the MoE: a softmax router over
    ``n_routed_experts``, ``num_experts_per_tok`` chosen by
    ``topk_method`` (``greedy``, or ``group_limited_greedy`` within the
    ``topk_group`` best of ``n_group`` groups), weighted by the scores
    times ``routed_scaling_factor`` (``norm_topk_prob: false``) or
    renormalised (true, unscaled, as the published code does), plus
    ``n_shared_experts`` shared experts of ``moe_intermediate_size``
    every token goes through. Read from the published keys alone.

    ``held_experts`` is one chip's share of the routed experts (the
    ``model-configs`` guide's section 4), as ``longcat_flash_model_config``
    takes it; None holds them all. ``max_seq_len`` defaults to
    ``max_position_embeddings``."""
    if hf_config.get("attention_bias"):
        raise ValueError("attention_bias is not supported")
    if hf_config.get("hidden_act", "silu") != "silu":
        raise ValueError("hidden_act other than silu is not supported")
    if hf_config.get("tie_word_embeddings"):
        raise ValueError("tied embeddings are not supported: the head is untied")
    if hf_config.get("scoring_func", "softmax") != "softmax":
        raise ValueError(
            f"scoring_func {hf_config['scoring_func']!r} is not supported: "
            "the router is a softmax"
        )
    method = hf_config.get("topk_method", "greedy")
    if method not in ("greedy", "group_limited_greedy"):
        raise ValueError(
            f"topk_method {method!r} is not supported: 'greedy' and "
            "'group_limited_greedy' are built"
        )
    if hf_config.get("moe_layer_freq", 1) != 1:
        raise ValueError(
            "moe_layer_freq other than 1 is not supported: every layer past "
            "first_k_dense_replace is routed"
        )
    if not hf_config.get("q_lora_rank"):
        raise ValueError(
            "q_lora_rank null (a full-rank query) is not supported: the "
            "latent layer's query goes through the low-rank path"
        )
    from cs744_pytorch_distributed_tutorial_tpu.models.latent import LatentDims

    scaling, score_factor = _deepseek_yarn(hf_config.get("rope_scaling"))
    renormalize = bool(hf_config.get("norm_topk_prob", False))
    grouped = method == "group_limited_greedy"
    return dict(
        vocab_size=hf_config["vocab_size"],
        num_layers=hf_config["num_hidden_layers"],
        num_heads=hf_config["num_attention_heads"],
        d_model=hf_config["hidden_size"],
        d_ff=hf_config["moe_intermediate_size"],
        dense_d_ff=hf_config["intermediate_size"],
        max_seq_len=max_seq_len or hf_config["max_position_embeddings"],
        use_rope=True,
        rope_base=float(hf_config["rope_theta"]),
        tie_embeddings=False,
        norm="rmsnorm",
        norm_eps=hf_config["rms_norm_eps"],
        mlp="swiglu",
        latent=LatentDims(
            q_lora_rank=hf_config["q_lora_rank"],
            kv_lora_rank=hf_config["kv_lora_rank"],
            qk_nope_head_dim=hf_config["qk_nope_head_dim"],
            qk_rope_head_dim=hf_config["qk_rope_head_dim"],
            v_head_dim=hf_config["v_head_dim"],
            rope_scaling=scaling,
            score_factor=score_factor,
        ),
        latent_block="plain",
        dense_layers=int(hf_config.get("first_k_dense_replace", 0)),
        num_experts=hf_config["n_routed_experts"],
        moe_top_k=hf_config["num_experts_per_tok"],
        moe_dispatch="dropless",
        moe_bias=False,
        moe_held_experts=None if held_experts is None else tuple(held_experts),
        moe_renormalize=renormalize,
        moe_routed_scale=(
            1.0 if renormalize else float(hf_config.get("routed_scaling_factor", 1.0))
        ),
        moe_n_group=int(hf_config["n_group"]) if grouped else 1,
        moe_topk_group=int(hf_config["topk_group"]) if grouped else 1,
        moe_shared_d_ff=int(hf_config.get("n_shared_experts") or 0)
        * hf_config["moe_intermediate_size"],
        attn_bias=False,
        attention_impl="dense",
    )


def lightning_rates(num_heads: int, layer: int, num_layers: int) -> tuple[float, ...]:
    """The decay exponent of each head of a lightning layer, ``lam_h =
    exp(-rate_h)``, as MiniMax-01's lightning attention builds it
    (``_build_slope_tensor``): the slope ``s_h = 2^(-8 (h + 1) / H)`` (H
    a power of two) times ``1 - layer / (num_layers - 1) + 1e-5``, for
    the layer's index ``layer`` among ``num_layers``."""
    if num_heads & (num_heads - 1):
        raise ValueError(
            f"lightning heads {num_heads} is no power of two: the slopes of "
            "other head counts are not built"
        )
    depth = 1.0 - layer / (num_layers - 1) + 1e-5
    return tuple(2.0 ** (-8.0 * (h + 1) / num_heads) * depth for h in range(num_heads))


# MiniCPM's mixer names -> TransformerLM's layer_types
_SALA_MIXERS = {
    "lightning-attn": "lightning_attention",
    "minicpm4": "block_sparse_attention",
}


def minicpm_sala_model_config(
    hf_config: Mapping[str, Any], max_seq_len: int | None = None,
    layer_ids: Sequence[int] | None = None,
) -> dict:
    """``TransformerLM`` kwargs for a ``minicpm_sala`` ``config.json``
    (openbmb/MiniCPM-SALA): each layer of ``mixer_types`` is lightning
    attention (``lightning-attn``: ``lightning_nh`` heads of
    ``lightning_head_dim``, RoPE and q/k RMSNorm, the output RMSNorm over
    every head and the sigmoid gate, a head's decay as
    ``lightning_rates`` gives it for the layer's index) or block-sparse
    attention over compressed keys (``minicpm4``: GQA with no position
    encoding, q/k RMSNorm, the sigmoid output gate, the selection of
    ``sparse_config`` as MiniCPM4 names its keys, MiniCPM4-8B's values
    where the file gives none), each beside a dense SwiGLU of
    ``intermediate_size``; MiniCPM's muP: the embedding times
    ``scale_emb``, each residual times ``scale_depth /
    sqrt(mup_denominator)``, the final norm's output times
    ``dim_model_base / hidden_size``; untied head. Read from the
    published keys alone.

    ``layer_ids`` are the layers of the file that one chip holds (a
    stage of a pipeline, the ``model-configs`` guide's section 4), in
    order; each keeps its own kind and its decay by its index in the
    file. None holds them all. ``max_seq_len`` defaults to
    ``max_position_embeddings``."""
    from cs744_pytorch_distributed_tutorial_tpu.ops.block_sparse import BlockSparse

    c = hf_config
    if c.get("attention_bias"):
        raise ValueError("attention_bias is not supported")
    if c.get("hidden_act", "silu") != "silu":
        raise ValueError("hidden_act other than silu is not supported")
    if c.get("tie_word_embeddings"):
        raise ValueError("tied embeddings are not supported: the head is untied")
    if c.get("attn_use_rope"):
        raise ValueError(
            "attn_use_rope true is not supported: the block-sparse layers "
            "use no position encoding"
        )
    if not c.get("lightning_use_rope", True):
        raise ValueError("lightning_use_rope false is not supported: the lightning layers rotate")
    for key in ("qk_norm", "use_output_gate", "use_output_norm", "attn_use_output_gate"):
        if not c.get(key, True):
            raise ValueError(f"{key} false is not supported: the layers are built with it")
    heads, head_dim = c["num_attention_heads"], c["head_dim"]
    if (
        c.get("lightning_nh", heads) != heads or c.get("lightning_nkv", heads) != heads
        or c.get("lightning_head_dim", head_dim) != head_dim
    ):
        raise ValueError(
            "lightning heads other than num_attention_heads of head_dim (or "
            "shared among queries: lightning_nkv < lightning_nh) are not supported"
        )
    if c.get("lightning_scale", "1/sqrt(d)") != "1/sqrt(d)":
        raise ValueError(f"lightning_scale {c['lightning_scale']!r} is not supported")
    mixers = list(c["mixer_types"])
    if len(mixers) != c["num_hidden_layers"] or not set(mixers) <= set(_SALA_MIXERS):
        raise ValueError(
            f"mixer_types must name {c['num_hidden_layers']} layers, each one "
            f"of {sorted(_SALA_MIXERS)}; got {sorted(set(mixers))}"
        )
    ids = list(range(len(mixers))) if layer_ids is None else [int(i) for i in layer_ids]
    if not ids or any(not 0 <= i < len(mixers) for i in ids):
        raise ValueError(f"layer_ids {ids} are not layers of {len(mixers)}")
    kinds = tuple(_SALA_MIXERS[mixers[i]] for i in ids)
    sc = c.get("sparse_config") or {}
    default = BlockSparse()
    sparse = BlockSparse(
        kernel_size=int(sc.get("kernel_size", default.kernel_size)),
        kernel_stride=int(sc.get("kernel_stride", default.kernel_stride)),
        block_size=int(sc.get("block_size", default.block_size)),
        window=int(sc.get("window_size", default.window)),
        topk=int(sc.get("topk", default.topk)),
        init_blocks=int(sc.get("init_blocks", default.init_blocks)),
        dense_len=int(sc.get("dense_len", default.dense_len)),
    )
    sparse.check()
    return dict(
        vocab_size=c["vocab_size"],
        num_layers=len(ids),
        num_heads=heads,
        num_kv_heads=c["num_key_value_heads"],
        head_dim=head_dim,
        d_model=c["hidden_size"],
        d_ff=c["intermediate_size"],
        max_seq_len=max_seq_len or c["max_position_embeddings"],
        use_rope=True,
        rope_base=float(c["rope_theta"]),
        tie_embeddings=False,
        norm="rmsnorm",
        norm_eps=c["rms_norm_eps"],
        mlp="swiglu",
        layer_types=kinds,
        lightning_rates=tuple(
            lightning_rates(heads, i, len(mixers)) if k == "lightning_attention" else None
            for i, k in zip(ids, kinds)
        ),
        block_sparse=sparse,
        embed_scale=float(c["scale_emb"]),
        residual_scale=float(c["scale_depth"]) / float(c["mup_denominator"]) ** 0.5,
        logit_scale=float(c["dim_model_base"]) / float(c["hidden_size"]),
        attention_impl="dense",
    )


# ``model_type`` of a published config.json -> the builder of its kwargs
CONFIG_BUILDERS = {
    "KeyeVL2": keye_model_config,
    "mellum": mellum_model_config,
    "longcat_flash": longcat_flash_model_config,
    "deepseek_v2": deepseek_v2_model_config,
    "minicpm_sala": minicpm_sala_model_config,
}


def model_config_from_hf(
    hf_config: Mapping[str, Any], max_seq_len: int | None = None, **how_deployed,
) -> dict:
    """``TransformerLM`` kwargs from a published ``config.json``, by its
    ``model_type``. ``how_deployed`` goes to the builder: what a
    deployment decides and no published file says (``held_experts`` of
    ``longcat_flash`` and ``deepseek_v2``, ``layer_ids`` of
    ``minicpm_sala``); a builder that has no such argument raises."""
    kind = hf_config.get("model_type")
    if kind not in CONFIG_BUILDERS:
        raise ValueError(
            f"no builder for model_type {kind!r}; known: "
            f"{sorted(CONFIG_BUILDERS)}"
        )
    return CONFIG_BUILDERS[kind](hf_config, max_seq_len=max_seq_len, **how_deployed)


def lm_params_from_hf_llama(state_dict: Mapping[str, Any]) -> dict:
    """Convert a ``LlamaForCausalLM.state_dict()`` into the ``params``
    tree of the matching ``TransformerLM`` (``llama_model_config``).
    torch ``Linear`` weights are [out, in] and transpose to the flax
    [in, out] kernel; llama has no projection biases, but this
    framework's ``mlp_in`` bias and post-psum ``mlp_out_bias`` always
    exist — they are zero-filled (numerically identical)."""
    _require_layout(
        state_dict, "model.layers.0.input_layernorm.weight",
        "LlamaForCausalLM",
    )
    params: dict = {
        "tok_embed": {"embedding": _np(state_dict["model.embed_tokens.weight"])},
        "ln_f": {"scale": _np(state_dict["model.norm.weight"])},
    }
    if "lm_head.weight" in state_dict:
        params["lm_head"] = {"kernel": _np(state_dict["lm_head.weight"]).T}
    # else: tied embeddings — the model's attend path reuses tok_embed.
    i = 0
    while f"model.layers.{i}.input_layernorm.weight" in state_dict:
        pre = f"model.layers.{i}"

        def lin(name: str) -> np.ndarray:
            return _np(state_dict[f"{pre}.{name}.weight"]).T  # [out,in]->[in,out]

        gate = lin("mlp.gate_proj")
        d_model, d_ff = gate.shape
        params[f"block_{i}"] = {
            "ln1": {"scale": _np(state_dict[f"{pre}.input_layernorm.weight"])},
            "ln2": {
                "scale": _np(
                    state_dict[f"{pre}.post_attention_layernorm.weight"]
                )
            },
            "attn": {
                "q": {"kernel": lin("self_attn.q_proj")},
                "k": {"kernel": lin("self_attn.k_proj")},
                "v": {"kernel": lin("self_attn.v_proj")},
                "attn_out": {"kernel": lin("self_attn.o_proj")},
            },
            "mlp_gate": {"kernel": gate},
            "mlp_in": {
                "kernel": lin("mlp.up_proj"),
                "bias": np.zeros(d_ff, np.float32),
            },
            "mlp_out": {"kernel": lin("mlp.down_proj")},
            "mlp_out_bias": np.zeros(d_model, np.float32),
        }
        i += 1
    return params
