"""Operations and bytes the ``deepseek_v2`` family's algorithms need, from
shapes and from the engine's own counters (``work_latent_moe.py`` counts
the ``longcat_flash`` family's by its keys). They count what the
mathematics requires whatever implements it: the latent row of an
attended token read ONCE for all heads at its unpadded width, the
matrices of the held experts that received a token read once a step;
padding, masked-out work and re-reads are not counted.
"""

from __future__ import annotations

from typing import Any, Mapping


def dims(cfg: Mapping[str, Any]) -> dict[str, int]:
    held = cfg["n_routed_experts"]
    routed = (cfg.get("published") or {}).get("n_routed_experts", held)
    layers = cfg["num_hidden_layers"]
    dense = min(cfg.get("first_k_dense_replace", 0), layers)
    return dict(
        d=cfg["hidden_size"], layers=layers, dense_layers=dense, moe_layers=layers - dense,
        vocab=cfg["vocab_size"], h=cfg["num_attention_heads"], qr=cfg["q_lora_rank"],
        kvr=cfg["kv_lora_rank"], dn=cfg["qk_nope_head_dim"], dr=cfg["qk_rope_head_dim"],
        dv=cfg["v_head_dim"], f=cfg["intermediate_size"], fe=cfg["moe_intermediate_size"],
        shared=cfg.get("n_shared_experts") or 0, held=held, routed=routed, k=cfg["num_experts_per_tok"],
        groups=cfg.get("n_group") or 1, topk_group=cfg.get("topk_group") or 1,
    )


def as_published(cfg: Mapping[str, Any]) -> tuple[dict[str, Any], tuple[int, ...] | None]:
    """A configuration file cut to one chip's share, as the program's
    builder takes it (``deepseek_v2_model_config``): the keys with
    ``n_routed_experts`` back at the router's published width, and the
    ids held here (``0 .. n_routed_experts - 1`` of the file), None where
    the file holds every expert."""
    c = dims(cfg)
    held = tuple(range(c["held"])) if c["held"] < c["routed"] else None
    return {**cfg, "n_routed_experts": c["routed"]}, held


def attention_params(cfg) -> int:
    """One latent attention: the query's two matrices, the key-value
    path's two, the output's."""
    c = dims(cfg)
    return (
        c["d"] * c["qr"] + c["qr"] * c["h"] * (c["dn"] + c["dr"]) + c["d"] * (c["kvr"] + c["dr"])
        + c["kvr"] * c["h"] * (c["dn"] + c["dv"]) + c["h"] * c["dv"] * c["d"]
    )


def expert_params(cfg) -> int:
    c = dims(cfg)
    return 3 * c["d"] * c["fe"]


def dense_layer_params(cfg) -> int:
    """The leading dense layer: attention and a SwiGLU of
    ``intermediate_size``."""
    c = dims(cfg)
    return attention_params(cfg) + 3 * c["d"] * c["f"]


def moe_layer_params_outside_routed(cfg) -> int:
    """Attention, the shared experts, the router: what every token
    multiplies in an MoE layer whatever it is routed to. Absorbed, a
    token multiplies ``W_kvb`` as ``q_nope W_uk`` and ``o W_uv``: the
    same count."""
    c = dims(cfg)
    return attention_params(cfg) + c["shared"] * expert_params(cfg) + c["d"] * c["routed"]


def held_experts_per_token_expected(cfg) -> float:
    """Held experts a token chooses an MoE layer under an even router:
    ``top_k * held / routed`` (0.75 at 6 of 160 with 20 held)."""
    c = dims(cfg)
    return c["k"] * c["held"] / c["routed"]


def held_group_share_expected(cfg) -> float:
    """Tokens whose kept groups include the held group under an even
    router: ``topk_group / n_group`` (3/8)."""
    c = dims(cfg)
    return c["topk_group"] / c["groups"]


def active_matmul_params(cfg, held_per_token: float | None = None) -> float:
    """Parameters a token multiplies HERE: the dense layers, every MoE
    layer's part outside the routed experts and the held experts it
    chose (``held_per_token`` a layer, by the engine's counters, else the
    expectation), and the vocabulary slice's head (the embedding is a
    gather)."""
    c = dims(cfg)
    if held_per_token is None:
        held_per_token = held_experts_per_token_expected(cfg)
    moe = moe_layer_params_outside_routed(cfg) + held_per_token * expert_params(cfg)
    return c["dense_layers"] * dense_layer_params(cfg) + c["moe_layers"] * moe + c["d"] * c["vocab"]


def dense_equivalent(cfg, held_per_token: float | None = None) -> dict[str, float]:
    """The GPT-2-style keys under which ``work.transformer_matmul_params``
    counts exactly ``active_matmul_params``: the width as it is, and the
    MLP width ``n_inner`` at which ``n_layer * (4 d^2 + 2 d n_inner) + d *
    vocab`` equals it. The accepted ``mfu.serve`` reads these keys, so it
    reads this cell with the active count and no edit."""
    c = dims(cfg)
    per_layer = (active_matmul_params(cfg, held_per_token) - c["d"] * c["vocab"]) / c["layers"]
    return {"n_embd": c["d"], "n_inner": (per_layer - 4 * c["d"] * c["d"]) / (2 * c["d"]), "n_layer": c["layers"]}


def latent_row_bytes(cfg, itemsize: int = 2) -> int:
    """What a token caches a layer: the latent and its one rope key
    (1,152 B at 512 + 64 in bfloat16)."""
    c = dims(cfg)
    return (c["kvr"] + c["dr"]) * itemsize


def kv_row_bytes_unabsorbed(cfg, itemsize: int = 2) -> int:
    """The same token's keys and values kept a head (81,920 B at 128
    heads)."""
    c = dims(cfg)
    return c["h"] * (c["dn"] + c["dr"] + c["dv"]) * itemsize


def absorbed_attention_flops(rows_read: float, cfg) -> float:
    """The absorbed products over the latent rows a query attends, every
    head: scores over the row's ``kv_lora_rank + rope`` lanes, values
    over its ``kv_lora_rank`` (278,528 a row at 128 heads; ``rows_read``
    is summed over layers, as the engine counts it)."""
    c = dims(cfg)
    return 2.0 * c["h"] * (2 * c["kvr"] + c["dr"]) * rows_read


def latent_attention_bytes(rows_read: float, cfg, itemsize: int = 2) -> float:
    """The latent rows attended, read once for all heads."""
    return float(latent_row_bytes(cfg, itemsize)) * rows_read


def built_attention_flops(pairs: float, cfg) -> float:
    """Scores and values a (query, key) pair with keys and values built a
    head: what a causal pass over a prompt needs (its ``W_kvb`` products
    are counted with the parameters)."""
    c = dims(cfg)
    return 2.0 * c["h"] * (c["dn"] + c["dr"] + c["dv"]) * pairs


def attention_flops_in_window(latent_tokens_read: float, prompts, cfg) -> float:
    """Attention's FLOPs of a serving window: the decode steps' absorbed
    products over the rows the engine counted, prefill's causal pass over
    each prompt prefilled (n (n + 1) / 2 pairs a layer, built a head: the
    lesser count)."""
    c = dims(cfg)
    pairs = sum(c["layers"] * n * (n + 1) / 2.0 for n in prompts)
    return absorbed_attention_flops(latent_tokens_read, cfg) + built_attention_flops(pairs, cfg)


def as_sparse_moe_config(cfg) -> dict[str, Any]:
    """The keys under which ``work_sparse_moe`` (the accepted
    ``counted_roofline`` reader's) counts this model's experts' work:
    ``moe_flops`` a held (token, expert) pair and ``moe_bytes`` a held
    expert that received a token, at width ``moe_intermediate_size``
    (the file's own key, as the others it reads: the width, the layers,
    the heads)."""
    c = dims(cfg)
    return {
        "head_dim": c["dn"] + c["dr"], "num_experts": c["held"],
        "sa_config": {"indexer_num_heads": 0, "indexer_head_dim": 0, "topk": 0},
    }
