"""Operations and bytes the ``longcat_flash`` family's algorithms need,
from shapes and from the engine's own counters (``perfbench/work.py``,
``work_sparse_moe.py`` and ``work_window_moe.py`` have the others'). They
count what the mathematics requires whatever implements it: the latent
row of an attended token read ONCE for all heads at its unpadded width
(the pool's lane padding is the implementation's), the matrices of the
held experts that received a token read once a step; padding, masked-out
work and re-reads are not counted.
"""

from __future__ import annotations

from typing import Any, Mapping


def dims(cfg: Mapping[str, Any]) -> dict[str, int]:
    held = cfg["n_routed_experts"]
    routed = (cfg.get("published") or {}).get("n_routed_experts", held)
    return dict(
        d=cfg["hidden_size"], layers=cfg["num_layers"], sublayers=2 * cfg["num_layers"], vocab=cfg["vocab_size"],
        h=cfg["num_attention_heads"], qr=cfg["q_lora_rank"], kvr=cfg["kv_lora_rank"],
        dn=cfg["qk_nope_head_dim"], dr=cfg["qk_rope_head_dim"], dv=cfg["v_head_dim"],
        f=cfg["ffn_hidden_size"], fe=cfg["expert_ffn_hidden_size"], held=held, routed=routed,
        zero=cfg["zero_expert_num"], outputs=routed + cfg["zero_expert_num"], k=cfg["moe_topk"],
    )


def as_published(cfg: Mapping[str, Any]) -> tuple[dict[str, Any], tuple[int, ...] | None]:
    """A configuration file cut to one chip's share, as the program's
    builder takes it (``longcat_flash_model_config``): the keys with
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


def params_outside_experts_per_layer(cfg) -> int:
    """Two attentions, two dense MLPs, the router: what every token
    multiplies in a layer whatever it is routed to. Absorbed, a token
    multiplies ``W_kvb`` as ``q_nope W_uk`` and ``o W_uv``: the same
    count."""
    c = dims(cfg)
    return 2 * attention_params(cfg) + 2 * 3 * c["d"] * c["f"] + c["d"] * c["outputs"]


def expert_params(cfg) -> int:
    c = dims(cfg)
    return 3 * c["d"] * c["fe"]


def held_experts_per_token_expected(cfg) -> float:
    """Held experts a token chooses a layer under an even router:
    ``moe_topk * held / outputs`` (0.25 at 12 of 768 with 16 held)."""
    c = dims(cfg)
    return c["k"] * c["held"] / c["outputs"]


def active_matmul_params(cfg, held_per_token: float | None = None) -> float:
    """Parameters a token multiplies HERE: every layer's part outside the
    experts, the held experts it chose (``held_per_token`` a layer, by
    the engine's counters, else the expectation), and the vocabulary
    slice's head (the embedding is a gather)."""
    c = dims(cfg)
    if held_per_token is None:
        held_per_token = held_experts_per_token_expected(cfg)
    per_layer = params_outside_experts_per_layer(cfg) + held_per_token * expert_params(cfg)
    return c["layers"] * per_layer + c["d"] * c["vocab"]


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
    """What a token caches a sublayer: the latent and its one rope key
    (1,152 B at 512 + 64 in bfloat16)."""
    c = dims(cfg)
    return (c["kvr"] + c["dr"]) * itemsize


def kv_row_bytes_unabsorbed(cfg, itemsize: int = 2) -> int:
    """The same token's keys and values kept a head (40,960 B)."""
    c = dims(cfg)
    return c["h"] * (c["dn"] + c["dr"] + c["dv"]) * itemsize


def absorbed_attention_flops(rows_read: float, cfg) -> float:
    """The absorbed products over the latent rows a query attends, every
    head: scores over the row's ``kv_lora_rank + rope`` lanes, values
    over its ``kv_lora_rank`` (``rows_read`` is summed over sublayers, as
    the engine counts it)."""
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
    each prompt prefilled (n (n + 1) / 2 pairs a sublayer, built a head:
    the lesser count)."""
    c = dims(cfg)
    pairs = sum(c["sublayers"] * n * (n + 1) / 2.0 for n in prompts)
    return absorbed_attention_flops(latent_tokens_read, cfg) + built_attention_flops(pairs, cfg)


def as_sparse_moe_config(cfg) -> dict[str, Any]:
    """The keys under which ``work_sparse_moe`` (the accepted
    ``counted_roofline`` reader's) counts this model's experts' work:
    ``moe_flops`` a held (token, expert) pair and ``moe_bytes`` a held
    expert that received a token, at width ``expert_ffn_hidden_size``."""
    c = dims(cfg)
    return {
        "num_hidden_layers": c["layers"], "num_attention_heads": c["h"], "num_key_value_heads": 1,
        "head_dim": c["dn"] + c["dr"], "num_experts": c["held"], "num_experts_per_tok": c["k"],
        "moe_intermediate_size": c["fe"],
        "sa_config": {"indexer_num_heads": 0, "indexer_head_dim": 0, "topk": 0},
    }
