"""Operations and bytes the ``keye`` family's algorithms need, from
shapes and from the engine's own counters (``perfbench/work.py`` has the
others'). They count what the mathematics requires whatever implements
it: a selected token's K and V rows read once, an indexer key read once
a scored token, the matrices of the experts that received a token read
once a step; padding, masked-out work and re-reads are not counted.
"""

from __future__ import annotations

from typing import Any, Mapping


def dims(cfg: Mapping[str, Any]) -> dict[str, int]:
    sa = cfg["sa_config"]
    return dict(
        d=cfg["hidden_size"], layers=cfg["num_hidden_layers"], vocab=cfg["vocab_size"],
        h=cfg["num_attention_heads"], hkv=cfg["num_key_value_heads"], hd=cfg["head_dim"],
        experts=cfg["num_experts"], k=cfg["num_experts_per_tok"], f=cfg["moe_intermediate_size"],
        j=sa["indexer_num_heads"], di=sa["indexer_head_dim"], topk=sa["topk"],
    )


def active_matmul_params_per_layer(cfg) -> int:
    """Parameters a token multiplies in one layer: q, k, v and output
    projections, the indexer's three, the router, and the
    ``num_experts_per_tok`` experts it is routed to (three matrices
    each)."""
    c = dims(cfg)
    attn = c["d"] * c["h"] * c["hd"] * 2 + c["d"] * c["hkv"] * c["hd"] * 2
    indexer = c["d"] * (c["j"] * c["di"] + c["di"] + c["j"])
    return attn + indexer + c["d"] * c["experts"] + c["k"] * 3 * c["d"] * c["f"]


def active_matmul_params(cfg) -> int:
    """All layers, and the vocabulary head (the embedding is a gather)."""
    c = dims(cfg)
    return c["layers"] * active_matmul_params_per_layer(cfg) + c["d"] * c["vocab"]


def dense_equivalent(cfg) -> dict[str, int]:
    """The GPT-2-style keys under which ``work.transformer_matmul_params``
    counts exactly ``active_matmul_params``: the width as it is, and the
    MLP width ``n_inner`` at which ``n_layer * (4 d^2 + 2 d n_inner) +
    d * vocab`` equals it (an integer at the published sizes: 10344).
    The accepted ``mfu.serve`` reads these keys, so it reads this cell
    with the active count and no edit."""
    c = dims(cfg)
    rest = active_matmul_params_per_layer(cfg) - 4 * c["d"] * c["d"]
    n_inner = rest // (2 * c["d"]) if rest % (2 * c["d"]) == 0 else rest / (2 * c["d"])
    return {"n_embd": c["d"], "n_inner": n_inner, "n_layer": c["layers"]}


def attention_flops(selected_tokens: float, cfg) -> float:
    """q.K^T and p.V over the selected tokens: 4 * head_dim * heads a
    selected token (``selected_tokens`` is summed over layers, as the
    engine counts it)."""
    c = dims(cfg)
    return 4.0 * c["hd"] * c["h"] * selected_tokens


def attention_bytes(selected_tokens: float, cfg, itemsize: int = 2) -> float:
    """K and V rows of the selected tokens, read once."""
    c = dims(cfg)
    return 2.0 * c["hkv"] * c["hd"] * itemsize * selected_tokens


def indexer_flops(scored_tokens: float, cfg) -> float:
    """qI . kI over the scored tokens: 2 * heads * head width each."""
    c = dims(cfg)
    return 2.0 * c["j"] * c["di"] * scored_tokens


def indexer_bytes(scored_tokens: float, cfg, itemsize: int = 2) -> float:
    """One indexer key a scored token, read once (the key as the
    algorithm has it, ``indexer_head_dim`` wide: the lane padding of the
    pool is the implementation's)."""
    return float(dims(cfg)["di"] * itemsize * scored_tokens)


def prefill_selection(prompt_len: int, cfg) -> tuple[float, float]:
    """(selected, scored) token counts of one layer's causal pass over a
    prompt: query t scores t + 1 tokens and keeps min(t + 1, topk)."""
    k, n = dims(cfg)["topk"], int(prompt_len)
    scored = n * (n + 1) / 2.0
    m = min(n, k)
    return m * (m + 1) / 2.0 + max(n - k, 0) * float(k), scored


def moe_flops(token_expert_pairs: float, cfg) -> float:
    """Three matmuls of d x f a routed (token, expert) pair."""
    c = dims(cfg)
    return 6.0 * c["d"] * c["f"] * token_expert_pairs


def moe_bytes(experts_hit: float, cfg, itemsize: int = 2) -> float:
    """The three matrices of every expert that received a token."""
    c = dims(cfg)
    return 3.0 * c["d"] * c["f"] * itemsize * experts_hit
