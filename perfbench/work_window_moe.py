"""Operations and bytes the ``mellum`` family's algorithms need, from
shapes and from the engine's own counters (``perfbench/work.py`` and
``work_sparse_moe.py`` have the others'). They count what the mathematics
requires whatever implements it: the K and V rows of the keys a query
attends read once (every key at or before it on a full layer, the last
``sliding_window`` on a sliding one), the matrices of the experts that
received a token read once a step; padding, masked-out work and re-reads
are not counted.
"""

from __future__ import annotations

from typing import Any, Mapping

# ``work_sparse_moe.dims`` reads an indexer's sizes whatever is asked of
# it; this family has none. The driver hands these beside the
# configuration so that the accepted ``counted_roofline`` reader can count
# the experts' work (``moe_gmm``) here as it does in the long cell.
NO_INDEXER = {"indexer_num_heads": 0, "indexer_head_dim": 0, "topk": 0}


def dims(cfg: Mapping[str, Any]) -> dict[str, int]:
    layers = cfg["num_hidden_layers"]
    sliding = sum(k == "sliding_attention" for k in cfg["layer_types"][:layers])
    return dict(
        d=cfg["hidden_size"], layers=layers, vocab=cfg["vocab_size"],
        h=cfg["num_attention_heads"], hkv=cfg["num_key_value_heads"], hd=cfg["head_dim"],
        experts=cfg["num_experts"], k=cfg["num_experts_per_tok"], f=cfg["moe_intermediate_size"],
        window=cfg["sliding_window"], window_layers=sliding, full_layers=layers - sliding,
    )


def active_matmul_params_per_layer(cfg) -> int:
    """Parameters a token multiplies in one layer: q, k, v and output
    projections, the router, and the ``num_experts_per_tok`` experts it
    is routed to (three matrices each)."""
    c = dims(cfg)
    attn = c["d"] * c["h"] * c["hd"] * 2 + c["d"] * c["hkv"] * c["hd"] * 2
    return attn + c["d"] * c["experts"] + c["k"] * 3 * c["d"] * c["f"]


def matmul_params_per_layer(cfg) -> int:
    """All of a layer's matmul parameters, every expert among them."""
    c = dims(cfg)
    return active_matmul_params_per_layer(cfg) + (c["experts"] - c["k"]) * 3 * c["d"] * c["f"]


def active_matmul_params(cfg) -> int:
    """All layers, and the vocabulary head (the embedding is a gather)."""
    c = dims(cfg)
    return c["layers"] * active_matmul_params_per_layer(cfg) + c["d"] * c["vocab"]


def dense_equivalent(cfg) -> dict[str, int]:
    """The GPT-2-style keys under which ``work.transformer_matmul_params``
    counts exactly ``active_matmul_params``: the width as it is, and the
    MLP width ``n_inner`` at which ``n_layer * (4 d^2 + 2 d n_inner) +
    d * vocab`` equals it (an integer at the published sizes: 10784).
    The accepted ``mfu.serve`` reads these keys, so it reads this cell
    with the active count and no edit."""
    c = dims(cfg)
    rest = active_matmul_params_per_layer(cfg) - 4 * c["d"] * c["d"]
    n_inner = rest // (2 * c["d"]) if rest % (2 * c["d"]) == 0 else rest / (2 * c["d"])
    return {"n_embd": c["d"], "n_inner": n_inner, "n_layer": c["layers"]}


def attention_flops(keys_read: float, cfg) -> float:
    """q.K^T and p.V over the keys a query attends: 4 * head_dim * heads
    a key (``keys_read`` is summed over the layers of a kind, as the
    engine counts it)."""
    c = dims(cfg)
    return 4.0 * c["hd"] * c["h"] * keys_read


def attention_bytes(keys_read: float, cfg, itemsize: int = 2) -> float:
    """K and V rows of the attended keys, read once."""
    c = dims(cfg)
    return 2.0 * c["hkv"] * c["hd"] * itemsize * keys_read


def prefill_keys(prompt_len: int, cfg) -> tuple[float, float]:
    """(keys attended on one full layer, on one sliding layer) by the
    causal pass over a prompt: query t sees t + 1 keys on a full layer
    and min(t + 1, window) on a sliding one."""
    w, n = dims(cfg)["window"], int(prompt_len)
    m = min(n, w)
    return n * (n + 1) / 2.0, m * (m + 1) / 2.0 + max(n - w, 0) * float(w)


def attention_flops_in_window(full_read: float, window_read: float, prompts, cfg) -> float:
    """Attention's FLOPs of a serving window, over the keys inside each
    layer's reach: the decode steps' from the engine's counters (summed
    over layers there), prefill's from the lengths of the prompts
    prefilled."""
    c = dims(cfg)
    for n in prompts:
        full, sliding = prefill_keys(n, cfg)
        full_read += c["full_layers"] * full
        window_read += c["window_layers"] * sliding
    return attention_flops(full_read + window_read, cfg)


def kv_live_share(pages_full: float, pages_window: float, cfg) -> float:
    """Live KV of the two page groups over what one group for every
    layer would hold for the same live tokens (each layer then holds
    what a full layer holds)."""
    c = dims(cfg)
    return (c["full_layers"] * pages_full + c["window_layers"] * pages_window) / max(c["layers"] * pages_full, 1)
