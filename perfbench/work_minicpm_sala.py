"""Operations and bytes the ``minicpm_sala`` family's algorithms need,
from shapes and from the engine's own counters. They count what the
mathematics requires whatever implements it: a lightning state read and
written once an update, a chosen key and value read once, a compressed
key read once a query group that scores it; padding, masked-out work,
re-reads and the selection's bookkeeping are not counted.
"""

from __future__ import annotations

from typing import Any, Mapping

STATE_BYTES = 4  # the lightning state is float32
ITEM = 2  # bfloat16 weights, pools, activations


def dims(cfg: Mapping[str, Any]) -> dict[str, int]:
    sc = cfg.get("sparse_config") or {}
    kinds = list(cfg["mixer_types"])
    return dict(
        d=cfg["hidden_size"], layers=cfg["num_hidden_layers"], vocab=cfg["vocab_size"],
        h=cfg["num_attention_heads"], g=cfg["num_key_value_heads"], hd=cfg["head_dim"],
        f=cfg["intermediate_size"], lightning=kinds.count("lightning-attn"),
        sparse=kinds.count("minicpm4"), kernel_size=sc.get("kernel_size", 32),
        kernel_stride=sc.get("kernel_stride", 16), block_size=sc.get("block_size", 64),
        window=sc.get("window_size", 2048), topk=sc.get("topk", 64),
        dense_len=sc.get("dense_len", 8192),
    )


def as_published(cfg: Mapping[str, Any]) -> tuple[dict[str, Any], tuple[int, ...]]:
    """A configuration file cut in depth, as the program's builder takes
    it (``minicpm_sala_model_config``): the keys with ``num_hidden_layers``
    and ``mixer_types`` back at the published model's, and the published
    indices of the layers kept here (``kept_layers``; all where the file
    is uncut). The kept layers' kinds must be the file's."""
    pub = cfg.get("published") or {}
    layers = pub.get("num_hidden_layers", cfg["num_hidden_layers"])
    mixers = list(pub.get("mixer_types", cfg["mixer_types"]))
    kept = tuple(cfg.get("kept_layers", range(cfg["num_hidden_layers"])))
    if [mixers[i] for i in kept] != list(cfg["mixer_types"]):
        raise ValueError(
            f"kept_layers {kept} name layers of kinds {[mixers[i] for i in kept]}, "
            f"the file's mixer_types are {cfg['mixer_types']}"
        )
    return {**cfg, "num_hidden_layers": layers, "mixer_types": mixers}, kept


def lightning_layer_params(cfg) -> int:
    """q, k, v, the gate and the output (five of ``d x H hd``) and the
    SwiGLU."""
    c = dims(cfg)
    return 5 * c["d"] * c["h"] * c["hd"] + 3 * c["d"] * c["f"]


def sparse_layer_params(cfg) -> int:
    """q, the gate and the output (``d x H hd``), k and v (``d x G hd``)
    and the SwiGLU."""
    c = dims(cfg)
    return 3 * c["d"] * c["h"] * c["hd"] + 2 * c["d"] * c["g"] * c["hd"] + 3 * c["d"] * c["f"]


def active_matmul_params(cfg) -> int:
    """Every layer's matrices and the head (the embedding is a gather)."""
    c = dims(cfg)
    return (
        c["lightning"] * lightning_layer_params(cfg) + c["sparse"] * sparse_layer_params(cfg)
        + c["d"] * c["vocab"]
    )


def dense_equivalent(cfg) -> dict[str, float]:
    """The GPT-2-style keys under which ``work.transformer_matmul_params``
    counts exactly ``active_matmul_params``: the width as it is, and the
    MLP width ``n_inner`` at which ``n_layer * (4 d^2 + 2 d n_inner) + d
    * vocab`` equals it. The accepted ``mfu.serve`` reads these keys."""
    c = dims(cfg)
    per_layer = (active_matmul_params(cfg) - c["d"] * c["vocab"]) / c["layers"]
    return {"n_embd": c["d"], "n_inner": (per_layer - 4 * c["d"] * c["d"]) / (2 * c["d"]), "n_layer": c["layers"]}


# ---- the kernels ------------------------------------------------------------


def lightning_decode_flops(updates: float, cfg) -> float:
    """A (slot, layer) update, every head: the decay and the token's
    outer product into the state (a multiply-add an element) and the
    read-out ``q S`` (a multiply-add an element): ``4 H d^2``."""
    c = dims(cfg)
    return 4.0 * c["h"] * c["hd"] ** 2 * updates


def lightning_decode_bytes(updates: float, cfg) -> float:
    """The state read and written once an update; q, k, v and o are
    ``1/d`` of that and not counted."""
    c = dims(cfg)
    return 2.0 * STATE_BYTES * c["h"] * c["hd"] ** 2 * updates


def lightning_chunk_flops(rows: float, pairs: float, cfg) -> float:
    """Chunks of ``rows`` real positions in all, with ``pairs`` causal
    (query, key) pairs inside them, a lightning layer: ``Q K^T`` and its
    product with ``V`` over the pairs (``4 H d`` a pair), the state's
    read-out and update (``4 H d^2`` a row)."""
    c = dims(cfg)
    return 4.0 * c["h"] * c["hd"] * pairs + 4.0 * c["h"] * c["hd"] ** 2 * rows


def lightning_chunk_bytes(rows: float, chunks: float, cfg) -> float:
    """q, k and v read (bfloat16) and o written (float32) a row; the
    slot's state read and written once a chunk."""
    c = dims(cfg)
    return (3 * ITEM + 4) * c["h"] * c["hd"] * rows + 2.0 * STATE_BYTES * c["h"] * c["hd"] ** 2 * chunks


def sparse_select_flops(scored: float, cfg) -> float:
    """A compressed key scored by a KV group's query heads (the engine's
    ``sparse_scored_kernels`` counts (key, group) pairs): ``2 (H/G) d``."""
    c = dims(cfg)
    return 2.0 * (c["h"] // c["g"]) * c["hd"] * scored


def sparse_select_bytes(scored: float, cfg) -> float:
    """Each (compressed key, group) read once, its ``d`` lanes."""
    c = dims(cfg)
    return float(ITEM * c["hd"]) * scored


def sparse_attn_flops(selected: float, cfg) -> float:
    """Scores and values over a chosen (position, group): ``4 (H/G) d``."""
    c = dims(cfg)
    return 4.0 * (c["h"] // c["g"]) * c["hd"] * selected


def sparse_attn_bytes(selected: float, cfg) -> float:
    """A chosen (position, group)'s key and value read once."""
    c = dims(cfg)
    return 2.0 * ITEM * c["hd"] * selected


def needed(kind: str, counts: Mapping[str, float], cfg) -> tuple[float, float]:
    """(FLOPs, bytes) of one kernel over the traced steps, from the
    counters the driver leaves under ``counts.traced``."""
    if kind == "lightning_decode":
        u = counts["lightning_state_updates"]
        return lightning_decode_flops(u, cfg), lightning_decode_bytes(u, cfg)
    if kind == "lightning_chunk":
        n = dims(cfg)["lightning"]
        rows, pairs, chunks = (n * counts[k] for k in ("chunk_rows", "chunk_pairs", "chunks"))
        return lightning_chunk_flops(rows, pairs, cfg), lightning_chunk_bytes(rows, chunks, cfg)
    if kind == "sparse_select":
        s = counts["sparse_scored_kernels"]
        return sparse_select_flops(s, cfg), sparse_select_bytes(s, cfg)
    if kind == "sparse_attn":
        s = counts["sparse_selected_tokens"]
        return sparse_attn_flops(s, cfg), sparse_attn_bytes(s, cfg)
    raise ValueError(f"unknown work {kind!r}")


def attended_positions(t: int, cfg) -> int:
    """Positions a query at ``t`` attends in a block-sparse layer: every
    one below ``dense_len``, else ``topk`` blocks' worth (the most; the
    last block is partly in the future)."""
    c = dims(cfg)
    return t + 1 if t < c["dense_len"] else min(t + 1, c["topk"] * c["block_size"])


def prompt_attention_flops(n: int, chunk: int, cfg) -> float:
    """A prompt of ``n`` tokens prefilled by chunks of ``chunk``: the
    lightning layers' intra-chunk pairs and state products, the
    block-sparse layers' scores and values over what each query attends
    (its selection scored over the compressed keys not counted)."""
    c = dims(cfg)
    pairs = sum(m * (m + 1) / 2.0 for m in (min(chunk, n - o) for o in range(0, n, chunk)))
    lightning = lightning_chunk_flops(n, pairs, cfg) * c["lightning"]
    dense = min(n, c["dense_len"])
    cap = c["topk"] * c["block_size"]
    attended = dense * (dense + 1) / 2.0 + sum(min(t + 1, cap) for t in range(dense, n))
    return lightning + c["sparse"] * 4.0 * c["h"] * c["hd"] * attended


def attention_flops_in_window(window: Mapping[str, float], prompts, chunk: int, cfg) -> float:
    """Attention's FLOPs of a serving window: the decode steps' by the
    engine's counters (lightning updates, chosen positions, compressed
    keys scored), and each prompt prefilled in it."""
    return (
        lightning_decode_flops(window["lightning_state_updates"], cfg)
        + sparse_attn_flops(window["sparse_selected_tokens"], cfg)
        + sparse_select_flops(window["sparse_scored_kernels"], cfg)
        + sum(prompt_attention_flops(n, chunk, cfg) for n in prompts)
    )
