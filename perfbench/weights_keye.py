"""Shapes and draws of the ``keye`` family's weights (Keye-VL-2.0's
language model as ``perfbench/configs/keye-vl2-30b-a3b.json`` states
it), made from the seed on the device like ``weights.py`` makes the
others, under paths that mirror the program's parameter tree.

Kernels are normal with variance 1 / fan-in, so every projection of a
normalised stream comes out with unit variance and no softmax of the
layer is flat: the router's over 128 experts, the attention's over
thousands of keys, the indexer's scores. Norm scales sit around 1
(1 + 0.1 n). One gain is stated apart, ``weights.qk_gain`` of the
configuration file, on the per-head query norm's scale: with it at 1 the
attention logits ``q . k / sqrt(head_dim)`` are N(0, 1) and a query
spreads its weight so evenly over two thousand keys that WHICH keys were
selected hardly shows in the logits; at the stated gain they are N(0,
gain^2), a few keys carry a query, and a wrong selection reads
(PERF.md, PR 27, gives the readings).
"""

from __future__ import annotations

from typing import Any, Mapping

import numpy as np

from perfbench.weights import seed31


def keye_shapes(cfg: Mapping[str, Any]) -> dict[str, tuple[int, ...]]:
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    h, hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    e, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    sa = cfg["sa_config"]
    j, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    shapes: dict[str, tuple[int, ...]] = {"tok_embed/embedding": (v, d)}
    for i in range(cfg["num_hidden_layers"]):
        p = f"block_{i}"
        shapes[f"{p}/ln1/scale"] = (d,)
        shapes[f"{p}/ln2/scale"] = (d,)
        shapes[f"{p}/attn/q/kernel"] = (d, h * hd)
        shapes[f"{p}/attn/k/kernel"] = (d, hkv * hd)
        shapes[f"{p}/attn/v/kernel"] = (d, hkv * hd)
        shapes[f"{p}/attn/attn_out/kernel"] = (h * hd, d)
        shapes[f"{p}/attn/q_norm/scale"] = (hd,)
        shapes[f"{p}/attn/k_norm/scale"] = (hd,)
        shapes[f"{p}/attn/idx_q/kernel"] = (d, j * di)
        shapes[f"{p}/attn/idx_k/kernel"] = (d, di)
        shapes[f"{p}/attn/idx_k_norm/scale"] = (di,)
        shapes[f"{p}/attn/idx_k_norm/bias"] = (di,)
        shapes[f"{p}/attn/idx_w/kernel"] = (d, j)
        shapes[f"{p}/moe/router/kernel"] = (d, e)
        shapes[f"{p}/moe/w_gate"] = (e, d, f)
        shapes[f"{p}/moe/w_in"] = (e, d, f)
        shapes[f"{p}/moe/w_out"] = (e, f, d)
    shapes["ln_f/scale"] = (d,)
    shapes["lm_head/kernel"] = (d, v)
    return shapes


def _kind(path: str) -> str:
    leaf = path.rsplit("/", 1)[-1]
    if leaf == "scale":
        return "q_scale" if path.endswith("q_norm/scale") else "scale"
    return leaf if leaf in ("bias", "embedding") else "kernel"


def make_weights(cfg: Mapping[str, Any], seed: int, dtype="float32"):
    """All leaves, as a flat ``{path: array}`` dict. One jitted draw a
    leaf (a compile a kind and shape), so the float32 normal of the
    largest leaf, an expert stack ``[E, d, f]``, is the one temporary."""
    from functools import partial

    import jax
    import jax.numpy as jnp

    gain = float(cfg.get("weights", {}).get("qk_gain", 1.0))

    @partial(jax.jit, static_argnames=("kind", "shape"))
    def draw(key, kind, shape):
        n = jax.random.normal(key, shape, jnp.float32)
        if kind in ("scale", "q_scale"):
            w = (gain if kind == "q_scale" else 1.0) + 0.1 * n
        elif kind == "bias":
            w = 0.02 * n
        elif kind == "embedding":
            w = n
        else:  # [.., fan_in, fan_out]
            w = n * np.float32(shape[-2] ** -0.5)
        return w.astype(jnp.dtype(dtype))

    shapes = keye_shapes(cfg)
    # the chip's own bit generator: threefry takes most of a minute for
    # 4.4e9 normals there (same seed, same device kind, same weights)
    root = jax.random.key(seed31(seed), impl="rbg")
    return {
        name: draw(jax.random.fold_in(root, i), _kind(name), shapes[name])
        for i, name in enumerate(sorted(shapes))
    }
