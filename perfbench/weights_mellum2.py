"""Shapes and draws of the ``mellum`` family's weights (Mellum2 as
``perfbench/configs/mellum2-12b-a2.5b.json`` states it), made from the
seed on the device like ``weights.py`` makes the others, under paths
that mirror the program's parameter tree.

The recipe is ``weights_keye.py``'s, for its reasons: kernels normal
with variance 1 / fan-in, so every projection of a normalised stream
comes out with unit variance and no softmax of the layer is flat (the
router's over 64 experts, the attention's over a thousand or thirty
thousand keys); norm scales around 1 (1 + 0.1 n). One gain is stated
apart, ``weights.qk_gain`` of the configuration file, the mean of the
per-head query norm's scale: at 1 the attention logits ``q . k /
sqrt(head_dim)`` are N(0, 1) and a query spreads its weight so evenly
over its keys that WHICH keys it saw (a window a page short, a window
layer run as a full one) hardly shows in the logits; at the stated gain
they are N(0, gain^2), a few keys carry a query, and a wrong window
reads. On the full layers the scores carry YaRN's ``attention_factor``
squared besides (1.63 as published).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Mapping

import numpy as np

from perfbench.weights import seed31


def mellum_shapes(cfg: Mapping[str, Any]) -> dict[str, tuple[int, ...]]:
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    h, hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    e, f = cfg["num_experts"], cfg["moe_intermediate_size"]
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
    return "embedding" if leaf == "embedding" else "kernel"


def make_weights(cfg: Mapping[str, Any], seed: int, dtype="float32"):
    """All leaves, as a flat ``{path: array}`` dict. One jitted draw a
    leaf (a compile a kind and shape), so the float32 normal of the
    largest leaf, an expert stack ``[E, d, f]``, is the one temporary."""
    import jax
    import jax.numpy as jnp

    gain = float(cfg.get("weights", {}).get("qk_gain", 1.0))

    @partial(jax.jit, static_argnames=("kind", "shape"))
    def draw(key, kind, shape):
        n = jax.random.normal(key, shape, jnp.float32)
        if kind in ("scale", "q_scale"):
            w = (gain if kind == "q_scale" else 1.0) + 0.1 * n
        elif kind == "embedding":
            w = n
        else:  # [.., fan_in, fan_out]
            w = n * np.float32(shape[-2] ** -0.5)
        return w.astype(jnp.dtype(dtype))

    shapes = mellum_shapes(cfg)
    # the chip's own bit generator: threefry takes most of a minute for
    # the 3.8e9 normals there (same seed, same device kind, same weights)
    root = jax.random.key(seed31(seed), impl="rbg")
    return {
        name: draw(jax.random.fold_in(root, i), _kind(name), shapes[name])
        for i, name in enumerate(sorted(shapes))
    }
