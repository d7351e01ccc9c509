"""Shapes and draws of the ``deepseek_v2`` family's weights (DeepSeek-V2
as ``perfbench/configs/deepseek-v2.json`` states it), made from the seed
on the device like ``weights_longcat.py`` makes LongCat's, under paths
that mirror the program's parameter tree: ``block_i/attn/...`` and
``block_i/ln_attn``, ``block_i/ln_ffn``; the dense layers'
``block_i/mlp_{gate,in,out}``, the routed ones' ``block_i/moe/...``
(router, the held experts' stacks, the shared experts' three kernels).

The recipe is ``weights_longcat.py``'s: kernels normal with variance 1 /
fan-in, norm scales 1 + 0.1 n, the embedding N(0, 1). Three gains are
stated apart, in the configuration file's ``weights`` (its ``assumed``
says why each):

- ``q_gain`` (1.25) on the query's second kernel ``W_qb``: the scores
  carry YaRN's whole-score factor 1.5896, so at 1 they are N(0, 1.59^2);
  at 1.25 they are N(0, 2^2), as the other serving cells set theirs.
- ``router_gain`` (0.5) on the router's kernel: logits N(0, 1/4), so
  the six chosen weights times 16 sum to ~1.5, a routed term of the
  stream's size, as ``routed_scaling_factor`` calibrates it; a token's
  three groups and six experts are a near-tie (within 1%) for 9.9% and
  13.3% of tokens, not everywhere. At 2.0 the weights sum to ~7.9 and a
  bfloat16 flip of a heavy expert moved a served logit as far as a wrong
  token does (PERF.md).
- ``held_gain`` (2.83 = sqrt(160 / 20)) on the held experts' last kernel
  ``moe/w_out``: the uncut model adds six routed terms a token, this
  share 0.75 of one; at sqrt(routed / held) the held terms carry, in
  variance over tokens, what the whole routed term carries uncut.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Mapping

import numpy as np

from perfbench.weights import seed31
from perfbench.work_deepseek_v2 import dims


def deepseek_v2_shapes(cfg: Mapping[str, Any]) -> dict[str, tuple[int, ...]]:
    c = dims(cfg)
    d, h, dn, dr, dv = c["d"], c["h"], c["dn"], c["dr"], c["dv"]
    fs = c["shared"] * c["fe"]
    shapes: dict[str, tuple[int, ...]] = {"tok_embed/embedding": (c["vocab"], d)}
    for i in range(c["layers"]):
        p = f"block_{i}"
        a = f"{p}/attn"
        shapes[f"{p}/ln_attn/scale"] = (d,)
        shapes[f"{p}/ln_ffn/scale"] = (d,)
        shapes[f"{a}/q_a/kernel"] = (d, c["qr"])
        shapes[f"{a}/q_a_norm/scale"] = (c["qr"],)
        shapes[f"{a}/q_b/kernel"] = (c["qr"], h * (dn + dr))
        shapes[f"{a}/kv_a/kernel"] = (d, c["kvr"] + dr)
        shapes[f"{a}/kv_a_norm/scale"] = (c["kvr"],)
        shapes[f"{a}/kv_b/kernel"] = (c["kvr"], h * (dn + dv))
        shapes[f"{a}/attn_out/kernel"] = (h * dv, d)
        if i < c["dense_layers"]:
            shapes[f"{p}/mlp_gate/kernel"] = (d, c["f"])
            shapes[f"{p}/mlp_in/kernel"] = (d, c["f"])
            shapes[f"{p}/mlp_out/kernel"] = (c["f"], d)
            continue
        shapes[f"{p}/moe/router/kernel"] = (d, c["routed"])
        shapes[f"{p}/moe/w_gate"] = (c["held"], d, c["fe"])
        shapes[f"{p}/moe/w_in"] = (c["held"], d, c["fe"])
        shapes[f"{p}/moe/w_out"] = (c["held"], c["fe"], d)
        if fs:
            shapes[f"{p}/moe/shared_gate/kernel"] = (d, fs)
            shapes[f"{p}/moe/shared_in/kernel"] = (d, fs)
            shapes[f"{p}/moe/shared_out/kernel"] = (fs, d)
    shapes["ln_f/scale"] = (d,)
    shapes["lm_head/kernel"] = (d, c["vocab"])
    return shapes


def _kind(path: str) -> str:
    leaf = path.rsplit("/", 1)[-1]
    if leaf in ("scale", "embedding"):
        return leaf
    if path.endswith("router/kernel"):
        return "router"
    if path.endswith("moe/w_out"):
        return "held_out"
    return "q_b" if path.endswith("q_b/kernel") else "kernel"


def make_weights(cfg: Mapping[str, Any], seed: int, dtype="float32"):
    """All leaves, as a flat ``{path: array}`` dict. One jitted draw a
    leaf (a compile a kind and shape), so the float32 normal of the
    largest leaf, an expert stack ``[held, d, f]``, is the one
    temporary."""
    import jax
    import jax.numpy as jnp

    w = cfg.get("weights", {})
    gains = {
        "router": float(w.get("router_gain", 1.0)), "q_b": float(w.get("q_gain", 1.0)),
        "held_out": float(w.get("held_gain", 1.0)),
    }

    @partial(jax.jit, static_argnames=("kind", "shape"))
    def draw(key, kind, shape):
        n = jax.random.normal(key, shape, jnp.float32)
        if kind == "scale":
            out = 1.0 + 0.1 * n
        elif kind == "embedding":
            out = n
        else:  # [.., fan_in, fan_out]
            out = n * np.float32(shape[-2] ** -0.5 * gains.get(kind, 1.0))
        return out.astype(jnp.dtype(dtype))

    shapes = deepseek_v2_shapes(cfg)
    # the chip's own bit generator, as weights_longcat.py
    root = jax.random.key(seed31(seed), impl="rbg")
    return {
        name: draw(jax.random.fold_in(root, i), _kind(name), shapes[name])
        for i, name in enumerate(sorted(shapes))
    }
