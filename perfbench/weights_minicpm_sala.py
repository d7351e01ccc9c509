"""Shapes and draws of the ``minicpm_sala`` family's weights
(MiniCPM-SALA as ``perfbench/configs/minicpm-sala.json`` states it),
made from the seed on the device like ``weights_deepseek_v2.py`` makes
DeepSeek-V2's, under paths that mirror the program's parameter tree:
``block_i/attn/...`` (``q``, ``k``, ``v``, ``gate``, ``attn_out``
kernels, ``q_norm`` and ``k_norm`` scales, a lightning layer's
``o_norm``), ``block_i/ln_attn``, ``block_i/ln_ffn`` and
``block_i/mlp_{gate,in,out}``.

The recipe is ``weights_deepseek_v2.py``'s: kernels normal with variance
1 / fan-in, norm scales 1 + 0.1 n, the embedding N(0, 1). Three gains are
stated apart, in the configuration file's ``weights`` (its ``assumed``
says why each):

- ``embed_gain`` (1/12) on the embedding: the model multiplies it by
  ``scale_emb`` 12, so the residual stream starts at N(0, 1), and the
  sixteen residual terms of eight layers, each times 1.4 / sqrt(32),
  move it as far as they would in a trained model; at N(0, 1) the
  stream starts at N(0, 144) and no layer moves a logit.
- ``qk_gain`` (1.5) on every ``q_norm`` and ``k_norm`` scale: q and k are
  normed a head, so their kernels' scale does not reach the scores; at
  1.5 each a score ``q . k / sqrt(d)`` is N(0, 2.25^2), as the other
  serving cells set theirs.
- ``head_gain`` (32) on the head's kernel: the model divides the final
  norm's output by ``hidden_size / dim_model_base`` 16, so at 1 the
  logits are N(0, 1/16^2); at 32 they are N(0, 2^2).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Mapping

import numpy as np

from perfbench.weights import seed31
from perfbench.work_minicpm_sala import dims


def minicpm_sala_shapes(cfg: Mapping[str, Any]) -> dict[str, tuple[int, ...]]:
    c = dims(cfg)
    d, h, g, hd = c["d"], c["h"], c["g"], c["hd"]
    shapes: dict[str, tuple[int, ...]] = {"tok_embed/embedding": (c["vocab"], d)}
    for i, kind in enumerate(cfg["mixer_types"]):
        p = f"block_{i}"
        a = f"{p}/attn"
        kv = h if kind == "lightning-attn" else g
        shapes[f"{p}/ln_attn/scale"] = (d,)
        shapes[f"{p}/ln_ffn/scale"] = (d,)
        shapes[f"{a}/q/kernel"] = (d, h * hd)
        shapes[f"{a}/k/kernel"] = (d, kv * hd)
        shapes[f"{a}/v/kernel"] = (d, kv * hd)
        shapes[f"{a}/q_norm/scale"] = (hd,)
        shapes[f"{a}/k_norm/scale"] = (hd,)
        shapes[f"{a}/gate/kernel"] = (d, h * hd)
        shapes[f"{a}/attn_out/kernel"] = (h * hd, d)
        if kind == "lightning-attn":
            shapes[f"{a}/o_norm/scale"] = (h * hd,)
        shapes[f"{p}/mlp_gate/kernel"] = (d, c["f"])
        shapes[f"{p}/mlp_in/kernel"] = (d, c["f"])
        shapes[f"{p}/mlp_out/kernel"] = (c["f"], d)
    shapes["ln_f/scale"] = (d,)
    shapes["lm_head/kernel"] = (d, c["vocab"])
    return shapes


def _kind(path: str) -> str:
    leaf = path.rsplit("/", 1)[-1]
    if path.endswith(("q_norm/scale", "k_norm/scale")):
        return "qk_scale"
    if leaf in ("scale", "embedding"):
        return leaf
    return "head" if path.startswith("lm_head") else "kernel"


def make_weights(cfg: Mapping[str, Any], seed: int, dtype="float32"):
    """All leaves, as a flat ``{path: array}`` dict. One jitted draw a
    leaf (a compile a kind and shape), so the float32 normal of the
    largest leaf, the embedding or the head, is the one temporary."""
    import jax
    import jax.numpy as jnp

    w = cfg.get("weights", {})
    embed_gain = float(w.get("embed_gain", 1.0))
    qk_gain = float(w.get("qk_gain", 1.0))
    head_gain = float(w.get("head_gain", 1.0))

    @partial(jax.jit, static_argnames=("kind", "shape"))
    def draw(key, kind, shape):
        n = jax.random.normal(key, shape, jnp.float32)
        if kind == "scale":
            out = 1.0 + 0.1 * n
        elif kind == "qk_scale":
            out = np.float32(qk_gain) * (1.0 + 0.1 * n)
        elif kind == "embedding":
            out = n * np.float32(embed_gain)
        else:  # [fan_in, fan_out]
            gain = head_gain if kind == "head" else 1.0
            out = n * np.float32(shape[-2] ** -0.5 * gain)
        return out.astype(jnp.dtype(dtype))

    shapes = minicpm_sala_shapes(cfg)
    root = jax.random.key(seed31(seed), impl="rbg")
    return {
        name: draw(jax.random.fold_in(root, i), _kind(name), shapes[name])
        for i, name in enumerate(sorted(shapes))
    }
