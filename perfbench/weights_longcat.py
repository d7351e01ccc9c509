"""Shapes and draws of the ``longcat_flash`` family's weights (LongCat-
Flash-Chat as ``perfbench/configs/longcat-flash-chat.json`` states it),
made from the seed on the device like ``weights.py`` makes the others,
under paths that mirror the program's parameter tree.

The recipe is ``weights_keye.py``'s: kernels normal with variance 1 /
fan-in, so every projection of a normalised stream comes out with unit
variance; norm scales around 1 (1 + 0.1 n); the embedding N(0, 1).
Four gains are stated apart, in the configuration file's ``weights``:

- ``q_gain`` (0.35) on the query's second kernel ``W_qb``. At 1 the
  published ``mla_scale_q_lora`` (2) and ``mla_scale_kv_lora`` (3.46)
  make the scores ``(q_nope . k_nope + q_rope . k_rope) / sqrt(192)``
  come out near N(0, 5.8^2): one key carries a query, every rounding of
  a score moves the winner, and a sound bfloat16 run on the chip read a
  mean served-logit gap of 0.32, above one planted fault's and half
  another's (PERF.md, PR 34). At 0.35 they are N(0, 2^2), what
  ``weights_keye.py`` and ``weights_mellum2.py`` set by their
  ``qk_gain``: a few keys carry a query, and a wrong key, a missing
  scale or a wrong rotation still reads in the logits.

- ``router_gain`` (2.0) on the router's kernel: its logits are N(0, 4)
  where 1 would give every one of 768 outputs a score near 1/768 and
  the top-12 a near-tie throughout. At 2 a token's best score is ~0.07
  and its twelfth ~0.01; times ``routed_scaling_factor`` 6 the chosen
  weights sum to ~1.5, of which the zero-compute experts carry a third
  (``w * x`` on a unit-variance ``x``) and the 16 held experts a
  fiftieth.
- ``held_gain`` (5.66 = sqrt(512 / 16)) on the held experts' last kernel
  ``moe/w_out``. The uncut model adds the terms of all ~8 routed experts
  a token chose; this chip's share adds 0.25 of one, so at gain 1 the
  routed term has a 32nd of its variance and moves a logit by less than
  bfloat16 rounding does: the least-weighted held expert left out (a
  token that chose a held one chose one, so nearly every held term) read
  a mean served-logit gap of 0.0015 beside sound runs at up to 0.0007,
  and ``correct`` could not see the grouped matmuls (PERF.md, PR 34). At
  sqrt(routed / held) the held experts' terms carry, in variance over
  tokens, what the whole routed term carries in the uncut model: the
  experts' term is a visible part of a logit, as the issue asks. Read
  on the chip over twelve seeds, that fault is then 0.012-0.19 on eleven
  (median 0.050) beside sound runs at 0.0003-0.0024; what it costs is a
  wider widest gap of a sound run (0.99 once where 0.14 was the most):
  a router near-tie that bfloat16 flips now moves a whole held term.
- ``choice_bias_std`` (0.01) is the spread of ``e_score_correction_bias``
  (the trained buffer is not published as a number; this is a seeded
  draw): as large as the twelfth score, so the bias really moves the
  choice, and a weight that counted it in would move by a tenth to all
  of itself.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Mapping

import numpy as np

from perfbench.weights import seed31


def longcat_shapes(cfg: Mapping[str, Any]) -> dict[str, tuple[int, ...]]:
    d, v, h = cfg["hidden_size"], cfg["vocab_size"], cfg["num_attention_heads"]
    qr, kvr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    f, fe = cfg["ffn_hidden_size"], cfg["expert_ffn_hidden_size"]
    held = cfg["n_routed_experts"]
    routed = (cfg.get("published") or {}).get("n_routed_experts", held)
    outputs = routed + cfg["zero_expert_num"]
    shapes: dict[str, tuple[int, ...]] = {"tok_embed/embedding": (v, d)}
    for i in range(cfg["num_layers"]):
        p = f"block_{i}"
        for j in (0, 1):
            a = f"{p}/attn_{j}"
            shapes[f"{p}/ln_a{j}/scale"] = (d,)
            shapes[f"{p}/ln_p{j}/scale"] = (d,)
            shapes[f"{a}/q_a/kernel"] = (d, qr)
            shapes[f"{a}/q_a_norm/scale"] = (qr,)
            shapes[f"{a}/q_b/kernel"] = (qr, h * (dn + dr))
            shapes[f"{a}/kv_a/kernel"] = (d, kvr + dr)
            shapes[f"{a}/kv_a_norm/scale"] = (kvr,)
            shapes[f"{a}/kv_b/kernel"] = (kvr, h * (dn + dv))
            shapes[f"{a}/attn_out/kernel"] = (h * dv, d)
            shapes[f"{p}/mlp_{j}_gate/kernel"] = (d, f)
            shapes[f"{p}/mlp_{j}_in/kernel"] = (d, f)
            shapes[f"{p}/mlp_{j}_out/kernel"] = (f, d)
        shapes[f"{p}/moe/router/kernel"] = (d, outputs)
        shapes[f"{p}/moe/choice_bias"] = (outputs,)
        shapes[f"{p}/moe/w_gate"] = (held, d, fe)
        shapes[f"{p}/moe/w_in"] = (held, d, fe)
        shapes[f"{p}/moe/w_out"] = (held, fe, d)
    shapes["ln_f/scale"] = (d,)
    shapes["lm_head/kernel"] = (d, v)
    return shapes


def _kind(path: str) -> str:
    leaf = path.rsplit("/", 1)[-1]
    if leaf in ("scale", "embedding", "choice_bias"):
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
    bias_std = float(w.get("choice_bias_std", 0.0))

    @partial(jax.jit, static_argnames=("kind", "shape"))
    def draw(key, kind, shape):
        n = jax.random.normal(key, shape, jnp.float32)
        if kind == "scale":
            out = 1.0 + 0.1 * n
        elif kind == "embedding":
            out = n
        elif kind == "choice_bias":
            out = n * np.float32(bias_std)
        else:  # [.., fan_in, fan_out]
            out = n * np.float32(shape[-2] ** -0.5 * gains.get(kind, 1.0))
        return out.astype(jnp.dtype(dtype))

    shapes = longcat_shapes(cfg)
    # the chip's own bit generator, as weights_mellum2.py
    root = jax.random.key(seed31(seed), impl="rbg")
    return {
        name: draw(jax.random.fold_in(root, i), _kind(name), shapes[name])
        for i, name in enumerate(sorted(shapes))
    }
