"""Plain forward of Mellum2-12B-A2.5B-Instruct: jax.numpy, float32,
matmuls at "highest", full causal pass, no cache, no pages, no kernels.
The layer of kind ``layer_types[l]``, as the configuration file states it
(``x`` the residual stream, every matmul without bias):

1. ``h = RMSNorm(x)``; ``q = W_q h`` as ``num_attention_heads`` heads of
   ``head_dim``, ``k = W_k h`` and ``v = W_v h`` as
   ``num_key_value_heads`` heads (GQA); ``q``, ``k`` each through a
   per-head RMSNorm over ``head_dim`` (learned scale).
2. RoPE by the token's position, dimension ``i`` turning with ``i +
   head_dim / 2``, by the layer kind's entry of ``rope_parameters``:
   ``default`` is ``inv_freq_i = theta^(-2i / head_dim)``; ``yarn`` keeps
   a frequency that turns more than ``beta_fast`` times over
   ``original_max_position_embeddings``, divides one that turns less
   than ``beta_slow`` times by ``factor``, blends those between linearly
   in ``i`` (``yarn_inv_freq``), and multiplies cos and sin by
   ``attention_factor``.
3. ``a_t = sum_s softmax_s(q_t . k_s / sqrt(head_dim)) v_s`` over ``s <=
   t``, and on a ``sliding_attention`` layer over ``t - sliding_window <
   s`` only; ``x = x + W_o a``.
4. ``h = RMSNorm(x)``; ``p = softmax(W_r h)`` over the experts;
   ``E_t`` = top ``num_experts_per_tok`` of ``p``, weights
   ``p_e / sum_{E_t} p``; ``x = x + sum_e weight_e W2_e(silu(W1_e h) *
   W3_e h)``.

Final RMSNorm, untied head. It takes one sequence, and is computed in
blocks so that 33k tokens at the published widths fit one chip (run so on the chip, PR 32): queries
a block at a time, against all keys on a full layer and against the keys
a window reaches on a sliding one; experts one at a time over the rows
routed to them; logits at the asked positions only. Weights stay in the
type they were made in and are upcast a matrix at a time (exact).
Nothing is imported from the program.

``quant`` is the control (every weight matmul on rounded operands,
``common.matmul``); ``fault`` plants one of the mistakes an
implementation could make, for setting the limits: ``"window_as_full"``
(the sliding layers see every key), ``"window_short"`` /
``"window_long"`` (the window a 64th short or long: a page of 16 at
the published 1,024),
``"rope_default"`` (default RoPE on the full layers), ``"drop_expert"``
(the least of a token's experts left out).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Mapping

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.reference.common import HIGHEST, matmul

QUERY_BLOCK = 512
# Shapes are bucketed so that a run's requests, all of other lengths,
# compile few programs (on the chip a pass over 30k tokens computes in
# seconds and compiles in a minute): a sequence is padded to one of
# SEQUENCE_BUCKETS or, past the last, to a multiple of it (six lengths up
# to 33k tokens: five to the cell's 16,896), an expert's rows to EXPERT_ROW_BLOCK times a
# power of two, the asked positions to a multiple of AT_BLOCK. Padding
# tokens sit after the real ones, where no real query sees them; padding
# rows carry weight 0.
SEQUENCE_BUCKETS = (512, 2048, 8192)
EXPERT_ROW_BLOCK = 256
AT_BLOCK = 128


def padded_length(t: int) -> int:
    for bucket in SEQUENCE_BUCKETS:
        if t <= bucket:
            return bucket
    return -(-t // SEQUENCE_BUCKETS[-1]) * SEQUENCE_BUCKETS[-1]


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * scale


def yarn_inv_freq(head_dim: int, rope: Mapping[str, Any]) -> tuple[np.ndarray, float]:
    """(the ``head_dim / 2`` rotary frequencies, the factor on cos and
    sin) of one entry of ``rope_parameters``, in float64."""
    theta, half = float(rope["rope_theta"]), head_dim // 2
    i = np.arange(half, dtype=np.float64)
    inv = theta ** (-i / half)
    if rope.get("rope_type", "default") == "default":
        return inv, 1.0
    factor, orig = float(rope["factor"]), float(rope["original_max_position_embeddings"])

    def index_turning(r):  # the index whose frequency turns r times over the original context
        return head_dim * math.log(orig / (2 * math.pi * r)) / (2 * math.log(theta))

    low = max(math.floor(index_turning(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(index_turning(float(rope["beta_slow"]))), head_dim - 1)
    ramp = np.clip((i - low) / ((high if high != low else high + 0.001) - low), 0.0, 1.0)
    scale = rope.get("attention_factor")
    return inv * ((1.0 - ramp) + ramp / factor), float(0.1 * math.log(factor) + 1.0 if scale is None else scale)


def _rope(x, positions, inv_freq, scale):
    """x [T, H, D]: dimension i turns with dimension i + D/2."""
    half = x.shape[-1] // 2
    ang = positions.astype(jnp.float32)[:, None] * inv_freq
    sin, cos = jnp.sin(ang)[:, None, :] * scale, jnp.cos(ang)[:, None, :] * scale
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@partial(jax.jit, static_argnames=("h", "hkv", "d", "eps", "scale", "quant"))
def _project(p, x, positions, inv_freq, h, hkv, d, eps, scale, quant):
    t = x.shape[0]
    hn = _rms(x, p["ln1/scale"].astype(jnp.float32), eps)
    q = matmul(hn, p["attn/q/kernel"], quant).reshape(t, h, d)
    k = matmul(hn, p["attn/k/kernel"], quant).reshape(t, hkv, d)
    v = matmul(hn, p["attn/v/kernel"], quant).reshape(t, hkv, d)
    q = _rms(q, p["attn/q_norm/scale"].astype(jnp.float32), eps)
    k = _rms(k, p["attn/k_norm/scale"].astype(jnp.float32), eps)
    return _rope(q, positions, inv_freq, scale), _rope(k, positions, inv_freq, scale), v


@partial(jax.jit, static_argnames=("window",))
def _attend_block(q, k, v, q_pos, k_pos, window):
    """One block of queries [C, H, D] at positions ``q_pos`` against the
    keys [S, Hkv, D] at positions ``k_pos``: causal, and with ``window``
    no further back than it."""
    c, h, d = q.shape
    hkv = k.shape[1]
    seen = k_pos[None, :] <= q_pos[:, None]
    if window:
        seen = seen & (k_pos[None, :] > q_pos[:, None] - window)
    qg = q.reshape(c, hkv, h // hkv, d)
    out = []
    for g in range(hkv):  # one KV head's scores at a time
        sc = jnp.einsum("cgd,sd->gcs", qg[:, g], k[:, g], precision=HIGHEST) / jnp.sqrt(jnp.float32(d))
        a = jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), axis=-1)
        out.append(jnp.einsum("gcs,sd->cgd", a, v[:, g], precision=HIGHEST))
    return jnp.stack(out, axis=1).reshape(c, h, d)


@partial(jax.jit, static_argnames=("eps", "k", "quant"))
def _route(x, ln2, router, eps, k, quant):
    h = _rms(x, ln2.astype(jnp.float32), eps)
    p = jax.nn.softmax(matmul(h, router, quant), axis=-1)
    top = jnp.argsort(-p, axis=-1, stable=True)[:, :k]
    pk = jnp.take_along_axis(p, top, axis=-1)
    return h, top, pk / jnp.sum(pk, -1, keepdims=True)


@partial(jax.jit, static_argnames=("quant",))
def _expert(h_rows, w1, w3, w2, quant):
    return matmul(jax.nn.silu(matmul(h_rows, w1, quant)) * matmul(h_rows, w3, quant), w2, quant)


@partial(jax.jit, static_argnames=("quant",))
def _out_proj(x, a, wo, quant):
    return x + matmul(a.reshape(a.shape[0], -1), wo, quant)


@partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x_rows, ln_f, head, eps, quant):
    return matmul(_rms(x_rows, ln_f.astype(jnp.float32), eps), head, quant)


def layer_window(cfg: Mapping[str, Any], layer: int, fault: str | None = None) -> int:
    """Keys a query of ``layer`` sees back, itself among them; 0 = all."""
    if cfg["layer_types"][layer] != "sliding_attention" or fault == "window_as_full":
        return 0
    window = int(cfg["sliding_window"])
    miss = max(1, window // 64)  # a page of 16 at the published 1,024
    return window + {"window_short": -miss, "window_long": miss}.get(fault, 0)


def forward(
    params: Mapping[str, Any], tokens, cfg: Mapping[str, Any], at=None, quant: str | None = None,
    fault: str | None = None,
):
    """``tokens`` [T] -> float32 logits [len(at), V] (every position
    where ``at`` is None)."""
    h, hkv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps, top_k = cfg["rms_norm_eps"], cfg["num_experts_per_tok"]
    rope = {kind: yarn_inv_freq(d, entry) for kind, entry in cfg["rope_parameters"].items()}
    if fault == "rope_default":
        rope["full_attention"] = yarn_inv_freq(
            d, {"rope_theta": cfg["rope_parameters"]["full_attention"]["rope_theta"]}
        )
    tokens = np.asarray(tokens, np.int32)
    t = len(tokens)
    block, t_pad = QUERY_BLOCK, padded_length(t)
    ids = np.zeros((t_pad,), np.int32)
    ids[:t] = tokens
    positions = jnp.arange(t_pad)
    x = params["tok_embed/embedding"][jnp.asarray(ids)].astype(jnp.float32)
    for i in range(cfg["num_hidden_layers"]):
        pre = f"block_{i}/"
        p = {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}
        inv_freq, scale = rope[cfg["layer_types"][i]]
        q, k, v = _project(
            p, x, positions, jnp.asarray(inv_freq, jnp.float32), h=h, hkv=hkv, d=d, eps=eps, scale=scale,
            quant=quant,
        )
        window = layer_window(cfg, i, fault)
        # the keys a block of queries can see lie in a span of this many
        # rows that ends with the block (all of them on a full layer)
        span = -(-(window + block) // block) * block if window else t_pad
        outs = []
        for lo in range(0, t_pad, block):
            first = max(0, lo + block - span) if window else 0
            keys = slice(first, lo + block if window else t_pad)
            outs.append(_attend_block(
                q[lo:lo + block], k[keys], v[keys], positions[lo:lo + block], positions[keys], window=window,
            ))
        x = _out_proj(x, jnp.concatenate(outs, 0), p["attn/attn_out/kernel"], quant)
        del q, k, v, outs

        hn, top, weight = _route(x, p["ln2/scale"], p["moe/router/kernel"], eps, top_k, quant)
        top_h, weight_h = np.asarray(top), np.asarray(weight)
        if fault == "drop_expert":  # the least of each token's experts never runs
            top_h, weight_h = top_h[:, :-1], weight_h[:, :-1]
        y = jnp.zeros_like(x)
        for e in range(cfg["num_experts"]):
            rows, col = np.nonzero(top_h == e)
            if len(rows) == 0:
                continue
            n = EXPERT_ROW_BLOCK << max(0, (len(rows) - 1) // EXPERT_ROW_BLOCK).bit_length()
            idx = np.zeros((n,), np.int32)
            idx[: len(rows)] = rows
            wt = np.zeros((n,), np.float32)
            wt[: len(rows)] = weight_h[rows, col]
            out = _expert(hn[jnp.asarray(idx)], p["moe/w_gate"][e], p["moe/w_in"][e], p["moe/w_out"][e], quant)
            y = y.at[jnp.asarray(idx)].add(out * jnp.asarray(wt)[:, None])
        x = x + y
    at = np.arange(t) if at is None else np.asarray(at)
    rows = np.full((-(-len(at) // AT_BLOCK) * AT_BLOCK,), at[-1], at.dtype)
    rows[: len(at)] = at
    return _head(x[jnp.asarray(rows)], params["ln_f/scale"], params["lm_head/kernel"], eps, quant)[: len(at)]
