"""Plain forward of Keye-VL-2.0-30B-A3B's language model: jax.numpy,
float32, matmuls at "highest", full causal pass, no cache, no pages, no
kernels. The layer, as the configuration file states it (``x`` the
residual stream, every matmul without bias):

1. ``h = RMSNorm(x)``; ``q = W_q h`` as ``num_attention_heads`` heads of
   ``head_dim``, ``k = W_k h`` and ``v = W_v h`` as
   ``num_key_value_heads`` heads (GQA); ``q``, ``k`` each through a
   per-head RMSNorm over ``head_dim`` (learned scale); RoPE, base
   ``rope_theta``, over the whole head, by the token's position.
2. Indexer: ``qI = W_qI h`` as ``indexer_num_heads`` heads of
   ``indexer_head_dim``, ``kI = LayerNorm(W_kI h)`` one head,
   ``w = W_w h``; RoPE on both by position.
   ``I[t,s] = sum_j w[t,j] relu(qI[t,j] . kI[s])``; ``S_t`` = the
   ``topk`` positions ``s <= t`` with the largest ``I[t,s]`` (all while
   ``t < topk``), equal scores to the lower index.
3. ``a_t = sum_{s in S_t} softmax_s(q_t . k_s / sqrt(head_dim)) v_s``;
   ``x = x + W_o a``.
4. ``h = RMSNorm(x)``; ``p = softmax(W_r h)`` over the experts;
   ``E_t`` = top ``num_experts_per_tok`` of ``p``, weights
   ``p_e / sum_{E_t} p``; ``x = x + sum_e weight_e W2_e(silu(W1_e h) *
   W3_e h)``.

Final RMSNorm, untied head. It takes one sequence, and is computed in
blocks so that 16.9k tokens at the published widths fit one chip:
queries a block at a time against all keys, experts one at a time over
the rows routed to them, logits at the asked positions only. Weights
stay in the type they were made in and are upcast a matrix at a time
(exact). Nothing is imported from the program.

``quant`` is the control (every weight matmul on rounded operands,
``common.matmul``); ``fault`` plants one of the mistakes an
implementation could make, for setting the limits: ``"window"`` (the
most recent ``topk`` tokens in the selection's place), ``"topk_half"``
(half as many selected), ``"drop_expert"`` (the least of a token's
experts left out).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Mapping

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.reference.common import HIGHEST, matmul

QUERY_BLOCK = 512
# Shapes are bucketed so that a run's requests, all of other lengths,
# compile few programs: a long sequence is padded to a multiple of
# SEQUENCE_BUCKET, an expert's rows to EXPERT_ROW_BLOCK times a power of
# two. Padding tokens sit after the real ones, where no real query sees
# them; padding rows carry weight 0.
SEQUENCE_BUCKET = 2048
EXPERT_ROW_BLOCK = 256


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * scale


def _layer_norm(x, scale, bias, eps=1e-6):
    m = jnp.mean(x, -1, keepdims=True)
    v = jnp.mean(jnp.square(x - m), -1, keepdims=True)
    return (x - m) * jax.lax.rsqrt(v + eps) * scale + bias


def _rope(x, positions, base):
    """x [T, H, D]: dimension i turns with dimension i + D/2."""
    half = x.shape[-1] // 2
    freqs = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freqs
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _dims(cfg):
    sa = cfg["sa_config"]
    return dict(
        h=cfg["num_attention_heads"], hkv=cfg["num_key_value_heads"], d=cfg["head_dim"],
        j=sa["indexer_num_heads"], di=sa["indexer_head_dim"], topk=sa["topk"],
        eps=cfg["rms_norm_eps"], base=float(cfg["rope_theta"]),
        e=cfg["num_experts"], k=cfg["num_experts_per_tok"],
    )


@partial(jax.jit, static_argnames=("cfg_key", "quant"))
def _project(p, x, positions, cfg_key, quant):
    c = dict(cfg_key)
    t = x.shape[0]
    h = _rms(x, p["ln1/scale"].astype(jnp.float32), c["eps"])
    q = matmul(h, p["attn/q/kernel"], quant).reshape(t, c["h"], c["d"])
    k = matmul(h, p["attn/k/kernel"], quant).reshape(t, c["hkv"], c["d"])
    v = matmul(h, p["attn/v/kernel"], quant).reshape(t, c["hkv"], c["d"])
    q = _rms(q, p["attn/q_norm/scale"].astype(jnp.float32), c["eps"])
    k = _rms(k, p["attn/k_norm/scale"].astype(jnp.float32), c["eps"])
    q, k = _rope(q, positions, c["base"]), _rope(k, positions, c["base"])
    qi = matmul(h, p["attn/idx_q/kernel"], quant).reshape(t, c["j"], c["di"])
    ki = _layer_norm(
        matmul(h, p["attn/idx_k/kernel"], quant),
        p["attn/idx_k_norm/scale"].astype(jnp.float32), p["attn/idx_k_norm/bias"].astype(jnp.float32),
    )
    w = matmul(h, p["attn/idx_w/kernel"], quant)
    qi = _rope(qi, positions, c["base"])
    ki = _rope(ki[:, None, :], positions, c["base"])[:, 0]
    return q, k, v, qi, ki, w


@partial(jax.jit, static_argnames=("topk", "window"))
def _attend_block(q, k, v, qi, ki, w, q_pos, topk, window):
    """One block of queries [C, ...] at positions ``q_pos`` against all
    keys [T, ...]: the selection as a mask [C, T], and the attention
    output [C, H, D]."""
    c, h, d = q.shape
    t, hkv = k.shape[0], k.shape[1]
    s_pos = jnp.arange(t)
    causal = s_pos[None, :] <= q_pos[:, None]
    if window:
        sel = causal & (s_pos[None, :] > q_pos[:, None] - topk)
    else:
        dots = jnp.einsum("cjd,sd->cjs", qi, ki, precision=HIGHEST)
        score = jnp.einsum("cjs,cj->cs", jax.nn.relu(dots), w, precision=HIGHEST)
        score = jnp.where(causal, score, -jnp.inf)
        # a stable sort of the negated scores: the largest first, equal
        # scores in the order of their positions
        order = jnp.argsort(-score, axis=-1, stable=True)[:, : min(topk, t)]
        sel = jnp.zeros((c, t), bool).at[jnp.arange(c)[:, None], order].set(True) & causal
    qg = q.reshape(c, hkv, h // hkv, d)
    out = []
    for g in range(hkv):  # one KV head's scores at a time
        sc = jnp.einsum("cgd,sd->gcs", qg[:, g], k[:, g], precision=HIGHEST) / jnp.sqrt(jnp.float32(d))
        a = jax.nn.softmax(jnp.where(sel[None], sc, -jnp.inf), axis=-1)
        out.append(jnp.einsum("gcs,sd->cgd", a, v[:, g], precision=HIGHEST))
    return sel, jnp.stack(out, axis=1).reshape(c, h, d)


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


def forward(
    params: Mapping[str, Any], tokens, cfg: Mapping[str, Any], at=None, quant: str | None = None,
    fault: str | None = None, return_selection: bool = False,
):
    """``tokens`` [T] -> float32 logits [len(at), V] (every position
    where ``at`` is None); with ``return_selection`` also each layer's
    selection mask [T, T] (numpy, for tests at small sizes)."""
    c = _dims(cfg)
    if fault == "topk_half":
        c["topk"] = c["topk"] // 2
    cfg_key = tuple(sorted(c.items()))
    tokens = np.asarray(tokens, np.int32)
    t = len(tokens)
    block = min(QUERY_BLOCK, t)
    bucket = SEQUENCE_BUCKET if t > SEQUENCE_BUCKET else block
    t_pad = -(-t // bucket) * bucket
    ids = np.zeros((t_pad,), np.int32)
    ids[:t] = tokens
    positions = jnp.arange(t_pad)
    x = params["tok_embed/embedding"][jnp.asarray(ids)].astype(jnp.float32)
    selections = []
    for i in range(cfg["num_hidden_layers"]):
        pre = f"block_{i}/"
        p = {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}
        q, k, v, qi, ki, w = _project(p, x, positions, cfg_key, quant)
        outs, sels = [], []
        for lo in range(0, t_pad, block):
            sl = slice(lo, lo + block)
            sel, a = _attend_block(
                q[sl], k, v, qi[sl], ki, w[sl], positions[sl], topk=c["topk"], window=fault == "window",
            )
            outs.append(a)
            if return_selection:
                sels.append(np.asarray(sel))
        if return_selection:
            selections.append(np.concatenate(sels, 0)[:t, :t])
        x = _out_proj(x, jnp.concatenate(outs, 0), p["attn/attn_out/kernel"], quant)
        del q, k, v, qi, ki, w, outs

        h, top, weight = _route(x, p["ln2/scale"], p["moe/router/kernel"], c["eps"], c["k"], quant)
        top_h, weight_h = np.asarray(top), np.asarray(weight)
        if fault == "drop_expert":  # the least of each token's experts never runs
            top_h, weight_h = top_h[:, :-1], weight_h[:, :-1]
        y = jnp.zeros_like(x)
        for e in range(c["e"]):
            rows, col = np.nonzero(top_h == e)
            if len(rows) == 0:
                continue
            n = EXPERT_ROW_BLOCK << max(0, (len(rows) - 1) // EXPERT_ROW_BLOCK).bit_length()
            idx = np.zeros((n,), np.int32)
            idx[: len(rows)] = rows
            wt = np.zeros((n,), np.float32)
            wt[: len(rows)] = weight_h[rows, col]
            out = _expert(h[jnp.asarray(idx)], p["moe/w_gate"][e], p["moe/w_in"][e], p["moe/w_out"][e], quant)
            y = y.at[jnp.asarray(idx)].add(out * jnp.asarray(wt)[:, None])
        x = x + y
    at = np.arange(t) if at is None else np.asarray(at)
    logits = _head(x[jnp.asarray(at)], params["ln_f/scale"], params["lm_head/kernel"], c["eps"], quant)
    return (logits, selections) if return_selection else logits
