"""Plain forward of LongCat-Flash-Chat: jax.numpy, float32, matmuls at
"highest", full causal pass, keys and values built a head (NOT
absorbed), no cache, no pages, no kernels, experts as a loop. Layer
``l`` as the configuration file states it and transformers'
``modular_longcat_flash.py`` computes it (``x`` the residual stream,
every matmul without bias, ``N`` an RMSNorm of eps ``rms_norm_eps``,
``i`` in (0, 1) the layer's two sublayers):

1. Latent attention ``A_i(h)``: ``c_q = N(h W_qa)``; ``q = c_q W_qb`` as
   ``num_attention_heads`` heads of ``qk_nope_head_dim +
   qk_rope_head_dim``, times ``s_q = sqrt(hidden / q_lora_rank)``; ``h
   W_kva`` splits into ``c_kv`` (``kv_lora_rank``) and ONE ``k_rope`` a
   token; ``c = N(c_kv) s_kv``, ``s_kv = sqrt(hidden / kv_lora_rank)``;
   ``c W_kvb`` as heads of ``qk_nope_head_dim + v_head_dim``: ``k_nope``,
   ``v``. RoPE turns the pairs ``(2j, 2j+1)`` of ``q_rope`` and
   ``k_rope`` by ``pos * theta^(-2j / rope)``. Scores ``(q_nope . k_nope
   + q_rope . k_rope) / sqrt(nope + rope)`` over ``s <= t``, softmax,
   ``A_i = concat_heads(p v) W_o``.
2. The layer: ``x1 = x + A_0(N_a0 x)``; ``m = N_p0 x1``; ``s = MoE(m)``;
   ``x2 = x1 + MLP_0(m)``; ``x3 = x2 + A_1(N_a1 x2)``; ``out = x3 +
   MLP_1(N_p1 x3) + s``, ``MLP_i(m) = (silu(m W_g) * (m W_u)) W_d``.
3. The MoE: ``scores = softmax(m W_r)`` over the routed and the
   zero-compute experts; ``idx = top moe_topk of (scores + b)``;
   ``w = scores[idx] * routed_scaling_factor`` (no ``b`` in it, not
   renormalised); a routed expert is ``(silu(m W_g^e) * (m W_u^e))
   W_d^e``, a zero-compute expert is ``m`` itself; ``s = sum_k w_k
   E_idx_k(m)``.
4. **The share.** ``held`` lists the routed experts whose matrices are
   given (``moe/w_*`` stack them in that order); the terms of the other
   routed experts are left out of ``s``; every zero-compute term is in.

Final RMSNorm, untied head. It takes one sequence, and is computed in
blocks so that 4,608 tokens at the published widths fit one chip beside
the weights: queries a block at a time against all keys, eight heads'
scores at a time; experts one at a time over the rows routed to them;
logits at the asked positions only. Weights stay in the type they were
made in and are upcast a matrix at a time (exact). Nothing is imported
from the program.

``quant`` is the control (every weight matmul on rounded operands,
``common.matmul``); ``fault`` plants one of the mistakes an
implementation could make, for setting the limits: ``"no_zero_experts"``
(the zero-compute experts' terms left out), ``"bias_in_weights"`` (the
choice bias counted into the weights), ``"no_kv_scale"`` (``s_kv`` left
out), ``"rope_half_on_q"`` (the queries' RoPE pairs ``(j, j + rope/2)``,
the keys' ``(2j, 2j+1)``), ``"moe_before_second_attention"`` (``s``
added to ``x2``), ``"drop_expert"`` (the least-weighted held expert of a
token left out).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Mapping

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.reference.common import HIGHEST, matmul

QUERY_BLOCK = 512
HEAD_BLOCK = 8
# Shapes are bucketed so that a run's requests, all of other lengths,
# compile few programs: a sequence is padded to one of SEQUENCE_BUCKETS
# or, past the last, to a multiple of QUERY_BLOCK; an expert's rows to
# EXPERT_ROW_BLOCK times a power of two; the asked positions to a
# multiple of AT_BLOCK. Padding tokens sit after the real ones, where no
# real query sees them; padding rows carry weight 0.
SEQUENCE_BUCKETS = (512, 1024, 2048, 3072, 4608)
EXPERT_ROW_BLOCK = 128
AT_BLOCK = 128

FAULTS = (
    "no_zero_experts", "bias_in_weights", "no_kv_scale", "rope_half_on_q",
    "moe_before_second_attention", "drop_expert",
)


def padded_length(t: int) -> int:
    for bucket in SEQUENCE_BUCKETS:
        if t <= bucket:
            return bucket
    return -(-t // QUERY_BLOCK) * QUERY_BLOCK


def held_experts(cfg: Mapping[str, Any]) -> tuple[int, ...]:
    """The routed experts a configuration file holds: all of them, or,
    cut to a chip's share, ids ``0 .. n_routed_experts - 1`` of
    ``published.n_routed_experts``."""
    return tuple(range(cfg["n_routed_experts"]))


def routed_experts(cfg: Mapping[str, Any]) -> int:
    return (cfg.get("published") or {}).get("n_routed_experts", cfg["n_routed_experts"])


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * scale.astype(jnp.float32)


def _rope_pairs(x, positions, theta: float, half_split: bool = False):
    """``x [T, H, D]``: the pair ``(2j, 2j+1)`` turns by ``pos *
    theta^(-2j / D)``, in place; ``half_split`` pairs ``(j, j + D/2)``
    instead (the planted fault)."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(d // 2, dtype=jnp.float32) * 2.0 / d)
    ang = positions.astype(jnp.float32)[:, None] * inv
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    if half_split:
        a, b = x[..., : d // 2], x[..., d // 2:]
        return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1).reshape(x.shape)


@partial(jax.jit, static_argnames=("h", "dn", "dr", "dv", "r", "eps", "s_q", "s_kv", "theta", "quant", "q_half"))
def _project(p, hn, positions, h, dn, dr, dv, r, eps, s_q, s_kv, theta, quant, q_half):
    """Normalised input ``hn [T, D]`` -> (q [T, H, nope + rope], k the
    same, v [T, H, dv]): keys and values built a head."""
    t = hn.shape[0]
    c_q = _rms(matmul(hn, p["q_a/kernel"], quant), p["q_a_norm/scale"], eps)
    q = matmul(c_q, p["q_b/kernel"], quant).reshape(t, h, dn + dr) * s_q
    ckv = matmul(hn, p["kv_a/kernel"], quant)
    c = _rms(ckv[:, :r], p["kv_a_norm/scale"], eps) * s_kv
    kv = matmul(c, p["kv_b/kernel"], quant).reshape(t, h, dn + dv)
    q_rope = _rope_pairs(q[..., dn:], positions, theta, half_split=q_half)
    k_rope = _rope_pairs(ckv[:, None, r:], positions, theta)
    q = jnp.concatenate([q[..., :dn], q_rope], -1)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_rope, (t, h, dr))], -1)
    return q, k, kv[..., dn:]


@jax.jit
def _attend_block(q, k, v, q_pos, k_pos):
    """One block of queries [C, H, Dk] at ``q_pos`` against keys
    [S, H, Dk] / values [S, H, Dv] at ``k_pos``, causal; some heads'
    scores at a time."""
    h, d = q.shape[1], q.shape[2]
    seen = (k_pos[None, :] <= q_pos[:, None])[None]
    out = []
    for h0 in range(0, h, HEAD_BLOCK):
        hs = slice(h0, h0 + HEAD_BLOCK)
        sc = jnp.einsum("chd,shd->hcs", q[:, hs], k[:, hs], precision=HIGHEST) / jnp.sqrt(jnp.float32(d))
        a = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
        out.append(jnp.einsum("hcs,shv->chv", a, v[:, hs], precision=HIGHEST))
    return jnp.concatenate(out, axis=1)


@partial(jax.jit, static_argnames=("quant",))
def _out_proj(x, a, wo, quant):
    return x + matmul(a.reshape(a.shape[0], -1), wo, quant)


@partial(jax.jit, static_argnames=("quant",))
def _mlp(h, wg, wu, wd, quant):
    return matmul(jax.nn.silu(matmul(h, wg, quant)) * matmul(h, wu, quant), wd, quant)


@partial(jax.jit, static_argnames=("k", "scale", "quant", "bias_in_weights"))
def _route(m, router, bias, k, scale, quant, bias_in_weights):
    scores = jax.nn.softmax(matmul(m, router, quant), axis=-1)
    chosen = scores + bias.astype(jnp.float32)
    top = jnp.argsort(-chosen, axis=-1, stable=True)[:, :k]
    w = jnp.take_along_axis(chosen if bias_in_weights else scores, top, axis=-1)
    return top, w * scale


def attention(p, hn, positions, cfg, quant=None, fault=None):
    """``A_i`` over the whole (padded) sequence: ``hn [T, D]`` -> the
    heads' outputs ``[T, H, dv]`` (before ``W_o``), queries a block at a
    time."""
    d = cfg["hidden_size"]
    q, k, v = _project(
        p, hn, positions, h=cfg["num_attention_heads"], dn=cfg["qk_nope_head_dim"],
        dr=cfg["qk_rope_head_dim"], dv=cfg["v_head_dim"], r=cfg["kv_lora_rank"], eps=cfg["rms_norm_eps"],
        s_q=(d / cfg["q_lora_rank"]) ** 0.5 if cfg.get("mla_scale_q_lora") else 1.0,
        s_kv=(d / cfg["kv_lora_rank"]) ** 0.5 if cfg.get("mla_scale_kv_lora") and fault != "no_kv_scale" else 1.0,
        theta=float(cfg["rope_theta"]), quant=quant, q_half=fault == "rope_half_on_q",
    )
    t = hn.shape[0]
    block = min(QUERY_BLOCK, t)
    return jnp.concatenate([
        _attend_block(q[lo:lo + block], k, v, positions[lo:lo + block], positions)
        for lo in range(0, t, block)
    ], 0)


def shortcut_moe(p, m, cfg, held=None, quant=None, fault=None):
    """``s = MoE(m)`` for ``m [T, D]`` (already normalised), over the
    share ``held`` (ids of the routed experts whose matrices ``p`` stacks,
    in that order; None: the configuration's own)."""
    held = held_experts(cfg) if held is None else tuple(held)
    routed, top_k = routed_experts(cfg), cfg["moe_topk"]
    top, w = _route(
        m, p["moe/router/kernel"], p["moe/choice_bias"], top_k, float(cfg["routed_scaling_factor"]), quant,
        fault == "bias_in_weights",
    )
    top_h, w_h = np.asarray(top), np.asarray(w)
    if fault == "drop_expert":  # the least-weighted held expert of a token never runs
        is_held = np.isin(top_h, held)
        least = np.where(is_held, w_h, np.inf).argmin(-1)
        drop = np.zeros_like(is_held)
        drop[np.arange(len(least)), least] = True
        w_h = np.where(drop & is_held, 0.0, w_h)
    s = jnp.zeros_like(m)
    for local, e in enumerate(held):
        rows, col = np.nonzero((top_h == e) & (w_h != 0.0))
        if len(rows) == 0:
            continue
        n = EXPERT_ROW_BLOCK << max(0, (len(rows) - 1) // EXPERT_ROW_BLOCK).bit_length()
        idx = np.zeros((n,), np.int32)
        idx[: len(rows)] = rows
        wt = np.zeros((n,), np.float32)
        wt[: len(rows)] = w_h[rows, col]
        out = _mlp(m[jnp.asarray(idx)], p["moe/w_gate"][local], p["moe/w_in"][local], p["moe/w_out"][local], quant)
        s = s.at[jnp.asarray(idx)].add(out * jnp.asarray(wt)[:, None])
    if cfg["zero_expert_num"] and fault != "no_zero_experts":
        # a zero-compute expert is the identity: w * m, no weights
        s = s + jnp.asarray(np.where(top_h >= routed, w_h, 0.0).sum(-1, keepdims=True)) * m
    return s


def layer(p, x, positions, cfg, held=None, quant=None, fault=None):
    """One layer over the residual stream ``x [T, D]``."""
    eps = cfg["rms_norm_eps"]

    def sub(prefix):
        return {k[len(prefix):]: v for k, v in p.items() if k.startswith(prefix)}

    def attn(i, x):
        a = attention(sub(f"attn_{i}/"), _rms(x, p[f"ln_a{i}/scale"], eps), positions, cfg, quant, fault)
        return _out_proj(x, a, p[f"attn_{i}/attn_out/kernel"], quant)

    def mlp(i, m):
        return _mlp(m, p[f"mlp_{i}_gate/kernel"], p[f"mlp_{i}_in/kernel"], p[f"mlp_{i}_out/kernel"], quant)

    x = attn(0, x)
    m = _rms(x, p["ln_p0/scale"], eps)
    s = shortcut_moe(p, m, cfg, held, quant, fault)
    x = x + mlp(0, m)
    if fault == "moe_before_second_attention":
        x = x + s
    x = attn(1, x)
    x = x + mlp(1, _rms(x, p["ln_p1/scale"], eps))
    return x if fault == "moe_before_second_attention" else x + s


@partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x_rows, ln_f, head, eps, quant):
    return matmul(_rms(x_rows, ln_f, eps), head, quant)


def forward(
    params: Mapping[str, Any], tokens, cfg: Mapping[str, Any], at=None, quant: str | None = None,
    fault: str | None = None, held=None,
):
    """``tokens`` [T] -> float32 logits [len(at), V] (every position
    where ``at`` is None)."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; one of {FAULTS}")
    tokens = np.asarray(tokens, np.int32)
    t = len(tokens)
    t_pad = padded_length(t)
    ids = np.zeros((t_pad,), np.int32)
    ids[:t] = tokens
    positions = jnp.arange(t_pad)
    x = params["tok_embed/embedding"][jnp.asarray(ids)].astype(jnp.float32)
    for i in range(cfg["num_layers"]):
        pre = f"block_{i}/"
        p = {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}
        x = layer(p, x, positions, cfg, held, quant, fault)
    at = np.arange(t) if at is None else np.asarray(at)
    rows = np.full((-(-len(at) // AT_BLOCK) * AT_BLOCK,), at[-1], at.dtype)
    rows[: len(at)] = at
    return _head(x[jnp.asarray(rows)], params["ln_f/scale"], params["lm_head/kernel"], cfg["rms_norm_eps"], quant)[: len(at)]
