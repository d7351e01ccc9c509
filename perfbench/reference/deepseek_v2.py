"""Plain forward of DeepSeek-V2: jax.numpy, float32, matmuls at
"highest", full causal pass, keys and values built a head (NOT
absorbed), no cache, no pages, no kernels, experts as a loop. Layer
``l`` as the configuration file states it and the published
``modeling_deepseek.py`` computes it (``x`` the residual stream, every
matmul without bias, ``N`` an RMSNorm of eps ``rms_norm_eps``):

1. Latent attention ``A(h)``: ``c_q = N(h W_qa)``; ``q = c_q W_qb`` as
   ``num_attention_heads`` heads of ``qk_nope_head_dim +
   qk_rope_head_dim``; ``h W_kva`` splits into ``c_kv``
   (``kv_lora_rank``) and ONE ``k_rope`` a token; ``c = N(c_kv)``; ``c
   W_kvb`` as heads of ``qk_nope_head_dim + v_head_dim``: ``k_nope``,
   ``v``. RoPE turns the pairs ``(2j, 2j+1)`` of ``q_rope`` and
   ``k_rope`` by ``pos * f_j``, cos and sin times ``c_rope``; ``f_j`` are
   YaRN's frequencies: ``theta^(-2j / rope)``, kept for ``j <= low``,
   divided by ``factor`` for ``j >= high`` and blended linearly between
   (``low``, ``high`` the floor and ceil of the indices that turn
   ``beta_fast`` and ``beta_slow`` times over the original context).
   Scores ``(q_nope . k_nope + q_rope . k_rope) * m_all^2 / sqrt(nope +
   rope)`` over ``s <= t``, softmax, ``A = concat_heads(p v) W_o``. With
   ``m(a) = 0.1 a ln(factor) + 1``: ``c_rope = m(mscale) / m(mscale_all_dim)``,
   ``m_all = m(mscale_all_dim)``.
2. The layer: ``x1 = x + A(N_attn x)``; ``out = x1 + F(N_ffn x1)``,
   ``F`` a dense SwiGLU ``(silu(m W_g) * (m W_u)) W_d`` of
   ``intermediate_size`` on the first ``first_k_dense_replace`` layers,
   the MoE on the others.
3. The MoE: ``scores = softmax(m W_r)`` over the ``n_routed_experts``;
   group-limited greedy: ``n_group`` contiguous groups, a group scores
   its best expert's score, the ``topk_group`` best groups are kept, the
   others' scores set to 0, ``idx = top num_experts_per_tok`` of what is
   left; ``w = scores[idx] * routed_scaling_factor`` (not renormalised);
   ``s = sum_k w_k E_idx_k(m) + S(m)``, ``E_e`` and the shared experts'
   ``S`` SwiGLUs (``S`` of ``n_shared_experts * moe_intermediate_size``).
4. **The share.** ``held`` lists the routed experts whose matrices are
   given (``moe/w_*`` stack them in that order); the terms of the other
   routed experts are left out of ``s``; the router and ``S`` are whole.

Final RMSNorm, untied head. It takes one sequence, and is computed in
blocks so that 17,408 tokens at the published widths fit one chip beside
the weights: keys and values built for eight heads at a time, queries a
block at a time against all keys; experts one at a time over the rows
routed to them; logits at the asked positions only. Weights stay in the
type they were made in and are upcast a matrix at a time (exact).
Nothing is imported from the program.

``quant`` is the control (every weight matmul on rounded operands,
``common.matmul``); ``fault`` plants one of the mistakes an
implementation could make, for setting the limits: ``"no_groups"``
(plain top-k over all experts), ``"yarn_on_cos_sin"`` (``m_all`` on cos
and sin, none on the score: the rope dimensions' part of a score scaled
alone), ``"no_shared_experts"`` (``S`` left out), ``"drop_expert"`` (the
least-weighted held expert of a token left out).
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
HEAD_BLOCK = 8
# Shapes are bucketed so that a run's requests, all of other lengths,
# compile few programs: a sequence is padded to one of SEQUENCE_BUCKETS
# or, past the last, to a multiple of SEQUENCE_STEP; an expert's rows to
# EXPERT_ROW_BLOCK times a power of two; the asked positions to a
# multiple of AT_BLOCK. Padding tokens sit after the real ones, where no
# real query sees them; padding rows carry weight 0.
SEQUENCE_BUCKETS = (512, 1024, 2048)
SEQUENCE_STEP = 2048
EXPERT_ROW_BLOCK = 128
AT_BLOCK = 128

FAULTS = ("no_groups", "yarn_on_cos_sin", "no_shared_experts", "drop_expert")


def padded_length(t: int) -> int:
    for bucket in SEQUENCE_BUCKETS:
        if t <= bucket:
            return bucket
    return -(-t // SEQUENCE_STEP) * SEQUENCE_STEP


def held_experts(cfg: Mapping[str, Any]) -> tuple[int, ...]:
    """The routed experts a configuration file holds: all of them, or,
    cut to a chip's share, ids ``0 .. n_routed_experts - 1`` of
    ``published.n_routed_experts``."""
    return tuple(range(cfg["n_routed_experts"]))


def routed_experts(cfg: Mapping[str, Any]) -> int:
    return (cfg.get("published") or {}).get("n_routed_experts", cfg["n_routed_experts"])


def yarn(cfg: Mapping[str, Any]) -> tuple[np.ndarray, float, float]:
    """(the rope dimensions' frequencies, the factor on cos and sin, the
    factor on the whole score), from ``rope_scaling`` as published."""
    dim, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    inv = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    rs = cfg.get("rope_scaling")
    if not rs:
        return inv.astype(np.float32), 1.0, 1.0
    factor, orig = float(rs["factor"]), float(rs["original_max_position_embeddings"])

    def index_turning(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(index_turning(rs.get("beta_fast", 32))), 0)
    high = min(math.ceil(index_turning(rs.get("beta_slow", 1))), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    inv = inv / factor * ramp + inv * (1.0 - ramp)

    def m(a):
        return 0.1 * a * math.log(factor) + 1.0 if factor > 1 else 1.0

    all_dim = float(rs.get("mscale_all_dim", 0))
    return inv.astype(np.float32), m(float(rs.get("mscale", 1))) / m(all_dim), (m(all_dim) ** 2 if all_dim else 1.0)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * scale.astype(jnp.float32)


def _rope_pairs(x, positions, inv, c):
    """``x [T, H, D]``: the pair ``(2j, 2j+1)`` turns by ``pos * inv_j``,
    cos and sin times ``c``, in place."""
    ang = positions.astype(jnp.float32)[:, None] * inv
    sin, cos = c * jnp.sin(ang)[:, None, :], c * jnp.cos(ang)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1).reshape(x.shape)


@partial(jax.jit, static_argnames=("r", "eps", "quant"))
def _latents(p, hn, r, eps, quant):
    """``hn [T, D]`` -> (c_q [T, q_lora_rank], c [T, kv_lora_rank], the
    rope key before RoPE [T, rope])."""
    c_q = _rms(matmul(hn, p["q_a/kernel"], quant), p["q_a_norm/scale"], eps)
    ckv = matmul(hn, p["kv_a/kernel"], quant)
    return c_q, _rms(ckv[:, :r], p["kv_a_norm/scale"], eps), ckv[:, r:]


@partial(jax.jit, static_argnames=("dn", "dv", "quant"))
def _heads_attend(c_q, c, k_pe, w_qb, w_kvb, positions, inv, c_rope, scale, dn, dv, quant):
    """A block of heads: their queries, keys and values built from the
    latents (``w_qb`` / ``w_kvb`` their columns), then causal attention,
    queries a block at a time against every key -> [T, heads, dv]."""
    t, h = c_q.shape[0], w_kvb.shape[1] // (dn + dv)
    q = matmul(c_q, w_qb, quant).reshape(t, h, -1)
    kv = matmul(c, w_kvb, quant).reshape(t, h, dn + dv)
    q = jnp.concatenate([q[..., :dn], _rope_pairs(q[..., dn:], positions, inv, c_rope)], -1)
    k_rope = _rope_pairs(k_pe[:, None, :], positions, inv, c_rope)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_rope, (t, h, k_rope.shape[-1]))], -1)
    v = kv[..., dn:]
    block = min(QUERY_BLOCK, t)

    def one(args):
        qb, pos = args
        sc = jnp.einsum("chd,shd->hcs", qb, k, precision=HIGHEST) * scale
        a = jax.nn.softmax(jnp.where((positions[None, :] <= pos[:, None])[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("hcs,shv->chv", a, v, precision=HIGHEST)

    out = jax.lax.map(one, (q.reshape(t // block, block, h, -1), positions.reshape(t // block, block)))
    return out.reshape(t, h, dv)


def attention(p, hn, positions, cfg, quant=None, fault=None):
    """``A`` over the whole (padded) sequence: ``hn [T, D]`` -> the heads'
    outputs ``[T, H, dv]`` (before ``W_o``), eight heads at a time."""
    h, dn, dr, dv = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    inv, c_rope, score = yarn(cfg)
    if fault == "yarn_on_cos_sin":  # the whole-score factor put on cos and sin
        c_rope, score = c_rope * score ** 0.5, 1.0
    c_q, c, k_pe = _latents(p, hn, r=cfg["kv_lora_rank"], eps=cfg["rms_norm_eps"], quant=quant)
    out = []
    for h0 in range(0, h, HEAD_BLOCK):
        h1 = min(h0 + HEAD_BLOCK, h)
        out.append(_heads_attend(
            c_q, c, k_pe, p["q_b/kernel"][:, h0 * (dn + dr):h1 * (dn + dr)],
            p["kv_b/kernel"][:, h0 * (dn + dv):h1 * (dn + dv)], positions, jnp.asarray(inv),
            np.float32(c_rope), np.float32(score / math.sqrt(dn + dr)), dn=dn, dv=dv, quant=quant,
        ))
    return jnp.concatenate(out, axis=1)


@partial(jax.jit, static_argnames=("quant",))
def _out_proj(x, a, wo, quant):
    return x + matmul(a.reshape(a.shape[0], -1), wo, quant)


@partial(jax.jit, static_argnames=("quant",))
def _mlp(h, wg, wu, wd, quant):
    return matmul(jax.nn.silu(matmul(h, wg, quant)) * matmul(h, wu, quant), wd, quant)


@partial(jax.jit, static_argnames=("k", "groups", "keep", "scale", "quant"))
def _route(m, router, k, groups, keep, scale, quant):
    """Softmax scores over the routed experts; the top ``k`` of them
    within the ``keep`` best of ``groups`` groups (``groups`` 1: over
    all), weighted by their scores times ``scale``."""
    scores = jax.nn.softmax(matmul(m, router, quant), axis=-1)
    t, e = scores.shape
    if groups > 1:
        best = scores.reshape(t, groups, e // groups).max(-1)
        kept = jnp.argsort(-best, axis=-1, stable=True)[:, :keep]
        mask = jnp.zeros((t, groups), bool).at[jnp.arange(t)[:, None], kept].set(True)
        scores = jnp.where(jnp.repeat(mask, e // groups, axis=-1), scores, 0.0)
    top = jnp.argsort(-scores, axis=-1, stable=True)[:, :k]
    return top, jnp.take_along_axis(scores, top, axis=-1) * scale


def moe(p, m, cfg, held=None, quant=None, fault=None):
    """``s = MoE(m)`` for ``m [T, D]`` (already normalised), over the
    share ``held`` (ids of the routed experts whose matrices ``p`` stacks,
    in that order; None: the configuration's own)."""
    held = held_experts(cfg) if held is None else tuple(held)
    grouped = cfg.get("topk_method") == "group_limited_greedy" and fault != "no_groups"
    top, w = _route(
        m, p["moe/router/kernel"], cfg["num_experts_per_tok"], cfg["n_group"] if grouped else 1,
        cfg["topk_group"] if grouped else 1, float(cfg["routed_scaling_factor"]), quant,
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
    if cfg.get("n_shared_experts") and fault != "no_shared_experts":
        s = s + _mlp(m, p["moe/shared_gate/kernel"], p["moe/shared_in/kernel"], p["moe/shared_out/kernel"], quant)
    return s


def layer(p, x, positions, cfg, dense: bool, held=None, quant=None, fault=None):
    """One layer over the residual stream ``x [T, D]``."""
    eps = cfg["rms_norm_eps"]
    a = attention(
        {k[len("attn/"):]: v for k, v in p.items() if k.startswith("attn/")},
        _rms(x, p["ln_attn/scale"], eps), positions, cfg, quant, fault,
    )
    x = _out_proj(x, a, p["attn/attn_out/kernel"], quant)
    m = _rms(x, p["ln_ffn/scale"], eps)
    if dense:
        return x + _mlp(m, p["mlp_gate/kernel"], p["mlp_in/kernel"], p["mlp_out/kernel"], quant)
    return x + moe(p, m, cfg, held, quant, fault)


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
    for i in range(cfg["num_hidden_layers"]):
        pre = f"block_{i}/"
        p = {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}
        x = layer(p, x, positions, cfg, i < cfg.get("first_k_dense_replace", 0), held, quant, fault)
    at = np.arange(t) if at is None else np.asarray(at)
    rows = np.full((-(-len(at) // AT_BLOCK) * AT_BLOCK,), at[-1], at.dtype)
    rows[: len(at)] = at
    return _head(x[jnp.asarray(rows)], params["ln_f/scale"], params["lm_head/kernel"], cfg["rms_norm_eps"], quant)[: len(at)]
