"""Plain forward of MiniCPM-SALA: jax.numpy, float32, matmuls at
"highest", one sequence, no cache, no pages, no kernels. Layer ``l`` as
the configuration file states it (``x`` the residual stream, every
matmul without bias, ``N`` an RMSNorm of eps ``rms_norm_eps`` with its
learned scale, ``a = scale_depth / sqrt(mup_denominator)``)::

    x_0 = scale_emb * Embed(ids)
    x  += a * Mixer_l(N_attn x)
    x  += a * (silu(m W_g) * (m W_u)) W_d,     m = N_ffn x
    logits = W_head (N_f x) / (hidden_size / dim_model_base)

**Lightning** (``lightning-attn``; H heads of d): ``q = RoPE(N_d(h W_q))
/ sqrt(d)``, ``k = RoPE(N_d(h W_k))``, ``v = h W_v``, RoPE on the halves
``(j, j + d/2)`` at theta ``rope_theta``; by its definition ``o_t =
sum_{s <= t} lam^(t - s) (q_t . k_s) v_s`` with ``lam = exp(-rate_h)``,
``rate_h = 2^(-8 (h + 1) / H) (1 - l / (L - 1) + 1e-5)`` for the layer's
published index ``l`` of ``L``; ``y = (N_{Hd}(o) * sigmoid(h W_g))
W_o``. It is computed a block of positions at a time, the sum over the
earlier blocks carried as ``S = sum_s lam^(n - 1 - s) k_s^T v_s``.

**Block-sparse** (``minicpm4``; H query heads, G KV heads, no position
encoding): ``q = N_d(h W_q)``, ``k = N_d(h W_k)``, ``v = h W_v``; for a
query at ``t``, each KV group ``g`` chooses blocks of ``block_size``
positions by brute force: the mean key of every kernel ``[i S, i S +
K)`` that ends at or before ``t`` (from a running sum of the keys), its
relevance ``sum over the group's heads of softmax_i(q . Kc_i /
sqrt(d))``, every block's score the largest relevance of the kernels
that touch it, the chosen the first ``init_blocks`` blocks, every block
that meets ``[t - window_size + 1, t]`` and the best others by a stable
sort (ties to the lower index), ``topk`` in all; below ``dense_len`` all
blocks. Causal softmax at ``1/sqrt(d)`` over the positions of the
chosen blocks; ``y = (A * sigmoid(h W_g)) W_o``.

Nothing is imported from the program. Weights stay in the type they were
made in and are upcast a matrix at a time (exact). The layers are run a
block of positions at a time (a block-sparse layer's keys and values
first, for every position), so 69,632 positions at the published widths
fit a chip beside the weights.

``quant`` is the control (every weight matmul on rounded operands,
``common.matmul``); ``fault`` plants one of the mistakes an
implementation could make, for setting the limits: ``"no_decay"`` (lam
= 1), ``"bf16_state"`` (the lightning state rounded to bfloat16 after
every position), ``"no_output_gate"`` (both kinds' sigmoid gates left
out), ``"recent_blocks"`` (past ``dense_len``, the last ``topk *
block_size`` positions in the selection's place), ``"no_window"`` (the
window's blocks not forced: ``init_blocks`` and the best others),
``"no_mup"`` (``scale_emb``, the residual factor and the logits' divisor
left out).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Mapping

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.reference.common import HIGHEST, matmul

BLOCK = 512  # positions a block of a layer
QUERY_BLOCK = 64  # queries a block of a block-sparse layer's attention
AT_BLOCK = 128

FAULTS = ("no_decay", "bf16_state", "no_output_gate", "recent_blocks", "no_window", "no_mup")


def _rms(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale.astype(jnp.float32)


def _rope(x, positions, theta):
    """``x [T, H, d]``: dimension ``j`` and ``j + d/2`` turn by ``pos *
    theta^(-2j/d)``."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def decay_rates(heads: int, layer: int, layers: int) -> np.ndarray:
    """``rate_h`` of a lightning layer (module docstring)."""
    h = np.arange(heads, dtype=np.float64)
    return 2.0 ** (-8.0 * (h + 1) / heads) * (1.0 - layer / (layers - 1) + 1e-5)


def sizes(cfg: Mapping[str, Any]) -> dict[str, Any]:
    sc = cfg.get("sparse_config") or {}
    pub = cfg.get("published") or {}
    return dict(
        d=cfg["hidden_size"], h=cfg["num_attention_heads"], g=cfg["num_key_value_heads"],
        hd=cfg["head_dim"], eps=cfg["rms_norm_eps"], theta=float(cfg["rope_theta"]),
        layers=pub.get("num_hidden_layers", cfg["num_hidden_layers"]),
        kept=list(cfg.get("kept_layers", range(cfg["num_hidden_layers"]))),
        kinds=list(cfg["mixer_types"]), k_len=sc.get("kernel_size", 32),
        stride=sc.get("kernel_stride", 16), bs=sc.get("block_size", 64),
        window=sc.get("window_size", 2048), topk=sc.get("topk", 64),
        init=sc.get("init_blocks", 1), dense_len=sc.get("dense_len", 8192),
        emb=float(cfg["scale_emb"]), res=float(cfg["scale_depth"]) / math.sqrt(cfg["mup_denominator"]),
        logit=float(cfg["hidden_size"]) / float(cfg["dim_model_base"]),
    )


def _mlp(x, p, s, quant):
    m = _rms(x, p["ln_ffn/scale"], s["eps"])
    y = jax.nn.silu(matmul(m, p["mlp_gate/kernel"], quant)) * matmul(m, p["mlp_in/kernel"], quant)
    return matmul(y, p["mlp_out/kernel"], quant)


@partial(jax.jit, static_argnames=("s", "quant", "fault"))
def _lightning_block(x, state, n0, rate, p, s, quant, fault):
    """One block of positions ``n0 ..`` of a lightning layer: (the
    block's stream after the layer, the carried sum)."""
    s = dict(s)
    t, hh, d = x.shape[0], s["h"], s["hd"]
    a = 1.0 if fault == "no_mup" else s["res"]
    hn = _rms(x, p["ln_attn/scale"], s["eps"])
    pos = n0 + jnp.arange(t)
    q = _rms(matmul(hn, p["attn/q/kernel"], quant).reshape(t, hh, d), p["attn/q_norm/scale"], s["eps"])
    k = _rms(matmul(hn, p["attn/k/kernel"], quant).reshape(t, hh, d), p["attn/k_norm/scale"], s["eps"])
    v = matmul(hn, p["attn/v/kernel"], quant).reshape(t, hh, d)
    q = _rope(q, pos, s["theta"]) / math.sqrt(d)
    k = _rope(k, pos, s["theta"])
    r = jnp.zeros_like(rate) if fault == "no_decay" else rate
    if fault == "bf16_state":
        def one(carry, qkv):
            qi, ki, vi = qkv
            carry = jnp.exp(-r)[:, None, None] * carry + ki[:, :, None] * vi[:, None, :]
            carry = carry.astype(jnp.bfloat16).astype(jnp.float32)
            return carry, jnp.einsum("hi,hij->hj", qi, carry, precision=HIGHEST)

        state, o = jax.lax.scan(one, state, (q, k, v))
    else:
        lag = (jnp.arange(t)[:, None] - jnp.arange(t)[None, :]).astype(jnp.float32)
        w = jnp.where(lag >= 0, jnp.exp(-r[:, None, None] * jnp.maximum(lag, 0.0)), 0.0)
        qk = jnp.einsum("ihd,jhd->hij", q, k, precision=HIGHEST)
        o = jnp.einsum("hij,jhd->ihd", qk * w, v, precision=HIGHEST)
        # the earlier blocks: lam^(t - s) = lam^(i + 1) lam^(n0 - 1 - s)
        back = jnp.exp(-r[None, :] * (jnp.arange(t)[:, None] + 1.0))  # [T, H]
        o = o + back[:, :, None] * jnp.einsum("ihd,hde->ihe", q, state, precision=HIGHEST)
        ahead = jnp.exp(-r[None, :] * (t - 1.0 - jnp.arange(t)[:, None]))  # [T, H]
        state = jnp.exp(-r * t)[:, None, None] * state + jnp.einsum(
            "jhd,jhe->hde", k * ahead[:, :, None], v, precision=HIGHEST
        )
    o = _rms(o.reshape(t, hh * d), p["attn/o_norm/scale"], s["eps"])
    if fault != "no_output_gate":
        o = o * jax.nn.sigmoid(matmul(hn, p["attn/gate/kernel"], quant))
    x = x + a * matmul(o, p["attn/attn_out/kernel"], quant)
    return x + a * _mlp(x, p, s, quant), state


@partial(jax.jit, static_argnames=("s", "quant"))
def _sparse_kv(x, p, s, quant):
    s = dict(s)
    t = x.shape[0]
    hn = _rms(x, p["ln_attn/scale"], s["eps"])
    k = _rms(matmul(hn, p["attn/k/kernel"], quant).reshape(t, s["g"], s["hd"]), p["attn/k_norm/scale"], s["eps"])
    return k, matmul(hn, p["attn/v/kernel"], quant).reshape(t, s["g"], s["hd"])


def _kernel_means(k, s):
    """The mean key of every kernel ``[i S, i S + K)`` that fits the
    sequence, from a running sum: ``[n, G, d]``."""
    csum = jnp.concatenate([jnp.zeros_like(k[:1]), jnp.cumsum(k, 0)], 0)
    starts = np.arange(0, k.shape[0] - s["k_len"] + 1, s["stride"])
    return (csum[starts + s["k_len"]] - csum[starts]) / s["k_len"]


def _chosen(q, kc, t, s, fault):
    """Chosen blocks ``[Q, G, nblk]`` of queries ``q [Q, H, d]`` at ``t
    [Q]`` over the kernels' mean keys ``kc [n, G, d]``."""
    nq, hh, d = q.shape
    g, n = s["g"], kc.shape[0]
    nblk = -(-s["seq"] // s["bs"])
    logits = jnp.einsum(
        "qgjd,ngd->qgjn", q.reshape(nq, g, hh // g, d), kc, precision=HIGHEST
    ) / math.sqrt(d)
    ends = np.arange(n) * s["stride"] + s["k_len"] - 1
    whole = jnp.asarray(ends)[None, :] <= t[:, None]  # [Q, n]
    logits = jnp.where(whole[:, None, None, :], logits, -jnp.inf)
    some = whole.any(-1)[:, None, None, None]
    rel = jnp.where(some, jax.nn.softmax(jnp.where(some, logits, 0.0), -1), 0.0).sum(2)
    # every kernel's score reaches each block it touches
    first_b = np.arange(n) * s["stride"] // s["bs"]
    last_b = ends // s["bs"]
    score = jnp.full((nq, g, nblk), -jnp.inf)
    for u in range(int((last_b - first_b).max()) + 1):
        touch = first_b + u <= last_b
        tgt = np.where(touch, np.minimum(first_b + u, nblk - 1), nblk - 1)
        score = score.at[:, :, tgt].max(jnp.where(jnp.asarray(touch), rel, -jnp.inf))
    blk = jnp.arange(nblk)[None, :]
    valid = blk * s["bs"] <= t[:, None]
    lo = t[:, None] - s["window"] + 1
    in_window = (blk * s["bs"] + s["bs"] - 1 >= lo) & valid
    forced = (blk < s["init"]) & valid
    if fault != "no_window":
        forced = forced | in_window
    n_rest = s["topk"] - forced.sum(-1)  # [Q]
    other = valid & ~forced
    key = jnp.where(other[:, None, :], score, -jnp.inf)
    order = jnp.argsort(-key, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    chosen = forced[:, None, :] | (other[:, None, :] & (rank < n_rest[:, None, None]))
    dense = (t < s["dense_len"])[:, None, None]
    chosen = jnp.where(dense, valid[:, None, :], chosen)
    if fault == "recent_blocks":
        recent = valid & (blk * s["bs"] + s["bs"] - 1 >= t[:, None] - s["topk"] * s["bs"] + 1)
        chosen = jnp.where(dense, chosen, recent[:, None, :])
    return chosen


@partial(jax.jit, static_argnames=("s", "quant", "fault"))
def _sparse_queries(x, n0, k, v, kc, p, s, quant, fault):
    """Queries ``n0 .. n0 + QUERY_BLOCK`` of a block-sparse layer over
    every key: the attention's output ``[QUERY_BLOCK, H d]``."""
    s = dict(s)
    t, hh, g, d = x.shape[0], s["h"], s["g"], s["hd"]
    hn = _rms(x, p["ln_attn/scale"], s["eps"])
    q = _rms(matmul(hn, p["attn/q/kernel"], quant).reshape(t, hh, d), p["attn/q_norm/scale"], s["eps"])
    pos = n0 + jnp.arange(t)
    chosen = _chosen(q, kc, pos, s, fault)  # [Q, G, nblk]
    keys = jnp.arange(k.shape[0])
    ok = jnp.take(chosen, keys // s["bs"], axis=-1) & (keys[None, None, :] <= pos[:, None, None])
    scores = jnp.einsum(
        "qgjd,sgd->qgjs", q.reshape(t, g, hh // g, d), k, precision=HIGHEST
    ) / math.sqrt(d)
    prob = jax.nn.softmax(jnp.where(ok[:, :, None, :], scores, -jnp.inf), -1)
    out = jnp.einsum("qgjs,sgd->qgjd", prob, v, precision=HIGHEST).reshape(t, hh * d)
    if fault != "no_output_gate":
        out = out * jax.nn.sigmoid(matmul(hn, p["attn/gate/kernel"], quant))
    return out


@partial(jax.jit, static_argnames=("s", "quant", "fault"))
def _sparse_finish(x, attn, p, s, quant, fault):
    s = dict(s)
    a = 1.0 if fault == "no_mup" else s["res"]
    x = x + a * matmul(attn, p["attn/attn_out/kernel"], quant)
    return x + a * _mlp(x, p, s, quant)


@partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x_rows, ln_f, head, divisor, eps, quant):
    return matmul(_rms(x_rows, ln_f, eps) / divisor, head, quant)


def forward(
    params: Mapping[str, Any], tokens, cfg: Mapping[str, Any], at=None,
    quant: str | None = None, fault: str | None = None,
):
    """``tokens`` [T] -> float32 logits [len(at), V] (every position where
    ``at`` is None)."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; one of {FAULTS}")
    s = sizes(cfg)
    tokens = np.asarray(tokens, np.int32)
    t = len(tokens)
    t_pad = -(-t // BLOCK) * BLOCK
    s["seq"] = t_pad
    static = tuple(sorted((k, v) for k, v in s.items() if not isinstance(v, list)))
    ids = np.zeros((t_pad,), np.int32)
    ids[:t] = tokens
    emb = 1.0 if fault == "no_mup" else s["emb"]
    x = params["tok_embed/embedding"][jnp.asarray(ids)].astype(jnp.float32) * emb
    for i, kind in enumerate(s["kinds"]):
        pre = f"block_{i}/"
        p = {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}
        blocks = []
        if kind == "lightning-attn":
            rate = jnp.asarray(decay_rates(s["h"], s["kept"][i], s["layers"]), jnp.float32)
            state = jnp.zeros((s["h"], s["hd"], s["hd"]), jnp.float32)
            for n0 in range(0, t_pad, BLOCK):
                out, state = _lightning_block(x[n0:n0 + BLOCK], state, n0, rate, p, static, quant, fault)
                blocks.append(out)
        else:
            k, v = _sparse_kv(x, p, static, quant)
            kc = _kernel_means(k, s)
            attn = jnp.concatenate([
                _sparse_queries(x[n0:n0 + QUERY_BLOCK], n0, k, v, kc, p, static, quant, fault)
                for n0 in range(0, t_pad, QUERY_BLOCK)
            ])
            for n0 in range(0, t_pad, BLOCK):
                blocks.append(_sparse_finish(
                    x[n0:n0 + BLOCK], attn[n0:n0 + BLOCK], p, static, quant, fault
                ))
        x = jnp.concatenate(blocks)
    at = np.arange(t) if at is None else np.asarray(at)
    rows = np.full((-(-len(at) // AT_BLOCK) * AT_BLOCK,), at[-1], at.dtype)
    rows[: len(at)] = at
    divisor = 1.0 if fault == "no_mup" else s["logit"]
    return _head(
        x[jnp.asarray(rows)], params["ln_f/scale"], params["lm_head/kernel"], divisor,
        s["eps"], quant,
    )[: len(at)]
