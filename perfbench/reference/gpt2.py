"""Plain GPT-2 forward, loss and AdamW step: jax.numpy, float32, matmuls
at "highest", dense causal attention, no kernels, no cache, no batching
tricks. Follows Radford et al. 2019 as the configuration file states it:
pre-LayerNorm blocks, learned positions, tanh-GELU MLP. Departures of the
program that the configuration lists (untied head, no attention biases,
LayerNorm epsilon) are followed here, since the file is the statement.
"""

from __future__ import annotations

from typing import Any, Mapping

import jax
import jax.numpy as jnp

from perfbench.reference.common import HIGHEST, matmul


def _ln(x, scale, bias, eps):
    m = jnp.mean(x, -1, keepdims=True)
    v = jnp.mean(jnp.square(x - m), -1, keepdims=True)
    return (x - m) * jax.lax.rsqrt(v + eps) * scale + bias


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(0.7978845608028654 * (x + 0.044715 * x**3)))


def forward(params: Mapping[str, Any], tokens, cfg: Mapping[str, Any], quant=None):
    """tokens [B, T] -> float32 logits [B, T, V]."""
    p = {k: v.astype(jnp.float32) for k, v in params.items()}
    b, t = tokens.shape
    h, d = cfg["n_head"], cfg["n_embd"]
    eps = cfg["layer_norm_epsilon"]
    x = p["tok_embed/embedding"][tokens] + p["pos_embed/embedding"][:t][None]
    mask = jnp.tril(jnp.ones((t, t), bool))
    for i in range(cfg["n_layer"]):
        n = f"block_{i}"
        y = _ln(x, p[f"{n}/ln1/scale"], p[f"{n}/ln1/bias"], eps)
        q = matmul(y, p[f"{n}/attn/q/kernel"], quant).reshape(b, t, h, d // h)
        k = matmul(y, p[f"{n}/attn/k/kernel"], quant).reshape(b, t, h, d // h)
        v = matmul(y, p[f"{n}/attn/v/kernel"], quant).reshape(b, t, h, d // h)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) / jnp.sqrt(
            jnp.float32(d // h)
        )
        s = jnp.where(mask[None, None], s, -jnp.inf)
        a = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", a, v, precision=HIGHEST).reshape(b, t, d)
        x = x + matmul(o, p[f"{n}/attn/attn_out/kernel"], quant)
        y = _ln(x, p[f"{n}/ln2/scale"], p[f"{n}/ln2/bias"], eps)
        u = _gelu_tanh(matmul(y, p[f"{n}/mlp_in/kernel"], quant) + p[f"{n}/mlp_in/bias"])
        x = x + matmul(u, p[f"{n}/mlp_out/kernel"], quant) + p[f"{n}/mlp_out_bias"]
    x = _ln(x, p["ln_f/scale"], p["ln_f/bias"], eps)
    return matmul(x, p["lm_head/kernel"], quant)


def loss_sum(params, tokens, targets, cfg, quant=None):
    """Summed next-token cross-entropy over a block of rows."""
    logits = forward(params, tokens, cfg, quant)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.sum(logz - picked)


def loss_and_grads(params, tokens, targets, cfg, quant=None, rows_per_block=4):
    """Mean loss and its gradient over [B, T] tokens, in blocks of rows so
    that the dense attention scores and float32 logits fit the chip."""
    b, t = tokens.shape
    step = jax.jit(
        jax.value_and_grad(lambda p, x, y: loss_sum(p, x, y, cfg, quant))
    )
    total, grads = 0.0, None
    for lo in range(0, b, rows_per_block):
        l, g = step(params, tokens[lo:lo + rows_per_block], targets[lo:lo + rows_per_block])
        total = total + l
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    n = b * t
    return total / n, jax.tree.map(lambda g: g / n, grads)


def adamw_init(params):
    zeros = lambda: {k: jnp.zeros(p.shape, jnp.float32) for k, p in params.items()}
    return {"mu": zeros(), "nu": zeros(), "count": jnp.zeros((), jnp.float32)}


def _adamw(params, grads, state, opt):
    b1, b2, eps, lr, wd = opt
    c = state["count"] + 1.0
    new_p, mu, nu = {}, {}, {}
    for k, p in params.items():
        g = grads[k]
        m = b1 * state["mu"][k] + (1 - b1) * g
        v = b2 * state["nu"][k] + (1 - b2) * g * g
        mh = m / (1 - b1**c)
        vh = v / (1 - b2**c)
        new_p[k] = p - lr * (mh / (jnp.sqrt(vh) + eps) + wd * p)
        mu[k], nu[k] = m, v
    return new_p, {"mu": mu, "nu": nu, "count": c}


_adamw_jit = jax.jit(_adamw, static_argnums=3, donate_argnums=(0, 2))


def adamw_step(params, grads, state, opt: Mapping[str, float]):
    """optax.adamw's rule: bias-corrected moments, decoupled decay."""
    key = (opt["b1"], opt["b2"], opt["eps"], opt["learning_rate"], opt["weight_decay"])
    return _adamw_jit(params, grads, state, key)
