"""Shared pieces of the plain references: float32 matmuls at "highest",
and the lower-precision stand-in that serves as the control.

The control computes every weight matmul or convolution on operands
rounded to 8 bits: ``fp8`` is float8 e4m3 with one scale per tensor,
``int8`` is symmetric int8 with a scale per row (activations) or column
(weights). Gradients pass straight through the rounding, so the backward
products see the rounded operands, as an 8-bit training recipe would.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST


@jax.custom_vjp
def _ste(x, q):
    return q


def _ste_fwd(x, q):
    return q, None


def _ste_bwd(_, g):
    return g, jnp.zeros_like(g)


_ste.defvjp(_ste_fwd, _ste_bwd)


def fake_quant(x, kind: str | None, axis: int | None = None):
    """``x`` rounded to 8 bits and back to float32, straight-through."""
    if kind is None:
        return x
    x = x.astype(jnp.float32)
    if kind == "fp8":
        amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
        s = amax / 448.0
        q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    elif kind == "int8":
        amax = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None), 1e-30)
        s = amax / 127.0
        q = jnp.clip(jnp.round(x / s), -127, 127) * s
    elif kind == "bf16":
        q = x.astype(jnp.bfloat16).astype(jnp.float32)
    else:
        raise ValueError(f"unknown control precision {kind!r}")
    return _ste(x, lax.stop_gradient(q))


def matmul(a, w, quant: str | None = None):
    """a [..., K] @ w [K, N] in float32 at highest precision."""
    a = fake_quant(a, quant, axis=-1)
    w = fake_quant(w, quant, axis=0)
    return jnp.matmul(a.astype(jnp.float32), w.astype(jnp.float32), precision=HIGHEST)
