"""Fused softmax cross-entropy as a Pallas TPU kernel.

The LM loss at large vocabularies is bandwidth-bound: XLA's unfused
path materializes the [N, V] log-softmax (one full extra read+write of
the logits) before gathering the label column. This kernel computes
per-row ``logsumexp - logit[label]`` in ONE pass over the logits —
vocab tiles stream through VMEM with the online (max, sumexp) update,
and the label logit is picked up by the tile that contains it. Nothing
of [N, V] shape is ever written.

Differentiation is one-pass on BOTH sides (round 2 — previously the
backward re-derived through the dense log-softmax, resurrecting the
[N, V] buffer the kernel exists to avoid): the forward additionally
emits the per-row logsumexp (an [N] residual), and the backward is a
stateless tile kernel ``(exp(logit - lse) - onehot) * g`` — one read of
the logits, one write of the cotangent, nothing else of [N, V] shape.

``interpret=True`` runs the same kernel on any backend for tests.
Reference CE semantics (torch ``nn.CrossEntropyLoss``,
``master/part1/part1.py:94``) pinned in ``tests/test_torch_parity.py``;
this kernel is pinned against optax in ``tests/test_fused_xent.py``.

The carried win is the absent [N, V] log-softmax buffer (peak memory,
not speed; its time against XLA's is not measured on this
installation). Default blocks (256, 512) fit VMEM with double-buffering;
(512, 4096) exceeds the 16 MB scoped limit.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30


def _kernel(
    num_v_blocks, logits_ref, labels_ref, loss_ref, lse_ref, m_ref, s_ref, p_ref
):
    vi = pl.program_id(1)
    bn, bv = logits_ref.shape

    @pl.when(vi == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        s_ref[...] = jnp.zeros_like(s_ref)
        p_ref[...] = jnp.zeros_like(p_ref)

    tile = logits_ref[...].astype(jnp.float32)
    labels = labels_ref[...]  # [bn, 1] int32
    cols = vi * bv + jax.lax.broadcasted_iota(jnp.int32, (bn, bv), 1)

    m_old = m_ref[...]
    m_new = jnp.maximum(m_old, tile.max(axis=1, keepdims=True))
    s_ref[...] = s_ref[...] * jnp.exp(m_old - m_new) + jnp.exp(tile - m_new).sum(
        axis=1, keepdims=True
    )
    m_ref[...] = m_new
    p_ref[...] += jnp.where(cols == labels, tile, 0.0).sum(axis=1, keepdims=True)

    @pl.when(vi == num_v_blocks - 1)
    def _finish():
        lse = m_ref[...] + jnp.log(s_ref[...])
        lse_ref[...] = lse
        loss_ref[...] = lse - p_ref[...]


def _bwd_kernel(logits_ref, labels_ref, lse_ref, g_ref, d_ref):
    """One tile of ``d = (softmax - onehot) * g``: softmax comes from the
    saved row logsumexp, so the tile is read once and written once —
    no cross-tile state at all."""
    vi = pl.program_id(1)
    bn, bv = logits_ref.shape
    tile = logits_ref[...].astype(jnp.float32)
    labels = labels_ref[...]
    cols = vi * bv + jax.lax.broadcasted_iota(jnp.int32, (bn, bv), 1)
    soft = jnp.exp(tile - lse_ref[...])
    d = (soft - jnp.where(cols == labels, 1.0, 0.0)) * g_ref[...]
    d_ref[...] = d.astype(d_ref.dtype)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def fused_cross_entropy(
    logits: jax.Array,
    labels: jax.Array,
    block_n: int = 256,
    block_v: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Per-example softmax CE: ``[N, V] logits, [N] int labels -> [N]``.

    Equals ``optax.softmax_cross_entropy_with_integer_labels`` (float32
    accumulation regardless of logits dtype). Any N/V: inputs are padded
    to tile multiples with ``-1e30`` columns (zero softmax mass) and
    dummy rows, both sliced away.
    """
    return _forward(logits, labels, block_n, block_v, interpret)[0]


def _blocking(n, v, block_n, block_v):
    bn, bv = min(block_n, _round_up(n, 8)), min(block_v, _round_up(v, 128))
    return bn, bv, _round_up(n, bn), _round_up(v, bv)


def _forward(logits, labels, block_n, block_v, interpret):
    n, v = logits.shape
    bn, bv, n_pad, v_pad = _blocking(n, v, block_n, block_v)
    if (n_pad, v_pad) != (n, v):
        logits = jnp.pad(
            logits, ((0, n_pad - n), (0, v_pad - v)), constant_values=_NEG
        )
        labels = jnp.pad(labels, (0, n_pad - n))
    labels2 = labels.astype(jnp.int32)[:, None]  # [N, 1]: TPU-friendly 2-D

    num_v_blocks = v_pad // bv
    scratch = [pltpu.VMEM((bn, 1), jnp.float32)] * 3
    loss, lse = pl.pallas_call(
        partial(_kernel, num_v_blocks),
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),
            jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),
        ],
        grid=(n_pad // bn, num_v_blocks),
        in_specs=[
            pl.BlockSpec((bn, bv), lambda ni, vi: (ni, vi)),
            pl.BlockSpec((bn, 1), lambda ni, vi: (ni, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, 1), lambda ni, vi: (ni, 0)),
            pl.BlockSpec((bn, 1), lambda ni, vi: (ni, 0)),
        ],
        scratch_shapes=scratch,
        interpret=interpret,
    )(logits, labels2)
    return loss[:n, 0], lse[:n, 0]


def _dense_reference(logits, labels):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None].astype(jnp.int32), axis=1)[
        :, 0
    ]


def _fwd(logits, labels, block_n, block_v, interpret):
    loss, lse = _forward(logits, labels, block_n, block_v, interpret)
    return loss, (logits, labels, lse)


def _bwd(block_n, block_v, interpret, residuals, g):
    logits, labels, lse = residuals
    n, v = logits.shape
    bn, bv, n_pad, v_pad = _blocking(n, v, block_n, block_v)
    if (n_pad, v_pad) != (n, v):
        logits = jnp.pad(
            logits, ((0, n_pad - n), (0, v_pad - v)), constant_values=_NEG
        )
        labels = jnp.pad(labels, (0, n_pad - n))
        lse = jnp.pad(lse, (0, n_pad - n))
        g = jnp.pad(g, (0, n_pad - n))
    labels2 = labels.astype(jnp.int32)[:, None]
    lse2 = lse.astype(jnp.float32)[:, None]
    g2 = g.astype(jnp.float32)[:, None]
    col = pl.BlockSpec((bn, 1), lambda ni, vi: (ni, 0))
    d = pl.pallas_call(
        _bwd_kernel,
        out_shape=jax.ShapeDtypeStruct((n_pad, v_pad), logits.dtype),
        grid=(n_pad // bn, v_pad // bv),
        in_specs=[
            pl.BlockSpec((bn, bv), lambda ni, vi: (ni, vi)),
            col, col, col,
        ],
        out_specs=pl.BlockSpec((bn, bv), lambda ni, vi: (ni, vi)),
        interpret=interpret,
    )(logits, labels2, lse2, g2)
    return (d[:n, :v], None)


fused_cross_entropy.defvjp(_fwd, _bwd)
