"""Flash attention as a Pallas TPU kernel — the on-chip hot path.

The reference has no attention (SURVEY §5.7); this kernel is the
single-chip compute half of the framework's long-context story:
``parallel/ring_attention.py`` moves K/V blocks BETWEEN chips over ICI,
and this kernel is the within-chip blockwise attention that never
materializes the [T, T] score matrix — scores live tile-at-a-time in
VMEM, with the flash-style running (max, normalizer, accumulator) update.

Layout: [B, T, H, D] (the model zoo's convention), computed per
(batch*head) over a grid of query blocks. K/V for one (batch, head) ride
in VMEM whole (T*D*4 bytes each — ~2 MB at T=4096, D=128, well inside
the ~16 MB budget); the kernel loops over K blocks, and the causal
variant prunes the loop to blocks at or below the query block's
diagonal. Softmax statistics accumulate in float32 regardless of input
dtype (bfloat16 inputs hit the MXU; the normalizer stays full precision).

Differentiation: ``jax.custom_vjp`` with Pallas kernels on BOTH sides
(FlashAttention-2 style). The forward additionally emits the per-row
logsumexp; the backward recomputes score tiles from (q, k, lse) and
accumulates dq (grid over query blocks) and dk/dv (grid over key
blocks) — nothing of [T, T] shape is materialized in either direction.
The softmax-grad identity ``ds = p * (dp - rowsum(do*o))`` uses the
delta vector computed once outside the kernel.

``interpret=True`` runs the same kernels on any backend for tests.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_NEG = -1e30


def _kernel(
    causal: bool, block_k: int, scale: float, q_ref, k_ref, v_ref, o_ref,
    lse_ref=None,
):
    block_q, d = q_ref.shape[1], q_ref.shape[2]
    t = k_ref.shape[1]
    qi = pl.program_id(1)
    # Feed the MXU its native input dtype (bf16 stays bf16 — casting to
    # f32 first would quarter the matmul rate); accumulate in f32 via
    # preferred_element_type, scale afterwards (distributes).
    q = q_ref[0]

    m0 = jnp.full((block_q, 1), _NEG, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros((block_q, d), jnp.float32)

    def body(kb, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(kb * block_k, block_k), :]
        v = v_ref[0, pl.ds(kb * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [block_q, block_k] f32
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            k_pos = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(q_pos >= k_pos, s, _NEG)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        correction = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_new = correction * l + p.sum(axis=-1, keepdims=True)
        acc_new = acc * correction + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc_new

    if causal:
        # Blocks strictly above the diagonal contribute nothing: stop at
        # the query block's last row.
        num_kb = (qi * block_q + block_q + block_k - 1) // block_k
    else:
        num_kb = t // block_k
    m, l, acc = jax.lax.fori_loop(0, num_kb, body, (m0, l0, acc0))
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    if lse_ref is not None:
        lse_ref[0] = m + jnp.log(l)  # [block_q, 1]


def _pick_block(t: int, preferred: int) -> int:
    if t % preferred == 0:
        return preferred
    b = min(t, preferred)
    while t % b:
        b -= 1
    if b < min(t, 8):
        # A degenerate divisor (worst case 1 when T is prime) would grid
        # one sublane-padded row per step — orders of magnitude slower
        # than dense. Refuse instead of silently crawling.
        raise ValueError(
            f"sequence length {t} has no block divisor >= 8 near {preferred}; "
            "pad the sequence to a multiple of the block size"
        )
    return b


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    block_q: int = 512,
    block_k: int = 1024,
    interpret: bool = False,
) -> jax.Array:
    """Blockwise attention on [B, T, H, D] without the [T, T] matrix.

    Default blocks measured on TPU v5e (T=2048, D=64, bf16): (512, 1024)
    runs 2.5x faster than XLA dense attention forward; the earlier
    (128, 128) default was 2x SLOWER than dense — per-iteration VPU
    overhead dominates small tiles. ``_pick_block`` shrinks to a divisor
    for short sequences."""
    return _forward(q, k, v, causal, block_q, block_k, interpret)


def _to_bh(x, b, t, h, d):  # [B, T, H, D] -> [B*H, T, D]
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def _from_bh(x, b, t, h, d):
    return x.reshape(b, h, t, d).transpose(0, 2, 1, 3)


def _forward(q, k, v, causal, block_q, block_k, interpret, with_lse=False):
    b, t, h, d = q.shape
    block_q = _pick_block(t, block_q)
    block_k = _pick_block(t, block_k)
    scale = d**-0.5

    qb, kb, vb = (_to_bh(x, b, t, h, d) for x in (q, k, v))
    q_spec = pl.BlockSpec((1, block_q, d), lambda bh, qi: (bh, qi, 0))
    kv_spec = pl.BlockSpec((1, t, d), lambda bh, qi: (bh, 0, 0))
    # Row statistics ride as [BH, T, 1]: a trailing singleton keeps the
    # last-two-dims (8, 128)-divisibility rule satisfiable at any block.
    row_spec = pl.BlockSpec((1, block_q, 1), lambda bh, qi: (bh, qi, 0))

    out_shapes = [jax.ShapeDtypeStruct(qb.shape, v.dtype)]
    out_specs = [q_spec]
    if with_lse:
        out_shapes.append(jax.ShapeDtypeStruct((b * h, t, 1), jnp.float32))
        out_specs.append(row_spec)

    res = pl.pallas_call(
        partial(_kernel, causal, block_k, scale),
        out_shape=out_shapes,
        grid=(b * h, t // block_q),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=out_specs,
        interpret=interpret,
    )(qb, kb, vb)
    out = _from_bh(res[0], b, t, h, d)
    return (out, res[1]) if with_lse else out


def _dq_kernel(
    causal, block_k, scale,
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
):
    block_q, d = q_ref.shape[1], q_ref.shape[2]
    t = k_ref.shape[1]
    qi = pl.program_id(1)
    q, do = q_ref[0], do_ref[0]
    lse = lse_ref[0]  # [bq, 1] f32
    delta = delta_ref[0]

    def body(kb, acc):
        k = k_ref[0, pl.ds(kb * block_k, block_k), :]
        v = v_ref[0, pl.ds(kb * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            k_pos = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(q_pos >= k_pos, s, _NEG)
        p = jnp.exp(s - lse)  # masked entries underflow to 0
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta)
        return acc + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        num_kb = (qi * block_q + block_q + block_k - 1) // block_k
    else:
        num_kb = t // block_k
    acc = jax.lax.fori_loop(
        0, num_kb, body, jnp.zeros((block_q, d), jnp.float32)
    )
    dq_ref[0] = (acc * scale).astype(dq_ref.dtype)


def _dkv_kernel(
    causal, block_q, scale,
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
):
    block_k, d = k_ref.shape[1], k_ref.shape[2]
    t = q_ref.shape[1]
    ki = pl.program_id(1)
    k, v = k_ref[0], v_ref[0]

    def body(qb, carry):
        dk_acc, dv_acc = carry
        q = q_ref[0, pl.ds(qb * block_q, block_q), :]
        do = do_ref[0, pl.ds(qb * block_q, block_q), :]
        lse = lse_ref[0, pl.ds(qb * block_q, block_q), :]
        delta = delta_ref[0, pl.ds(qb * block_q, block_q), :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if causal:
            q_pos = qb * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(q_pos >= k_pos, s, _NEG)
        p = jnp.exp(s - lse)
        dv_new = dv_acc + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta)
        dk_new = dk_acc + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return dk_new, dv_new

    if causal:
        # Query blocks strictly above this key block's first row see none
        # of it: start at the block containing that row.
        start_qb = (ki * block_k) // block_q
    else:
        start_qb = 0
    zeros = jnp.zeros((block_k, d), jnp.float32)
    dk_acc, dv_acc = jax.lax.fori_loop(
        start_qb, t // block_q, body, (zeros, zeros)
    )
    dk_ref[0] = (dk_acc * scale).astype(dk_ref.dtype)
    dv_ref[0] = dv_acc.astype(dv_ref.dtype)


def flash_forward_lse(
    q, k, v, causal=False, block_q=512, block_k=1024, interpret=False
):
    """Non-differentiable forward primitive returning ``(out, lse)`` with
    ``lse`` as ``[B*H, T, 1]`` float32 — the building block composite
    attentions (``parallel/ring_attention.py::ring_flash_attention``)
    merge across partial key sets. Differentiate the composite with its
    own custom_vjp, not through this."""
    return _forward(q, k, v, causal, block_q, block_k, interpret, with_lse=True)


def flash_delta(out, g):
    """The softmax-grad row term delta = rowsum(do * o) as [B*H, T, 1]
    float32 — O(T*D), no [T, T] shape, plain XLA."""
    b, t, h, d = out.shape
    ob, gb = _to_bh(out, b, t, h, d), _to_bh(g, b, t, h, d)
    return jnp.sum(
        gb.astype(jnp.float32) * ob.astype(jnp.float32), axis=-1, keepdims=True
    )


def flash_dq(
    q, k, v, do, lse, delta, causal, block_q=512, block_k=1024, interpret=False
):
    """dq for attention of ``q`` [B,Tq,H,D] against keys ``k``/``v``
    [B,Tk,H,D], given the FINAL per-row ``lse``/``delta`` [B*H,Tq,1].
    With an lse computed over a superset of these keys (a merged
    multi-block softmax), this yields exactly this key-set's additive
    contribution to dq."""
    b, tq, h, d = q.shape
    tk = k.shape[1]
    block_q = _pick_block(tq, block_q)
    block_k = _pick_block(tk, block_k)
    scale = d**-0.5
    qb, kb, vb, gb = (
        _to_bh(x, b, x.shape[1], h, d) for x in (q, k, v, do)
    )
    q_tile = pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0))
    kv_full = pl.BlockSpec((1, tk, d), lambda i, j: (i, 0, 0))
    row_tile = pl.BlockSpec((1, block_q, 1), lambda i, j: (i, j, 0))
    dq = pl.pallas_call(
        partial(_dq_kernel, causal, block_k, scale),
        out_shape=jax.ShapeDtypeStruct(qb.shape, q.dtype),
        grid=(b * h, tq // block_q),
        in_specs=[q_tile, kv_full, kv_full, q_tile, row_tile, row_tile],
        out_specs=q_tile,
        interpret=interpret,
    )(qb, kb, vb, gb, lse, delta)
    return _from_bh(dq, b, tq, h, d)


def flash_dkv(
    q, k, v, do, lse, delta, causal, block_q=512, block_k=1024, interpret=False
):
    """(dk, dv) for keys ``k``/``v`` [B,Tk,H,D] under queries ``q``
    [B,Tq,H,D] with FINAL ``lse``/``delta`` [B*H,Tq,1] (see flash_dq)."""
    b, tq, h, d = q.shape
    tk = k.shape[1]
    block_q = _pick_block(tq, block_q)
    block_k = _pick_block(tk, block_k)
    scale = d**-0.5
    qb, kb, vb, gb = (
        _to_bh(x, b, x.shape[1], h, d) for x in (q, k, v, do)
    )
    q_full = pl.BlockSpec((1, tq, d), lambda i, j: (i, 0, 0))
    k_tile = pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0))
    row_full = pl.BlockSpec((1, tq, 1), lambda i, j: (i, 0, 0))
    dk, dv = pl.pallas_call(
        partial(_dkv_kernel, causal, block_q, scale),
        out_shape=[
            jax.ShapeDtypeStruct(kb.shape, k.dtype),
            jax.ShapeDtypeStruct(vb.shape, v.dtype),
        ],
        grid=(b * h, tk // block_k),
        in_specs=[q_full, k_tile, k_tile, q_full, row_full, row_full],
        out_specs=[k_tile, k_tile],
        interpret=interpret,
    )(qb, kb, vb, gb, lse, delta)
    return _from_bh(dk, b, tk, h, d), _from_bh(dv, b, tk, h, d)


def _fwd(q, k, v, causal, block_q, block_k, interpret):
    out, lse = _forward(q, k, v, causal, block_q, block_k, interpret, with_lse=True)
    return out, (q, k, v, out, lse)


def _bwd(causal, block_q, block_k, interpret, residuals, g):
    q, k, v, out, lse = residuals
    delta = flash_delta(out, g)
    dq = flash_dq(q, k, v, g, lse, delta, causal, block_q, block_k, interpret)
    dk, dv = flash_dkv(q, k, v, g, lse, delta, causal, block_q, block_k, interpret)
    return dq, dk, dv


flash_attention.defvjp(_fwd, _bwd)
