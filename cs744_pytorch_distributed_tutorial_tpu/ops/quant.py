"""Weight-only int8 quantization for the decode path (Pallas kernel).

No counterpart exists in the reference (it never runs inference beyond a
float eval loop, ``master/part1/part1.py:47-62``) — this is a
TPU-native *inference* capability: autoregressive decoding is bound by
HBM bandwidth (every step re-reads all projection weights plus the KV
cache), so storing the Dense kernels as int8 with a per-output-channel
float scale halves the weight traffic vs bfloat16.

Why a Pallas kernel instead of ``x @ (q * scale)`` in XLA: the decode
loop is a ``lax.scan`` whose weights are loop-invariant, so XLA hoists
any out-of-matmul dequantization above the loop — the program then reads
*bfloat16* weights every step and the bandwidth win evaporates (it only
pays the dequant once, which was never the expensive part). The kernel
dequantizes INSIDE the matmul tile loop: int8 tiles stream from HBM into
VMEM, widen to the activation dtype in registers, hit the MXU, and the
per-channel scale is applied to the f32 accumulator after the dot (for a
per-OUTPUT-channel scale the two orderings are algebraically identical).

Quantization scheme: symmetric per-output-channel —
``q = round(w / s)`` with ``s = max|w| / 127`` per column, clipped to
[-127, 127] (the -128 code is unused, keeping the scheme symmetric).
Only matmul kernels quantize; biases, embeddings, and layernorms stay in
float (they are a rounding error of the weight bytes).

``QuantDense`` is the drop-in flax module (same call surface as
``nn.Dense``) that ``models/transformer.py`` swaps in under
``quant_dense=True``; ``quantize_lm_params`` converts a trained
``TransformerLM`` param tree into the matching quantized tree.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def quantize_int8(w: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Symmetric per-output-channel int8 quantization of a ``[K, N]``
    kernel: returns ``(q int8 [K, N], scale f32 [N])`` with
    ``q * scale ~= w``. All-zero columns get scale 1 (and stay zero)."""
    if w.ndim != 2:
        raise ValueError(f"quantize_int8 expects a [K, N] kernel, got {w.shape}")
    w32 = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(w32), axis=0)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(w32 / scale[None, :]), -127, 127).astype(jnp.int8)
    return q, scale


def quantize_chunked(x: jax.Array, chunk: int) -> tuple[jax.Array, jax.Array]:
    """Symmetric per-chunk int8 quantization of a flat f32 buffer whose
    size is a multiple of ``chunk``: returns ``(q int8 [m, chunk],
    scale f32 [m])`` with ``dequantize_chunked(q, scale) ~= x``. The same
    max-abs/127 scheme as ``quantize_int8``, but grouped along the buffer
    (gradient-sync payloads have no channel structure to exploit).
    All-zero chunks get scale 1 (and stay zero)."""
    if x.ndim != 1 or x.size % chunk:
        raise ValueError(
            f"quantize_chunked expects a flat buffer sized a multiple of "
            f"{chunk}, got shape {x.shape}"
        )
    x2 = x.astype(jnp.float32).reshape(-1, chunk)
    amax = jnp.max(jnp.abs(x2), axis=1)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x2 / scale[:, None]), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_chunked(q: jax.Array, scale: jax.Array) -> jax.Array:
    """Inverse companion of ``quantize_chunked``: ``[m, chunk]`` int8 +
    ``[m]`` f32 scales -> flat f32 buffer."""
    return (q.astype(jnp.float32) * scale[:, None]).reshape(-1)


def int8_matmul_ref(x: jax.Array, q: jax.Array, scale: jax.Array) -> jax.Array:
    """XLA reference semantics of the kernel: widen-to-activation-dtype
    matmul with f32 accumulation, then the per-channel scale. Used as the
    fallback for shapes the kernel does not tile and as the test oracle
    (the kernel must match it exactly up to dot reassociation)."""
    acc = jax.lax.dot_general(
        x,
        q.astype(x.dtype),
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return (acc * scale.astype(jnp.float32)).astype(x.dtype)


def _kernel(x_ref, q_ref, s_ref, o_ref):
    x = x_ref[...]  # [bm, K] activation dtype
    w = q_ref[...]  # [K, bn] int8 — widened HERE, after the HBM read
    acc = jax.lax.dot_general(
        x, w.astype(x.dtype), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    o_ref[...] = (acc * s_ref[...]).astype(o_ref.dtype)


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    r = x.shape[axis] % mult
    if not r:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, mult - r)
    return jnp.pad(x, pad)


def default_quant_interpret() -> bool:
    """Mosaic-compile on TPU backends, interpret elsewhere — the shared
    probe (``ops/_backend.py``)."""
    from cs744_pytorch_distributed_tutorial_tpu.ops._backend import (
        default_interpret,
    )

    return default_interpret()


def int8_matmul(
    x: jax.Array,
    q: jax.Array,
    scale: jax.Array,
    *,
    block_m: int = 512,
    block_n: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """``x [..., K] @ dequant(q [K, N], scale [N]) -> [..., N]`` reading
    the weight as int8 (half the HBM bytes of bf16). Leading dims of
    ``x`` flatten into the row-block grid; K rides whole in VMEM (fine
    through d_model 4096 at the default blocks). Shapes whose K is not
    lane-aligned fall back to the XLA reference path.

    ``block_n=None`` adapts to the row count: decode-time gemv (a few
    rows against a wide weight) is per-grid-step-overhead-bound, so it
    takes 2048-wide tiles (measured ~2x over 512 at the [16,512]x[512,
    32768] head shape); matmul-shaped calls keep 512."""
    if q.ndim != 2 or scale.shape != (q.shape[1],):
        raise ValueError(
            f"expected q [K, N] and scale [N], got {q.shape} / {scale.shape}"
        )
    *lead, k = x.shape
    if q.shape[0] != k:
        raise ValueError(f"x K dim {k} != q K dim {q.shape[0]}")
    if interpret is None:
        interpret = default_quant_interpret()
    if k % 128:
        return int8_matmul_ref(x, q, scale)
    n = q.shape[1]
    x2 = x.reshape(-1, k)
    m = x2.shape[0]
    if block_n is None:
        block_n = 2048 if m <= 64 else 512
    bm, bn = min(block_m, m), min(block_n, n)
    # K rides whole per tile, so cap the block sizes as K grows or the
    # x tile ([bm, K] activation dtype) and weight tile ([K, bn] int8)
    # overflow VMEM at large d_ff (e.g. mlp_out's K = 4*d_model during
    # prefill). Budgets leave headroom for Pallas double-buffering.
    x_budget, w_budget = 2 << 20, 4 << 20
    elt = jnp.dtype(x.dtype).itemsize
    if k * elt * bm > x_budget:
        bm = max(8, x_budget // (k * elt) // 8 * 8)
    if k * bn > w_budget:
        bn = max(128, w_budget // k // 128 * 128)
    xp = _pad_to(x2, 0, bm)
    qp = _pad_to(q, 1, bn)
    sp = _pad_to(scale.astype(jnp.float32)[None, :], 1, bn)
    mp, np_ = xp.shape[0], qp.shape[1]
    out = pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((mp, np_), x.dtype),
        grid=(mp // bm, np_ // bn),
        in_specs=[
            pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
            pl.BlockSpec((k, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        interpret=interpret,
    )(xp, qp, sp)
    return out[:m, :n].reshape(*lead, n)


class QuantDense(nn.Module):
    """Drop-in ``nn.Dense`` with an int8 kernel + per-channel scale.

    Parameters are ``qkernel`` (int8, created by ``quantize_lm_params``
    from a trained kernel — ``init`` only zero-fills them for shape) and
    ``scale`` (f32); the optional bias stays float. Inference-only by
    design: the matmul is non-differentiable on the int8 side.

    Bandwidth caveat: when the input feature dim K is not a multiple of
    128 (the TPU lane width), ``int8_matmul`` silently takes the XLA
    reference path — numerically identical, but XLA hoists the dequant
    OUT of a decode scan, so the documented HBM-bytes win evaporates for
    odd-width models. Pad ``d_model``/``d_ff``/``vocab`` to 128-multiples
    (as every shipped config does) before benchmarking int8 decode.
    """

    features: int
    use_bias: bool = True
    dtype: Any = jnp.float32
    interpret: bool | None = None  # None = probe default backend

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        k = x.shape[-1]
        qkernel = self.param(
            "qkernel",
            lambda _, shape, dtype: jnp.zeros(shape, dtype),
            (k, self.features),
            jnp.int8,
        )
        scale = self.param(
            "scale",
            lambda _, shape, dtype: jnp.ones(shape, dtype),
            (self.features,),
            jnp.float32,
        )
        y = int8_matmul(
            x.astype(self.dtype), qkernel, scale, interpret=self.interpret
        )
        if self.use_bias:
            bias = self.param(
                "bias", nn.initializers.zeros_init(), (self.features,)
            )
            y = y + bias.astype(self.dtype)
        return y


def quantize_kv(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Symmetric per-(batch, position, head) int8 quantization of K or V
    rows ``[B, T, H, D]`` -> ``(q int8 [B, T, H, D], scale f32 [B, T, H])``
    with ``q * scale[..., None] ~= x``. One scale per cache row keeps the
    dequant a cheap per-key multiply applied AFTER the score/PV dot
    (``decode_attention_quant``), and rows are quantized exactly once —
    at cache-write time."""
    x32 = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x32), axis=-1)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(
        jnp.round(x32 / scale[..., None]), -127, 127
    ).astype(jnp.int8)
    return q, scale


def decode_attention_quant(
    q: jax.Array,
    cached_k: jax.Array,
    cached_v: jax.Array,
    k_scale: jax.Array,
    v_scale: jax.Array,
    pos: jax.Array,
) -> jax.Array:
    """``parallel/ring_attention.py::decode_attention`` over an int8 KV
    cache: one decode step of ``q`` [B, 1, Hq, D] against ``cached_k``/
    ``cached_v`` int8 [B, L, Hkv, D] with per-row scales [B, L, Hkv].

    The cache mutates every step, so (unlike the weight path) XLA cannot
    hoist the dequant out of the decode scan — reading int8 rows from
    HBM is the win by itself and no Pallas kernel is needed. Dequant
    rides outside the dots: scores pick up ``k_scale`` per key position
    (algebraically identical to scaling K first), and ``v_scale`` folds
    into the probabilities before the PV contraction. Positions > ``pos``
    are masked exactly as in the float variant.
    """
    b, t, hq, d = q.shape
    hkv = cached_k.shape[2]
    if hq % hkv:
        raise ValueError(f"query heads {hq} not a multiple of kv heads {hkv}")
    group = hq // hkv
    qg = q.reshape(b, t, hkv, group, d)
    scale = d**-0.5
    scores = jnp.einsum(
        "bqhgd,bkhd->bhgqk",
        qg.astype(jnp.float32),
        cached_k.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    ) * scale
    scores = scores * k_scale.transpose(0, 2, 1)[:, :, None, None, :]
    neg = jnp.float32(-1e30)
    from cs744_pytorch_distributed_tutorial_tpu.parallel.ring_attention import (
        decode_mask,
    )

    # Chunk rows sit at positions pos..pos+t-1 (see the float variant);
    # pos may be [B] for per-slot depths (serve/).
    scores = jnp.where(decode_mask(cached_k.shape[1], t, pos), scores, neg)
    probs = jax.nn.softmax(scores, axis=-1)
    pv = probs * v_scale.transpose(0, 2, 1)[:, :, None, None, :]
    out = jnp.einsum(
        "bhgqk,bkhd->bqhgd", pv, cached_v.astype(jnp.float32)
    )
    return out.reshape(b, t, hq, d).astype(q.dtype)


def paged_decode_attention_quant(
    q: jax.Array,
    key_pages: jax.Array,
    value_pages: jax.Array,
    key_scale_pages: jax.Array,
    value_scale_pages: jax.Array,
    page_table: jax.Array,
    pos: jax.Array,
) -> jax.Array:
    """``decode_attention_quant`` against paged int8 pools (``serve/``):
    ``key_pages``/``value_pages`` are ``[num_pages, page_size, Hkv*D]``
    int8 pools (heads folded, as the float pools) with per-row scale
    pools ``[num_pages, page_size, Hkv]``; ``page_table`` ``[B, P]`` and
    per-slot depths ``pos`` ``[B]`` as in the float variant. Gather
    first, unfold the slot's view, then the exact int8 decode path —
    parity with the dense int8 cache is structural.

    Reference implementation: the four-pool gather reads capacity-many
    pages per step. The serving hot path dequantizes inside the Pallas
    kernel instead — ``ops/paged_attention.py::paged_attention`` with
    ``key/value_scale_pages`` passed — reading only live pages."""
    from cs744_pytorch_distributed_tutorial_tpu.parallel.ring_attention import (
        gather_pages,
        unfold_heads,
    )

    return decode_attention_quant(
        q,
        unfold_heads(gather_pages(key_pages, page_table), q.shape[-1]),
        unfold_heads(gather_pages(value_pages, page_table), q.shape[-1]),
        gather_pages(key_scale_pages, page_table),
        gather_pages(value_scale_pages, page_table),
        pos,
    )


# All TransformerLM Dense modules whose kernels CAN quantize (embeddings
# and layernorms stay float; ``mlp_in``'s bias rides along unquantized).
QUANT_MODULES = frozenset(
    {"q", "k", "v", "attn_out", "mlp_in", "mlp_gate", "mlp_out", "lm_head"}
)
# Measured default (one v5e, docs/kernels.md's decode rows): every Pallas call
# in the decode step carries a fixed dispatch cost, so quantizing the
# small per-layer projections LOSES to XLA while the wide head matmul —
# most of the weight bytes at LM vocab sizes — wins. "head" quantizes
# only lm_head; "all" is the full set for weight-memory-bound uses.
QUANT_HEAD_ONLY = ("lm_head",)


def quantize_lm_params(params, modules=QUANT_MODULES) -> Any:
    """Convert a trained ``TransformerLM`` param tree into the tree a
    ``quant_dense=True`` clone expects: every ``modules`` Dense's
    ``kernel`` becomes ``(qkernel int8, scale f32)``; everything else
    (biases, embeddings, layernorms) passes through unchanged. With
    ``tie_embeddings=True`` there is no ``lm_head`` and the embedding's
    ``attend`` path deliberately stays float. ``modules`` must match the
    model clone's ``quant_modules``."""

    from collections.abc import Mapping

    modules = frozenset(modules)
    unknown = modules - QUANT_MODULES
    if unknown:
        raise ValueError(f"unknown quant modules {sorted(unknown)}")

    def walk(tree):
        out = {}
        for name, sub in tree.items():
            if (
                name in modules
                and isinstance(sub, Mapping)
                and "kernel" in sub
            ):
                kernel = jnp.asarray(sub["kernel"])
                if kernel.ndim == 3:
                    # scan_layers layout: a stacked [L, K, N] kernel
                    # quantizes per layer — nn.scan slices it back to
                    # ([K, N] int8, [N] scale) per step, exactly what
                    # QuantDense expects.
                    qkernel, scale = jax.vmap(quantize_int8)(kernel)
                else:
                    qkernel, scale = quantize_int8(kernel)
                new = {"qkernel": qkernel, "scale": scale}
                for extra, leaf in sub.items():
                    if extra != "kernel":
                        new[extra] = leaf
                out[name] = new
            elif isinstance(sub, Mapping):
                out[name] = walk(sub)
            else:
                out[name] = sub
        return out

    return walk(params)
