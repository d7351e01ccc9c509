"""Custom conv backward (weight-gradient) Pallas kernel — the scored
step's hot spot.

Profiling the ResNet-18/CIFAR training step on the TPU (see
``docs/kernels.md``) shows the conv *weight gradients* are where
XLA leaves the most on the table: the stage-1 wgrads run at ~55 TF/s
(``EmitAllBatchInSublanes`` emitter) while the same chip does ~190 TF/s
on the forward convs of deeper stages. The reference hits the analogous
path through ``loss.backward()`` into cuDNN/ATen
(``master/part1/part1.py:37``); here the backward is ours to schedule.

The kernel computes, for a 3x3 (stride 1 or 2, SAME) NHWC conv:

    dW[ky,kx,c,k] = sum_{b,y,x} X[b, s*y+ky-p, s*x+kx-p, c] * G[b,y,x,k]

as ONE MXU contraction per batch-chunk: the 9 shifted/masked copies of
the X chunk are materialized *in VMEM only* (never HBM) and concatenated
into an im2col block [M, 9C], then a single
``[M, 9C]^T @ [M, K] -> [9C, K]`` dot accumulates into a float32 VMEM
scratch across sequential grid steps. Putting all 9 taps in one dot
matters: output rows 9C (vs C per-tap) keep the MXU's 128-row tiles
full, which is exactly what XLA's per-tap wgrad schedule gives up.

HBM traffic is the unavoidable one read of X and G; everything else
(im2col, accumulator) stays on-chip. The forward and the data-gradient
stay on XLA's conv emitter (already at its lane-fill ceiling);
``conv3x3`` wires this wgrad into ``jax.custom_vjp``.

``interpret=True`` runs the same kernel on CPU for tests.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["conv3x3_wgrad", "conv3x3"]


def _shift2d(xv: jnp.ndarray, dy: int, dx: int) -> jnp.ndarray:
    """``out[b, y, x, c] = xv[b, y+dy, x+dx, c]``, zero where out of
    bounds. Pure value-level concats — Mosaic vector ops, VMEM only.
    The boundary zeros are derived from slices (``xv[...] * 0``) rather
    than ``jnp.zeros``: in interpret mode the kernel inlines into the
    enclosing trace, and under a check_vma shard_map a freshly created
    (replicated) zeros array cannot concatenate with the device-varying
    operand."""
    if dy == 1:
        xv = jnp.concatenate([xv[:, 1:], xv[:, :1] * 0], axis=1)
    elif dy == -1:
        xv = jnp.concatenate([xv[:, :1] * 0, xv[:, :-1]], axis=1)
    if dx == 1:
        xv = jnp.concatenate([xv[:, :, 1:], xv[:, :, :1] * 0], axis=2)
    elif dx == -1:
        xv = jnp.concatenate([xv[:, :, :1] * 0, xv[:, :, :-1]], axis=2)
    return xv


def _wgrad_kernel_s1(x_ref, g_ref, o_ref, acc_ref):
    """Stride-1 SAME: taps are (dy, dx) in {-1,0,1}^2 shifts.

    MXU-native dimension order: the only contraction combos Mosaic lowers
    without inserting vector transposes contract lhs dim 1 / rhs dim 0
    or 1. Contracting over the sample axis M therefore wants one operand
    with M in lanes — we transpose the *small* operand (the g chunk,
    [M, K] -> [K, M]) once per chunk and compute
    ``dW^T [K, 9C] = gT @ im2col`` with native dims; the [9C, K]
    orientation is restored outside the kernel on the tiny result."""
    xv = x_ref[...]
    bb, h, w, c = xv.shape
    k = g_ref.shape[-1]
    taps = [
        _shift2d(xv, dy, dx) for dy in (-1, 0, 1) for dx in (-1, 0, 1)
    ]
    im2col = jnp.concatenate(taps, axis=-1).reshape(bb * h * w, 9 * c)
    gt = g_ref[...].reshape(bb * h * w, k).T
    contrib = lax.dot_general(
        gt, im2col, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(pl.program_id(1) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += contrib

    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _out():
        o_ref[...] = acc_ref[...]


def _wgrad_kernel_s2(p00, p01, p10, p11, g_ref, o_ref, acc_ref):
    """Stride-2 SAME on even H, W (pad_lo=0, pad_hi=1): input row for
    output row y' at tap dy is ``2y' + dy`` — parity ``dy % 2``, shifted
    by ``dy // 2`` with the far edge (the pad_hi row) zeroed. The four
    parity planes are de-interleaved OUTSIDE the kernel (cheap fused XLA
    slices): Mosaic lowers neither the in-kernel [H/2, 2] reshape nor
    strided vector slices, but plain shifts of pre-split planes it
    handles fine — the same concat idiom as the stride-1 kernel."""
    planes = {
        (0, 0): p00[...], (0, 1): p01[...],
        (1, 0): p10[...], (1, 1): p11[...],
    }
    bb, ho, wo, c = planes[(0, 0)].shape
    k = g_ref.shape[-1]
    taps = []
    for dy in (0, 1, 2):
        for dx in (0, 1, 2):
            t = planes[(dy % 2, dx % 2)]
            taps.append(_shift2d(t, dy // 2, dx // 2))
    im2col = jnp.concatenate(taps, axis=-1).reshape(bb * ho * wo, 9 * c)
    gt = g_ref[...].reshape(bb * ho * wo, k).T
    contrib = lax.dot_general(
        gt, im2col, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(pl.program_id(1) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += contrib

    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _out():
        o_ref[...] = acc_ref[...]


def _pick_block_batch(b: int, h: int, w: int, c: int) -> int:
    """Largest batch chunk whose im2col block [bb*h*w, 9c] (bf16) stays
    within ~3 MB. Peak VMEM is roughly taps + im2col (the concat holds
    both live) + f32 accumulator + double-buffered input blocks, against
    the 16 MB scoped limit — 3 MB each keeps the sum comfortably under."""
    budget = 3 * 1024 * 1024
    bb = max(1, budget // (h * w * 9 * c * 2))
    while b % bb:
        bb -= 1
    return bb


@partial(jax.jit, static_argnames=("stride", "block_batch", "interpret"))
def conv3x3_wgrad(
    x: jax.Array,
    g: jax.Array,
    *,
    stride: int = 1,
    block_batch: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Weight gradient of a 3x3 SAME conv (NHWC, no bias): returns
    ``dW [3, 3, C, K]`` float32. ``x`` is the conv input [B,H,W,C],
    ``g`` the output cotangent [B,Ho,Wo,K]."""
    b, h, w, c = x.shape
    gb, ho, wo, k = g.shape
    if stride not in (1, 2):
        raise ValueError(f"stride {stride} unsupported (1 or 2)")
    if gb != b or ho != h // stride or wo != w // stride:
        # ValueError, not assert: a mismatched cotangent under python -O
        # would otherwise reach the kernel and mis-accumulate opaquely.
        raise ValueError(
            f"cotangent shape {g.shape} inconsistent with input {x.shape} "
            f"at stride {stride} (expected [{b}, {h // stride}, "
            f"{w // stride}, K])"
        )
    if stride == 2 and (h % 2 or w % 2):
        raise ValueError("stride-2 wgrad needs even H, W")
    if interpret and jax.typeof(x).vma:
        # Inside a check_vma=True shard_map, interpret-mode pallas
        # inlines the kernel into the vma-checked trace, where its
        # replicated constants (scratch init, boundary zeros) cannot
        # meet the device-varying operands. Use the reference
        # formulation there — the kernel's numerics are pinned by the
        # direct tests, and compiled runs never take this branch. The
        # vjp point is pcast varying so the result keeps the LOCAL-grad
        # contract (no implicit psum).
        def f(wk):
            return lax.conv_general_dilated(
                x, wk, (stride, stride), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
            )

        w0 = jnp.zeros((3, 3, c, k), x.dtype)
        for name in sorted(jax.typeof(x).vma | jax.typeof(g).vma):
            w0 = lax.pcast(w0, name, to="varying")
        return jax.vjp(f, w0)[1](g)[0].astype(jnp.float32)

    bb = block_batch or _pick_block_batch(b, h, w, c)
    if b % bb:
        raise ValueError(
            f"block_batch {bb} must divide batch {b} — a non-divisor would "
            "silently drop trailing samples from the accumulated dW"
        )
    # K tiles keep the f32 accumulator small enough for VMEM alongside
    # the im2col block (deep stages: [512, 4608] f32 alone is 9.4 MB).
    kb = k
    while kb > 128 and kb % 2 == 0 and kb * 9 * c * 4 > 3 * 1024 * 1024:
        kb //= 2
    assert k % kb == 0, (k, kb)
    scratch = pltpu.VMEM((kb, 9 * c), jnp.float32)
    # Under a check_vma=True shard_map (the CIFAR engine), pallas
    # outputs must declare their device-varying axes; the wgrad
    # inherits the union of its operands' (activations vary over
    # the data axis).
    out_shape = jax.ShapeDtypeStruct(
        (k, 9 * c),
        jnp.float32,
        vma=jax.typeof(x).vma | jax.typeof(g).vma,
    )
    g_spec = pl.BlockSpec((bb, ho, wo, kb), lambda j, i: (i, 0, 0, j))
    out_spec = pl.BlockSpec((kb, 9 * c), lambda j, i: (j, 0))
    # Grid order (k_tile, batch): batch innermost, so the accumulator
    # finishes a full pass over B before the next K tile reinitializes
    # it. X blocks are re-read once per K tile — bounded, tiny traffic.
    if stride == 1:
        out = pl.pallas_call(
            _wgrad_kernel_s1,
            grid=(k // kb, b // bb),
            in_specs=[
                pl.BlockSpec((bb, h, w, c), lambda j, i: (i, 0, 0, 0)),
                g_spec,
            ],
            out_specs=out_spec,
            out_shape=out_shape,
            scratch_shapes=[scratch],
            interpret=interpret,
        )(x, g)
    else:
        # De-interleave the four stride-2 parity planes in XLA (fused
        # strided slices) — Mosaic lowers neither the in-kernel
        # [H/2, 2] reshape nor strided vector slices.
        planes = [x[:, p::2, q::2, :] for p in (0, 1) for q in (0, 1)]
        plane_spec = pl.BlockSpec((bb, ho, wo, c), lambda j, i: (i, 0, 0, 0))
        out = pl.pallas_call(
            _wgrad_kernel_s2,
            grid=(k // kb, b // bb),
            in_specs=[plane_spec] * 4 + [g_spec],
            out_specs=out_spec,
            out_shape=out_shape,
            scratch_shapes=[scratch],
            interpret=interpret,
        )(*planes, g)
    # Kernel emits dW^T [K, 9C]; rows of 9C are tap-major/channel-minor.
    return out.T.reshape(3, 3, c, k)


def _conv_fwd(x: jax.Array, w: jax.Array, stride: int) -> jax.Array:
    return lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=x.dtype,
    )


@partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def conv3x3(x: jax.Array, w: jax.Array, stride: int = 1,
            interpret: bool = False) -> jax.Array:
    """3x3 SAME conv (NHWC, HWIO weights, no bias) whose backward uses
    the Pallas wgrad kernel. Forward and data-grad stay on XLA's conv
    emitter — those already run at the MXU lane-fill ceiling; the wgrad
    is the schedule XLA loses (see module docstring)."""
    return _conv_fwd(x, w, stride)


def _conv3x3_fwd_rule(x, w, stride, interpret):
    return _conv_fwd(x, w, stride), (x, w)


def _match_vma(val, like):
    """psum ``val`` over the varying axes it carries beyond ``like``'s —
    exactly the reduction AD's transpose would insert for a replicated
    primal under a check_vma shard_map (the engine's 'auto' strategy);
    a no-op when the primal is itself device-varying (manual
    strategies, which pcast params before differentiating)."""
    extra = tuple(sorted(jax.typeof(val).vma - jax.typeof(like).vma))
    if extra:
        val = lax.psum(val, extra)
    return val


def _conv3x3_bwd_rule(stride, interpret, res, g):
    x, w = res
    # dgrad via XLA's transposed conv (the emitter already at ceiling).
    _, dgrad = jax.vjp(lambda xx: _conv_fwd(xx, w, stride), x)
    (dx,) = dgrad(g)
    dw = conv3x3_wgrad(x, g, stride=stride, interpret=interpret)
    return _match_vma(dx, x), _match_vma(dw.astype(w.dtype), w)


conv3x3.defvjp(_conv3x3_fwd_rule, _conv3x3_bwd_rule)
