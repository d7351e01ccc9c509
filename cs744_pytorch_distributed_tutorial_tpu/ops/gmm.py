"""Grouped (ragged) matmul — the compute core of dropless MoE.

The reference has no MoE (data parallelism over one dense VGG-11 is its
whole scope, SURVEY §2.3); this module extends the framework's
expert-parallel family with the *dropless* formulation: tokens sorted by
expert form E contiguous row groups of **data-dependent** sizes, and each
group multiplies its own expert matrix —

    out[start_e : end_e] = lhs[start_e : end_e] @ rhs[e]

with ``group_sizes`` a traced ``[E]`` vector (static SHAPES, dynamic
row counts — the XLA-compatible middle ground between the capacity-slot
formulation's fixed padding and torch-style fully dynamic dispatch).

Two implementations, parity-tested against each other and a dense
oracle:

- ``impl="ragged"`` — ``jax.lax.ragged_dot``: XLA's native ragged
  contraction, differentiable out of the box.
- ``impl="pallas"`` — a megablocks-style TPU kernel (`gmm`), grid over
  (n-tile, visit-step) with scalar-prefetched step→(row-tile, group)
  maps: each group's row span is walked tile by tile, boundary tiles are
  row-masked, and output tiles accumulate in VMEM across the consecutive
  steps that share them (grid iteration on TPU is sequential, so a
  revisited output block stays resident). The backward pair is
  ``dx = gmm(dout, rhsᵀ)`` (same kernel, transposed experts) and
  ``dw = tgmm`` (per-group ``lhsᵀ @ dout``, same step maps, output
  block keyed by group) under ``jax.custom_vjp``.

The step count is the static upper bound ``M/block_m + E - 1`` (each
group boundary adds at most one revisited row tile); unused trailing
steps are masked off with a prefetched validity flag, costing at most
``E - 1`` wasted tile-matmuls — noise next to the ``M·K·N`` useful work.
"""

from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


def _step_maps(group_sizes, m_padded: int, block_m: int, num_steps: int):
    """Traced step→(group, row-tile) maps for the visit schedule.

    ``group_sizes`` must sum to ``m_padded`` (the wrapper folds padding
    into the last group). Returns int32 arrays of length ``num_steps``:
    ``sg`` (group id), ``sm`` (row-tile id), ``first`` (1 where this
    step is its row tile's first visit — zero-initialize the output
    block), ``valid`` (0 for trailing dummy steps), plus per-group
    ``start``/``end`` row offsets for in-kernel row masking.
    """
    e = group_sizes.shape[0]
    offs = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(group_sizes, dtype=jnp.int32)]
    )
    start, end = offs[:-1], offs[1:]
    nonempty = end > start
    first_tile = start // block_m
    tiles = jnp.where(nonempty, -((-end) // block_m) - first_tile, 0)
    step_start = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(tiles, dtype=jnp.int32)]
    )
    total = step_start[-1]
    s = jnp.arange(num_steps, dtype=jnp.int32)
    sg = jnp.searchsorted(step_start[1:], s, side="right").astype(jnp.int32)
    sg = jnp.clip(sg, 0, e - 1)
    sm = first_tile[sg] + (s - step_start[sg])
    # Trailing dummy steps repeat the LAST real step's (group, tile) so
    # they never look like a fresh first-visit; `valid` masks their
    # contribution (the last real tile would otherwise double-count).
    last = jnp.maximum(total - 1, 0)
    sg = jnp.where(s < total, sg, sg[last])
    sm = jnp.clip(jnp.where(s < total, sm, sm[last]), 0, m_padded // block_m - 1)
    prev = jnp.concatenate([jnp.full((1,), -1, jnp.int32), sm[:-1]])
    first = ((sm != prev) & (s < total)).astype(jnp.int32)
    valid = (s < total).astype(jnp.int32)
    return sg, sm, first, valid, start, end


def _row_mask(row0, start_g, end_g, block_m: int):
    ids = row0 + lax.broadcasted_iota(jnp.int32, (block_m, 1), 0)
    return (ids >= start_g) & (ids < end_g)


def _gmm_kernel(block_m: int, sg, sm, first, valid, start, end,
                lhs_ref, rhs_ref, out_ref):
    s = pl.program_id(1)
    g = sg[s]
    mask = _row_mask(sm[s] * block_m, start[g], end[g], block_m)
    x = jnp.where(mask, lhs_ref[...], jnp.zeros_like(lhs_ref[...]))
    partial_ = jnp.dot(
        x, rhs_ref[0], preferred_element_type=jnp.float32
    )

    @pl.when(first[s] == 1)
    def _init():
        out_ref[...] = partial_

    @pl.when((first[s] == 0) & (valid[s] == 1))
    def _acc():
        out_ref[...] += partial_


def _gmm_live_kernel(block_m: int, sg, sm, first, valid, start, end,
                     lhs_ref, rhs_ref, out_ref):
    """``_gmm_kernel`` where the groups need not fill the rows: a
    trailing dummy step (it repeats the last real step's blocks, so it
    moves nothing) multiplies nothing either."""
    s = pl.program_id(1)

    @pl.when(valid[s] == 1)
    def _visit():
        g = sg[s]
        mask = _row_mask(sm[s] * block_m, start[g], end[g], block_m)
        x = jnp.where(mask, lhs_ref[...], jnp.zeros_like(lhs_ref[...]))
        partial_ = jnp.dot(
            x, rhs_ref[0], preferred_element_type=jnp.float32
        )

        @pl.when(first[s] == 1)
        def _init():
            out_ref[...] = partial_

        @pl.when(first[s] == 0)
        def _acc():
            out_ref[...] += partial_


def _tgmm_kernel(block_m: int, sg, sm, first_g, valid, start, end,
                 lhs_ref, dout_ref, out_ref):
    s = pl.program_id(1)
    g = sg[s]
    mask = _row_mask(sm[s] * block_m, start[g], end[g], block_m)
    x = jnp.where(mask, lhs_ref[...], jnp.zeros_like(lhs_ref[...]))
    partial_ = lax.dot_general(
        x, dout_ref[...], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )[None]

    @pl.when(first_g[s] == 1)
    def _init():
        out_ref[...] = partial_

    @pl.when((first_g[s] == 0) & (valid[s] == 1))
    def _acc():
        out_ref[...] += partial_


def _pad_rows(x, m_padded: int):
    m = x.shape[0]
    if m == m_padded:
        return x
    return jnp.pad(x, ((0, m_padded - m), (0, 0)))


def _prep(lhs, group_sizes, block_m: int, num_experts: int,
          fold: bool = True):
    """Pad rows to a tile multiple and fold the padding into the LAST
    group (padded rows compute garbage that the caller's row count
    slices away; zero lhs rows keep the garbage finite). Without
    ``fold`` the rows past the groups belong to none: no step visits a
    tile that holds only such rows."""
    m = lhs.shape[0]
    m_padded = max(_ceil_to(m, block_m), block_m)
    lhs = _pad_rows(lhs, m_padded)
    gs = group_sizes.astype(jnp.int32)
    if fold:
        gs = gs.at[num_experts - 1].add(m_padded - jnp.sum(gs))
    return lhs, gs, m_padded


def _gmm_fwd_impl(lhs, rhs, group_sizes, block_m, block_n, interpret,
                  live_only=False):
    m, k = lhs.shape
    e, _, n = rhs.shape
    lhs_p, gs, m_padded = _prep(lhs, group_sizes, block_m, e, not live_only)
    bn = min(block_n, n)
    num_steps = m_padded // block_m + e - 1
    sg, sm, first, valid, start, end = _step_maps(
        gs, m_padded, block_m, num_steps
    )
    grid = (-(-n // bn), num_steps)
    n_padded = _ceil_to(n, bn)
    if n_padded != n:
        rhs = jnp.pad(rhs, ((0, 0), (0, 0), (0, n_padded - n)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, k), lambda j, s, sg, sm, *_: (sm[s], 0)),
            pl.BlockSpec((1, k, bn), lambda j, s, sg, sm, *_: (sg[s], 0, j)),
        ],
        out_specs=pl.BlockSpec(
            (block_m, bn), lambda j, s, sg, sm, *_: (sm[s], j)
        ),
    )
    out = pl.pallas_call(
        partial(_gmm_live_kernel if live_only else _gmm_kernel, block_m),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m_padded, n_padded), jnp.float32),
        interpret=interpret,
    )(sg, sm, first, valid, start, end, lhs_p, rhs)
    return out[:m, :n]


def _tgmm_impl(lhs, dout, group_sizes, num_experts, block_m, block_n,
               interpret):
    """Per-group ``lhsᵀ @ dout`` → ``[E, K, N]`` (the dW of gmm)."""
    m, k = lhs.shape
    n = dout.shape[1]
    e = num_experts
    lhs_p, gs, m_padded = _prep(lhs, group_sizes, block_m, e)
    dout_p = _pad_rows(dout, m_padded)
    bn = min(block_n, n)
    n_padded = _ceil_to(n, bn)
    if n_padded != n:
        dout_p = jnp.pad(dout_p, ((0, 0), (0, n_padded - n)))
    num_steps = m_padded // block_m + e - 1
    sg, sm, first, valid, start, end = _step_maps(
        gs, m_padded, block_m, num_steps
    )
    # first-visit is per GROUP here (the output block is keyed by sg);
    # a group's steps are consecutive by construction.
    prev_g = jnp.concatenate([jnp.full((1,), -1, jnp.int32), sg[:-1]])
    first_g = ((sg != prev_g) & (valid == 1)).astype(jnp.int32)
    grid = (-(-n // bn), num_steps)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, k), lambda j, s, sg, sm, *_: (sm[s], 0)),
            pl.BlockSpec((block_m, bn), lambda j, s, sg, sm, *_: (sm[s], j)),
        ],
        out_specs=pl.BlockSpec(
            (1, k, bn), lambda j, s, sg, sm, *_: (sg[s], 0, j)
        ),
    )
    dw = pl.pallas_call(
        partial(_tgmm_kernel, block_m),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((e, k, n_padded), jnp.float32),
        interpret=interpret,
    )(sg, sm, first_g, valid, start, end, lhs_p, dout_p)
    dw = dw[:, :, :n]
    # Empty groups are never visited — their (uninitialized) blocks must
    # read as zero gradient.
    return jnp.where((group_sizes > 0)[:, None, None], dw, 0.0)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _gmm_pallas(lhs, rhs, group_sizes, block_m, block_n, interpret,
                live_only):
    return _gmm_fwd_impl(
        lhs, rhs, group_sizes, block_m, block_n, interpret, live_only
    )


def _gmm_pallas_fwd(lhs, rhs, group_sizes, block_m, block_n, interpret,
                    live_only):
    out = _gmm_fwd_impl(
        lhs, rhs, group_sizes, block_m, block_n, interpret, live_only
    )
    return out, (lhs, rhs, group_sizes)


def _gmm_pallas_bwd(block_m, block_n, interpret, live_only, res, dout):
    # the folded kernels: a cotangent's rows past the groups are the
    # caller's to zero, as the forward's are the caller's to drop
    lhs, rhs, group_sizes = res
    return _gmm_bwd_core(
        lhs, rhs, group_sizes, dout, block_m, block_n, interpret,
        with_bias=False,
    )


_gmm_pallas.defvjp(_gmm_pallas_fwd, _gmm_pallas_bwd)


def _gmm_fused_kernel(block_m: int, act: str, h_dtype,
                      sg, sm, first, valid, start, end,
                      lhs_ref, rhs_ref, b_ref, h_ref, z_ref=None):
    """Grouped matmul with a bias(+activation) EPILOGUE. Each row
    belongs to exactly one group, so no cross-visit accumulation is
    needed: a visit writes its group's rows (``where`` on the row
    mask) and leaves the others to their own visits. When a ``z_ref``
    output is present (the differentiated gelu path) the
    pre-activation is emitted too — the backward's gelu' input."""
    s = pl.program_id(1)
    g = sg[s]
    mask = _row_mask(sm[s] * block_m, start[g], end[g], block_m)
    sel = mask & (valid[s] == 1)
    x = jnp.where(mask, lhs_ref[...], jnp.zeros_like(lhs_ref[...]))
    val = jnp.dot(
        x, rhs_ref[0], preferred_element_type=jnp.float32
    ) + b_ref[0, 0]

    @pl.when(first[s] == 1)
    def _init():
        h_ref[...] = jnp.zeros(h_ref.shape, h_ref.dtype)
        if z_ref is not None:
            z_ref[...] = jnp.zeros(z_ref.shape, z_ref.dtype)

    if z_ref is not None:
        z_ref[...] = jnp.where(sel, val.astype(z_ref.dtype), z_ref[...])
    out = jax.nn.gelu(val) if act == "gelu" else val
    h_ref[...] = jnp.where(sel, out.astype(h_dtype), h_ref[...])


def _gmm_fused_fwd_impl(lhs, rhs, bias, group_sizes, act, h_dtype,
                        block_m, block_n, interpret, with_z=False):
    m, k = lhs.shape
    e, _, n = rhs.shape
    lhs_p, gs, m_padded = _prep(lhs, group_sizes, block_m, e)
    bn = min(block_n, n)
    n_padded = _ceil_to(n, bn)
    if n_padded != n:
        rhs = jnp.pad(rhs, ((0, 0), (0, 0), (0, n_padded - n)))
        bias = jnp.pad(bias, ((0, 0), (0, n_padded - n)))
    # [E, 1, N]: Mosaic's last-two-dims tiling rule wants the
    # second-to-last block dim to equal the array's (a (1, bn) block
    # of [E, N] is rejected; (1, 1, bn) of [E, 1, N] is fine).
    bias = bias[:, None, :]
    num_steps = m_padded // block_m + e - 1
    sg, sm, first, valid, start, end = _step_maps(
        gs, m_padded, block_m, num_steps
    )
    grid = (-(-n // bn), num_steps)
    out_shape = [jax.ShapeDtypeStruct((m_padded, n_padded), h_dtype)]
    out_specs = [
        pl.BlockSpec((block_m, bn), lambda j, s, sg, sm, *_: (sm[s], j))
    ]
    if with_z:
        # Pre-activation residual for the backward's gelu', stored at
        # the COMPUTE dtype — the same bytes XLA's AD saves on the
        # unfused path (where the bias+gelu chain runs in h_dtype).
        out_shape.append(
            jax.ShapeDtypeStruct((m_padded, n_padded), h_dtype)
        )
        out_specs.append(
            pl.BlockSpec(
                (block_m, bn), lambda j, s, sg, sm, *_: (sm[s], j)
            )
        )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, k), lambda j, s, sg, sm, *_: (sm[s], 0)),
            pl.BlockSpec((1, k, bn), lambda j, s, sg, sm, *_: (sg[s], 0, j)),
            pl.BlockSpec((1, 1, bn), lambda j, s, sg, sm, *_: (sg[s], 0, j)),
        ],
        out_specs=tuple(out_specs),
    )
    out = pl.pallas_call(
        partial(_gmm_fused_kernel, block_m, act, h_dtype),
        grid_spec=grid_spec,
        out_shape=tuple(out_shape),
        interpret=interpret,
    )(sg, sm, first, valid, start, end, lhs_p, rhs, bias)
    if with_z:
        return out[0][:m, :n], out[1][:m, :n]
    return out[0][:m, :n], None


def _segment_sum_rows(dout, group_sizes, num_experts, block_m, block_n,
                      interpret):
    """Per-group column sums of ``dout`` — the bias gradient — as a
    tgmm with an all-ones [M, 1] lhs."""
    ones = jnp.ones((dout.shape[0], 1), jnp.float32)
    db = _tgmm_impl(
        ones, dout.astype(jnp.float32), group_sizes, num_experts,
        block_m, block_n, interpret,
    )
    return db.reshape(num_experts, dout.shape[1])


def _check_gmm_shapes(lhs, rhs, group_sizes):
    if lhs.ndim != 2 or rhs.ndim != 3 or lhs.shape[1] != rhs.shape[1]:
        raise ValueError(
            f"grouped_matmul shapes: lhs {lhs.shape}, rhs {rhs.shape}"
        )
    if group_sizes.shape != (rhs.shape[0],):
        raise ValueError(
            f"group_sizes {group_sizes.shape} != [num_groups {rhs.shape[0]}]"
        )


def _gmm_bwd_core(lhs, rhs, group_sizes, dout, block_m, block_n,
                  interpret, with_bias):
    """The shared backward of every Pallas grouped-matmul variant:
    dlhs = gmm(dout, rhsᵀ), drhs = tgmm(lhs, dout), and (for the fused
    variants) dbias = per-group column sums of dout."""
    dout = dout.astype(jnp.float32)
    dlhs = _gmm_fwd_impl(
        dout, jnp.swapaxes(rhs, 1, 2).astype(jnp.float32), group_sizes,
        block_m, block_n, interpret,
    ).astype(lhs.dtype)
    drhs = _tgmm_impl(
        lhs.astype(jnp.float32), dout, group_sizes, rhs.shape[0],
        block_m, block_n, interpret,
    ).astype(rhs.dtype)
    gs_ct = np.zeros(group_sizes.shape, jax.dtypes.float0)
    if not with_bias:
        return dlhs, drhs, gs_ct
    dbias = _segment_sum_rows(
        dout, group_sizes, rhs.shape[0], block_m, block_n, interpret
    ).astype(jnp.float32)
    return dlhs, drhs, dbias, gs_ct


@partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _gmm_gelu_pallas(lhs, rhs, bias, group_sizes, h_dtype, block_m,
                     block_n, interpret):
    # Undifferentiated primal: no z output (an opaque custom call's
    # outputs cannot be DCE'd, so emitting z here would pay a wasted
    # [M, N] write on every inference forward).
    return _gmm_fused_fwd_impl(
        lhs, rhs, bias, group_sizes, "gelu", h_dtype, block_m, block_n,
        interpret,
    )[0]


def _gmm_gelu_fwd(lhs, rhs, bias, group_sizes, h_dtype, block_m, block_n,
                  interpret):
    h, z = _gmm_fused_fwd_impl(
        lhs, rhs, bias, group_sizes, "gelu", h_dtype, block_m, block_n,
        interpret, with_z=True,
    )
    return h, (lhs, rhs, group_sizes, z)


def _gmm_gelu_bwd(h_dtype, block_m, block_n, interpret, res, dh):
    lhs, rhs, group_sizes, z = res
    # dz = dh * gelu'(z) — elementwise; XLA fuses the recompute.
    zf = z.astype(jnp.float32)
    _, vjp = jax.vjp(jax.nn.gelu, zf)
    (dz,) = vjp(dh.astype(jnp.float32))
    return _gmm_bwd_core(
        lhs, rhs, group_sizes, dz, block_m, block_n, interpret,
        with_bias=True,
    )


_gmm_gelu_pallas.defvjp(_gmm_gelu_fwd, _gmm_gelu_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _gmm_bias_pallas(lhs, rhs, bias, group_sizes, h_dtype, block_m,
                     block_n, interpret):
    return _gmm_fused_fwd_impl(
        lhs, rhs, bias, group_sizes, "none", h_dtype, block_m, block_n,
        interpret,
    )[0]


def _gmm_bias_fwd(lhs, rhs, bias, group_sizes, h_dtype, block_m, block_n,
                  interpret):
    h, _ = _gmm_fused_fwd_impl(
        lhs, rhs, bias, group_sizes, "none", h_dtype, block_m, block_n,
        interpret,
    )
    return h, (lhs, rhs, group_sizes)


def _gmm_bias_bwd(h_dtype, block_m, block_n, interpret, res, dout):
    lhs, rhs, group_sizes = res
    return _gmm_bwd_core(
        lhs, rhs, group_sizes, dout, block_m, block_n, interpret,
        with_bias=True,
    )


_gmm_bias_pallas.defvjp(_gmm_bias_fwd, _gmm_bias_bwd)


def grouped_matmul_fused(
    lhs,
    rhs,
    bias,
    group_sizes,
    *,
    activation: str = "none",
    out_dtype=None,
    block_m: int = 256,
    block_n: int = 512,
    interpret: bool = False,
):
    """Pallas-only grouped matmul with the per-group bias (and
    optionally gelu) fused into the kernel EPILOGUE:

        out[r] = act(lhs[r] @ rhs[g(r)] + bias[g(r)])

    The unfused pallas path pays an extra HBM round-trip of the [M, N]
    intermediate for the bias/activation elementwise chain (XLA cannot
    fuse into a Pallas custom call); the epilogue removes it. Under
    differentiation with ``activation="gelu"`` the forward also
    stashes the pre-activation at the compute dtype for the backward's
    gelu' (the same residual bytes XLA's AD saves on the unfused
    path); the undifferentiated primal emits only the output.
    Differentiable in lhs/rhs/bias.
    """
    _check_gmm_shapes(lhs, rhs, group_sizes)
    if bias.shape != (rhs.shape[0], rhs.shape[2]):
        raise ValueError(
            f"bias {bias.shape} != [groups, N] {(rhs.shape[0], rhs.shape[2])}"
        )
    if activation not in ("none", "gelu"):
        raise ValueError(f"unknown activation {activation!r}")
    h_dtype = jnp.dtype(out_dtype or lhs.dtype)
    fn = _gmm_gelu_pallas if activation == "gelu" else _gmm_bias_pallas
    return fn(
        lhs, rhs, bias.astype(jnp.float32), group_sizes, h_dtype,
        block_m, block_n, interpret,
    )


def grouped_matmul(
    lhs,
    rhs,
    group_sizes,
    *,
    impl: str = "ragged",
    precision=None,
    block_m: int = 256,
    block_n: int = 512,
    interpret: bool = False,
    live_only: bool = False,
):
    """``out[r] = lhs[r] @ rhs[g(r)]`` where row ``r`` belongs to group
    ``g(r)`` under the contiguous-group layout (``group_sizes[e]`` rows
    per expert ``e``, in order; rows past ``sum(group_sizes)`` are
    don't-care and come back unspecified).

    lhs ``[M, K]``, rhs ``[E, K, N]``, group_sizes int ``[E]`` (traced —
    dynamic values, static shapes) → ``[M, N]``. Differentiable in lhs
    and rhs with both impls.

    The Pallas kernel counts the rows past the groups into the last
    group and multiplies them too: nothing, where the groups fill
    nearly all rows. ``live_only`` is for a caller whose groups fill a
    small and varying part of them (a chip's share of the experts,
    ``MoEFFN._shared_out``): only the tiles that hold a group's rows are
    visited, so the rest cost no step's matmul and no expert's weight
    stream, and their output rows are never written (mask them with
    ``where``, not by a product).
    """
    _check_gmm_shapes(lhs, rhs, group_sizes)
    if impl == "ragged":
        return lax.ragged_dot(
            lhs, rhs, group_sizes.astype(jnp.int32), precision=precision
        )
    if impl == "pallas":
        return _gmm_pallas(
            lhs, rhs, group_sizes, block_m, block_n, interpret,
            bool(live_only),
        ).astype(lhs.dtype)
    raise ValueError(f"unknown grouped_matmul impl {impl!r}")


# What a kernel call's buffers may take of a TensorCore's VMEM: 14 MiB of
# the v5e's 16 (the smallest of the chips in perfbench/peaks.py), the rest
# left to Mosaic's own scratch.
VMEM_BUDGET_BYTES = 14 * 2**20


def fit_block_n(k_rows: int, n_cols: int, block_m: int, block_n: int,
                itemsize: int) -> int:
    """The column tile for ``grouped_matmul`` / ``grouped_matmul_fused``
    over ``rhs [E, k_rows, n_cols]``: the widest of ``block_n``, 384, 256,
    128 that is no wider than ``block_n``, divides ``n_cols`` (the kernel
    pads what does not divide, and padding the columns copies every
    expert's matrix) and whose ``[k_rows, tile]`` block of an expert's
    matrix, double-buffered beside the ``[block_m, k_rows]`` row tile and
    the float32 output tile, fits ``VMEM_BUDGET_BYTES`` (K = 6144 at 512
    columns and 256 rows does not). Where none divides, ``block_n`` and
    the padding, as ever; where some divide and none fits, it raises."""

    def vmem(c):
        return 2 * itemsize * k_rows * (c + block_m) + 8 * block_m * c

    divide = [
        c for c in (block_n, 384, 256, 128)
        if c <= block_n and n_cols % c == 0
    ]
    if not divide:
        return block_n
    fits = [c for c in divide if vmem(c) <= VMEM_BUDGET_BYTES]
    if not fits:
        raise ValueError(
            f"grouped matmul over [{k_rows}, {n_cols}] matrices: no column "
            f"tile of {divide} beside a row tile of {block_m} fits "
            f"{VMEM_BUDGET_BYTES} bytes of VMEM; lower block_m"
        )
    return fits[0]
