"""Paged-attention decode as a Pallas TPU kernel — serve's HBM-bound path.

The serving engine (``serve/``) keeps every slot's KV in a shared pool of
fixed-size pages (``[num_pages, page_size, Hkv*D]`` per layer, heads
folded into the lane dimension: head ``h`` owns lanes ``[h*D, (h+1)*D)``)
indexed by a per-slot page table. The reference decode path
(``parallel/ring_attention.py::paged_decode_attention``) gathers each
slot's pages into the dense ``[B, P*page_size, Hkv, D]`` view and runs
the standard einsum — correct (and bitwise-parity-testable against the
dense cache), but its HBM traffic per step scales with the slot's page
CAPACITY ``P``, not with how many tokens are actually live. Decode is
memory-bound, so that is exactly the wrong scaling.

This kernel reads **only live pages**, straight out of the pool:

- Grid ``(slot, page_block)`` with the page dimension fastest. The page
  table and per-slot depths ride as **scalar-prefetched** operands
  (``PrefetchScalarGridSpec``), so each grid step's BlockSpec index_map
  picks its page from ``page_table[slot, i]`` — data-dependent DMA, no
  gather, no dense intermediate.
- One block is a whole page, all KV heads: ``(1, page_size, Hkv*D)``,
  one unpadded DMA; the kernel loops the heads over lane slices.
  Folded, because the TPU's default layout of a 4-D ``[.., Hkv, D]``
  pool puts ``num_pages`` minor-most, and this kernel and every scatter
  then had the whole pool converted to row-major and back, two
  pool-sized copies a pool a program (``serve/layout.py``;
  ``tests/test_serve_layout.py`` checks it with the compile-only
  topology).
- Dead iterations (``i >= ceil((pos+1)/page_size)``) CLAMP their
  index_map to the slot's last live page. Pallas skips the re-fetch when
  a block index repeats, so capacity-sized grids cost live-sized HBM
  reads — and the reserved trash page 0 is never touched past a slot's
  first block boundary.
- Flash-style online softmax (running max / normalizer / accumulator in
  f32 VMEM scratch, ``ops/flash_attention.py`` discipline); the last
  live page masks its tail rows by position, dead iterations are skipped
  by ``pl.when``, and the output block flushes once at the end of each
  slot's pass.

Three variants share this one entry point:

- float (f32/bf16 pools): numerics follow ``decode_attention`` — f32
  scores/softmax, PV matmul in the pool dtype.
- int8-KV (``key/value_scale_pages`` given): dequant happens INSIDE the
  kernel (each K/V row times its scale before the dots — the same
  algebra as ``ops/quant.py::decode_attention_quant``, which scales the
  scores and probabilities after them) — the scale pools ride the same
  clamped index_map, replacing ``paged_decode_attention_quant``'s
  four-pool gather.
- tensor-parallel: under ``shard_map`` the pools arrive sliced over KV
  heads (a contiguous lane range of the folded last dimension) and
  ``q`` over query heads; the blocks derive from the LOCAL shapes, so
  the kernel partitions over the head axis with no changes.

Online softmax reassociates the reduction, so kernel-vs-reference parity
is tolerance-level (tests/test_paged_attention.py), not bitwise — the
gather path remains the reference implementation and the engine's
bitwise dense-parity story stays on it.

``pages_per_slot`` statically prunes the page-table width and grid — the
compiled ``cost_analysis`` bytes-read then scales with
``ceil(live/page_size) * page_size`` instead of capacity, which is how
CPU CI checks the byte count analytically.

``interpret=True`` runs the same kernel on any backend for tests.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30


def _decode_kernel(
    page_size: int,
    num_blocks: int,
    scale: float,
    quant: bool,
    lens_ref,
    pt_ref,
    q_ref,
    k_ref,
    v_ref,
    *rest,
):
    if quant:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    b = pl.program_id(0)
    i = pl.program_id(1)
    pos = lens_ref[b]
    # Page i holds positions [i*page_size, (i+1)*page_size); the slot's
    # current token sits at ``pos``, so pages 0..pos//page_size are live.
    live = pos // page_size + 1

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, _NEG, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(i < live)
    def _update():
        d = q_ref.shape[-1]
        for h in range(q_ref.shape[1]):  # static: the page's KV heads
            q = q_ref[0, h]  # [group, D]
            k = k_ref[0, :, h * d:(h + 1) * d]  # [page_size, D]
            v = v_ref[0, :, h * d:(h + 1) * d]
            if quant:
                # Per-row dequant ahead of the dots: the [page_size, 1]
                # scale column broadcasts along lanes, where scaling the
                # [group, page_size] scores would need it transposed.
                q = q.astype(jnp.float32)
                k = k.astype(jnp.float32) * ks_ref[0, :, h:h + 1]
                v = v.astype(jnp.float32) * vs_ref[0, :, h:h + 1]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # [group, page_size] f32
            k_pos = i * page_size + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1
            )
            s = jnp.where(k_pos <= pos, s, _NEG)
            m_prev, l_prev = m_ref[h], l_ref[h]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            correction = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            m_ref[h] = m_new
            l_ref[h] = correction * l_prev + p.sum(axis=-1, keepdims=True)
            acc_ref[h] = acc_ref[h] * correction + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

    # Position 0 is always visible (pos >= 0), so l > 0 — no NaN rows
    # even for freshly-admitted or parked slots.
    @pl.when(i == num_blocks - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def paged_attention(
    q: jax.Array,
    key_pages: jax.Array,
    value_pages: jax.Array,
    page_table: jax.Array,
    pos: jax.Array,
    *,
    key_scale_pages: jax.Array | None = None,
    value_scale_pages: jax.Array | None = None,
    interpret: bool | None = None,
    pages_per_slot: int | None = None,
) -> jax.Array:
    """One decode step of ``q`` [B, 1, Hq, D] against paged KV pools,
    reading only each slot's live pages (module docstring).

    ``key_pages``/``value_pages`` are ``[num_pages, page_size, Hkv*D]``
    pools (``D`` is ``q``'s; the int8 variant's scale pools stay
    ``[num_pages, page_size, Hkv]``), ``page_table`` ``[B, P]`` page
    indices in sequence order, and ``pos`` ``[B]`` the slots' current
    depths — the exact signature of ``paged_decode_attention`` (+ scale
    pools for the int8 variant, matching
    ``paged_decode_attention_quant``). ``Hq`` may be a multiple
    of ``Hkv`` (GQA). ``pages_per_slot`` statically narrows the page
    table and grid to the first N pages — the capacity stays a runtime
    fact for the engine's fixed-shape step (live length enters via the
    grid mask, never the shape), while analytical byte-accounting tests
    pin it to make the live-scaling visible to ``cost_analysis``.
    """
    b, t, hq, d = q.shape
    if t != 1:
        raise ValueError(f"paged decode steps one token at a time, got t={t}")
    if key_pages.ndim != 3 or key_pages.shape[-1] % d:
        raise ValueError(
            f"pools are [num_pages, page_size, Hkv*D] with D={d}, "
            f"got {key_pages.shape}"
        )
    _, page_size, folded = key_pages.shape
    hkv = folded // d
    if hq % hkv:
        raise ValueError(f"query heads {hq} not a multiple of kv heads {hkv}")
    quant = key_scale_pages is not None
    if quant != (value_scale_pages is not None):
        raise ValueError("pass both scale pools or neither")
    if interpret is None:
        from cs744_pytorch_distributed_tutorial_tpu.ops._backend import (
            default_interpret,
        )

        interpret = default_interpret()

    group = hq // hkv
    pt = page_table
    if pages_per_slot is not None:
        pt = pt[:, :pages_per_slot]
    num_blocks = pt.shape[1]
    qg = q[:, 0].reshape(b, hkv, group, d)

    def q_map(bi, i, lens, table):
        return bi, 0, 0, 0

    def live_page(bi, i, lens, table):
        # Dead iterations re-point at the last live page: an unchanged
        # block index skips the DMA, so capacity-wide grids read
        # live-sized bytes (and never the trash page past block 0).
        return table[bi, jnp.minimum(i, lens[bi] // page_size)]

    def page_map(bi, i, lens, table):
        return live_page(bi, i, lens, table), 0, 0

    q_spec = pl.BlockSpec((1, hkv, group, d), q_map)
    kv_spec = pl.BlockSpec((1, page_size, folded), page_map)
    in_specs = [q_spec, kv_spec, kv_spec]
    operands = [qg, key_pages, value_pages]
    if quant:
        sc_spec = pl.BlockSpec((1, page_size, hkv), page_map)
        in_specs += [sc_spec, sc_spec]
        operands += [key_scale_pages, value_scale_pages]
    out_dtype = q.dtype if quant else value_pages.dtype

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, num_blocks),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, hkv, group, d), q_map),
        scratch_shapes=[
            pltpu.VMEM((hkv, group, 1), jnp.float32),
            pltpu.VMEM((hkv, group, 1), jnp.float32),
            pltpu.VMEM((hkv, group, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        partial(_decode_kernel, page_size, num_blocks, d**-0.5, quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, d), out_dtype),
        interpret=interpret,
    )(pos.astype(jnp.int32), pt.astype(jnp.int32), *operands)
    return out.reshape(b, 1, hq, d)
