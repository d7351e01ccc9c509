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

This kernel reads **only live pages**, straight out of the pool, and
scores **every head of a block of rows in one matmul pair**.

**The walk** (float pools whose folded rows are whole lane tiles,
``Hkv*D % 128 == 0``: every pool the benchmark serves from):

- Grid ``(slot,)``: one grid step a slot. The page table and the slots'
  depths ride as **scalar-prefetched** operands; the pools stay in HBM
  (``memory_space=pl.ANY``) and the kernel copies pages itself, one
  ``pltpu.make_async_copy`` a page of K and of V, into double-buffered
  VMEM blocks of N pages.
- A ``lax.fori_loop`` runs to ``ceil(live_pages / N)``, a runtime bound
  read from the depths: no iteration exists for a dead page. The next
  block's copies are in flight while this block is computed, and under a
  slot's last block rides the next slot's first, so only the first slot
  of a call starts on an empty buffer (which buffer a slot starts in is
  carried in SMEM; the grid runs in order).
- The last block starts copies for its live pages only. Rows it did not
  copy keep what the buffer held: their scores are masked by position,
  and their V rows are zeroed, since ``p = 0`` times a stale NaN is NaN.
  Page 0 (the engine's trash page) and every page past a slot's live
  length are never read.
- N is ``_BLOCK_TOKENS // page_size`` pages (256 tokens: 0.28 ms a call
  at the serve cell's shape against 0.32 at 128 and 0.28 at 512, PERF.md
  PR 28), never more than a slot holds, halved until both buffers of K
  and V fit ``_VMEM_BUDGET``. The copies of a block are started and
  awaited in runtime loops over its live pages, not unrolled: sixteen
  pages unrolled behind ``pl.when`` traced and lowered three times
  slower, twelve layers a program, and tripled the engine's start-up.

**A window** (``window``, ``first_pos``; a model with sliding-window
layers, whose engine keeps those layers' pages in a group of their own).
The query sees positions ``pos - window + 1 .. pos`` only, and the page
table lists the slot's live window pages from the one that holds
position ``first_pos[slot]`` on (a third scalar-prefetched operand).
The walk is the same walk, started at the table's first page that holds
a key inside the window (the engine may still hold a page or a chunk's
worth behind it) and run to the page that holds ``pos``: the trip count
is ``ceil(window / page_size) + 1`` pages at most whatever the context,
and the position mask has a lower side. Without a window nothing is
added: the kernel traces to the one it always was. The page tables ride
WHOLE as scalar-prefetched operands (SMEM), a full group's ``[32,
2080]`` int32 (266 KB, 278 KB padded; 33k positions a slot) among them:
Mosaic accepts that on the v5e (compiled for it and run on the chip,
PERF.md PR 32), so no grid step copies a table row.

**All heads in one matmul pair** (both fetch paths). ``q`` arrives
block-diagonal, ``[Hq, Hkv*D]`` with row ``h`` non-zero only on its KV
head's lanes (built by the entry point in XLA), so ``scores = q_bd x
K_block^T`` is ``[Hq, tokens]`` for every head at once, the online
softmax runs on that one tile, and ``acc [Hq, Hkv*D] += p x V_block``
holds each head's output in its diagonal ``[group, D]`` block, picked
once at the slot's end. The zeros add nothing; the off-diagonal blocks
are never read. GQA is the same with ``group`` rows a KV head. A loop
over heads would slice 64 lanes out of 128-lane tiles and feed the MXU
one row at a time; this feeds it whole folded rows as they lie in the
pool.

**A page a grid step** (the int8 variant, and float pools whose folded
row is not a lane multiple). Mosaic refuses a DMA of a slice whose lane
extent is not a multiple of 128 — a ``[page_size, Hkv]`` page of the
scale pools, a ``[page_size, 192]`` page of a three-head pool — so these
pools are fetched by BlockSpec's own pipeline instead: grid ``(slot,
page)``, a page a step through a scalar-prefetched index map, running
statistics in VMEM scratch. Dead steps re-point at the slot's last live
page; an unchanged block index skips the copy, so the bytes read follow
live pages there too, and only the grid's steps follow capacity. The
body is the same ``_attend`` on one page's rows.

Three variants share this one entry point:

- float (f32/bf16 pools): numerics follow ``decode_attention`` — f32
  scores/softmax, PV matmul in the pool dtype, f32 accumulation.
- int8-KV (``key/value_scale_pages`` given): dequant happens INSIDE the
  kernel (each K/V row times its scale before the products — the same
  algebra as ``ops/quant.py::decode_attention_quant``, which scales the
  scores and probabilities after them); a 0/1 ``[Hkv, Hkv*D]`` matmul
  widens a page's ``[page_size, Hkv]`` scales over each head's lanes.
  Replaces ``paged_decode_attention_quant``'s four-pool gather.
- tensor-parallel: under ``shard_map`` the pools arrive sliced over KV
  heads (a contiguous lane range of the folded last dimension) and
  ``q`` over query heads; everything derives from the LOCAL shapes, so
  the kernel partitions over the head axis with no changes.

Online softmax reassociates the reduction, so kernel-vs-reference parity
is tolerance-level (tests/test_paged_attention.py), not bitwise — the
gather path remains the reference implementation and the engine's
bitwise dense-parity story stays on it.

**A latent pool** (``value_pages`` None; models/latent.py). Latent
attention caches one row a token, ``[c | k_rope | 0]`` in whole lane
tiles (576 -> 640), shared by every head; absorbed, a head's query lies
over the same lanes, so ``Hq`` rows of ``q [Hq, lanes]`` against a
block's rows is the ``_attend`` body as it is with ``Hkv = 1``. What is
new is ONE pool serving as keys (the whole row) and values (its first
``value_lanes`` lanes): a block is copied once, and the accumulator
``[Hq, value_lanes]`` is written as it lies (no diagonal to pick).

**The chunk walk** (``paged_chunk_attention``; a prefill chunk over a
latent pool). The same walk with a query axis: the chunk's ``[C, H,
lanes]`` absorbed queries flattened to ``C*H`` rows, a grid step a block
of ~``_CHUNK_ROWS`` of them, row ``i`` at position ``offset + t0 + i //
H`` (the mask by position takes a ``[rows, 1]`` column). A block walks
key blocks of ``_CHUNK_BLOCK_TOKENS`` up to the page that holds its last
real position, the next query block's first key block copied under
this one's last; key blocks wholly before the block's first position
skip the mask. The online softmax's state lives in VMEM scratch, so no
``[heads, C, view]`` float32 scores ever reach HBM, and no key past the
chunk's last real position is read.

``pages_per_slot`` statically narrows the page table to its first N
columns (the walk's capacity, the other path's grid).

``interpret=True`` runs the same kernel on any backend for tests.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30
# A block of the walk holds this many tokens (module docstring).
_BLOCK_TOKENS = 256
# Both buffers of K and of V together stay under this much VMEM.
_VMEM_BUDGET = 8 * 1024 * 1024
# The chunk walk (``paged_chunk_attention``): about this many query rows
# (tokens x heads) a grid step and keys a block of its walk (on the v5e
# at 128 heads over 16k keys: 15.4 ms a call where 1,024 x 256 takes
# 18.3, at 80% of the compute bound; PERF.md, the chunk walk), and the
# scoped VMEM its blocks ask for (their scores, probabilities and
# accumulator in float32 are ~30 MB, over Mosaic's default 16).
_CHUNK_ROWS = 2048
_CHUNK_BLOCK_TOKENS = 512
_CHUNK_VMEM_LIMIT = 64 * 1024 * 1024


def _pages_per_block(
    capacity: int, page_size: int, row_bytes: int,
    block_tokens: int = _BLOCK_TOKENS,
) -> int:
    """Pages a block of the walk holds: ``block_tokens`` tokens, never
    more than a slot can hold, halved until the double-buffered K and V
    blocks fit the budget."""
    n = max(1, min(capacity, block_tokens // page_size))
    while n > 1 and 4 * n * page_size * row_bytes > _VMEM_BUDGET:
        n //= 2
    return n


def _attend(q, k, v, first, pos, scale, carry, oldest=None):
    """Fold one block of rows into the online softmax, every head at
    once. ``q`` is block-diagonal ``[Hq, Hkv*D]`` (row ``h`` non-zero on
    its KV head's lanes), ``k``/``v`` ``[tokens, Hkv*D]`` whole folded
    rows starting at position ``first``. The accumulator is ``[Hq,
    Hkv*D]``: row ``h``'s own head sits in its diagonal block, the other
    lanes hold products with other heads' values and are never read.
    ``pos`` is the query's position, or a ``[rows, 1]`` column of them
    (the chunk walk); None where every row sees the whole block.
    ``oldest`` (a window layer) is the first position the query sees."""
    m_prev, l_prev, acc = carry
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # [Hq, tokens] f32
    if pos is not None:
        k_pos = first + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(k_pos <= pos, s, _NEG)
    if oldest is not None:
        s = jnp.where(k_pos >= oldest, s, _NEG)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    correction = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_new = correction * l_prev + p.sum(axis=-1, keepdims=True)
    acc = acc * correction + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return m_new, l_new, acc


def _init_carry(hq: int, folded: int):
    return (
        jnp.full((hq, 1), _NEG, jnp.float32),
        jnp.zeros((hq, 1), jnp.float32),
        jnp.zeros((hq, folded), jnp.float32),
    )


def _write_heads(o_ref, wide_ref):
    """Once a slot: pick each head's diagonal ``[group, D]`` block out of
    the normalised ``[Hq, Hkv*D]`` accumulator."""
    hkv, group, d = o_ref.shape[1:]
    for h in range(hkv):
        o_ref[0, h] = wide_ref[
            h * group:(h + 1) * group, h * d:(h + 1) * d
        ].astype(o_ref.dtype)


def _walk_kernel(
    page_size: int,
    block_pages: int,
    capacity: int,
    scale: float,
    window: int | None,
    value_lanes: int | None,
    lens_ref,
    pt_ref,
    *refs,
):
    # A window layer's table starts at position ``first_ref[slot]`` (a
    # third prefetched scalar a slot) and the walk at the table's first
    # page that holds a key inside the window; without a window the
    # table starts at position 0 and so does the walk, and this traces
    # to the kernel it always was.
    first_ref = None
    if window is not None:
        first_ref, *refs = refs
    if value_lanes is None:
        q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, wide_ref, sem, first_buf = refs
        pools = ((k_hbm, kbuf), (v_hbm, vbuf))
    else:
        # A latent pool: ONE pool serves as keys (the whole row) and as
        # values (its first ``value_lanes`` lanes), copied once a block.
        q_ref, k_hbm, o_ref, kbuf, sem, first_buf = refs
        vbuf, pools = kbuf, ((k_hbm, kbuf),)
    hq, folded = q_ref.shape[1:]
    tokens = block_pages * page_size
    b = pl.program_id(0)
    pos = lens_ref[b]

    def oldest_page(slot):
        # index, in the slot's table, of the page that holds the oldest
        # key the slot's query sees
        oldest = jnp.maximum(lens_ref[slot] - window + 1, first_ref[slot])
        return (oldest - first_ref[slot]) // page_size

    def live_pages(slot):
        # Page i holds positions [i*page_size, (i+1)*page_size); the
        # slot's current token sits at ``pos``, so pages 0..pos//page_size
        # are live.
        if window is None:
            return jnp.minimum(lens_ref[slot] // page_size + 1, capacity)
        newest = (lens_ref[slot] - first_ref[slot]) // page_size
        return jnp.clip(newest - oldest_page(slot) + 1, 1, capacity)

    def block_start(slot, blk):
        # (window only) table index of block ``blk``'s first page, worked
        # out once a block and not once a page
        return None if window is None else (
            oldest_page(slot) + blk * block_pages
        )

    def copies(slot, blk, buf, j, at=None):
        if window is None:
            page = pt_ref[slot, blk * block_pages + j]
        else:
            page = pt_ref[slot, at + j]
        return [
            pltpu.make_async_copy(src.at[page], dst.at[buf, j], sem.at[buf])
            for src, dst in pools
        ]

    def live_in_block(slot, blk):
        return jnp.clip(live_pages(slot) - blk * block_pages, 0, block_pages)

    def start(slot, blk, buf):
        n = live_in_block(slot, blk)
        at = block_start(slot, blk)

        @pl.loop(0, n)
        def _copy(j):
            for c in copies(slot, blk, buf, j, at):
                c.start()

        # A page the walk does not copy keeps what the buffer held: its
        # scores are masked by position, but p = 0 times a stale NaN
        # would still poison the PV product, so its V rows go 0.
        @pl.loop(n, block_pages)
        def _zero(j):
            vbuf[buf, j] = jnp.zeros(vbuf.shape[2:], vbuf.dtype)

    def wait(slot, blk, buf):
        at = block_start(slot, blk)

        @pl.loop(0, live_in_block(slot, blk))
        def _wait(j):
            for c in copies(slot, blk, buf, j, at):
                c.wait()

    num_blocks = (live_pages(b) + block_pages - 1) // block_pages

    @pl.when(b == 0)
    def _first_slot():
        first_buf[0] = 0
        start(0, 0, 0)

    # Buffers alternate through the whole grid, not a slot: the buffer
    # this slot's first block was copied into is carried in SMEM.
    base = first_buf[0]

    def body(blk, carry):
        buf = (base + blk) % 2
        more = blk + 1 < num_blocks

        @pl.when(more)
        def _next_block():
            start(b, blk + 1, 1 - buf)

        # Under the slot's last block rides the next slot's first, so
        # no slot but the first starts on an empty buffer.
        @pl.when(jnp.logical_and(~more, b + 1 < pl.num_programs(0)))
        def _next_slot():
            start(b + 1, 0, 1 - buf)

        wait(b, blk, buf)
        k = kbuf[buf].reshape(tokens, folded)
        if value_lanes is not None:
            return _attend(
                q_ref[0], k, k[:, :value_lanes], blk * tokens, pos, scale,
                carry,
            )
        v = vbuf[buf].reshape(tokens, folded)
        if window is None:
            return _attend(q_ref[0], k, v, blk * tokens, pos, scale, carry)
        return _attend(
            q_ref[0], k, v,
            first_ref[b] + oldest_page(b) * page_size + blk * tokens,
            pos, scale, carry, oldest=pos - window + 1,
        )

    # Position 0 is always visible (pos >= 0), so l > 0 — no NaN rows
    # even for freshly-admitted or parked slots.
    _, l, acc = jax.lax.fori_loop(
        0, num_blocks, body,
        _init_carry(hq, folded if value_lanes is None else value_lanes),
    )
    first_buf[0] = (base + num_blocks) % 2
    if value_lanes is not None:  # every head's p . c, as it lies
        o_ref[0] = (acc / l).astype(o_ref.dtype)
        return
    wide_ref[...] = acc / l
    _write_heads(o_ref, wide_ref)


def _page_step_kernel(
    page_size: int,
    capacity: int,
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
    hkv, _, d = o_ref.shape[1:]
    hq, folded = q_ref.shape[1:]
    b = pl.program_id(0)
    i = pl.program_id(1)
    pos = lens_ref[b]

    @pl.when(i == 0)
    def _init():
        m_ref[...], l_ref[...], acc_ref[...] = _init_carry(hq, folded)

    @pl.when(i <= pos // page_size)
    def _update():
        q, k, v = q_ref[0], k_ref[0], v_ref[0]
        if quant:
            # Per-row dequant ahead of the products. A 0/1 [Hkv, Hkv*D]
            # matmul widens the [page_size, Hkv] scales over each head's
            # lanes (exact: one non-zero term a lane).
            lane = jax.lax.broadcasted_iota(jnp.int32, (hkv, folded), 1)
            lo = jax.lax.broadcasted_iota(jnp.int32, (hkv, folded), 0) * d
            widen = jnp.logical_and(lane >= lo, lane < lo + d)

            def dequant(rows, scales):
                return rows.astype(jnp.float32) * jax.lax.dot_general(
                    scales, widen.astype(jnp.float32),
                    (((1,), (0,)), ((), ())),
                    precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32,
                )

            q = q.astype(jnp.float32)
            k, v = dequant(k, ks_ref[0]), dequant(v, vs_ref[0])
        m_ref[...], l_ref[...], acc_ref[...] = _attend(
            q, k, v, i * page_size, pos, scale,
            (m_ref[...], l_ref[...], acc_ref[...]),
        )

    @pl.when(i == capacity - 1)
    def _finalize():
        acc_ref[...] = acc_ref[...] / l_ref[...]
        _write_heads(o_ref, acc_ref)


def paged_attention(
    q: jax.Array,
    key_pages: jax.Array,
    value_pages: jax.Array | None,
    page_table: jax.Array,
    pos: jax.Array,
    *,
    key_scale_pages: jax.Array | None = None,
    value_scale_pages: jax.Array | None = None,
    interpret: bool | None = None,
    pages_per_slot: int | None = None,
    first_pos: jax.Array | None = None,
    window: int | None = None,
    value_lanes: int | None = None,
    scale: float | None = None,
) -> jax.Array:
    """One decode step of ``q`` [B, 1, Hq, D] against paged KV pools,
    reading only each slot's live pages (module docstring).

    **A latent pool** (``value_pages`` None, ``value_lanes`` and
    ``scale`` given; models/latent.py): ``key_pages`` is ``[num_pages,
    page_size, lanes]``, one row a token shared by every head, and ``q``
    ``[B, 1, Hq, lanes]`` holds each head's absorbed query over the same
    lanes. Scores are ``q . row * scale`` over the whole row, values the
    row's first ``value_lanes`` lanes: the same walk with ONE pool,
    copied once a block; returns ``[B, 1, Hq, value_lanes]``.

    ``key_pages``/``value_pages`` are ``[num_pages, page_size, Hkv*D]``
    pools (``D`` is ``q``'s; the int8 variant's scale pools stay
    ``[num_pages, page_size, Hkv]``), ``page_table`` ``[B, P]`` page
    indices in sequence order, and ``pos`` ``[B]`` the slots' current
    depths — the exact signature of ``paged_decode_attention`` (+ scale
    pools for the int8 variant, matching
    ``paged_decode_attention_quant``). ``Hq`` may be a multiple
    of ``Hkv`` (GQA). ``pages_per_slot`` statically narrows the page
    table to its first N columns; the live length is a runtime fact (the
    walk's trip count, the position mask), never a shape, so the engine's
    fixed-shape step compiles once.

    ``window`` (with ``first_pos`` ``[B]``): the query sees the keys at
    positions ``pos - window + 1 .. pos`` only, and ``page_table`` lists
    the pages from the one that holds position ``first_pos`` (a multiple
    of ``page_size``) on: the engine's window page group. The walk
    starts at the first page that holds a key inside the window and
    masks by position inside the first and the last. Float pools with
    whole-lane rows only. Without a window the lowered kernel is the one
    it always was.
    """
    b, t, hq, d = q.shape
    if t != 1:
        raise ValueError(f"paged decode steps one token at a time, got t={t}")
    if interpret is None:
        from cs744_pytorch_distributed_tutorial_tpu.ops._backend import (
            default_interpret,
        )

        interpret = default_interpret()
    if value_pages is None:
        return _latent_walk(
            q, key_pages, page_table, pos, value_lanes, scale, interpret,
            pages_per_slot,
        )
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
    if (window is None) != (first_pos is None):
        raise ValueError("pass both window and first_pos or neither")
    if window is not None and (quant or folded % 128):
        raise ValueError(
            "the window walks float pools whose folded row Hkv*D is a "
            f"multiple of 128 lanes (got {folded}, int8={quant}): the "
            "page-a-step path has no window"
        )

    group = hq // hkv
    pt = page_table
    if pages_per_slot is not None:
        pt = pt[:, :pages_per_slot]
    capacity = pt.shape[1]
    # Block-diagonal q: row h keeps its D values on its KV head's lanes
    # and zeros elsewhere, so one [Hq, Hkv*D] x [Hkv*D, tokens] product
    # scores every head against whole folded rows.
    own = (jnp.arange(hq) // group)[:, None] == (jnp.arange(folded) // d)[None]
    q_bd = jnp.where(own, jnp.tile(q[:, 0], (1, 1, hkv)), 0)
    out_dtype = q.dtype if quant else value_pages.dtype
    # q and the output move a slot a grid step on either path (the index
    # maps also receive the second grid index, where there is one, and
    # the two prefetched scalars).
    q_spec = pl.BlockSpec((1, hq, folded), lambda bi, *_: (bi, 0, 0))
    out_spec = pl.BlockSpec((1, hkv, group, d), lambda bi, *_: (bi, 0, 0, 0))
    operands = [q_bd, key_pages, value_pages]

    if not quant and folded % 128 == 0:
        block_pages = _pages_per_block(
            capacity, page_size, folded * key_pages.dtype.itemsize
        )
        buffers = pltpu.VMEM(
            (2, block_pages, page_size, folded), key_pages.dtype
        )
        in_hbm = pl.BlockSpec(memory_space=pl.ANY)
        grid = (b,)
        in_specs = [q_spec, in_hbm, in_hbm]
        scratch_shapes = [
            buffers,
            buffers,
            pltpu.VMEM((hq, folded), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
        ]
        kernel = partial(
            _walk_kernel, page_size, block_pages, capacity, d**-0.5, window,
            None,
        )
    else:
        def live_page(bi, i, lens, table):
            # Dead steps re-point at the last live page: an unchanged
            # block index skips the DMA, so the capacity-wide grid reads
            # live-sized bytes (and never the trash page past block 0).
            return table[bi, jnp.minimum(i, lens[bi] // page_size)], 0, 0

        kv_spec = pl.BlockSpec((1, page_size, folded), live_page)
        grid = (b, capacity)
        in_specs = [q_spec, kv_spec, kv_spec]
        if quant:
            in_specs += [pl.BlockSpec((1, page_size, hkv), live_page)] * 2
            operands += [key_scale_pages, value_scale_pages]
        scratch_shapes = [
            pltpu.VMEM((hq, 1), jnp.float32),
            pltpu.VMEM((hq, 1), jnp.float32),
            pltpu.VMEM((hq, folded), jnp.float32),
        ]
        kernel = partial(
            _page_step_kernel, page_size, capacity, d**-0.5, quant
        )
    prefetched = [pos.astype(jnp.int32), pt.astype(jnp.int32)]
    if window is not None:
        prefetched.append(first_pos.astype(jnp.int32))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetched),
            grid=grid,
            in_specs=in_specs,
            out_specs=out_spec,
            scratch_shapes=scratch_shapes,
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, d), out_dtype),
        interpret=interpret,
    )(*prefetched, *operands)
    return out.reshape(b, 1, hq, d)


def _latent_walk(
    q, latent_pages, page_table, pos, value_lanes, scale, interpret,
    pages_per_slot,
):
    """``paged_attention`` over one latent pool (its docstring): the
    walk of ``_walk_kernel`` with every head on the pool's one row."""
    b, _, hq, lanes = q.shape
    if (
        value_lanes is None or scale is None or latent_pages.ndim != 3
        or latent_pages.shape[-1] != lanes or lanes % 128
        or value_lanes > lanes
    ):
        raise ValueError(
            "a latent pool is [num_pages, page_size, lanes] with lanes whole "
            "128-lane tiles (the walk DMAs lane-aligned slices), q [B, 1, "
            "Hq, lanes], and needs value_lanes <= lanes and scale; got pool "
            f"{latent_pages.shape}, "
            f"q {q.shape}, value_lanes {value_lanes}, scale {scale}"
        )
    page_size = latent_pages.shape[1]
    pt = page_table if pages_per_slot is None else page_table[:, :pages_per_slot]
    capacity = pt.shape[1]
    block_pages = _pages_per_block(
        capacity, page_size, lanes * latent_pages.dtype.itemsize
    )
    out = pl.pallas_call(
        partial(
            _walk_kernel, page_size, block_pages, capacity, float(scale),
            None, value_lanes,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((1, hq, lanes), lambda bi, *_: (bi, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(
                (1, hq, value_lanes), lambda bi, *_: (bi, 0, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM(
                    (2, block_pages, page_size, lanes), latent_pages.dtype
                ),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(
            (b, hq, value_lanes), latent_pages.dtype
        ),
        interpret=interpret,
    )(pos.astype(jnp.int32), pt.astype(jnp.int32), q[:, 0], latent_pages)
    return out[:, None]


def _chunk_kernel(
    page_size: int,
    block_pages: int,
    capacity: int,
    scale: float,
    value_lanes: int,
    heads: int,
    q_tokens: int,
    off_ref,
    len_ref,
    pt_ref,
    q_ref,
    pool_hbm,
    o_ref,
    buf,
    sem,
    m_ref,
    l_ref,
    acc_ref,
    first_buf,
):
    """A block of ``q_tokens`` query tokens (x ``heads`` rows each) of a
    slot's chunk walks the slot's latent pages from position 0 to the
    page that holds its last real position (``paged_chunk_attention``)."""
    rows, lanes = q_ref.shape[1:]
    tokens = block_pages * page_size
    slots = len_ref.shape[0]
    b, qi = pl.program_id(0), pl.program_id(1)

    def is_real(slot, blk):  # a query block holds a row of the chunk
        return blk * q_tokens < len_ref[slot]

    def live_pages(slot, blk):
        last = off_ref[slot] + jnp.minimum(
            (blk + 1) * q_tokens, len_ref[slot]
        ) - 1
        return jnp.minimum(last // page_size + 1, capacity)

    def live_in_block(slot, blk, kb):
        return jnp.clip(
            live_pages(slot, blk) - kb * block_pages, 0, block_pages
        )

    def copy(slot, kb, bi, j):
        page = pt_ref[slot, kb * block_pages + j]
        return pltpu.make_async_copy(
            pool_hbm.at[page], buf.at[bi, j], sem.at[bi]
        )

    def start(slot, blk, kb, bi):
        n = live_in_block(slot, blk, kb)

        @pl.loop(0, n)
        def _copy(j):
            copy(slot, kb, bi, j).start()

        # Rows of pages not copied are masked by position; p = 0 times
        # a stale NaN would still be NaN, so they go 0.
        @pl.loop(n, block_pages)
        def _zero(j):
            buf[bi, j] = jnp.zeros(buf.shape[2:], buf.dtype)

    def wait(slot, blk, kb, bi):
        @pl.loop(0, live_in_block(slot, blk, kb))
        def _wait(j):
            copy(slot, kb, bi, j).wait()

    @pl.when(jnp.logical_and(b == 0, qi == 0))
    def _first_block():
        first_buf[0] = 0

        @pl.when(is_real(0, 0))
        def _start():
            start(0, 0, 0, 0)

    # The next query block that walks: this slot's next, else the next
    # slot's first; its first key block rides under this one's last.
    same = jnp.logical_and(qi + 1 < pl.num_programs(1), is_real(b, qi + 1))
    nb = jnp.where(same, b, jnp.minimum(b + 1, slots - 1))
    nqi = jnp.where(same, qi + 1, 0)
    next_walks = jnp.logical_or(
        same, jnp.logical_and(b + 1 < slots, is_real(nb, 0))
    )
    real = is_real(b, qi)

    @pl.when(real)
    def _walk():
        p0 = off_ref[b] + qi * q_tokens
        last = off_ref[b] + len_ref[b] - 1
        row_pos = p0 + jax.lax.div(
            jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0), heads
        )
        # a padding row sees what the last real row sees: no key past
        # it, so nothing stale enters its softmax before it goes 0
        pos = jnp.minimum(row_pos, last)
        num_blocks = (live_pages(b, qi) + block_pages - 1) // block_pages
        # Key blocks wholly at or before the block's first position are
        # seen by every row: no mask there, only in the last one or two.
        n_full = jnp.minimum((p0 + 1) // tokens, num_blocks)
        m_ref[...], l_ref[...], acc_ref[...] = _init_carry(rows, value_lanes)
        base = first_buf[0]

        def body(masked):
            def step(kb, carry):
                bi = (base + kb) % 2
                more = kb + 1 < num_blocks

                @pl.when(more)
                def _next_block():
                    start(b, qi, kb + 1, 1 - bi)

                @pl.when(jnp.logical_and(~more, next_walks))
                def _next_query_block():
                    start(nb, nqi, 0, 1 - bi)

                wait(b, qi, kb, bi)
                k = buf[bi].reshape(tokens, lanes)
                m_ref[...], l_ref[...], acc_ref[...] = _attend(
                    q_ref[0], k, k[:, :value_lanes], kb * tokens,
                    pos if masked else None, scale,
                    (m_ref[...], l_ref[...], acc_ref[...]),
                )
                return carry

            return step

        jax.lax.fori_loop(0, n_full, body(False), 0)
        jax.lax.fori_loop(n_full, num_blocks, body(True), 0)
        first_buf[0] = (base + num_blocks) % 2
        o_ref[0] = jnp.where(
            row_pos <= last, acc_ref[...] / l_ref[...], 0.0
        ).astype(o_ref.dtype)

    @pl.when(~real)
    def _padding():
        o_ref[0] = jnp.zeros(o_ref.shape[1:], o_ref.dtype)


def paged_chunk_attention(
    q: jax.Array,
    latent_pages: jax.Array,
    page_table: jax.Array,
    offset: jax.Array,
    length: jax.Array,
    *,
    value_lanes: int,
    scale: float,
    interpret: bool | None = None,
) -> jax.Array:
    """A prefill chunk's causal attention over one latent pool, read
    straight out of the pool (models/latent.py, ``paged_prefill``).

    ``q [B, C, H, lanes]`` holds each head's absorbed query of the
    chunk's tokens at positions ``offset[b] + t``; rows ``t >=
    length[b]`` (``length >= 1``) are padding and come back 0.
    ``latent_pages [num_pages, page_size, lanes]`` is the pool, already
    holding the chunk's own rows, ``page_table [B, P]`` the slot's pages
    in position order. Scores are ``q . row * scale`` over the whole row,
    values the row's first ``value_lanes`` lanes, as ``paged_attention``
    over a latent pool; returns ``[B, C, H, value_lanes]``.

    The decode walk with a query axis: ``q`` flattened to ``[C*H,
    lanes]`` rows, a grid step a block of about ``_CHUNK_ROWS`` of them
    (``C*H / rows`` grid steps a slot), row ``i`` of a block at position
    ``offset + t0 + i // H``. A block walks key blocks of
    ``_CHUNK_BLOCK_TOKENS`` up to the page that holds its last real
    position, copied a page at a time by the page table into
    double-buffered VMEM while the block before is computed, the next
    query block's first under this one's last; the mask by position
    bites only in the last one or two. Pages past that page, the trash
    page 0 among them, are never read. Online softmax as the decode
    walk: scores, max and sum in float32, ``p`` in the pool's dtype for
    ``p . V``, float32 accumulation."""
    b, c, h, lanes = q.shape
    if (
        latent_pages.ndim != 3 or latent_pages.shape[-1] != lanes
        or lanes % 128 or value_lanes > lanes
    ):
        raise ValueError(
            "a latent pool is [num_pages, page_size, lanes] with lanes whole "
            "128-lane tiles, q [B, C, H, lanes], value_lanes <= lanes; got "
            f"pool {latent_pages.shape}, q {q.shape}, value_lanes "
            f"{value_lanes}"
        )
    if interpret is None:
        from cs744_pytorch_distributed_tutorial_tpu.ops._backend import (
            default_interpret,
        )

        interpret = default_interpret()
    page_size = latent_pages.shape[1]
    capacity = page_table.shape[1]
    q_tokens = min(c, max(1, _CHUNK_ROWS // h))
    n_q = -(-c // q_tokens)
    if n_q * q_tokens > c:
        q = jnp.pad(q, ((0, 0), (0, n_q * q_tokens - c), (0, 0), (0, 0)))
    rows = q_tokens * h
    block_pages = _pages_per_block(
        capacity, page_size, lanes * latent_pages.dtype.itemsize,
        _CHUNK_BLOCK_TOKENS,
    )
    out = pl.pallas_call(
        partial(
            _chunk_kernel, page_size, block_pages, capacity, float(scale),
            value_lanes, h, q_tokens,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, n_q),
            in_specs=[
                pl.BlockSpec((1, rows, lanes), lambda bi, qi, *_: (bi, qi, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(
                (1, rows, value_lanes), lambda bi, qi, *_: (bi, qi, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM(
                    (2, block_pages, page_size, lanes), latent_pages.dtype
                ),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, value_lanes), jnp.float32),
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(
            (b, n_q * rows, value_lanes), latent_pages.dtype
        ),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_CHUNK_VMEM_LIMIT
        ),
        interpret=interpret,
    )(
        offset.astype(jnp.int32), length.astype(jnp.int32),
        page_table.astype(jnp.int32), q.reshape(b, n_q * rows, lanes),
        latent_pages,
    )
    return out.reshape(b, n_q * q_tokens, h, value_lanes)[:, :c]
