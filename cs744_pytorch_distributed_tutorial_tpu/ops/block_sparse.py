"""Block-sparse attention over compressed keys (InfLLM v2, as MiniCPM4's
sparse layers run it; models/block_sparse.py has the layer).

A query at position ``t`` attends only to the blocks of ``block_size``
positions it selects, one selection a KV group (the group's query heads
share it):

1. **Compressed keys.** ``Kc[g, i] = mean(K[g, i*S : i*S + K_len])``,
   kernel ``K_len`` (``kernel_size``), stride ``S`` (``kernel_stride``).
   Only kernels wholly at or before ``t`` count: ``i*S + K_len - 1 <= t``.
2. **Relevance.** ``r[g, i] = sum over the group's heads h of
   softmax_i(q_h . Kc[g, i] / sqrt(d))``.
3. **Block scores.** A block scores the largest ``r`` of the kernels
   that intersect it.
4. **Selection.** The first ``init_blocks`` blocks, the blocks that meet
   the last ``window`` positions, and the best others, ``topk`` blocks in
   all; ties go to the lower index. Below ``dense_len`` a query takes
   every block at or before it (dense causal attention).
5. **Attention.** Causal softmax over the positions ``s <= t`` of the
   chosen blocks.

The serving cache keeps one compressed row a page (the stride is the
page size): kernel ``i`` spans pages ``i`` and ``i + 1`` of the slot's
table and its row lives at page ``i``'s index of the compressed pool, so
it rides on the page table and is final once page ``i + 1`` is full.

The selection (steps 2-4) is XLA, the same function for a decode step
and for a prefill chunk. Decode attends by ``block_sparse_decode``, a
Pallas walk over the chosen blocks' pages: one grid step a slot and a KV
group, the group's ``block_size / page_size`` pages a block copied by
their indices (scalar-prefetched) into double-buffered VMEM, the group's
lanes only, with the next grid step's first pages in flight under each
step's last; an online softmax in float32, ``p`` in the pool's type for
``p . V``. A chunk attends by ``block_sparse_chunk_attention`` under the
engine's "kernel": a Pallas walk over the slot's live pages, a grid step
a tile of queries, key blocks copied a page at a time into
double-buffered VMEM up to the page of the tile's last real position,
each query's chosen blocks and the causal mask applied in VMEM, the
online softmax in VMEM scratch. Under "gather" it attends in XLA
(``chunk_attention``, the kernel's reference): the slot's keys a tile at
a time, each query masked to its selection, an online softmax across
tiles, so no ``[C, H, view]`` scores are ever held, though each tile's
float32 scores go through HBM.

``interpret=True`` runs the kernel on any backend for tests.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30
# the id that stands for no block, past the chosen: its first position
# (times a block's positions, still an int32) lies past every context
NO_BLOCK = 1 << 20
# Blocks a step of the decode walk copies (4 blocks of 64 positions: 16
# pages of 16, 256 keys).
_WALK_BLOCKS = 4
# Positions a tile of the chunk's XLA attention scores (its float32
# scores at C = 512 and 32 heads are 134 MB).
_CHUNK_TILE = 2048
# The chunk walk (``block_sparse_chunk_attention``): (query, head) rows a
# KV group of a query tile, selection blocks a key block of its walk (a
# bit each of an int32 word; 1,024 positions at 64 a block), and the
# scoped VMEM it asks for (a group's float32 scores over a key block are
# 16 MB). On the v5e at MiniCPM-SALA's shapes, 20k keys back: 1.55 ms a
# layer, 57% of the bf16 peak, where 2,048 rows x 512 keys take 2.22 ms
# and 4,096 x 2,048 or 8,192 x 1,024 take 1.65-1.85 (PERF.md).
_CHUNK_ROWS = 4096
_CHUNK_KEY_BLOCKS = 16
_CHUNK_VMEM_LIMIT = 64 * 1024 * 1024


class BlockSparse(NamedTuple):
    """The selection's parameters, as MiniCPM4's ``sparse_config`` names
    them."""

    kernel_size: int = 32
    kernel_stride: int = 16
    block_size: int = 64
    window: int = 2048
    topk: int = 64
    init_blocks: int = 1
    dense_len: int = 8192

    @property
    def width(self) -> int:
        """Blocks a query may attend: ``topk``, or every block below
        ``dense_len``, whichever is more."""
        return max(self.topk, -(-self.dense_len // self.block_size))

    def check(self, page_size: int | None = None) -> None:
        """Raise, with the reason, for parameters the cache cannot hold
        or the selection cannot honour."""
        bs, ks, st = self.block_size, self.kernel_size, self.kernel_stride
        if ks % st or bs % st:
            raise ValueError(
                f"kernel_size {ks} and block_size {bs} must be multiples of "
                f"kernel_stride {st}"
            )
        if page_size is not None and (st != page_size or bs % page_size):
            raise ValueError(
                f"the cache keeps one compressed row a page: kernel_stride "
                f"{st} must be the page size {page_size}, and block_size {bs} "
                "a multiple of it"
            )
        if self.init_blocks + -(-self.window // bs) + 1 > self.topk:
            raise ValueError(
                f"topk {self.topk} blocks cannot hold the {self.init_blocks} "
                f"initial blocks and a window of {self.window} positions"
            )


def compress(keys: jax.Array, sp: BlockSparse) -> jax.Array:
    """``keys [..., T, G, d]`` -> the compressed keys of every kernel that
    starts inside ``T`` (kernels running past ``T`` average what is
    there; a query never counts them) ``[..., ceil(T / S), G, d]``,
    float32."""
    t = keys.shape[-3]
    n = -(-t // sp.kernel_stride)
    pad = (n - 1) * sp.kernel_stride + sp.kernel_size - t
    k = jnp.pad(
        keys.astype(jnp.float32),
        [(0, 0)] * (keys.ndim - 3) + [(0, pad), (0, 0), (0, 0)],
    )
    parts = sp.kernel_size // sp.kernel_stride
    runs = k[..., : (n + parts - 1) * sp.kernel_stride, :, :].reshape(
        *k.shape[:-3], n + parts - 1, sp.kernel_stride, *k.shape[-2:]
    ).sum(-3)
    return sum(runs[..., o: o + n, :, :] for o in range(parts)) / sp.kernel_size


def select(q, ckeys, t, sp: BlockSparse, scale: float):
    """The chosen blocks of queries ``q [B, C, H, d]`` at positions ``t
    [B, C]`` over compressed keys ``ckeys [B, P, G, d]`` (kernel ``i`` in
    row ``i``; rows of kernels not yet whole are never counted): (block
    ids ``[B, C, G, width]`` ascending; how many are chosen ``[B, C, G]``; kernels
    scored ``[B, C]``, 0 below ``dense_len``). Module docstring, steps
    2-4. Past the chosen the ids are ``NO_BLOCK``, so a narrower view of
    the same keys gives the same ids."""
    b, c, h, d = q.shape
    p, g = ckeys.shape[1], ckeys.shape[2]
    st, bs = sp.kernel_stride, sp.block_size
    per = bs // st
    nblk = -(-p * st // bs)
    s = jnp.einsum(
        "bcgjd,bpgd->bcgjp", q.reshape(b, c, g, h // g, d).astype(jnp.float32),
        ckeys.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST,
    ) * scale
    whole = (
        jnp.arange(p)[None, None, :] * st + sp.kernel_size - 1 <= t[:, :, None]
    )  # [B, C, P]
    s = jnp.where(whole[:, :, None, None], s, -jnp.inf)
    any_whole = whole.any(-1)[:, :, None, None, None]
    prob = jnp.where(any_whole, jax.nn.softmax(jnp.where(any_whole, s, 0.0), -1), 0.0)
    r = prob.sum(3)  # [B, C, G, P]
    # a block's score: the largest r of the kernels that meet it, i from
    # block * per - (parts - 1) to block * per + per - 1
    parts = sp.kernel_size // st
    span = per + parts - 1
    rp = jnp.pad(r, ((0, 0),) * 3 + ((parts - 1, nblk * per + parts - 1 - p - (parts - 1)),))
    score = rp[..., 0: nblk * per: per]
    for o in range(1, span):
        score = jnp.maximum(score, rp[..., o: o + nblk * per: per])
    blk = jnp.arange(nblk)[None, None, :]
    tt = t[:, :, None]
    valid = blk * bs <= tt
    forced = (
        (blk < sp.init_blocks) | (blk >= (tt - sp.window + 1) // bs)
        | (tt < sp.dense_len)
    )
    score = jnp.where(
        valid[:, :, None], jnp.where(forced[:, :, None], jnp.inf, score), -jnp.inf
    )
    width = min(sp.width, nblk)
    vals, idx = jax.lax.top_k(score, width)  # ties: the lower index first
    keep = (jnp.arange(width) < jnp.where(tt < sp.dense_len, width, sp.topk)[..., None]) & (
        vals > -jnp.inf
    )
    ids = jnp.sort(jnp.where(keep, idx, NO_BLOCK), axis=-1)
    if width < sp.width:
        ids = jnp.pad(ids, ((0, 0),) * 3 + ((0, sp.width - width),), constant_values=NO_BLOCK)
    scored = jnp.where(t >= sp.dense_len, whole.sum(-1), 0)
    return ids, keep.sum(-1), scored


def attended(ids, t, sp: BlockSparse):
    """Positions ``s <= t`` in the chosen blocks ``ids [..., width]`` of a
    query at ``t [...]``."""
    start = ids * sp.block_size
    return jnp.clip(t[..., None] - start + 1, 0, sp.block_size).sum(-1)


def allowed_blocks(ids, nblk: int):
    """``ids [..., width]`` -> a ``[..., nblk]`` mask of the chosen."""
    hit = jnp.zeros(ids.shape[:-1] + (nblk + 1,), jnp.bool_)
    lead = tuple(i[..., None] for i in jnp.indices(ids.shape[:-1]))
    return hit.at[lead + (jnp.minimum(ids, nblk),)].set(True)[..., :nblk]


def masked_attention(q, k, v, q_pos, allowed, sp: BlockSparse, scale: float):
    """Attention of ``q [B, C, H, d]`` at ``q_pos [B, C]`` over rows
    ``k``, ``v [B, S, G, d]`` (row ``s`` is position ``s``), masked to
    the chosen blocks ``allowed [B, C, G, nblk]`` and by position: the
    whole forward and the reference of the kernels. Float32 scores."""
    b, c, h, d = q.shape
    s_len, g = k.shape[1], k.shape[2]
    qg = q.reshape(b, c, g, h // g, d)
    scores = jnp.einsum(
        "bcgjd,bsgd->bcgjs", qg, k, preferred_element_type=jnp.float32
    ) * scale
    blk = jnp.arange(s_len) // sp.block_size
    ok = jnp.take(allowed, blk, axis=-1, mode="clip") & (
        jnp.arange(s_len)[None, None, None, :] <= q_pos[:, :, None, None]
    )
    p = jax.nn.softmax(jnp.where(ok[:, :, :, None], scores, _NEG), -1)
    out = jnp.einsum(
        "bcgjs,bsgd->bcgjd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(b, c, h, d)


def chunk_attention(
    q, key_pages, value_pages, page_row, q_pos, allowed, sp: BlockSparse,
    scale: float, tile: int = _CHUNK_TILE,
):
    """A prefill chunk's attention: ``q [C, H, d]`` at ``q_pos [C]`` over
    the slot's pages (``page_row [P]`` indices into pools ``[num_pages,
    page_size, G*d]``), masked to ``allowed [C, G, nblk]`` and by
    position, a tile of ``tile`` positions at a time up to the chunk's
    last, with an online softmax across tiles (module docstring)."""
    c, h, d = q.shape
    ps = key_pages.shape[1]
    g = key_pages.shape[-1] // d
    tile = max(sp.block_size, min(tile, page_row.shape[0] * ps))
    tile -= tile % sp.block_size
    n_tiles = -(-page_row.shape[0] * ps // tile)
    pages = jnp.pad(page_row, (0, n_tiles * tile // ps - page_row.shape[0]))
    per = tile // sp.block_size
    allowed = jnp.pad(
        allowed, ((0, 0), (0, 0), (0, n_tiles * per - allowed.shape[-1]))
    )
    qg = q.reshape(c, g, h // g, d)

    def body(i, carry):
        m, l, acc = carry
        rows = jax.lax.dynamic_slice_in_dim(pages, i * (tile // ps), tile // ps)
        k = key_pages[rows].reshape(tile, g, d)
        v = value_pages[rows].reshape(tile, g, d)
        s = jnp.einsum(
            "cgjd,sgd->cgjs", qg, k, preferred_element_type=jnp.float32
        ) * scale
        ok = jnp.repeat(
            jax.lax.dynamic_slice_in_dim(allowed, i * per, per, axis=2),
            sp.block_size, axis=2,
        ) & ((i * tile + jnp.arange(tile))[None, None, :] <= q_pos[:, None, None])
        s = jnp.where(ok[:, :, None, :], s, _NEG)
        m_new = jnp.maximum(m, s.max(-1, keepdims=True))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        acc = acc * corr + jnp.einsum(
            "cgjs,sgd->cgjd", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32,
        )
        return m_new, corr * l + p.sum(-1, keepdims=True), acc

    init = (
        jnp.full((c, g, h // g, 1), _NEG, jnp.float32),
        jnp.zeros((c, g, h // g, 1), jnp.float32),
        jnp.zeros((c, g, h // g, d), jnp.float32),
    )
    last = jnp.minimum(jnp.max(q_pos) // tile + 1, n_tiles)
    _, l, acc = jax.lax.fori_loop(0, last, body, init)
    return (acc / l).reshape(c, h, d)


def decode_tables(ids, count, page_table, sp: BlockSparse, page_size: int):
    """What the decode walk reads, from the chosen blocks ``ids [B, G,
    width]`` (ascending) and ``count [B, G]``: each block's first
    position ``[B, G, width]`` (past every context beyond the chosen)
    and its pages' indices ``[B, G, width * pages a block]`` (0, the
    trash page, beyond the chosen)."""
    ppb = sp.block_size // page_size
    chosen = jnp.arange(ids.shape[-1])[None, None, :] < count[..., None]
    first = ids * sp.block_size
    cols = ids[..., None] * ppb + jnp.arange(ppb)  # [B, G, W, ppb]
    cols = jnp.minimum(cols, page_table.shape[1] - 1).reshape(ids.shape[0], -1)
    pages = jnp.take_along_axis(page_table, cols, axis=1).reshape(*ids.shape, ppb)
    pages = jnp.where(chosen[..., None], pages, 0)
    return first.astype(jnp.int32), pages.reshape(*ids.shape[:2], -1).astype(jnp.int32)


def decode_reference(q, key_pages, value_pages, first, pages, pos, sp, scale):
    """``block_sparse_decode`` in XLA (the engine's "gather" path): the
    chosen blocks' rows gathered, attended by position."""
    b, h, d = q.shape
    g = key_pages.shape[-1] // d
    w = first.shape[-1]
    rows = key_pages[pages].reshape(b, g, w * sp.block_size, g, d)
    vrows = value_pages[pages].reshape(b, g, w * sp.block_size, g, d)
    own = jnp.arange(g)
    k = rows[:, own, :, own].transpose(1, 0, 2, 3)  # [B, G, S, d]
    v = vrows[:, own, :, own].transpose(1, 0, 2, 3)
    k_pos = (first[..., None] + jnp.arange(sp.block_size)).reshape(b, g, -1)
    qg = q.reshape(b, g, h // g, d)
    s = jnp.einsum("bgjd,bgsd->bgjs", qg, k, preferred_element_type=jnp.float32) * scale
    s = jnp.where((k_pos <= pos[:, None, None])[:, :, None, :], s, _NEG)
    p = jax.nn.softmax(s, -1)
    out = jnp.einsum(
        "bgjs,bgsd->bgjd", p.astype(v.dtype), v, preferred_element_type=jnp.float32
    )
    return out.reshape(b, h, d)


def _walk_kernel(
    page_size: int, ppb: int, block_size: int, groups: int, scale: float,
    pos_ref, count_ref, first_ref, pages_ref,
    q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sem, parity,
):
    b, g = pl.program_id(0), pl.program_id(1)
    nb, ng = pl.num_programs(0), pl.num_programs(1)
    d = q_ref.shape[-1]
    step_pages = _WALK_BLOCKS * ppb
    tokens = step_pages * page_size

    def steps(bi, gi):
        return (count_ref[bi, gi] + _WALK_BLOCKS - 1) // _WALK_BLOCKS

    def live(bi, gi, st):
        return jnp.clip(count_ref[bi, gi] * ppb - st * step_pages, 0, step_pages)

    def copies(page, gs, buf, j):
        return [
            pltpu.make_async_copy(
                src.at[page, :, pl.ds(gs * d, d)], dst.at[buf, j], sem.at[buf]
            )
            for src, dst in ((k_hbm, kbuf), (v_hbm, vbuf))
        ]

    def start(bi, gi, st, buf):
        n = live(bi, gi, st)

        @pl.loop(0, n)
        def _copy(j):
            page = pages_ref[bi, gi, st * step_pages + j]
            for gs in range(groups):  # the group's lanes: a static slice

                @pl.when(gi == gs)
                def _start():
                    for cp in copies(page, gs, buf, j):
                        cp.start()

        # pages not copied keep what the buffer held: their scores are
        # masked, and p = 0 times a stale NaN is NaN, so their V rows go 0
        @pl.loop(n, step_pages)
        def _zero(j):
            vbuf[buf, j] = jnp.zeros(vbuf.shape[2:], vbuf.dtype)

    def wait(bi, gi, st, buf):
        @pl.loop(0, live(bi, gi, st))
        def _wait(j):
            for cp in copies(0, 0, buf, j):
                cp.wait()

    @pl.when((b == 0) & (g == 0))
    def _first():
        parity[0] = 0
        start(0, 0, 0, 0)

    base = parity[0]
    n_steps = steps(b, g)
    last_group = g + 1 == ng
    nbi = jnp.minimum(jnp.where(last_group, b + 1, b), nb - 1)
    ngi = jnp.where(last_group, 0, g + 1)
    has_next = jnp.logical_or(~last_group, b + 1 < nb)
    pos = pos_ref[b]
    row = jax.lax.broadcasted_iota(jnp.int32, (1, tokens), 1)
    q = q_ref[0, 0]
    hg = q.shape[0]

    def body(st, carry):
        m_prev, l_prev, acc = carry
        buf = (base + st) % 2
        more = st + 1 < n_steps

        @pl.when(more)
        def _next_step():
            start(b, g, st + 1, 1 - buf)

        @pl.when(jnp.logical_and(~more, has_next))
        def _next_walk():
            start(nbi, ngi, 0, 1 - buf)

        wait(b, g, st, buf)
        k = kbuf[buf].reshape(tokens, d)
        v = vbuf[buf].reshape(tokens, d)
        # each row's position: its block's first plus its offset; rows of
        # blocks past the chosen (not copied) lie past every query
        k_pos = jnp.full((1, tokens), NO_BLOCK * block_size, jnp.int32)
        for u in range(_WALK_BLOCKS):
            blk = st * _WALK_BLOCKS + u
            first = first_ref[b, g, jnp.minimum(blk, first_ref.shape[2] - 1)]
            k_pos = jnp.where(
                (row // block_size == u) & (blk < count_ref[b, g]),
                first + row % block_size, k_pos,
            )
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        s = jnp.where(k_pos <= pos, s, _NEG)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        acc = acc * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, corr * l_prev + p.sum(axis=-1, keepdims=True), acc

    _, l, acc = jax.lax.fori_loop(
        0, n_steps, body,
        (
            jnp.full((hg, 1), _NEG, jnp.float32),
            jnp.zeros((hg, 1), jnp.float32),
            jnp.zeros((hg, d), jnp.float32),
        ),
    )
    parity[0] = (base + n_steps) % 2
    o_ref[0, 0] = (acc / l).astype(o_ref.dtype)


def block_sparse_decode(
    q: jax.Array,
    key_pages: jax.Array,
    value_pages: jax.Array,
    first: jax.Array,
    pages: jax.Array,
    count: jax.Array,
    pos: jax.Array,
    sp: BlockSparse,
    scale: float,
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """One decode step of ``q [B, H, d]`` at ``pos [B]`` over the chosen
    blocks only (``decode_tables``: their first positions, their pages,
    ``count [B, G]`` chosen a group), straight out of the pools
    ``[num_pages, page_size, G*d]`` (module docstring) -> ``[B, H, d]``
    in the pools' type. The block that holds ``pos`` is always chosen, so
    every softmax has a key."""
    b, h, d = q.shape
    _, ps, folded = key_pages.shape
    g = folded // d
    if interpret is None:
        from cs744_pytorch_distributed_tutorial_tpu.ops._backend import (
            default_interpret,
        )

        interpret = default_interpret()
    if folded % d or sp.block_size % ps or (d % 128 and not interpret):
        raise ValueError(
            f"pools [num_pages, page_size, G*d] with d a multiple of 128 "
            f"lanes (a group's lanes are copied alone) and block_size a "
            f"multiple of the page; got pool {key_pages.shape}, d {d}, "
            f"block {sp.block_size}"
        )
    ppb = sp.block_size // ps
    hg = h // g
    buffers = pltpu.VMEM((2, _WALK_BLOCKS * ppb, ps, d), key_pages.dtype)
    group = pl.BlockSpec((1, 1, hg, d), lambda bi, gi, *_: (bi, gi, 0, 0))
    out = pl.pallas_call(
        partial(_walk_kernel, ps, ppb, sp.block_size, g, float(scale)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b, g),
            in_specs=[
                group,
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=group,
            scratch_shapes=[
                buffers, buffers, pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, g, hg, d), key_pages.dtype),
        interpret=interpret,
    )(
        pos.astype(jnp.int32), count.astype(jnp.int32), first, pages,
        q.reshape(b, g, hg, d).astype(key_pages.dtype), key_pages, value_pages,
    )
    return out.reshape(b, h, d)


def _chunk_kernel(
    page_size: int, key_pages_a_block: int, key_blocks: int, capacity: int,
    block_size: int, scale: float,
    meta_ref, pt_ref, q_ref, bits_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sem,
    m_ref, l_ref, acc_ref, parity,
):
    """One query tile of a chunk, every KV group, walks the key blocks up
    to its last real position (``block_sparse_chunk_attention``)."""
    groups, hg, tq, d = q_ref.shape
    ppk = key_pages_a_block
    keys = ppk * page_size
    folded = kbuf.shape[-1]
    qi, nq = pl.program_id(0), pl.num_programs(0)
    offset, length = meta_ref[0], meta_ref[1]

    def last(ti):  # tile ti's last real position
        return offset + jnp.minimum((ti + 1) * tq, length) - 1

    def steps(ti):  # key blocks tile ti walks (none for a padding tile)
        return jnp.where(
            ti * tq < length, jnp.minimum(last(ti) // keys + 1, key_blocks), 0
        )

    def live(ti, kb):  # pages of key block kb at or before tile ti's last
        return jnp.clip(
            jnp.minimum(last(ti) // page_size + 1, capacity) - kb * ppk, 0, ppk
        )

    def copies(kb, buf, j):
        page = pt_ref[kb * ppk + j]
        return [
            pltpu.make_async_copy(src.at[page], dst.at[buf, j], sem.at[buf])
            for src, dst in ((k_hbm, kbuf), (v_hbm, vbuf))
        ]

    def start(ti, kb, buf):
        @pl.loop(0, live(ti, kb))
        def _copy(j):
            for cp in copies(kb, buf, j):
                cp.start()

        # pages not copied keep what the buffer held: their scores are
        # masked, and p = 0 times a stale NaN is NaN, so their V rows go 0
        @pl.loop(live(ti, kb), ppk)
        def _zero(j):
            vbuf[buf, j] = jnp.zeros(vbuf.shape[2:], vbuf.dtype)

    def wait(ti, kb, buf):
        @pl.loop(0, live(ti, kb))
        def _wait(j):
            for cp in copies(0, buf, j):
                cp.wait()

    @pl.when(qi == 0)
    def _first_tile():
        parity[0] = 0

        @pl.when(steps(0) > 0)
        def _start():
            start(0, 0, 0)

    base = parity[0]
    n = steps(qi)
    nxt = jnp.minimum(qi + 1, nq - 1)
    next_walks = jnp.logical_and(qi + 1 < nq, steps(nxt) > 0)
    tok_pos = offset + qi * tq + jax.lax.broadcasted_iota(jnp.int32, (tq, 1), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (tq, keys), 1)
    lane_tile = jax.lax.broadcasted_iota(jnp.int32, (tq, 128), 1)
    m_ref[...] = jnp.full(m_ref.shape, _NEG, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def body(kb, carry):
        buf = (base + kb) % 2
        more = kb + 1 < n

        @pl.when(more)
        def _next_block():
            start(qi, kb + 1, 1 - buf)

        @pl.when(jnp.logical_and(~more, next_walks))
        def _next_tile():
            start(nxt, 0, 1 - buf)

        wait(qi, kb, buf)
        k = kbuf[buf].reshape(keys, folded)
        v = vbuf[buf].reshape(keys, folded)
        before = kb * keys + lane <= tok_pos  # [tq, keys], causal
        for g in range(groups):
            # each query's chosen blocks of kb, a bit each: its lane of the
            # 128-lane tile that holds kb
            word = jnp.sum(
                jnp.where(lane_tile == kb % 128, bits_ref[g, kb // 128], 0),
                axis=1, keepdims=True,
            )
            chosen = jax.lax.shift_right_logical(
                jnp.broadcast_to(word, (tq, keys)), lane // block_size
            ) & 1
            ok = jnp.logical_and(chosen == 1, before)
            s = jax.lax.dot_general(
                q_ref[g].reshape(hg * tq, d), k[:, g * d:(g + 1) * d],
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
            ) * scale
            s = jnp.where(ok[None], s.reshape(hg, tq, keys), _NEG).reshape(
                hg * tq, keys
            )
            m_prev = m_ref[g]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_ref[g] = corr * l_ref[g] + p.sum(axis=-1, keepdims=True)
            acc_ref[g] = acc_ref[g] * corr + jax.lax.dot_general(
                p.astype(v.dtype), v[:, g * d:(g + 1) * d],
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
            )
            m_ref[g] = m_new
        return carry

    jax.lax.fori_loop(0, n, body, 0)
    parity[0] = (base + n) % 2
    real = (tok_pos < offset + length)[None]
    for g in range(groups):
        o_ref[g] = jnp.where(
            real, (acc_ref[g] / l_ref[g]).reshape(hg, tq, d), 0.0
        ).astype(o_ref.dtype)


def block_sparse_chunk_attention(
    q: jax.Array,
    key_pages: jax.Array,
    value_pages: jax.Array,
    page_row: jax.Array,
    offset: jax.Array,
    length: jax.Array,
    allowed: jax.Array,
    sp: BlockSparse,
    scale: float,
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """A prefill chunk's attention read straight out of the pools
    (models/block_sparse.py, ``paged_prefill``, kernel named
    ``attn_sparse_chunk``): ``chunk_attention``'s result without its
    float32 scores over the view in HBM.

    ``q [C, H, d]`` holds the chunk's queries at positions ``offset +
    t``; rows ``t >= length`` (``length >= 1``) are padding and come
    back 0. ``key_pages``/``value_pages [num_pages, page_size, G*d]``
    already hold the chunk's own rows, ``page_row [P]`` is the slot's
    pages in position order, ``allowed [C, G, nblk]`` each query's
    chosen blocks (``allowed_blocks``), which must hold a key at or
    before every real query (``select`` always chooses the query's own
    block). Returns ``[C, H, d]`` in the pools' type.

    A grid step is a tile of ``_CHUNK_ROWS / (H / G)`` queries (256 at
    MiniCPM-SALA's 16 heads a group: 4,096 (query, head) rows a group),
    every KV group. It walks key blocks of ``_CHUNK_KEY_BLOCKS``
    selection blocks (1,024 positions at 64 a block) in order, whole
    pages of both groups copied a page at a time by the scalar-prefetched
    page row into double-buffered VMEM while the block before is
    computed, the next tile's first under this tile's last; in each, the
    query's chosen blocks (a bit a block, from a word a query and key
    block) and the causal mask apply before an online softmax in VMEM
    scratch: scores, max and sum in float32, ``p`` in the pools' type
    for ``p . V``, float32 accumulation (``chunk_attention``'s
    numerics). The walk reads only up to the page that holds the tile's
    last real position, so pages past it, the trash page 0 among them,
    are never read."""
    c, h, d = q.shape
    _, ps, folded = key_pages.shape
    g = folded // d
    hg = h // g
    cap = page_row.shape[0]
    bs = sp.block_size
    keys = _CHUNK_KEY_BLOCKS * bs
    if interpret is None:
        from cs744_pytorch_distributed_tutorial_tpu.ops._backend import (
            default_interpret,
        )

        interpret = default_interpret()
    if folded % d or h % g or bs % ps or (d % 128 and not interpret):
        raise ValueError(
            f"pools [num_pages, page_size, G*d] with d a multiple of 128 "
            f"lanes, G dividing the {h} heads and block_size a multiple of "
            f"the page; got pool {key_pages.shape}, d {d}, block {bs}"
        )
    ppk = keys // ps
    nkb = -(-cap // ppk)
    tq = min(c, max(8, _CHUNK_ROWS // hg))
    n_q = -(-c // tq)
    rows = n_q * tq
    lanes = -(-nkb // 128) * 128
    # each (query, group, key block): a bit a chosen block
    a = jnp.pad(
        allowed.astype(jnp.int32),
        ((0, rows - c), (0, 0), (0, nkb * _CHUNK_KEY_BLOCKS - allowed.shape[-1])),
    ).reshape(rows, g, nkb, _CHUNK_KEY_BLOCKS)
    bits = jnp.sum(a << jnp.arange(_CHUNK_KEY_BLOCKS), -1)
    bits = jnp.pad(bits, ((0, 0), (0, 0), (0, lanes - nkb))).reshape(
        rows, g, lanes // 128, 128
    ).transpose(1, 2, 0, 3)  # [G, lane tiles, rows, 128]
    qg = jnp.pad(q.reshape(c, g, hg, d), ((0, rows - c), (0, 0), (0, 0), (0, 0)))
    tile = pl.BlockSpec((g, hg, tq, d), lambda qi, *_: (0, 0, qi, 0))
    buffers = pltpu.VMEM((2, ppk, ps, folded), key_pages.dtype)
    out = pl.pallas_call(
        partial(_chunk_kernel, ps, ppk, nkb, cap, bs, float(scale)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_q,),
            in_specs=[
                tile,
                pl.BlockSpec((g, lanes // 128, tq, 128), lambda qi, *_: (0, 0, qi, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=tile,
            scratch_shapes=[
                buffers, buffers, pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((g, hg * tq, 1), jnp.float32),
                pltpu.VMEM((g, hg * tq, 1), jnp.float32),
                pltpu.VMEM((g, hg * tq, d), jnp.float32),
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((g, hg, rows, d), key_pages.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_CHUNK_VMEM_LIMIT),
        interpret=interpret,
        name="attn_sparse_chunk",
    )(
        jnp.stack([offset, length]).astype(jnp.int32), page_row.astype(jnp.int32),
        qg.transpose(1, 2, 0, 3).astype(key_pages.dtype), bits, key_pages, value_pages,
    )
    return out.transpose(2, 0, 1, 3)[:c].reshape(c, h, d)
