"""Block-sparse attention over compressed keys (InfLLM v2, as MiniCPM4's
and MiniCPM-SALA's ``minicpm4`` layers run it): grouped-query attention
with no position encoding, a query attending only the blocks it selects
(``ops/block_sparse.py`` has the selection's five steps).

Imported where such a layer is built (``TransformerLM`` with a
``"block_sparse_attention"`` entry in ``layer_types``), so a model
without one pays nothing for it.

For the normed input ``x``: ``q = RMSNorm_d(x W_q)`` (``H`` heads), ``k =
RMSNorm_d(x W_k)``, ``v = x W_v`` (``G`` KV heads, a group of ``H / G``
query heads sharing each), scores at ``1/sqrt(d)``, ``y = (A *
sigmoid(x W_g)) W_o``.

**Served**, the layer keeps its keys and values in pools under the page
table, as any GQA layer does (``key_pages`` / ``value_pages``, ``[num_pages,
page_size, G*d]``), and one more pool beside them,
``compressed_key_pages [num_pages, G*d]``: the compressed key of kernel
``i`` in the row of the slot's page ``i`` (the kernel stride is the
page size; a kernel spans two pages, so row ``i`` is written when page
``i + 1`` fills: by the chunk that fills it, or by the decode step that
writes its last row). A chunk selects for each of its queries (XLA,
``attn_sparse_select``), then attends under ``attn_sparse_chunk``: by the
Pallas walk over the slot's live pages under "kernel", in XLA tile by
tile under "gather". A decode step selects over the slot's compressed
rows (XLA, named ``attn_sparse_select``), then reads only the chosen
blocks' pages (the Pallas walk under "kernel", named ``attn_sparse``;
the gathered rows under "gather").
"""

from __future__ import annotations

from functools import partial
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp


class BlockSparseAttention(nn.Module):
    """One block-sparse mixer (module docstring)."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    sparse: Any  # ops/block_sparse.py::BlockSparse
    dtype: Any = jnp.float32
    norm_eps: float = 1e-6
    page_size: int | None = None
    num_pages: int | None = None
    paged_attention_impl: str = "gather"
    flash_interpret: bool | None = None

    @nn.compact
    def __call__(self, x, *, mode="train", decode_pos=None, page_table=None, last_idx=None):
        from cs744_pytorch_distributed_tutorial_tpu.ops import block_sparse as B

        if mode not in ("train", "paged_prefill", "paged_decode"):
            raise ValueError(
                f"mode={mode!r}: a block-sparse layer selects over compressed "
                "keys that only the paged pools keep; it runs in modes "
                "'train', 'paged_prefill' and 'paged_decode'"
            )
        b, t, d_model = x.shape
        h, g, d, sp = self.num_heads, self.num_kv_heads, self.head_dim, self.sparse
        if h % g:
            raise ValueError(f"num_kv_heads {g} must divide num_heads {h}")
        sp.check(self.page_size if mode != "train" else None)
        dense = partial(nn.Dense, use_bias=False, dtype=self.dtype)
        norm = partial(nn.RMSNorm, epsilon=self.norm_eps, dtype=self.dtype)
        # q and k are made whole before their norm a head: fused into it,
        # a decode step's projection compiles (for the v5e) to a
        # multiply-reduce over the kernel laid out [heads, d, in], a copy
        # of the whole kernel every step
        bar = jax.lax.optimization_barrier
        q = norm(name="q_norm")(bar(dense(h * d, name="q")(x)).reshape(b, t, h, d))
        k = norm(name="k_norm")(bar(dense(g * d, name="k")(x)).reshape(b, t, g, d))
        v = dense(g * d, name="v")(x).reshape(b, t, g, d)
        scale = d ** -0.5

        def gated_out(out):  # [B, T, H, d] -> y
            gate = nn.sigmoid(dense(h * d, name="gate")(x))
            return dense(d_model, name="attn_out")(
                out.reshape(b, t, h * d).astype(self.dtype) * gate
            )

        if mode == "train":
            pos = jnp.broadcast_to(jnp.arange(t), (b, t))
            ckeys = B.compress(k, sp)
            ids, _, _ = B.select(q, ckeys, pos, sp, scale)
            nblk = -(-ckeys.shape[1] * sp.kernel_stride // sp.block_size)
            if not self.is_initializing():
                self.sow("intermediates", "selected", ids)
            out = B.masked_attention(
                q, k, v, pos, B.allowed_blocks(ids, nblk), sp, scale
            )
            return gated_out(out)

        if self.page_size is None or self.num_pages is None:
            raise ValueError(
                f"mode={mode!r} needs page_size and num_pages (the paged "
                "pools' geometry; see serve/engine.py)"
            )
        if decode_pos is None or page_table is None:
            raise ValueError(f"mode={mode!r} needs decode_pos ([B]) and page_table ([B, P])")
        ps = self.page_size
        pool_shape = (self.num_pages, ps, g * d)
        kp = self.variable("pages", "key_pages", jnp.zeros, pool_shape, k.dtype)
        vp = self.variable("pages", "value_pages", jnp.zeros, pool_shape, v.dtype)
        cp = self.variable(
            "pages", "compressed_key_pages", jnp.zeros, (self.num_pages, g * d), k.dtype
        )
        cap = page_table.shape[1]
        parts = sp.kernel_size // ps

        def page_at(idx):  # [B, n] table indices -> pages (trash past it)
            inside = (idx >= 0) & (idx < cap)
            return jnp.where(
                inside, jnp.take_along_axis(page_table, jnp.clip(idx, 0, cap - 1), axis=1), 0
            )

        def write_compressed(kernels, whole):
            """Rows of ``kernels [B, n]`` (``whole``: complete in the pool
            now) as the mean of their pages' keys; the others to the
            trash page."""
            span = kernels[..., None] + jnp.arange(parts)  # [B, n, parts]
            rows = kp.value[page_at(span.reshape(b, -1))]  # [B, n*parts, ps, G*d]
            mean = rows.reshape(b, kernels.shape[1], parts * ps, g * d).astype(
                jnp.float32
            ).mean(2)
            dest = jnp.where(whole, page_at(kernels), 0)
            cp.value = cp.value.at[dest].set(mean.astype(cp.value.dtype))

        positions = jnp.asarray(decode_pos)[:, None] + jnp.arange(t)
        rows_page = page_at(positions // ps)
        kp.value = kp.value.at[rows_page, positions % ps].set(k.reshape(b, t, g * d))
        vp.value = vp.value.at[rows_page, positions % ps].set(v.reshape(b, t, g * d))
        last = positions[:, -1:] if last_idx is None else (
            jnp.asarray(decode_pos)[:, None] + last_idx[:, None]
        )
        # the kernels the new rows complete: those whose last position,
        # kernel * ps + kernel_size - 1, lies among them
        first_k = jnp.asarray(decode_pos)[:, None] // ps - parts + 1
        kernels = first_k + jnp.arange(-(-t // ps) + 1)
        end = kernels * ps + sp.kernel_size - 1
        write_compressed(
            kernels,
            (kernels >= 0) & (end >= jnp.asarray(decode_pos)[:, None]) & (end <= last),
        )
        if mode == "paged_prefill":
            # The chunk's queries score the slot's compressed keys only as
            # far as its last position reaches: one of four static
            # fractions of the table, picked at run time (one program, a
            # branch a width); every width chooses the same blocks.
            per = sp.block_size // ps
            widths = sorted({
                min(cap, max(per, -(-cap * i // 4 // per) * per)) for i in (1, 2, 3, 4)
            })

            def choose(n):
                ckeys = cp.value[page_table[:, :n]].reshape(b, n, g, d)
                return B.select(q, ckeys, positions, sp, scale)[0]

            with jax.named_scope("attn_sparse_select"):
                needed = jnp.max(positions) // ps + 1
                ids = jax.lax.switch(
                    sum((needed > w).astype(jnp.int32) for w in widths[:-1]),
                    [partial(choose, w) for w in widths],
                )
            nblk = -(-cap * ps // sp.block_size)
            with jax.named_scope("attn_sparse_chunk"):
                allowed = B.allowed_blocks(ids[0], nblk)
                if self.paged_attention_impl == "kernel":
                    length = t if last_idx is None else last_idx[0] + 1
                    out = B.block_sparse_chunk_attention(
                        q[0], kp.value, vp.value, page_table[0], positions[0, 0],
                        length, allowed, sp, scale, interpret=self.flash_interpret,
                    )
                else:
                    out = B.chunk_attention(
                        q[0], kp.value, vp.value, page_table[0], positions[0],
                        allowed, sp, scale,
                    )
            return gated_out(out[None])

        if t != 1:
            raise ValueError(f"paged decode steps one token at a time, got t={t}")
        with jax.named_scope("attn_sparse_select"):
            ckeys = cp.value[page_table].reshape(b, cap, g, d)
            ids, count, scored = B.select(q, ckeys, positions, sp, scale)
            first, pages = B.decode_tables(ids[:, 0], count[:, 0], page_table, sp, ps)
        if self.paged_attention_impl == "kernel":
            with jax.named_scope("attn_sparse"):
                out = B.block_sparse_decode(
                    q[:, 0], kp.value, vp.value, first, pages, count[:, 0],
                    decode_pos, sp, scale, interpret=self.flash_interpret,
                )
        else:
            out = B.decode_reference(
                q[:, 0], kp.value, vp.value, first, pages, decode_pos, sp, scale
            )
        if not self.is_initializing():
            # positions attended and live, and kernels scored, summed over
            # the KV groups (the engine's counters; a no-op unless
            # "serve_stats" is asked for)
            self.sow(
                "serve_stats", "sparse_selected_tokens",
                B.attended(ids[:, 0], decode_pos[:, None], sp).sum(-1),
            )
            self.sow("serve_stats", "sparse_live_tokens", g * (decode_pos + 1))
            self.sow("serve_stats", "sparse_scored_kernels", g * scored[:, 0])
        return gated_out(out[:, None])

