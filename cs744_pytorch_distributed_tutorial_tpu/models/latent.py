"""Latent attention (MLA) and the two layers built on it: the
two-attention shortcut-MoE layer and the plain pre-norm block.

Imported where such a layer is built (``TransformerLM`` with ``latent``
set), so a model without one pays nothing for it.

**Latent attention.** Queries and keys/values go through low-rank
paths: ``c_q = RMSNorm(x W_qa)``, ``q = c_q W_qb`` as ``H`` heads of
``nope + rope`` dimensions; ``x W_kva`` splits into the latent ``c_kv``
(``kv_lora_rank`` wide) and ONE rope key a token (``rope`` wide, shared
by every head); ``c = RMSNorm(c_kv)``, and ``c W_kvb`` gives each head
its no-rope key and its value. ``scale_q`` / ``scale_kv`` multiply ``q``
and ``c`` (LongCat's ``mla_scale_q_lora`` / ``mla_scale_kv_lora``).
Scores are ``(q_nope . k_nope + q_rope . k_rope) / sqrt(nope + rope)``.

What is cached is the latent, not the heads' keys and values: one pool
``latent_pages [num_pages, page_size, lanes]`` a sublayer, a row ``[c |
k_rope after RoPE | 0]`` padded to whole 128-lane tiles (576 -> 640:
the page walk DMAs lane-aligned slices only, and a pool whose last
dimension is no lane multiple is laid out with ``num_pages`` minor-most
and copied by every program, ``serve/layout.py``). Decode runs the
ABSORBED form: ``q_abs_h = q_nope_h W_uk_h^T`` (``W_kvb`` split a head
into ``W_uk_h``, ``W_uv_h``), scores ``q_abs_h . c + q_rope_h . k_rope``
against the stored row, ``o_h = p c``, then ``o_h W_uv_h``: a step reads
each cached row once and never builds a key or a value.

**RoPE** pairs dimensions ``(2j, 2j+1)`` (the published
``apply_rotary_pos_emb_interleave``). As there, the rotated vector is
kept DE-INTERLEAVED (even dimensions first, then odd: ``apply_rope``'s
``(j, j + half)`` pairing on the permuted vector); queries and keys are
permuted alike, so every dot product is the interleaved one's, and the
de-interleaved ``k_rope`` is what the pool stores.

**YaRN on the rope dimensions** (DeepSeek-V2's form, ``LatentDims.
rope_scaling`` / ``score_factor``): the frequencies are
``rope_inv_freq``'s, cos and sin carry ``attention_factor`` (``m(s,
mscale) / m(s, mscale_all_dim)``, ``m(s, a) = 0.1 a ln s + 1``), and the
WHOLE score, no-rope and rope dimensions together, carries
``score_factor`` (``m(s, mscale_all_dim)^2``): a factor on cos and sin
alone would scale the rope dimensions' part of a score only.

**The shortcut-MoE layer** (``ShortcutMoEBlock``): two (attention, dense
MLP) pairs and one MoE whose input leaves the stream inside the first
pair and whose output joins it after the second::

    x1 = x  + A_0(N_a0 x);   m = N_p0 x1;   s = MoE(m)
    x2 = x1 + MLP_0(m)
    x3 = x2 + A_1(N_a1 x2)
    out = x3 + MLP_1(N_p1 x3) + s

**The plain block** (``LatentBlock``): ``x + A(N_a x)``, then ``x +
FFN(N_f x)``, the FFN a dense SwiGLU MLP or the MoE; one pool a layer.
"""

from __future__ import annotations

from functools import partial
from typing import Any, NamedTuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from cs744_pytorch_distributed_tutorial_tpu.models.transformer import apply_rope

class LatentDims(NamedTuple):
    """The sizes of a latent-attention layer, as a published config
    names them."""

    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    scale_q: float = 1.0  # on q (both parts), after the query norm
    scale_kv: float = 1.0  # on the normalised latent
    # YaRN on the rope dimensions (models/transformer.py::RopeScaling;
    # its attention_factor on cos and sin), and a factor on the whole
    # score (module docstring). None and 1: the plain rotation and scale.
    rope_scaling: Any = None
    score_factor: float = 1.0

    @property
    def row(self) -> int:
        """What a token caches: the latent and its one rope key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def lanes(self) -> int:
        """The pool's last dimension: ``row`` in whole 128-lane tiles."""
        return -(-self.row // 128) * 128


def rope_interleaved(x, positions, base: float, scaling=None):
    """RoPE on pairs ``(2j, 2j+1)`` of ``x [B, T, H, D]``; returns the
    rotated vector de-interleaved (module docstring). ``scaling``:
    ``apply_rope``'s."""
    return apply_rope(
        jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1),
        positions, base, scaling,
    )


def attend_by_position(q, k, v, q_pos, scale: float, head_block: int = 8):
    """Causal attention by row position, a block of heads at a time so
    that the float32 scores of every head are never held at once (64
    heads x 512 queries x 4,608 keys are 604 MB): ``q [B, C, H, Dk]``
    at positions ``q_pos [B, C]`` over the rows of ``k [B, S, Hk, Dk]``
    / ``v [B, S, Hk, Dv]`` (row ``s`` is position ``s``; ``Hk`` is ``H``
    or 1, one key and value shared by every head) -> ``[B, C, H, Dv]``."""
    h, shared = q.shape[2], k.shape[2] == 1
    rows = "bsd" if shared else "bshd"
    seen = jnp.arange(k.shape[1])[None, None, :] <= q_pos[:, :, None]
    outs = []
    for h0 in range(0, h, head_block):
        heads = slice(h0, min(h0 + head_block, h))
        of = (lambda a: a[:, :, 0]) if shared else (lambda a: a[:, :, heads])
        s = jnp.einsum(
            f"bchd,{rows}->bhcs", q[:, :, heads], of(k),
            preferred_element_type=jnp.float32,
        )
        p = jax.nn.softmax(
            jnp.where(seen[:, None], s * scale, -1e30), axis=-1
        ).astype(v.dtype)
        outs.append(jnp.einsum(
            f"bhcs,{rows.replace('d', 'v')}->bchv", p, of(v),
            preferred_element_type=jnp.float32,
        ))
    return jnp.concatenate(outs, axis=2)


class _Kernel(nn.Module):
    """A bare ``kernel`` under a module's name: ``kv_b`` is read whole
    (train, the built chunk view) and split a head (absorbed)."""

    shape: tuple

    @nn.compact
    def __call__(self):
        return self.param("kernel", nn.initializers.lecun_normal(), self.shape)


class LatentAttention(nn.Module):
    """Latent attention in modes ``train`` (keys and values built a
    head), ``paged_prefill`` (a chunk written into the slot's latent
    pages, then attended, absorbed, over the slot's view) and
    ``paged_decode`` (absorbed, over the latent pool), each by
    ``paged_attention_impl`` "gather" or "kernel". What is not built
    raises in ``TransformerLM``. The chunk runs absorbed as decode does:
    building every view row's keys and values a head spends ~1.9x
    fewer FLOPs at any head count but writes them all out, and measured
    even over the chat cell's mix of prompts (PERF.md, section 6). Under
    "kernel" the chunk is ONE Pallas call a layer
    (``ops/paged_attention.py::paged_chunk_attention``, named
    ``attn_latent_chunk``) that walks the slot's live pages straight out
    of the pool with an online softmax: no float32 scores in HBM, no key
    past the chunk's last real position. Under "gather" (the reference)
    it attends over the gathered view, as wide as its last position
    needs (four static widths, one program)."""

    num_heads: int
    dims: LatentDims
    dtype: Any = jnp.float32
    rope_base: float = 10000.0
    norm_eps: float = 1e-6
    page_size: int | None = None
    num_pages: int | None = None
    paged_attention_impl: str = "gather"
    flash_interpret: bool | None = None

    @nn.compact
    def __call__(
        self, x, *, mode="train", decode_pos=None, page_table=None,
        last_idx=None,
    ):
        """``last_idx`` ([B], ``paged_prefill``): the index in the chunk
        of each row's last real token; the tokens after it are padding.
        None: every token is real."""
        if mode not in ("train", "paged_prefill", "paged_decode"):
            raise ValueError(
                f"mode={mode!r} keeps a dense cache of keys and values a "
                "head, which latent attention exists to avoid: it runs in "
                "modes 'train', 'paged_prefill' and 'paged_decode' (serve "
                "it by chunks, ServeConfig.prefill_chunk)"
            )
        b, t, d_model = x.shape
        dm, h = self.dims, self.num_heads
        r, dn, dr, dv = (
            dm.kv_lora_rank, dm.qk_nope_head_dim, dm.qk_rope_head_dim,
            dm.v_head_dim,
        )
        dense = partial(nn.Dense, use_bias=False, dtype=self.dtype)
        norm = partial(nn.RMSNorm, epsilon=self.norm_eps, dtype=self.dtype)
        c_q = norm(name="q_a_norm")(dense(dm.q_lora_rank, name="q_a")(x))
        q = dense(h * (dn + dr), name="q_b")(c_q).reshape(b, t, h, dn + dr)
        q = q * jnp.asarray(dm.scale_q, self.dtype)
        ckv = dense(r + dr, name="kv_a")(x)
        c = norm(name="kv_a_norm")(ckv[..., :r]) * jnp.asarray(
            dm.scale_kv, self.dtype
        )
        w_kvb = _Kernel((r, h * (dn + dv)), name="kv_b")().astype(self.dtype)
        if mode == "train":
            positions = jnp.arange(t)
        else:
            if decode_pos is None or page_table is None:
                raise ValueError(
                    f"mode={mode!r} needs decode_pos ([B]) and page_table "
                    "([B, P])"
                )
            positions = jnp.asarray(decode_pos)[:, None] + jnp.arange(t)
        q_nope = q[..., :dn]
        q_rope = rope_interleaved(
            q[..., dn:], positions, self.rope_base, dm.rope_scaling
        )
        k_rope = rope_interleaved(
            ckv[..., None, r:], positions, self.rope_base, dm.rope_scaling
        )[:, :, 0]
        scale = dm.score_factor * float(dn + dr) ** -0.5
        out_proj = dense(d_model, name="attn_out")

        if mode == "train":
            kv = jnp.dot(c, w_kvb).reshape(b, t, h, dn + dv)
            k = jnp.concatenate(
                [kv[..., :dn], jnp.broadcast_to(
                    k_rope[:, :, None, :], (b, t, h, dr)
                )], axis=-1,
            )
            out = attend_by_position(
                jnp.concatenate([q_nope, q_rope], axis=-1), k, kv[..., dn:],
                jnp.broadcast_to(positions, (b, t)), scale,
            )
            return out_proj(out.reshape(b, t, h * dv).astype(self.dtype))

        if self.page_size is None or self.num_pages is None:
            raise ValueError(
                f"mode={mode!r} needs page_size and num_pages (the paged "
                "pool's geometry; see serve/engine.py)"
            )
        pool = self.variable(
            "pages", "latent_pages", jnp.zeros,
            (self.num_pages, self.page_size, dm.lanes), self.dtype,
        )
        row = jnp.pad(
            jnp.concatenate([c, k_rope], axis=-1).astype(self.dtype),
            ((0, 0), (0, 0), (0, dm.lanes - dm.row)),
        )
        w_uk = w_kvb.reshape(r, h, dn + dv)[..., :dn]  # [r, H, nope]
        w_uv = w_kvb.reshape(r, h, dn + dv)[..., dn:]  # [r, H, v]

        def absorbed_query():
            """``[q_abs | q_rope | 0]`` a head: scores against a cached
            row are one dot product over its lanes."""
            q_abs = jnp.einsum(
                "bthn,rhn->bthr", q_nope, w_uk,
                preferred_element_type=jnp.float32,
            ).astype(self.dtype)
            return jnp.pad(
                jnp.concatenate([q_abs, q_rope], axis=-1),
                ((0, 0), (0, 0), (0, 0), (0, dm.lanes - dm.row)),
            )

        def values_of(o_latent):  # p.c [B, T, H, r] -> heads' values
            return jnp.einsum(
                "bthr,rhv->bthv", o_latent.astype(self.dtype), w_uv,
                preferred_element_type=jnp.float32,
            )

        from cs744_pytorch_distributed_tutorial_tpu.parallel.ring_attention import (
            gather_pages,
        )

        if mode == "paged_prefill":
            # The chunk's rows go into the slot's pages (positions past
            # the table, or on entries the engine left 0, land on the
            # trash page), then the chunk attends over the slot's view,
            # as wide as its last position needs (four static widths,
            # one program; every width gives the same numbers).
            page_idx = positions // self.page_size
            rows_page = jnp.where(
                page_idx < page_table.shape[1],
                jnp.take_along_axis(
                    page_table,
                    jnp.minimum(page_idx, page_table.shape[1] - 1), axis=1,
                ),
                0,
            )
            pool.value = pool.value.at[
                rows_page, positions % self.page_size
            ].set(row)
            if self.paged_attention_impl == "kernel":
                from cs744_pytorch_distributed_tutorial_tpu.ops.paged_attention import (
                    paged_chunk_attention,
                )

                # the slot's live pages walked out of the pool by the
                # page table: one call a layer, named for the trace
                length = (
                    jnp.full((b,), t, jnp.int32) if last_idx is None
                    else last_idx + 1
                )
                with jax.named_scope("attn_latent_chunk"):
                    o_latent = paged_chunk_attention(
                        absorbed_query(), pool.value, page_table, decode_pos,
                        length, value_lanes=r, scale=scale,
                        interpret=self.flash_interpret,
                    )
                return out_proj(
                    values_of(o_latent).reshape(b, t, h * dv).astype(self.dtype)
                )
            pages_cap = page_table.shape[1]
            widths = sorted(
                {max(1, -(-pages_cap * i // 4)) for i in (1, 2, 3, 4)}
            )

            def attend(n_pages):
                view = gather_pages(pool.value, page_table[:, :n_pages])
                return values_of(attend_by_position(
                    absorbed_query(), view[:, :, None, :],
                    view[:, :, None, :r], positions, scale,
                ))

            pages_needed = jnp.max(positions) // self.page_size + 1
            out = lax.switch(
                sum((pages_needed > w).astype(jnp.int32) for w in widths[:-1]),
                [partial(attend, w) for w in widths],
            )
            return out_proj(out.reshape(b, t, h * dv).astype(self.dtype))

        # paged_decode: one token a slot, absorbed, each row read once
        if t != 1:
            raise ValueError(
                f"paged decode steps one token at a time, got t={t}"
            )
        if self.paged_attention_impl not in ("gather", "kernel"):
            raise ValueError(
                "paged_attention_impl must be 'gather' or 'kernel', got "
                f"{self.paged_attention_impl!r}"
            )
        slot_page = jnp.take_along_axis(
            page_table, (decode_pos // self.page_size)[:, None], axis=1
        )[:, 0]
        pool.value = pool.value.at[
            slot_page, decode_pos % self.page_size
        ].set(row[:, 0])
        q_lat = absorbed_query()
        if self.paged_attention_impl == "kernel":
            from cs744_pytorch_distributed_tutorial_tpu.ops.paged_attention import (
                paged_attention,
            )

            # the calls carry their kind in their name, for the trace
            with jax.named_scope("attn_latent"):
                o_latent = paged_attention(
                    q_lat, pool.value, None, page_table, decode_pos,
                    value_lanes=r, scale=scale,
                    interpret=self.flash_interpret,
                )
        else:
            view = gather_pages(pool.value, page_table)
            o_latent = attend_by_position(
                q_lat, view[:, :, None, :], view[:, :, None, :r],
                decode_pos[:, None], scale, head_block=h,
            )
        return out_proj(
            values_of(o_latent).reshape(b, 1, h * dv).astype(self.dtype)
        )


class ShortcutMoEBlock(nn.Module):
    """Two (latent attention, dense SwiGLU MLP) pairs and one MoE that
    runs beside them (module docstring)."""

    num_heads: int
    dims: LatentDims
    dense_d_ff: int
    moe: tuple  # MoEFFN's keywords, as (name, value) pairs
    dtype: Any = jnp.float32
    rope_base: float = 10000.0
    norm_eps: float = 1e-6
    page_size: int | None = None
    num_pages: int | None = None
    paged_attention_impl: str = "gather"
    flash_interpret: bool | None = None

    @nn.compact
    def __call__(
        self, x, deterministic: bool = True, *, mode="train",
        decode_pos=None, page_table=None, last_idx=None,
    ):
        from cs744_pytorch_distributed_tutorial_tpu.models.moe import MoEFFN

        del deterministic  # no dropout in this layer
        norm = partial(nn.RMSNorm, epsilon=self.norm_eps, dtype=self.dtype)
        dense = partial(nn.Dense, use_bias=False, dtype=self.dtype)

        def attention(i, h):
            return LatentAttention(
                num_heads=self.num_heads, dims=self.dims, dtype=self.dtype,
                rope_base=self.rope_base, norm_eps=self.norm_eps,
                page_size=self.page_size, num_pages=self.num_pages,
                paged_attention_impl=self.paged_attention_impl,
                flash_interpret=self.flash_interpret, name=f"attn_{i}",
            )(
                h, mode=mode, decode_pos=decode_pos, page_table=page_table,
                last_idx=last_idx,
            )

        def mlp(i, h):
            gate = dense(self.dense_d_ff, name=f"mlp_{i}_gate")(h)
            up = dense(self.dense_d_ff, name=f"mlp_{i}_in")(h)
            return dense(x.shape[-1], name=f"mlp_{i}_out")(nn.silu(gate) * up)

        x = x + attention(0, norm(name="ln_a0")(x))
        m = norm(name="ln_p0")(x)
        shortcut = MoEFFN(**dict(self.moe), dtype=self.dtype, name="moe")(m)
        x = x + mlp(0, m)
        x = x + attention(1, norm(name="ln_a1")(x))
        return x + mlp(1, norm(name="ln_p1")(x)) + shortcut


class LatentBlock(nn.Module):
    """The plain pre-norm block over latent attention (module
    docstring): its FFN is a dense SwiGLU MLP of ``dense_d_ff`` where
    ``moe`` is None, else the MoE those keywords build."""

    num_heads: int
    dims: LatentDims
    dense_d_ff: int | None = None
    moe: tuple | None = None  # MoEFFN's keywords, as (name, value) pairs
    dtype: Any = jnp.float32
    rope_base: float = 10000.0
    norm_eps: float = 1e-6
    page_size: int | None = None
    num_pages: int | None = None
    paged_attention_impl: str = "gather"
    flash_interpret: bool | None = None

    @nn.compact
    def __call__(
        self, x, deterministic: bool = True, *, mode="train",
        decode_pos=None, page_table=None, last_idx=None,
    ):
        del deterministic  # no dropout in this layer
        norm = partial(nn.RMSNorm, epsilon=self.norm_eps, dtype=self.dtype)
        dense = partial(nn.Dense, use_bias=False, dtype=self.dtype)
        x = x + LatentAttention(
            num_heads=self.num_heads, dims=self.dims, dtype=self.dtype,
            rope_base=self.rope_base, norm_eps=self.norm_eps,
            page_size=self.page_size, num_pages=self.num_pages,
            paged_attention_impl=self.paged_attention_impl,
            flash_interpret=self.flash_interpret, name="attn",
        )(
            norm(name="ln_attn")(x), mode=mode, decode_pos=decode_pos,
            page_table=page_table, last_idx=last_idx,
        )
        h = norm(name="ln_ffn")(x)
        if self.moe is not None:
            from cs744_pytorch_distributed_tutorial_tpu.models.moe import MoEFFN

            return x + MoEFFN(**dict(self.moe), dtype=self.dtype, name="moe")(h)
        gate = dense(self.dense_d_ff, name="mlp_gate")(h)
        up = dense(self.dense_d_ff, name="mlp_in")(h)
        return x + dense(x.shape[-1], name="mlp_out")(nn.silu(gate) * up)
