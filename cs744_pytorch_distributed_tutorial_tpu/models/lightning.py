"""Lightning attention: linear attention with one decay a head, in the
three forms that must agree (MiniMax-01's lightning attention, as
MiniCPM-SALA's ``lightning-attn`` layers run it).

Imported where such a layer is built (``TransformerLM`` with a
``"lightning_attention"`` entry in ``layer_types``), so a model without
one pays nothing for it.

For the normed input ``x``, ``H`` independent heads of ``d``::

    q_t = RoPE(RMSNorm_d(x W_q))_t / sqrt(d)
    k_t = RoPE(RMSNorm_d(x W_k))_t
    v_t = (x W_v)_t
    S_t = lam_h S_{t-1} + k_t^T v_t        (S_{-1} = 0, float32, d x d)
    o_t = q_t S_t
    y   = (RMSNorm_{H d}(o) * sigmoid(x W_g)) W_o

``lam_h = exp(-rate_h)``; ``rate`` is the layer's own (the config builder
derives it from the head and the layer's published index). The output
norm runs over all ``H d`` lanes.

**The forms.** ``train`` computes every position of a sequence by the
chunked form a block at a time with the state carried between blocks
(``ops/lightning.py::full_forward``); ``paged_prefill`` runs a chunk of
one slot and carries the slot's state row across chunks; ``paged_decode``
advances every live slot's state by one token. The state rows live in
the ``pages`` collection as ``lightning_state [num_slots, H, d, d]``
float32, one row a slot, addressed by the slot and not by the page
table: the engine builds, donates and carries them with the pools. A
chunk at position 0 starts its row from zero, so a slot given to another
request (a new one, or a preempted one recomputed) needs no reset.
Under ``paged_attention_impl`` "kernel" both serving forms are Pallas
kernels (named ``attn_lightning_chunk`` and ``attn_lightning``), under
"gather" the same mathematics in XLA.
"""

from __future__ import annotations

from functools import partial
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from cs744_pytorch_distributed_tutorial_tpu.models.transformer import apply_rope


class LightningAttention(nn.Module):
    """One lightning-attention mixer (module docstring)."""

    num_heads: int
    head_dim: int
    rate: tuple  # a head's decay exponent: lam = exp(-rate)
    dtype: Any = jnp.float32
    rope_base: float = 10000.0
    norm_eps: float = 1e-6
    paged_attention_impl: str = "gather"
    flash_interpret: bool | None = None

    @nn.compact
    def __call__(
        self, x, *, mode="train", decode_pos=None, slot_rows=None,
        slot_live=None, last_idx=None,
    ):
        """``slot_rows`` ([1], ``paged_prefill``): the state row of the
        chunk's slot; ``slot_live`` ([B], ``paged_decode``): the slots
        whose state advances (None: all); ``last_idx`` ([1]): the chunk's
        last real index."""
        from cs744_pytorch_distributed_tutorial_tpu.ops import lightning as L

        if mode not in ("train", "paged_prefill", "paged_decode"):
            raise ValueError(
                f"mode={mode!r}: a lightning layer keeps a recurrent state, "
                "not a cache of keys and values; it runs in modes 'train', "
                "'paged_prefill' and 'paged_decode'"
            )
        b, t, d_model = x.shape
        h, d = self.num_heads, self.head_dim
        if len(self.rate) != h:
            raise ValueError(f"rate has {len(self.rate)} heads, the layer {h}")
        dense = partial(nn.Dense, use_bias=False, dtype=self.dtype)
        norm = partial(nn.RMSNorm, epsilon=self.norm_eps, dtype=self.dtype)
        # q and k are made whole before their norm a head: fused into it,
        # a decode step's projection compiles (for the v5e) to a
        # multiply-reduce over the kernel laid out [heads, d, in], a copy
        # of the whole kernel every step
        bar = jax.lax.optimization_barrier
        q = norm(name="q_norm")(bar(dense(h * d, name="q")(x)).reshape(b, t, h, d))
        k = norm(name="k_norm")(bar(dense(h * d, name="k")(x)).reshape(b, t, h, d))
        v = dense(h * d, name="v")(x).reshape(b, t, h, d)
        if mode == "train":
            positions = jnp.arange(t)
        else:
            if decode_pos is None:
                raise ValueError(f"mode={mode!r} needs decode_pos ([B])")
            positions = jnp.asarray(decode_pos)[:, None] + jnp.arange(t)
        q = apply_rope(q, positions, self.rope_base) * jnp.asarray(d ** -0.5, q.dtype)
        k = apply_rope(k, positions, self.rope_base)
        rate = jnp.asarray(self.rate, jnp.float32)
        kernel = self.paged_attention_impl == "kernel"
        if mode == "train":
            o = jax.vmap(lambda a, c, e: L.full_forward(a, c, e, rate))(q, k, v)
        else:
            state = self.variable(
                "pages", "lightning_state", jnp.zeros, (b, h, d, d), jnp.float32
            )
        if mode == "paged_prefill":
            if b != 1 or slot_rows is None:
                raise ValueError(
                    "a prefill chunk is one slot's, and names its state row "
                    "(slot_rows [1])"
                )
            length = jnp.int32(t) if last_idx is None else last_idx[0] + 1
            if kernel:
                with jax.named_scope("attn_lightning_chunk"):
                    o, state.value = L.lightning_chunk(
                        q[0], k[0], v[0], state.value, rate, slot_rows[0],
                        decode_pos[0], length, interpret=self.flash_interpret,
                    )
            else:
                slot = slot_rows[0]
                prev = jnp.where(decode_pos[0] == 0, 0.0, state.value[slot])
                o, new = L.chunk_reference(q[0], k[0], v[0], prev, rate, length)
                state.value = state.value.at[slot].set(new)
            o = o[None]
        elif mode == "paged_decode":
            if t != 1:
                raise ValueError(f"paged decode steps one token at a time, got t={t}")
            live = (
                jnp.ones((b,), jnp.int32) if slot_live is None
                else slot_live.astype(jnp.int32)
            )
            if kernel:
                with jax.named_scope("attn_lightning"):
                    o, state.value = L.lightning_decode(
                        q[:, 0], k[:, 0], v[:, 0], state.value, rate, live,
                        interpret=self.flash_interpret,
                    )
            else:
                o, state.value = L.decode_reference(
                    q[:, 0], k[:, 0], v[:, 0], state.value, rate, live
                )
            o = o[:, None]
            if not self.is_initializing():
                # (slot, layer) state updates (the engine's counters; a
                # no-op unless "serve_stats" is asked for)
                self.sow("serve_stats", "lightning_state_updates", live)
        o = nn.RMSNorm(epsilon=self.norm_eps, dtype=self.dtype, name="o_norm")(
            o.reshape(b, t, h * d).astype(self.dtype)
        )
        gate = nn.sigmoid(dense(h * d, name="gate")(x))
        return dense(d_model, name="attn_out")(o * gate)
