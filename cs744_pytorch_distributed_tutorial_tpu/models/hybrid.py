"""The pre-norm block of a model whose mixers differ by layer between
lightning attention (models/lightning.py) and block-sparse attention
(models/block_sparse.py): MiniCPM-SALA's layer, with MiniCPM's muP
residual scale::

    x = x + a * Mixer(RMSNorm(x))
    x = x + a * SwiGLU(RMSNorm(x))

``a`` is ``scale_depth / sqrt(mup_denominator)`` (``minicpm_sala_model_config`` computes it). Imported
where such a layer is built, so a model without one pays nothing for it.
"""

from __future__ import annotations

from functools import partial
from typing import Any

import flax.linen as nn
import jax.numpy as jnp

from cs744_pytorch_distributed_tutorial_tpu.models.transformer import HYBRID_KINDS


class HybridBlock(nn.Module):
    """One layer: a mixer of ``kind`` (its keywords as (name, value)
    pairs), then a dense SwiGLU MLP of ``d_ff``, each residual times
    ``residual_scale``."""

    kind: str
    mixer: tuple
    d_ff: int
    dtype: Any = jnp.float32
    norm_eps: float = 1e-6
    residual_scale: float = 1.0

    @nn.compact
    def __call__(
        self, x, deterministic: bool = True, *, mode="train", decode_pos=None,
        page_table=None, slot_rows=None, slot_live=None, last_idx=None,
    ):
        del deterministic  # no dropout in this layer
        norm = partial(nn.RMSNorm, epsilon=self.norm_eps, dtype=self.dtype)
        dense = partial(nn.Dense, use_bias=False, dtype=self.dtype)
        a = jnp.asarray(self.residual_scale, self.dtype)
        h = norm(name="ln_attn")(x)
        if self.kind == "lightning_attention":
            from cs744_pytorch_distributed_tutorial_tpu.models.lightning import (
                LightningAttention,
            )

            y = LightningAttention(**dict(self.mixer), dtype=self.dtype, name="attn")(
                h, mode=mode, decode_pos=decode_pos, slot_rows=slot_rows,
                slot_live=slot_live, last_idx=last_idx,
            )
        elif self.kind == "block_sparse_attention":
            from cs744_pytorch_distributed_tutorial_tpu.models.block_sparse import (
                BlockSparseAttention,
            )

            y = BlockSparseAttention(**dict(self.mixer), dtype=self.dtype, name="attn")(
                h, mode=mode, decode_pos=decode_pos, page_table=page_table,
                last_idx=last_idx,
            )
        else:
            raise ValueError(f"unknown mixer {self.kind!r}; one of {HYBRID_KINDS}")
        x = x + a * y
        h = norm(name="ln_ffn")(x)
        gate = dense(self.d_ff, name="mlp_gate")(h)
        up = dense(self.d_ff, name="mlp_in")(h)
        return x + a * dense(x.shape[-1], name="mlp_out")(nn.silu(gate) * up)
