"""Decoder-only transformer LM with pluggable sequence parallelism.

No counterpart exists in the reference (its only model is conv VGG-11,
``master/part1/model.py:30-46``) — this is the long-context model family
that exercises the framework's sequence/context parallelism
(``parallel/ring_attention.py``) as a first-class capability, the same
way VGG exercises data parallelism.

Design for SPMD: the module is agnostic to whether it runs on a full or a
sequence-sharded block. When ``seq_axis`` is set, the module is being
traced inside ``shard_map`` with activations of shape
``[B_local, T_local, ...]``; attention routes through the ring or
all-to-all variant over that axis and position embeddings use the
device's global offset (``lax.axis_index * T_local``). With
``seq_axis=None`` the same code is plain single-device attention — which
also makes host-side ``init`` trivial (attention has no parameters, so
the param tree is identical either way).
"""

from __future__ import annotations

from functools import partial
from typing import Any, NamedTuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from cs744_pytorch_distributed_tutorial_tpu.parallel.ring_attention import (
    decode_attention,
    dense_attention,
    ring_attention,
    ring_flash_attention,
    ulysses_attention,
)
from cs744_pytorch_distributed_tutorial_tpu.parallel.tensor import (
    copy_to_tp_region,
    reduce_from_tp_region,
)

ATTENTION_IMPLS = (
    "dense", "flash", "ring", "ring_flash", "ulysses", "ulysses_flash"
)

REMAT_POLICIES = ("none", "dots")

NORM_IMPLS = ("layernorm", "rmsnorm")
MLP_IMPLS = ("gelu", "swiglu")
# layer_types of a model whose mixers are lightning and block-sparse
# attention (models/hybrid.py)
HYBRID_KINDS = ("lightning_attention", "block_sparse_attention")


def _norm_cls(norm: str, eps: float = 1e-6):
    """The block's normalization layer: the GPT-2-style LayerNorm
    default, or RMSNorm (no mean subtraction, no bias) — the
    llama-family choice, cheaper on the VPU by one reduction pass.
    ``eps`` is exposed because checkpoint families pin it (GPT-2: 1e-5,
    flax default 1e-6) and eval-parity imports need the exact value."""
    if norm == "layernorm":
        return partial(nn.LayerNorm, epsilon=eps)
    if norm == "rmsnorm":
        return partial(nn.RMSNorm, epsilon=eps)
    raise ValueError(f"unknown norm {norm!r}; choose from {NORM_IMPLS}")


def _dense_cls(quant: bool):
    """``nn.Dense``, or the weight-only-int8 ``QuantDense`` under
    ``quant_dense=True`` (lazy import — the quant path is decode-only)."""
    if not quant:
        return nn.Dense
    from cs744_pytorch_distributed_tutorial_tpu.ops.quant import QuantDense

    return QuantDense


class RopeScaling(NamedTuple):
    """YaRN's parameters as a published ``rope_parameters`` entry of
    ``rope_type: "yarn"`` gives them. ``attention_factor`` None is the
    paper's ``0.1 ln(factor) + 1``."""

    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float | None = None


def rope_inv_freq(
    head_dim: int, base: float, scaling: RopeScaling | None = None
) -> tuple[jnp.ndarray, float]:
    """The ``head_dim // 2`` rotary frequencies and the factor on cos and
    sin, for both kinds of RoPE. Default: ``base**(-i/half)``, factor 1.
    YaRN: a frequency that turns more than ``beta_fast`` times over the
    original context is kept, one that turns less than ``beta_slow``
    times is divided by ``factor``, those between are blended linearly
    in the frequency's index; cos and sin carry ``attention_factor``, so
    the scores carry its square."""
    half = head_dim // 2
    freqs = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    if scaling is None:
        return freqs, 1.0
    import math

    def turns_at(r: float) -> float:  # the index that turns r times
        return (
            head_dim
            * math.log(scaling.original_max_position / (2 * math.pi * r))
            / (2 * math.log(base))
        )

    low = max(math.floor(turns_at(scaling.beta_fast)), 0)
    high = min(math.ceil(turns_at(scaling.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001  # no division by zero (as the published code has it)
    ramp = jnp.clip(
        (jnp.arange(half, dtype=jnp.float32) - low) / (high - low), 0.0, 1.0
    )
    freqs = freqs * ((1.0 - ramp) + ramp / scaling.factor)
    factor = scaling.attention_factor
    if factor is None:
        factor = 0.1 * math.log(scaling.factor) + 1.0
    return freqs, float(factor)


def apply_rope(
    x: jnp.ndarray,
    positions: jnp.ndarray,
    base: float = 10000.0,
    scaling: RopeScaling | None = None,
) -> jnp.ndarray:
    """Rotary position embedding on [B, T, H, D] (D even).

    Pairs dimension i with i + D/2 and rotates each pair by
    ``positions * base**(-2i/D)`` — attention then depends on RELATIVE
    positions only, which is what makes RoPE exact under sequence
    sharding: each shard rotates its q/k by its GLOBAL positions before
    any collective, and ring/all-to-all attention needs no further
    position bookkeeping. ``scaling`` (YaRN) changes the frequencies and
    scales cos and sin (``rope_inv_freq``); None is the plain rotation.
    """
    d = x.shape[-1]
    if d % 2:
        raise ValueError(f"RoPE needs an even head_dim, got {d}")
    half = d // 2
    freqs, scale = rope_inv_freq(d, base, scaling)
    # positions is [T] (shared across the batch) or [B, T] (per-slot
    # depths on the paged-decode serve path — each slot rotates by its
    # own global position).
    angles = positions.astype(jnp.float32)[..., :, None] * freqs
    sin = jnp.sin(angles)[..., None, :]
    cos = jnp.cos(angles)[..., None, :]
    if scaling is not None:
        sin, cos = sin * scale, cos * scale
    if angles.ndim == 2:  # [T, half] -> broadcast over batch as before
        sin, cos = sin[None], cos[None]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1
    ).astype(x.dtype)


def resolve_remat_policy(name: str | None):
    """Map a policy name to a jax.checkpoint policy: "none" recomputes
    everything in backward (maximum memory saving, one extra forward of
    FLOPs); "dots" saves matmul outputs and recomputes only elementwise
    ops (cheaper backward, the MXU-work-is-sacred trade)."""
    if name in (None, "none"):
        return None
    if name == "dots":
        return jax.checkpoint_policies.dots_saveable
    raise ValueError(
        f"unknown remat_policy {name!r}; choose from {REMAT_POLICIES}"
    )


def default_flash_interpret() -> bool:
    """The Pallas kernel Mosaic-compiles only on TPU; interpret
    elsewhere. This probes the global default backend — when the
    computation targets a non-default device set (e.g. a CPU test mesh
    on a TPU host), set the module's ``flash_interpret`` field from the
    mesh instead (as LMTrainer does)."""
    from cs744_pytorch_distributed_tutorial_tpu.ops._backend import (
        default_interpret,
    )

    return default_interpret()


class Attention(nn.Module):
    """Multi-head self-attention; the comm pattern is a config knob.

    With ``tensor_axis`` set (Megatron-style tensor parallelism), each
    device projects and attends over its contiguous slice of
    ``num_heads // tensor_axis_size`` heads — q/k/v are column-parallel,
    the output projection is row-parallel, and one psum per sublayer
    (inside ``reduce_from_tp_region``) restores the replicated residual
    stream. q/k/v are separate projections (not one fused 3x matmul) so
    the global parameter layout is invariant to the tensor-axis size:
    sharding a head-sliced kernel over devices is a plain column split.
    """

    num_heads: int
    dtype: Any = jnp.float32
    impl: str = "dense"
    seq_axis: str | None = None
    seq_axis_size: int = 1
    tensor_axis: str | None = None
    tensor_axis_size: int = 1
    causal: bool = True
    flash_interpret: bool | None = None  # None = probe default backend
    # KV-cache length for autoregressive decoding (infer/generate.py);
    # required when __call__ runs in "prefill"/"decode" mode.
    max_decode_len: int | None = None
    # Rotary position embeddings applied to q/k (global positions, so
    # sequence sharding and cached decode are position-exact).
    rope: bool = False
    rope_base: float = 10000.0
    # Grouped-query attention: K/V get this many heads (must divide
    # num_heads; 1 = multi-query). The KV cache stores only KV heads —
    # the decode-memory/bandwidth lever — and K/V repeat up to the query
    # head count at compute time. None = standard MHA.
    num_kv_heads: int | None = None
    # Weight-only int8 projections (ops/quant.py::QuantDense) — the
    # decode-bandwidth lever; params come from quantize_lm_params.
    # quant_modules narrows which Dense modules quantize (per-call
    # dispatch cost makes small projections a measured loss — see
    # ops/quant.py::QUANT_HEAD_ONLY).
    quant_dense: bool = False
    quant_modules: tuple = ("q", "k", "v", "attn_out", "mlp_in", "mlp_gate", "mlp_out", "lm_head")
    # Int8 KV cache (ops/quant.py::quantize_kv): rows stored int8 with a
    # per-(batch, position, head) scale — the long-context decode
    # bandwidth lever, independent of quant_dense.
    quant_kv_cache: bool = False
    # Biases on the q/k/v/attn_out projections (GPT-2 checkpoints have
    # them; the default False matches the modern bias-free convention).
    # Incompatible with a tensor axis: the row-parallel attn_out bias
    # would be psum-summed tensor_axis_size times.
    attn_bias: bool = False
    # Paged KV pool (mode="paged_decode", serve/): per-layer
    # [num_pages, page_size, Hkv*D] pools in the "pages" collection,
    # indexed by a per-slot page table — memory scales with live tokens
    # across the whole engine, not B x max_seq_len. Both must be set to
    # use the paged mode.
    page_size: int | None = None
    num_pages: int | None = None
    # Paged-decode attention implementation: "gather" materializes each
    # slot's dense view via gather_pages + einsum (the reference,
    # bitwise-parity-exact with the dense cache); "kernel" runs the
    # Pallas paged-attention kernel (ops/paged_attention.py) that reads
    # only live pages — tolerance-level parity (online softmax), HBM
    # traffic scaling with live tokens instead of page capacity.
    paged_attention_impl: str = "gather"
    # Width of one head where it is not d_model / num_heads (q, k, v
    # project to heads * head_dim, attn_out back to d_model).
    head_dim: int | None = None
    # RMSNorm over each head's q and k (learned scale over head_dim),
    # before RoPE.
    qk_norm: bool = False
    qk_norm_eps: float = 1e-6
    # Learned sparse attention (ops/sparse_attention.py): with
    # ``indexer_heads`` > 0 an indexer of that many heads of
    # ``indexer_head_dim`` (one key head, cached beside K and V) scores
    # every token at or before a query, and the query attends over the
    # ``sparse_topk`` best only.
    indexer_heads: int = 0
    indexer_head_dim: int = 64
    sparse_topk: int = 0
    # A sliding window: a query at t sees the keys s with
    # t - window < s <= t (``window`` keys, itself among them). Built for
    # modes train (impl "dense"), paged_prefill and paged_decode; in the
    # paged modes ``page_table`` lists the slot's live window pages only
    # and ``first_pos`` gives the position of the table's first row
    # (serve/engine.py: the window page group).
    window: int | None = None
    # YaRN on this layer's RoPE (``rope_inv_freq``); None = plain RoPE.
    rope_scaling: RopeScaling | None = None
    # A name scope around the paged-attention kernel's call
    # ("attn_window" / "attn_full": a model whose layers differ names
    # them by kind); None leaves the call under the module's own name.
    attn_scope: str | None = None

    def _window_attention(self, q, k, v, q_pos):
        """Causal attention of ``q`` [B, C, H, D] at row positions
        ``q_pos`` [B, C] over the rows of ``k`` / ``v`` [B, S, Hkv, D],
        no further back than the window."""
        from cs744_pytorch_distributed_tutorial_tpu.ops.sparse_attention import (
            masked_attention,
        )

        in_window = jnp.arange(k.shape[1])[None, None, :] > (
            q_pos[:, :, None] - self.window
        )
        return masked_attention(q, k, v, q_pos, in_window)

    def _window_view_attention(self, q, key_pages, value_pages, table, rel):
        """The same over a slot's window pages: the table's whole view
        (row ``r`` holds position ``first_pos + r``), queries at
        ``rel`` [B, C] rows into it. One static view: the table is as
        wide as a window and a chunk need (serve/engine.py: P_w)."""
        from cs744_pytorch_distributed_tutorial_tpu.parallel.ring_attention import (
            gather_pages,
            unfold_heads,
        )

        d = q.shape[-1]
        return self._window_attention(
            q,
            unfold_heads(gather_pages(key_pages, table), d),
            unfold_heads(gather_pages(value_pages, table), d),
            rel,
        )

    @nn.compact
    def __call__(
        self,
        x: jnp.ndarray,
        *,
        mode: str = "train",
        decode_pos: jnp.ndarray | None = None,
        page_table: jnp.ndarray | None = None,
        first_pos: jnp.ndarray | None = None,
    ) -> jnp.ndarray:
        if self.impl not in ATTENTION_IMPLS:
            raise ValueError(
                f"unknown attention impl {self.impl!r}; choose from {ATTENTION_IMPLS}"
            )
        if mode not in (
            "train", "prefill", "decode", "paged_decode", "paged_prefill"
        ):
            raise ValueError(
                f"unknown mode {mode!r}; choose from ('train', 'prefill', "
                "'decode', 'paged_decode', 'paged_prefill')"
            )
        b, t, d_model = x.shape
        if self.head_dim is None and d_model % self.num_heads:
            raise ValueError(
                f"d_model {d_model} not divisible by num_heads {self.num_heads}"
            )
        head_dim = self.head_dim or d_model // self.num_heads
        tp = self.tensor_axis is not None and self.tensor_axis_size > 1
        sparse = self.indexer_heads > 0
        if sparse:
            if self.sparse_topk < 1:
                raise ValueError(
                    "indexer_heads > 0 needs sparse_topk >= 1, got "
                    f"{self.sparse_topk}"
                )
            if tp or (self.seq_axis is not None and self.seq_axis_size > 1):
                raise ValueError(
                    "the sparse-attention indexer runs on one device: no "
                    "tensor or sequence axis"
                )
            if mode == "decode" or self.quant_kv_cache or not self.causal:
                raise ValueError(
                    "the sparse-attention indexer serves through the paged "
                    "pools in float (modes train, prefill, paged_prefill, "
                    "paged_decode; causal; no int8 KV)"
                )
        windowed = self.window is not None
        if windowed:
            if self.window < 1:
                raise ValueError(f"window must be >= 1, got {self.window}")
            if tp or (self.seq_axis is not None and self.seq_axis_size > 1):
                raise ValueError(
                    "a window layer runs on one device: no tensor or "
                    "sequence axis (the window's pages and mask are not "
                    "sharded)"
                )
            if mode in ("prefill", "decode"):
                raise ValueError(
                    f"mode={mode!r} keeps a dense cache of every position, "
                    "which a window layer neither needs nor masks: serve "
                    "it through the paged pools (ServeConfig.prefill_chunk)"
                )
            if self.impl != "dense" and mode == "train":
                raise ValueError(
                    f"impl={self.impl!r} has no window (the flash tile plan "
                    "knows one diagonal); a window layer trains with "
                    "impl='dense'"
                )
            if self.quant_kv_cache or sparse or not self.causal:
                raise ValueError(
                    "a window layer is causal, caches float K and V and "
                    "has no indexer (no int8 KV, no sparse_topk)"
                )
            if mode in ("paged_prefill", "paged_decode") and first_pos is None:
                raise ValueError(
                    f"mode={mode!r} on a window layer needs first_pos (the "
                    "position of the window page table's first row, [B])"
                )
        if tp and self.num_heads % self.tensor_axis_size:
            raise ValueError(
                f"num_heads {self.num_heads} not divisible by tensor axis "
                f"{self.tensor_axis_size}"
            )
        heads_local = (
            self.num_heads // self.tensor_axis_size if tp else self.num_heads
        )
        kv_heads = (
            self.num_heads if self.num_kv_heads is None else self.num_kv_heads
        )
        if kv_heads < 1 or self.num_heads % kv_heads:
            raise ValueError(
                f"num_kv_heads {kv_heads} must be >= 1 and divide "
                f"num_heads {self.num_heads}"
            )
        if tp and kv_heads % self.tensor_axis_size:
            raise ValueError(
                f"num_kv_heads {kv_heads} not divisible by tensor axis "
                f"{self.tensor_axis_size}"
            )
        kv_local = kv_heads // self.tensor_axis_size if tp else kv_heads
        if tp:
            x = copy_to_tp_region(x, self.tensor_axis)
        if self.attn_bias and tp:
            raise ValueError(
                "attn_bias does not compose with a tensor axis (the "
                "row-parallel attn_out bias would be summed "
                f"{self.tensor_axis_size}x by the sublayer psum)"
            )

        def proj_cls(mod):
            return _dense_cls(self.quant_dense and mod in self.quant_modules)

        def proj(feats, name):
            return proj_cls(name)(
                feats, use_bias=self.attn_bias, dtype=self.dtype, name=name
            )

        q = proj(heads_local * head_dim, name="q")(x)
        k = proj(kv_local * head_dim, name="k")(x)
        v = proj(kv_local * head_dim, name="v")(x)
        q = q.reshape(b, t, heads_local, head_dim)
        k = k.reshape(b, t, kv_local, head_dim)
        v = v.reshape(b, t, kv_local, head_dim)
        if self.qk_norm:
            qk_norm = partial(
                nn.RMSNorm, epsilon=self.qk_norm_eps, dtype=self.dtype
            )
            q = qk_norm(name="q_norm")(q)
            k = qk_norm(name="k_norm")(k)
        if sparse:
            q_idx = proj(
                self.indexer_heads * self.indexer_head_dim, name="idx_q"
            )(x).reshape(b, t, self.indexer_heads, self.indexer_head_dim)
            k_idx = nn.LayerNorm(
                epsilon=1e-6, dtype=self.dtype, name="idx_k_norm"
            )(proj(self.indexer_head_dim, name="idx_k")(x))[:, :, None, :]
            w_idx = proj(self.indexer_heads, name="idx_w")(x)

        if self.rope:
            # GLOBAL positions of this block's tokens: the shard offset
            # under sequence sharding, the cache position when decoding.
            if mode in ("decode", "paged_decode", "paged_prefill"):
                if decode_pos is None:
                    raise ValueError(f"mode={mode!r} needs decode_pos")
                offset = decode_pos
            elif self.seq_axis is not None and self.seq_axis_size > 1:
                offset = lax.axis_index(self.seq_axis) * t
            else:
                offset = 0
            if jnp.ndim(offset):
                # Per-slot depths (paged decode): [B] offsets -> [B, t]
                # positions, each row rotating by its own depth.
                positions = jnp.asarray(offset)[:, None] + jnp.arange(t)
            else:
                positions = offset + jnp.arange(t)
            rope = partial(
                apply_rope, positions=positions, base=self.rope_base,
                scaling=self.rope_scaling,
            )
            q, k = rope(q), rope(k)
            if sparse:
                q_idx, k_idx = rope(q_idx), rope(k_idx)
        if sparse:
            k_idx = k_idx[:, :, 0]  # [B, T, Di]: one key head
            # As cached: the row padded with zeros to whole 128-lane
            # tiles. A [num_pages, page_size, 64] bf16 pool is laid out
            # with num_pages minor-most by the TPU compiler, and every
            # program then copies it to row-major and back
            # (serve/layout.py: the K and V pools' fault before they
            # were folded); 128 lanes tile without padding and stay
            # row-major. Zero lanes add nothing to a dot product.
            idx_lanes = -(-self.indexer_head_dim // 128) * 128
            k_idx_row = jnp.pad(
                k_idx, ((0, 0), (0, 0), (0, idx_lanes - k_idx.shape[-1]))
            )

            def idx_view(pool, table):  # cached indexer keys [B, S, Di]
                from cs744_pytorch_distributed_tutorial_tpu.parallel.ring_attention import (
                    gather_pages,
                )

                return gather_pages(pool, table)[..., : self.indexer_head_dim]

        decode_step = False
        if mode in ("prefill", "decode"):
            # Cached prefill/decode (infer/generate.py): the cache holds
            # the FULL sequence, so the sequence axis must be unsharded
            # (generation runs outside shard_map; data parallelism comes
            # from jit's batch sharding instead).
            if self.seq_axis is not None and self.seq_axis_size > 1:
                raise ValueError(
                    "cached prefill/decode requires an unsharded sequence "
                    f"axis; got seq_axis={self.seq_axis!r} "
                    f"(size {self.seq_axis_size})"
                )
            if self.max_decode_len is None:
                raise ValueError(
                    f"mode={mode!r} needs max_decode_len (the KV-cache length)"
                )
            # Only KV heads are cached — with GQA this is the
            # num_heads/num_kv_heads memory and bandwidth saving per
            # decode step. With quant_kv_cache the rows are stored int8
            # with a per-(batch, position, head) scale (ops/quant.py) —
            # the LONG-context decode bandwidth lever: past a few
            # thousand positions the cache, not the weights, is most of
            # the bytes a decode step reads.
            cache_shape = (b, self.max_decode_len, kv_local, head_dim)
            cache_dtype = jnp.int8 if self.quant_kv_cache else k.dtype
            ck = self.variable(
                "cache", "cached_key", jnp.zeros, cache_shape, cache_dtype
            )
            cv = self.variable(
                "cache", "cached_value", jnp.zeros, cache_shape, cache_dtype
            )
            if self.quant_kv_cache:
                cks = self.variable(
                    "cache", "key_scale", jnp.ones, cache_shape[:3],
                    jnp.float32,
                )
                cvs = self.variable(
                    "cache", "value_scale", jnp.ones, cache_shape[:3],
                    jnp.float32,
                )

            if sparse:
                cik = self.variable(
                    "cache", "cached_index_key", jnp.zeros,
                    (b, self.max_decode_len, idx_lanes), k_idx.dtype,
                )

            def write_cache(pos0) -> None:
                if sparse:
                    cik.value = lax.dynamic_update_slice(
                        cik.value, k_idx_row, (0, pos0, 0)
                    )
                if self.quant_kv_cache:
                    from cs744_pytorch_distributed_tutorial_tpu.ops.quant import (
                        quantize_kv,
                    )

                    kq, ks = quantize_kv(k)
                    vq, vs = quantize_kv(v)
                    ck.value = lax.dynamic_update_slice(
                        ck.value, kq, (0, pos0, 0, 0)
                    )
                    cv.value = lax.dynamic_update_slice(
                        cv.value, vq, (0, pos0, 0, 0)
                    )
                    cks.value = lax.dynamic_update_slice(
                        cks.value, ks, (0, pos0, 0)
                    )
                    cvs.value = lax.dynamic_update_slice(
                        cvs.value, vs, (0, pos0, 0)
                    )
                else:
                    ck.value = lax.dynamic_update_slice(
                        ck.value, k, (0, pos0, 0, 0)
                    )
                    cv.value = lax.dynamic_update_slice(
                        cv.value, v, (0, pos0, 0, 0)
                    )

            if mode == "prefill":
                # Write the prompt's K/V at positions [0, t); attention
                # itself is the ordinary causal pass below over the
                # FRESH full-precision k/v (quantization error enters
                # only where the cache is read back — decode steps).
                write_cache(0)
            else:
                if decode_pos is None:
                    raise ValueError("mode='decode' needs decode_pos")
                # t == 1 is the classic decode step; t > 1 is a chunk at
                # positions decode_pos..decode_pos+t-1 attending over the
                # cache with per-row causal masking (chunked prefill /
                # speculative verification — decode_attention handles
                # both shapes).
                write_cache(decode_pos)
                decode_step = True
        elif mode in ("paged_decode", "paged_prefill"):
            # Continuous-batching serve path (serve/): KV lives in a
            # POOL of fixed-size pages shared by every slot —
            # [num_pages, page_size, Hkv*D] per layer in the "pages"
            # collection, head h in lanes [h*D, (h+1)*D) — and each
            # slot's pages are listed (in sequence order) by its
            # ``page_table`` row. Folded, because the TPU's default
            # layout of a 4-D [.., Hkv, D] pool puts num_pages minor-most
            # and every program then converts the pool to row-major and
            # back (serve/layout.py; tests/test_serve_layout.py checks
            # it with the compile-only topology). Pool memory scales
            # with LIVE tokens across the engine instead of B x
            # max_seq_len, and a retired slot's pages recycle
            # immediately. The new
            # token's K/V scatters into (page_table[b, pos//page],
            # pos%page); attention then either gathers the slot's pages
            # into the dense per-slot view and runs the exact
            # decode_attention path (impl="gather" — bitwise-identical
            # to the dense cache, tests/test_serve.py), or runs the
            # Pallas paged-attention kernel straight over the pools
            # (impl="kernel" — reads only live pages, tolerance-level
            # parity; ops/paged_attention.py).
            if self.seq_axis is not None and self.seq_axis_size > 1:
                raise ValueError(
                    "paged decode requires an unsharded sequence axis; "
                    f"got seq_axis={self.seq_axis!r} "
                    f"(size {self.seq_axis_size})"
                )
            if self.page_size is None or self.num_pages is None:
                raise ValueError(
                    f"mode={mode!r} needs page_size and num_pages "
                    "(the paged KV pool geometry; see serve/engine.py)"
                )
            if decode_pos is None or page_table is None:
                raise ValueError(
                    f"mode={mode!r} needs decode_pos (per-slot "
                    "depths, [B]) and page_table ([B, P] page indices)"
                )
            if mode == "paged_decode" and t != 1:
                raise ValueError(
                    f"paged decode steps one token at a time, got t={t}"
                )
            if mode == "paged_prefill" and self.quant_kv_cache:
                raise ValueError(
                    "chunked prefill writes float pools; an int8-KV engine "
                    "prefills one shot (ServeConfig.prefill_chunk=None)"
                )
            pool_shape = (self.num_pages, self.page_size, kv_local * head_dim)
            scale_shape = (self.num_pages, self.page_size, kv_local)
            pool_dtype = jnp.int8 if self.quant_kv_cache else k.dtype
            kp = self.variable(
                "pages", "key_pages", jnp.zeros, pool_shape, pool_dtype
            )
            vp = self.variable(
                "pages", "value_pages", jnp.zeros, pool_shape, pool_dtype
            )
            if self.quant_kv_cache:
                ksp = self.variable(
                    "pages", "key_scale_pages", jnp.ones, scale_shape,
                    jnp.float32,
                )
                vsp = self.variable(
                    "pages", "value_scale_pages", jnp.ones, scale_shape,
                    jnp.float32,
                )
            if sparse:
                # The indexer's keys: a third pool under the same page
                # table, one (lane-padded) row a token.
                ip = self.variable(
                    "pages", "index_key_pages", jnp.zeros,
                    (self.num_pages, self.page_size, idx_lanes), k_idx.dtype,
                )
        if mode == "paged_prefill":
            # A chunk of t tokens a slot at positions decode_pos..+t-1:
            # its K, V (and indexer keys) go into the slot's pages, then
            # the chunk attends over the slot's whole view, which now
            # holds it. Positions past the page table's width, or on
            # entries the engine left 0, land on the trash page; rows
            # past the prompt's end inside its last page are overwritten
            # by decode before any query can see them (every mask is by
            # position).
            from cs744_pytorch_distributed_tutorial_tpu.ops import (
                sparse_attention as sa,
            )
            from cs744_pytorch_distributed_tutorial_tpu.parallel.ring_attention import (
                gather_pages,
                unfold_heads,
            )

            positions = jnp.asarray(decode_pos)[:, None] + jnp.arange(t)
            if windowed:
                # The window group's table starts at the page that holds
                # position first_pos (a multiple of page_size).
                rel = positions - jnp.asarray(first_pos)[:, None]
                page_idx = rel // self.page_size
                in_table = (page_idx >= 0) & (page_idx < page_table.shape[1])
            else:
                page_idx = positions // self.page_size
                in_table = page_idx < page_table.shape[1]
            rows_page = jnp.where(
                in_table,
                jnp.take_along_axis(
                    page_table,
                    jnp.minimum(page_idx, page_table.shape[1] - 1),
                    axis=1,
                ),
                0,
            )
            rows_off = positions % self.page_size
            kp.value = kp.value.at[rows_page, rows_off].set(
                k.reshape(b, t, kv_local * head_dim)
            )
            vp.value = vp.value.at[rows_page, rows_off].set(
                v.reshape(b, t, kv_local * head_dim)
            )
            if sparse:
                ip.value = ip.value.at[rows_page, rows_off].set(k_idx_row)

            # The chunk attends over the slot's paged view, but only over
            # as much of it as the chunk's last position reaches: the
            # view's width is one of a few static fractions of the
            # slot's capacity, picked at run time (one program, a branch
            # a width), so a chunk early in a long prompt does not score
            # the whole capacity to mask most of it. Every width gives
            # the same numbers: keys past the last position are masked.
            pages_cap = page_table.shape[1]
            widths = sorted(
                {max(1, -(-pages_cap * i // 4)) for i in (1, 2, 3, 4)}
            )

            def attend(n_pages):
                table = page_table[:, :n_pages]
                allowed = None
                if sparse:
                    view = idx_view(ip.value, table)
                    valid = (
                        jnp.arange(view.shape[1])[None, None, :]
                        <= positions[:, :, None]
                    )
                    allowed = sa.topk_mask(
                        sa.indexer_scores(q_idx, view, w_idx), valid,
                        self.sparse_topk,
                    )
                return sa.masked_attention(
                    q,
                    unfold_heads(gather_pages(kp.value, table), head_dim),
                    unfold_heads(gather_pages(vp.value, table), head_dim),
                    positions,
                    allowed,
                )

            if windowed:  # one static view: nothing to choose
                paged_out = self._window_view_attention(
                    q, kp.value, vp.value, page_table, rel
                )
            else:
                pages_needed = jnp.max(positions) // self.page_size + 1
                paged_out = lax.switch(
                    sum(
                        (pages_needed > w).astype(jnp.int32)
                        for w in widths[:-1]
                    ),
                    [partial(attend, w) for w in widths],
                )
            decode_step = True
        elif mode == "paged_decode":
            # Scatter the new token's K/V. Inactive slots are parked on
            # the reserved trash page 0 by the engine — their writes
            # collide there harmlessly (the page is never gathered by a
            # live slot).
            # (a window layer's table starts at position first_pos)
            rel_pos = decode_pos - first_pos if windowed else decode_pos
            slot_page = jnp.take_along_axis(
                page_table, (rel_pos // self.page_size)[:, None], axis=1
            )[:, 0]
            slot_off = decode_pos % self.page_size

            def fold(x):  # the new token's [B, 1, Hkv, D] -> [B, Hkv*D]
                return x[:, 0].reshape(b, kv_local * head_dim)

            if self.paged_attention_impl not in ("gather", "kernel"):
                raise ValueError(
                    "paged_attention_impl must be 'gather' or 'kernel', "
                    f"got {self.paged_attention_impl!r}"
                )
            use_kernel = self.paged_attention_impl == "kernel" and not sparse
            if use_kernel:
                from cs744_pytorch_distributed_tutorial_tpu.ops.paged_attention import (
                    paged_attention,
                )
            if self.quant_kv_cache:
                from cs744_pytorch_distributed_tutorial_tpu.ops.quant import (
                    paged_decode_attention_quant,
                    quantize_kv,
                )

                kq, ks = quantize_kv(k)
                vq, vs = quantize_kv(v)
                kp.value = kp.value.at[slot_page, slot_off].set(fold(kq))
                vp.value = vp.value.at[slot_page, slot_off].set(fold(vq))
                ksp.value = ksp.value.at[slot_page, slot_off].set(ks[:, 0])
                vsp.value = vsp.value.at[slot_page, slot_off].set(vs[:, 0])
                if use_kernel:
                    # Dequant happens INSIDE the kernel (a page's
                    # scales are fetched beside its rows) — no gather
                    # of any of the four pools.
                    paged_out = paged_attention(
                        q, kp.value, vp.value, page_table, decode_pos,
                        key_scale_pages=ksp.value,
                        value_scale_pages=vsp.value,
                        interpret=self.flash_interpret,
                    )
                else:
                    paged_out = paged_decode_attention_quant(
                        q, kp.value, vp.value, ksp.value, vsp.value,
                        page_table, decode_pos,
                    )
            else:
                from cs744_pytorch_distributed_tutorial_tpu.parallel.ring_attention import (
                    paged_decode_attention,
                )

                kp.value = kp.value.at[slot_page, slot_off].set(fold(k))
                vp.value = vp.value.at[slot_page, slot_off].set(fold(v))
                if sparse:
                    # Score the slot's cached indexer keys, keep the
                    # sparse_topk best positions, and read only those
                    # rows of the K and V pools.
                    from cs744_pytorch_distributed_tutorial_tpu.ops import (
                        sparse_attention as sa,
                    )

                    ip.value = ip.value.at[slot_page, slot_off].set(
                        k_idx_row[:, 0]
                    )
                    view = idx_view(ip.value, page_table)
                    valid = (
                        jnp.arange(view.shape[1])[None, :]
                        <= decode_pos[:, None]
                    )
                    sel, sel_ok = sa.topk_indices(
                        sa.indexer_scores(q_idx, view, w_idx)[:, 0], valid,
                        self.sparse_topk,
                    )
                    rows = (
                        jnp.take_along_axis(
                            page_table, sel // self.page_size, axis=1
                        ) * self.page_size + sel % self.page_size
                    )

                    def take_rows(pool):
                        flat = pool.reshape(-1, kv_local * head_dim)
                        return flat[rows].reshape(
                            b, rows.shape[1], kv_local, head_dim
                        )

                    paged_out = sa.selected_rows_attention(
                        q, take_rows(kp.value), take_rows(vp.value), sel_ok
                    )
                    # what the selection kept and what the indexer
                    # scored, a slot (the engine's counters; a no-op
                    # unless "serve_stats" is asked for)
                    if not self.is_initializing():
                        self.sow(
                            "serve_stats", "selected_tokens", sel_ok.sum(-1)
                        )
                        self.sow(
                            "serve_stats", "scored_tokens", valid.sum(-1)
                        )
                elif use_kernel:
                    # where the layers differ the kernel's calls carry
                    # the layer kind in their name, for the trace to
                    # tell them apart
                    import contextlib

                    with (
                        jax.named_scope(self.attn_scope)
                        if self.attn_scope
                        else contextlib.nullcontext()
                    ):
                        paged_out = paged_attention(
                            q, kp.value, vp.value, page_table, decode_pos,
                            first_pos=first_pos if windowed else None,
                            window=self.window,
                            interpret=self.flash_interpret,
                        )
                elif windowed:  # the reference: the whole view, masked
                    paged_out = self._window_view_attention(
                        q, kp.value, vp.value, page_table, rel_pos[:, None]
                    )
                else:
                    paged_out = paged_decode_attention(
                        q, kp.value, vp.value, page_table, decode_pos
                    )
            decode_step = True

        interpret = (
            self.flash_interpret
            if self.flash_interpret is not None
            else default_flash_interpret()
        )
        # GQA: the CACHE stays at kv heads (decode_attention groups query
        # heads over it — no repeated cache), and the sequence-parallel
        # variants take kv-width K/V directly: ring rotates kv-width
        # blocks (per-hop widen inside), ulysses runs its K/V all_to_alls
        # at kv width when divisible — the H/KV ICI saving. Only the
        # single-device dense/flash paths repeat up front.
        rep = heads_local // kv_local
        sp_kv_native = self.impl in (
            "ring", "ring_flash", "ulysses", "ulysses_flash"
        ) and (self.seq_axis is not None and self.seq_axis_size > 1)
        if not decode_step and not sp_kv_native and not sparse and not windowed:
            from cs744_pytorch_distributed_tutorial_tpu.parallel.ring_attention import (
                repeat_kv,
            )

            k, v = repeat_kv(k, rep), repeat_kv(v, rep)
        if decode_step:
            if mode in ("paged_decode", "paged_prefill"):
                out = paged_out
            elif self.quant_kv_cache:
                from cs744_pytorch_distributed_tutorial_tpu.ops.quant import (
                    decode_attention_quant,
                )

                out = decode_attention_quant(
                    q, ck.value, cv.value, cks.value, cvs.value, decode_pos
                )
            else:
                out = decode_attention(q, ck.value, cv.value, decode_pos)
        elif sparse:
            # The full forward (and the one-shot prefill): causal
            # attention masked to each query's selection.
            from cs744_pytorch_distributed_tutorial_tpu.ops import (
                sparse_attention as sa,
            )

            q_pos = jnp.broadcast_to(jnp.arange(t), (b, t))
            allowed = sa.topk_mask(
                sa.indexer_scores(q_idx, k_idx, w_idx),
                jnp.arange(t)[None, None, :] <= q_pos[:, :, None],
                self.sparse_topk,
            )
            if not self.is_initializing():
                # S_t as a [B, T, T] mask, for who asks for intermediates
                self.sow("intermediates", "selected", allowed)
            out = sa.masked_attention(q, k, v, q_pos, allowed)
        elif windowed:
            # The full forward of a window layer: causal, and no key
            # further back than the window.
            out = self._window_attention(
                q, k, v, jnp.broadcast_to(jnp.arange(t), (b, t))
            )
        elif self.seq_axis is None or self.seq_axis_size == 1:
            if self.impl in ("flash", "ring_flash", "ulysses_flash"):
                from cs744_pytorch_distributed_tutorial_tpu.ops.flash_attention import (
                    flash_attention,
                )

                out = flash_attention(
                    q, k, v, self.causal, interpret=interpret
                )
            else:
                out = dense_attention(q, k, v, causal=self.causal)
        elif self.impl == "ring":
            out = ring_attention(
                q, k, v, self.seq_axis, self.seq_axis_size, causal=self.causal
            )
        elif self.impl == "ring_flash":
            out = ring_flash_attention(
                q, k, v, self.seq_axis, self.seq_axis_size, self.causal,
                interpret,
            )
        elif self.impl in ("ulysses", "ulysses_flash"):
            out = ulysses_attention(
                q, k, v, self.seq_axis, self.seq_axis_size, causal=self.causal,
                inner="flash" if self.impl == "ulysses_flash" else "dense",
                flash_interpret=interpret,
            )
        else:  # dense/flash on a sequence-sharded axis
            raise ValueError(
                f"impl={self.impl!r} cannot run on a sequence-sharded axis "
                "(no communication to see the full sequence); use 'ring', "
                "'ulysses', or 'ulysses_flash', or set seq_axis=None"
            )
        out = out.reshape(b, t, heads_local * head_dim).astype(self.dtype)
        out = proj_cls("attn_out")(
            d_model, use_bias=self.attn_bias, dtype=self.dtype,
            name="attn_out",
        )(out)
        if tp:
            out = reduce_from_tp_region(out, self.tensor_axis)
        return out


class Block(nn.Module):
    num_heads: int
    d_ff: int
    dtype: Any = jnp.float32
    impl: str = "dense"
    seq_axis: str | None = None
    seq_axis_size: int = 1
    tensor_axis: str | None = None
    tensor_axis_size: int = 1
    causal: bool = True
    flash_interpret: bool | None = None
    # MoE FFN (models/moe.py): num_experts > 0 replaces the dense MLP with
    # a routed expert mixture, optionally expert-parallel over expert_axis.
    num_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_num_groups: int = 1
    # token movement: einsum | scatter | dropless (no capacity — ragged
    # grouped matmuls, ops/gmm.py)
    moe_dispatch: str = "scatter"
    moe_gmm_impl: str = "auto"  # dropless backend: auto | ragged | pallas
    expert_axis: str | None = None
    expert_axis_size: int = 1
    max_decode_len: int | None = None
    rope: bool = False
    rope_base: float = 10000.0
    num_kv_heads: int | None = None
    # Residual dropout on the attention and MLP sublayer outputs. Active
    # only when the CALLER passes deterministic=False (and supplies a
    # 'dropout' rng); rate 0.0 is a no-op either way.
    dropout_rate: float = 0.0
    quant_dense: bool = False
    quant_modules: tuple = ("q", "k", "v", "attn_out", "mlp_in", "mlp_gate", "mlp_out", "lm_head")
    # Int8 KV cache (ops/quant.py::quantize_kv): rows stored int8 with a
    # per-(batch, position, head) scale — the long-context decode
    # bandwidth lever, independent of quant_dense.
    quant_kv_cache: bool = False
    # Llama-family block options: norm ("layernorm" default | "rmsnorm")
    # and MLP ("gelu" default | "swiglu": silu(gate(x)) * up(x) with a
    # third column-parallel projection named mlp_gate).
    norm: str = "layernorm"
    mlp: str = "gelu"
    norm_eps: float = 1e-6
    attn_bias: bool = False
    # Paged KV pool geometry for mode="paged_decode" (serve/engine.py).
    page_size: int | None = None
    num_pages: int | None = None
    paged_attention_impl: str = "gather"
    # Attention options passed through (see Attention): a head width
    # apart from d_model / num_heads, per-head q/k RMSNorm, and the
    # learned sparse-attention indexer.
    head_dim: int | None = None
    qk_norm: bool = False
    indexer_heads: int = 0
    indexer_head_dim: int = 64
    sparse_topk: int = 0
    # Expert biases b_in / b_out (models/moe.py::MoEFFN). With
    # mlp="swiglu" the experts are gated as the dense MLP would be.
    moe_bias: bool = True
    # This block's attention window, RoPE scaling and kernel name scope
    # (see Attention): a block's own where the layers differ.
    window: int | None = None
    rope_scaling: RopeScaling | None = None
    attn_scope: str | None = None

    @nn.compact
    def __call__(
        self,
        x: jnp.ndarray,
        deterministic: bool = True,
        *,
        mode: str = "train",
        decode_pos: jnp.ndarray | None = None,
        page_table: jnp.ndarray | None = None,
        first_pos: jnp.ndarray | None = None,
    ) -> jnp.ndarray:
        # ``deterministic`` is positional (arg index 2 counting self) so
        # the remat wrapper can declare it static — as a kw-only arg it
        # would be traced and TracerBoolConversionError on the branch.
        tp = self.tensor_axis is not None and self.tensor_axis_size > 1
        # The MoE path never shards d_ff over the tensor axis (experts
        # compute replicated), so the divisibility constraint applies to
        # the dense FFN only.
        if tp and self.num_experts == 0 and self.d_ff % self.tensor_axis_size:
            raise ValueError(
                f"d_ff {self.d_ff} not divisible by tensor axis "
                f"{self.tensor_axis_size}"
            )
        d_ff_local = self.d_ff // self.tensor_axis_size if tp else self.d_ff

        if self.mlp not in MLP_IMPLS:
            raise ValueError(
                f"unknown mlp {self.mlp!r}; choose from {MLP_IMPLS}"
            )
        if (
            self.num_experts > 0 and self.mlp == "swiglu"
            and self.moe_dispatch != "dropless"
        ):
            # The MoE branch replaces the dense MLP entirely, and only
            # the dropless path has gated experts — on the capacity
            # paths a swiglu request would be silently ignored.
            raise ValueError(
                f"mlp={self.mlp!r} does not compose with MoE "
                f"(num_experts={self.num_experts}) under "
                f"moe_dispatch={self.moe_dispatch!r}: gated experts run "
                "on moe_dispatch='dropless'; drop --mlp swiglu or pick it"
            )
        drop = partial(
            nn.Dropout, rate=self.dropout_rate, deterministic=deterministic
        )
        norm = partial(_norm_cls(self.norm, self.norm_eps), dtype=self.dtype)
        h = norm(name="ln1")(x)
        attn_out = Attention(
            num_heads=self.num_heads,
            dtype=self.dtype,
            impl=self.impl,
            seq_axis=self.seq_axis,
            seq_axis_size=self.seq_axis_size,
            tensor_axis=self.tensor_axis,
            tensor_axis_size=self.tensor_axis_size,
            causal=self.causal,
            flash_interpret=self.flash_interpret,
            max_decode_len=self.max_decode_len,
            rope=self.rope,
            rope_base=self.rope_base,
            num_kv_heads=self.num_kv_heads,
            quant_dense=self.quant_dense,
            quant_modules=self.quant_modules,
            quant_kv_cache=self.quant_kv_cache,
            attn_bias=self.attn_bias,
            page_size=self.page_size,
            num_pages=self.num_pages,
            paged_attention_impl=self.paged_attention_impl,
            head_dim=self.head_dim,
            qk_norm=self.qk_norm,
            qk_norm_eps=self.norm_eps,
            indexer_heads=self.indexer_heads,
            indexer_head_dim=self.indexer_head_dim,
            sparse_topk=self.sparse_topk,
            window=self.window,
            rope_scaling=self.rope_scaling,
            attn_scope=self.attn_scope,
            name="attn",
        )(
            h, mode=mode, decode_pos=decode_pos, page_table=page_table,
            first_pos=first_pos,
        )
        if self.dropout_rate > 0.0:
            attn_out = drop(name="attn_drop")(attn_out)
        x = x + attn_out
        h = norm(name="ln2")(x)
        if self.num_experts > 0:
            from cs744_pytorch_distributed_tutorial_tpu.models.moe import MoEFFN

            # Experts are NOT tensor-sharded: with a tensor axis in the
            # mesh they compute replicated (identical activations in,
            # replicated expert params), which keeps the EP all-to-all a
            # pure expert_axis collective.
            y = MoEFFN(
                num_experts=self.num_experts,
                d_ff=self.d_ff,
                top_k=self.moe_top_k,
                capacity_factor=self.moe_capacity_factor,
                num_groups=self.moe_num_groups,
                dispatch_impl=self.moe_dispatch,
                gmm_impl=self.moe_gmm_impl,
                gmm_interpret=self.flash_interpret,
                dtype=self.dtype,
                expert_axis=self.expert_axis,
                expert_axis_size=self.expert_axis_size,
                gated=self.mlp == "swiglu",
                use_bias=self.moe_bias,
                name="moe",
            )(h)
            return x + y
        if tp:
            h = copy_to_tp_region(h, self.tensor_axis)
        # Column-parallel in, row-parallel out; the out bias is a separate
        # parameter applied AFTER the tp psum (a row-parallel Dense's own
        # bias would be summed tensor_axis_size times).
        up = _dense_cls(self.quant_dense and "mlp_in" in self.quant_modules)(
            d_ff_local, dtype=self.dtype, name="mlp_in"
        )(h)
        if self.mlp == "swiglu":
            # silu(gate) * up — the gate is a third column-parallel
            # projection, so TP sharding splits all three the same way.
            gate = _dense_cls(
                self.quant_dense and "mlp_gate" in self.quant_modules
            )(d_ff_local, use_bias=False, dtype=self.dtype, name="mlp_gate")(h)
            h = nn.silu(gate) * up
        else:
            h = nn.gelu(up)
        h = _dense_cls(self.quant_dense and "mlp_out" in self.quant_modules)(
            x.shape[-1], use_bias=False, dtype=self.dtype, name="mlp_out"
        )(h)
        if self.dropout_rate > 0.0:
            h = drop(name="mlp_drop")(h)
        if tp:
            h = reduce_from_tp_region(h, self.tensor_axis)
        bias = self.param(
            "mlp_out_bias", nn.initializers.zeros_init(), (x.shape[-1],)
        )
        return x + h + bias.astype(self.dtype)


class TransformerLM(nn.Module):
    """GPT-style causal LM over token ids.

    ``__call__(tokens [B, T_local]) -> logits [B, T_local, vocab]``
    (float32 logits for a full-precision softmax, as elsewhere in the
    model zoo). Works both as a plain model and inside ``shard_map`` with
    the sequence dimension sharded (set ``seq_axis``/``seq_axis_size``).
    """

    vocab_size: int = 1024
    num_layers: int = 4
    num_heads: int = 8
    d_model: int = 256
    d_ff: int = 1024
    max_seq_len: int = 2048
    dtype: Any = jnp.float32
    attention_impl: str = "ring"
    seq_axis: str | None = None
    seq_axis_size: int = 1
    tensor_axis: str | None = None
    tensor_axis_size: int = 1
    causal: bool = True
    flash_interpret: bool | None = None
    num_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_num_groups: int = 1
    # token movement: einsum | scatter | dropless (ops/gmm.py)
    moe_dispatch: str = "scatter"
    moe_gmm_impl: str = "auto"
    expert_axis: str | None = None
    expert_axis_size: int = 1
    # Rematerialization: recompute each block's activations during the
    # backward pass instead of storing them (jax.checkpoint via nn.remat)
    # — the HBM-for-FLOPs trade that makes long sequences fit. Numerics
    # are identical; only the autodiff schedule changes. remat_policy
    # "dots" keeps matmul outputs (see resolve_remat_policy).
    remat: bool = False
    remat_policy: str = "none"
    # Weight tying: reuse the token embedding as the output projection
    # (logits = x @ E^T) instead of a separate lm_head — the standard
    # vocab-parameter halving; gradients flow to the embedding from both
    # uses.
    tie_embeddings: bool = False
    # Rotary position embeddings (use_rope=True): q/k rotate by their
    # GLOBAL positions inside attention and the learned absolute
    # pos_embed table is dropped — the modern long-context default.
    use_rope: bool = False
    rope_base: float = 10000.0
    # Grouped-query attention: KV head count (None = num_heads). The KV
    # cache shrinks by num_heads/num_kv_heads.
    num_kv_heads: int | None = None
    # Residual dropout on each block's attention/MLP sublayer outputs
    # (Block.dropout_rate). Active only when the caller passes
    # deterministic=False and supplies a 'dropout' rng. Masks must be
    # IDENTICAL across a tensor-parallel axis (mlp dropout applies to
    # partial sums before the row-parallel psum), so the rng the trainer
    # folds must not vary along it — train/lm.py derives it from
    # (step, data index, seq index) only.
    dropout_rate: float = 0.0
    # Weight-only int8 Dense kernels (ops/quant.py) — the decode
    # bandwidth lever. Pair with params from ``quantize_lm_params``
    # (same ``modules``); see ``LMTrainer.quantized_decode_model``.
    # quant_modules narrows the set (QUANT_HEAD_ONLY is the measured
    # decode default — per-call dispatch cost vs bytes saved).
    quant_dense: bool = False
    quant_modules: tuple = ("q", "k", "v", "attn_out", "mlp_in", "mlp_gate", "mlp_out", "lm_head")
    # Int8 KV cache (ops/quant.py::quantize_kv): rows stored int8 with a
    # per-(batch, position, head) scale — the long-context decode
    # bandwidth lever, independent of quant_dense.
    quant_kv_cache: bool = False
    # Llama-family options (see Block.norm / Block.mlp): rmsnorm applies
    # to the final norm too; swiglu adds the column-parallel mlp_gate.
    norm: str = "layernorm"
    mlp: str = "gelu"
    norm_eps: float = 1e-6
    # q/k/v/attn_out projection biases (GPT-2 checkpoints; no tensor axis).
    attn_bias: bool = False
    # Layer stacking: run the homogeneous blocks as ONE block scanned
    # over a leading layer dimension (``nn.scan``) instead of unrolling
    # ``num_layers`` copies into the traced program. Numerics are
    # identical (parity pinned in tests/test_scan_layers.py); what
    # changes is PROGRAM SIZE — the XLA input is one block body + a loop,
    # not L inlined bodies, the option for when a deep unrolled program
    # stops compiling. Params (and the
    # decode cache) carry a leading ``[num_layers]`` axis under module
    # name "blocks"; convert to/from the unrolled layout with
    # ``stack_block_params`` / ``unstack_block_params``. Composes with
    # remat (the scanned body is checkpointed per layer — the classic
    # scan-over-remat memory profile). MoE is excluded: stacking would
    # silently change the sown aux-loss reduction, and routed blocks are
    # the pipeline engine's domain.
    scan_layers: bool = False
    # Paged KV pool geometry for mode="paged_decode": per-layer pools of
    # ``num_pages`` pages x ``page_size`` tokens in the "pages" variable
    # collection, indexed by the ``page_table`` call kwarg
    # (serve/engine.py owns allocation; docs/serving.md).
    page_size: int | None = None
    num_pages: int | None = None
    # "gather" (reference, bitwise vs dense cache) or "kernel" (Pallas
    # live-pages-only decode — ops/paged_attention.py; see Attention).
    paged_attention_impl: str = "gather"
    # Attention options passed through (see Attention): a head width
    # apart from d_model / num_heads, per-head q/k RMSNorm, and the
    # learned sparse-attention indexer.
    head_dim: int | None = None
    qk_norm: bool = False
    indexer_heads: int = 0
    indexer_head_dim: int = 64
    sparse_topk: int = 0
    # Expert biases b_in / b_out (models/moe.py::MoEFFN). With
    # mlp="swiglu" the experts are gated as the dense MLP would be.
    moe_bias: bool = True
    # YaRN on the RoPE of the full-attention layers (every layer, where
    # ``layer_types`` is None); see ``rope_inv_freq``.
    rope_scaling: RopeScaling | None = None
    # Layers that differ by kind: one entry a layer, "full_attention" or
    # "sliding_attention" (the published ``layer_types``). A sliding
    # layer sees ``window`` keys (Attention.window), rotates by plain
    # RoPE over ``window_rope_base`` (None = ``rope_base``) and, served,
    # keeps its K and V in a page group of its own: pools of
    # ``window_num_pages`` pages under ``window_page_table`` /
    # ``window_first_pos`` (serve/engine.py sizes and fills them). None
    # = every layer full, the model as it always was.
    layer_types: tuple | None = None
    window: int | None = None
    window_rope_base: float | None = None
    window_num_pages: int | None = None
    # Latent attention (models/latent.py::LatentDims: the low-rank query
    # and key-value paths' sizes) in the two-attention shortcut-MoE layer
    # (``ShortcutMoEBlock``): two (latent attention, dense SwiGLU MLP of
    # ``dense_d_ff``) pairs and one MoE of ``d_ff``-wide experts beside
    # them, every layer. Served, a sublayer keeps ONE pool of latents
    # (``latent_pages``) where another layer keeps keys and values.
    # None = the model as it always was, and models/latent.py is never
    # imported. ``latent_block="plain"`` builds the plain pre-norm block
    # (``LatentBlock``) instead: one latent attention and one FFN a
    # layer, one pool a layer; the FFN of the first ``dense_layers``
    # layers is a dense SwiGLU MLP of ``dense_d_ff``, the others' the MoE.
    latent: Any = None
    dense_d_ff: int | None = None
    latent_block: str = "shortcut"
    dense_layers: int = 0
    # MoEFFN's share and router options (models/moe.py): the ids of the
    # routed experts held here (None = all), zero-compute experts behind
    # the routed ones, top-k weights renormalised or not and their
    # scale, a bias on the choice alone, group-limited choice, shared
    # experts' width.
    moe_held_experts: tuple | None = None
    moe_zero_experts: int = 0
    moe_renormalize: bool = True
    moe_routed_scale: float = 1.0
    moe_choice_bias: bool = False
    moe_n_group: int = 1
    moe_topk_group: int = 1
    moe_shared_d_ff: int = 0
    # Mixers that differ by layer between lightning attention
    # (models/lightning.py) and block-sparse attention over compressed
    # keys (models/block_sparse.py): ``layer_types`` names each layer
    # "lightning_attention" or "block_sparse_attention" (HYBRID_KINDS),
    # every layer the pre-norm block of models/hybrid.py. A lightning
    # layer's heads decay by ``exp(-rate)``, its rates the layer's entry
    # of ``lightning_rates`` (one a layer, None on the others); the
    # block-sparse layers select by ``block_sparse``
    # (ops/block_sparse.py::BlockSparse). Served, a lightning layer keeps
    # one state row a slot (``slot_state_layers``) and a block-sparse
    # layer its K and V pools and a compressed key a page. MiniCPM's muP
    # scalars: the embedding times ``embed_scale``, each residual times
    # ``residual_scale``, the final norm's output times ``logit_scale``;
    # at 1.0 none is applied.
    lightning_rates: tuple | None = None
    block_sparse: Any = None
    embed_scale: float = 1.0
    residual_scale: float = 1.0
    logit_scale: float = 1.0

    def window_layers(self) -> int:
        """How many layers are sliding-window layers."""
        return sum(k == "sliding_attention" for k in self.layer_types or ())

    def slot_state_layers(self) -> int:
        """How many layers keep a recurrent state a slot (lightning)."""
        return sum(k == "lightning_attention" for k in self.layer_types or ())

    def _hybrid(self) -> bool:
        return bool(set(self.layer_types or ()) & set(HYBRID_KINDS))

    def _check_hybrid(self, mode: str) -> None:
        """A model with lightning or block-sparse layers: raise, with its
        reason, for each combination that is not built."""

        def no(what: str, why: str):
            raise ValueError(
                f"lightning and block-sparse layers with {what} are not "
                f"built: {why}"
            )

        kinds = set(self.layer_types)
        if len(self.layer_types) != self.num_layers or not kinds <= set(HYBRID_KINDS):
            no(
                f"layer_types {self.layer_types!r}",
                f"name {self.num_layers} layers, each one of {HYBRID_KINDS}",
            )
        if mode in ("prefill", "decode"):
            no(
                f"mode={mode!r}",
                "the dense cache holds keys and values of every position, "
                "where a lightning layer keeps a state and a block-sparse "
                "layer compressed keys; serve through the paged pools and "
                "the slots' state rows (ServeConfig.prefill_chunk)",
            )
        if self.quant_kv_cache or self.quant_dense:
            no("int8 (quant_kv_cache, quant_dense)", "pools, state and kernels are float")
        if (self.tensor_axis is not None and self.tensor_axis_size > 1) or (
            self.seq_axis is not None and self.seq_axis_size > 1
        ):
            no("a tensor or sequence axis", "the state rows and the selection run on one device")
        if self.scan_layers:
            no("scan_layers", "the layers differ by kind and are built unrolled")
        if self.num_experts or self.latent is not None or self.window is not None or self.indexer_heads:
            no(
                "experts, latent attention, a window or an indexer",
                "the layer is a lightning or block-sparse mixer and a dense SwiGLU",
            )
        if "lightning_attention" in kinds and (
            self.lightning_rates is None or len(self.lightning_rates) != self.num_layers
            or any(
                r is None for r, k in zip(self.lightning_rates, self.layer_types)
                if k == "lightning_attention"
            )
        ):
            no("no lightning_rates", "each lightning layer needs its heads' decay")
        if "block_sparse_attention" in kinds and self.block_sparse is None:
            no("no block_sparse", "the block-sparse layers need the selection's parameters")
        if not self.use_rope or self.norm != "rmsnorm" or self.mlp != "swiglu":
            no(
                "learned positions, LayerNorm or a GELU MLP",
                "use_rope=True (the lightning layers rotate, the block-sparse "
                "ones use no position), norm='rmsnorm', mlp='swiglu'",
            )

    def _check_latent(self, mode: str) -> None:
        """A model with latent attention: raise, with its reason, for
        each combination that is not built."""

        def no(what: str, why: str):
            raise ValueError(f"latent attention with {what} is not built: {why}")

        if self.attention_impl != "dense":
            no(
                f"attention_impl={self.attention_impl!r}",
                "the flash kernels take one head width for q, k and v (here "
                "192 / 192 / 128) and the ring and ulysses variants shard "
                "keys a head; it trains with attention_impl='dense'",
            )
        if mode in ("prefill", "decode"):
            no(
                f"mode={mode!r}",
                "the dense cache holds keys and values a head, which the "
                "latent exists to avoid; serve through the paged latent "
                "pools (ServeConfig.prefill_chunk)",
            )
        if self.quant_kv_cache:
            no("quant_kv_cache", "the latent pool is float; no int8 rows")
        if self.quant_dense:
            no("quant_dense", "the low-rank projections are float kernels")
        if (self.tensor_axis is not None and self.tensor_axis_size > 1) or (
            self.seq_axis is not None and self.seq_axis_size > 1
        ):
            no(
                "a tensor or sequence axis",
                "every head reads the ONE latent row of a token, so heads "
                "do not shard the pool; it runs on one device",
            )
        if self.scan_layers:
            no("scan_layers", "the latent layers are built unrolled")
        if self.layer_types is not None or self.window is not None:
            no("a window (layer_types)", "the latent walk has no window")
        if self.indexer_heads:
            no("the sparse-attention indexer", "no indexer over latents")
        if self.expert_axis is not None:
            no(
                "expert_axis",
                "the latent layers' MoE is dropless; a chip's share is "
                "moe_held_experts, without the exchange",
            )
        if self.latent_block not in ("shortcut", "plain"):
            no(
                f"latent_block={self.latent_block!r}",
                "the layers built are 'shortcut' (ShortcutMoEBlock) and "
                "'plain' (LatentBlock)",
            )
        if self.latent_block == "shortcut" and (
            self.num_experts < 1 or self.dense_d_ff is None or self.dense_layers
        ):
            no(
                "no experts, no dense_d_ff or dense_layers",
                "the layer is two dense MLPs (dense_d_ff) and one MoE "
                "(num_experts of d_ff), every layer",
            )
        if self.latent_block == "plain" and not (
            0 <= self.dense_layers <= self.num_layers
            and (self.dense_layers == 0 or self.dense_d_ff is not None)
            and (self.dense_layers == self.num_layers or self.num_experts >= 1)
        ):
            no(
                f"dense_layers={self.dense_layers} of {self.num_layers}, "
                f"dense_d_ff={self.dense_d_ff}, num_experts={self.num_experts}",
                "the plain block's FFN is a dense MLP of dense_d_ff on the "
                "first dense_layers layers and the MoE (num_experts of d_ff) "
                "on the others",
            )
        if not self.use_rope or self.tie_embeddings or self.norm != "rmsnorm":
            no(
                "learned positions, tied embeddings or LayerNorm",
                "the layer rotates its rope dimensions and normalises by "
                "RMSNorm; use_rope=True, norm='rmsnorm', untied head",
            )

    @nn.compact
    def __call__(
        self,
        tokens: jnp.ndarray,
        *,
        mode: str = "train",
        decode_pos: jnp.ndarray | None = None,
        page_table: jnp.ndarray | None = None,
        deterministic: bool = True,
        logits_at: jnp.ndarray | None = None,
        window_page_table: jnp.ndarray | None = None,
        window_first_pos: jnp.ndarray | None = None,
        slot_rows: jnp.ndarray | None = None,
        slot_live: jnp.ndarray | None = None,
    ) -> jnp.ndarray:
        """``logits_at`` ([B] indices into this call's tokens) asks for
        the logits of one position a row only, ``[B, 1, vocab]``: the
        final norm and the head then run on that row alone (a prefill
        chunk needs one token's logits, not a chunk's). A model with
        lightning layers (``slot_state_layers``) is told, by a prefill
        chunk, the state row of each batch row (``slot_rows`` [B]) and, by
        a decode step, which slots advance (``slot_live`` [B])."""
        b, t_local = tokens.shape
        hybrid = self._hybrid()
        if hybrid:
            self._check_hybrid(mode)
        elif (
            self.lightning_rates is not None or self.block_sparse is not None
            or self.residual_scale != 1.0
        ):
            raise ValueError(
                "lightning_rates, block_sparse and residual_scale belong to "
                "the lightning and block-sparse layers (layer_types of "
                f"{HYBRID_KINDS})"
            )
        if self.latent is not None:
            self._check_latent(mode)
        elif (
            self.moe_held_experts is not None or self.moe_zero_experts
            or not self.moe_renormalize or self.moe_routed_scale != 1.0
            or self.moe_choice_bias or self.moe_n_group != 1
            or self.moe_topk_group != 1 or self.moe_shared_d_ff
        ):
            raise ValueError(
                "moe_held_experts, moe_zero_experts, moe_renormalize, "
                "moe_routed_scale, moe_choice_bias, moe_n_group, "
                "moe_topk_group and moe_shared_d_ff are built in the "
                "shortcut-MoE layer and the plain latent block (latent "
                "set); Block's MoE takes none"
            )
        if self.layer_types is not None and not hybrid:
            kinds = set(self.layer_types)
            if len(self.layer_types) != self.num_layers or not kinds <= {
                "full_attention", "sliding_attention"
            }:
                raise ValueError(
                    f"layer_types must name {self.num_layers} layers as "
                    "'full_attention' or 'sliding_attention', got "
                    f"{self.layer_types!r}"
                )
            if "sliding_attention" in kinds and self.window is None:
                raise ValueError("sliding_attention layers need a window")
            if "sliding_attention" in kinds and (
                (self.seq_axis is not None and self.seq_axis_size > 1)
                or (self.tensor_axis is not None and self.tensor_axis_size > 1)
            ):
                raise ValueError(
                    "a model with window layers runs on one device: no "
                    "tensor or sequence axis (the window's pages and mask "
                    "are not sharded)"
                )
            if self.scan_layers:
                raise ValueError(
                    "scan_layers runs ONE block body over stacked "
                    "parameters; blocks that differ by layer_types do not "
                    "stack. Run them unrolled"
                )
        tok_embed = nn.Embed(
            self.vocab_size, self.d_model, dtype=self.dtype, name="tok_embed"
        )
        x = tok_embed(tokens)
        if self.embed_scale != 1.0:
            x = x * jnp.asarray(self.embed_scale, x.dtype)
        # Global positions: a sequence-sharded block starts at the
        # device's offset along the seq axis, not at 0; a cached decode
        # step sits at its decode position.
        if mode in ("decode", "paged_decode", "paged_prefill"):
            if decode_pos is None:
                raise ValueError(f"mode={mode!r} needs decode_pos")
            offset = decode_pos
        else:
            offset = (
                lax.axis_index(self.seq_axis) * t_local
                if self.seq_axis is not None and self.seq_axis_size > 1
                else 0
            )
        if not self.use_rope:
            if jnp.ndim(offset):
                # Per-slot positions ([B] decode_pos, paged decode): an
                # explicit [B, t] table — the bare (B,)+(t,) broadcast
                # would collapse to (B,) at t=1 and then mis-broadcast
                # against x [B, 1, D].
                positions = jnp.asarray(offset)[:, None] + jnp.arange(t_local)
            else:
                positions = offset + jnp.arange(t_local)
            x = x + nn.Embed(
                self.max_seq_len, self.d_model, dtype=self.dtype,
                name="pos_embed",
            )(positions)
        # Remat applies to the training path only: decoding has no
        # backward pass whose activation memory it could save.
        if self.remat and mode == "train":
            block_cls = nn.remat(
                Block,
                policy=resolve_remat_policy(self.remat_policy),
                static_argnums=(2,),  # deterministic (self=0, x=1)
            )
        else:
            block_cls = Block
        block_kw = dict(
            num_heads=self.num_heads,
            d_ff=self.d_ff,
            dtype=self.dtype,
            impl=self.attention_impl,
            seq_axis=self.seq_axis,
            seq_axis_size=self.seq_axis_size,
            tensor_axis=self.tensor_axis,
            tensor_axis_size=self.tensor_axis_size,
            causal=self.causal,
            flash_interpret=self.flash_interpret,
            num_experts=self.num_experts,
            moe_top_k=self.moe_top_k,
            moe_capacity_factor=self.moe_capacity_factor,
            moe_num_groups=self.moe_num_groups,
            moe_dispatch=self.moe_dispatch,
            moe_gmm_impl=self.moe_gmm_impl,
            expert_axis=self.expert_axis,
            expert_axis_size=self.expert_axis_size,
            max_decode_len=self.max_seq_len,
            rope=self.use_rope,
            rope_base=self.rope_base,
            num_kv_heads=self.num_kv_heads,
            dropout_rate=self.dropout_rate,
            quant_dense=self.quant_dense,
            quant_modules=self.quant_modules,
            quant_kv_cache=self.quant_kv_cache,
            norm=self.norm,
            mlp=self.mlp,
            norm_eps=self.norm_eps,
            attn_bias=self.attn_bias,
            page_size=self.page_size,
            num_pages=self.num_pages,
            paged_attention_impl=self.paged_attention_impl,
            head_dim=self.head_dim,
            qk_norm=self.qk_norm,
            indexer_heads=self.indexer_heads,
            indexer_head_dim=self.indexer_head_dim,
            sparse_topk=self.sparse_topk,
            moe_bias=self.moe_bias,
            rope_scaling=self.rope_scaling,
        )
        if self.latent is not None:
            from cs744_pytorch_distributed_tutorial_tpu.models.latent import (
                LatentBlock,
                ShortcutMoEBlock,
            )

            moe = (
                ("num_experts", self.num_experts),
                ("d_ff", self.d_ff),
                ("top_k", self.moe_top_k),
                ("dispatch_impl", "dropless"),
                ("gmm_impl", self.moe_gmm_impl),
                ("gmm_interpret", self.flash_interpret),
                ("gated", True),
                ("use_bias", False),
                ("held_experts", self.moe_held_experts),
                ("zero_experts", self.moe_zero_experts),
                ("renormalize", self.moe_renormalize),
                ("routed_scale", self.moe_routed_scale),
                ("choice_bias", self.moe_choice_bias),
            )
            plain = self.latent_block == "plain"
            for i in range(self.num_layers):
                layer_kw = dict(
                    num_heads=self.num_heads,
                    dims=self.latent,
                    dense_d_ff=self.dense_d_ff,
                    dtype=self.dtype,
                    rope_base=self.rope_base,
                    norm_eps=self.norm_eps,
                    page_size=self.page_size,
                    num_pages=self.num_pages,
                    paged_attention_impl=self.paged_attention_impl,
                    flash_interpret=self.flash_interpret,
                    name=f"block_{i}",
                )
                if not plain:
                    layer = ShortcutMoEBlock(moe=moe, **layer_kw)
                elif i < self.dense_layers:
                    layer = LatentBlock(**layer_kw)
                else:
                    layer = LatentBlock(moe=moe + (
                        ("n_group", self.moe_n_group),
                        ("topk_group", self.moe_topk_group),
                        ("shared_d_ff", self.moe_shared_d_ff),
                    ), **layer_kw)
                x = layer(
                    x, deterministic, mode=mode, decode_pos=decode_pos,
                    page_table=page_table, last_idx=logits_at,
                )
            if mode == "paged_decode" and not self.is_initializing():
                # Latent rows each slot's step attended, over the
                # attention sublayers of every layer (two a shortcut-MoE
                # layer, one a plain block; the engine's counters; a
                # no-op unless "serve_stats" is asked for).
                self.sow(
                    "serve_stats", "latent_tokens_read",
                    (1 if plain else 2) * self.num_layers * (decode_pos + 1),
                )
        elif hybrid:
            from cs744_pytorch_distributed_tutorial_tpu.models.hybrid import (
                HybridBlock,
            )

            head_dim = self.head_dim or self.d_model // self.num_heads
            for i, kind in enumerate(self.layer_types):
                if kind == "lightning_attention":
                    mixer = (
                        ("num_heads", self.num_heads), ("head_dim", head_dim),
                        ("rate", tuple(self.lightning_rates[i])),
                        ("rope_base", self.rope_base),
                    )
                else:
                    mixer = (
                        ("num_heads", self.num_heads),
                        ("num_kv_heads", self.num_kv_heads or self.num_heads),
                        ("head_dim", head_dim), ("sparse", self.block_sparse),
                        ("page_size", self.page_size), ("num_pages", self.num_pages),
                    )
                mixer += (
                    ("norm_eps", self.norm_eps),
                    ("paged_attention_impl", self.paged_attention_impl),
                    ("flash_interpret", self.flash_interpret),
                )
                x = HybridBlock(
                    kind=kind, mixer=mixer, d_ff=self.d_ff, dtype=self.dtype,
                    norm_eps=self.norm_eps, residual_scale=self.residual_scale,
                    name=f"block_{i}",
                )(
                    x, deterministic, mode=mode, decode_pos=decode_pos,
                    page_table=page_table, slot_rows=slot_rows,
                    slot_live=slot_live, last_idx=logits_at,
                )
        elif self.scan_layers:
            if self.num_experts > 0:
                raise ValueError(
                    "scan_layers does not compose with MoE "
                    f"(num_experts={self.num_experts}): stacking would "
                    "change the sown aux-loss reduction (each layer's "
                    "term must be summed, not stacked); run routed "
                    "blocks unrolled or in the pipeline engine"
                )

            # One block, scanned over a leading [num_layers] axis: the
            # carry is the residual stream, params/cache stack per layer
            # (variable_axes=0), and each layer draws its own init and
            # dropout rngs (split_rngs). mode/decode_pos/deterministic
            # ride the closure — they are schedule, not data.
            def body(block, carry):
                if mode == "train":
                    return block(carry, deterministic), None
                return (
                    block(carry, deterministic, mode=mode,
                          decode_pos=decode_pos, page_table=page_table),
                    None,
                )

            x, _ = nn.scan(
                body,
                # "intermediates" rides along (stacked per layer) so
                # capture_intermediates debugging works under the scan;
                # empty unless a capture filter is active. "pages" stacks
                # the per-layer paged KV pools the same way the cache
                # stacks.
                variable_axes={
                    "params": 0, "cache": 0, "intermediates": 0, "pages": 0,
                },
                split_rngs={"params": True, "dropout": True},
                length=self.num_layers,
            )(block_cls(**block_kw, name="blocks"), x)
        else:
            for i in range(self.num_layers):
                kw, table, first = block_kw, page_table, None
                if self.layer_types is not None:
                    if self.layer_types[i] == "sliding_attention":
                        kw = dict(
                            block_kw,
                            window=self.window,
                            rope_scaling=None,
                            rope_base=self.window_rope_base or self.rope_base,
                            num_pages=self.window_num_pages,
                            attn_scope="attn_window",
                        )
                        table, first = window_page_table, window_first_pos
                    else:
                        kw = dict(block_kw, attn_scope="attn_full")
                block = block_cls(**kw, name=f"block_{i}")
                # remat (train-only) rejects non-array kwargs; the
                # defaults ARE train mode, so pass the decode kwargs only
                # off of it. ``deterministic`` rides positionally so the
                # remat static_argnums above keeps it a Python bool.
                if mode == "train":
                    x = block(x, deterministic)
                else:
                    # Forward ``deterministic`` here too so the unrolled
                    # and scanned paths agree in every mode (layout
                    # parity is the scan_layers contract).
                    x = block(
                        x, deterministic, mode=mode, decode_pos=decode_pos,
                        page_table=table, first_pos=first,
                    )
            if (
                mode == "paged_decode" and self.window_layers()
                and not self.is_initializing()
            ):
                # Keys each slot's step attended, summed over the layers
                # of a kind (the engine's counters; a no-op unless
                # "serve_stats" is asked for).
                n_window = self.window_layers()
                self.sow(
                    "serve_stats", "full_tokens_read",
                    (self.num_layers - n_window) * (decode_pos + 1),
                )
                self.sow(
                    "serve_stats", "window_tokens_read",
                    n_window * jnp.minimum(decode_pos + 1, self.window),
                )
        if logits_at is not None:
            x = jnp.take_along_axis(x, logits_at[:, None, None], axis=1)
        x = _norm_cls(self.norm, self.norm_eps)(dtype=self.dtype, name="ln_f")(x)
        if self.logit_scale != 1.0:
            x = x * jnp.asarray(self.logit_scale, x.dtype)
        if self.tie_embeddings:
            # The attend path reuses the (unquantized) embedding table —
            # quant_dense deliberately leaves it float.
            logits = tok_embed.attend(x)
        else:
            logits = _dense_cls(
                self.quant_dense and "lm_head" in self.quant_modules
            )(
                self.vocab_size, use_bias=False, dtype=self.dtype, name="lm_head"
            )(x)
        return logits.astype(jnp.float32)


def transformer_lm(**kw: Any) -> TransformerLM:
    return TransformerLM(**kw)


def stack_block_params(params, num_layers: int | None = None):
    """Unrolled param layout (``block_0`` .. ``block_{L-1}``) -> the
    ``scan_layers=True`` layout (one ``blocks`` subtree whose leaves
    carry a leading ``[L]`` layer axis). The non-block leaves (embeddings,
    ``ln_f``, ``lm_head``) pass through untouched. Inverse of
    ``unstack_block_params``; parity of the two layouts is pinned in
    tests/test_scan_layers.py. ``num_layers`` defaults to the count in
    the tree; an explicit mismatch raises rather than silently dropping
    layers."""
    present = sorted(
        int(k[len("block_"):]) for k in params if k.startswith("block_")
    )
    if present != list(range(len(present))):
        raise ValueError(f"non-contiguous block indices in params: {present}")
    if num_layers is None:
        num_layers = len(present)
    elif num_layers != len(present):
        raise ValueError(
            f"num_layers={num_layers} but params carry {len(present)} "
            "block_* subtrees — stacking would silently drop layers"
        )
    blocks = [params[f"block_{i}"] for i in range(num_layers)]
    rest = {k: v for k, v in params.items() if not k.startswith("block_")}
    rest["blocks"] = jax.tree.map(lambda *ls: jnp.stack(ls), *blocks)
    return rest


def unstack_block_params(params):
    """``scan_layers`` param layout -> the unrolled ``block_i`` layout
    (e.g. for HF/torch export, or decoding with an unrolled clone)."""
    rest = {k: v for k, v in params.items() if k != "blocks"}
    stacked = params["blocks"]
    n = jax.tree_util.tree_leaves(stacked)[0].shape[0]
    for i in range(n):
        rest[f"block_{i}"] = jax.tree.map(lambda leaf: leaf[i], stacked)
    return rest


def lm_param_specs(params, tensor_axis: str | None, expert_axis: str | None = None):
    """PartitionSpec tree for a ``TransformerLM`` param tree.

    Maps each leaf to how its GLOBAL array splits over the mesh (the
    shard_map in/out spec): column-parallel kernels (q/k/v, ``mlp_in``)
    shard the output-feature dim over the tensor axis, row-parallel
    kernels (``attn_out``, ``mlp_out``) the input-feature dim, ``mlp_in``'s
    bias the feature dim; MoE expert params (``moe/{w,b}_{in,out}``) shard
    their leading expert dim over ``expert_axis`` (the router stays
    replicated); embeddings, layernorms, ``lm_head`` and the post-psum
    ``mlp_out_bias`` stay replicated. With both axes ``None`` everything
    is replicated.

    The ``scan_layers`` layout (one ``blocks`` subtree, leaves with a
    leading ``[L]`` layer axis) gets the same per-module specs shifted
    one dim right — the layer axis itself stays unsharded (it is the
    scan/carry dimension; FSDP-style layer sharding is ``parallel/zero.py``'s
    job, not the tensor axis's).
    """
    from jax.sharding import PartitionSpec as P

    t = tensor_axis

    def spec(path, leaf):
        names = [getattr(k, "key", str(k)) for k in path]
        module = names[-2] if len(names) >= 2 else ""
        scanned = bool(names) and names[0] == "blocks"

        def shift(p):
            # Prepend the unsharded layer dim for stacked leaves.
            return P(None, *p) if scanned and tuple(p) else p

        if module == "moe" and expert_axis is not None:
            return shift(P(expert_axis))
        if t is None:
            return P()
        leaf_name = names[-1]
        if module in ("q", "k", "v", "mlp_gate"):
            return shift(P(None, t))
        if module in ("attn_out", "mlp_out"):
            return shift(P(t, None))
        if module == "mlp_in":
            return shift(P(None, t) if leaf_name == "kernel" else P(t))
        return P()

    return jax.tree_util.tree_map_with_path(spec, params)
