"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

The reference has no attention models (its only model is conv VGG-11,
``master/part1/model.py:30-46``) — but its ``part2a_extra`` p2p layer
(``master/part2a/part2a_extra.py:41-58``) exercises exactly the
neighbor-exchange communication pattern that long-context training scales
with. This module builds sequence parallelism as a first-class capability
on that primitive:

- ``ring_attention``: blockwise attention with online (flash-style)
  softmax accumulation; K/V blocks rotate around the mesh axis via
  ``lax.ppermute`` — one ICI neighbor hop per step, overlapping each
  hop's transfer with the previous block's compute. Memory per device is
  O(T_local^2-free): only the running (m, l, o) accumulators and one K/V
  block are resident. This is the Ring Attention construction (Liu et
  al.) expressed in pure XLA collectives.
- ``ulysses_attention``: the all-to-all alternative (DeepSpeed-Ulysses):
  one ``all_to_all`` re-shards sequence -> heads, full attention runs
  locally per head group, a second ``all_to_all`` re-shards back. Two
  collectives total, better for moderate sequence lengths; requires
  ``num_heads % axis_size == 0``.

Both are meant to be called inside ``jax.shard_map``-ped jitted code with
the sequence dimension sharded along ``axis_name``, and both accumulate
softmax in float32 regardless of input dtype (bfloat16 Q/K/V on the MXU,
full-precision normalizer — the TPU-correct numerics).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

# Additive mask value: large-negative instead of -inf so exp() underflows
# to exactly 0.0 without generating NaNs in fully-masked rows.
_MASK = -1e30


def dense_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    q_offset: int | jax.Array = 0,
    k_offset: int | jax.Array = 0,
) -> jax.Array:
    """Plain softmax attention on [B, T, H, D] blocks (float32 softmax).

    The single-device reference semantics that the parallel variants must
    reproduce; offsets give Q/K their *global* sequence positions so a
    causal mask stays correct on local blocks of a sharded sequence.
    """
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if causal:
        q_pos = q_offset + jnp.arange(q.shape[1])
        k_pos = k_offset + jnp.arange(k.shape[1])
        scores = jnp.where(q_pos[:, None] >= k_pos[None, :], scores, _MASK)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum(
        "bhqk,bkhd->bqhd", probs.astype(v.dtype), v,
    )


def decode_attention(
    q: jax.Array,
    cached_k: jax.Array,
    cached_v: jax.Array,
    pos: jax.Array,
) -> jax.Array:
    """Autoregressive decode step(s) against a KV cache.

    ``q`` is [B, T, Hq, D] — T == 1 is the classic single-token decode
    step; T > 1 is a CHUNK whose row i sits at global position
    ``pos + i`` (chunked prefill, and the verification pass of
    speculative decoding — ``infer/speculative.py``). ``cached_k``/
    ``cached_v`` are [B, L, Hkv, D] caches whose entries at positions
    beyond each row's own position are unwritten garbage or future
    tokens — masked per row (``k_pos <= pos + i``), so softmax weights
    for them are exactly 0.0 and each row matches ``dense_attention``
    over its visible prefix. ``Hq`` may be a multiple of ``Hkv``
    (grouped-query attention): query heads group over the shared KV
    heads directly in the einsums — the cache is never materialized at
    query-head width, which is GQA's decode-bandwidth saving. Same
    numerics discipline as the other variants: float32 scores/softmax,
    PV matmul in the cache dtype.

    ``pos`` may also be a ``[B]`` vector (the continuous-batching serve
    path, ``serve/``): row ``b``'s chunk then sits at global positions
    ``pos[b]..pos[b]+t-1`` and each row masks against its OWN visible
    prefix — slots at different depths share one fixed-shape decode step.
    """
    b, t, hq, d = q.shape
    hkv = cached_k.shape[2]
    if hq % hkv:
        raise ValueError(f"query heads {hq} not a multiple of kv heads {hkv}")
    group = hq // hkv
    qg = q.reshape(b, t, hkv, group, d)
    scale = d**-0.5
    scores = jnp.einsum(
        "bqhgd,bkhd->bhgqk", qg, cached_k, preferred_element_type=jnp.float32
    ) * scale
    scores = jnp.where(
        decode_mask(cached_k.shape[1], t, pos), scores, _MASK
    )
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bhgqk,bkhd->bqhgd", probs.astype(cached_v.dtype), cached_v,
    )
    return out.reshape(b, t, hq, d)


def decode_mask(cache_len: int, t: int, pos: jax.Array) -> jax.Array:
    """Visibility mask for decode steps, broadcastable against
    ``[B, Hkv, group, t, L]`` scores: key position ``k`` is visible to
    query row ``i`` iff ``k <= pos + i``. Scalar ``pos`` gives the
    classic shared-position mask ``[1, 1, 1, t, L]``; a ``[B]`` vector
    gives per-row masks ``[B, 1, 1, t, L]`` (per-slot depths in the
    serving engine)."""
    k_pos = jnp.arange(cache_len)
    pos = jnp.asarray(pos)
    if pos.ndim == 0:
        q_pos = pos + jnp.arange(t)
        return (k_pos[None, :] <= q_pos[:, None])[None, None, None]  # [t, L]
    if pos.ndim != 1:
        raise ValueError(f"pos must be a scalar or [B] vector, got {pos.shape}")
    q_pos = pos[:, None] + jnp.arange(t)  # [B, t]
    return (k_pos[None, None, :] <= q_pos[:, :, None])[:, None, None]


def gather_pages(pages: jax.Array, page_table: jax.Array) -> jax.Array:
    """Materialize each slot's contiguous KV view from a paged pool.

    ``pages`` is ``[num_pages, page_size, ...]`` (one pool per layer);
    ``page_table`` is ``[B, P]`` page indices in sequence order, so the
    gathered ``[B, P*page_size, ...]`` view places token position ``i``
    of slot ``b`` at row ``i`` — exactly the dense-cache layout, which is
    what keeps paged decode bitwise-parity-exact with the dense path
    (tests/test_serve.py)."""
    b, p = page_table.shape
    g = pages[page_table]  # [B, P, page_size, ...]
    return g.reshape(b, p * pages.shape[1], *pages.shape[2:])


def unfold_heads(rows: jax.Array, head_dim: int) -> jax.Array:
    """A gathered ``[B, T, Hkv*D]`` view of folded pools as the dense
    cache's ``[B, T, Hkv, D]``."""
    return rows.reshape(*rows.shape[:2], -1, head_dim)


def paged_decode_attention(
    q: jax.Array,
    key_pages: jax.Array,
    value_pages: jax.Array,
    page_table: jax.Array,
    pos: jax.Array,
) -> jax.Array:
    """``decode_attention`` against a paged KV pool (``serve/``).

    ``key_pages``/``value_pages`` are ``[num_pages, page_size, Hkv*D]``
    pools shared by every slot (heads folded into the last dimension,
    ``D`` is ``q``'s; ``ops/paged_attention.py`` says why);
    ``page_table`` ``[B, P]`` lists each slot's pages in sequence order
    and ``pos`` ``[B]`` the slots' current depths. The gather produces
    the dense per-slot view, unfolded to ``[B, P*page_size, Hkv, D]``
    after the gather, and the masking/softmax/PV path is literally
    ``decode_attention`` — paged parity is structural, not approximate.

    This is the REFERENCE implementation: its HBM traffic scales with
    page capacity ``P``, not live length. The serving hot path is
    ``ops/paged_attention.py::paged_attention`` — a Pallas kernel with
    the same signature that reads only live pages straight from the
    pool (no gather, no dense intermediate) and is tolerance-tested
    against this function."""
    gk = unfold_heads(gather_pages(key_pages, page_table), q.shape[-1])
    gv = unfold_heads(gather_pages(value_pages, page_table), q.shape[-1])
    return decode_attention(q, gk, gv, pos)


def _kv_group(q, k):
    """GQA head grouping for the ring variants: query heads must be a
    multiple of KV heads; returns the repeat factor."""
    hq, hkv = q.shape[2], k.shape[2]
    if hq % hkv:
        raise ValueError(f"query heads {hq} not a multiple of kv heads {hkv}")
    return hq // hkv


def repeat_kv(x: jax.Array, rep: int) -> jax.Array:
    """Widen [B, T, Hkv, D] KV heads to the query head count (the GQA
    repeat; identity when rep == 1)."""
    return jnp.repeat(x, rep, axis=2) if rep > 1 else x


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    axis_size: int,
    *,
    causal: bool = False,
) -> jax.Array:
    """Blockwise ring attention over a sequence-sharded mesh axis.

    Call under ``shard_map`` with q of shape [B, T_local, H, D] and k/v
    [B, T_local, Hkv, D] (T_local = T_global / axis_size, sharded along
    ``axis_name``; Hkv may divide H — grouped-query attention, in which
    case the blocks ROTATE at kv width, an H/Hkv ICI saving, and repeat
    per hop for compute). At ring step s each device holds the K/V block
    originally owned by device ``(idx - s) mod axis_size``, folds it into
    flash-style running accumulators (block max ``m``, normalizer ``l``,
    unnormalized output ``o``), and passes the block one neighbor up the
    ring — ``axis_size - 1`` single-hop ``ppermute``s total, the
    ``part2a_extra`` p2p pattern doing real long-context work.
    """
    rep = _kv_group(q, k)
    widen = lambda x: repeat_kv(x, rep)

    if axis_size == 1:
        return dense_attention(q, widen(k), widen(v), causal=causal)

    b, t_local, h, d = q.shape
    idx = lax.axis_index(axis_name)
    scale = d**-0.5
    up = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    # K/V rotate and contract in their native dtype (bf16 rides the MXU
    # at full rate — an f32 pre-cast would quarter it AND double the ICI
    # bytes per hop); accumulators stay float32.
    m0 = jnp.full((b, h, t_local), _MASK, jnp.float32)
    l0 = jnp.zeros((b, h, t_local), jnp.float32)
    o0 = jnp.zeros((b, t_local, h, d), jnp.float32)

    def merge(kb, vb, m, l, o, s):
        """Fold the held K/V block (home device ``(idx - s) % N``) into
        the flash-style running accumulators."""
        kb_w, vb_w = widen(kb), widen(vb)
        k_off = ((idx - s) % axis_size) * t_local
        scores = (
            jnp.einsum(
                "bqhd,bkhd->bhqk", q, kb_w, preferred_element_type=jnp.float32
            )
            * scale
        )
        if causal:
            q_pos = idx * t_local + jnp.arange(t_local)
            k_pos = k_off + jnp.arange(t_local)
            scores = jnp.where(q_pos[:, None] >= k_pos[None, :], scores, _MASK)
        m_new = jnp.maximum(m, scores.max(axis=-1))
        correction = jnp.exp(m - m_new)
        p = jnp.exp(scores - m_new[..., None])
        l_new = correction * l + p.sum(axis=-1)
        pv = jnp.einsum(
            "bhqk,bkhd->bqhd", p.astype(vb_w.dtype), vb_w,
            preferred_element_type=jnp.float32,
        )
        o_new = o * correction.transpose(0, 2, 1)[..., None] + pv
        return m_new, l_new, o_new

    def step(s, carry):
        kb, vb, m, l, o = carry
        # Overlap-capable (double-buffered) hop structure: the transfers
        # are issued UNCONDITIONALLY, on the same operands the compute
        # reads — no data dependence ties the hop's ICI transfer to the
        # hop's attention math, so the latency-hiding scheduler may run
        # them concurrently (the in-flight blocks land in the next
        # tick's carry). A lax.cond around the ppermute — the round-2
        # formulation — made the collective conditional and therefore
        # unschedulable as async; the dead final transfer is avoided by
        # PEELING the last merge below instead.
        kb_next = lax.ppermute(kb, axis_name, perm=up)
        vb_next = lax.ppermute(vb, axis_name, perm=up)
        m, l, o = merge(kb, vb, m, l, o, s)
        return kb_next, vb_next, m, l, o

    kb, vb, m, l, o = lax.fori_loop(
        0, axis_size - 1, step, (k, v, m0, l0, o0)
    )
    _, l, o = merge(kb, vb, m, l, o, axis_size - 1)
    out = o / l.transpose(0, 2, 1)[..., None]
    return out.astype(v.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def ring_flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    axis_size: int,
    causal: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """Ring attention with the Pallas flash kernel doing per-hop math.

    The full Ring Attention construction: K/V blocks rotate one ICI
    neighbor per hop (as in ``ring_attention``), but each hop's blockwise
    attention runs in the on-chip kernel (``ops/flash_attention.py``)
    instead of XLA einsums, and per-hop results merge via logsumexp.
    Because block offsets are multiples of T_local, every hop is one of
    exactly three cases — fully visible (k block strictly earlier),
    diagonal (same offset: the kernel's own causal mask applies), or
    fully masked (skipped) — so the kernel needs no offset plumbing.

    Backward is the ring FA-2: per hop, ``flash_dq`` (accumulated
    locally) and ``flash_dkv`` computed against the FINAL merged lse;
    dk/dv accumulators travel around the ring WITH their k/v block and
    arrive home after the last rotation.
    """
    out, _ = _rfa_forward(q, k, v, axis_name, axis_size, causal, interpret)
    return out


def _rfa_hop_case(k_blk, idx, causal, diag_fn, lower_fn, masked_fn):
    """Dispatch one ring hop to its visibility case (traced selector)."""
    if not causal:
        # Every hop is fully visible, but still route through a
        # (degenerate, always-true) lax.cond: calling lower_fn directly
        # makes the pallas_call a plain call-site inside the custom_vjp
        # body, which the CPU SPMD partitioner lowers via PartitionId
        # and rejects ("UNIMPLEMENTED: PartitionId") under
        # jit(shard_map) in interpret mode. Inside a cond branch it
        # partitions like the causal path (which always worked) — same
        # trace shape, no runtime branch taken but the masked one.
        return lax.cond(k_blk >= 0, lower_fn, masked_fn, None)
    return lax.cond(
        k_blk == idx,
        diag_fn,
        lambda _: lax.cond(k_blk < idx, lower_fn, masked_fn, None),
        None,
    )


def _rfa_forward(q, k, v, axis_name, axis_size, causal, interpret):
    from cs744_pytorch_distributed_tutorial_tpu.ops.flash_attention import (
        _from_bh,
        _to_bh,
        flash_forward_lse,
    )

    b, t, h, d = q.shape
    rep = _kv_group(q, k)
    idx = lax.axis_index(axis_name)
    up = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    o0 = jnp.zeros((b * h, t, d), jnp.float32)
    lse0 = jnp.full((b * h, t, 1), _MASK, jnp.float32)

    def merge(kb, vb, o_acc, lse_acc, s):
        k_blk = (idx - s) % axis_size

        def compute(hop_causal):
            def fn(_):
                # GQA: blocks rotate at kv width; widen per hop.
                kb_w, vb_w = repeat_kv(kb, rep), repeat_kv(vb, rep)
                out_h, lse_h = flash_forward_lse(
                    q, kb_w, vb_w, hop_causal, interpret=interpret
                )
                return _to_bh(out_h, b, t, h, d).astype(jnp.float32), lse_h

            return fn

        def masked(_):
            return o0, lse0

        out_h, lse_h = _rfa_hop_case(
            k_blk, idx, causal, compute(True), compute(False), masked
        )
        new_lse = jnp.logaddexp(lse_acc, lse_h)
        o_new = o_acc * jnp.exp(lse_acc - new_lse) + out_h * jnp.exp(
            lse_h - new_lse
        )
        return o_new, new_lse

    def hop(s, carry):
        kb, vb, o_acc, lse_acc = carry
        # Unconditional transfers co-issued with the hop's kernel (see
        # ring_attention.step): the ppermutes read the same kb/vb the
        # kernel does and nothing downstream in this tick consumes
        # their results, so transfer and compute may overlap. The dead
        # final transfer is avoided by peeling the last merge.
        kb_next = lax.ppermute(kb, axis_name, perm=up)
        vb_next = lax.ppermute(vb, axis_name, perm=up)
        o_acc, lse_acc = merge(kb, vb, o_acc, lse_acc, s)
        return kb_next, vb_next, o_acc, lse_acc

    kb, vb, o_acc, lse = lax.fori_loop(
        0, axis_size - 1, hop, (k, v, o0, lse0)
    )
    o_acc, lse = merge(kb, vb, o_acc, lse, axis_size - 1)
    return _from_bh(o_acc, b, t, h, d).astype(v.dtype), lse


def _rfa_fwd(q, k, v, axis_name, axis_size, causal, interpret):
    out, lse = _rfa_forward(q, k, v, axis_name, axis_size, causal, interpret)
    return out, (q, k, v, out, lse)


def _rfa_bwd(axis_name, axis_size, causal, interpret, residuals, g):
    from cs744_pytorch_distributed_tutorial_tpu.ops.flash_attention import (
        flash_delta,
        flash_dkv,
        flash_dq,
    )

    q, k, v, out, lse = residuals
    rep = _kv_group(q, k)
    idx = lax.axis_index(axis_name)
    up = [(i, (i + 1) % axis_size) for i in range(axis_size)]
    delta = flash_delta(out, g)

    dq0 = jnp.zeros_like(q, jnp.float32)
    widen = lambda x: repeat_kv(x, rep)

    def narrow_grad(gx):
        # Transpose of the head repeat: sum each query-head group's grad
        # back onto its shared KV head.
        if rep == 1:
            return gx
        b_, t_, hq, d_ = gx.shape
        return gx.reshape(b_, t_, hq // rep, rep, d_).sum(axis=3)

    def hop(s, carry):
        kb, vb, dk_acc, dv_acc, dq_acc = carry
        k_blk = (idx - s) % axis_size

        def dq_case(hop_causal):
            def fn(_):
                return flash_dq(
                    q, widen(kb), widen(vb), g, lse, delta, hop_causal,
                    interpret=interpret,
                ).astype(jnp.float32)

            return fn

        def dkv_case(hop_causal):
            def fn(_):
                dk_h, dv_h = flash_dkv(
                    q, widen(kb), widen(vb), g, lse, delta, hop_causal,
                    interpret=interpret,
                )
                return (
                    narrow_grad(dk_h.astype(jnp.float32)),
                    narrow_grad(dv_h.astype(jnp.float32)),
                )

            return fn

        dq_h = _rfa_hop_case(
            k_blk, idx, causal, dq_case(True), dq_case(False),
            lambda _: dq0,
        )
        dk_h, dv_h = _rfa_hop_case(
            k_blk, idx, causal, dkv_case(True), dkv_case(False),
            lambda _: (jnp.zeros_like(kb, jnp.float32),
                       jnp.zeros_like(vb, jnp.float32)),
        )
        # dk/dv accumulators travel WITH their block; after the final
        # rotation (every hop rotates) each block's grads land home.
        kb, vb, dk_acc, dv_acc = (
            lax.ppermute(x, axis_name, perm=up)
            for x in (kb, vb, dk_acc + dk_h, dv_acc + dv_h)
        )
        return kb, vb, dk_acc, dv_acc, dq_acc + dq_h

    _, _, dk, dv, dq = lax.fori_loop(
        0,
        axis_size,
        hop,
        (k, v, jnp.zeros_like(k, jnp.float32), jnp.zeros_like(v, jnp.float32),
         dq0),
    )
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


ring_flash_attention.defvjp(_rfa_fwd, _rfa_bwd)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    axis_size: int,
    *,
    causal: bool = False,
    inner: str = "dense",
    flash_interpret: bool = False,
) -> jax.Array:
    """All-to-all sequence parallelism (DeepSpeed-Ulysses pattern).

    Call under ``shard_map`` with [B, T_local, H, D] inputs. One
    ``all_to_all`` turns the sequence sharding into a *head* sharding
    (every device sees the FULL sequence for H/axis_size heads), full
    attention runs locally, and a second ``all_to_all`` restores the
    sequence sharding. Two collectives per attention call vs. the ring's
    axis_size-1 hops.

    ``inner`` picks the local attention: ``"dense"`` (exact, [T, T]
    materialized) or ``"flash"`` — the Pallas kernel
    (``ops/flash_attention.py``), valid here because each head group
    sees the FULL sequence starting at position 0, so no offset masking
    is needed. The on-chip/between-chip composition: all_to_all moves
    the data, the kernel does the math.

    GQA: k/v may arrive at kv width (Hkv dividing H). When Hkv is also
    divisible by the axis, the K/V all_to_alls run at kv width (the
    H/Hkv ICI saving) and heads widen after. When it is NOT divisible
    (ragged MQA/GQA — exactly the configs that need the saving most),
    the grouped exchange routes each device the kv heads ITS head group
    actually consumes: kv heads are gathered into per-device-aligned
    groups (``grouped_kv_plan``) before the all_to_all, so the exchange
    runs at ``ulysses_kv_exchange_width`` heads per device instead of
    the full ``H/axis`` of the widen-first fallback. Widen-first remains
    only when the grouped width wouldn't beat it.
    """
    if inner not in ("dense", "flash"):
        raise ValueError(f"unknown inner attention {inner!r}")
    rep = _kv_group(q, k)
    widen = lambda x: repeat_kv(x, rep)

    def local_attention(qg, kg, vg):
        if inner == "flash":
            from cs744_pytorch_distributed_tutorial_tpu.ops.flash_attention import (
                flash_attention,
            )

            return flash_attention(
                qg, kg, vg, causal, interpret=flash_interpret
            )
        return dense_attention(qg, kg, vg, causal=causal)

    if axis_size == 1:
        return local_attention(q, widen(k), widen(v))
    h = q.shape[2]
    if h % axis_size:
        raise ValueError(
            f"ulysses needs num_heads ({h}) divisible by axis size ({axis_size})"
        )

    def seq_to_heads(x):
        # [B, T/n, H, D] -> [B, T, H/n, D]
        return lax.all_to_all(
            x, axis_name, split_axis=2, concat_axis=1, tiled=True
        )

    def heads_to_seq(x):
        return lax.all_to_all(
            x, axis_name, split_axis=1, concat_axis=2, tiled=True
        )

    hkv = k.shape[2]
    if rep > 1 and hkv % axis_size == 0:
        # kv-width collectives: split kv heads over the axis, widen after.
        kg, vg = widen(seq_to_heads(k)), widen(seq_to_heads(v))
    elif rep > 1 and ulysses_kv_exchange_width(h, hkv, axis_size) < h // axis_size:
        # Ragged Hkv: grouped exchange at (near-)kv width. Each device's
        # q head group [i*H/n, (i+1)*H/n) consumes a SMALL set of kv
        # heads; gather those into per-device slots pre-exchange so the
        # tiled all_to_all hands every device exactly its set, then map
        # each local q head onto its received slot.
        idx, local_map, per_dev = grouped_kv_plan(h, hkv, axis_size)
        sel = jnp.asarray(idx)
        kg_, vg_ = (
            seq_to_heads(x[:, :, sel, :]) for x in (k, v)
        )  # [B, T, per_dev, D]
        me = lax.axis_index(axis_name)
        lmap = jnp.asarray(local_map)[me]  # [H/n] -> received slot
        kg = jnp.take(kg_, lmap, axis=2)
        vg = jnp.take(vg_, lmap, axis=2)
    else:
        kg, vg = seq_to_heads(widen(k)), seq_to_heads(widen(v))
    qg = seq_to_heads(q)
    out = local_attention(qg, kg, vg)  # full seq, head group
    return heads_to_seq(out)


def grouped_kv_plan(h: int, hkv: int, n: int):
    """Per-device kv routing for ragged GQA (``hkv % n != 0``).

    Returns ``(idx, local_map, per_dev)``: ``idx`` ([n * per_dev]) lists
    the kv head to place in each pre-exchange slot (device i's slots are
    ``idx[i*per_dev:(i+1)*per_dev]`` — the distinct kv heads its q group
    needs, right-padded by repetition); ``local_map`` ([n, h/n]) maps
    each device's local q head to its received slot. Pure host-side
    numpy — the plan is static per (h, hkv, n).
    """
    import numpy as np

    rep = h // hkv
    groups = []
    for i in range(n):
        lo, hi = i * h // n, (i + 1) * h // n
        heads = sorted({qh // rep for qh in range(lo, hi)})
        groups.append(heads)
    per_dev = max(len(g) for g in groups)
    idx, local = [], []
    for i, g in enumerate(groups):
        g_pad = g + [g[-1]] * (per_dev - len(g))
        idx.extend(g_pad)
        lo = i * h // n
        local.append([g_pad.index((lo + ql) // rep) for ql in range(h // n)])
    return np.asarray(idx, np.int32), np.asarray(local, np.int32), per_dev


def ulysses_kv_exchange_width(h: int, hkv: int, n: int) -> int:
    """Heads per device the K/V all_to_all moves under the grouped plan —
    the collective-bytes accounting the GQA tests assert on (widen-first
    moves ``h // n``; divisible kv-width moves ``hkv // n``)."""
    if hkv % n == 0:
        return hkv // n
    return grouped_kv_plan(h, hkv, n)[2]
