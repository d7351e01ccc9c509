"""Learned sparse attention: an indexer scores every cached token for a
query and attention runs over the ``top_k`` best only.

The indexer is a small attention-like scorer (DeepSeek sparse
attention's "lightning indexer"): per query ``t`` a few heads ``qI[t,j]``
and a weight ``w[t,j]`` each, per cached token ``s`` ONE key ``kI[s]``::

    I[t, s] = sum_j  w[t, j] * relu(qI[t, j] . kI[s])

and ``S_t`` is the ``top_k`` positions ``s <= t`` with the largest
``I[t, s]`` (all of them while ``t < top_k``), ties to the lower index,
exact. That one definition has two forms here, because the two users
want different things from it:

- ``topk_mask`` gives ``S_t`` as a boolean mask over the positions, by a
  bitwise search for the k-th largest score (32 passes of compare and
  count over the scores; no sort). The full forward and the chunked
  prefill mask ordinary causal attention with it.
- ``topk_indices`` gives ``S_t`` as ``top_k`` positions
  (``lax.top_k``, which puts the lower index first among equals), for
  the decode step, which then reads only those rows of the paged pools.

``tests/test_sparse_attention.py`` holds the two to each other and to a
plain reference, ties included.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

_MASK = -1e30


def indexer_scores(q_idx, k_idx, w):
    """``q_idx`` [B, C, J, Di], ``k_idx`` [B, S, Di], ``w`` [B, C, J] ->
    float32 scores [B, C, S]."""
    dots = jnp.einsum(
        "bcjd,bsd->bcjs", q_idx, k_idx, preferred_element_type=jnp.float32
    )
    return jnp.einsum(
        "bcjs,bcj->bcs", jax.nn.relu(dots), w.astype(jnp.float32)
    )


def _one_zero(scores):
    """-0.0 and 0.0 are equal scores; a sort by bits (ours, and
    ``lax.top_k``'s total order) would put one before the other."""
    scores = scores.astype(jnp.float32)
    return jnp.where(scores == 0, 0.0, scores)


def _sortable(scores):
    """float32 -> uint32 whose unsigned order is the floats' order."""
    bits = lax.bitcast_convert_type(_one_zero(scores), jnp.int32)
    key = bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))
    return lax.bitcast_convert_type(key, jnp.uint32) ^ jnp.uint32(0x80000000)


def topk_mask(scores, valid, k: int):
    """The ``k`` largest of each row of ``scores`` [..., S] among the
    ``valid`` positions, as a mask [..., S]; rows with ``k`` or fewer
    valid positions keep them all. Equal scores go to the lower index."""
    s = scores.shape[-1]
    if k >= s:
        return valid
    ukey = jnp.where(valid, _sortable(scores), jnp.uint32(0))

    def bit(i, tau):
        cand = tau | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        enough = jnp.sum(ukey >= cand[..., None], axis=-1) >= k
        return jnp.where(enough, cand, tau)

    # tau = the k-th largest key of the row (0 where fewer than k exist)
    tau = lax.fori_loop(
        0, 32, bit, jnp.zeros(scores.shape[:-1], jnp.uint32)
    )[..., None]
    above = ukey > tau
    equal = ukey == tau
    room = k - jnp.sum(above, axis=-1, keepdims=True)
    keep_equal = equal & (jnp.cumsum(equal, axis=-1) <= room)
    return (above | keep_equal) & valid


def topk_indices(scores, valid, k: int):
    """The same set as ``topk_mask`` as positions [..., k] and a flag
    [..., k] that is False where the row had fewer than ``k`` valid
    positions and the entry is filler."""
    k = min(k, scores.shape[-1])
    vals, idx = lax.top_k(jnp.where(valid, _one_zero(scores), -jnp.inf), k)
    return idx, vals > -jnp.inf


def masked_attention(q, k, v, q_pos, allowed=None):
    """Causal attention of queries at positions ``q_pos`` [B, C] over
    keys at positions ``0..S-1``, restricted to ``allowed`` [B, C, S]
    where given. ``q`` [B, C, H, D], ``k``/``v`` [B, S, Hkv, D] (GQA:
    query heads grouped over the KV heads, no repeat). One KV head at a
    time, so the float32 scores held at once are [B, H/Hkv, C, S]."""
    b, c, h, d = q.shape
    s, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    mask = jnp.arange(s)[None, None, :] <= q_pos[:, :, None]
    if allowed is not None:
        mask = mask & allowed
    scale = d ** -0.5
    qg = q.reshape(b, c, hkv, g, d).transpose(2, 0, 3, 1, 4)  # [Hkv,B,G,C,D]

    def one_kv_head(args):
        qh, kh, vh = args
        sc = jnp.einsum(
            "bgcd,bsd->bgcs", qh, kh, preferred_element_type=jnp.float32
        ) * scale
        sc = jnp.where(mask[:, None], sc, _MASK)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("bgcs,bsd->bgcd", p.astype(vh.dtype), vh)

    out = lax.map(
        one_kv_head, (qg, k.transpose(2, 0, 1, 3), v.transpose(2, 0, 1, 3))
    )  # [Hkv, B, G, C, D]
    return out.transpose(1, 3, 0, 2, 4).reshape(b, c, h, d)


def selected_rows_attention(q, k_rows, v_rows, row_valid):
    """One query a slot over the rows the selection kept: ``q``
    [B, 1, H, D], ``k_rows``/``v_rows`` [B, K, Hkv, D], ``row_valid``
    [B, K] -> [B, 1, H, D]."""
    b, _, h, d = q.shape
    hkv = k_rows.shape[2]
    qg = q[:, 0].reshape(b, hkv, h // hkv, d)
    sc = jnp.einsum(
        "bhgd,bkhd->bhgk", qg, k_rows, preferred_element_type=jnp.float32
    ) * d ** -0.5
    sc = jnp.where(row_valid[:, None, None, :], sc, _MASK)
    p = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", p.astype(v_rows.dtype), v_rows)
    return out.reshape(b, 1, h, d)
