"""Learned sparse attention (ops/sparse_attention.py, the indexer in
models/transformer.py::Attention) against the plain reference
``perfbench/reference/keye.py``: the scores, the selection ``S_t`` as
exact index sets (ties to the lower index), the model's full forward,
and the rule that a context of ``sparse_topk`` tokens or fewer selects
everything and so gives what the dense path gives.

Tolerances. Everything is float32 on the CPU, where a float32 matmul is
exact to rounding. Model against reference: 2e-5 on logits of magnitude
~4, because the two sum the same products in different orders (the
model's grouped matmuls and one-KV-head-at-a-time attention against
the reference's per-expert and per-block loops), and a 64- to 128-term
float32 sum moves by a few 1e-6 with the order. Sparse against dense:
the same, for the same reason (``masked_attention`` groups query heads
over KV heads, ``dense_attention`` repeats the KV heads). The selected
sets are compared exactly: the scores are computed by both sides in
float32 from the same weights, so a set could differ only at a tie that
rounding broke one way on each side, and the test names the positions
if it ever does.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cs744_pytorch_distributed_tutorial_tpu.ops import sparse_attention as sa
from perfbench.reference import keye as R

from keye_tiny import build, tiny_config



def _plain_topk_mask(scores, valid, k):
    """Stable argsort of the negated scores: the plain statement."""
    scores = np.where(valid, scores, -np.inf)
    order = np.argsort(-scores, axis=-1, kind="stable")[..., :k]
    mask = np.zeros(scores.shape, bool)
    np.put_along_axis(mask, order, True, axis=-1)
    return mask & valid


@pytest.mark.parametrize("levels", [0, 7], ids=["distinct", "ties"])
@pytest.mark.parametrize("k", [1, 5, 16, 40])
def test_topk_mask_and_indices_are_the_plain_top_k(k, levels):
    rng = np.random.default_rng(k + levels)
    scores = rng.standard_normal((3, 9, 33)).astype(np.float32)
    if levels:  # few distinct values, zeros of both signs among them
        scores = np.round(scores * 2) / 2 * np.float32(1.0)
        scores[0, 0, :4] = [0.0, -0.0, 0.0, -0.0]
    # causal validity, so that the first rows hold fewer than k
    valid = np.arange(33)[None, None, :] <= (np.arange(9) * 4)[None, :, None]
    valid = np.broadcast_to(valid, scores.shape)
    want = _plain_topk_mask(scores, valid, k)
    got = np.asarray(sa.topk_mask(jnp.asarray(scores), jnp.asarray(valid), k))
    np.testing.assert_array_equal(got, want)
    idx, ok = sa.topk_indices(jnp.asarray(scores), jnp.asarray(valid), k)
    idx, ok = np.asarray(idx), np.asarray(ok)
    from_idx = np.zeros(scores.shape, bool)
    for b, c in np.ndindex(scores.shape[:2]):
        from_idx[b, c, idx[b, c][ok[b, c]]] = True
    np.testing.assert_array_equal(from_idx, want)
    assert (ok.sum(-1) == np.minimum(valid.sum(-1), k)).all()


def test_indexer_scores_are_the_stated_sum():
    rng = np.random.default_rng(0)
    q = rng.standard_normal((2, 5, 3, 8)).astype(np.float32)
    k = rng.standard_normal((2, 11, 8)).astype(np.float32)
    w = rng.standard_normal((2, 5, 3)).astype(np.float32)
    want = np.zeros((2, 5, 11), np.float32)
    for b, t, s in np.ndindex(2, 5, 11):
        want[b, t, s] = sum(
            w[b, t, j] * max(float(q[b, t, j] @ k[b, s]), 0.0) for j in range(3)
        )
    got = sa.indexer_scores(jnp.asarray(q), jnp.asarray(k), jnp.asarray(w))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("seed", [5, 11])
def test_full_forward_and_selected_sets_match_the_reference(seed):
    cfg = tiny_config(topk=16)
    model, params, flat = build(cfg, seed)
    tokens = np.random.default_rng(seed).integers(0, 256, 96).astype(np.int32)
    logits, inter = model.apply(
        {"params": params}, jnp.asarray(tokens)[None], mutable=["intermediates"]
    )
    ref, ref_sel = R.forward(flat, tokens, cfg, return_selection=True)
    np.testing.assert_allclose(logits[0], ref, atol=2e-5, rtol=0)
    for i, want in enumerate(ref_sel):
        got = np.asarray(
            inter["intermediates"][f"block_{i}"]["attn"]["selected"][0][0]
        )
        assert want.sum(-1).max() == 16 and want[:16].sum() == 16 * 17 // 2
        differ = np.argwhere(got != want)
        assert differ.size == 0, f"layer {i}: sets differ at (t, s) {differ[:8]}"


def _paged_logits(model, params, tokens, chunk, page_size=8, pages=16):
    """Logits of every position through the two paged modes: the prompt
    by chunks (mode="paged_prefill"), then one token at a time
    (mode="paged_decode"), on one slot whose page row is 1..pages."""
    n_prefill = (len(tokens) // 2 // chunk) * chunk
    row = jnp.arange(1, pages + 1, dtype=jnp.int32)[None]
    state = jax.eval_shape(
        lambda: model.init(
            jax.random.key(0), jnp.zeros((1, 1), jnp.int32), mode="paged_decode",
            decode_pos=jnp.zeros((1,), jnp.int32), page_table=row,
        )
    )["pages"]
    state = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), state)
    def call(mode):
        return jax.jit(
            lambda state, x, pos: model.apply(
                {"params": params, "pages": state}, x, mode=mode,
                decode_pos=pos, page_table=row, mutable=["pages"],
            )
        )

    prefill, decode = call("paged_prefill"), call("paged_decode")
    out = []
    for lo in range(0, len(tokens)):
        if lo < n_prefill and lo % chunk:
            continue
        fn, n = (prefill, chunk) if lo < n_prefill else (decode, 1)
        logits, mut = fn(
            state, jnp.asarray(tokens[lo:lo + n])[None], jnp.array([lo], jnp.int32)
        )
        state = mut["pages"]
        out.append(logits[0])
    return jnp.concatenate(out, 0)


@pytest.mark.parametrize("mode", ["train", "paged"])
def test_context_within_topk_equals_the_dense_path(mode):
    """top-k 64 over 48 tokens selects every token: the indexer is
    computed and changes nothing."""
    cfg = tiny_config(topk=64)
    sparse, sparse_params, _ = build(cfg, page_size=8, num_pages=17)
    dense, dense_params, _ = build(
        cfg, page_size=8, num_pages=17, indexer_heads=0, sparse_topk=0
    )
    tokens = np.random.default_rng(3).integers(0, 256, 48).astype(np.int32)
    if mode == "train":
        got = sparse.apply({"params": sparse_params}, jnp.asarray(tokens)[None])[0]
        want = dense.apply({"params": dense_params}, jnp.asarray(tokens)[None])[0]
    else:
        got = _paged_logits(sparse, sparse_params, tokens, chunk=8)
        want = _paged_logits(dense, dense_params, tokens, chunk=8)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


def test_paged_modes_match_the_reference_past_topk():
    """Chunks then single tokens through the pools, top-k 16 over 80
    tokens: the logits of every position are the reference's."""
    cfg = tiny_config(topk=16)
    model, params, flat = build(cfg, page_size=8, num_pages=17)
    tokens = np.random.default_rng(9).integers(0, 256, 80).astype(np.int32)
    got = _paged_logits(model, params, tokens, chunk=8, pages=12)
    np.testing.assert_allclose(got, R.forward(flat, tokens, cfg), atol=2e-5, rtol=0)


def test_indexer_rejects_what_it_cannot_run():
    cfg = tiny_config()
    model, params, _ = build(cfg)
    with pytest.raises(ValueError, match="paged pools"):
        model.clone(quant_kv_cache=True).init(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
        )
    with pytest.raises(ValueError, match="sparse_topk"):
        model.clone(sparse_topk=0).init(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
        )
