"""Pallas flash attention vs dense reference (interpret mode on CPU)."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cs744_pytorch_distributed_tutorial_tpu.ops.flash_attention import (
    flash_attention,
    flash_delta,
    flash_dkv,
    flash_dq,
    flash_forward_lse,
    flash_tile_plan,
)
from cs744_pytorch_distributed_tutorial_tpu.parallel.ring_attention import (
    dense_attention,
)

B, T, H, D = 2, 64, 2, 16


@pytest.fixture(scope="module")
def qkv():
    ks = jax.random.split(jax.random.key(42), 3)
    mk = lambda k: jax.random.normal(k, (B, T, H, D), jnp.float32)
    return mk(ks[0]), mk(ks[1]), mk(ks[2])


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("block", [16, 32, 64])
def test_matches_dense(qkv, causal, block):
    q, k, v = qkv
    expected = np.asarray(dense_attention(q, k, v, causal=causal))
    got = np.asarray(
        flash_attention(q, k, v, causal, block, block, True)
    )
    np.testing.assert_allclose(got, expected, rtol=2e-5, atol=2e-5)


def test_uneven_block_sizes_fall_back_to_divisors(qkv):
    q, k, v = qkv  # T=64; preferred 48 does not divide -> picks a divisor
    expected = np.asarray(dense_attention(q, k, v, causal=True))
    got = np.asarray(flash_attention(q, k, v, True, 48, 48, True))
    np.testing.assert_allclose(got, expected, rtol=2e-5, atol=2e-5)


def test_gradients_match_dense(qkv):
    q, k, v = qkv

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, True, 32, 32, True) ** 2).sum()

    def loss_dense(q, k, v):
        return (dense_attention(q, k, v, causal=True) ** 2).sum()

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for gf, gd in zip(g_flash, g_dense):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gd), rtol=1e-4, atol=1e-4
        )


@pytest.mark.parametrize("t", [24, 40, 96, 160])
def test_odd_lengths_pick_divisor_blocks(t):
    """Sequence lengths that don't divide the default 512/1024 blocks:
    _pick_block must find a working divisor, forward AND backward."""
    ks = jax.random.split(jax.random.key(t), 3)
    q, k, v = (jax.random.normal(kk, (1, t, 2, 8)) for kk in ks)
    expected = np.asarray(dense_attention(q, k, v, causal=True))
    got = np.asarray(flash_attention(q, k, v, True, interpret=True))
    np.testing.assert_allclose(got, expected, rtol=2e-5, atol=2e-5)

    g_f = jax.grad(
        lambda a: (flash_attention(a, k, v, True, interpret=True) ** 2).sum()
    )(q)
    g_d = jax.grad(
        lambda a: (dense_attention(a, k, v, causal=True) ** 2).sum()
    )(q)
    np.testing.assert_allclose(np.asarray(g_f), np.asarray(g_d),
                               rtol=1e-4, atol=1e-4)


def test_prime_length_rejected_loudly():
    """A prime T larger than the block size has no usable divisor — the
    kernel refuses instead of silently crawling one padded row per grid
    step. (Primes BELOW the block size are fine: the whole sequence is
    one block.)"""
    q = jnp.zeros((1, 1031, 2, 8))  # prime > 512
    with pytest.raises(ValueError, match="block"):
        flash_attention(q, q, q, True, interpret=True)
    small = jnp.zeros((1, 37, 2, 8))  # prime < block: single-block path
    out = flash_attention(small, small, small, True, interpret=True)
    assert out.shape == small.shape


def test_bfloat16_inputs(qkv):
    q, k, v = (a.astype(jnp.bfloat16) for a in qkv)
    expected = np.asarray(
        dense_attention(q, k, v, causal=False).astype(jnp.float32)
    )
    got = np.asarray(
        flash_attention(q, k, v, False, 32, 32, True).astype(jnp.float32)
    )
    np.testing.assert_allclose(got, expected, rtol=2e-2, atol=2e-2)


# ------------------------------------------------------------ causal tiling
def _coverage(plan, t_q, t_k, sweep):
    """What the plan makes of every score element: 0 never computed,
    1 computed without a mask, 2 computed and masked."""
    kind = np.zeros((t_q, t_k), int)
    bq, bk, cut = plan.block_q, plan.block_k, plan.cut
    for i in range(plan.blocks):
        for r, (tiles, span, clear) in enumerate(plan.pieces):
            tiles += i * plan.ratio
            if sweep == "keys":
                rows = slice(i * bq + r * cut, i * bq + (r + 1) * cut)
                kind[rows, :tiles * bk + clear] = 1
                kind[rows, tiles * bk + clear:tiles * bk + span] = 2
            else:
                cols = slice(i * bk + r * cut, i * bk + (r + 1) * cut)
                q0 = min(tiles * bq, t_q)
                kind[q0 - span:q0 - clear, cols] = 2
                kind[q0 - clear:, cols] = 1
    return kind


def _check_plan(plan, t_q, t_k, sweep):
    kind = _coverage(plan, t_q, t_k, sweep)
    allowed = np.arange(t_q)[:, None] >= np.arange(t_k)[None, :]
    assert not allowed[kind == 0].any(), "skipped an element the mask keeps"
    assert allowed[kind == 1].all(), "left a masked-out element unmasked"
    assert (plan.run, plan.masked, plan.skipped) == (
        (kind > 0).sum(), (kind == 2).sum(), (kind == 0).sum()
    )
    return kind


@pytest.mark.parametrize("sweep", ["keys", "queries"])
def test_plan_at_the_lm_cells_shape(sweep):
    """T=1024, D=64, causal: the skipping engages (the fixed (512, 1024)
    tiles ran and masked the whole matrix), and what is masked is the
    squares on the diagonal and nothing else."""
    plan = flash_tile_plan(1024, 1024, True, sweep)
    kind = _check_plan(plan, 1024, 1024, sweep)
    assert plan.skipped > 0 and plan.run > plan.masked > 0
    idx = np.arange(1024) // plan.cut
    np.testing.assert_array_equal(kind == 2, idx[:, None] == idx[None, :])
    if sweep == "keys":
        assert plan.run_share <= 0.63


@pytest.mark.parametrize("sweep", ["keys", "queries"])
@pytest.mark.parametrize(
    "t_q,t_k,block_q,block_k",
    [
        (1024, 1024, 128, 128),
        (1024, 1024, 256, 128),
        (1024, 1024, 128, 512),
        (4096, 4096, None, None),
        (384, 384, 256, 256),   # shrinks to the divisor 192
        (256, 512, 64, 128),    # more keys than queries
        (512, 256, 128, 64),    # more queries than keys
        (96, 96, 32, 24),
    ],
)
def test_plan_covers_what_the_mask_keeps(sweep, t_q, t_k, block_q, block_k):
    plan = flash_tile_plan(t_q, t_k, True, sweep, block_q, block_k)
    assert t_q % plan.block_q == 0 and t_k % plan.block_k == 0
    _check_plan(plan, t_q, t_k, sweep)


@pytest.mark.parametrize("sweep", ["keys", "queries"])
def test_plan_noncausal_skips_and_masks_none(sweep):
    plan = flash_tile_plan(1024, 1024, False, sweep)
    assert (plan.run, plan.masked, plan.skipped) == (1024 * 1024, 0, 0)
    assert plan.run_share == 1.0
    assert all(span == 0 for _, span, _ in plan.pieces)  # loop tiles only


def _dense_lse(q, k):
    """Row logsumexp of the causal scores, positions counted from 0 on
    both sides (what the kernels mean by causal when t_q != t_k), as
    ``[B*H, T_q, 1]``: the layout the kernels emit and take."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    allowed = jnp.arange(q.shape[1])[:, None] >= jnp.arange(k.shape[1])[None]
    lse = jax.nn.logsumexp(jnp.where(allowed, s, -1e30), axis=-1)
    return lse.reshape(-1, q.shape[1], 1)


# (t_q, t_k, d, block_q, block_k); None = the plan's own tiles
TILED_CASES = {
    "t256_plan_d64": (256, 256, 64, None, None),
    "t1024_plan_d64": (1024, 1024, 64, None, None),
    "t1024_plan_d128": (1024, 1024, 128, None, None),
    "t256_q32_k128": (256, 256, 64, 32, 128),
    "t256_q128_k32": (256, 256, 64, 128, 32),
    "t512_q64_k64_d128": (512, 512, 128, 64, 64),
    "t384_not_a_multiple": (384, 384, 64, None, None),
    "t320_q128_shrinks": (320, 320, 64, 128, 128),
    "tq128_tk256": (128, 256, 64, 32, 64),
    "tq256_tk128": (256, 128, 64, 64, 32),
}


@pytest.mark.parametrize("case", TILED_CASES)
def test_causal_tiling_matches_float32_dense(case):
    """Forward and all three gradients, causal, with tiles on, under and
    over the diagonal. ``t_q != t_k`` goes through ``flash_dq`` /
    ``flash_dkv`` as the ring's hops call them (lse and delta given)."""
    t_q, t_k, d, block_q, block_k = TILED_CASES[case]
    for sweep in ("keys", "queries"):
        plan = flash_tile_plan(t_q, t_k, True, sweep, block_q, block_k)
        if block_q is not None or t_q >= 1024:  # a short T may be one block
            assert plan.skipped > 0 and plan.run > plan.masked > 0, plan
    ks = jax.random.split(jax.random.key(t_q + t_k + d), 4)
    q, do = (jax.random.normal(kk, (1, t_q, 2, d)) for kk in ks[:2])
    k, v = (jax.random.normal(kk, (1, t_k, 2, d)) for kk in ks[2:])

    want_out, vjp = jax.vjp(partial(dense_attention, causal=True), q, k, v)
    want = vjp(do)
    if t_q == t_k:
        out, vjp = jax.vjp(
            lambda q, k, v: flash_attention(
                q, k, v, True, block_q, block_k, True
            ),
            q, k, v,
        )
        got = vjp(do)
    else:
        # the reference's lse and delta, as a merged ring softmax has them
        lse = _dense_lse(q, k)
        out = want_out
        delta = flash_delta(out, do)
        args = (q, k, v, do, lse, delta, True, block_q, block_k, True)
        got = (flash_dq(*args), *flash_dkv(*args))
    np.testing.assert_allclose(out, want_out, rtol=2e-5, atol=2e-5)
    for g, w, name in zip(got, want, ("dq", "dk", "dv")):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4, err_msg=name)


def test_forward_lse_matches_dense_logsumexp():
    """The ring merges hops by this lse: it must be the tile loops' own,
    masked tiles included."""
    ks = jax.random.split(jax.random.key(7), 3)
    q, k, v = (jax.random.normal(kk, (1, 256, 2, 64)) for kk in ks)
    out, lse = flash_forward_lse(q, k, v, True, 64, 32, True)
    np.testing.assert_allclose(lse, _dense_lse(q, k), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(
        out, dense_attention(q, k, v, causal=True), rtol=2e-5, atol=2e-5
    )
