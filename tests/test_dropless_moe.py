"""Dropless MoE: grouped matmuls (ops/gmm.py) + dispatch_impl="dropless".

No counterpart exists in the reference (data parallelism over one dense
VGG-11 is its whole scope, SURVEY §2.3). The key properties pinned here:

- ``grouped_matmul`` computes ``out[r] = lhs[r] @ rhs[g(r)]`` under the
  contiguous-group layout for BOTH backends — XLA's ``lax.ragged_dot``
  and the Pallas gmm kernel — including empty groups, tile-unaligned row
  counts, and gradients (the Pallas backward pair is dx = gmm with
  transposed experts, dw = the tgmm kernel).
- ``dispatch_impl="dropless"`` is the capacity-free limit of the routed
  layer: it must match the scatter path exactly when capacity is large
  enough that nothing drops (same router, same gates — only the token
  movement differs), report a zero drop metric, and train.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cs744_pytorch_distributed_tutorial_tpu.data import synthetic_tokens
from cs744_pytorch_distributed_tutorial_tpu.models import MoEFFN
from cs744_pytorch_distributed_tutorial_tpu.ops.gmm import grouped_matmul
from cs744_pytorch_distributed_tutorial_tpu.parallel import make_mesh
from cs744_pytorch_distributed_tutorial_tpu.train import LMConfig, LMTrainer

MOE = dict(
    vocab_size=64, num_layers=2, num_heads=4, d_model=64, d_ff=128,
    max_seq_len=256, global_batch_size=8, seq_len=64, learning_rate=1e-2,
    moe_experts=4,
)


def _oracle(x, w, gs):
    ids = np.repeat(np.arange(w.shape[0]), np.asarray(gs))
    return jnp.einsum("nd,ndf->nf", x, jnp.asarray(w)[ids])


@pytest.mark.parametrize(
    "m,e,gs_list",
    [
        (16, 4, [3, 5, 0, 8]),      # empty group mid-list
        (64, 3, [64, 0, 0]),        # everything in group 0
        (100, 5, [0, 30, 20, 0, 50]),  # tile-unaligned M
        (7, 2, [2, 5]),             # M smaller than one tile
    ],
)
def test_grouped_matmul_both_impls_match_oracle(m, e, gs_list):
    k, n = 8, 12
    rng = np.random.default_rng(m)
    x = jnp.array(rng.standard_normal((m, k)), jnp.float32)
    w = jnp.array(rng.standard_normal((e, k, n)), jnp.float32)
    gs = jnp.array(gs_list, jnp.int32)
    ref = _oracle(x, w, gs)
    ragged = grouped_matmul(x, w, gs, impl="ragged")
    pallas = grouped_matmul(
        x, w, gs, impl="pallas", block_m=8, block_n=8, interpret=True
    )
    # Both run the matmul at the backend's default precision; the
    # oracle's einsum may differ at bf16-level on TPU-default backends.
    np.testing.assert_allclose(ragged, ref, rtol=2e-2, atol=2e-2)
    # The two impls walk the same groups tile-by-tile — bitwise-close.
    np.testing.assert_allclose(pallas, ragged, rtol=1e-6, atol=1e-6)


def test_grouped_matmul_grads_match():
    """d/d(lhs) and d/d(rhs) agree between ragged_dot's native AD and
    the Pallas custom_vjp (dx = gmm(dout, rhsᵀ), dw = tgmm)."""
    rng = np.random.default_rng(0)
    x = jnp.array(rng.standard_normal((40, 8)), jnp.float32)
    w = jnp.array(rng.standard_normal((4, 8, 12)), jnp.float32)
    gs = jnp.array([10, 0, 25, 5], jnp.int32)

    def loss(impl):
        kw = (
            dict(impl="pallas", block_m=8, block_n=8, interpret=True)
            if impl == "pallas"
            else dict(impl="ragged")
        )
        return lambda x, w: jnp.sum(grouped_matmul(x, w, gs, **kw) ** 2)

    grx, grw = jax.grad(loss("ragged"), argnums=(0, 1))(x, w)
    gpx, gpw = jax.grad(loss("pallas"), argnums=(0, 1))(x, w)
    np.testing.assert_allclose(gpx, grx, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(gpw, grw, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("top_k", [1, 2])
def test_dropless_matches_uncapped_scatter(top_k):
    """With capacity high enough that nothing drops, scatter and
    dropless are the same mathematical layer (same router, same gates,
    every token computes) — outputs, aux loss and parameter gradients
    must agree; the dropless drop metric is identically zero."""
    e, d, f = 4, 8, 32
    x = jax.random.normal(jax.random.key(1), (2, 16, d), jnp.float32)
    drop = MoEFFN(
        num_experts=e, d_ff=f, top_k=top_k, dispatch_impl="dropless",
        gmm_interpret=True, gmm_block_m=8, gmm_block_n=8,
    )
    ref = MoEFFN(
        num_experts=e, d_ff=f, top_k=top_k, dispatch_impl="scatter",
        capacity_factor=float(e),  # capacity >= all tokens: zero drops
    )
    params = drop.init(jax.random.key(0), x)
    yd, md = drop.apply(params, x, mutable=["losses", "metrics"])
    yr, mr = ref.apply(params, x, mutable=["losses", "metrics"])
    np.testing.assert_allclose(yd, yr, rtol=2e-5, atol=2e-5)
    assert float(jax.tree.leaves(mr["metrics"])[0]) == 0.0  # truly uncapped
    assert float(jax.tree.leaves(md["metrics"])[0]) == 0.0
    np.testing.assert_allclose(
        jax.tree.leaves(md["losses"])[0], jax.tree.leaves(mr["losses"])[0],
        rtol=1e-6,
    )

    def loss(layer, p):
        y, _ = layer.apply(p, x, mutable=["losses", "metrics"])
        return jnp.sum(y**2)

    gd = jax.grad(lambda p: loss(drop, p))(params)
    gr = jax.grad(lambda p: loss(ref, p))(params)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4),
        gd,
        gr,
    )


def test_dropless_pallas_matches_ragged_in_layer():
    """The two gmm backends are interchangeable inside the layer."""
    x = jax.random.normal(jax.random.key(1), (2, 16, 8), jnp.float32)
    mk = lambda impl: MoEFFN(
        num_experts=4, d_ff=32, top_k=2, dispatch_impl="dropless",
        gmm_impl=impl, gmm_interpret=True, gmm_block_m=8, gmm_block_n=8,
    )
    params = mk("ragged").init(jax.random.key(0), x)
    yr = mk("ragged").apply(params, x)
    yp = mk("pallas").apply(params, x)
    np.testing.assert_allclose(yp, yr, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize(
    "kw",
    [dict(capacity_factor=2.0), dict(num_groups=4), dict(num_groups=0)],
)
def test_dropless_rejects_capacity_knobs(kw):
    """dropless has no capacity: a tuned capacity_factor or group count
    must be rejected loudly, not silently ignored (same reject-don't-
    drop rule as the expert_axis case)."""
    x = jnp.zeros((1, 8, 8), jnp.float32)
    layer = MoEFFN(
        num_experts=4, d_ff=16, dispatch_impl="dropless", **kw
    )
    with pytest.raises(ValueError, match="dropless"):
        layer.init(jax.random.key(0), x)


def test_dropless_rejects_expert_parallel():
    layer = MoEFFN(
        num_experts=4, d_ff=16, dispatch_impl="dropless",
        expert_axis="data", expert_axis_size=2,
    )
    x = jnp.zeros((1, 8, 8))
    with pytest.raises(ValueError, match="dropless"):
        layer.init(jax.random.key(0), x)
    cfg = LMConfig(
        **MOE, attention_impl="dense", data_parallel=2,
        moe_dispatch="dropless", moe_expert_parallel=True,
    )
    mesh = make_mesh({"data": 2, "seq": 1}, devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="dropless"):
        LMTrainer(cfg, mesh=mesh)


@pytest.mark.slow
def test_dropless_lm_trains():
    """A 2-device data-parallel dropless-MoE LM learns the cyclic
    synthetic stream (the end-to-end descent check the other dispatch
    impls have)."""
    mesh = make_mesh({"data": 2, "seq": 1}, devices=jax.devices()[:2])
    cfg = LMConfig(
        **MOE, attention_impl="dense", data_parallel=2, seq_parallel=1,
        moe_dispatch="dropless",
    )
    tr = LMTrainer(cfg, mesh=mesh)
    tokens = synthetic_tokens(64, cfg.seq_len, cfg.vocab_size, seed=3)
    _, _, losses = tr.fit(tokens, steps=60)
    uniform = np.log(cfg.vocab_size)
    assert losses[-1] < 0.7 * uniform
    assert np.isfinite(losses).all()
    # the drop metric surfaces as identically zero
    params, opt_state = tr.init()
    x, y = tr.shard_batch(tokens[:8])
    _, _, m = tr.train_step(params, opt_state, x, y)
    assert float(m["moe_drop"]) == 0.0


@pytest.mark.parametrize("act", ["none", "gelu"])
def test_grouped_matmul_fused_matches_unfused(act):
    """The fused-epilogue kernels (bias(+gelu) inside the gmm — the
    in-model Pallas path) compute exactly the
    unfused chain, forward and gradients (custom_vjp: dx/dw via the
    plain kernels, db via a K=1 tgmm segment-sum)."""
    from cs744_pytorch_distributed_tutorial_tpu.ops.gmm import (
        grouped_matmul_fused,
    )

    rng = np.random.default_rng(1)
    x = jnp.array(rng.standard_normal((24, 8)), jnp.float32)
    w = jnp.array(rng.standard_normal((4, 8, 12)), jnp.float32)
    b = jnp.array(rng.standard_normal((4, 12)), jnp.float32)
    gs = jnp.array([5, 0, 11, 8], jnp.int32)
    ids = np.repeat(np.arange(4), np.asarray(gs))

    def unfused(x, w, b):
        z = grouped_matmul(
            x, w, gs, impl="pallas", block_m=8, block_n=8, interpret=True
        ) + b[ids]
        return jax.nn.gelu(z) if act == "gelu" else z

    def fused(x, w, b):
        return grouped_matmul_fused(
            x, w, b, gs, activation=act, block_m=8, block_n=8,
            interpret=True,
        )

    np.testing.assert_allclose(
        fused(x, w, b), unfused(x, w, b), rtol=1e-5, atol=1e-5
    )
    gf = jax.grad(lambda *a: jnp.sum(fused(*a) ** 2), argnums=(0, 1, 2))(
        x, w, b
    )
    gu = jax.grad(lambda *a: jnp.sum(unfused(*a) ** 2), argnums=(0, 1, 2))(
        x, w, b
    )
    for a, c in zip(gf, gu):
        np.testing.assert_allclose(a, c, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("gmm_impl", ["ragged", "pallas"])
def test_gated_dropless_experts_match_a_per_token_loop(gmm_impl):
    """Gated (SiLU) experts with no biases, softmax over all experts,
    top-8 of 16, weights renormalised over the eight: every token
    against a plain loop over its experts. Nothing is dropped, so each
    token's output is the full weighted sum."""
    e, k, d, f = 16, 8, 32, 24
    layer = MoEFFN(
        num_experts=e, d_ff=f, top_k=k, dispatch_impl="dropless",
        gated=True, use_bias=False, gmm_impl=gmm_impl, gmm_interpret=True,
        gmm_block_m=16, gmm_block_n=8,
    )
    x = jax.random.normal(jax.random.key(1), (2, 9, d), jnp.float32)
    variables = layer.init(jax.random.key(0), x)
    p = variables["params"]
    assert set(p) == {"router", "w_in", "w_gate", "w_out"}  # no biases
    assert "serve_stats" not in variables
    y, sown = layer.apply(variables, x, mutable=["serve_stats"])
    routed = np.asarray(sown["serve_stats"]["expert_idx"][0])  # [tokens, k]

    xs = np.asarray(x, np.float64).reshape(-1, d)
    w_r = np.asarray(p["router"]["kernel"], np.float64)
    w1, w3, w2 = (np.asarray(p[n], np.float64) for n in ("w_gate", "w_in", "w_out"))
    want = np.zeros_like(xs)
    for t, h in enumerate(xs):
        logits = h @ w_r
        prob = np.exp(logits - logits.max())
        prob /= prob.sum()
        top = np.argsort(-prob, kind="stable")[:k]
        assert set(top) == set(routed[t])
        for ex in top:
            a = h @ w1[ex]
            want[t] += prob[ex] / prob[top].sum() * ((a / (1 + np.exp(-a))) * (h @ w3[ex])) @ w2[ex]
    # float32 sums of 24 to 32 products in another order than float64's
    np.testing.assert_allclose(np.asarray(y).reshape(-1, d), want, rtol=2e-5, atol=2e-5)


def test_gated_experts_need_the_dropless_path():
    x = jnp.zeros((1, 4, 8), jnp.float32)
    with pytest.raises(ValueError, match="dropless"):
        MoEFFN(num_experts=4, d_ff=8, gated=True).init(jax.random.key(0), x)
