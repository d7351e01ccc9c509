"""``MoEFFN`` told which experts it holds (models/moe.py): the router of
a shortcut-MoE layer (choice by ``scores + b``, weights without ``b``,
times the routed scale, not renormalised; a zero-compute expert returns
``w * x``), the defaults left bit for bit as they were, and the test
that ties a chip's share to the model (the ``model-configs`` guide's
section 4): at a small size the outputs of every share, with the
zero-compute terms counted once, add up to the uncut reference's output
for the whole layer.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cs744_pytorch_distributed_tutorial_tpu.models.moe import MoEFFN
from perfbench.reference import longcat_flash as R

D, F, ROUTED, ZERO, K = 32, 16, 8, 4, 3
CFG = dict(
    n_routed_experts=ROUTED, zero_expert_num=ZERO, moe_topk=K, routed_scaling_factor=6.0,
    published=dict(n_routed_experts=ROUTED),
)


def _weights(seed=0):
    """The whole layer's weights, under the reference's names."""
    ks = jax.random.split(jax.random.key(seed), 5)
    return {
        "moe/router/kernel": 2.0 * jax.random.normal(ks[0], (D, ROUTED + ZERO)) / D ** 0.5,
        "moe/choice_bias": 0.05 * jax.random.normal(ks[1], (ROUTED + ZERO,)),
        "moe/w_gate": jax.random.normal(ks[2], (ROUTED, D, F)) / D ** 0.5,
        "moe/w_in": jax.random.normal(ks[3], (ROUTED, D, F)) / D ** 0.5,
        "moe/w_out": jax.random.normal(ks[4], (ROUTED, F, D)) / F ** 0.5,
    }


def _layer(held=None, zero=ZERO, **kw):
    return MoEFFN(
        num_experts=ROUTED, d_ff=F, top_k=K, dispatch_impl="dropless", gated=True, use_bias=False,
        held_experts=held, zero_experts=zero, renormalize=False, routed_scale=6.0, choice_bias=True, **kw,
    )


def _params(w, held):
    ids = jnp.asarray(list(range(ROUTED)) if held is None else list(held))
    return {
        "router": {"kernel": w["moe/router/kernel"]}, "choice_bias": w["moe/choice_bias"],
        "w_gate": w["moe/w_gate"][ids], "w_in": w["moe/w_in"][ids], "w_out": w["moe/w_out"][ids],
    }


def _share_of_reference(w, m, held):
    p = {**w, **{k: w[k][jnp.asarray(list(held))] for k in ("moe/w_gate", "moe/w_in", "moe/w_out")}}
    return R.shortcut_moe(p, m, CFG, held=held)


@pytest.fixture(scope="module")
def tokens():
    return jax.random.normal(jax.random.key(9), (2, 24, D))


def test_the_whole_layer_against_the_reference(tokens):
    w = _weights()
    got = _layer().apply({"params": _params(w, None)}, tokens)
    want = R.shortcut_moe(w, tokens.reshape(-1, D), CFG, held=range(ROUTED))
    assert float(jnp.max(jnp.abs(got.reshape(-1, D) - want))) < 1e-5
    assert float(jnp.mean(jnp.abs(want))) > 0.05


@pytest.mark.parametrize("held", [(0, 1), (5,), (2, 7, 4)])
def test_a_share_computes_its_experts_terms_and_every_zero_term(tokens, held):
    w = _weights()
    got = _layer(held=held).apply({"params": _params(w, held)}, tokens)
    want = _share_of_reference(w, tokens.reshape(-1, D), held)
    assert float(jnp.max(jnp.abs(got.reshape(-1, D) - want))) < 1e-5


def test_the_shares_sum_to_the_uncut_layer(tokens):
    """Held sets that partition the routed experts: their outputs, with
    the zero-compute terms (which every share computes, where the token
    lives) counted once, are the uncut reference's output."""
    w = _weights(1)
    m = tokens.reshape(-1, D)
    shares = [(0, 1), (2, 3, 4), (5, 6, 7)]
    outs = [
        _layer(held=h).apply({"params": _params(w, h)}, tokens).reshape(-1, D) for h in shares
    ]
    # the zero-compute terms alone: a share that holds no routed expert's pairs
    zero_terms = R.shortcut_moe(w, m, CFG, held=()) 
    total = sum(outs) - (len(shares) - 1) * zero_terms
    whole = R.shortcut_moe(w, m, CFG, held=range(ROUTED))
    assert float(jnp.max(jnp.abs(total - whole))) < 2e-5
    # and no share alone is the layer
    assert float(jnp.max(jnp.abs(outs[0] - whole))) > 0.1


def test_the_router_chooses_by_biased_scores_and_weighs_by_plain_ones():
    """One token, a router that makes expert 1 the best and a bias that
    lifts experts 3 and the zero-compute expert 9 over it: the layer
    takes what ``scores + b`` ranks first, weighs by ``scores * 6``
    without ``b`` and without renormalising, and the zero-compute
    expert's term is ``w * x``."""
    x = jnp.zeros((1, 1, D)).at[0, 0, 0].set(1.0)
    logits = jnp.asarray([0.0, 3.0, 0.0, 1.0, 0.0, 0.0, 0.0, 0.0, 0.0, 1.0, 0.0, 0.0])
    w = _weights(2)
    w["moe/router/kernel"] = jnp.zeros((D, ROUTED + ZERO)).at[0].set(logits)
    w["moe/choice_bias"] = jnp.zeros((ROUTED + ZERO,)).at[jnp.asarray([3, 9])].set(1.0)
    layer = _layer()
    out, sown = layer.apply({"params": _params(w, None)}, x, mutable=["serve_stats"])
    idx = sown["serve_stats"]["expert_idx"][0]
    assert sorted(np.asarray(idx[0]).tolist()) == [-1, 1, 3]  # 9 is zero-compute: not a held matrix
    scores = jax.nn.softmax(logits)
    m = x[0]

    def expert(e):
        return (jax.nn.silu(m @ w["moe/w_gate"][e]) * (m @ w["moe/w_in"][e])) @ w["moe/w_out"][e]

    want = 6 * (scores[1] * expert(1) + scores[3] * expert(3) + scores[9] * m)
    assert float(jnp.max(jnp.abs(out[0] - want))) < 1e-5
    assert float(6 * (scores[1] + scores[3] + scores[9])) != pytest.approx(1.0)  # not renormalised
    assert [int(sown["serve_stats"][k][0][0]) for k in
            ("held_expert_pairs", "zero_expert_pairs", "absent_expert_pairs")] == [2, 1, 0]


@pytest.mark.parametrize("sizes", [[3, 0, 5, 1], [0, 0, 0, 0], [40, 0, 0, 2], [0, 0, 0, 7], [64, 32, 0, 0]])
def test_the_grouped_matmul_visits_the_live_rows_alone(sizes):
    """``live_only`` (what a share's grouped matmuls run under): groups
    that fill a part of the rows, or none; the rows they fill come out as
    ``ragged_dot`` gives them, values and gradients, the rest is the
    caller's to mask."""
    from cs744_pytorch_distributed_tutorial_tpu.ops.gmm import grouped_matmul

    m, live = 96, sum(sizes)
    x = jax.random.normal(jax.random.key(1), (m, 64))
    w = jax.random.normal(jax.random.key(2), (4, 64, 256)) / 8.0
    gs = jnp.asarray(sizes, jnp.int32)
    mask = (jnp.arange(m) < live)[:, None]

    def loss(x, w, **kw):
        return jnp.sum(jnp.where(mask, grouped_matmul(x, w, gs, **kw), 0.0) ** 2)

    kernel = dict(impl="pallas", interpret=True, block_m=32, block_n=128, live_only=True)
    want, got = grouped_matmul(x, w, gs, impl="ragged"), grouped_matmul(x, w, gs, **kernel)
    assert got.shape == (m, 256) and float(jnp.max(jnp.abs(jnp.where(mask, got - want, 0.0)))) < 1e-4
    for a, b in zip(jax.grad(loss, (0, 1))(x, w, **kernel), jax.grad(loss, (0, 1))(x, w, impl="ragged")):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-2 * (1.0 + float(jnp.max(jnp.abs(b))))


def test_the_column_tile_is_the_kernel_s_to_fit():
    """``ops/gmm.py::fit_block_n`` at the chat cell's shapes: an expert's
    [6144, 2048] matrix beside a row tile of 32 takes 512 columns, beside
    one of 256 only 256; where no candidate divides the width it is the
    asked tile and the kernel's padding; where some divide and none fits,
    it raises."""
    from cs744_pytorch_distributed_tutorial_tpu.ops.gmm import VMEM_BUDGET_BYTES, fit_block_n

    assert VMEM_BUDGET_BYTES == 14 * 2**20
    assert fit_block_n(6144, 2048, 32, 512, 2) == 512 and fit_block_n(6144, 2048, 256, 512, 2) == 256
    assert fit_block_n(2048, 6144, 32, 512, 2) == 512 and fit_block_n(2048, 768, 32, 512, 2) == 384
    assert fit_block_n(64, 100, 32, 512, 4) == 512
    with pytest.raises(ValueError, match="no column tile"):
        fit_block_n(60_000, 2048, 256, 512, 2)


def test_the_share_s_matmuls_run_through_the_kernel_too():
    """The same share through the Pallas kernels (interpreted), whose
    calls visit the held pairs' rows alone, against the reference."""
    w, held = _weights(5), (1, 6)
    x = jax.random.normal(jax.random.key(6), (1, 40, D))
    layer = _layer(held=held, gmm_impl="pallas", gmm_interpret=True)
    got = layer.apply({"params": _params(w, held)}, x)
    assert float(jnp.max(jnp.abs(got[0] - _share_of_reference(w, x[0], held)))) < 1e-4


def test_more_held_pairs_than_the_cap_are_all_computed():
    """Every token chooses the three held experts only (the other
    outputs' scores are sunk), under a router of 16 outputs: 192 held
    pairs where the first branch gathers 160 rows (four times what an
    even router sends to 3 of 16 outputs, in row tiles of 32), so the
    second branch computes them all."""
    w, zero = _weights(3), 8
    width = ROUTED + zero
    router = jnp.zeros((D, width)).at[:, :3].set(w["moe/router/kernel"][:, :3])
    w = {**w, "moe/router/kernel": router, "moe/choice_bias": jnp.zeros((width,)).at[3:].set(-1.0)}
    x = jax.random.normal(jax.random.key(4), (1, 64, D))
    held = (0, 1, 2)
    assert 4 * (64 * K // width) * len(held) <= 160 < 64 * K
    got, sown = _layer(held=held, zero=zero).apply({"params": _params(w, held)}, x, mutable=["serve_stats"])
    assert int(sown["serve_stats"]["held_expert_pairs"][0].sum()) == 64 * K
    p = {**w, **{k: w[k][jnp.asarray(list(held))] for k in ("moe/w_gate", "moe/w_in", "moe/w_out")}}
    want = R.shortcut_moe(p, x[0], {**CFG, "zero_expert_num": zero}, held=held)
    assert float(jnp.max(jnp.abs(got[0] - want))) < 1e-5


def test_the_defaults_are_today_s_layer_bit_for_bit():
    """A layer with none of the new fields set: the same parameters and
    the same output as the formulas it always computed (softmax, top-k,
    renormalised, every expert), for the gated dropless path."""
    layer = MoEFFN(num_experts=4, d_ff=F, top_k=2, dispatch_impl="dropless", gated=True, use_bias=False)
    x = jax.random.normal(jax.random.key(0), (2, 8, D))
    params = layer.init(jax.random.key(1), x)["params"]
    assert sorted(params) == ["router", "w_gate", "w_in", "w_out"]  # no choice_bias
    assert params["w_in"].shape == (4, D, F) and params["router"]["kernel"].shape == (D, 4)
    out, sown = layer.apply({"params": params}, x, mutable=["serve_stats"])
    assert list(sown["serve_stats"]) == ["expert_idx"]
    m = x.reshape(-1, D)
    gates = jax.nn.softmax(m @ params["router"]["kernel"])
    top, idx = jax.lax.top_k(gates, 2)
    top = top / top.sum(-1, keepdims=True)
    want = jnp.zeros_like(m)
    for e in range(4):
        y = (jax.nn.silu(m @ params["w_gate"][e]) * (m @ params["w_in"][e])) @ params["w_out"][e]
        want = want + jnp.where(idx == e, top, 0.0).sum(-1, keepdims=True) * y
    assert float(jnp.max(jnp.abs(out.reshape(-1, D) - want))) < 1e-5
    # the same module with the new fields at their defaults, spelled out, is the same program
    spelled = MoEFFN(
        num_experts=4, d_ff=F, top_k=2, dispatch_impl="dropless", gated=True, use_bias=False,
        held_experts=None, zero_experts=0, renormalize=True, routed_scale=1.0, choice_bias=False,
    )
    assert bool(jnp.array_equal(spelled.apply({"params": params}, x), out))
    a = jax.jit(lambda p, v: layer.apply({"params": p}, v)).lower(params, x).as_text()
    b = jax.jit(lambda p, v: spelled.apply({"params": p}, v)).lower(params, x).as_text()
    assert a == b


@pytest.mark.parametrize("kw, reason", [
    (dict(dispatch_impl="scatter", gated=False), "dropless"),
    (dict(expert_axis="data", expert_axis_size=2), "expert_axis"),
    (dict(held_experts=(0, 9)), "distinct ids of the"),
    (dict(held_experts=()), "at least one"),
    (dict(held_experts=(1, 1)), "distinct ids"),
])
def test_what_a_share_does_not_compose_with_raises(kw, reason):
    args = dict(
        num_experts=ROUTED, d_ff=F, top_k=K, dispatch_impl="dropless", gated=True, use_bias=False,
        held_experts=(0, 1),
    )
    with pytest.raises(ValueError, match=reason):
        MoEFFN(**{**args, **kw}).init(jax.random.key(0), jnp.zeros((1, 4, D)))
