"""DeepSeek-V2 (``model_type: deepseek_v2``) through the normal path:
the group-limited router against a NumPy transcription of the published
gate, the shared experts, YaRN on latent attention against hand values,
the share identity (the 8 shares' routed outputs with the shared experts
counted once are the uncut layer), the plain latent block against the
plain reference (perfbench/reference/deepseek_v2.py), the engine's served
tokens by chunks and absorbed decode (gather and the page walk,
interpreted), the counters, the builder and its refusals; and the
defaults of every new field leaving the programs as they were.

Tolerances as tests/test_latent_attention.py and test_latent_serving.py
have them: float32 on the CPU, the two sides differing in the order of
their sums only.
"""

import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cs744_pytorch_distributed_tutorial_tpu.models import (
    TransformerLM,
    deepseek_v2_model_config,
    model_config_from_hf,
)
from cs744_pytorch_distributed_tutorial_tpu.models.latent import LatentDims
from cs744_pytorch_distributed_tutorial_tpu.models.moe import MoEFFN
from cs744_pytorch_distributed_tutorial_tpu.models.transformer import rope_inv_freq
from cs744_pytorch_distributed_tutorial_tpu.serve import Request, ServeConfig, ServingEngine
from perfbench.reference import deepseek_v2 as R
from perfbench.work_deepseek_v2 import as_published

from deepseek_tiny import build, model_kwargs, tiny_config

ROOT = Path(__file__).resolve().parents[1]
PUBLISHED = json.loads((ROOT / "perfbench/configs/deepseek-v2.json").read_text())

# ---- the router ------------------------------------------------------------------

D, F, E, GROUPS, KEEP, K, SCALE = 32, 8, 160, 8, 3, 6, 16.0


def _gate_numpy(logits, groups=GROUPS, keep=KEEP, k=K, scale=SCALE):
    """``MoEGate.forward`` of the published ``modeling_deepseek.py``
    (``topk_method == "group_limited_greedy"``, ``norm_topk_prob`` false),
    in NumPy: softmax scores; a group's score its best expert's; the
    ``keep`` best groups' experts kept, the others' scores set to 0; the
    top ``k`` of what is left, weighted by score times ``scale``."""
    z = logits - logits.max(-1, keepdims=True)
    scores = np.exp(z) / np.exp(z).sum(-1, keepdims=True)
    n = scores.shape[0]
    group_scores = scores.reshape(n, groups, -1).max(-1)
    group_idx = np.argsort(-group_scores, axis=-1, kind="stable")[:, :keep]
    group_mask = np.zeros_like(group_scores)
    np.put_along_axis(group_mask, group_idx, 1.0, axis=-1)
    score_mask = np.repeat(group_mask, scores.shape[1] // groups, axis=-1)
    tmp = np.where(score_mask > 0, scores, 0.0)
    idx = np.argsort(-tmp, axis=-1, kind="stable")[:, :k]
    return idx, np.take_along_axis(tmp, idx, -1) * scale


def _moe(held=None, experts=E, shared=0, groups=GROUPS, keep=KEEP, k=K, **kw):
    return MoEFFN(
        num_experts=experts, d_ff=F, top_k=k, dispatch_impl="dropless", gated=True, use_bias=False,
        held_experts=held, renormalize=False, routed_scale=SCALE, n_group=groups, topk_group=keep,
        shared_d_ff=shared, **kw,
    )


def _moe_weights(seed=0, experts=E, shared=0):
    ks = jax.random.split(jax.random.key(seed), 7)
    w = {
        "moe/router/kernel": 2.0 * jax.random.normal(ks[0], (D, experts)) / D ** 0.5,
        "moe/w_gate": jax.random.normal(ks[1], (experts, D, F)) / D ** 0.5,
        "moe/w_in": jax.random.normal(ks[2], (experts, D, F)) / D ** 0.5,
        "moe/w_out": jax.random.normal(ks[3], (experts, F, D)) / F ** 0.5,
    }
    if shared:
        w["moe/shared_gate/kernel"] = jax.random.normal(ks[4], (D, shared)) / D ** 0.5
        w["moe/shared_in/kernel"] = jax.random.normal(ks[5], (D, shared)) / D ** 0.5
        w["moe/shared_out/kernel"] = jax.random.normal(ks[6], (shared, D)) / shared ** 0.5
    return w


def _params(w, held):
    ids = jnp.asarray(list(range(w["moe/w_in"].shape[0])) if held is None else list(held))
    p = {"router": {"kernel": w["moe/router/kernel"]}}
    p.update({k: w[f"moe/{k}"][ids] for k in ("w_gate", "w_in", "w_out")})
    for name in ("shared_gate", "shared_in", "shared_out"):
        if f"moe/{name}/kernel" in w:
            p[name] = {"kernel": w[f"moe/{name}/kernel"]}
    return p


def _experts_numpy(w, m, idx, weight, held=None):
    """sum over a token's chosen experts (those held) of weight x SwiGLU."""
    m = np.asarray(m, np.float64)
    out = np.zeros_like(m)
    silu = lambda v: v / (1.0 + np.exp(-v))
    for t in range(m.shape[0]):
        for e, g in zip(idx[t], weight[t]):
            if held is not None and e not in held:
                continue
            wg, wi, wo = (np.asarray(w[f"moe/{n}"][e], np.float64) for n in ("w_gate", "w_in", "w_out"))
            out[t] += g * ((silu(m[t] @ wg) * (m[t] @ wi)) @ wo)
    return out


def test_group_limited_choice_against_the_published_gate():
    """One token whose plain top-6 holds five experts of group 3, which is
    not among its three best groups (its best expert is the fourth
    group's best), and 40 seeded tokens: the layer chooses what the
    published gate chooses, weighs by score x 16, not renormalised."""
    logits = np.full((E,), -1.0)
    logits[5], logits[27], logits[44] = 5.0, 3.0, 2.5  # the best of groups 0, 1, 2
    logits[61:66] = 2.0  # five experts of group 3, all under group 2's best
    w = _moe_weights(1)
    router = np.array(w["moe/router/kernel"])
    router[0] = logits  # the token e_0 reads row 0 of the router
    w["moe/router/kernel"] = jnp.asarray(router)
    x = np.array(jax.random.normal(jax.random.key(2), (1, 41, D)))
    x[0, 0] = 0.0
    x[0, 0, 0] = 1.0
    out, sown = _moe().apply({"params": _params(w, None)}, jnp.asarray(x), mutable=["serve_stats"])
    got_idx = np.asarray(sown["serve_stats"]["expert_idx"][0])
    m = x[0]
    want_idx, want_w = _gate_numpy(m @ router)
    plain_idx, _ = _gate_numpy(m @ router, groups=1, keep=1)
    assert set(plain_idx[0]) == {5, 27, 44, 61, 62, 63} and set(want_idx[0]) != set(plain_idx[0])
    assert set(want_idx[0]) >= {5, 27, 44} and all(e < 60 for e in want_idx[0])
    assert [set(a) for a in got_idx] == [set(a) for a in want_idx]
    want = _experts_numpy(w, m, want_idx, want_w)
    assert np.max(np.abs(np.asarray(out[0]) - want)) < 1e-4 and np.mean(np.abs(want)) > 0.1
    # the weights are scores x 16, not renormalised: their sums are neither 1 nor 16, and vary
    sums = want_w.sum(-1)
    assert sums[0] < 0.9 * SCALE and np.ptp(sums) > 1.0 and not np.allclose(sums, 1.0)


def test_the_reference_routes_as_the_published_gate():
    w = _moe_weights(3)
    m = np.asarray(jax.random.normal(jax.random.key(4), (50, D)))
    top, weight = R._route(jnp.asarray(m), w["moe/router/kernel"], K, GROUPS, KEEP, SCALE, None)
    want_idx, want_w = _gate_numpy(m @ np.asarray(w["moe/router/kernel"], np.float64))
    assert [set(a) for a in np.asarray(top)] == [set(a) for a in want_idx]
    assert np.max(np.abs(np.sort(np.asarray(weight), -1) - np.sort(want_w, -1))) < 1e-5


def test_shared_experts_are_added_once():
    """The layer's output is the routed sum plus ONE SwiGLU of the shared
    width over every token; the same layer with the shared kernels zeroed
    is the routed sum alone."""
    w = _moe_weights(5, shared=16)
    x = jax.random.normal(jax.random.key(6), (2, 12, D))
    layer = _moe(shared=16)
    out = layer.apply({"params": _params(w, None)}, x)
    zeroed = {**w, "moe/shared_out/kernel": jnp.zeros_like(w["moe/shared_out/kernel"])}
    routed = layer.apply({"params": _params(zeroed, None)}, x)
    m = np.asarray(x.reshape(-1, D), np.float64)
    silu = lambda v: v / (1.0 + np.exp(-v))
    s = (silu(m @ np.asarray(w["moe/shared_gate/kernel"])) * (m @ np.asarray(w["moe/shared_in/kernel"]))) @ np.asarray(
        w["moe/shared_out/kernel"])
    assert np.max(np.abs(np.asarray((out - routed).reshape(-1, D)) - s)) < 1e-4 and np.mean(np.abs(s)) > 0.1
    cfg = dict(n_routed_experts=E, num_experts_per_tok=K, routed_scaling_factor=SCALE, topk_method="group_limited_greedy",
               n_group=GROUPS, topk_group=KEEP, n_shared_experts=2)
    want = R.moe(w, x.reshape(-1, D), cfg, held=range(E))
    assert float(jnp.max(jnp.abs(out.reshape(-1, D) - want))) < 1e-4


def test_the_shares_sum_to_the_uncut_layer():
    """The deployment's shares (one routing group a chip, 8 chips): their
    outputs, with the shared experts (which every share computes, where
    the token lives) counted once, are the uncut reference's layer; no
    share alone is."""
    w = _moe_weights(7, shared=16)
    x = jax.random.normal(jax.random.key(8), (1, 64, D))
    per = E // GROUPS
    shares = [tuple(range(g * per, (g + 1) * per)) for g in range(GROUPS)]
    outs = [_moe(held=h, shared=16).apply({"params": _params(w, h)}, x)[0] for h in shares]
    cfg = dict(n_routed_experts=E, num_experts_per_tok=K, routed_scaling_factor=SCALE, topk_method="group_limited_greedy",
               n_group=GROUPS, topk_group=KEEP, n_shared_experts=2)
    shared_alone = R.moe(w, x[0], cfg, held=())
    whole = R.moe(w, x[0], cfg, held=range(E))
    total = sum(outs) - (GROUPS - 1) * shared_alone
    assert float(jnp.max(jnp.abs(total - whole))) < 5e-5
    assert float(jnp.max(jnp.abs(outs[0] - whole))) > 0.1
    # a share's output, as the reference computes that share
    p0 = {**w, **{k: w[k][jnp.asarray(shares[2])] for k in ("moe/w_gate", "moe/w_in", "moe/w_out")}}
    assert float(jnp.max(jnp.abs(outs[2] - R.moe(p0, x[0], cfg, held=shares[2])))) < 1e-5


def test_held_group_tokens_counts_tokens_whose_kept_groups_hold_the_share():
    w = _moe_weights(9)
    x = jax.random.normal(jax.random.key(10), (1, 80, D))
    held = tuple(range(20, 40))  # group 1
    _, sown = _moe(held=held).apply({"params": _params(w, held)}, x, mutable=["serve_stats"])
    got = np.asarray(sown["serve_stats"]["held_group_tokens"][0])
    scores = np.asarray(jax.nn.softmax(x[0] @ w["moe/router/kernel"], -1))
    kept = np.argsort(-scores.reshape(80, GROUPS, -1).max(-1), axis=-1, kind="stable")[:, :KEEP]
    assert got.tolist() == (kept == 1).any(-1).astype(int).tolist() and 0 < got.sum() < 80
    # no group-limited choice, no counter
    _, plain = _moe(held=held, groups=1, keep=1).apply({"params": _params(w, held)}, x, mutable=["serve_stats"])
    assert "held_group_tokens" not in plain["serve_stats"]


@pytest.mark.parametrize("kw, reason", [
    (dict(groups=7), "to divide"),
    (dict(keep=9), "topk_group"),
    (dict(keep=1, k=21), "top_k"),
    (dict(zero_experts=4), "no zero-compute experts"),
    (dict(choice_bias=True), "no choice_bias"),
    (dict(shared=16, dispatch_impl="scatter", gated=False), "shared experts"),
])
def test_what_group_choice_and_shared_experts_do_not_compose_with_raises(kw, reason):
    args = dict(experts=E)
    for name in ("groups", "keep", "k", "shared"):
        if name in kw:
            args[name] = kw.pop(name)
    layer = _moe(**args)
    layer = layer.clone(**kw) if kw else layer
    with pytest.raises(ValueError, match=reason):
        layer.init(jax.random.key(0), jnp.zeros((1, 4, D)))


def test_the_new_fields_at_their_defaults_are_today_s_layer():
    """``n_group`` / ``topk_group`` 1 and ``shared_d_ff`` 0 spelled out:
    the same parameters and the same lowered program as a layer that
    names none of them."""
    plain = MoEFFN(num_experts=8, d_ff=F, top_k=2, dispatch_impl="dropless", gated=True, use_bias=False,
                   held_experts=(0, 3), renormalize=False, routed_scale=6.0)
    spelled = plain.clone(n_group=1, topk_group=1, shared_d_ff=0)
    x = jax.random.normal(jax.random.key(0), (2, 8, D))
    params = plain.init(jax.random.key(1), x)["params"]
    assert sorted(params) == ["router", "w_gate", "w_in", "w_out"]
    a = jax.jit(lambda p, v: plain.apply({"params": p}, v)).lower(params, x).as_text()
    b = jax.jit(lambda p, v: spelled.apply({"params": p}, v)).lower(params, x).as_text()
    assert a == b


# ---- YaRN on latent attention ------------------------------------------------------

def test_yarn_on_the_rope_dimensions_by_hand():
    """The published rope_scaling (factor 40 over 4,096, beta 32 / 1,
    mscale 0.707 twice) at rope width 64, base 1e4: frequencies 0-10
    kept, 23-31 divided by 40, 11-22 blended by (j - 10) / 13; cos and
    sin at factor 1; the whole score at (0.1 x 0.707 x ln 40 + 1)^2 =
    1.5896, so the softmax scale is 0.11472."""
    kw = deepseek_v2_model_config(as_published(PUBLISHED)[0])
    dims = kw["latent"]
    assert (dims.q_lora_rank, dims.kv_lora_rank, dims.qk_nope_head_dim, dims.qk_rope_head_dim, dims.v_head_dim) == (
        1536, 512, 128, 64, 128)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert m == pytest.approx(1.26081, abs=1e-5)
    assert dims.score_factor == pytest.approx(1.5896, abs=1e-4) == m * m
    assert dims.score_factor / math.sqrt(192) == pytest.approx(0.11472, abs=1e-5)
    assert dims.rope_scaling.attention_factor == 1.0 and dims.scale_q == dims.scale_kv == 1.0
    freqs, factor = rope_inv_freq(64, 1e4, dims.rope_scaling)
    plain = 1e4 ** (-np.arange(32) / 32)
    ramp = np.clip((np.arange(32) - 10) / 13, 0, 1)
    assert factor == 1.0
    np.testing.assert_allclose(np.asarray(freqs), plain * (1 - ramp + ramp / 40), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(freqs)[:11], plain[:11], rtol=1e-5)
    np.testing.assert_allclose(np.asarray(freqs)[23:], plain[23:] / 40, rtol=1e-5)
    # the reference's own transcription agrees
    inv, c_rope, score = R.yarn(PUBLISHED)
    np.testing.assert_allclose(inv, np.asarray(freqs), rtol=1e-5)
    assert c_rope == 1.0 and score == pytest.approx(dims.score_factor)


# ---- the model ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config()
    return (cfg, *build(cfg))


@pytest.mark.parametrize("length", [1, 17, 40, 100])
def test_the_whole_model_s_logits_against_the_reference_s(tiny, length):
    """A dense layer, then two routed ones with a share of 4 of 16
    experts, YaRN at a context past its 32 original positions."""
    cfg, model, params, flat = tiny
    toks = np.asarray(jax.random.randint(jax.random.key(length), (length,), 0, 256))
    got = model.apply({"params": params}, toks[None])[0]
    want = R.forward(flat, toks, cfg)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4
    assert float(jnp.mean(jnp.abs(want))) > 0.1


def test_the_blocks_are_plain_the_first_dense(tiny):
    _, _, params, _ = tiny
    assert sorted(params["block_0"]) == ["attn", "ln_attn", "ln_ffn", "mlp_gate", "mlp_in", "mlp_out"]
    assert sorted(params["block_1"]) == ["attn", "ln_attn", "ln_ffn", "moe"]
    assert sorted(params["block_2"]["moe"]) == [
        "router", "shared_gate", "shared_in", "shared_out", "w_gate", "w_in", "w_out"]
    assert params["block_1"]["moe"]["w_in"].shape == (4, 64, 16)  # the held share
    assert params["block_1"]["moe"]["router"]["kernel"].shape == (64, 16)  # the router whole
    assert params["block_1"]["moe"]["shared_in"]["kernel"].shape == (64, 32)  # two shared experts as one


@pytest.mark.parametrize("fault", R.FAULTS)
def test_every_planted_fault_moves_the_reference_s_logits(tiny, fault):
    cfg, _, _, flat = tiny
    toks = np.asarray(jax.random.randint(jax.random.key(1), (60,), 0, 256))
    assert float(jnp.max(jnp.abs(R.forward(flat, toks, cfg, fault=fault) - R.forward(flat, toks, cfg)))) > 0.05


# ---- served by chunks ---------------------------------------------------------------

LENGTHS = ((70, 12), (23, 9), (41, 20), (9, 5), (64, 8))
SERVE = dict(num_slots=3, page_size=8, num_pages=49, max_pages_per_slot=14, prefill_chunk=12)


def _serve(model, params, **cfg):
    engine = ServingEngine(model, params, ServeConfig(**{**SERVE, **cfg}))
    rng = np.random.default_rng(0)
    reqs = [
        engine.submit(Request(prompt=rng.integers(0, 256, n).astype(np.int32), max_new_tokens=m))
        for n, m in LENGTHS
    ]
    engine.run()
    return engine, reqs


def _served_gap(flat, cfg, req):
    seq = np.concatenate([req.prompt, np.asarray(req.generated, np.int32)])
    lo, hi = req.orig_prompt_len - 1, len(seq) - 1
    ref = R.forward(flat, seq, cfg, at=np.arange(lo, hi))
    served = jnp.asarray(seq[lo + 1: hi + 1])
    return float(jnp.max(jnp.max(ref, -1) - jnp.take_along_axis(ref, served[:, None], -1)[:, 0]))


@pytest.fixture(scope="module")
def served(tiny):
    _, model, params, _ = tiny
    return _serve(model, params, paged_attention_impl="gather")


def test_chunks_then_absorbed_decode_serve_the_reference_s_tokens(tiny, served):
    cfg, _, _, flat = tiny
    engine, reqs = served
    assert all(r.status == "completed" and len(r.generated) == m for r, (_, m) in zip(reqs, LENGTHS))
    for r in reqs:
        assert _served_gap(flat, cfg, r) < 1e-4
    assert engine.stats()["prefill_chunks"] == sum(-(-n // 12) for n, _ in LENGTHS)


def test_the_page_walk_serves_the_same_tokens(tiny, served):
    """The chunk's and decode's Pallas walks (interpret mode) serve the
    gather reference's tokens; the chunk walk's causal pairs a layer are
    counted at dispatch, and none under "gather"."""
    cfg, _, params, flat = tiny
    model, _, _ = build(cfg, flash_interpret=True)
    engine, reqs = _serve(model, params, paged_attention_impl="kernel")
    assert [list(r.generated) for r in reqs] == [list(r.generated) for r in served[1]]
    assert _served_gap(flat, cfg, reqs[2]) < 1e-4
    assert engine.stats()["preemptions"] == 0
    chunk = SERVE["prefill_chunk"]
    assert engine.stats()["chunk_attn_pairs"] == sum(
        n * off + n * (n + 1) // 2
        for p, _ in LENGTHS for off in range(0, p, chunk) for n in [min(chunk, p - off)]
    )
    assert served[0].stats()["chunk_attn_pairs"] == 0


def test_one_latent_pool_a_layer(served):
    engine, _ = served
    pools = jax.tree_util.tree_leaves_with_path(engine._pages)
    assert {path[-1].key for path, _ in pools} == {"latent_pages"}
    assert [leaf.shape for _, leaf in pools] == [(49, 8, 128)] * 3  # 3 layers, a row of 40 in 128 lanes
    assert engine.pool.check_invariants() and engine.pool.allocated_pages == 0


def test_the_counters_of_the_plain_block_and_of_the_group(tiny):
    """Behind the step's tokens, no new transfer: latent rows attended (a
    slot at depth L reads L + 1 rows a layer: one attention a layer), the
    (token, expert) pairs by where the expert is (``top_k`` a token an
    MoE layer, none zero-compute), held experts hit, and the tokens whose
    kept groups include the held group."""
    cfg, model, params, _ = tiny
    engine = ServingEngine(model, params, ServeConfig(**SERVE, paged_attention_impl="gather"))
    prompts = (21, 8, 13)
    rng = np.random.default_rng(5)
    for n in prompts:
        engine.submit(Request(prompt=rng.integers(0, 256, n).astype(np.int32), max_new_tokens=4))
    engine.run()
    stats = engine.stats()
    assert engine._counter_names == (
        "selected_tokens", "scored_tokens", "experts_hit", "expert_ratio_milli", "latent_tokens_read",
        "held_expert_pairs", "zero_expert_pairs", "absent_expert_pairs", "held_group_tokens",
    )
    depths = [n + i for n in prompts for i in range(3)]  # three decode steps a request
    moe_layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    assert stats["latent_tokens_read"] == cfg["num_hidden_layers"] * sum(d + 1 for d in depths)
    pairs = [stats[k] for k in ("held_expert_pairs", "zero_expert_pairs", "absent_expert_pairs")]
    assert sum(pairs) == cfg["num_experts_per_tok"] * moe_layers * len(depths) and pairs[1] == 0
    assert 0 < stats["held_group_tokens"] <= moe_layers * len(depths)
    # a token that chose a held expert kept the held group
    assert stats["held_group_tokens"] * cfg["num_experts_per_tok"] >= pairs[0] > 0
    assert 0 < stats["experts_hit"] <= min(pairs[0], 4 * moe_layers * stats["decode_steps"])


# ---- the builder ----------------------------------------------------------------------

def test_the_builder_reads_the_published_keys():
    published, held = as_published(PUBLISHED)
    assert held == tuple(range(20)) and published["n_routed_experts"] == 160
    kw = model_config_from_hf(published, held_experts=held)
    assert kw == deepseek_v2_model_config(published, held_experts=held)
    assert (kw["num_layers"], kw["num_heads"], kw["d_model"], kw["vocab_size"]) == (5, 128, 5120, 12800)
    assert (kw["d_ff"], kw["dense_d_ff"], kw["dense_layers"], kw["latent_block"]) == (1536, 12288, 1, "plain")
    assert (kw["num_experts"], kw["moe_top_k"], kw["moe_n_group"], kw["moe_topk_group"]) == (160, 6, 8, 3)
    assert (kw["moe_shared_d_ff"], kw["moe_routed_scale"], kw["moe_renormalize"]) == (3072, 16.0, False)
    assert kw["moe_held_experts"] == tuple(range(20)) and kw["max_seq_len"] == 163840
    assert kw["norm_eps"] == 1e-6 and kw["rope_base"] == 10000.0 and kw["tie_embeddings"] is False
    # greedy: one group; renormalised weights carry no scale, as the published code has it
    greedy = deepseek_v2_model_config({**published, "topk_method": "greedy", "norm_topk_prob": True})
    assert (greedy["moe_n_group"], greedy["moe_topk_group"], greedy["moe_routed_scale"]) == (1, 1, 1.0)
    assert greedy["moe_held_experts"] is None and greedy["moe_renormalize"] is True
    # no scaling: the plain rotation and scale
    assert deepseek_v2_model_config({**published, "rope_scaling": None})["latent"] == LatentDims(
        1536, 512, 128, 64, 128)


@pytest.mark.parametrize("change, reason", [
    (dict(scoring_func="sigmoid"), "scoring_func"),
    (dict(topk_method="noaux_tc"), "topk_method"),
    (dict(moe_layer_freq=2), "moe_layer_freq"),
    (dict(q_lora_rank=None), "q_lora_rank"),
    (dict(rope_scaling=dict(type="linear", factor=2.0)), "rope_scaling of type"),
    (dict(hidden_act="gelu"), "hidden_act"),
    (dict(attention_bias=True), "attention_bias"),
    (dict(tie_word_embeddings=True), "tied embeddings"),
])
def test_the_builder_refuses_what_is_not_built(change, reason):
    with pytest.raises(ValueError, match=reason):
        deepseek_v2_model_config({**as_published(PUBLISHED)[0], **change})


@pytest.mark.parametrize("overrides, reason", [
    (dict(attention_impl="flash"), "one head width"),
    (dict(quant_kv_cache=True), "no int8 rows"),
    (dict(quant_dense=True), "float kernels"),
    (dict(tensor_axis="model", tensor_axis_size=2), "do not shard the pool"),
    (dict(scan_layers=True), "built unrolled"),
    (dict(layer_types=("full_attention",) * 3, window=8), "no window"),
    (dict(indexer_heads=2, sparse_topk=4), "no indexer"),
    (dict(expert_axis="data", expert_axis_size=2), "moe_held_experts"),
    (dict(dense_d_ff=None), "dense MLP of dense_d_ff"),
    (dict(dense_layers=4), "dense MLP of dense_d_ff"),
    (dict(num_experts=0), "dense MLP of dense_d_ff"),
    (dict(latent_block="double"), "'plain'"),
    (dict(norm="layernorm"), "RMSNorm"),
])
def test_each_unbuilt_combination_of_the_plain_block_raises_with_its_reason(overrides, reason):
    model = TransformerLM(**{**model_kwargs(tiny_config()), **overrides})
    with pytest.raises(ValueError, match=reason):
        model.init(jax.random.key(0), jnp.zeros((1, 4), jnp.int32))


def test_the_engine_refuses_the_one_shot_prefill(tiny):
    _, model, params, _ = tiny
    with pytest.raises(ValueError, match="served by chunks"):
        ServingEngine(model, params, ServeConfig(num_slots=2, page_size=8, num_pages=9, max_pages_per_slot=4))


def test_the_group_and_shared_options_belong_to_the_latent_layers():
    model = TransformerLM(
        vocab_size=32, num_layers=1, num_heads=2, d_model=16, d_ff=32, num_experts=4,
        moe_dispatch="dropless", moe_shared_d_ff=8, attention_impl="dense",
    )
    with pytest.raises(ValueError, match="plain latent block"):
        model.init(jax.random.key(0), jnp.zeros((1, 4), jnp.int32))
