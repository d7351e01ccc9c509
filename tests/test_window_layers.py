"""Layers that differ by kind in one ``TransformerLM``: sliding-window
and full attention mixed (``layer_types``), default RoPE on the window
layers and YaRN on the full ones, against the plain reference
(``perfbench/reference/mellum2.py``, nothing shared with the program) on
seeded weights; the rotary frequencies against their closed form; and
each combination that is not built raising with its reason."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cs744_pytorch_distributed_tutorial_tpu.models import (
    TransformerLM,
    mellum_model_config,
    model_config_from_hf,
)
from cs744_pytorch_distributed_tutorial_tpu.models.transformer import (
    Attention,
    RopeScaling,
    apply_rope,
    rope_inv_freq,
)
from cs744_pytorch_distributed_tutorial_tpu.serve import (
    ServeConfig,
    ServingEngine,
)
from perfbench.reference import mellum2 as R

from mellum_tiny import WINDOW, build, tiny_config

PUBLISHED = RopeScaling(16.0, 8192, 32.0, 1.0, 1.2772588722239782)


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config()
    return (cfg, *build(cfg))


def test_full_forward_matches_the_plain_reference(tiny):
    """Two periods, window 8, T 40: five windows deep, so every window
    layer masks and every full layer rotates by YaRN past its blend."""
    cfg, model, params, flat = tiny
    tokens = np.random.default_rng(0).integers(0, 256, 40).astype(np.int32)
    got = model.apply({"params": params}, tokens[None])[0]
    want = R.forward(flat, tokens, cfg)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4
    # and each planted fault of the reference moves its logits
    for fault in ("window_as_full", "window_short", "window_long", "rope_default", "drop_expert"):
        other = R.forward(flat, tokens, cfg, fault=fault)
        assert float(jnp.max(jnp.abs(other - want)[-1])) > 1e-3, fault


def test_a_window_layer_sees_the_window_and_no_further(tiny):
    """Changing a token more than a window behind the last position
    moves no window layer's output there; the model as a whole does
    move, through its full layers."""
    cfg, model, params, _ = tiny
    window_only = model.clone(
        num_layers=3, layer_types=("sliding_attention",) * 3
    )
    sub = {k: v for k, v in params.items() if k not in (
        "block_3", "block_4", "block_5", "block_6", "block_7")}
    rng = np.random.default_rng(1)
    a = rng.integers(0, 256, 40).astype(np.int32)
    b = a.copy()
    # three window layers reach 3 * (WINDOW - 1) back
    b[40 - 1 - 3 * (WINDOW - 1) - 1] = (b[40 - 1 - 3 * (WINDOW - 1) - 1] + 1) % 256
    la = window_only.apply({"params": sub}, a[None])[0, -1]
    lb = window_only.apply({"params": sub}, b[None])[0, -1]
    assert np.array_equal(np.asarray(la), np.asarray(lb))
    b2 = a.copy()
    b2[40 - 1 - 3 * (WINDOW - 1)] = (b2[40 - 1 - 3 * (WINDOW - 1)] + 1) % 256
    assert not np.array_equal(
        np.asarray(la), np.asarray(window_only.apply({"params": sub}, b2[None])[0, -1])
    )
    fa = model.apply({"params": params}, a[None])[0, -1]
    fb = model.apply({"params": params}, b[None])[0, -1]
    assert float(jnp.max(jnp.abs(fa - fb))) > 1e-4


# ---- RoPE ------------------------------------------------------------------

def test_yarn_frequencies_at_the_published_parameters():
    """(128, 5e5, 16, 8192, 32, 1): frequencies 0-18 kept, 35-63 divided
    by 16, those between blended; cos and sin carry 1.2772588722239782."""
    dim = lambda r: 128 * math.log(8192 / (2 * math.pi * r)) / (2 * math.log(5e5))
    assert 18 < dim(32) < 19 and 34 < dim(1) < 35
    low, high = 18, 35
    i = np.arange(64, dtype=np.float64)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    want = 5e5 ** (-i / 64) * ((1 - ramp) + ramp / 16)
    freqs, scale = rope_inv_freq(128, 5e5, PUBLISHED)
    np.testing.assert_allclose(np.asarray(freqs), want, rtol=2e-6)
    assert scale == 1.2772588722239782 == pytest.approx(0.1 * math.log(16) + 1)
    assert np.array_equal(np.asarray(freqs[:19]), np.asarray(rope_inv_freq(128, 5e5)[0][:19]))
    np.testing.assert_allclose(np.asarray(freqs[35:]) * 16, np.asarray(rope_inv_freq(128, 5e5)[0][35:]), rtol=1e-6)
    # attention_factor left out is the paper's 0.1 ln(factor) + 1
    assert rope_inv_freq(128, 5e5, PUBLISHED._replace(attention_factor=None))[1] == pytest.approx(scale)
    # the reference computes the same, on its own
    ref_freqs, ref_scale = R.yarn_inv_freq(128, dict(
        rope_type="yarn", rope_theta=5e5, factor=16, original_max_position_embeddings=8192,
        beta_fast=32, beta_slow=1, attention_factor=1.2772588722239782,
    ))
    np.testing.assert_allclose(np.asarray(freqs), ref_freqs, rtol=2e-6)
    assert ref_scale == scale


def _rope_before(x, positions, base=10000.0):
    """``apply_rope`` as it stood before it took a scaling."""
    half = x.shape[-1] // 2
    freqs = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[..., :, None] * freqs
    sin = jnp.sin(angles)[..., None, :]
    cos = jnp.cos(angles)[..., None, :]
    if angles.ndim == 2:
        sin, cos = sin[None], cos[None]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1).astype(x.dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("per_slot", [False, True])
def test_default_rope_is_unchanged_bit_for_bit(dtype, per_slot):
    x = jax.random.normal(jax.random.key(0), (3, 5, 4, 32)).astype(dtype)
    pos = jnp.arange(5) + 1000 if not per_slot else jnp.array([[7], [4000], [31]]) + jnp.arange(5)
    for base in (10000.0, 5e5):
        got, want = apply_rope(x, pos, base), _rope_before(x, pos, base)
        assert np.array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))
    # and the traced computation is the same one
    assert str(jax.make_jaxpr(lambda x: apply_rope(x, pos, 5e5))(x)) == str(
        jax.make_jaxpr(lambda x: _rope_before(x, pos, 5e5))(x)
    )


def test_yarn_rope_scales_cos_and_sin():
    x = jnp.ones((1, 2, 1, 128), jnp.float32)
    got = apply_rope(x, jnp.zeros((2,), jnp.int32), 5e5, PUBLISHED)
    np.testing.assert_allclose(np.asarray(got), 1.2772588722239782, rtol=1e-6)


# ---- the published keys ------------------------------------------------------

def test_mellum_model_config_maps_the_published_keys():
    import json
    from pathlib import Path

    hf = json.loads((Path(__file__).parents[1] / "perfbench/configs/mellum2-12b-a2.5b.json").read_text())
    kw = mellum_model_config(hf, max_seq_len=16896)
    assert kw == model_config_from_hf(hf, max_seq_len=16896)
    assert (kw["num_layers"], kw["d_model"], kw["num_heads"], kw["num_kv_heads"], kw["head_dim"]) == (8, 2304, 32, 4, 128)
    assert (kw["num_experts"], kw["moe_top_k"], kw["d_ff"], kw["vocab_size"]) == (64, 8, 896, 98304)
    assert kw["layer_types"] == ("sliding_attention",) * 3 + ("full_attention",) + ("sliding_attention",) * 3 + ("full_attention",)
    assert kw["window"] == 1024 and kw["rope_base"] == 5e5 and kw["window_rope_base"] == 5e5
    assert kw["rope_scaling"] == PUBLISHED
    assert kw["qk_norm"] and kw["moe_dispatch"] == "dropless" and not kw["tie_embeddings"]
    assert mellum_model_config(hf)["max_seq_len"] == 131072
    with pytest.raises(ValueError, match="no builder for model_type"):
        model_config_from_hf({**hf, "model_type": "other"})


@pytest.mark.parametrize("key,bad,match", [
    ("mlp_layer_types", ["dense"] * 8, "dense layers among the routed"),
    ("norm_topk_prob", False, "norm_topk_prob"),
    ("attention_bias", True, "attention_bias"),
    ("layer_types", ["full_attention"], "layer_types names 1 layers"),
    ("use_sliding_window", False, "sliding_attention layers need"),
    ("rope_parameters", {"full_attention": {"rope_type": "llama3", "rope_theta": 1.0}}, "rope_type 'llama3'"),
    ("rope_parameters", {"full_attention": {"rope_theta": 1.0},
                         "sliding_attention": {"rope_type": "yarn", "rope_theta": 1.0, "factor": 2,
                                               "original_max_position_embeddings": 8}}, "default RoPE"),
])
def test_mellum_model_config_refuses_what_is_not_built(key, bad, match):
    with pytest.raises(ValueError, match=match):
        mellum_model_config({**tiny_config(), key: bad})


# ---- what is not built raises, with its reason -------------------------------

def _apply(model, mode="train", **kw):
    tokens = jnp.zeros((1, 4), jnp.int32)
    return jax.eval_shape(lambda: model.init(jax.random.key(0), tokens, mode=mode, **kw))


@pytest.mark.parametrize("overrides,mode,match", [
    (dict(scan_layers=True, num_experts=0, mlp="gelu"), "train", "scan_layers runs ONE block body"),
    (dict(), "prefill", "dense cache of every position"),
    (dict(), "decode", "dense cache of every position"),
    (dict(attention_impl="flash"), "train", "has no window"),
    (dict(quant_kv_cache=True), "train", "no int8 KV"),
    (dict(tensor_axis="model", tensor_axis_size=2), "train", "no tensor or sequence axis"),
    (dict(seq_axis="seq", seq_axis_size=2, attention_impl="ring"), "train", "no tensor or sequence axis"),
    (dict(window=None), "train", "need a window"),
    (dict(layer_types=("sliding_attention", "other")), "train", "layer_types must name"),
    (dict(page_size=4, num_pages=9, window_num_pages=9), "paged_decode", "needs first_pos"),
])
def test_unbuilt_combinations_raise(overrides, mode, match):
    cfg = tiny_config()
    model = TransformerLM(**{**mellum_model_config(cfg, max_seq_len=64), **overrides})
    kw = {}
    if mode in ("decode", "paged_decode"):
        kw["decode_pos"] = jnp.zeros((1,), jnp.int32) if mode == "paged_decode" else jnp.int32(0)
    if mode == "paged_decode":
        kw["page_table"] = jnp.zeros((1, 4), jnp.int32)
    with pytest.raises(ValueError, match=match):
        _apply(model, mode, **kw)


def test_a_window_needs_a_positive_width_and_the_kernel_both_arguments():
    from cs744_pytorch_distributed_tutorial_tpu.ops.paged_attention import paged_attention

    with pytest.raises(ValueError, match="window must be >= 1"):
        jax.eval_shape(lambda: Attention(num_heads=2, window=0).init(
            jax.random.key(0), jnp.zeros((1, 4, 32))))
    q, pool = jnp.zeros((2, 1, 2, 64)), jnp.zeros((9, 4, 128))
    table, pos = jnp.zeros((2, 3), jnp.int32), jnp.zeros((2,), jnp.int32)
    with pytest.raises(ValueError, match="both window and first_pos"):
        paged_attention(q, pool, pool, table, pos, window=8, interpret=True)
    with pytest.raises(ValueError, match="multiple of 128 lanes"):
        paged_attention(q[..., :32], pool[..., :64], pool[..., :64], table, pos, window=8,
                        first_pos=pos, interpret=True)


def test_the_engine_refuses_window_layers_without_chunks(tiny):
    _, model, params, _ = tiny
    with pytest.raises(ValueError, match="served by chunks"):
        ServingEngine(model, params, ServeConfig(num_slots=2, page_size=4, num_pages=33, max_pages_per_slot=8))


def test_serve_cli_takes_a_published_config_under_either_spelling():
    """``--model-config`` picks the builder by ``model_type``;
    ``--keye-config`` is the same flag, with no code path of its own."""
    from cs744_pytorch_distributed_tutorial_tpu.serve_cli import build_parser

    a = build_parser().parse_args(["--model-config", "x.json"])
    b = build_parser().parse_args(["--keye-config", "x.json"])
    assert a.model_config == b.model_config == "x.json" and not hasattr(a, "keye_config")
    hf = {**tiny_config(), "model_type": "KeyeVL2", "rope_theta": 1e4, "sa_config": None, "use_sliding_window": False}
    assert model_config_from_hf(hf, max_seq_len=64)["sparse_topk"] == 0  # the other builder
