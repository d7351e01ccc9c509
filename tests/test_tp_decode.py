"""Tensor-parallel decode: generation/beam on tensor-SHARDED params.

Round 1's generation required gathered full params
(``LMTrainer.decode_model``) — the one strategy-family composition
hole. The ``mesh=`` path added to ``make_generator`` /
``make_beam_searcher`` runs the whole sampling loop inside shard_map:
each device projects and caches its local heads, and the per-sublayer
psums keep the logits replicated. These tests pin exact token parity
against the gathered path on a tensor=2 mesh.
"""

from __future__ import annotations

import jax
import numpy as np
import pytest

# Tensor-parallel decode engines: heavy compile per case.
pytestmark = pytest.mark.slow


def _make_trainer(mesh, tensor):
    from cs744_pytorch_distributed_tutorial_tpu.train.lm import (
        LMConfig,
        LMTrainer,
    )

    cfg = LMConfig(
        vocab_size=64,
        num_layers=2,
        num_heads=4,
        d_model=32,
        d_ff=64,
        max_seq_len=64,
        attention_impl="dense",
        global_batch_size=4,
        seq_len=16,
        seed=11,
        data_parallel=2,
        tensor_parallel=tensor,
    )
    return LMTrainer(cfg, mesh=mesh)


def _trained_params(tr, steps=2):
    from cs744_pytorch_distributed_tutorial_tpu.data.text import (
        synthetic_tokens,
    )

    params, opt_state = tr.init()
    toks = synthetic_tokens(8, 16, 64, seed=0)
    for s in range(steps):
        x, y = tr.shard_batch(toks[s * 4 : s * 4 + 4])
        params, opt_state, _ = tr.train_step(params, opt_state, x, y)
    return params


@pytest.fixture(scope="module")
def tp_setup():
    from cs744_pytorch_distributed_tutorial_tpu.parallel import make_mesh

    mesh = make_mesh({"data": 2, "seq": 1, "tensor": 2},
                     devices=jax.devices()[:4])
    tr = _make_trainer(mesh, tensor=2)
    params = _trained_params(tr)
    return tr, params


def test_tp_generate_matches_gathered(tp_setup):
    """Greedy decode on tensor-sharded params must emit exactly the
    tokens the gathered-single-device path emits from the same params."""
    from cs744_pytorch_distributed_tutorial_tpu.infer import make_generator

    tr, params = tp_setup
    prompt = np.asarray(
        [[1, 2, 3, 4], [5, 6, 7, 8], [9, 10, 11, 12], [13, 14, 15, 16]],
        np.int32,
    )

    gen_tp = make_generator(
        tr.tp_decode_model(), max_new_tokens=8, temperature=0.0,
        mesh=tr.mesh, param_specs=tr.param_specs,
    )
    out_tp = np.asarray(gen_tp(params, prompt, jax.random.key(0)))

    # gathered path: one all-gather of the sharded params, then the
    # plain single-program decode
    gen_full = make_generator(
        tr.decode_model(), max_new_tokens=8, temperature=0.0
    )
    full_params = tr.gather_for_decode(params)
    out_full = np.asarray(gen_full(full_params, prompt, jax.random.key(0)))
    np.testing.assert_array_equal(out_tp, out_full)


def test_tp_generate_sampling_deterministic(tp_setup):
    """Stochastic sampling on the TP path is deterministic per key:
    every device draws from the same replicated logits, so repeated runs
    agree exactly. (Cross-path bitwise parity is pinned on the GREEDY
    test above — under sampling, psum-order float differences can
    legitimately flip near-tied draws.)"""
    from cs744_pytorch_distributed_tutorial_tpu.infer import make_generator

    tr, params = tp_setup
    prompt = np.asarray([[1, 2, 3, 4], [5, 6, 7, 8]] * 2, np.int32)
    gen_tp = make_generator(
        tr.tp_decode_model(), max_new_tokens=6, temperature=0.8, top_k=8,
        mesh=tr.mesh, param_specs=tr.param_specs,
    )
    a = np.asarray(gen_tp(params, prompt, jax.random.key(3)))
    b = np.asarray(gen_tp(params, prompt, jax.random.key(3)))
    np.testing.assert_array_equal(a, b)
    assert ((0 <= a) & (a < 64)).all()


def test_sampling_decorrelated_across_data_shards(tp_setup):
    """Identical prompts landing on DIFFERENT data shards must draw
    different random streams: the decode key is folded with the data
    axis index inside shard_map (without it, row i of every shard
    sampled identically — advisor finding, round 2)."""
    from cs744_pytorch_distributed_tutorial_tpu.infer import make_generator

    tr, params = tp_setup
    # 4 identical rows over data=2 -> rows 0,1 on shard 0, rows 2,3 on
    # shard 1. Same in-shard index + same prompt would have collided.
    prompt = np.asarray([[1, 2, 3, 4]] * 4, np.int32)
    gen_tp = make_generator(
        tr.tp_decode_model(), max_new_tokens=16, temperature=1.0,
        mesh=tr.mesh, param_specs=tr.param_specs,
    )
    out = np.asarray(gen_tp(params, prompt, jax.random.key(7)))
    # Within a shard, identical rows still share the per-shard stream
    # only through different per-row key folds inside sample_tokens —
    # the cross-shard pairs (0,2) and (1,3) are the regression surface.
    assert not np.array_equal(out[0], out[2]) or not np.array_equal(
        out[1], out[3]
    )


def test_tp_beam_matches_gathered(tp_setup):
    from cs744_pytorch_distributed_tutorial_tpu.infer import (
        make_beam_searcher,
    )

    tr, params = tp_setup
    prompt = np.asarray([[1, 2, 3, 4], [9, 10, 11, 12]] * 2, np.int32)
    beam_tp = make_beam_searcher(
        tr.tp_decode_model(), beam_size=3, max_new_tokens=5,
        mesh=tr.mesh, param_specs=tr.param_specs,
    )
    beam_full = make_beam_searcher(
        tr.decode_model(), beam_size=3, max_new_tokens=5
    )
    tok_tp, sc_tp = beam_tp(params, prompt)
    tok_full, sc_full = beam_full(tr.gather_for_decode(params), prompt)
    np.testing.assert_array_equal(np.asarray(tok_tp), np.asarray(tok_full))
    np.testing.assert_allclose(
        np.asarray(sc_tp), np.asarray(sc_full), rtol=1e-5
    )


def test_non_tp_model_rejected_without_mesh():
    """The guard rail: a tensor-parallel model without the shard_map
    path must fail with the pointer to it."""
    from cs744_pytorch_distributed_tutorial_tpu.infer import make_generator
    from cs744_pytorch_distributed_tutorial_tpu.parallel import make_mesh

    mesh = make_mesh({"data": 2, "seq": 1, "tensor": 2},
                     devices=jax.devices()[:4])
    tr = _make_trainer(mesh, tensor=2)
    with pytest.raises(ValueError, match="shard_map path"):
        make_generator(tr.tp_decode_model(), max_new_tokens=4)
