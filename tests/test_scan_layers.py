"""scan_layers: the stacked-layer TransformerLM is numerically the
unrolled one (models/transformer.py::TransformerLM.scan_layers).

No counterpart in the reference (conv VGG-11 only,
``master/part1/model.py:30-46``) — this is compile-scalability
infrastructure: the scanned program is one block body + a loop instead
of L inlined bodies, the option for when a deep unrolled program stops
compiling.
These tests pin that the layout change is EXACTLY a layout change:
logits, grads, the training step, remat, dropout keying, decode with a
KV cache, and tensor-parallel sharding all agree with the unrolled
path.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cs744_pytorch_distributed_tutorial_tpu.models import TransformerLM
from cs744_pytorch_distributed_tutorial_tpu.models.transformer import (
    lm_param_specs,
    stack_block_params,
    unstack_block_params,
)

L = 3


def _lm(**kw) -> TransformerLM:
    base = dict(
        vocab_size=128,
        num_layers=L,
        num_heads=4,
        d_model=64,
        d_ff=128,
        max_seq_len=64,
        dtype=jnp.float32,
        attention_impl="dense",
        use_rope=True,
        flash_interpret=True,
    )
    base.update(kw)
    return TransformerLM(**base)


@pytest.fixture(scope="module")
def unrolled_params():
    m = _lm()
    toks = jnp.zeros((2, 16), jnp.int32)
    return m.init(jax.random.key(0), toks)["params"]


def test_forward_logit_parity(unrolled_params):
    toks = jax.random.randint(jax.random.key(1), (2, 16), 0, 128)
    out_u = _lm().apply({"params": unrolled_params}, toks)
    stacked = stack_block_params(unrolled_params, L)
    out_s = _lm(scan_layers=True).apply({"params": stacked}, toks)
    np.testing.assert_allclose(
        np.asarray(out_u), np.asarray(out_s), rtol=1e-6, atol=1e-5
    )


def test_stack_unstack_roundtrip(unrolled_params):
    stacked = stack_block_params(unrolled_params, L)
    back = unstack_block_params(stacked)
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)
        ),
        unrolled_params,
        back,
    )


def test_grad_parity(unrolled_params):
    toks = jax.random.randint(jax.random.key(2), (2, 16), 0, 128)
    tgts = jax.random.randint(jax.random.key(3), (2, 16), 0, 128)

    def loss(model, p):
        import optax

        logits = model.apply({"params": p}, toks)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, tgts
        ).mean()

    g_u = jax.grad(lambda p: loss(_lm(), p))(unrolled_params)
    stacked = stack_block_params(unrolled_params, L)
    g_s = jax.grad(lambda p: loss(_lm(scan_layers=True), p))(stacked)
    g_u_stacked = stack_block_params(g_u, L)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5
        ),
        g_u_stacked,
        g_s,
    )


def test_remat_scan_parity(unrolled_params):
    """remat composes with scan_layers: the scanned body is checkpointed
    per layer (scan-over-remat), numerics unchanged."""
    toks = jax.random.randint(jax.random.key(4), (2, 16), 0, 128)
    out_u = _lm().apply({"params": unrolled_params}, toks)
    stacked = stack_block_params(unrolled_params, L)
    m_rs = _lm(scan_layers=True, remat=True, remat_policy="dots")
    out_s = m_rs.apply({"params": stacked}, toks)
    np.testing.assert_allclose(
        np.asarray(out_u), np.asarray(out_s), rtol=1e-6, atol=1e-5
    )
    g = jax.grad(lambda p: m_rs.apply({"params": p}, toks).sum())(stacked)
    assert all(
        bool(jnp.all(jnp.isfinite(leaf))) for leaf in jax.tree.leaves(g)
    )


def test_decode_cache_parity(unrolled_params):
    """Cached prefill+decode through the scanned stack matches the
    teacher-forced forward at every generated position (the cache gets a
    leading [L] axis; reads/writes must hit the right layer's slice)."""
    stacked = stack_block_params(unrolled_params, L)
    m = _lm(scan_layers=True)
    toks = jax.random.randint(jax.random.key(5), (2, 24), 0, 128)
    full = m.apply({"params": stacked}, toks)

    prompt = toks[:, :16]
    cache = m.init(jax.random.key(0), prompt, mode="prefill")["cache"]
    logits, mut = m.apply(
        {"params": stacked, "cache": cache}, prompt, mode="prefill",
        mutable=["cache"],
    )
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(full[:, :16]), rtol=1e-5, atol=1e-5
    )
    cache = mut["cache"]
    for pos in range(16, 24):
        step_logits, mut = m.apply(
            {"params": stacked, "cache": cache},
            toks[:, pos : pos + 1],
            mode="decode",
            decode_pos=jnp.int32(pos),
            mutable=["cache"],
        )
        cache = mut["cache"]
        np.testing.assert_allclose(
            np.asarray(step_logits[:, 0]),
            np.asarray(full[:, pos]),
            rtol=1e-4,
            atol=1e-4,
        )


def test_dropout_runs_and_differs_per_layer():
    """split_rngs gives each scanned layer its own dropout stream: the
    zero patterns the per-layer Dropout modules apply must DIFFER across
    layers (a regression to a shared rng would correlate them exactly).
    Pinned via captured intermediates — under nn.scan each submodule's
    outputs stack along the leading layer axis."""
    m = _lm(scan_layers=True, dropout_rate=0.5)
    toks = jnp.zeros((2, 16), jnp.int32)
    params = m.init(jax.random.key(0), toks)["params"]
    out, state = m.apply(
        {"params": params},
        toks,
        deterministic=False,
        rngs={"dropout": jax.random.key(7)},
        capture_intermediates=lambda mdl, _: mdl.name == "attn_drop",
    )
    assert bool(jnp.all(jnp.isfinite(out)))
    (dropped,) = jax.tree.leaves(state["intermediates"])
    assert dropped.shape[0] == L  # stacked per layer
    masks = np.asarray(dropped == 0.0).reshape(L, -1)
    for i in range(1, L):
        assert (masks[0] != masks[i]).any(), (
            f"layer 0 and layer {i} drew identical dropout masks — "
            "split_rngs regressed"
        )


def test_moe_scan_rejected():
    m = _lm(scan_layers=True, num_experts=4)
    toks = jnp.zeros((2, 16), jnp.int32)
    with pytest.raises(ValueError, match="scan_layers does not compose"):
        m.init(jax.random.key(0), toks)


def test_param_specs_scanned_layout(unrolled_params):
    """Tensor-axis specs shift one dim right for stacked leaves; the
    layer dim stays unsharded."""
    from jax.sharding import PartitionSpec as P

    stacked = stack_block_params(unrolled_params, L)
    specs = lm_param_specs(stacked, "tensor")
    blk = specs["blocks"]
    assert blk["attn"]["q"]["kernel"] == P(None, None, "tensor")
    assert blk["attn"]["attn_out"]["kernel"] == P(None, "tensor", None)
    assert blk["mlp_in"]["kernel"] == P(None, None, "tensor")
    assert blk["mlp_in"]["bias"] == P(None, "tensor")
    assert blk["mlp_out"]["kernel"] == P(None, "tensor", None)
    assert specs["tok_embed"]["embedding"] == P()


@pytest.mark.slow
def test_trainer_scan_layers_loss_parity(mesh8):
    """LMTrainer(scan_layers=True) takes the stacked version of the
    unrolled trainer's params to the SAME loss — the full shard_map
    train path (dp2 x tp2, grad sync, optimizer) is layout-invariant."""
    from cs744_pytorch_distributed_tutorial_tpu.data import synthetic_tokens
    from cs744_pytorch_distributed_tutorial_tpu.parallel import make_mesh
    from cs744_pytorch_distributed_tutorial_tpu.train import LMConfig, LMTrainer

    mesh = make_mesh(
        {"data": 2, "seq": 1, "tensor": 2}, devices=jax.devices()[:4]
    )
    cfg = LMConfig(
        vocab_size=128,
        num_layers=L,
        num_heads=4,
        d_model=64,
        d_ff=128,
        max_seq_len=64,
        seq_len=32,
        global_batch_size=4,
        attention_impl="dense",
        data_parallel=2,
        tensor_parallel=2,
        use_rope=True,
    )
    tr_u = LMTrainer(cfg, mesh=mesh)
    tr_s = LMTrainer(cfg.replace(scan_layers=True), mesh=mesh)
    tokens = synthetic_tokens(4, 32, 128, seed=0)
    x, y = tr_u.shard_batch(tokens)

    params_u, opt_u = tr_u.init()
    host_u = jax.tree.map(np.asarray, jax.device_get(params_u))
    stacked = stack_block_params(host_u, L)
    from jax.sharding import NamedSharding

    from cs744_pytorch_distributed_tutorial_tpu.parallel.mesh import (
        host_to_global,
    )

    params_s = jax.tree.map(
        lambda p, s: host_to_global(p, NamedSharding(mesh, s)),
        stacked,
        tr_s.param_specs,
    )
    opt_s = jax.tree.map(
        lambda o, s: host_to_global(np.asarray(o), NamedSharding(mesh, s)),
        jax.device_get(tr_s.tx.init(stacked)),
        tr_s.opt_specs,
    )

    losses_u, losses_s = [], []
    for step in range(3):
        params_u, opt_u, m_u = tr_u.train_step(params_u, opt_u, x, y, step)
        params_s, opt_s, m_s = tr_s.train_step(params_s, opt_s, x, y, step)
        losses_u.append(float(m_u["loss"]))
        losses_s.append(float(m_s["loss"]))
    np.testing.assert_allclose(losses_u, losses_s, rtol=2e-5)
