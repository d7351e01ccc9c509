"""LM dropout rng plumbing — the round-1 deferred migration:
``LMTrainer.train_step`` takes a step index that keys
the dropout mask stream.

Pinned properties:
- dropout=0 ignores the step entirely (the golden LM traces stay valid);
- dropout>0 is deterministic per step and varies across steps;
- tensor-parallel shards draw IDENTICAL masks (the MLP dropout applies
  to row-parallel partial sums before their psum), so the tp=2 and tp=1
  trajectories coincide exactly — the correctness condition called out
  in models/transformer.py.
"""

from __future__ import annotations

import jax
import numpy as np
import pytest

# LM fit runs per case: heavy compile.
pytestmark = pytest.mark.slow


def _trainer(mesh, **kw):
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
        global_batch_size=4,
        seq_len=16,
        seed=7,
        **kw,
    )
    return LMTrainer(cfg, mesh=mesh)


def _tokens(seed=0):
    from cs744_pytorch_distributed_tutorial_tpu.data.text import (
        synthetic_tokens,
    )

    return synthetic_tokens(16, 16, 64, seed=seed)


def _run(tr, steps, step_indices=None):
    params, opt_state = tr.init()
    toks = _tokens()
    losses = []
    for s in range(steps):
        x, y = tr.shard_batch(toks[s * 4 : s * 4 + 4])
        idx = step_indices[s] if step_indices is not None else s
        params, opt_state, m = tr.train_step(params, opt_state, x, y, idx)
        losses.append(float(m["loss"]))
    return losses


def test_dropout_deterministic_per_step(mesh4):
    from cs744_pytorch_distributed_tutorial_tpu.parallel import make_mesh

    mesh = make_mesh({"data": 2, "seq": 2}, devices=jax.devices()[:4])
    tr = _trainer(mesh, data_parallel=2, seq_parallel=2, dropout_rate=0.3)
    a = _run(tr, 3)
    tr2 = _trainer(mesh, data_parallel=2, seq_parallel=2, dropout_rate=0.3)
    b = _run(tr2, 3)
    assert a == b  # same steps -> same masks -> identical trajectory


def test_dropout_masks_vary_with_step(mesh4):
    from cs744_pytorch_distributed_tutorial_tpu.parallel import make_mesh

    mesh = make_mesh({"data": 2, "seq": 2}, devices=jax.devices()[:4])
    # Same BATCH every time, only the step index differs: the loss after
    # one update differs iff the masks do.
    tr = _trainer(mesh, data_parallel=2, seq_parallel=2, dropout_rate=0.3)
    a = _run(tr, 2, step_indices=[0, 0])
    tr2 = _trainer(mesh, data_parallel=2, seq_parallel=2, dropout_rate=0.3)
    b = _run(tr2, 2, step_indices=[0, 1])
    assert a[0] == b[0]  # identical first step
    assert a[1] != b[1]  # different masks at the second


def test_dropout_zero_ignores_step(mesh4):
    from cs744_pytorch_distributed_tutorial_tpu.parallel import make_mesh

    mesh = make_mesh({"data": 2, "seq": 2}, devices=jax.devices()[:4])
    tr = _trainer(mesh, data_parallel=2, seq_parallel=2, dropout_rate=0.0)
    a = _run(tr, 2, step_indices=[0, 0])
    tr2 = _trainer(mesh, data_parallel=2, seq_parallel=2, dropout_rate=0.0)
    b = _run(tr2, 2, step_indices=[5, 9])
    assert a == b  # the step argument is inert without dropout


def test_dropout_identical_across_tensor_shards(mesh8):
    """tp=2 must reproduce tp=1 EXACTLY under dropout: tensor shards
    share masks by construction (rng folds data/seq indices only)."""
    from cs744_pytorch_distributed_tutorial_tpu.parallel import make_mesh

    mesh1 = make_mesh({"data": 2, "seq": 1}, devices=jax.devices()[:2])
    mesh2 = make_mesh(
        {"data": 2, "seq": 1, "tensor": 2}, devices=jax.devices()[:4]
    )
    tr1 = _trainer(mesh1, data_parallel=2, dropout_rate=0.25)
    tr2 = _trainer(
        mesh2, data_parallel=2, tensor_parallel=2, dropout_rate=0.25
    )
    a = _run(tr1, 3)
    b = _run(tr2, 3)
    np.testing.assert_allclose(a, b, rtol=2e-5)


def test_dropout_composes_with_remat(mesh4):
    """remat functionalizes Block.__call__; ``deterministic`` must ride
    as a STATIC argument (models/transformer.py static_argnums) — this
    pins the combination that raised TracerBoolConversionError when it
    was a traced kwarg."""
    from cs744_pytorch_distributed_tutorial_tpu.parallel import make_mesh

    mesh = make_mesh({"data": 2, "seq": 1}, devices=jax.devices()[:2])
    tr = _trainer(mesh, data_parallel=2, dropout_rate=0.3, remat=True)
    a = _run(tr, 2)
    assert all(np.isfinite(a))
    # remat is numerics-preserving: same trajectory as without it
    tr2 = _trainer(mesh, data_parallel=2, dropout_rate=0.3, remat=False)
    b = _run(tr2, 2)
    np.testing.assert_allclose(a, b, rtol=2e-6)
