"""Golden loss-curve test — SURVEY §4's prescribed replacement for the
reference's verification-by-eyeball.

The reference establishes cross-part equivalence only by fixed seed
(5000 everywhere: ``master/part1/part1.py:107``,
``master/part2a/part2a.py:89-90``) + manually comparing printed loss
curves. Here the part-3 configuration's first 8 step losses are pinned
against a recorded trace: any semantic regression in the model, the
augmentation RNG discipline, the gradient averaging, or the SGD update
shifts the curve and fails loudly. The gentle learning rate keeps the
trajectory non-chaotic so the tolerance absorbs compiler-version
numeric drift without masking real changes.
"""

import numpy as np
import pytest
from conftest import TINY_DP4_CFG, run_tiny_dp4_steps

# Recorded on the 8-virtual-CPU-device harness (4-device data mesh),
# tiny_cnn, sync="auto", global batch 32, synthetic CIFAR seed 5000,
# lr 0.01. Re-record ONLY for a deliberate semantic change.
GOLDEN = [3.075281, 2.268045, 2.254324, 2.11918, 2.098891, 1.907552,
          1.650272, 1.748724]


# Full engine fit — heavy compile; the curve is pinned to the
# AD-inserted-sync path.
@pytest.mark.slow
def test_part3_loss_curve_matches_golden_trace(mesh4):
    losses, _, _ = run_tiny_dp4_steps(
        "auto",
        mesh4,
        steps=len(GOLDEN),
        cfg_overrides=dict(seed=5000, learning_rate=0.01),
        data_seed=5000,
    )
    np.testing.assert_allclose(losses, GOLDEN, rtol=5e-3)


# Long-context engine golden: ring attention on a 2x4 data x seq mesh,
# AdamW lr 1e-2, synthetic cyclic tokens seed 5000. Pins the sequence-
# parallel attention, offset position embeddings, spec-aware gradient
# averaging, and the AdamW update in one curve.
GOLDEN_LM = [4.61314, 4.38864, 4.223654, 4.082678, 4.278648, 4.134741,
             4.185895, 4.089676]


@pytest.mark.slow
def test_lm_seq_parallel_loss_curve_matches_golden_trace():
    from cs744_pytorch_distributed_tutorial_tpu.data import synthetic_tokens
    from cs744_pytorch_distributed_tutorial_tpu.parallel import make_mesh
    from cs744_pytorch_distributed_tutorial_tpu.train import LMConfig, LMTrainer

    cfg = LMConfig(vocab_size=64, num_layers=2, num_heads=4, d_model=64,
                   d_ff=128, max_seq_len=256, seq_len=64, global_batch_size=8,
                   attention_impl="ring", data_parallel=2, seq_parallel=4,
                   learning_rate=1e-2, seed=5000)
    tr = LMTrainer(cfg, mesh=make_mesh({"data": 2, "seq": 4}))
    tokens = synthetic_tokens(64, cfg.seq_len, cfg.vocab_size, seed=5000)
    _, _, losses = tr.fit(tokens, steps=len(GOLDEN_LM))
    np.testing.assert_allclose(losses, GOLDEN_LM, rtol=5e-3)


@pytest.mark.parametrize("ndev", [1, 4])
def test_cifar_train_step_compiles_exactly_once(ndev):
    """Compile-count regression gate: after the warm-up call traces and
    compiles the CIFAR train step, further steps on same-shaped inputs
    must hit the jit cache — 0 additional backend compiles. A retrace
    hazard (unstable static args, fresh wrappers, shifting shapes) shows
    up here as a nonzero steady-state count, the dynamic twin of
    graftlint's GL002. One device is its own case: the init state must
    be committed to the mesh like the step's outputs are (the one-chip
    run recompiled at step 1 until ``host_to_global`` did that)."""
    import jax

    from cs744_pytorch_distributed_tutorial_tpu.config import TrainConfig
    from cs744_pytorch_distributed_tutorial_tpu.data import synthetic_cifar10
    from cs744_pytorch_distributed_tutorial_tpu.obs.system import CompileCounter
    from cs744_pytorch_distributed_tutorial_tpu.parallel.mesh import (
        shard_global_batch,
    )
    from cs744_pytorch_distributed_tutorial_tpu.train import Trainer

    from cs744_pytorch_distributed_tutorial_tpu.parallel import make_mesh

    warm = CompileCounter()
    cfg = TrainConfig(
        **{**TINY_DP4_CFG, "num_devices": ndev},
        sync="allreduce" if ndev > 1 else "auto",
    )
    mesh = make_mesh({"data": ndev}, devices=jax.devices()[:ndev])
    tr = Trainer(cfg, mesh=mesh)
    state = tr.init()
    ds = synthetic_cifar10(TINY_DP4_CFG["global_batch_size"], 8, seed=0)
    x, y = shard_global_batch(mesh, ds.train_images, ds.train_labels)
    key = jax.random.key(0)
    state, m = tr.train_step(state, x, y, key)
    assert warm.count >= 1, "the first step's compile was not counted"

    steady = CompileCounter()
    for _ in range(5):
        state, m = tr.train_step(state, x, y, key)
    assert np.isfinite(float(m["loss"]))
    assert steady.count == 0, (
        f"train_step triggered {steady.count} backend compile(s) after "
        "warm-up — the step is retracing"
    )
