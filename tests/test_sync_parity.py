"""Gradient-sync strategy parity.

The reference's central (implicit) property: part2a, part2a_extra, part2b
and part3 compute the SAME update — four mechanisms, one semantics —
which it establishes only by fixed seed + eyeballing loss curves
(SURVEY §4). Here it is a real test: from identical init and an identical
global batch, one train step under every strategy must produce identical
parameters.
"""

import jax
import numpy as np
import pytest

from cs744_pytorch_distributed_tutorial_tpu.config import TrainConfig
from cs744_pytorch_distributed_tutorial_tpu.data import synthetic_cifar10
from cs744_pytorch_distributed_tutorial_tpu.parallel import make_mesh
from cs744_pytorch_distributed_tutorial_tpu.train import Trainer

STRATEGIES = [
    "allreduce",
    "gather_scatter",
    "p2p_star",
    "ring",
    "auto",
    "zero1",
    "fsdp",
]


def _one_step_params(strategy, mesh, batch):
    cfg = TrainConfig(
        model="tiny_cnn",
        sync=strategy,
        num_devices=4,
        global_batch_size=16,
        seed=5000,
    )
    tr = Trainer(cfg, mesh=mesh)
    state = tr.init()
    x, y = batch
    from cs744_pytorch_distributed_tutorial_tpu.parallel.mesh import shard_global_batch

    gx, gy = shard_global_batch(mesh, x, y)
    key = jax.random.key(cfg.seed)
    new_state, metrics = tr.train_step(state, gx, gy, key)
    params = jax.device_get(new_state.params)
    if strategy == "fsdp":
        # fsdp persists [axis_size, chunk] flat shards; unshard host-side
        # to the original shapes so the matrix compares like with like.
        import jax.numpy as jnp

        sample = jnp.zeros((1, cfg.image_size, cfg.image_size, 3), jnp.float32)
        shapes = jax.eval_shape(
            lambda: tr.model.init(jax.random.key(0), sample, train=False)
        )["params"]
        params = jax.tree.map(
            lambda sh, ref: np.asarray(sh).reshape(-1)[
                : int(np.prod(ref.shape))
            ].reshape(ref.shape),
            params,
            shapes,
        )
    return (
        jax.tree.map(np.asarray, params),
        float(metrics["loss"]),
    )


@pytest.fixture(scope="module")
def batch():
    ds = synthetic_cifar10(64, 16, seed=3)
    return ds.train_images[:16], ds.train_labels[:16]


@pytest.fixture(scope="module")
def results(batch):
    mesh = make_mesh({"data": 4}, devices=jax.devices()[:4])
    return {s: _one_step_params(s, mesh, batch) for s in STRATEGIES}


@pytest.mark.parametrize("strategy", STRATEGIES[1:])
def test_strategies_match_allreduce(results, strategy):
    ref_params, ref_loss = results["allreduce"]
    got_params, got_loss = results[strategy]
    assert got_loss == pytest.approx(ref_loss, rel=1e-6)
    ref_leaves = jax.tree.leaves(ref_params)
    got_leaves = jax.tree.leaves(got_params)
    assert len(ref_leaves) == len(got_leaves)
    for r, g in zip(ref_leaves, got_leaves):
        np.testing.assert_allclose(g, r, rtol=1e-5, atol=1e-6)


def test_sync_actually_replicates_params(results):
    """After one synced step, every replica's params must agree (DDP's
    broadcast-at-construction + identical-updates invariant)."""
    params, _ = results["p2p_star"]
    # Values came back as a single global (replicated) array; a second
    # step from them must not diverge — run two more steps under star.
    # (Replication is structurally guaranteed by out_specs=P(); this
    # checks the star's mean really is the global mean on every replica
    # by comparing against gather_scatter.)
    ref, _ = results["gather_scatter"]
    for r, g in zip(jax.tree.leaves(ref), jax.tree.leaves(params)):
        np.testing.assert_allclose(g, r, rtol=1e-5, atol=1e-6)


# ------------------------------------------------------- overlapped schedule
def _run_steps(mesh, batch, steps, **cfg_kw):
    """Final params + per-step losses for a tiny_cnn run on 4 devices."""
    from cs744_pytorch_distributed_tutorial_tpu.parallel.mesh import (
        shard_global_batch,
    )

    cfg = TrainConfig(
        model="tiny_cnn", num_devices=4, global_batch_size=16, seed=5000,
        **cfg_kw,
    )
    tr = Trainer(cfg, mesh=mesh)
    state = tr.init()
    gx, gy = shard_global_batch(mesh, *batch)
    key = jax.random.key(cfg.seed)
    losses = []
    for _ in range(steps):
        state, metrics = tr.train_step(state, gx, gy, key)
        losses.append(float(metrics["loss"]))
    return jax.tree.map(np.asarray, jax.device_get(state.params)), losses


@pytest.mark.parametrize("strategy", ["allreduce", "ring"])
def test_overlap_bitwise_vs_fused(mesh4, batch, strategy):
    """The overlapped bucket schedule (--sync-overlap bucket) reorders
    WHEN each bucket syncs and applies, not WHAT is computed: for the
    float wires the reverse-bucket mean and per-bucket SGD apply are the
    same f32 operations on the same operands, so parity is bitwise —
    any drift means the schedule changed the math."""
    fused_p, fused_l = _run_steps(mesh4, batch, 3, sync=strategy)
    ov_p, ov_l = _run_steps(
        mesh4, batch, 3, sync=strategy, sync_overlap="bucket"
    )
    assert fused_l == ov_l
    for r, g in zip(jax.tree.leaves(fused_p), jax.tree.leaves(ov_p)):
        np.testing.assert_array_equal(g, r)


@pytest.mark.slow
def test_overlap_int8_ef_trajectory(mesh4):
    """int8+EF overlap is NOT bitwise vs fused int8 — the reverse bucket
    layout regroups the quantization chunks — but error feedback keeps
    the trajectories together: over 50 steps the mean per-step relative
    loss gap stays under 1% (the compression suite's tolerance class;
    measured 0.66%). The mean is the stable statistic — single-step
    losses on this chaotic repeated-batch config oscillate ~10%, so a
    final-step bar would gate on noise, not on the schedule."""
    from conftest import run_tiny_dp4_steps

    fused_l, _, _ = run_tiny_dp4_steps(
        "allreduce", mesh4, steps=50, cfg_overrides={"grad_compress": "int8"}
    )
    ov_l, _, _ = run_tiny_dp4_steps(
        "allreduce", mesh4, steps=50,
        cfg_overrides={
            "grad_compress": "int8", "sync_overlap": "bucket+int8",
        },
    )
    rels = [abs(a - b) / max(abs(a), 1.0) for a, b in zip(fused_l, ov_l)]
    assert sum(rels) / len(rels) <= 0.01, (max(rels), sum(rels) / len(rels))
    assert ov_l[-1] < ov_l[0]  # and it actually trained


def test_overlap_int8_short_run_stays_close(mesh4):
    """Fast (tier-1) version of the int8 overlap check: 8 steps, 2% —
    the same bar as the fused int8-vs-f32 short-run test (measured
    final-loss gap: 6e-5)."""
    from conftest import run_tiny_dp4_steps

    fused_l, _, _ = run_tiny_dp4_steps(
        "allreduce", mesh4, steps=8, cfg_overrides={"grad_compress": "int8"}
    )
    ov_l, _, _ = run_tiny_dp4_steps(
        "allreduce", mesh4, steps=8,
        cfg_overrides={
            "grad_compress": "int8", "sync_overlap": "bucket+int8",
        },
    )
    assert ov_l[-1] == pytest.approx(fused_l[-1], rel=0.02)


@pytest.mark.parametrize("strategy", ["zero1", "fsdp"])
def test_overlap_sharded_bitwise_vs_fused(mesh4, batch, strategy):
    """zero1/fsdp overlap (reverse-bucket psum_scatter -> per-shard
    apply -> all_gather, parallel/zero.py) changes only bucket
    ASSIGNMENT: every collective stays column-elementwise on the same
    per-leaf [axis_size, chunk] blocks and the chunk rules are
    elementwise, so the float path is bitwise vs the fused schedule.
    (fsdp params persist as flat shards on both sides — same layout,
    so the leaves compare directly.)"""
    fused_p, fused_l = _run_steps(mesh4, batch, 3, sync=strategy)
    ov_p, ov_l = _run_steps(
        mesh4, batch, 3, sync=strategy, sync_overlap="bucket"
    )
    assert fused_l == ov_l
    for r, g in zip(jax.tree.leaves(fused_p), jax.tree.leaves(ov_p)):
        np.testing.assert_array_equal(g, r)


@pytest.mark.parametrize(
    "strategy",
    ["zero1", pytest.param("allreduce", marks=pytest.mark.slow)],
)
def test_overlap_accum_final_microstep(mesh4, batch, strategy):
    """accum_steps>1 composes with overlap: intermediate micro-steps
    stay local adds and only the FINAL micro-step's sync+apply runs the
    bucket schedule. zero1 syncs once per step either way, so it stays
    bitwise. Fused pure-DP allreduce syncs per micro-step (mean of
    means) while overlap syncs the accumulated sum once — equal up to
    f32 reassociation, so the parity-suite allclose bar applies."""
    fused_p, fused_l = _run_steps(
        mesh4, batch, 2, sync=strategy, accum_steps=2
    )
    ov_p, ov_l = _run_steps(
        mesh4, batch, 2, sync=strategy, accum_steps=2, sync_overlap="bucket"
    )
    if strategy == "zero1":
        assert fused_l == ov_l
        for r, g in zip(jax.tree.leaves(fused_p), jax.tree.leaves(ov_p)):
            np.testing.assert_array_equal(g, r)
    else:
        for a, b in zip(fused_l, ov_l):
            assert b == pytest.approx(a, rel=1e-5)
        for r, g in zip(jax.tree.leaves(fused_p), jax.tree.leaves(ov_p)):
            np.testing.assert_allclose(g, r, rtol=1e-5, atol=1e-6)


@pytest.mark.slow
def test_overlap_zero1_int8_short_run_stays_close(mesh4):
    """zero1 + bucket+int8: the quantized wire replaces each bucket's
    psum_scatter; error feedback keeps the trajectory on the float
    zero1 run — 8 steps, the compression suite's 2% short-run bar
    (measured ~2e-4). Tier-1 still exercises this wire end-to-end via
    test_profiling's zero1-int8 segmented parity and the zero-retrace
    sweep; the trajectory bars live in the slow tier."""
    from conftest import run_tiny_dp4_steps

    fused_l, _, _ = run_tiny_dp4_steps("zero1", mesh4, steps=8)
    ov_l, _, _ = run_tiny_dp4_steps(
        "zero1", mesh4, steps=8,
        cfg_overrides={
            "grad_compress": "int8", "sync_overlap": "bucket+int8",
        },
    )
    assert ov_l[-1] == pytest.approx(fused_l[-1], rel=0.02)


@pytest.mark.slow
def test_overlap_zero1_int8_trajectory(mesh4):
    """50-step bar for the zero1 int8 wire vs float zero1: mean
    per-step relative loss gap <= 1% (same statistic as the pure-DP
    int8 overlap bar; measured ~2e-4)."""
    from conftest import run_tiny_dp4_steps

    fused_l, _, _ = run_tiny_dp4_steps("zero1", mesh4, steps=50)
    ov_l, _, _ = run_tiny_dp4_steps(
        "zero1", mesh4, steps=50,
        cfg_overrides={
            "grad_compress": "int8", "sync_overlap": "bucket+int8",
        },
    )
    rels = [abs(a - b) / max(abs(a), 1.0) for a, b in zip(fused_l, ov_l)]
    assert sum(rels) / len(rels) <= 0.01, (max(rels), sum(rels) / len(rels))
    assert ov_l[-1] < ov_l[0]  # and it actually trained


def test_overlap_int8_rejects_fsdp(mesh4):
    # fsdp has no separate gradient wire to quantize — its reduction IS
    # the AD transpose of the param all_gather — so the engine must
    # refuse int8 there and point at the zero1 schedule instead.
    cfg = TrainConfig(
        model="tiny_cnn", sync="fsdp", grad_compress="int8",
        num_devices=4, global_batch_size=16,
    )
    with pytest.raises(ValueError, match="fsdp"):
        Trainer(cfg, mesh=mesh4)


@pytest.mark.parametrize(
    "cfg_kw",
    [
        dict(sync="zero1", sync_overlap="bucket"),
        pytest.param(
            dict(sync="fsdp", sync_overlap="bucket"),
            marks=pytest.mark.slow,
        ),
        pytest.param(
            dict(
                sync="zero1", grad_compress="int8",
                sync_overlap="bucket+int8",
            ),
            marks=pytest.mark.slow,
        ),
    ],
    ids=["zero1-bucket", "fsdp-bucket", "zero1-int8"],
)
def test_overlap_modes_zero_retrace(mesh4, batch, cfg_kw):
    """Each overlapped sharded mode compiles ONCE: steady-state steps
    must not retrace (the per-bucket python loops run at trace time —
    any shape/layout instability would show up as a recompile)."""
    from cs744_pytorch_distributed_tutorial_tpu.obs.system import (
        CompileCounter,
    )
    from cs744_pytorch_distributed_tutorial_tpu.parallel.mesh import (
        shard_global_batch,
    )

    cfg = TrainConfig(
        model="tiny_cnn", num_devices=4, global_batch_size=16, seed=5000,
        **cfg_kw,
    )
    tr = Trainer(cfg, mesh=mesh4)
    state = tr.init()
    gx, gy = shard_global_batch(mesh4, *batch)
    key = jax.random.key(cfg.seed)
    warm = CompileCounter()
    state, _ = tr.train_step(state, gx, gy, key)
    assert warm.count >= 1, "the first step's compile was not counted"
    steady = CompileCounter()
    for _ in range(3):
        state, m = tr.train_step(state, gx, gy, key)
    assert np.isfinite(float(m["loss"]))
    assert steady.count == 0, (
        f"overlapped step triggered {steady.count} backend compile(s) "
        "after warm-up — the bucket schedule is retracing"
    )


# --------------------------------------------------- LM overlapped schedule
def _lm_run(mesh, steps=4, **kw):
    """Final params + per-step losses for a tiny LM run on dp=4."""
    from cs744_pytorch_distributed_tutorial_tpu.data import synthetic_tokens
    from cs744_pytorch_distributed_tutorial_tpu.train import (
        LMConfig,
        LMTrainer,
    )

    base = dict(
        vocab_size=64, num_layers=2, num_heads=4, d_model=32, d_ff=64,
        max_seq_len=64, seq_len=16, global_batch_size=8,
        attention_impl="dense", use_rope=True, learning_rate=3e-3,
        optimizer="sgd", lr_schedule="constant", data_parallel=4,
    )
    base.update(kw)
    cfg = LMConfig(**base)
    tr = LMTrainer(cfg, mesh=mesh)
    params, opt = tr.init()
    tokens = synthetic_tokens(8, 16, 64, seed=0)
    x, y = tr.shard_batch(tokens)
    losses = []
    for s in range(steps):
        params, opt, m = tr.train_step(params, opt, x, y, s)
        losses.append(float(m["loss"]))
    return jax.tree.map(np.asarray, jax.device_get(params)), losses


@pytest.fixture(scope="module")
def lm_mesh4():
    return make_mesh({"data": 4, "seq": 1}, devices=jax.devices()[:4])


@pytest.mark.slow
@pytest.mark.parametrize("shard", ["zero1", "fsdp"])
def test_lm_overlap_sharded_bitwise_vs_fused(lm_mesh4, shard):
    """The LM engine's zero1/fsdp overlap is the same bucket-assignment-
    only change as CIFAR's: float SGD parity is bitwise. Slow tier —
    tier-1 pins the same property on the CIFAR engine
    (test_overlap_sharded_bitwise_vs_fused) and the LM schedules' wire
    accounting via the TA003 rows in test_trace_audit.py."""
    kw = {"zero1": True} if shard == "zero1" else {"fsdp": True}
    fused_p, fused_l = _lm_run(lm_mesh4, **kw)
    ov_p, ov_l = _lm_run(lm_mesh4, sync_overlap="bucket", **kw)
    assert fused_l == ov_l
    for r, g in zip(jax.tree.leaves(fused_p), jax.tree.leaves(ov_p)):
        np.testing.assert_array_equal(g, r)


@pytest.mark.slow
def test_lm_overlap_zero1_adamw_short_run(lm_mesh4):
    """AdamW under overlap hoists the schedule/bias-correction step
    scalars once and applies the chunk rule per bucket — float
    reassociation only, so 6 steps stay within the zero1-vs-replicated
    AdamW suite's rtol. Slow tier with the 50-step bar below: tier-1
    keeps the bitwise SGD sweep, which pins the same bucket schedule."""
    kw = dict(
        optimizer="adamw", lr_schedule="warmup_cosine", warmup_steps=2,
        total_steps=8, zero1=True,
    )
    _, fused_l = _lm_run(lm_mesh4, steps=6, **kw)
    _, ov_l = _lm_run(lm_mesh4, steps=6, sync_overlap="bucket", **kw)
    np.testing.assert_allclose(fused_l, ov_l, rtol=2e-5)


@pytest.mark.slow
def test_lm_overlap_zero1_adamw_trajectory(lm_mesh4):
    """The ISSUE's 50-step bar: overlapped zero1 AdamW holds a <=1%
    mean per-step relative loss gap vs the fused schedule (measured
    ~1e-5)."""
    kw = dict(
        optimizer="adamw", lr_schedule="warmup_cosine", warmup_steps=2,
        total_steps=50, zero1=True,
    )
    _, fused_l = _lm_run(lm_mesh4, steps=50, **kw)
    _, ov_l = _lm_run(lm_mesh4, steps=50, sync_overlap="bucket", **kw)
    rels = [abs(a - b) / max(abs(a), 1.0) for a, b in zip(fused_l, ov_l)]
    assert sum(rels) / len(rels) <= 0.01, (max(rels), sum(rels) / len(rels))
    assert ov_l[-1] < ov_l[0]


@pytest.mark.slow
def test_lm_overlap_zero1_accum_bitwise(lm_mesh4):
    """LM zero1 + accumulation: the accumulated grads feed ONE scatter
    under both schedules, so overlap stays bitwise even with
    accum_steps=2. Slow tier — tier-1 covers accum composition via the
    CIFAR zero1 variant of test_overlap_accum_final_microstep."""
    kw = dict(zero1=True, accum_steps=2)
    fused_p, fused_l = _lm_run(lm_mesh4, steps=2, **kw)
    ov_p, ov_l = _lm_run(lm_mesh4, steps=2, sync_overlap="bucket", **kw)
    assert fused_l == ov_l
    for r, g in zip(jax.tree.leaves(fused_p), jax.tree.leaves(ov_p)):
        np.testing.assert_array_equal(g, r)


def test_none_requires_single_device():
    mesh = make_mesh({"data": 4}, devices=jax.devices()[:4])
    cfg = TrainConfig(model="tiny_cnn", sync="none", num_devices=4,
                      global_batch_size=16)
    with pytest.raises(ValueError):
        Trainer(cfg, mesh=mesh)


def test_unknown_strategy_rejected():
    from cs744_pytorch_distributed_tutorial_tpu.parallel.sync import get_sync

    with pytest.raises(ValueError):
        get_sync("nccl")
