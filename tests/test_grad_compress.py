"""Bucketed flat gradient sync + int8 quantized all-reduce.

Three contracts pinned here (parallel/buckets.py, parallel/sync.py):

- Bucketed f32 sync is BITWISE identical to the per-leaf collectives it
  replaces: 'allreduce' pmeans a flat concatenation (elementwise — the
  layout cannot change a value), and 'ring' preserves each leaf's
  per-row chunk placement so the explicit ring's accumulation order is
  unchanged. Bucketing is a pure wire-layout optimization.
- The int8 strategies approximate the f32 mean within per-chunk
  quantization error and ship ~3.9x fewer bytes (int8 codes + one f32
  scale per 256 elements, exactly accounted by sync_bytes_per_step).
- Error feedback closes the loop: sync_grads_compressed returns the
  residual (input minus what was transmitted), and an SGD run with
  int8+EF converges to within 1% of the f32 run's final loss — the
  compressed-DP acceptance bar.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from cs744_pytorch_distributed_tutorial_tpu.ops.quant import (
    dequantize_chunked,
    quantize_chunked,
)
from cs744_pytorch_distributed_tutorial_tpu.parallel import buckets as B
from cs744_pytorch_distributed_tutorial_tpu.parallel.sync import (
    QUANT_CHUNK,
    SYNC_STRATEGIES,
    sync_grads,
    sync_grads_compressed,
)
from conftest import run_tiny_dp4_steps


def _tree(seed=0):
    """Mixed shapes/dtypes: oversized leaf, odd sizes, scalar, bf16."""
    rng = np.random.RandomState(seed)
    return {
        "conv": jnp.asarray(rng.randn(3, 3, 8, 16), jnp.float32),
        "dense": {
            "w": jnp.asarray(rng.randn(257, 5), jnp.float32),
            "b": jnp.asarray(rng.randn(5), jnp.float32),
            "scale": jnp.asarray(rng.randn(), jnp.float32),
        },
        "half": jnp.asarray(rng.randn(33), jnp.bfloat16),
    }


def _stacked(tree, n=4):
    """Per-device variants: device i's leaf is (i+1)/10-scaled."""
    return jax.tree.map(
        lambda x: jnp.stack([x * (i + 1) * 0.1 for i in range(n)]), tree
    )


def _run_sync(mesh, strategy, bucket_bytes, tree):
    g = _stacked(tree)

    def f(gs):
        gl = jax.tree.map(lambda a: a[0], gs)
        return sync_grads(gl, strategy, "data", 4, bucket_bytes=bucket_bytes)

    out = jax.jit(
        jax.shard_map(
            f, mesh=mesh, in_specs=(P("data"),), out_specs=P(),
            check_vma=False,
        )
    )(g)
    return jax.tree.map(np.asarray, jax.device_get(out))


# ---------------------------------------------------------------- layout
def test_bucket_layout_covers_every_element():
    tree = _tree()
    layout = B.bucket_layout(tree, 1024)
    sizes = [int(np.prod(l.shape)) or 1 for l in jax.tree.leaves(tree)]
    assert sum(s.size for s in layout.slots) == sum(sizes)
    # dtype segregation: every slot's dtype matches its bucket's.
    for s in layout.slots:
        assert s.dtype == layout.bucket_dtypes[s.bucket]


def test_bucket_layout_cached_per_structure():
    tree = _tree()
    assert B.bucket_layout(tree, 1024) is B.bucket_layout(tree, 1024)
    assert B.bucket_layout(tree, 1024) is not B.bucket_layout(tree, 2048)


def test_flatten_unflatten_roundtrip():
    for rows in (0, 4):
        tree = _tree()
        layout = B.bucket_layout(tree, 512, rows=rows)
        back = B.unflatten(B.flatten_for_sync(tree, layout), layout)
        jax.tree.map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b)
            ),
            tree,
            back,
        )


def test_quantize_chunked_roundtrip_error_bounded():
    x = jnp.asarray(np.random.RandomState(0).randn(4 * QUANT_CHUNK), jnp.float32)
    q, s = quantize_chunked(x, QUANT_CHUNK)
    err = np.abs(np.asarray(dequantize_chunked(q, s) - x))
    # Max error is half a quantization step per chunk.
    bound = np.repeat(np.asarray(s) / 2 * 1.0001, QUANT_CHUNK)
    assert (err <= bound).all()


# ------------------------------------------------------- bitwise parity
@pytest.mark.parametrize("strategy", ["allreduce", "ring"])
def test_bucketed_sync_bitwise_equals_per_leaf(mesh4, strategy):
    tree = _tree()
    per_leaf = _run_sync(mesh4, strategy, 0, tree)  # 0 disables bucketing
    for bucket_bytes in (512, B.DEFAULT_BUCKET_BYTES):
        bucketed = _run_sync(mesh4, strategy, bucket_bytes, tree)
        for a, b in zip(jax.tree.leaves(per_leaf), jax.tree.leaves(bucketed)):
            np.testing.assert_array_equal(a, b)


# ----------------------------------------------------------------- int8
@pytest.mark.parametrize("strategy", ["int8_allreduce", "int8_ring"])
def test_int8_strategies_close_to_f32_mean(mesh4, strategy):
    assert strategy in SYNC_STRATEGIES
    tree = _tree()
    ref = _run_sync(mesh4, "allreduce", 0, tree)
    got = _run_sync(mesh4, strategy, B.DEFAULT_BUCKET_BYTES, tree)
    for a, r in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
        a32, r32 = np.asarray(a, np.float32), np.asarray(r, np.float32)
        scale = max(np.abs(r32).max(), 1e-6)
        # Per-chunk int8: worst case ~scale/127 per quantization stage.
        np.testing.assert_allclose(a32, r32, atol=scale * 0.05, rtol=0)


def test_compressed_sync_returns_transmission_residual(mesh4):
    """new_ef == (grad + old_ef) - dequant(quant(...)): exactly what the
    wire did NOT carry this step, so mean + own residual reconstructs
    the device's pre-quantization contribution."""
    tree = _tree()
    g = _stacked(tree)
    ef0 = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), g)

    def f(gs, efs):
        gl = jax.tree.map(lambda a: a[0], gs)
        el = jax.tree.map(lambda a: a[0], efs)
        mean, ef = sync_grads_compressed(gl, el, "int8_allreduce", "data", 4)
        return mean, jax.tree.map(lambda a: a[None], ef)

    mean, ef = jax.jit(
        jax.shard_map(
            f, mesh=mesh4, in_specs=(P("data"), P("data")),
            out_specs=(P(), P("data")), check_vma=False,
        )
    )(g, ef0)
    # Residuals are nonzero (quantization is lossy) but small relative
    # to the gradient scale.
    for e, orig in zip(jax.tree.leaves(ef), jax.tree.leaves(g)):
        e, orig = np.asarray(e, np.float32), np.asarray(orig, np.float32)
        assert np.abs(e).max() > 0
        assert np.abs(e).max() < np.abs(orig).max() * 0.05


# ---------------------------------------------------------------- bytes
def test_int8_bytes_on_wire_ratio():
    tree = _tree()
    f32 = B.sync_bytes_per_step(tree, "allreduce", 4)
    int8 = B.sync_bytes_per_step(tree, "int8_allreduce", 4)
    assert f32 > 0 and int8 > 0
    assert f32 / int8 >= 3.5  # acceptance bar; analytic value ~3.94
    # none / single-device ship nothing.
    assert B.sync_bytes_per_step(tree, "none", 4) == 0
    assert B.sync_bytes_per_step(tree, "allreduce", 1) == 0


# ---------------------------------------------------------- convergence
@pytest.mark.slow
def test_int8_ef_sgd_converges_like_f32(mesh4):
    """The PR's acceptance criterion: 50 SGD steps on the tiny CNN, int8
    compressed sync with error feedback vs plain f32 allreduce — final
    loss within 1%."""
    ref, _, _ = run_tiny_dp4_steps("allreduce", mesh4, steps=50)
    got, _, _ = run_tiny_dp4_steps(
        "allreduce", mesh4, steps=50, cfg_overrides={"grad_compress": "int8"}
    )
    assert got[-1] == pytest.approx(ref[-1], rel=0.01)
    # And it actually trained (loss moved meaningfully from step 0).
    assert got[-1] < got[0]


@pytest.mark.slow
def test_int8_short_run_stays_close(mesh4):
    """Fast (tier-1) version of the convergence check: 8 steps, 2%."""
    ref, _, _ = run_tiny_dp4_steps("allreduce", mesh4, steps=8)
    got, tr, state = run_tiny_dp4_steps(
        "allreduce", mesh4, steps=8, cfg_overrides={"grad_compress": "int8"}
    )
    assert got[-1] == pytest.approx(ref[-1], rel=0.02)
    # EF state exists, is per-device, and is nonzero after stepping.
    ef_leaves = jax.tree.leaves(jax.device_get(state.ef))
    assert ef_leaves and all(l.shape[0] == 4 for l in ef_leaves)
    assert any(np.abs(np.asarray(l)).max() > 0 for l in ef_leaves)


def test_int8_sync_names_route_through_compression(mesh4):
    """sync='int8_allreduce' alone (no grad_compress flag) runs the
    compressed engine path."""
    losses, tr, _ = run_tiny_dp4_steps("int8_allreduce", mesh4, steps=2)
    assert tr._compress
    assert np.isfinite(losses).all()


def test_zero1_bucketed_update_within_one_ulp(mesh4):
    """Zero1SGD's bucketed reduce-scatter/all-gather (one collective per
    ~bucket instead of per leaf) equals the per-leaf path to ONE float32
    ulp. Column-concatenation preserves each leaf's per-row placement, so
    psum_scatter delivers the same shards; what differs is how XLA fuses
    the momentum update over a bucket and over a leaf (2 of 1,152
    elements off by 1.5e-8 at 0.127 on the CPU, one step's loss at
    6.0e-8 relative on the v5e), so bitwise is not the contract."""
    from jax import lax

    from cs744_pytorch_distributed_tutorial_tpu.parallel.zero import Zero1SGD

    tree = _tree()
    g = _stacked(tree)

    def run(bucket_bytes):
        opt = Zero1SGD(0.1, 0.9, 1e-4, "data", 4, bucket_bytes=bucket_bytes)
        mom = opt.init(tree)

        def f(p, m, gs):
            gl = jax.tree.map(lambda a: a[0], gs)
            return opt.apply(p, m, gl)

        return jax.jit(
            jax.shard_map(
                f, mesh=mesh4, in_specs=(P(), P("data"), P("data")),
                out_specs=(P(), P("data")), check_vma=False,
            )
        )(tree, mom, g)

    p0, m0 = run(0)
    p1, m1 = run(B.DEFAULT_BUCKET_BYTES)
    for a, b in zip(jax.tree.leaves((p0, m0)), jax.tree.leaves((p1, m1))):
        a, b = np.asarray(a), np.asarray(b)
        if a.dtype == np.float32:
            np.testing.assert_array_max_ulp(a, b, maxulp=1)
        else:  # numpy counts no ulps in bfloat16: that leaf stays exact
            np.testing.assert_array_equal(a, b)


def test_compress_rejects_incompatible_sync(mesh4):
    from cs744_pytorch_distributed_tutorial_tpu.config import TrainConfig
    from cs744_pytorch_distributed_tutorial_tpu.train import Trainer

    cfg = TrainConfig(
        model="tiny_cnn", num_devices=4, global_batch_size=16,
        sync="gather_scatter", grad_compress="int8",
    )
    with pytest.raises(ValueError, match="grad_compress"):
        Trainer(cfg, mesh=mesh4)
