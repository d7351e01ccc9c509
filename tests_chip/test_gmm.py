"""Mosaic-compiled parity for the Pallas grouped matmuls at the edges:
an empty group, splits that straddle tiles, a row count that is not a
block multiple, and the ``(block_m, 1)`` lhs block of the K=1 tgmm used
for dbias. ``tests/test_dropless_moe.py`` runs the same oracles through
the interpreter on CPU; the production shape is in ``test_kernels.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from parity import assert_close

from cs744_pytorch_distributed_tutorial_tpu.ops.gmm import (
    grouped_matmul,
    grouped_matmul_fused,
)


def _oracle(x, w, gs):
    ids = np.repeat(np.arange(w.shape[0]), np.asarray(gs))
    return jnp.einsum(
        "nd,ndf->nf", x, jnp.asarray(w)[ids],
        precision=jax.lax.Precision.HIGHEST,
    )


@pytest.mark.parametrize(
    "m,e,gs_list",
    [
        (512, 4, [100, 156, 0, 256]),  # empty group, tile-unaligned splits
        (300, 3, [300, 0, 0]),         # everything in group 0, M % block != 0
    ],
)
def test_gmm_compiled_matches_oracle(m, e, gs_list):
    k, n = 128, 128
    rng = np.random.default_rng(m)
    x = jnp.array(rng.standard_normal((m, k)), jnp.float32)
    w = jnp.array(rng.standard_normal((e, k, n)), jnp.float32)
    gs = jnp.array(gs_list, jnp.int32)
    out = grouped_matmul(
        x, w, gs, impl="pallas", block_m=128, block_n=128, interpret=False
    )
    # f32 inputs at the kernel's default precision against the
    # HIGHEST-precision oracle: a bf16-sized tolerance.
    assert_close(out, _oracle(x, w, gs), 2e-2)


@pytest.mark.parametrize("activation", ["none", "gelu"])
def test_gmm_fused_epilogue_compiled(activation):
    m, e, k, n = 512, 4, 128, 128
    rng = np.random.default_rng(7)
    x = jnp.array(rng.standard_normal((m, k)), jnp.float32)
    w = jnp.array(rng.standard_normal((e, k, n)), jnp.float32)
    b = jnp.array(rng.standard_normal((e, n)), jnp.float32)
    gs = jnp.array([128, 100, 0, 284], jnp.int32)
    fused = grouped_matmul_fused(
        x, w, b, gs, activation=activation,
        block_m=128, block_n=128, interpret=False,
    )
    ids = np.repeat(np.arange(e), np.asarray(gs))
    ref = _oracle(x, w, gs) + jnp.asarray(b)[ids]
    if activation == "gelu":
        ref = jax.nn.gelu(ref)
    assert_close(fused, ref, 2e-2)


def test_gmm_fused_grads_compiled():
    """The custom_vjp pair (dx = gmm, dw = tgmm, dbias = the K=1 tgmm
    row-segment-sum) under the real Mosaic lowering, vs ragged AD."""
    m, e, k, n = 256, 4, 128, 128
    rng = np.random.default_rng(3)
    x = jnp.array(rng.standard_normal((m, k)), jnp.float32)
    w = jnp.array(rng.standard_normal((e, k, n)), jnp.float32)
    b = jnp.array(rng.standard_normal((e, n)), jnp.float32)
    gs = jnp.array([64, 0, 100, 92], jnp.int32)

    def loss_fused(x, w, b):
        return jnp.sum(
            grouped_matmul_fused(
                x, w, b, gs, activation="gelu",
                block_m=128, block_n=128, interpret=False,
            )
            ** 2
        )

    def loss_ref(x, w, b):
        ids = jnp.repeat(jnp.arange(e), gs, total_repeat_length=m)
        y = grouped_matmul(x, w, gs, impl="ragged") + b[ids]
        return jnp.sum(jax.nn.gelu(y) ** 2)

    gf = jax.grad(loss_fused, argnums=(0, 1, 2))(x, w, b)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(x, w, b)
    for a, r in zip(gf, gr):
        assert_close(a, r, 3e-2)
