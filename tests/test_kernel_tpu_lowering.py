"""Every Pallas kernel lowers for TPU — checked from the CPU.

``jax.jit(f).trace(*args).lower(lowering_platforms=("tpu",))`` runs the
Pallas -> Mosaic lowering without a chip. It is where a block shape that
breaks Mosaic's tiling rule (last two block dims divisible by (8, 128)
or equal to the array's) is rejected — the defect that kept the paged
attention kernel from ever compiling — so every kernel goes through it
here with ``interpret=False`` at a production shape. Necessary, not
sufficient: VMEM limits and layout inference are checked by the Mosaic
compiler itself (``tests_chip/test_kernels.py``).
"""

import jax
import jax.numpy as jnp
import pytest

from cs744_pytorch_distributed_tutorial_tpu.ops.flash_attention import (
    flash_attention,
)
from cs744_pytorch_distributed_tutorial_tpu.ops.fused_conv import conv3x3_wgrad
from cs744_pytorch_distributed_tutorial_tpu.ops.fused_sgd import FusedSGD
from cs744_pytorch_distributed_tutorial_tpu.ops.fused_xent import (
    fused_cross_entropy,
)
from cs744_pytorch_distributed_tutorial_tpu.ops.gmm import (
    grouped_matmul,
    grouped_matmul_fused,
)
from cs744_pytorch_distributed_tutorial_tpu.ops.paged_attention import (
    paged_attention,
)
from cs744_pytorch_distributed_tutorial_tpu.ops import block_sparse as sparse
from cs744_pytorch_distributed_tutorial_tpu.ops import lightning
from cs744_pytorch_distributed_tutorial_tpu.ops.quant import int8_matmul

bf16, f32, i8, i32 = jnp.bfloat16, jnp.float32, jnp.int8, jnp.int32


def _s(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _sum_grad(f, argnums):
    return jax.grad(
        lambda *a: jnp.sum(f(*a).astype(f32)), argnums=argnums
    )


def _flash(q, k, v):
    return flash_attention(q, k, v, True)


def _gmm(x, w, gs):
    return grouped_matmul(x, w, gs, impl="pallas")


def _gmm_gelu(x, w, b, gs):
    return grouped_matmul_fused(x, w, b, gs, activation="gelu")


def _paged(q, k, v, table, pos, ks=None, vs=None):
    return paged_attention(
        q, k, v, table, pos, key_scale_pages=ks, value_scale_pages=vs,
        interpret=False,
    )


def _sgd(p, m, g):
    return FusedSGD(0.1, 0.9, 1e-4, interpret=False).apply(
        {"w": p}, {"w": m}, {"w": g}
    )


def _paged_args(
    hq, hkv, d, pool_dtype, q_dtype, slots=8, pages_per_slot=8, num_pages=64
):
    args = [
        _s((slots, 1, hq, d), q_dtype),
        _s((num_pages, 16, hkv * d), pool_dtype),  # pools fold the heads
        _s((num_pages, 16, hkv * d), pool_dtype),
        _s((slots, pages_per_slot), i32),
        _s((slots,), i32),
    ]
    if pool_dtype == i8:
        args += [_s((num_pages, 16, hkv), f32)] * 2
    return args


_GMM = [_s((4096, 512), bf16), _s((8, 512, 2048), bf16)]
_GS = _s((8,), i32)
_XENT = [_s((2048, 50257), bf16), _s((2048,), i32)]

CASES = {
    "flash_fwd_12x64": (_flash, [_s((2, 1024, 12, 64), bf16)] * 3),
    "flash_bwd_12x64": (
        _sum_grad(_flash, (0, 1, 2)), [_s((2, 1024, 12, 64), bf16)] * 3
    ),
    "flash_fwd_8x128": (_flash, [_s((2, 1024, 8, 128), bf16)] * 3),
    "flash_bwd_8x128": (
        _sum_grad(_flash, (0, 1, 2)), [_s((2, 1024, 8, 128), bf16)] * 3
    ),
    "gmm_fwd": (_gmm, [*_GMM, _GS]),
    "gmm_bwd": (_sum_grad(_gmm, (0, 1)), [*_GMM, _GS]),
    "gmm_gelu_fwd": (_gmm_gelu, [*_GMM, _s((8, 2048), f32), _GS]),
    "gmm_gelu_bwd": (
        _sum_grad(_gmm_gelu, (0, 1, 2)), [*_GMM, _s((8, 2048), f32), _GS]
    ),
    "paged_f32_12x64": (_paged, _paged_args(12, 12, 64, f32, f32)),
    "paged_bf16_12x64": (_paged, _paged_args(12, 12, 64, bf16, bf16)),
    "paged_int8_12x64": (_paged, _paged_args(12, 12, 64, i8, bf16)),
    "paged_f32_gqa_4x128": (_paged, _paged_args(32, 4, 128, f32, f32)),
    # the serve cell's own geometry: 128 slots of 64 pages, 8193 in the pool
    "paged_bf16_12x64_serve_cell": (
        _paged, _paged_args(12, 12, 64, bf16, bf16, 128, 64, 8193)
    ),
    # Hkv*D = 192: not a lane multiple, so a page a grid step, the
    # block's last dim the array's
    "paged_bf16_3x64": (_paged, _paged_args(3, 3, 64, bf16, bf16)),
    "paged_int8_gqa_4x128": (_paged, _paged_args(32, 4, 128, i8, f32)),
    "int8_matmul_lm_head": (
        lambda x, q, s: int8_matmul(x, q, s, interpret=False),
        [_s((8, 768), bf16), _s((768, 50304), i8), _s((50304,), f32)],
    ),
    "fused_xent_fwd": (fused_cross_entropy, _XENT),
    "fused_xent_bwd": (_sum_grad(fused_cross_entropy, 0), _XENT),
    "fused_sgd": (_sgd, [_s((3, 3, 512, 512), f32)] * 3),
    "conv3x3_wgrad_s1": (
        lambda x, g: conv3x3_wgrad(x, g, stride=1, interpret=False),
        [_s((4096, 16, 16, 128), bf16), _s((4096, 16, 16, 128), bf16)],
    ),
    "conv3x3_wgrad_s2": (
        lambda x, g: conv3x3_wgrad(x, g, stride=2, interpret=False),
        [_s((4096, 32, 32, 64), bf16), _s((4096, 16, 16, 128), bf16)],
    ),
    # the long-context SALA cell's geometry: 32 slots, 32 heads of 128,
    # chunks of 512, 4,352 pages a slot over 2 KV heads
    "lightning_decode_sala_cell": (
        lambda *a: lightning.lightning_decode(*a, interpret=False),
        [_s((32, 32, 128), bf16)] * 3
        + [_s((32, 32, 128, 128), f32), _s((32,), f32), _s((32,), i32)],
    ),
    "lightning_chunk_sala_cell": (
        lambda *a: lightning.lightning_chunk(*a, interpret=False),
        [_s((512, 32, 128), bf16)] * 3
        + [_s((32, 32, 128, 128), f32), _s((32,), f32)] + [_s((), i32)] * 3,
    ),
    "block_sparse_decode_sala_cell": (
        lambda *a: sparse.block_sparse_decode(
            *a, sparse.BlockSparse(), 128 ** -0.5, interpret=False
        ),
        [_s((32, 32, 128), bf16)] + [_s((139265, 16, 256), bf16)] * 2
        + [_s((32, 2, 128), i32), _s((32, 2, 512), i32), _s((32, 2), i32),
           _s((32,), i32)],
    ),
    "block_sparse_chunk_sala_cell": (
        lambda *a: sparse.block_sparse_chunk_attention(
            *a, sparse.BlockSparse(), 128 ** -0.5, interpret=False
        ),
        [_s((512, 32, 128), bf16)] + [_s((139265, 16, 256), bf16)] * 2
        + [_s((4352,), i32), _s((), i32), _s((), i32),
           _s((512, 2, 1088), jnp.bool_)],
    ),
}


@pytest.mark.parametrize("name", CASES)
def test_kernel_lowers_for_tpu(name):
    fn, args = CASES[name]
    lowered = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))
    # the kernel really is in there, not inlined by the interpreter
    assert "tpu_custom_call" in lowered.as_text()
