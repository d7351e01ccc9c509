"""The lightning and block-sparse kernels (ops/lightning.py,
ops/block_sparse.py), Mosaic-compiled (``interpret=False``) at the
``sala-serve-longctx-closed-1chip`` cell's shapes and compared with
their plain-XLA forms (the engine's "gather" path).

The lightning update is float32 throughout, so it is held to float32
round-off; the chunk's ``Q K^T`` and its product with ``V`` run in
bfloat16 on the MXU against the form's own bfloat16 einsums, and the
block-sparse walks' ``p . V`` in bfloat16 against the gathered rows' (the
decode step's) and the tiled XLA form's (the chunk's).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from parity import assert_close

from cs744_pytorch_distributed_tutorial_tpu.ops import block_sparse as B
from cs744_pytorch_distributed_tutorial_tpu.ops import lightning as L

H, D = 32, 128
RATE = 2.0 ** (-np.arange(1, H + 1) / 4.0) * (1.0 - 1.0 / 31 + 1e-5)


def _normal(seed, shape, dtype=jnp.float32):
    return jax.random.normal(jax.random.key(seed), shape, jnp.float32).astype(dtype)


def test_lightning_decode_cell_shape():
    b = 32
    q, k, v = (_normal(i, (b, H, D), jnp.bfloat16) for i in range(3))
    state = _normal(3, (b, H, D, D))
    live = jnp.asarray(np.arange(b) % 5 != 0, jnp.int32)
    rate = jnp.asarray(RATE, jnp.float32)
    got = jax.jit(lambda *a: L.lightning_decode(*a, interpret=False))(q, k, v, state, rate, live)
    want = L.decode_reference(q, k, v, state, rate, live)
    assert_close(got[1], want[1], 1e-5)
    assert_close(got[0], want[0], 1e-4)


def test_lightning_chunk_cell_shape():
    c, slots = 512, 32
    q, k, v = (_normal(i, (c, H, D), jnp.bfloat16) for i in range(3))
    q = q * D ** -0.5
    state = _normal(3, (slots, H, D, D))
    rate = jnp.asarray(RATE, jnp.float32)
    for offset, length in ((0, 512), (8192, 300)):
        got_o, got_s = jax.jit(lambda *a: L.lightning_chunk(*a, interpret=False))(
            q, k, v, state, rate, 7, offset, length
        )
        prev = jnp.zeros((H, D, D)) if offset == 0 else state[7]
        want_o, want_s = L.chunk_reference(q, k, v, prev, rate, length)
        assert_close(got_o[:length], want_o[:length], 2e-2)
        assert_close(got_s[7], want_s, 1e-3)
        np.testing.assert_array_equal(np.asarray(got_s[:7]), np.asarray(state[:7]))


def test_block_sparse_decode_cell_shape():
    b, g, ps, cap = 32, 2, 16, 4352
    sp = B.BlockSparse()
    num_pages = b * cap + 1
    kp, vp = (_normal(i, (num_pages, ps, g * D), jnp.bfloat16) for i in (0, 1))
    rng = np.random.default_rng(0)
    table = jnp.asarray(1 + rng.permutation(num_pages - 1).reshape(b, cap), jnp.int32)
    pos = jnp.asarray(rng.integers(100, cap * ps, b), jnp.int32)
    q = _normal(2, (b, H, D), jnp.bfloat16)
    ckeys = _normal(3, (b, cap, g, D))
    ids, count, _ = B.select(q[:, None], ckeys, pos[:, None], sp, D ** -0.5)
    first, pages = B.decode_tables(ids[:, 0], count[:, 0], table, sp, ps)
    got = jax.jit(
        lambda *a: B.block_sparse_decode(*a, sp, D ** -0.5, interpret=False)
    )(q, kp, vp, first, pages, count[:, 0], pos)
    want = B.decode_reference(q, kp, vp, first, pages, pos, sp, D ** -0.5)
    assert_close(got, want, 2e-2)


@pytest.mark.parametrize("offset", [0, 8192, 40960])
@pytest.mark.parametrize("length", [512, 300])
def test_block_sparse_chunk_cell_shape(offset, length):
    """The chunk walk at the cell's shapes (a chunk of 512 queries, 32
    heads over 2 KV heads of 128, a slot of 4,352 pages of 16): at
    position 0, at dense_len and deep past it, whole and with padding
    rows, against the XLA form over the same selection."""
    c, g, ps, cap = 512, 2, 16, 4352
    sp = B.BlockSparse()
    num_pages = cap + 1
    kp, vp = (_normal(i, (num_pages, ps, g * D), jnp.bfloat16) for i in (0, 1))
    rng = np.random.default_rng(offset + length)
    table = jnp.asarray(1 + rng.permutation(num_pages - 1), jnp.int32)
    q = _normal(2, (c, H, D), jnp.bfloat16)
    pos = offset + jnp.arange(c)
    ckeys = _normal(3, (1, cap, g, D))
    ids, _, _ = B.select(q[None], ckeys, pos[None], sp, D ** -0.5)
    allowed = B.allowed_blocks(ids[0], cap * ps // sp.block_size)
    got = jax.jit(
        lambda *a: B.block_sparse_chunk_attention(*a, sp, D ** -0.5, interpret=False)
    )(q, kp, vp, table, jnp.int32(offset), jnp.int32(length), allowed)
    want = jax.jit(
        lambda *a: B.chunk_attention(*a, sp, D ** -0.5)
    )(q, kp, vp, table, pos, allowed)
    assert_close(got[:length], want[:length], 2e-2)
    assert not np.asarray(got[length:], np.float32).any()
