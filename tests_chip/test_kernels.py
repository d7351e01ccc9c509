"""Every Pallas kernel in the tree, Mosaic-compiled (``interpret=False``)
at a production shape and compared with its ``jax.numpy`` reference.

The CPU suite runs the same kernels through the Pallas interpreter,
which checks the arithmetic but none of what Mosaic checks on the
device: block tiling, VMEM limits, layout inference. One case per
kernel here; small-shape edge cases (empty groups, ragged tiles) stay
in ``test_gmm.py`` and the CPU suite.

References run at ``Precision.HIGHEST``: on TPU a default-precision f32
matmul is a single bf16 pass, so tolerances are bf16-sized wherever the
kernel feeds the MXU bf16 (or f32 at default precision).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from parity import assert_close

from cs744_pytorch_distributed_tutorial_tpu.ops.flash_attention import (
    flash_attention,
    flash_tile_plan,
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
from cs744_pytorch_distributed_tutorial_tpu.ops.quant import (
    int8_matmul,
    int8_matmul_ref,
    paged_decode_attention_quant,
    quantize_int8,
    quantize_kv,
)
from cs744_pytorch_distributed_tutorial_tpu.parallel.ring_attention import (
    paged_decode_attention,
)

HIGHEST = jax.lax.Precision.HIGHEST


def _normal(seed, shape, dtype=jnp.float32, scale=1.0):
    x = jax.random.normal(jax.random.key(seed), shape, jnp.float32) * scale
    return x.astype(dtype)


# ------------------------------------------------------------ flash attention
def _dense_causal(q, k, v):
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST)
    s = s * q.shape[-1] ** -0.5
    t = q.shape[1]
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HIGHEST)


@pytest.mark.parametrize(
    "batch,heads,head_dim",
    [(2, 12, 64), (2, 8, 128), (16, 12, 64)],
    ids=["12x64", "8x128", "lm_cell_b16_12x64"],
)
def test_flash_attention_fwd_bwd(batch, heads, head_dim):
    """Causal, T=1024, bf16 — GPT-2-small's and a llama-style head, and
    the LM training cell's own call ([16, 1024, 12, 64]), under the
    tiles ``flash_tile_plan`` gives them."""
    shape = (batch, 1024, heads, head_dim)
    for sweep in ("keys", "queries"):
        plan = flash_tile_plan(1024, 1024, True, sweep)
        assert plan.skipped > 0 and plan.run > plan.masked
    q, k, v = (_normal(i, shape, jnp.bfloat16) for i in range(3))
    w = _normal(3, shape)  # fixed cotangent: a weighted sum as the loss

    def loss(attn):
        return lambda q, k, v: jnp.sum(attn(q, k, v).astype(jnp.float32) * w)

    flash = lambda q, k, v: flash_attention(q, k, v, True)  # noqa: E731
    out = jax.jit(flash)(q, k, v)
    assert out.dtype == jnp.bfloat16
    assert_close(out, _dense_causal(q, k, v), 5e-2)
    got = jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2)))(q, k, v)
    want = jax.jit(jax.grad(loss(_dense_causal), argnums=(0, 1, 2)))(q, k, v)
    # four bf16 roundings deep (g, p, ds, the result): measured 4e-2 of
    # this bound through the interpreter, where the dots are exact f32
    for g, r in zip(got, want):
        assert_close(g, r, 1.5e-1)


# ------------------------------------------------------------- grouped matmul
E, D_MODEL, D_FF, ROWS = 8, 512, 2048, 4096
GROUP_SIZES = [700, 0, 512, 300, 1000, 84, 988, 512]  # sums to ROWS


def _gmm_inputs(dtype):
    x = _normal(0, (ROWS, D_MODEL), dtype)
    w = _normal(1, (E, D_MODEL, D_FF), dtype, scale=D_MODEL**-0.5)
    b = _normal(2, (E, D_FF))
    return x, w, b, jnp.asarray(GROUP_SIZES, jnp.int32)


def _gmm_ref(x, w, gs, b=None, gelu=False):
    ids = jnp.repeat(jnp.arange(E), gs, total_repeat_length=ROWS)
    y = jax.lax.ragged_dot(
        x.astype(jnp.float32), w.astype(jnp.float32), gs, precision=HIGHEST
    )
    if b is not None:
        y = y + b[ids]
    return jax.nn.gelu(y) if gelu else y


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_gmm_fwd_bwd(dtype):
    """The MoE expert matmul at E=8, 512 -> 2048, default blocks."""
    x, w, _, gs = _gmm_inputs(dtype)
    pallas = lambda x, w: grouped_matmul(x, w, gs, impl="pallas")  # noqa: E731
    assert_close(jax.jit(pallas)(x, w), _gmm_ref(x, w, gs), 5e-2)
    ct = _normal(5, (ROWS, D_FF))
    got = jax.jit(jax.grad(
        lambda x, w: jnp.sum(pallas(x, w).astype(jnp.float32) * ct),
        argnums=(0, 1),
    ))(x, w)
    want = jax.jit(jax.grad(
        lambda x, w: jnp.sum(_gmm_ref(x, w, gs) * ct), argnums=(0, 1)
    ))(x, w)
    assert_close(got[0], want[0], 5e-2)
    assert_close(got[1], want[1], 5e-2)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_gmm_fused_gelu_fwd_bwd(dtype):
    """The bias+gelu epilogue kernel and its backward (dbias rides a K=1
    tgmm) at the same shape."""
    x, w, b, gs = _gmm_inputs(dtype)
    fused = lambda x, w, b: grouped_matmul_fused(  # noqa: E731
        x, w, b, gs, activation="gelu"
    )
    assert_close(jax.jit(fused)(x, w, b), _gmm_ref(x, w, gs, b, gelu=True), 5e-2)
    ct = _normal(6, (ROWS, D_FF))
    got = jax.jit(jax.grad(
        lambda x, w, b: jnp.sum(fused(x, w, b).astype(jnp.float32) * ct),
        argnums=(0, 1, 2),
    ))(x, w, b)
    want = jax.jit(jax.grad(
        lambda x, w, b: jnp.sum(_gmm_ref(x, w, gs, b, gelu=True) * ct),
        argnums=(0, 1, 2),
    ))(x, w, b)
    for g, r in zip(got, want):
        assert_close(g, r, 5e-2)


# ------------------------------------------------------------ paged attention
SLOTS, PAGE, NUM_PAGES, PAGES_PER_SLOT = 8, 16, 256, 24


def _paged_inputs(hq, hkv, d, dtype):
    q = _normal(0, (SLOTS, 1, hq, d), dtype)
    # pools as the engine stores them: heads folded into the lanes
    k = _normal(1, (NUM_PAGES, PAGE, hkv * d), dtype)
    v = _normal(2, (NUM_PAGES, PAGE, hkv * d), dtype)
    rng = np.random.default_rng(0)
    # distinct live pages per slot; page 0 is the engine's trash page
    table = rng.permutation(np.arange(1, NUM_PAGES))[: SLOTS * PAGES_PER_SLOT]
    table = jnp.asarray(table.reshape(SLOTS, PAGES_PER_SLOT), jnp.int32)
    # depths from a fresh slot (0) to a full table, page edges included
    pos = jnp.asarray([0, 15, 16, 100, 255, 256, 300, 383], jnp.int32)
    return q, k, v, table, pos


@pytest.mark.parametrize("hq,hkv,d", [(12, 12, 64), (32, 4, 128), (3, 3, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_float(hq, hkv, d, dtype):
    """GPT-2-small serving heads (12 x 64) and a GQA shape (32/4 x 128)
    through the walk, 3 x 64 (192 lanes: a page a grid step); page 16,
    slots at mixed depths."""
    q, k, v, table, pos = _paged_inputs(hq, hkv, d, dtype)
    got = jax.jit(
        lambda *a: paged_attention(*a, interpret=False)
    )(q, k, v, table, pos)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(paged_decode_attention)(
            *(x.astype(jnp.float32) for x in (q, k, v)), table, pos
        )
    assert got.shape == q.shape and got.dtype == dtype
    assert_close(got, want, 3e-2)


@pytest.mark.parametrize("hq,hkv,d", [(12, 12, 64), (32, 4, 128)])
@pytest.mark.parametrize("qdtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_int8_kv(hq, hkv, d, qdtype):
    q, k, v, table, pos = _paged_inputs(hq, hkv, d, jnp.float32)
    q = q.astype(qdtype)
    # quantize per (row, head), then fold the int8 rows again
    kq, ks = quantize_kv(k.reshape(NUM_PAGES, PAGE, hkv, d))
    vq, vs = quantize_kv(v.reshape(NUM_PAGES, PAGE, hkv, d))
    kq, vq = kq.reshape(k.shape), vq.reshape(v.shape)
    got = jax.jit(
        lambda q, kq, vq, ks, vs, table, pos: paged_attention(
            q, kq, vq, table, pos, key_scale_pages=ks, value_scale_pages=vs,
            interpret=False,
        )
    )(q, kq, vq, ks, vs, table, pos)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(paged_decode_attention_quant)(
            q.astype(jnp.float32), kq, vq, ks, vs, table, pos
        )
    assert got.dtype == qdtype
    assert_close(got, want, 3e-2)


def test_paged_attention_serve_cell_shape():
    """The serve cell's own call: 128 slots of up to 64 pages of 16 in a
    pool of 8193, bf16, 12 x 64; depths from a fresh slot to a full one,
    block edges of the walk (8 pages) among them."""
    slots, capacity, num_pages = 128, 64, 8193
    q = _normal(0, (slots, 1, 12, 64), jnp.bfloat16)
    k = _normal(1, (num_pages, PAGE, 768), jnp.bfloat16)
    v = _normal(2, (num_pages, PAGE, 768), jnp.bfloat16)
    rng = np.random.default_rng(1)
    table = 1 + rng.permutation(num_pages - 1)[: slots * capacity]
    table = jnp.asarray(table.reshape(slots, capacity), jnp.int32)
    pos = rng.integers(0, capacity * PAGE, slots)
    pos[:8] = [0, 15, 111, 127, 128, 143, 1007, 1023]
    pos = jnp.asarray(pos, jnp.int32)
    got = jax.jit(
        lambda *a: paged_attention(*a, interpret=False)
    )(q, k, v, table, pos)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(paged_decode_attention)(
            *(x.astype(jnp.float32) for x in (q, k, v)), table, pos
        )
    assert_close(got, want, 3e-2)


def test_paged_attention_mixed_cell_shapes():
    """The mixed cell's two calls (32 slots, 32 heads of 128 over 4 KV
    heads, pages of 16, bf16). A window layer's: the window group's
    table of 97 pages a slot, the walk started inside it by
    ``first_pos`` and ``window`` 1,024, slots from a fresh one to one 33k
    deep, the table's first page well behind the window in some. A full
    layer's: the full group's whole ``[32, 2080]`` table as a
    scalar-prefetched operand, depths to 33,279."""
    from cs744_pytorch_distributed_tutorial_tpu.ops.sparse_attention import (
        masked_attention,
    )
    from cs744_pytorch_distributed_tutorial_tpu.parallel.ring_attention import (
        gather_pages,
        unfold_heads,
    )

    slots, window, width = 32, 1024, 97
    q = _normal(0, (slots, 1, 32, 128), jnp.bfloat16)
    rng = np.random.default_rng(2)
    # ---- a window layer
    pages = slots * width + 1
    k = _normal(1, (pages, PAGE, 512), jnp.bfloat16)
    v = _normal(2, (pages, PAGE, 512), jnp.bfloat16)
    table = jnp.asarray(
        (1 + rng.permutation(pages - 1)).reshape(slots, width), jnp.int32
    )
    pos = rng.integers(0, 33_279, slots)
    pos[:8] = [0, 15, 16, 1022, 1023, 1024, 1039, 33_279]
    # the table starts up to 32 pages behind the page of the oldest key
    oldest_page = np.maximum(pos - window + 1, 0) // PAGE
    first = (np.maximum(oldest_page - rng.integers(0, 33, slots), 0)) * PAGE
    pos, first = jnp.asarray(pos, jnp.int32), jnp.asarray(first, jnp.int32)
    got = jax.jit(lambda *a: paged_attention(
        *a[:5], first_pos=a[5], window=window, interpret=False
    ))(q, k, v, table, pos, first)
    rel = (pos - first)[:, None]
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda q, k, v: masked_attention(
            q,
            unfold_heads(gather_pages(k, table), 128),
            unfold_heads(gather_pages(v, table), 128),
            rel,
            jnp.arange(width * PAGE)[None, None, :] > rel[:, :, None] - window,
        ))(*(x.astype(jnp.float32) for x in (q, k, v)))
    assert_close(got, want, 3e-2)
    # ---- a full layer: the whole table rides in SMEM
    capacity = 2080
    pages = slots * capacity + 1
    k = _normal(3, (pages, PAGE, 512), jnp.bfloat16)
    v = _normal(4, (pages, PAGE, 512), jnp.bfloat16)
    table = jnp.asarray(
        (1 + rng.permutation(pages - 1)).reshape(slots, capacity), jnp.int32
    )
    pos = rng.integers(0, capacity * PAGE, slots)
    pos[:4] = [0, 255, 256, capacity * PAGE - 1]
    pos = jnp.asarray(pos, jnp.int32)
    got = jax.jit(
        lambda *a: paged_attention(*a, interpret=False)
    )(q, k, v, table, pos)
    # the reference a slot at a time: the dense view of 33k keys in f32
    for b in (0, 1, 2, 3, 17):
        with jax.default_matmul_precision("highest"):
            want = jax.jit(paged_decode_attention)(
                q[b:b + 1].astype(jnp.float32), k.astype(jnp.float32),
                v.astype(jnp.float32), table[b:b + 1], pos[b:b + 1],
            )
        assert_close(got[b:b + 1], want, 3e-2)



def test_paged_attention_chat_cell_shape():
    """The chat cell's call over ONE latent pool: 64 slots of up to 288
    pages of 16, bf16, 64 heads' absorbed queries on a 640-lane row
    (512 latent + 64 rope + 64 zero), values the row's first 512 lanes;
    depths from a fresh slot to a full one, block edges of the walk (16
    pages) among them."""
    from cs744_pytorch_distributed_tutorial_tpu.models.latent import (
        attend_by_position,
    )
    from cs744_pytorch_distributed_tutorial_tpu.parallel.ring_attention import (
        gather_pages,
    )

    slots, capacity, num_pages, lanes, r = 64, 288, 18433, 640, 512
    scale = 192 ** -0.5
    q = _normal(0, (slots, 1, 64, lanes), jnp.bfloat16).at[..., 576:].set(0)
    pool = _normal(1, (num_pages, PAGE, lanes), jnp.bfloat16).at[..., 576:].set(0)
    rng = np.random.default_rng(2)
    table = 1 + rng.permutation(num_pages - 1)[: slots * capacity]
    table = jnp.asarray(table.reshape(slots, capacity), jnp.int32)
    pos = rng.integers(0, capacity * PAGE, slots)
    pos[:8] = [0, 15, 255, 256, 257, 511, 4095, 4607]
    pos = jnp.asarray(pos, jnp.int32)
    got = jax.jit(
        lambda q, pool, table, pos: paged_attention(
            q, pool, None, table, pos, value_lanes=r, scale=scale,
            interpret=False,
        )
    )(q, pool, table, pos)

    def reference(q, pool, table, pos):
        view = gather_pages(pool, table).astype(jnp.float32)
        return attend_by_position(
            q.astype(jnp.float32), view[:, :, None, :], view[:, :, None, :r],
            pos[:, None], scale,
        )

    with jax.default_matmul_precision("highest"):
        want = jax.jit(reference)(q, pool, table, pos)
    assert got.shape == (slots, 1, 64, r) and got.dtype == jnp.bfloat16
    assert_close(got, want, 3e-2)


@pytest.mark.parametrize("offset,length", [(0, 512), (8_000, 500), (16_896, 512)])
def test_paged_chunk_attention_doc_cell_shape(offset, length):
    """The doc cell's chunk walk: 512 tokens of 128 heads' absorbed
    queries on a 640-lane row over a slot of 1,088 pages of 16, bf16;
    at offset 0, mid-page with padding rows, and the slot's last
    chunk. The reference attends over the gathered view in float32."""
    from cs744_pytorch_distributed_tutorial_tpu.models.latent import (
        attend_by_position,
    )
    from cs744_pytorch_distributed_tutorial_tpu.ops.paged_attention import (
        paged_chunk_attention,
    )
    from cs744_pytorch_distributed_tutorial_tpu.parallel.ring_attention import (
        gather_pages,
    )

    capacity, num_pages, lanes, r, chunk = 1088, 4097, 640, 512, 512
    scale = 192 ** -0.5
    q = _normal(0, (1, chunk, 128, lanes), jnp.bfloat16).at[..., 576:].set(0)
    pool = _normal(1, (num_pages, PAGE, lanes), jnp.bfloat16).at[..., 576:].set(0)
    rng = np.random.default_rng(offset)
    table = jnp.asarray(
        (1 + rng.permutation(num_pages - 1)[:capacity])[None], jnp.int32
    )
    off, ln = jnp.asarray([offset]), jnp.asarray([length])
    got = jax.jit(
        lambda q, pool, table, off, ln: paged_chunk_attention(
            q, pool, table, off, ln, value_lanes=r, scale=scale,
            interpret=False,
        )
    )(q, pool, table, off, ln)

    def reference(q, pool, table):
        view = gather_pages(pool, table).astype(jnp.float32)
        return attend_by_position(
            q[:, :length].astype(jnp.float32), view[:, :, None, :],
            view[:, :, None, :r], offset + jnp.arange(length)[None], scale,
        )

    with jax.default_matmul_precision("highest"):
        want = jax.jit(reference)(q, pool, table)
    assert got.shape == (1, chunk, 128, r) and got.dtype == jnp.bfloat16
    assert_close(got[:, :length], want, 3e-2)
    assert not np.asarray(got[:, length:], np.float32).any()


@pytest.fixture(scope="module")
def serve_cell_programs():
    from cs744_pytorch_distributed_tutorial_tpu.models import TransformerLM
    from cs744_pytorch_distributed_tutorial_tpu.serve import (
        ServeConfig,
        ServingEngine,
    )
    from cs744_pytorch_distributed_tutorial_tpu.serve.layout import (
        compile_programs,
    )

    model = TransformerLM(
        vocab_size=50304, num_layers=12, num_heads=12, d_model=768,
        d_ff=3072, max_seq_len=1024, dtype=jnp.bfloat16,
        attention_impl="dense",
    )
    params = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    )["params"]
    engine = ServingEngine(
        model, params,
        ServeConfig(
            num_slots=128, page_size=16, num_pages=8193,
            max_pages_per_slot=64,
        ),
    )
    assert engine.paged_attention_impl == "kernel"
    return engine, compile_programs(engine, 512)


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_serve_programs_copy_no_pool(program, serve_cell_programs):
    """The serve cell's own programs, compiled on the device at its full
    geometry (GPT-2 small, 128 slots, 8193 pages of 16, bucket 512):
    the pools enter row-major, no program holds a pool-sized ``copy``
    and the temporaries stay under one pool's bytes. With 4-D pools
    each program held 48 such copies (two a pool) and 0.58-3.82 GB of
    temporaries; ``tests/test_serve_layout.py`` is the chipless twin."""
    from cs744_pytorch_distributed_tutorial_tpu.serve.layout import audit

    engine, programs = serve_cell_programs
    got = audit(programs[program], engine)
    assert len(got.entry_layouts) == 24 and got.row_major, got.entry_layouts
    assert got.pool_copies == []
    assert got.temp_bytes < got.pool_bytes, (got.temp_bytes, got.pool_bytes)


# --------------------------------------------------------------- int8 matmul
@pytest.mark.parametrize("rows", [8, 1024])
def test_int8_matmul_lm_head(rows):
    """GPT-2's 768 x 50304 head: the decode gemv and a prefill matmul."""
    x = _normal(0, (rows, 768), jnp.bfloat16)
    q, scale = quantize_int8(_normal(1, (768, 50304), scale=768**-0.5))
    got = jax.jit(lambda *a: int8_matmul(*a, interpret=False))(x, q, scale)
    want = jax.jit(int8_matmul_ref)(x, q, scale)
    assert got.shape == (rows, 50304)
    assert_close(got, want, 2e-2)


# ---------------------------------------------------------------- fused xent
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_fused_xent_gpt2_vocab(dtype):
    """V=50257 (not a tile multiple: the padded columns must carry no
    mass), forward and the one-pass backward."""
    n, v = 2048, 50257
    logits = _normal(0, (n, v), dtype, scale=2.0)
    labels = jax.random.randint(jax.random.key(1), (n,), 0, v)

    def ref(lg):
        logp = jax.nn.log_softmax(lg.astype(jnp.float32), axis=-1)
        return -jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]

    assert_close(jax.jit(fused_cross_entropy)(logits, labels), ref(logits), 1e-3)
    got = jax.jit(jax.grad(
        lambda lg: fused_cross_entropy(lg, labels).mean()
    ))(logits)
    want = jax.jit(jax.grad(lambda lg: ref(lg).mean()))(logits)
    assert got.dtype == dtype
    assert_close(got * n, want * n, 1e-2 if dtype == jnp.bfloat16 else 1e-4)


# ----------------------------------------------------------------- fused sgd
def test_fused_sgd_resnet_leaves():
    """Aligned and ragged leaves (a 3x3x512x512 conv, a stem conv, a
    bias, the 10-way head bias) against the torch-SGD formula."""
    lr, mu, wd = 0.1, 0.9, 1e-4
    shapes = {"conv": (3, 3, 512, 512), "stem": (3, 3, 3, 64),
              "bn": (64,), "head": (10,)}
    p = {k: _normal(i, s) for i, (k, s) in enumerate(shapes.items())}
    m = {k: _normal(10 + i, s) for i, (k, s) in enumerate(shapes.items())}
    g = {k: _normal(20 + i, s) for i, (k, s) in enumerate(shapes.items())}
    opt = FusedSGD(lr, mu, wd, interpret=False)
    new_p, new_m = jax.jit(opt.apply)(p, m, g)
    for k in shapes:
        want_m = mu * m[k] + (g[k] + wd * p[k])
        assert_close(new_m[k], want_m, 1e-5)
        assert_close(new_p[k], p[k] - lr * want_m, 1e-5)


# ---------------------------------------------------------------- conv wgrad
@pytest.mark.parametrize(
    "hw,cin,cout,stride",
    [(16, 128, 128, 1), (8, 256, 256, 1), (32, 64, 128, 2), (16, 128, 256, 2)],
)
def test_conv3x3_wgrad(hw, cin, cout, stride):
    """ResNet-18 stage-2/3 convs and the two stage-entry stride-2 convs
    at the scored per-chip batch, bf16."""
    batch = 4096
    x = _normal(0, (batch, hw, hw, cin), jnp.bfloat16)
    g = _normal(1, (batch, hw // stride, hw // stride, cout), jnp.bfloat16)
    got = jax.jit(
        lambda x, g: conv3x3_wgrad(x, g, stride=stride, interpret=False)
    )(x, g)

    def conv(w):
        return jax.lax.conv_general_dilated(
            x.astype(jnp.float32), w, (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST,
        )

    w0 = jnp.zeros((3, 3, cin, cout), jnp.float32)
    want = jax.jit(lambda g: jax.vjp(conv, w0)[1](g)[0])(g.astype(jnp.float32))
    assert got.shape == (3, 3, cin, cout) and got.dtype == jnp.float32
    assert_close(got, want, 1e-2)
