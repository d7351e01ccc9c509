"""Pallas paged-attention decode kernel (ops/paged_attention.py).

Four contracts, each against the gather+einsum reference that stays in
``parallel/ring_attention.py`` / ``ops/quant.py``:

1. **Parity** — float (f32/bf16 pools) and int8-KV (dequant inside the
   kernel) match the reference within the flash tolerance discipline.
   Online softmax reassociates the reduction, so this is tolerance-level
   by design, not bitwise (the gather path keeps the bitwise story).
2. **Live pages only** — pages past a slot's live length are NEVER read:
   poisoning every dead page with NaN must not change the output. This
   is the functional face of the clamped index_map (dead grid iterations
   re-point at the last live page, so no new DMA issues).
3. **Tensor-parallel** — under ``shard_map`` with pools sharded over KV
   heads (and q over query heads), per-shard kernels reproduce the
   unsharded answer: the grid derives from local shapes.
4. **Bytes scale with live tokens** — compiled ``cost_analysis``
   bytes-accessed for a decode step grows linearly with the live page
   count and is EXACTLY invariant to page-table capacity, at two pool
   geometries. The XLA CPU cost model counts operand shapes (the
   interpret-mode grid loop is counted once), so the test compiles a
   step whose operands ARE the live working set: pages allocated
   contiguously from 1, pool statically sliced to the live pages,
   ``pages_per_slot`` pruning the table — making "bytes ~ live, not
   max_seq_len" visible analytically on CPU. The same CPU cost model is
   why the un-sliced comparison still pins the gather reference's bytes
   growing with capacity while the kernel's stay flat.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from cs744_pytorch_distributed_tutorial_tpu.obs.phases import compiled_costs
from cs744_pytorch_distributed_tutorial_tpu.ops.paged_attention import (
    paged_attention,
)
from cs744_pytorch_distributed_tutorial_tpu.ops.quant import (
    paged_decode_attention_quant,
)
from cs744_pytorch_distributed_tutorial_tpu.parallel.ring_attention import (
    paged_decode_attention,
)

B, HQ, HKV, D = 3, 4, 2, 16


def _pools(key, num_pages, page_size, dtype=jnp.float32, hkv=HKV, d=D):
    """Pools as the engine stores them: ``[num_pages, page_size,
    Hkv*D]``, head ``h`` in lanes ``[h*D, (h+1)*D)``."""
    kk, kv = jax.random.split(key)
    shape = (num_pages, page_size, hkv * d)
    return (
        jax.random.normal(kk, shape, jnp.float32).astype(dtype),
        jax.random.normal(kv, shape, jnp.float32).astype(dtype),
    )


def _int8_pools(key, num_pages, page_size, hkv=HKV, d=D):
    """int8 data pools (folded) and their ``[num_pages, page_size,
    Hkv]`` scale pools."""
    ks = jax.random.split(key, 4)
    shape = (num_pages, page_size, hkv * d)
    kp, vp = (
        jax.random.randint(k, shape, -127, 128, jnp.int32).astype(jnp.int8)
        for k in ks[:2]
    )
    ksc, vsc = (
        jax.random.uniform(
            k, (num_pages, page_size, hkv), jnp.float32, 0.5 / 127, 1.5 / 127
        )
        for k in ks[2:]
    )
    return kp, vp, ksc, vsc


def _dense_reference(q, kp, vp, table, pos, ksc=None, vsc=None):
    """The slot's view gathered page by page on the host and attended
    with plain numpy: independent of ``gather_pages`` and of the fold's
    reshape, so a head landing in the wrong lanes shows."""
    q, kp, vp = (np.asarray(x, np.float32) for x in (q, kp, vp))
    table, pos = np.asarray(table), np.asarray(pos)
    b, _, hq, d = q.shape
    hkv = kp.shape[-1] // d
    out = np.zeros((b, 1, hq, d), np.float32)
    for bi in range(b):
        n = int(pos[bi]) + 1
        for h in range(hq):
            g = h // (hq // hkv)
            k = kp[table[bi]].reshape(-1, hkv * d)[:n, g * d:(g + 1) * d]
            v = vp[table[bi]].reshape(-1, hkv * d)[:n, g * d:(g + 1) * d]
            if ksc is not None:
                k = k * np.asarray(ksc)[table[bi]].reshape(-1, hkv)[:n, g, None]
                v = v * np.asarray(vsc)[table[bi]].reshape(-1, hkv)[:n, g, None]
            s = k @ q[bi, 0, h] * d**-0.5
            w = np.exp(s - s.max())
            out[bi, 0, h] = (w / w.sum()) @ v
    return out


def _layout(num_pages, page_size, ppr, seed=0):
    """Distinct pages per slot (shuffled — order must not matter) and
    staggered live depths, including a fresh slot at pos 0."""
    rng = np.random.default_rng(seed)
    perm = 1 + rng.permutation(num_pages - 1)[: B * ppr]
    table = jnp.asarray(perm.reshape(B, ppr), jnp.int32)
    depths = [0, page_size * (ppr - 1), ppr * page_size - 1][:B]
    pos = jnp.asarray(depths, jnp.int32)
    return table, pos


@pytest.mark.parametrize(
    "dtype,tol",
    [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)],
    ids=["f32", "bf16"],
)
def test_kernel_matches_gather_reference(dtype, tol):
    page_size, ppr = 4, 4
    kp, vp = _pools(jax.random.key(0), 17, page_size, dtype)
    table, pos = _layout(17, page_size, ppr)
    q = jax.random.normal(jax.random.key(1), (B, 1, HQ, D), jnp.float32)
    q = q.astype(dtype)
    expected = np.asarray(
        paged_decode_attention(q, kp, vp, table, pos), jnp.float32
    )
    got = np.asarray(
        paged_attention(q, kp, vp, table, pos, interpret=True), jnp.float32
    )
    np.testing.assert_allclose(got, expected, rtol=tol, atol=tol)


def test_kernel_int8_matches_quant_reference():
    """int8 pools + per-row scale pools, dequant INSIDE the kernel —
    same algebra as decode_attention_quant (k_scale on scores, v_scale
    folded into probs)."""
    page_size, ppr, num_pages = 4, 4, 17
    kp, vp, ksc, vsc = _int8_pools(jax.random.key(2), num_pages, page_size)
    table, pos = _layout(num_pages, page_size, ppr, seed=1)
    q = jax.random.normal(jax.random.key(3), (B, 1, HQ, D), jnp.float32)
    expected = np.asarray(
        paged_decode_attention_quant(q, kp, vp, ksc, vsc, table, pos)
    )
    got = np.asarray(
        paged_attention(
            q, kp, vp, table, pos,
            key_scale_pages=ksc, value_scale_pages=vsc, interpret=True,
        )
    )
    np.testing.assert_allclose(got, expected, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize(
    "hq,hkv,d,quant",
    [
        (4, 2, 16, False),  # GQA, Hkv*D = 32
        (4, 2, 16, True),
        (3, 3, 24, False),  # Hkv*D = 72: not a multiple of 128, nor of 8
        (6, 3, 24, True),
        (2, 2, 64, False),  # Hkv*D = 128: one full lane tile
        (12, 12, 64, False),  # the serve cell's heads: 768 lanes
    ],
    ids=["gqa", "gqa-int8", "odd72", "odd72-gqa-int8", "lanes128", "gpt2s"],
)
def test_folded_pools_match_host_reference(hq, hkv, d, quant):
    """Kernel AND gather reference against a numpy walk of the page
    table, over head geometries whose folded width is and is not a lane
    multiple. The gather reference must also be bitwise what
    ``decode_attention`` gives on the unfolded dense view."""
    from cs744_pytorch_distributed_tutorial_tpu.ops.quant import (
        decode_attention_quant,
    )
    from cs744_pytorch_distributed_tutorial_tpu.parallel.ring_attention import (
        decode_attention,
    )

    page_size, ppr, num_pages = 4, 4, 17
    table, pos = _layout(num_pages, page_size, ppr, seed=5)
    q = jax.random.normal(jax.random.key(20), (B, 1, hq, d), jnp.float32)
    if quant:
        kp, vp, ksc, vsc = _int8_pools(
            jax.random.key(21), num_pages, page_size, hkv, d
        )
        sc = dict(key_scale_pages=ksc, value_scale_pages=vsc)
        gathered = paged_decode_attention_quant(q, kp, vp, ksc, vsc, table, pos)
    else:
        kp, vp = _pools(jax.random.key(21), num_pages, page_size, hkv=hkv, d=d)
        ksc = vsc = None
        sc = {}
        gathered = paged_decode_attention(q, kp, vp, table, pos)
    want = _dense_reference(q, kp, vp, table, pos, ksc, vsc)
    np.testing.assert_allclose(np.asarray(gathered), want, rtol=2e-5, atol=2e-5)
    got = paged_attention(q, kp, vp, table, pos, interpret=True, **sc)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)

    # bitwise: gather-then-unfold IS the dense cache's layout
    def dense(pool, width):
        view = np.asarray(pool)[np.asarray(table)]  # [B, P, page, width*]
        return jnp.asarray(view.reshape(B, ppr * page_size, hkv, *width))

    if quant:
        exact = decode_attention_quant(
            q, dense(kp, (d,)), dense(vp, (d,)), dense(ksc, ()),
            dense(vsc, ()), pos,
        )
    else:
        exact = decode_attention(q, dense(kp, (d,)), dense(vp, (d,)), pos)
    np.testing.assert_array_equal(np.asarray(gathered), np.asarray(exact))


@pytest.mark.parametrize("quant", [False, True], ids=["float", "int8"])
def test_pages_per_slot_prunes_without_changing_live_slots(quant):
    """``pages_per_slot`` narrows table and grid; slots whose live pages
    all lie inside the pruned width read the same answer."""
    page_size, ppr, num_pages = 4, 4, 17
    table, _ = _layout(num_pages, page_size, ppr, seed=6)
    pos = jnp.asarray([0, 5, 2 * page_size - 1], jnp.int32)  # <= 2 pages
    q = jax.random.normal(jax.random.key(22), (B, 1, HQ, D), jnp.float32)
    if quant:
        kp, vp, ksc, vsc = _int8_pools(jax.random.key(23), num_pages, page_size)
        sc = dict(key_scale_pages=ksc, value_scale_pages=vsc)
    else:
        kp, vp = _pools(jax.random.key(23), num_pages, page_size)
        sc = {}
    full = paged_attention(q, kp, vp, table, pos, interpret=True, **sc)
    pruned = paged_attention(
        q, kp, vp, table, pos, interpret=True, pages_per_slot=2, **sc
    )
    np.testing.assert_array_equal(np.asarray(pruned), np.asarray(full))


def test_kernel_never_reads_dead_pages():
    """Poison every page past each slot's live length (and every
    unreferenced pool page) with NaN: the output must stay finite and
    EQUAL to the clean run — the clamped index_map means dead grid
    iterations issue no new reads."""
    page_size, ppr, num_pages = 4, 4, 33
    kp, vp = _pools(jax.random.key(4), num_pages, page_size)
    table, pos = _layout(num_pages, page_size, ppr, seed=2)
    q = jax.random.normal(jax.random.key(5), (B, 1, HQ, D), jnp.float32)
    clean = np.asarray(paged_attention(q, kp, vp, table, pos, interpret=True))

    live = np.asarray(pos) // page_size + 1
    live_pages = {
        int(np.asarray(table)[b, i])
        for b in range(B)
        for i in range(int(live[b]))
    }
    dead = np.asarray([p for p in range(num_pages) if p not in live_pages])
    kp = np.asarray(kp).copy()
    vp = np.asarray(vp).copy()
    kp[dead] = np.nan
    vp[dead] = np.nan
    poisoned = np.asarray(
        paged_attention(
            q, jnp.asarray(kp), jnp.asarray(vp), table, pos, interpret=True
        )
    )
    assert np.isfinite(poisoned).all()
    np.testing.assert_array_equal(poisoned, clean)


@pytest.mark.parametrize("quant", [False, True], ids=["float", "int8"])
def test_kernel_tensor_parallel_matches_unsharded(quant):
    """Pools sharded over KV heads (a contiguous lane range of the
    folded last dimension), q over query heads (the serving TP layout):
    per-shard grids over the LOCAL Hkv reproduce the unsharded kernel —
    no head-index plumbing needed."""
    from cs744_pytorch_distributed_tutorial_tpu.parallel import make_mesh

    page_size, ppr, num_pages = 4, 4, 17
    table, pos = _layout(num_pages, page_size, ppr, seed=3)
    q = jax.random.normal(jax.random.key(6), (B, 1, HQ, D), jnp.float32)
    if quant:
        kp, vp, *scales = _int8_pools(jax.random.key(7), num_pages, page_size)
    else:
        kp, vp = _pools(jax.random.key(7), num_pages, page_size)
        scales = ()

    def call(q, kp, vp, *scales):
        sc = (
            dict(key_scale_pages=scales[0], value_scale_pages=scales[1])
            if scales
            else {}
        )
        return paged_attention(q, kp, vp, table, pos, interpret=True, **sc)

    expected = np.asarray(call(q, kp, vp, *scales))
    mesh = make_mesh({"tensor": 2}, devices=jax.devices()[:2])
    head = P(None, None, "tensor", None)
    # folded pools and scale pools alike carry the heads last
    pool = P(None, None, "tensor")
    in_specs = (head, pool, pool) + (pool,) * len(scales)
    mapped = jax.shard_map(
        call, mesh=mesh, in_specs=in_specs, out_specs=head, check_vma=False
    )
    got = np.asarray(jax.jit(mapped)(q, kp, vp, *scales))
    np.testing.assert_allclose(got, expected, rtol=2e-5, atol=2e-5)


# ------------------------------------------------ analytical bytes gate


def _kernel_step_bytes(live_pages, capacity, page_size):
    """Compiled bytes-accessed for one decode step over a LIVE working
    set: pages contiguous from 1, pool sliced to them, table pruned to
    ``pages_per_slot=live_pages`` (module docstring on why the slice is
    what makes live-scaling visible to the CPU cost model)."""
    k_live = B * live_pages + 1  # + trash page 0
    kp, vp = _pools(jax.random.key(8), k_live, page_size)
    table = np.zeros((B, capacity), np.int32)
    for b in range(B):
        table[b, :live_pages] = 1 + b * live_pages + np.arange(live_pages)
    pos = jnp.full((B,), live_pages * page_size - 1, jnp.int32)
    q = jax.random.normal(jax.random.key(9), (B, 1, HQ, D), jnp.float32)

    def step(q, kp, vp, table):
        return paged_attention(
            q, kp, vp, table, pos, interpret=True,
            pages_per_slot=live_pages,
        )

    compiled = jax.jit(step).lower(q, kp, vp, jnp.asarray(table)).compile()
    return compiled_costs(compiled)["bytes_accessed"]


@pytest.mark.parametrize("page_size", [4, 8])
def test_cost_bytes_scale_with_live_pages_not_capacity(page_size):
    """The perf claim, gated analytically: bytes per decode step grow
    LINEARLY in live pages (equal increments per extra page) and are
    EXACTLY unchanged by page-table capacity — live tokens, not
    max_seq_len, set the HBM traffic."""
    b1, b2, b4 = (
        _kernel_step_bytes(n, capacity=8, page_size=page_size)
        for n in (1, 2, 4)
    )
    assert b1 < b2 < b4
    # linear: the marginal cost of one more live page is constant
    step1, step2 = b2 - b1, (b4 - b2) / 2
    assert abs(step2 - step1) <= 0.25 * step1, (b1, b2, b4)
    # capacity invariance: a 4x wider table moves nothing
    assert b2 == _kernel_step_bytes(2, capacity=32, page_size=page_size)


def test_cost_bytes_kernel_flat_where_gather_grows():
    """Same pools, same live length, growing capacity: the gather
    reference's compiled bytes grow with the table width (it always
    materializes the dense [B, P*page_size] view); the kernel's do not."""
    page_size, num_pages = 4, 129
    kp, vp = _pools(jax.random.key(10), num_pages, page_size)
    q = jax.random.normal(jax.random.key(11), (B, 1, HQ, D), jnp.float32)
    pos = jnp.full((B,), 2 * page_size - 1, jnp.int32)  # 2 live pages

    def bytes_of(fn, capacity):
        table = np.zeros((B, capacity), np.int32)
        for b in range(B):
            table[b, :capacity] = 1 + b * capacity + np.arange(capacity)
        lowered = jax.jit(fn).lower(q, kp, vp, jnp.asarray(table))
        return compiled_costs(lowered.compile())["bytes_accessed"]

    def kernel(q, kp, vp, table):
        return paged_attention(q, kp, vp, table, pos, interpret=True)

    def gather(q, kp, vp, table):
        return paged_decode_attention(q, kp, vp, table, pos)

    g8, g32 = bytes_of(gather, 8), bytes_of(gather, 32)
    k8, k32 = bytes_of(kernel, 8), bytes_of(kernel, 32)
    assert g32 > 1.5 * g8, (g8, g32)
    assert k8 == k32, (k8, k32)


def test_validation():
    page_size, ppr, num_pages = 4, 2, 9
    kp, vp = _pools(jax.random.key(12), num_pages, page_size)
    table, pos = _layout(num_pages, page_size, ppr, seed=4)
    q = jax.random.normal(jax.random.key(13), (B, 2, HQ, D), jnp.float32)
    with pytest.raises(ValueError, match="one token at a time"):
        paged_attention(q, kp, vp, table, pos, interpret=True)
    q = q[:, :1, :3]  # 3 query heads, 2 kv heads
    with pytest.raises(ValueError, match="not a multiple"):
        paged_attention(q, kp, vp, table, pos, interpret=True)
    q = jax.random.normal(jax.random.key(14), (B, 1, HQ, D), jnp.float32)
    with pytest.raises(ValueError, match="both scale pools"):
        paged_attention(
            q, kp, vp, table, pos,
            key_scale_pages=jnp.ones((*kp.shape[:2], HKV)), interpret=True,
        )
    # the old 4-D pools, and rows that are not whole heads, are refused
    for bad in (kp.reshape(*kp.shape[:2], HKV, D), kp[..., :-1]):
        with pytest.raises(ValueError, match=r"Hkv\*D"):
            paged_attention(q, bad, bad, table, pos, interpret=True)
