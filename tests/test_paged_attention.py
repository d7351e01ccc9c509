"""Pallas paged-attention decode kernel (ops/paged_attention.py).

Against the gather+einsum reference that stays in
``parallel/ring_attention.py`` / ``ops/quant.py``, over both ways the
kernel fetches pages (``PATHS``: the walk, for float pools whose folded
rows are whole lane tiles; a page a grid step, for the rest):

1. **Parity** — float (f32/bf16 pools) and int8-KV (dequant inside the
   kernel) match the reference within the flash tolerance discipline.
   Online softmax reassociates the reduction, so this is tolerance-level
   by design, not bitwise (the gather path keeps the bitwise story).
2. **The walk's edges** — live pages of 1, N-1, N, N+1 and the whole
   capacity in one batch, ``pos`` 0, a last page holding one row, a page
   table with repeated and shuffled pages, a capacity that is not a
   multiple of the block; and a slot's stale buffer rows never leak into
   the next slot's answer.
3. **Live pages only** — pages past a slot's live length are NEVER read:
   poisoning every dead page with NaN must not change the output.
4. **Tensor-parallel** — under ``shard_map`` with pools sharded over KV
   heads (and q over query heads), per-shard kernels reproduce the
   unsharded answer: everything derives from local shapes.
5. **Copies follow live pages, not capacity** — the walk's trip count is
   a runtime value, so XLA's ``cost_analysis`` of the interpreted kernel
   says nothing about it; instead every ``make_async_copy(...).start()``
   the walk executes is counted through a debug callback: two a live
   page (K and V), the same at any page-table capacity, where the gather
   reference's compiled bytes grow with the capacity.
6. **The chunk walk** (``paged_chunk_attention``, a prefill chunk over one
   latent pool) against the view gathered and attended by position, with
   every page past the chunk's last position poisoned.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from cs744_pytorch_distributed_tutorial_tpu.utils.profiling import compiled_costs
from cs744_pytorch_distributed_tutorial_tpu.ops import (
    paged_attention as kernel_module,
)
from cs744_pytorch_distributed_tutorial_tpu.ops.paged_attention import (
    paged_attention,
    paged_chunk_attention,
)
from cs744_pytorch_distributed_tutorial_tpu.ops.quant import (
    paged_decode_attention_quant,
)
from cs744_pytorch_distributed_tutorial_tpu.parallel.ring_attention import (
    paged_decode_attention,
)

B, HQ, HKV, D = 3, 4, 2, 16
# (Hq, Hkv, D) a fetch path: folded rows of 32 lanes go a page a grid
# step, of 128 lanes through the walk.
PATHS = {"page_step": (4, 2, 16), "walk": (4, 2, 64)}


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


@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize(
    "dtype,tol",
    [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)],
    ids=["f32", "bf16"],
)
def test_kernel_matches_gather_reference(dtype, tol, path):
    hq, hkv, d = PATHS[path]
    page_size, ppr = 4, 4
    kp, vp = _pools(jax.random.key(0), 17, page_size, dtype, hkv, d)
    table, pos = _layout(17, page_size, ppr)
    q = jax.random.normal(jax.random.key(1), (B, 1, hq, d), jnp.float32)
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


VARIANTS = pytest.mark.parametrize(
    "quant,path",
    [(False, "page_step"), (True, "page_step"), (False, "walk")],
    ids=["float", "int8", "float-walk"],
)


@VARIANTS
def test_pages_per_slot_prunes_without_changing_live_slots(quant, path):
    """``pages_per_slot`` narrows the page table (and with it the walk's
    capacity, or the grid); slots whose live pages all lie inside the
    pruned width read the same answer."""
    hq, hkv, d = PATHS[path]
    page_size, ppr, num_pages = 4, 4, 17
    table, _ = _layout(num_pages, page_size, ppr, seed=6)
    pos = jnp.asarray([0, 5, 2 * page_size - 1], jnp.int32)  # <= 2 pages
    q = jax.random.normal(jax.random.key(22), (B, 1, hq, d), jnp.float32)
    if quant:
        kp, vp, ksc, vsc = _int8_pools(jax.random.key(23), num_pages, page_size)
        sc = dict(key_scale_pages=ksc, value_scale_pages=vsc)
    else:
        kp, vp = _pools(jax.random.key(23), num_pages, page_size, hkv=hkv, d=d)
        sc = {}
    full = paged_attention(q, kp, vp, table, pos, interpret=True, **sc)
    pruned = paged_attention(
        q, kp, vp, table, pos, interpret=True, pages_per_slot=2, **sc
    )
    np.testing.assert_array_equal(np.asarray(pruned), np.asarray(full))


@pytest.mark.parametrize("path", PATHS)
def test_kernel_never_reads_dead_pages(path):
    """Poison every page past each slot's live length (and every
    unreferenced pool page, the trash page 0 among them) with NaN: the
    output must stay finite and EQUAL to the clean run. The walk copies
    a slot's live pages and no other; a page a grid step, dead steps
    re-point at the last live page and issue no new read."""
    hq, hkv, d = PATHS[path]
    page_size, ppr, num_pages = 4, 4, 33
    kp, vp = _pools(jax.random.key(4), num_pages, page_size, hkv=hkv, d=d)
    table, pos = _layout(num_pages, page_size, ppr, seed=2)
    q = jax.random.normal(jax.random.key(5), (B, 1, hq, d), jnp.float32)
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


@VARIANTS
def test_kernel_tensor_parallel_matches_unsharded(quant, path):
    """Pools sharded over KV heads (a contiguous lane range of the
    folded last dimension), q over query heads (the serving TP layout):
    per-shard kernels over the LOCAL Hkv reproduce the unsharded kernel —
    no head-index plumbing needed. The walk's case shards 256 lanes into
    two of 128, so each shard walks too."""
    from cs744_pytorch_distributed_tutorial_tpu.parallel import make_mesh

    hq, hkv, d = PATHS[path]
    d *= 2 if path == "walk" else 1
    page_size, ppr, num_pages = 4, 4, 17
    table, pos = _layout(num_pages, page_size, ppr, seed=3)
    q = jax.random.normal(jax.random.key(6), (B, 1, hq, d), jnp.float32)
    if quant:
        kp, vp, *scales = _int8_pools(jax.random.key(7), num_pages, page_size)
    else:
        kp, vp = _pools(jax.random.key(7), num_pages, page_size, hkv=hkv, d=d)
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


# ------------------------------------------------------ the walk's edges

WALK_PAGE = 16
WALK_BLOCK = kernel_module._BLOCK_TOKENS // WALK_PAGE  # pages a block


def _walk_batch(capacity, dtype=jnp.float32):
    """One batch over the walk's edges: live pages of 1 (``pos`` 0, and a
    full first page), N-1, N, N+1 whose last page holds one row, and the
    whole capacity; pages shuffled through the pool, one slot's table
    repeating a page."""
    hq, hkv, d = PATHS["walk"]
    n, ps = WALK_BLOCK, WALK_PAGE
    depths = [
        0, ps - 1, (n - 1) * ps - 3, n * ps - 1, n * ps,
        capacity * ps - 1, (capacity - 1) * ps,
    ]
    slots = len(depths)
    num_pages = 1 + slots * capacity
    rng = np.random.default_rng(7)
    table = 1 + rng.permutation(num_pages - 1).reshape(slots, capacity)
    table[2, 4] = table[2, 1]  # the same page at two places of a slot
    kp, vp = _pools(jax.random.key(30), num_pages, ps, dtype, hkv, d)
    q = jax.random.normal(jax.random.key(31), (slots, 1, hq, d), jnp.float32)
    return (
        q.astype(dtype), kp, vp, jnp.asarray(table, jnp.int32),
        jnp.asarray(depths, jnp.int32),
    )


@pytest.mark.parametrize(
    "capacity",
    [2 * WALK_BLOCK, 2 * WALK_BLOCK + WALK_BLOCK // 2],
    ids=["whole-blocks", "ragged-capacity"],
)
@pytest.mark.parametrize(
    "dtype,tol",
    [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)],
    ids=["f32", "bf16"],
)
def test_walk_block_edges(dtype, tol, capacity):
    q, kp, vp, table, pos = _walk_batch(capacity, dtype)
    assert kernel_module._pages_per_block(
        capacity, WALK_PAGE, kp.shape[-1] * kp.dtype.itemsize
    ) == WALK_BLOCK
    want = _dense_reference(q, kp, vp, table, pos)
    got = paged_attention(q, kp, vp, table, pos, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), want, rtol=tol, atol=tol
    )


def test_walk_stale_buffer_rows_do_not_leak():
    """A slot whose LIVE rows hold NaN answers NaN; the next slot, whose
    last block copies fewer pages than the buffer holds, must not: the
    rows it did not copy are masked in the scores and zeroed in V."""
    q, kp, vp, table, pos = _walk_batch(2 * WALK_BLOCK)
    full, short = 5, 2  # the whole capacity, then N-1 pages
    order = jnp.asarray([full, short])
    # the last page of either block: a row of either buffer that the
    # short slot's one block does not copy over
    bad = table[full, jnp.asarray([WALK_BLOCK - 1, 2 * WALK_BLOCK - 1])]
    vp = vp.at[bad].set(jnp.nan)
    got = np.asarray(
        paged_attention(
            q[order], kp, vp, table[order], pos[order], interpret=True
        )
    )
    assert np.isnan(got[0]).any()
    want = _dense_reference(
        q[order][1:], kp, vp, table[order][1:], pos[order][1:]
    )
    np.testing.assert_allclose(got[1:], want, rtol=2e-5, atol=2e-5)


# --------------------------------------- copies follow live pages, not capacity


@pytest.fixture
def page_copies(monkeypatch):
    """Counts every ``make_async_copy(...).start()`` the kernel EXECUTES
    (a debug callback beside the start, under the same ``pl.when`` and
    inside the same runtime-bounded loop)."""
    started = []
    real = kernel_module.pltpu.make_async_copy

    class Counted:
        def __init__(self, copy):
            self.copy = copy

        def start(self):
            jax.debug.callback(lambda: started.append(1))
            self.copy.start()

        def wait(self):
            self.copy.wait()

    monkeypatch.setattr(
        kernel_module.pltpu, "make_async_copy",
        lambda *a: Counted(real(*a)),
    )

    def count(*args, **kw):
        del started[:]
        out = paged_attention(*args, interpret=True, **kw)
        jax.block_until_ready(out)
        jax.effects_barrier()
        return len(started), np.asarray(out)

    return count


def _contiguous(live_pages, capacity, page_size):
    """Every slot ``live_pages`` deep, pages handed out in order from 1;
    the table's dead entries point at the trash page 0."""
    hq, hkv, d = PATHS["walk"]
    num_pages = 1 + B * 8
    kp, vp = _pools(jax.random.key(8), num_pages, page_size, hkv=hkv, d=d)
    table = np.zeros((B, capacity), np.int32)
    for b in range(B):
        table[b, :live_pages] = 1 + b * live_pages + np.arange(live_pages)
    pos = jnp.full((B,), live_pages * page_size - 1, jnp.int32)
    q = jax.random.normal(jax.random.key(9), (B, 1, hq, d), jnp.float32)
    return q, kp, vp, jnp.asarray(table), pos


@pytest.mark.parametrize("page_size", [4, 8])
def test_page_copies_follow_live_pages_not_capacity(page_size, page_copies):
    """The perf claim, counted: a decode step copies two pages (K and V)
    a live page, whatever the page table could hold — live tokens, not
    max_seq_len, set the HBM traffic. The trash page every dead table
    entry points at is poisoned, so a copy of it would show."""
    outs = {}
    for live, capacity in [(1, 8), (2, 8), (4, 8), (2, 32)]:
        q, kp, vp, table, pos = _contiguous(live, capacity, page_size)
        kp, vp = kp.at[0].set(jnp.nan), vp.at[0].set(jnp.nan)
        n, outs[live, capacity] = page_copies(q, kp, vp, table, pos)
        assert n == 2 * B * live, (live, capacity, n)
        assert np.isfinite(outs[live, capacity]).all()
    np.testing.assert_allclose(
        outs[2, 32], outs[2, 8], rtol=2e-6, atol=2e-6
    )


def test_kernel_copies_flat_where_gather_grows(page_copies):
    """Same pools, same live length, growing capacity: the gather
    reference's compiled bytes grow with the table width (it always
    materializes the dense [B, P*page_size] view); the copies the kernel
    issues do not, and every page past the live two is poisoned."""
    hq, hkv, d = PATHS["walk"]
    page_size, num_pages = 4, 129
    kp, vp = _pools(jax.random.key(10), num_pages, page_size, hkv=hkv, d=d)
    q = jax.random.normal(jax.random.key(11), (B, 1, hq, d), jnp.float32)
    pos = jnp.full((B,), 2 * page_size - 1, jnp.int32)  # 2 live pages

    def table_of(capacity):
        table = np.zeros((B, capacity), np.int32)
        for b in range(B):
            table[b, :capacity] = 1 + b * capacity + np.arange(capacity)
        return table

    def gather_bytes(capacity):
        lowered = jax.jit(
            lambda q, kp, vp, table: paged_decode_attention(
                q, kp, vp, table, pos
            )
        ).lower(q, kp, vp, jnp.asarray(table_of(capacity)))
        return compiled_costs(lowered.compile())["bytes_accessed"]

    def kernel_copies(capacity):
        table = table_of(capacity)
        dead = np.setdiff1d(np.arange(num_pages), table[:, :2])
        n, out = page_copies(
            q, kp.at[dead].set(jnp.nan), vp.at[dead].set(jnp.nan),
            jnp.asarray(table), pos,
        )
        assert np.isfinite(out).all()
        return n

    g8, g32 = gather_bytes(8), gather_bytes(32)
    assert g32 > 1.5 * g8, (g8, g32)
    assert kernel_copies(8) == kernel_copies(32) == 2 * B * 2


# ------------------------------------------------- the chunk walk, latent pool


@pytest.mark.parametrize(
    "heads,value_lanes,offset,length,chunk,width,small",
    [
        (4, 32, 0, 16, 16, 6, True),  # at offset 0, a block's rows a page
        (8, 64, 13, 20, 24, 6, True),  # mid-page, the last rows padding
        (4, 64, 43, 24, 24, 12, True),  # across several key blocks
        (8, 32, 40, 24, 32, 8, True),  # the last rows past the table
        (8, 64, 100, 40, 40, 20, False),  # the blocks the chip runs
    ],
    ids=["offset-0", "mid-page", "key-blocks", "past-table", "full-blocks"],
)
def test_chunk_walk_matches_the_gathered_view(
    heads, value_lanes, offset, length, chunk, width, small, monkeypatch
):
    """A chunk of ``length`` real rows at ``offset`` (padded to
    ``chunk``) over one latent pool: the walk (interpret mode) against
    ``attend_by_position`` over the gathered view. The table lists the
    pages that hold positions up to the chunk's last real one, then 0
    (the trash page) as the engine leaves it; every page past that
    position, page 0 among them, holds NaN, so a finite answer shows
    they are never read. Padding rows come back 0. ``small`` shrinks
    the query and key blocks so that several of each are walked."""
    from cs744_pytorch_distributed_tutorial_tpu.models.latent import (
        attend_by_position,
    )
    from cs744_pytorch_distributed_tutorial_tpu.parallel.ring_attention import (
        gather_pages,
    )

    if small:
        monkeypatch.setattr(kernel_module, "_CHUNK_ROWS", 32)
        monkeypatch.setattr(kernel_module, "_CHUNK_BLOCK_TOKENS", 16)
    page, lanes, scale = 8, 128, 0.09
    live = (offset + length - 1) // page + 1
    rng = np.random.default_rng(offset)
    pages = 1 + rng.permutation(width + 4)[:live]
    table = np.zeros((1, width), np.int32)
    table[0, :live] = pages
    pool = np.full((width + 5, page, lanes), np.nan, np.float32)
    pool[pages] = np.asarray(
        jax.random.normal(jax.random.key(offset), (live, page, lanes))
    )
    q = jax.random.normal(jax.random.key(1), (1, chunk, heads, lanes))
    got = np.asarray(paged_chunk_attention(
        q, jnp.asarray(pool), jnp.asarray(table), jnp.asarray([offset]),
        jnp.asarray([length]), value_lanes=value_lanes, scale=scale,
        interpret=True,
    ))
    assert got.shape == (1, chunk, heads, value_lanes)
    assert np.isfinite(got).all()
    assert (got[:, length:] == 0).all()
    view = gather_pages(jnp.nan_to_num(jnp.asarray(pool)), jnp.asarray(table))
    want = attend_by_position(
        q[:, :length], view[:, :, None, :], view[:, :, None, :value_lanes],
        offset + jnp.arange(length)[None], scale,
    )
    np.testing.assert_allclose(got[:, :length], want, rtol=2e-5, atol=2e-5)


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
