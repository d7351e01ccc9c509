"""The paged KV pools' device layout, held without a chip.

The engine's decode step and one prefill bucket are compiled for the v5e
through libtpu's compile-only topology (``serve/layout.py`` has the
recipe and the reason): a lane-realistic geometry (12 heads x 64, pages
of 16, bf16) on a small pool. Per program, the compiled module must show

(a) no ``copy`` whose result has a data pool's element count: with the
    4-D ``[num_pages, page_size, Hkv, D]`` pools every program converted
    each pool to row-major and back, two such copies a pool (this test
    at the parent of PR 26: 8 in each program, 2 layers x K and V x 2);
(b) temporaries under one pool's bytes;
(c) the pools entering row-major;
(d) the prefill's commit writing a whole page an index (PR 37).

One exception, counted and named: under ``scan_layers`` the decode step
keeps ONE same-layout copy of each stacked pool, ``lax.scan`` reading
the stack as ``xs`` and writing it as ``ys`` (the buffers cannot alias);
that is the scan's, not the layout's, and stays an open question in
``PERF.md``. The int8 variant's small scale pools
(``[num_pages, page_size, Hkv]`` float32) still convert, an open
question there too: (a) and (c) hold its data pools, (b) is the float
pools' alone.

A model with the sparse-attention indexer has a third pool a layer, the
indexer's keys, and is served by chunks: its decode step and its one
chunk program are held to (a) and (c) for all three pools, at 64-wide
indexer keys (the width the benchmark's configuration has). The pool is
``[num_pages, page_size, 128]``, the keys padded to a whole lane tile:
at ``[.., 64]`` bf16 the compiler laid it out with ``num_pages``
minor-most and each program copied it twice, the data pools' old fault.
(b) is held against the K pool for the decode step and against one
layer's three pools for the chunk, whose temporaries are its attention
scores: they grow with the slot's capacity and not with the pool.

The topology is described inside a fixture and every compile runs in
the test's own process (one process loads libtpu at a time); where no
topology can be described the tests skip and say why.
``tests_chip/test_kernels.py`` makes the same three assertions on the
device at the serve cell's full geometry.
"""

from __future__ import annotations

import math
import os

import jax
import jax.numpy as jnp
import pytest

from cs744_pytorch_distributed_tutorial_tpu.models import TransformerLM
from cs744_pytorch_distributed_tutorial_tpu.serve import (
    ServeConfig,
    ServingEngine,
)
from cs744_pytorch_distributed_tutorial_tpu.serve.layout import (
    audit,
    compile_programs,
)

BUCKET = 32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no libtpu, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def compiled(one_chip):
    """(quant, scan) -> (engine, its two programs compiled for the
    v5e), each pair compiled once for its two tests."""
    cache = {}

    def get(quant, scan):
        if (quant, scan) not in cache:
            model = TransformerLM(
                vocab_size=512, num_layers=2, num_heads=12, d_model=768,
                d_ff=1024, max_seq_len=256, dtype=jnp.bfloat16,
                attention_impl="dense", quant_kv_cache=quant,
                scan_layers=scan,
                # steer the CPU process onto the chip's path: the Pallas
                # kernel, Mosaic-compiled
                flash_interpret=False,
            )
            params = jax.eval_shape(
                lambda: model.init(
                    jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
                )
            )["params"]
            engine = ServingEngine(
                model, params,
                ServeConfig(
                    num_slots=8, page_size=16, num_pages=257,
                    max_pages_per_slot=16, paged_attention_impl="kernel",
                ),
            )
            cache[quant, scan] = engine, compile_programs(
                engine, BUCKET, one_chip
            )
        return cache[quant, scan]

    return get


@pytest.mark.parametrize("program", ["decode", "prefill"])
@pytest.mark.parametrize("scan", [False, True], ids=["unrolled", "scan"])
@pytest.mark.parametrize("quant", [False, True], ids=["float", "int8"])
def test_no_program_copies_a_pool(compiled, quant, scan, program):
    engine, programs = compiled(quant, scan)
    got = audit(programs[program], engine)
    # (c) row-major as it enters
    assert got.entry_layouts and got.row_major, got.entry_layouts
    # (a) no pool-sized copy ...
    if scan and program == "decode":
        # ... but the scan's own, one a stacked pool and no layout change
        assert len(got.pool_copies) <= len(got.entry_layouts), got
        for shape, minor_to_major in got.pool_copies:
            assert minor_to_major == tuple(reversed(range(len(shape))))
    else:
        assert got.pool_copies == []
    # (b) nothing pool-sized among the temporaries. Not held for int8:
    # there they are the scale pools' row-major copies (2.1 MB each at
    # this size, lanes padded 12 -> 128), which this layout leaves be.
    if not quant:
        assert got.temp_bytes < got.pool_bytes, got


@pytest.mark.parametrize("scan", [False, True], ids=["unrolled", "scan"])
@pytest.mark.parametrize("quant", [False, True], ids=["float", "int8"])
def test_prefill_commits_a_page_an_index(compiled, quant, scan):
    """(d) The prefill's commit writes each data pool a whole page an
    index: one scatter a pool (a stack of them under ``scan_layers``),
    its updates ``[.., page_size, lanes]``, at most ceil(BUCKET /
    page_size) indices a layer. XLA's writer pays per index (PR 37, on
    the v5e: 0.12 us a 768-lane row, 0.17 us a page of 16 of them), and
    row by row a bucket-1024 prompt made 24 pools x 1,024 of them."""
    engine, programs = compiled(quant, scan)
    got = audit(programs["prefill"], engine)
    pools = [
        leaf for path, leaf in jax.tree_util.tree_leaves_with_path(
            engine._pages
        ) if "scale" not in path[-1].key
    ]
    assert len(got.pool_writes) == len(pools), got.pool_writes
    page = pools[0].shape[-2:]
    layers = pools[0].shape[0] if scan else 1
    for _, updates in got.pool_writes:
        assert updates[-2:] == page, got.pool_writes
        n_pages = -(-BUCKET // page[0])
        assert math.prod(updates[:-2]) <= layers * n_pages, got.pool_writes


@pytest.fixture(scope="module")
def sparse_compiled(one_chip):
    from cs744_pytorch_distributed_tutorial_tpu.models import keye_model_config

    hf = dict(
        vocab_size=512, num_hidden_layers=2, num_attention_heads=8,
        num_key_value_heads=2, head_dim=128, hidden_size=256,
        moe_intermediate_size=256, max_position_embeddings=512,
        rope_theta=1e7, rms_norm_eps=1e-6, num_experts=8,
        num_experts_per_tok=2, norm_topk_prob=True,
        sa_config=dict(indexer_head_dim=64, indexer_num_heads=4,
                       indexer_num_kv_heads=1, topk=128),
    )
    model = TransformerLM(
        **keye_model_config(hf), dtype=jnp.bfloat16, flash_interpret=False,
    )
    params = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    )["params"]
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, jnp.bfloat16), params
    )
    engine = ServingEngine(
        model, params,
        ServeConfig(
            num_slots=4, page_size=16, num_pages=513, max_pages_per_slot=32,
            prefill_chunk=128,
        ),
    )
    return engine, compile_programs(engine, 0, one_chip)


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_no_program_copies_any_of_three_pools(sparse_compiled, program):
    engine, programs = sparse_compiled
    names = {
        path[-1].key
        for path, _ in jax.tree_util.tree_leaves_with_path(engine._pages)
    }
    assert names == {"key_pages", "value_pages", "index_key_pages"}
    got = audit(programs[program], engine)
    assert len(got.entry_layouts) == 3 * 2 and got.row_major, got.entry_layouts
    assert got.pool_copies == []
    k_pool_bytes = 513 * 16 * 2 * 128 * 2
    assert got.pool_bytes < k_pool_bytes  # the smallest pool: the indexer's
    # (b): the decode step's temporaries under one K pool; the chunk's
    # (float32 scores of a KV head's queries over the view) under one
    # layer's three pools
    most = k_pool_bytes if program == "decode" else 2 * k_pool_bytes + got.pool_bytes
    assert got.temp_bytes < most, got


@pytest.fixture(scope="module")
def latent_compiled(one_chip):
    """A model with latent attention: ONE pool a sublayer, at the
    published lane widths (a latent of 512 and a rope key of 64 in a
    row of 640 lanes), few heads and small matrices."""
    from cs744_pytorch_distributed_tutorial_tpu.models import (
        longcat_flash_model_config,
    )

    hf = dict(
        vocab_size=512, hidden_size=256, ffn_hidden_size=512,
        expert_ffn_hidden_size=256, num_layers=1, num_attention_heads=8,
        kv_lora_rank=512, q_lora_rank=128, qk_rope_head_dim=64,
        v_head_dim=128, qk_nope_head_dim=128, mla_scale_q_lora=True,
        mla_scale_kv_lora=True, routed_scaling_factor=6, n_routed_experts=16,
        max_position_embeddings=512,
        rms_norm_eps=1e-5, rope_theta=1e7, zero_expert_num=8, moe_topk=4,
    )
    model = TransformerLM(
        **longcat_flash_model_config(hf, held_experts=range(4)),
        dtype=jnp.bfloat16, flash_interpret=False,
    )
    params = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    )["params"]
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, jnp.bfloat16), params
    )
    engine = ServingEngine(
        model, params,
        ServeConfig(
            num_slots=4, page_size=16, num_pages=513, max_pages_per_slot=32,
            prefill_chunk=128, paged_attention_impl="kernel",
        ),
    )
    return engine, compile_programs(engine, 0, one_chip)


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_no_program_copies_a_latent_pool(latent_compiled, program):
    """One pool a sublayer, ``[num_pages, page_size, 640]``: no
    pool-sized copy in either program, the pools row-major as they
    enter, and each program's walk one call a sublayer over that one
    pool: ``attn_latent`` in the decode step, ``attn_latent_chunk`` in
    the chunk program."""
    engine, programs = latent_compiled
    pools = jax.tree_util.tree_leaves_with_path(engine._pages)
    assert {path[-1].key for path, _ in pools} == {"latent_pages"}
    assert [leaf.shape for _, leaf in pools] == [(513, 16, 640)] * 2
    got = audit(programs[program], engine)
    assert len(got.entry_layouts) == 2 and got.row_major, got.entry_layouts
    assert got.pool_copies == []
    assert got.pool_bytes == 513 * 16 * 640 * 2
    # (b): the chunk walks the pool: no scores over the view
    assert got.temp_bytes < got.pool_bytes, got
    text = programs[program].as_text()
    scope = "/attn_latent/" if program == "decode" else "/attn_latent_chunk/"
    walks = {
        line.split(" = ")[0].strip() for line in text.splitlines()
        if "tpu_custom_call" in line and scope in line
    }
    assert len(walks) == 2, walks
    for line in text.splitlines():
        if "tpu_custom_call" in line and scope in line:
            # the scalars, table, q, ONE pool
            assert line.count("bf16[513,16,640]") == 1, line
