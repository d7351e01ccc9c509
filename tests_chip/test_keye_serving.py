"""The sparse-attention serving cell's own two programs on the device
(``keye30b-serve-long-closed-1chip``: Keye-VL-2.0-30B-A3B's language
model, 6 layers at published widths, 16 slots, 16,897 pages of 16,
chunks of 512): the three pools a layer enter row-major and no program
copies one (``tests/test_serve_layout.py`` is the chipless twin), and
two layers of the same widths, served past the indexer's top-k, give
the plain reference's tokens within the cell's limit."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cs744_pytorch_distributed_tutorial_tpu.models import (
    TransformerLM,
    keye_model_config,
)
from cs744_pytorch_distributed_tutorial_tpu.serve import (
    Request,
    ServeConfig,
    ServingEngine,
)

ROOT = Path(__file__).resolve().parents[1]
CFG = json.loads((ROOT / "perfbench/configs/keye-vl2-30b-a3b.json").read_text())
TRAFFIC = json.loads((ROOT / "perfbench/traffic/serve-long-closed.json").read_text())
LIMITS = json.loads((ROOT / "perfbench/limits/keye30b-serve-long-closed-1chip.json").read_text())


def _model(cfg):
    return TransformerLM(
        **keye_model_config(cfg, max_seq_len=TRAFFIC["max_total_len"]), dtype=jnp.bfloat16,
    )


@pytest.fixture(scope="module")
def cell_programs():
    from cs744_pytorch_distributed_tutorial_tpu.serve.layout import compile_programs

    model = _model(CFG)
    params = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    )["params"]
    params = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, jnp.bfloat16), params)
    engine = ServingEngine(
        model, params,
        ServeConfig(
            num_slots=TRAFFIC["num_slots"], page_size=TRAFFIC["page_size"],
            num_pages=TRAFFIC["num_pages"], max_pages_per_slot=TRAFFIC["max_pages_per_slot"],
            prefill_chunk=TRAFFIC["prefill_chunk"],
        ),
    )
    programs = compile_programs(engine, 0)
    return engine, programs


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_cell_programs_copy_none_of_three_pools(program, cell_programs):
    from cs744_pytorch_distributed_tutorial_tpu.serve.layout import audit

    engine, programs = cell_programs
    got = audit(programs[program], engine)
    assert len(got.entry_layouts) == 3 * CFG["num_hidden_layers"] and got.row_major, got.entry_layouts
    assert got.pool_copies == []
    k_pool = TRAFFIC["num_pages"] * TRAFFIC["page_size"] * 4 * 128 * 2
    # a chunk's float32 scores of one KV head's eight query heads over
    # the slot's capacity, and little else
    assert got.temp_bytes < 1.25 * k_pool, (got.temp_bytes, k_pool)


def test_two_layers_served_past_topk_match_the_reference():
    from perfbench import weights as W, weights_keye as WK
    from perfbench.drivers.serve_engine_sparse_moe import gap_numbers, served_gaps

    cfg = {**CFG, "num_hidden_layers": 2}
    model = _model(cfg)
    template = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    )["params"]
    flat = WK.make_weights(cfg, 3700000099, "bfloat16")
    engine = ServingEngine(
        model, W.fill_tree(template, flat),
        ServeConfig(num_slots=2, page_size=16, num_pages=513, max_pages_per_slot=256, prefill_chunk=512),
    )
    rng = np.random.default_rng(0)
    reqs = [
        engine.submit(Request(prompt=rng.integers(0, 151936, n).astype(np.int32), max_new_tokens=24))
        for n in (3000, 2300)
    ]
    engine.run()
    stats = engine.stats()
    assert stats["selected_tokens"] < stats["scored_tokens"]  # contexts past 2,048
    del engine
    got = gap_numbers(served_gaps(cfg, flat, [(np.asarray(r.prompt), r.generated) for r in reqs]))
    for name in ("served_logit_gap", "served_logit_gap_mean"):
        assert got[name] <= LIMITS[name], got
