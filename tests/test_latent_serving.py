"""The small LongCat-like configuration (tests/longcat_tiny.py) through
``ServingEngine``: prefill by chunks then absorbed decode, both through ONE paged pool
of latents a sublayer, by the ``gather`` reference and by the page walk
in interpret mode, against the plain reference's full forward (keys and
values built a head, no cache) at every served position; the pools'
accounting through retire, preemption, deadline expiry and a snapshot
restored; the new counters; and a model without latent layers left
exactly as it was.

Tolerance: as tests/test_keye_serving.py judges, the gap by which a
served token's logit lies below the reference's best, held under 1e-4 in
float32 on the CPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cs744_pytorch_distributed_tutorial_tpu.models import TransformerLM
from cs744_pytorch_distributed_tutorial_tpu.obs.system import CompileCounter
from cs744_pytorch_distributed_tutorial_tpu.serve import (
    GuardConfig,
    Request,
    ServeConfig,
    ServeGuard,
    ServingEngine,
)
from perfbench.reference import longcat_flash as R

from longcat_tiny import build, tiny_config

# contexts over several pages of 8 (to 11 of them); chunk 12 divides no prompt
LENGTHS = ((70, 12), (23, 9), (41, 20), (9, 5), (64, 8))
SERVE = dict(num_slots=3, page_size=8, num_pages=49, max_pages_per_slot=14, prefill_chunk=12)


def _requests(seed=0):
    rng = np.random.default_rng(seed)
    return [
        Request(prompt=rng.integers(0, 256, n).astype(np.int32), max_new_tokens=m)
        for n, m in LENGTHS
    ]


def _serve(model, params, **cfg):
    engine = ServingEngine(model, params, ServeConfig(**{**SERVE, **cfg}))
    reqs = [engine.submit(r) for r in _requests()]
    engine.run()
    return engine, reqs


def _answers(reqs):
    return [list(r.prompt[r.orig_prompt_len:]) + list(r.generated) for r in reqs]


def _served_gap(flat, cfg, req):
    seq = np.concatenate([req.prompt, np.asarray(req.generated, np.int32)])
    lo, hi = req.orig_prompt_len - 1, len(seq) - 1
    ref = R.forward(flat, seq, cfg, at=np.arange(lo, hi))
    served = jnp.asarray(seq[lo + 1: hi + 1])
    return float(jnp.max(jnp.max(ref, -1) - jnp.take_along_axis(ref, served[:, None], -1)[:, 0]))


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config()
    return (cfg, *build(cfg))


@pytest.fixture(scope="module")
def served(tiny):
    _, model, params, _ = tiny
    return _serve(model, params, paged_attention_impl="gather")


def test_chunks_then_absorbed_decode_serve_the_reference_s_tokens(tiny, served):
    """Absorbed decode over the latent pool equals keys and values built
    a head: every served token is the full forward pass's best."""
    cfg, _, _, flat = tiny
    engine, reqs = served
    assert all(r.status == "completed" and len(r.generated) == m for r, (_, m) in zip(reqs, LENGTHS))
    for r in reqs:
        assert _served_gap(flat, cfg, r) < 1e-4
    assert engine.stats()["prefill_chunks"] == sum(-(-n // 12) for n, _ in LENGTHS)


def test_the_page_walk_serves_the_same_tokens(tiny, served):
    """The Pallas walks over the one pool (interpret mode), the chunk's
    and decode's: the tokens of the gather reference, judged by the
    plain reference too; the chunk walk's pairs counted (none under
    "gather")."""
    cfg, _, params, flat = tiny
    model, _, _ = build(cfg, flash_interpret=True)
    engine, reqs = _serve(model, params, paged_attention_impl="kernel")
    assert _answers(reqs) == _answers(served[1])
    assert _served_gap(flat, cfg, reqs[0]) < 1e-4
    # the chunk walk's causal pairs a layer, counted at dispatch
    assert engine.stats()["preemptions"] == 0
    chunk = SERVE["prefill_chunk"]
    assert engine.stats()["chunk_attn_pairs"] == sum(
        n * off + n * (n + 1) // 2
        for p, _ in LENGTHS for off in range(0, p, chunk) for n in [min(chunk, p - off)]
    )
    assert served[0].stats()["chunk_attn_pairs"] == 0


def test_one_latent_pool_a_sublayer(tiny, served):
    engine, _ = served
    pools = jax.tree_util.tree_leaves_with_path(engine._pages)
    assert {path[-1].key for path, _ in pools} == {"latent_pages"}
    # 2 layers x 2 sublayers, a row of 32 + 8 padded to one lane tile
    assert [leaf.shape for _, leaf in pools] == [(49, 8, 128)] * 4
    assert engine.window_pool is None and engine.pool.check_invariants()
    assert engine.pool.allocated_pages == 0  # every request retired


def test_the_walk_against_the_gathered_view():
    """``paged_attention`` over one latent pool (interpret mode) against
    the view gathered and attended by position: slots from a fresh one
    to a full one, scores over the whole row, values its first lanes."""
    from cs744_pytorch_distributed_tutorial_tpu.models.latent import attend_by_position
    from cs744_pytorch_distributed_tutorial_tpu.ops.paged_attention import paged_attention
    from cs744_pytorch_distributed_tutorial_tpu.parallel.ring_attention import gather_pages

    slots, page, width, lanes, r = 5, 8, 40, 256, 128
    rng = np.random.default_rng(0)
    q = jax.random.normal(jax.random.key(0), (slots, 1, 8, lanes))
    pool = jax.random.normal(jax.random.key(1), (slots * width + 1, page, lanes))
    table = jnp.asarray((1 + rng.permutation(slots * width)).reshape(slots, width), jnp.int32)
    pos = jnp.asarray([0, 7, 8, 150, page * width - 1], jnp.int32)
    got = paged_attention(q, pool, None, table, pos, value_lanes=r, scale=0.07, interpret=True)
    view = gather_pages(pool, table)
    want = attend_by_position(q, view[:, :, None, :], view[:, :, None, :r], pos[:, None], 0.07, head_block=8)
    assert got.shape == (slots, 1, 8, r)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5
    with pytest.raises(ValueError, match="whole 128-lane tiles"):
        paged_attention(q[..., :40], pool[..., :40], None, table, pos, value_lanes=32, scale=0.1, interpret=True)


def test_preemption_frees_and_restores_the_latent_pages(tiny, served):
    """A pool too small for three long requests preempts the youngest;
    it is prefilled again by chunks and goes on to the same tokens."""
    _, model, params, _ = tiny
    engine, reqs = _serve(model, params, paged_attention_impl="gather", num_pages=19)
    assert engine.stats()["preemptions"] > 0
    assert _answers(reqs) == _answers(served[1])
    assert engine.pool.check_invariants() and engine.pool.allocated_pages == 0


def test_a_snapshot_restored_rebuilds_the_latent_pages(tiny, served):
    _, model, params, _ = tiny
    cfg = ServeConfig(**SERVE, paged_attention_impl="gather")
    first = ServingEngine(model, params, cfg)
    sent = [first.submit(r) for r in _requests()]
    for _ in range(6):
        first.step()
    assert first.pool.allocated_pages > 0
    snap = first.snapshot()
    second = ServingEngine(model, params, cfg)
    resumed = {r.req_id: r for r in second.resume(snap)}
    second.run()
    for r, want in zip(sent, _answers(served[1])):
        assert _answers([resumed.get(r.req_id, r)])[0] == want
    assert second.pool.check_invariants() and second.pool.allocated_pages == 0


def test_deadline_expiry_frees_the_latent_pages(tiny):
    _, model, params, _ = tiny
    now = [0.0]
    engine = ServingEngine(
        model, params, ServeConfig(**SERVE, paged_attention_impl="gather"),
        clock=lambda: now[0], guard=ServeGuard(cfg=GuardConfig(deadline_s=5.0)),
    )
    reqs = [engine.submit(r) for r in _requests()]
    engine.step()
    assert engine.pool.allocated_pages > 0
    now[0] = 10.0  # every request is past its deadline, active or queued
    while engine.busy:
        engine.step()
    assert {r.terminal_status for r in reqs} == {"timed_out"}
    assert engine.pool.check_invariants() and engine.pool.allocated_pages == 0


def test_no_compile_after_warm_up_under_slot_churn(tiny):
    _, model, params, _ = tiny
    engine = ServingEngine(model, params, ServeConfig(**{**SERVE, "num_pages": 17}, paged_attention_impl="gather"))
    rng = np.random.default_rng(3)

    def burst(sizes):
        for n, m in sizes:
            engine.submit(Request(prompt=rng.integers(0, 256, n).astype(np.int32), max_new_tokens=m))
        engine.run()

    burst([(13, 3), (30, 4)])
    compiles = CompileCounter()
    burst([(70, 12), (5, 20), (40, 9), (64, 8), (23, 2), (50, 15)])  # preempts too
    assert compiles.count == 0 and engine.stats()["preemptions"] > 0
    assert len(engine._completed) == 8


def test_the_counters_of_the_latent_pool_and_of_the_share(tiny):
    """Behind the step's tokens, no new transfer: latent rows attended
    (a slot at depth L reads L + 1 rows a sublayer), and the (token,
    expert) pairs by where the expert is, which sum to ``moe_topk`` a
    token a layer; ``experts_hit`` counts the HELD experts only."""
    cfg, model, params, _ = tiny
    engine = ServingEngine(model, params, ServeConfig(**SERVE, paged_attention_impl="gather"))
    prompts = (21, 8, 13)
    rng = np.random.default_rng(5)
    for n in prompts:
        engine.submit(Request(prompt=rng.integers(0, 256, n).astype(np.int32), max_new_tokens=4))
    engine.run()
    stats = engine.stats()
    assert engine._counter_names == (
        "selected_tokens", "scored_tokens", "experts_hit", "expert_ratio_milli",
        "latent_tokens_read", "held_expert_pairs", "zero_expert_pairs", "absent_expert_pairs",
    )
    depths = [n + i for n in prompts for i in range(3)]  # three decode steps a request
    sublayers, layers = 2 * cfg["num_layers"], cfg["num_layers"]
    assert stats["latent_tokens_read"] == sublayers * sum(d + 1 for d in depths)
    pairs = [stats[k] for k in ("held_expert_pairs", "zero_expert_pairs", "absent_expert_pairs")]
    assert sum(pairs) == cfg["moe_topk"] * layers * len(depths) and all(p > 0 for p in pairs)
    # at most the two held experts a layer a step, at least one whenever a pair was held
    assert 0 < stats["experts_hit"] <= min(pairs[0], 2 * layers * stats["decode_steps"])
    assert stats["selected_tokens"] == stats["scored_tokens"] == stats["full_tokens_read"] == 0


# ---- a model without latent layers is left as it was ---------------------------

_TOY_ENGINE = """
import sys
import jax, jax.numpy as jnp, numpy as np
from cs744_pytorch_distributed_tutorial_tpu.models import TransformerLM
from cs744_pytorch_distributed_tutorial_tpu.obs.system import CompileCounter
from cs744_pytorch_distributed_tutorial_tpu.serve import Request, ServeConfig, ServingEngine
jax.config.update("jax_enable_compilation_cache", False)
model = TransformerLM(vocab_size=61, num_layers=2, num_heads=2, d_model=32, d_ff=64,
                      max_seq_len=64, attention_impl="dense", use_rope=True)
params = model.init(jax.random.key(0), jnp.zeros((1, 4), jnp.int32))["params"]
compiles = CompileCounter()
engine = ServingEngine(model, params, ServeConfig(num_slots=3, page_size=4, num_pages=33, max_pages_per_slot=8))
rng = np.random.default_rng(11)
for n, m in ((4, 3), (8, 5), (3, 8), (6, 2)):
    engine.submit(Request(prompt=rng.integers(1, 61, n).astype(np.int32), max_new_tokens=m))
engine.run()
latent = [m for m in sys.modules if m.endswith("models.latent")]
print("COMPILES", compiles.count, len(engine._prefill_cache), engine._decode_step._cache_size(), len(latent))
"""


def test_a_model_without_latent_layers_pays_nothing():
    """The toy GPT-2 engine of tests/test_serve.py, in a process of its
    own: the three backend compiles it always made (one prefill bucket,
    the decode step, the sampling key's fold), and models/latent.py
    never imported."""
    import os
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, "-c", _TOY_ENGINE], capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split("COMPILES")[1].split() == ["3", "1", "1", "0"]


def test_a_model_without_latent_layers_builds_the_pools_it_built():
    """Key and value pools a layer under their names, the decode step's
    arguments as they were, no counter behind its tokens, and the new
    counters of ``stats()`` at 0."""
    model = TransformerLM(
        vocab_size=61, num_layers=2, num_heads=2, d_model=32, d_ff=64,
        max_seq_len=64, attention_impl="dense", use_rope=True,
    )
    params = model.init(jax.random.key(0), jnp.zeros((1, 4), jnp.int32))["params"]
    engine = ServingEngine(model, params, ServeConfig(num_slots=3, page_size=4, num_pages=33, max_pages_per_slot=8))
    pools = jax.tree_util.tree_leaves_with_path(engine._pages)
    assert sorted(path[-1].key for path, _ in pools) == ["key_pages", "key_pages", "value_pages", "value_pages"]
    assert {leaf.shape for _, leaf in pools} == {(33, 4, 32)}
    i32 = jnp.int32
    decode = engine._decode_step.lower(params, engine._pages, jnp.zeros((39,), i32), engine._sample_root)
    n_params = len(jax.tree.leaves(params))
    shapes = [tuple(a.shape) for a in jax.tree.leaves(decode.in_avals)]
    assert shapes[n_params:] == [(33, 4, 32)] * 4 + [(39,), ()]  # the packed argument: 3 x (5 + 8)
    assert [tuple(o.shape) for o in jax.tree.leaves(decode.out_info)][-1] == (3,)  # tokens, nothing behind them
    assert engine._counter_names == ()
    engine.submit(Request(prompt=np.arange(1, 6, dtype=np.int32), max_new_tokens=3))
    engine.run()
    stats = engine.stats()
    assert [stats[k] for k in (
        "latent_tokens_read", "held_expert_pairs", "zero_expert_pairs", "absent_expert_pairs", "experts_hit",
    )] == [0] * 5
