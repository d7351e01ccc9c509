"""The small Mellum-like configuration (tests/mellum_tiny.py) through
``ServingEngine``: prefill by chunks then paged decode through BOTH page
groups (the full layers' pages that grow with the context, the window
layers' pages that are given back as the window passes them), against
the plain reference's full forward at every served position; the page
accounting of the second group through retire, preemption, deadline
expiry and a snapshot restored; and a model without window layers left
exactly as it was: one pool, one table, the programs' arguments.

Tolerance: as tests/test_keye_serving.py judges, the gap by which a
served token's logit lies below the reference's best, held under 1e-4
in float32 on the CPU.
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
from perfbench.reference import mellum2 as R

from mellum_tiny import WINDOW, build, tiny_config

# contexts past three windows (window 8); chunk 12 is no divisor of the
# window nor a multiple of it, pages of 4
LENGTHS = ((70, 12), (23, 9), (40, 20), (9, 5), (64, 8))
SERVE = dict(num_slots=3, page_size=4, num_pages=80, max_pages_per_slot=24, prefill_chunk=12)
P_W = -(-(WINDOW + 12 - 1) // 4) + 1  # 6 pages: what a window and a chunk touch


def _requests(seed=0):
    rng = np.random.default_rng(seed)
    return [
        Request(prompt=rng.integers(0, 256, n).astype(np.int32), max_new_tokens=m)
        for n, m in LENGTHS
    ]


def _serve(model, params, watch=None, **cfg):
    engine = ServingEngine(model, params, ServeConfig(**{**SERVE, **cfg}))
    reqs = [engine.submit(r) for r in _requests()]
    while engine.busy:
        engine.step()
        if watch is not None:
            watch(engine)
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
    held = []
    engine, reqs = _serve(
        model, params, paged_attention_impl="gather",
        watch=lambda e: held.append(max((len(s.window_pages) for s in e._slots if s), default=0)),
    )
    return engine, reqs, held


def test_chunks_then_decode_serve_the_reference_s_tokens(tiny, served):
    cfg, _, _, flat = tiny
    engine, reqs, _ = served
    for r in reqs:
        assert r.status == "completed" and len(r.generated) == r.max_new_tokens
        assert _served_gap(flat, cfg, r) < 1e-4
    assert engine._chunk_fn()._cache_size() == 1 and engine._decode_step._cache_size() == 1


def test_the_kernel_serves_the_same_tokens_through_the_window_walk():
    """The Pallas walk (interpret mode; lane-wide rows: 2 KV heads of
    64) with ``first_pos`` and ``window``, against the reference and
    against the gather implementation."""
    cfg = tiny_config(head_dim=64, periods=1)
    model, params, flat = build(cfg, flash_interpret=True)
    _, by_kernel = _serve(model, params, paged_attention_impl="kernel")
    _, by_gather = _serve(model, params, paged_attention_impl="gather")
    assert _answers(by_kernel) == _answers(by_gather)
    for r in by_kernel:
        assert _served_gap(flat, cfg, r) < 1e-4


def test_a_slot_never_holds_more_window_pages_than_the_table_is_wide(served):
    engine, _, held = served
    assert engine.window_table_width == P_W
    assert engine.window_pool.num_pages == SERVE["num_slots"] * P_W + 1
    # decode needs ceil(window / page) + 1 = 3 pages at most; a chunk up to P_W
    assert max(held) <= 3 and engine.window_pool.high_water <= SERVE["num_slots"] * P_W
    assert engine._window_table.shape == (SERVE["num_slots"], P_W)


def test_window_pages_come_back_during_prefill_and_during_decode(tiny):
    _, model, params, _ = tiny
    engine = ServingEngine(model, params, ServeConfig(**SERVE, paged_attention_impl="gather"))
    engine.submit(Request(prompt=np.arange(70, dtype=np.int32) % 250, max_new_tokens=26))
    engine.step()  # the admission (6 chunks) and the first decode step
    after_prefill = engine.stats()
    # 70 tokens are 18 pages; what is left is what position 70 sees
    slot = engine._slots[0]
    assert after_prefill["window_pages_freed"] >= 18 - P_W
    assert after_prefill["pages_live_full"] == 18 and after_prefill["pages_live_window"] == len(slot.window_pages) <= 3
    assert slot.window_first * 4 <= 70 - WINDOW + 1 < (slot.window_first + 1) * 4
    while engine.busy:
        engine.step()
    stats = engine.stats()
    # 25 decode steps (positions 70..94) walk the window over 6 more pages
    assert stats["window_pages_freed"] - after_prefill["window_pages_freed"] >= 6
    assert stats["pages_live_full"] == stats["pages_live_window"] == 0
    # every page of the window group was given back by the time the slot retired
    assert engine.window_pool.check_invariants() and engine.pool.check_invariants()
    assert engine.window_pool.total_allocs == engine.window_pool.total_frees == 24
    # keys attended: a full layer all of them, a window layer the window
    depths = [70 + i for i in range(25)]
    assert stats["full_tokens_read"] == 2 * sum(d + 1 for d in depths)
    assert stats["window_tokens_read"] == 6 * WINDOW * len(depths)


def test_preemption_frees_and_restores_both_groups(tiny, served):
    """A full group too small for three long requests preempts the
    youngest; it is prefilled again by chunks through both groups and
    goes on to the same tokens."""
    _, model, params, _ = tiny
    engine, reqs = _serve(model, params, paged_attention_impl="gather", num_pages=37)
    assert engine.stats()["preemptions"] > 0
    assert _answers(reqs) == _answers(served[1])
    for pool in (engine.pool, engine.window_pool):
        assert pool.check_invariants() and pool.allocated_pages == 0


def test_a_snapshot_restored_rebuilds_both_groups(tiny, served):
    _, model, params, _ = tiny
    cfg = ServeConfig(**SERVE, paged_attention_impl="gather")
    first = ServingEngine(model, params, cfg)
    sent = [first.submit(r) for r in _requests()]
    for _ in range(6):
        first.step()
    assert first.stats()["pages_live_window"] > 0
    snap = first.snapshot()
    second = ServingEngine(model, params, cfg)
    resumed = {r.req_id: r for r in second.resume(snap)}
    second.run()
    for r, want in zip(sent, _answers(served[1])):
        assert _answers([resumed.get(r.req_id, r)])[0] == want
    for pool in (second.pool, second.window_pool):
        assert pool.check_invariants() and pool.allocated_pages == 0


def test_deadline_expiry_frees_both_groups(tiny):
    _, model, params, _ = tiny
    now = [0.0]
    engine = ServingEngine(
        model, params, ServeConfig(**SERVE, paged_attention_impl="gather"),
        clock=lambda: now[0], guard=ServeGuard(cfg=GuardConfig(deadline_s=5.0)),
    )
    reqs = [engine.submit(r) for r in _requests()]
    engine.step()
    assert engine.stats()["pages_live_window"] > 0
    now[0] = 10.0  # every request is past its deadline, active or queued
    while engine.busy:
        engine.step()
    assert {r.terminal_status for r in reqs} == {"timed_out"}
    for pool in (engine.pool, engine.window_pool):
        assert pool.check_invariants() and pool.allocated_pages == 0


def test_no_compile_after_warm_up_under_slot_churn(tiny):
    _, model, params, _ = tiny
    engine = ServingEngine(model, params, ServeConfig(**{**SERVE, "num_pages": 37}, paged_attention_impl="gather"))
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


# ---- a model without window layers is left as it was --------------------------

def _arg_shapes(lowered):
    return [tuple(a.shape) for a in jax.tree.leaves(lowered.in_avals)]


def test_a_model_without_window_layers_builds_one_pool_and_one_table():
    """The toy GPT-2 engine of tests/test_serve.py: no second pool, a
    zero-width window table that no program takes, the decode step and
    the prefill programs with the arguments they always took (count and
    shapes), and as many compiled programs as before."""
    model = TransformerLM(
        vocab_size=61, num_layers=2, num_heads=2, d_model=32, d_ff=64,
        max_seq_len=64, attention_impl="dense", use_rope=True,
    )
    params = model.init(jax.random.key(0), jnp.zeros((1, 4), jnp.int32))["params"]
    cfg = ServeConfig(num_slots=3, page_size=4, num_pages=33, max_pages_per_slot=8)
    engine = ServingEngine(model, params, cfg)
    assert engine.window_pool is None and engine.window_table_width == 0
    assert engine._window_table.shape == (3, 0)
    assert sorted(jax.tree.leaves(jax.tree.map(lambda x: x.shape, engine._pages), is_leaf=lambda x: isinstance(x, tuple))) == [(33, 4, 32)] * 4
    n_params = len(jax.tree.leaves(params))
    i32 = jnp.int32
    # the decode step: one packed vector (five rows of 3 slots + the 3 x 8 table, and no window
    # rows) and the stream's root
    decode = engine._decode_step.lower(params, engine._pages, jnp.zeros((39,), i32), engine._sample_root)
    assert _arg_shapes(decode)[n_params:] == [(33, 4, 32)] * 4 + [(39,), ()]
    assert engine._decode_arg_len() == 39 and engine._window_first.shape == (0,)
    # the prefill programs: one packed vector (bucket 8 + true_len + 8 pages + the stream's two
    # integers; chunk 8 + four scalars + 8 pages, and no window row) and the stream's root
    prefill = engine._prefill_fn(8).lower(params, engine._pages, jnp.zeros((19,), i32), engine._sample_root)
    assert _arg_shapes(prefill)[n_params:] == [(33, 4, 32)] * 4 + [(19,), ()]
    chunked = ServingEngine(model, params, ServeConfig(**{**cfg.__dict__, "prefill_chunk": 8}))
    chunk = chunked._chunk_fn().lower(params, chunked._pages, jnp.zeros((20,), i32), chunked._sample_root)
    assert _arg_shapes(chunk)[n_params:] == [(33, 4, 32)] * 4 + [(20,), ()]
    assert engine._program_arg_len(8, 3) == 19 and chunked._program_arg_len(8, 4) == 20
    stats = engine.stats()
    assert stats["pages_live_window"] == stats["window_pages_freed"] == stats["window_tokens_read"] == 0


_TOY_ENGINE = """
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
print("COMPILES", compiles.count, len(engine._prefill_cache), engine._decode_step._cache_size())
"""


def test_the_toy_engine_compiles_the_programs_it_always_compiled():
    """From its construction through four requests of 3..8 tokens, in a
    process of its own (what was compiled before would not be counted):
    one prefill bucket, the decode step and the sampling key's fold,
    three backend compiles, as at the parent of PR 32."""
    import os
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, "-c", _TOY_ENGINE], capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split("COMPILES")[1].split() == ["3", "1", "1"]


@pytest.mark.parametrize("behind", [0, 3])
def test_the_window_walk_against_the_masked_view(behind):
    """``paged_attention(first_pos=, window=)`` in interpret mode against
    the gathered view under a position mask: slots from a fresh one to
    one many windows deep, the table starting ``behind`` pages before
    the page of the oldest key the query sees (as after a chunk)."""
    from cs744_pytorch_distributed_tutorial_tpu.ops.paged_attention import paged_attention
    from cs744_pytorch_distributed_tutorial_tpu.ops.sparse_attention import masked_attention
    from cs744_pytorch_distributed_tutorial_tpu.parallel.ring_attention import gather_pages, unfold_heads

    slots, page, window, width = 6, 4, 24, 12
    rng = np.random.default_rng(behind)
    q = jax.random.normal(jax.random.key(0), (slots, 1, 4, 64))
    k = jax.random.normal(jax.random.key(1), (slots * width + 1, page, 128))
    v = jax.random.normal(jax.random.key(2), (slots * width + 1, page, 128))
    table = jnp.asarray((1 + rng.permutation(slots * width)).reshape(slots, width), jnp.int32)
    pos = np.array([0, 3, 23, 24, 100, 1000])
    first = np.maximum(np.maximum(pos - window + 1, 0) // page - behind, 0) * page
    pos, first = jnp.asarray(pos, jnp.int32), jnp.asarray(first, jnp.int32)
    got = paged_attention(q, k, v, table, pos, first_pos=first, window=window, interpret=True)
    rel = (pos - first)[:, None]
    want = masked_attention(
        q, unfold_heads(gather_pages(k, table), 64), unfold_heads(gather_pages(v, table), 64), rel,
        jnp.arange(width * page)[None, None, :] > rel[:, :, None] - window,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)
