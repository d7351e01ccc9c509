"""The small Keye-like configuration (tests/keye_tiny.py) through
``ServingEngine``: chunked prefill (one compiled chunk program), then
paged decode over the indexer's selection, against the plain
reference's full forward at every served position; preemption and
resume; the counters.

Tolerance. Served tokens are judged as the benchmark judges them
(perfbench/drivers/serve_engine_sparse_moe.py): teacher-forced through
the reference, the gap by which a served token's logit lies below the
reference's best. In float32 on the CPU the engine's logits are the
reference's to ~1e-5 (different summation orders), so a served token is
the reference's argmax unless two logits lie closer than that: the gap
is held under 1e-4, ten times the rounding and a thousandth of the
typical distance between the best two logits (~0.1).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cs744_pytorch_distributed_tutorial_tpu.models import TransformerLM
from cs744_pytorch_distributed_tutorial_tpu.serve import (
    Request,
    ServeConfig,
    ServingEngine,
)
from perfbench.reference import keye as R

from keye_tiny import build, tiny_config


LENGTHS = ((70, 12), (23, 9), (40, 20), (17, 5), (64, 8))
SERVE = dict(num_slots=3, page_size=8, num_pages=40, max_pages_per_slot=12)


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config(topk=16)
    model, params, flat = build(cfg)
    return cfg, model, params, flat


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
    # a preempted request carries its earlier tokens at the prompt's end
    return [
        list(r.prompt[r.orig_prompt_len:]) + list(r.generated) for r in reqs
    ]


def _served_gap(flat, cfg, req):
    seq = np.concatenate([req.prompt, np.asarray(req.generated, np.int32)])
    lo, hi = req.orig_prompt_len - 1, len(seq) - 1
    ref = R.forward(flat, seq, cfg, at=np.arange(lo, hi))
    served = jnp.asarray(seq[lo + 1: hi + 1])
    return float(jnp.max(jnp.max(ref, -1) - jnp.take_along_axis(ref, served[:, None], -1)[:, 0]))


@pytest.fixture(scope="module")
def chunked(tiny):
    cfg, model, params, flat = tiny
    return _serve(model, params, prefill_chunk=16)


def test_chunked_prefill_then_decode_serves_the_reference_s_tokens(tiny, chunked):
    cfg, _, _, flat = tiny
    engine, reqs = chunked
    for r in reqs:
        assert r.status == "completed" and len(r.generated) == r.max_new_tokens
        assert _served_gap(flat, cfg, r) < 1e-4
    # contexts pass top-k 16, so the selection was at work throughout
    stats = engine.stats()
    assert 0 < stats["selected_tokens"] < stats["scored_tokens"]


def test_counters_count_what_was_served(tiny, chunked):
    cfg, model, _, _ = tiny
    engine, reqs = chunked
    stats = engine.stats()
    assert stats["prefill_chunks"] == sum(-(-n // 16) for n, _ in LENGTHS)
    # A decode step at depth L scores L + 1 tokens a layer and keeps
    # min(L + 1, 16); a request's steps run at depths prompt .. prompt +
    # answer - 2.
    layers = cfg["num_hidden_layers"]
    depths = [n + i for n, m in LENGTHS for i in range(m - 1)]
    assert stats["scored_tokens"] == layers * sum(d + 1 for d in depths)
    assert stats["selected_tokens"] == layers * sum(min(d + 1, 16) for d in depths)
    # at most top-2 of 8 experts a token a layer, at least one
    assert layers * stats["decode_steps"] <= stats["experts_hit"]
    assert stats["experts_hit"] <= layers * min(8, 2 * 3) * stats["decode_steps"]
    assert 1.0 <= stats["expert_tokens_max_over_mean"] <= 8.0


def test_one_chunk_program_and_one_decode_step(tiny, chunked):
    engine, _ = chunked
    assert engine._chunk_fn()._cache_size() == 1
    assert engine._decode_step._cache_size() == 1
    assert not engine._prefill_cache  # no bucketed program was built


def test_one_shot_prefill_serves_the_same_tokens(tiny, chunked):
    """The bucketed one-shot prefill (mode="prefill": the cache's rows
    committed to all three pools) and the chunk program agree."""
    _, model, params, _ = tiny
    _, reqs = _serve(model, params)
    assert _answers(reqs) == _answers(chunked[1])


def test_preempted_and_resumed_requests_re_read_the_same_tokens(tiny, chunked):
    """A pool too small for three long requests at once preempts the
    youngest, which is prefilled again by chunks (prompt plus what it
    had produced) and goes on to the same tokens."""
    cfg, model, params, flat = tiny
    engine, reqs = _serve(model, params, prefill_chunk=16, num_pages=21)
    assert engine.stats()["preemptions"] > 0
    assert _answers(reqs) == _answers(chunked[1])
    # snapshot and resume on a fresh engine, mid-flight
    first = ServingEngine(model, params, ServeConfig(**SERVE, prefill_chunk=16))
    sent = [first.submit(r) for r in _requests()]
    for _ in range(6):
        first.step()
    snap = first.snapshot()
    second = ServingEngine(model, params, ServeConfig(**SERVE, prefill_chunk=16))
    resumed = {r.req_id: r for r in second.resume(snap)}
    second.run()
    for r, want in zip(sent, _answers(chunked[1])):
        got = resumed.get(r.req_id, r)
        assert _answers([got])[0] == want


def test_chunked_prefill_of_a_dense_model_matches_its_one_shot_prefill():
    """The chunk program is the model's, not the indexer's: a dense
    GPT-2-like model served by chunks gives the tokens its bucketed
    prefill gives."""
    model = TransformerLM(
        vocab_size=256, num_layers=2, num_heads=4, d_model=64, d_ff=128,
        max_seq_len=128, attention_impl="dense",
    )
    params = model.init(jax.random.key(1), jnp.zeros((1, 8), jnp.int32))["params"]
    _, one_shot = _serve(model, params)
    engine, by_chunks = _serve(model, params, prefill_chunk=16)
    assert _answers(by_chunks) == _answers(one_shot)
    assert engine.stats()["selected_tokens"] == 0  # a dense model sows nothing
