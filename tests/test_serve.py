"""Continuous-batching serving engine (serve/): the PR's contracts.

Four pins, in dependency order:

1. **Paged == dense, bitwise.** ``mode="paged_decode"`` gathers the
   slot's pages into the dense cache layout and runs the SAME
   ``decode_attention`` einsum, so per-step logits must match the dense
   cache path to the bit (float32 and int8-KV) — not approximately:
   a tolerance here would hide an off-by-one page index.
2. **Engine == make_generator, token for token** (greedy). The whole
   request lifecycle — bucketed prefill+commit, slot decode, retire —
   must reproduce batch-at-a-time generation per request.
3. **Zero retraces across slot churn.** Retire/refill/preempt change
   batch membership every which way; the fixed-shape decode step must
   never recompile post-warmup (graftlint GL002 made executable).
4. **Preemption is safe.** A pool too small for the offered load forces
   LIFO recompute preemption; every request must still complete with
   its full budget (admission guarantees the oldest always fits alone).

Pins 2 and 3 run under BOTH decode-attention implementations: the
gather+einsum reference and the Pallas paged-attention kernel
(``paged_attention_impl="kernel"``, interpret mode on CPU — kernel-level
parity lives in tests/test_paged_attention.py). Newer contracts ride the
same harness: per-request PRNG streams make preemption-recompute
output-invariant for SAMPLED requests too, tokens stream out as they
decode (``on_token`` / ``iter_tokens``, ITL measured by the loadgen),
and ``scan_layers`` models serve token-identically to unrolled ones.

Plus the host-side units (PagePool), the load generator's determinism
and telemetry, and the regress.py budget gate the CI serve-smoke job
relies on.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cs744_pytorch_distributed_tutorial_tpu.infer import make_generator
from cs744_pytorch_distributed_tutorial_tpu.models import TransformerLM
from cs744_pytorch_distributed_tutorial_tpu.serve import (
    PagePool,
    Request,
    ServeConfig,
    ServingEngine,
    make_poisson_workload,
    run_poisson,
)

VOCAB = 61


class _ListSink:
    def __init__(self):
        self.records = []

    def emit(self, record):
        self.records.append(dict(record))


@pytest.fixture(scope="module")
def tiny_lm():
    model = TransformerLM(
        vocab_size=VOCAB,
        num_layers=2,
        num_heads=2,
        d_model=32,
        d_ff=64,
        max_seq_len=64,
        attention_impl="dense",
        use_rope=True,
    )
    params = model.init(
        jax.random.key(0), jnp.zeros((1, 4), jnp.int32)
    )["params"]
    return model, params


# ---------------------------------------------------------------- pool


def test_page_pool_reserves_trash_page():
    pool = PagePool(num_pages=8, page_size=4)
    assert pool.free_pages == 7  # page 0 reserved
    got = pool.alloc(7)
    assert 0 not in got
    assert sorted(got) == list(range(1, 8))
    with pytest.raises(RuntimeError, match="exhausted"):
        pool.alloc(1)


def test_page_pool_lifo_reuse_and_high_water():
    pool = PagePool(num_pages=8, page_size=4)
    a = pool.alloc(3)
    assert a == [1, 2, 3]
    pool.free([2])
    # the just-freed page comes back first (LIFO)
    assert pool.alloc(1) == [2]
    assert pool.high_water == 3
    pool.free([1, 2, 3])
    assert pool.allocated_pages == 0
    assert pool.high_water == 3  # high water does not recede


def test_page_pool_rejects_bad_frees():
    pool = PagePool(num_pages=8, page_size=4)
    pages = pool.alloc(2)
    pool.free(pages)
    with pytest.raises(ValueError, match="double free"):
        pool.free([pages[0]])
    with pytest.raises(ValueError, match="trash page"):
        pool.free([0])
    with pytest.raises(ValueError, match="out of range"):
        pool.free([99])
    with pytest.raises(ValueError, match="num_pages must be >= 2"):
        PagePool(num_pages=1, page_size=4)


def test_page_pool_pages_for_is_ceil():
    pool = PagePool(num_pages=8, page_size=4)
    assert [pool.pages_for(n) for n in (1, 4, 5, 8, 9)] == [1, 1, 2, 2, 3]


# ------------------------------------------------- paged/dense parity


def _commit_cache_to_pages(pages, cache, page_tables, true_len):
    """Reference host-side commit: scatter each batch row's first
    ``true_len`` dense-cache rows into that row's pages (the same
    mapping the engine's fused prefill does on device)."""

    def walk(p, c):
        if "key_pages" in p:
            out = {}
            for cname, pname in (
                ("cached_key", "key_pages"),
                ("cached_value", "value_pages"),
                ("key_scale", "key_scale_pages"),
                ("value_scale", "value_scale_pages"),
            ):
                if pname not in p:
                    continue
                pool = np.asarray(p[pname]).copy()
                # a scanned stack leads both with [L]; the batch first
                lead = pool.shape[:-3]
                rows = np.moveaxis(np.asarray(c[cname]), len(lead), 0)
                page_size = pool.shape[-2]
                for b in range(rows.shape[0]):
                    for i in range(true_len):
                        # pools fold the heads: [Hkv, D] -> [Hkv*D]
                        pool[
                            ..., page_tables[b, i // page_size],
                            i % page_size, :,
                        ] = np.take(rows[b], i, axis=len(lead)).reshape(
                            lead + pool.shape[-1:]
                        )
                out[pname] = jnp.asarray(pool)
            return out
        return {k: walk(p[k], c[k]) for k in p}

    return walk(pages, cache)


@pytest.mark.parametrize("quant_kv", [False, True])
def test_paged_decode_logits_bitwise_match_dense(tiny_lm, quant_kv):
    """Per-step decode logits from the page pools must equal the dense
    cache path's EXACTLY (same einsum over a gathered view — any
    difference is a paging bug, so no tolerance)."""
    model, params = tiny_lm
    page_size, num_pages, ppr = 4, 16, 4  # ppr = pages per row
    dense = model.clone(quant_kv_cache=quant_kv)
    paged = dense.clone(page_size=page_size, num_pages=num_pages)
    B, t0, steps = 2, 6, 5
    tokens = jax.random.randint(jax.random.key(1), (B, t0 + steps), 0, VOCAB)

    # dense prefill gives both the reference cache and the rows to page
    _, variables = dense.apply(
        {"params": params}, tokens[:, :t0], mode="prefill", mutable=["cache"]
    )
    cache = variables["cache"]

    page_tables = np.asarray(
        [[1 + r * ppr + i for i in range(ppr)] for r in range(B)], np.int32
    )
    pages = paged.init(
        jax.random.key(0),
        jnp.zeros((B, 1), jnp.int32),
        mode="paged_decode",
        decode_pos=jnp.zeros((B,), jnp.int32),
        page_table=jnp.asarray(page_tables),
    )["pages"]
    pages = _commit_cache_to_pages(pages, cache, page_tables, t0)

    for pos in range(t0, t0 + steps):
        step = tokens[:, pos : pos + 1]
        dense_logits, mutated = dense.apply(
            {"params": params, "cache": cache},
            step,
            mode="decode",
            decode_pos=jnp.asarray(pos, jnp.int32),
            mutable=["cache"],
        )
        cache = mutated["cache"]
        paged_logits, mutated = paged.apply(
            {"params": params, "pages": pages},
            step,
            mode="paged_decode",
            decode_pos=jnp.full((B,), pos, jnp.int32),
            page_table=jnp.asarray(page_tables),
            mutable=["pages"],
        )
        pages = mutated["pages"]
        np.testing.assert_array_equal(
            np.asarray(paged_logits), np.asarray(dense_logits)
        )


@pytest.mark.parametrize(
    "page_size,plen,bucket",
    [(4, 6, 8), (4, 8, 8), (4, 8, 16), (16, 5, 8)],
    ids=["mid-page", "plen-is-bucket", "page-boundary", "bucket-under-page"],
)
@pytest.mark.parametrize("scan", [False, True], ids=["unrolled", "scan"])
@pytest.mark.parametrize("quant_kv", [False, True], ids=["float", "int8"])
def test_engine_commit_then_decode_bitwise_matches_dense(
    tiny_lm, quant_kv, scan, page_size, plen, bucket
):
    """The engine's OWN device-side commit (its prefill program's
    scatter of the dense cache into folded pools a whole page an index,
    one flattened scatter for every layer under ``scan_layers``) and
    then a decode step over those pools equal the dense cache path to
    the bit, on the gather path: first token, committed pages, next-step
    logits. The slot's pages hold what a row-wise commit leaves on a
    fresh pool (rows past the prompt at the pool's fresh value), and no
    other page but the trash page is written, not even a page of the
    slot's row past the prompt's."""
    from cs744_pytorch_distributed_tutorial_tpu.models import (
        stack_block_params,
    )

    model, params = tiny_lm
    dense = model.clone(quant_kv_cache=quant_kv, scan_layers=scan)
    if scan:
        params = stack_block_params(params)
    num_pages = 17
    eng = ServingEngine(
        dense, params,
        ServeConfig(num_slots=2, page_size=page_size, num_pages=num_pages,
                    max_pages_per_slot=8, paged_attention_impl="gather"),
    )
    prompt = np.zeros((1, bucket), np.int32)
    prompt[0, :plen] = np.random.default_rng(5).integers(1, VOCAB, plen)
    need = -(-plen // page_size)
    row = np.zeros((8,), np.int32)
    row[:4] = [9, 3, 12, 5]  # out of pool order; the slot owns row[:need]
    fresh = jax.tree.map(np.asarray, eng._pages)
    sentinel = 7
    pages, tok = eng._prefill_fn(bucket)(
        params, jax.tree.map(lambda x: jnp.full_like(x, sentinel), eng._pages),
        eng._pack_program_arg(bucket, prompt[0, :plen], (plen, 0, 0), row),
        eng._sample_root,
    )

    # jitted like the engine's program: op-by-op execution rounds the
    # int8 scales differently in the last bit
    logits, variables = jax.jit(
        lambda p, x: dense.apply(
            {"params": p}, x, mode="prefill", mutable=["cache"]
        )
    )(params, jnp.asarray(prompt))
    cache = variables["cache"]
    assert int(tok) == int(jnp.argmax(logits[0, plen - 1]))
    want = _commit_cache_to_pages(fresh, cache, row[None], plen)
    owned = list(row[:need])
    others = sorted(set(range(1, num_pages)) - set(owned))
    leaves, want_leaves = jax.tree.leaves(pages), jax.tree.leaves(want)
    # K and V (and their scales) a layer, or a scanned stack's
    assert len(leaves) == len(want_leaves) == (
        (4 if quant_kv else 2) * (1 if scan else 2)
    )
    for got_leaf, want_leaf in zip(leaves, want_leaves):
        got_leaf, want_leaf = np.asarray(got_leaf), np.asarray(want_leaf)
        if scan:  # [L, num_pages, ...] -> [num_pages, L, ...]
            got_leaf = np.moveaxis(got_leaf, 0, 1)
            want_leaf = np.moveaxis(want_leaf, 0, 1)
        np.testing.assert_array_equal(got_leaf[owned], want_leaf[owned])
        assert (got_leaf[others] == sentinel).all()

    step = jnp.asarray([[int(tok)]], jnp.int32)
    dense_logits, _ = dense.apply(
        {"params": params, "cache": cache}, step, mode="decode",
        decode_pos=jnp.asarray(plen, jnp.int32), mutable=["cache"],
    )
    paged_logits, _ = eng.model.apply(
        {"params": params, "pages": pages}, step, mode="paged_decode",
        decode_pos=jnp.asarray([plen], jnp.int32),
        page_table=jnp.asarray(row[None]), mutable=["pages"],
    )
    np.testing.assert_array_equal(
        np.asarray(paged_logits), np.asarray(dense_logits)
    )


# --------------------------------------------------- engine lifecycle


def _reference_tokens(model, params, prompt, budget):
    gen = make_generator(model, max_new_tokens=budget, temperature=0.0)
    return np.asarray(
        gen(params, np.asarray(prompt, np.int32)[None], jax.random.key(0))
    )[0].tolist()


@pytest.mark.parametrize("impl", ["gather", "kernel"])
def test_engine_greedy_matches_make_generator(tiny_lm, impl):
    """Request-level output == batch generator output, token for token,
    across different prompt lengths, budgets, and admission order —
    under both decode-attention implementations."""
    model, params = tiny_lm
    cfg = ServeConfig(num_slots=2, page_size=4, num_pages=33,
                      max_pages_per_slot=8, paged_attention_impl=impl)
    eng = ServingEngine(model, params, cfg)
    rng = np.random.default_rng(7)
    cases = [(3, 9), (7, 4), (12, 11), (5, 17), (9, 6)]
    reqs = [
        eng.submit(Request(
            prompt=rng.integers(1, VOCAB, size=plen).astype(np.int32),
            max_new_tokens=budget,
        ))
        for plen, budget in cases
    ]
    eng.run()
    assert all(r.done_time is not None for r in reqs)
    for r in reqs:
        expect = _reference_tokens(
            model, params, r.prompt, r.max_new_tokens
        )
        assert r.generated == expect, (r.req_id, r.generated, expect)


@pytest.mark.parametrize("impl", ["gather", "kernel"])
def test_engine_zero_retraces_across_slot_churn(tiny_lm, impl):
    """The fixed-shape decode step never recompiles once warm, no
    matter how membership churns (the GL002 contract, measured) — the
    Pallas kernel keeps the invariant because live length enters via
    the grid mask, never the shape."""
    from cs744_pytorch_distributed_tutorial_tpu.obs.system import (
        CompileCounter,
    )

    model, params = tiny_lm
    cfg = ServeConfig(num_slots=3, page_size=4, num_pages=33,
                      max_pages_per_slot=8, paged_attention_impl=impl)
    eng = ServingEngine(model, params, cfg)
    rng = np.random.default_rng(11)

    def burst(sizes):
        for plen, budget in sizes:
            eng.submit(Request(
                prompt=rng.integers(1, VOCAB, size=plen).astype(np.int32),
                max_new_tokens=budget,
            ))
        eng.run()

    burst([(4, 3), (8, 5)])  # warmup: compiles prefill buckets + decode
    cc = CompileCounter()
    # same buckets, wildly different membership patterns
    burst([(3, 8), (6, 2), (8, 7), (5, 3), (7, 12), (4, 2)])
    assert cc.count == 0, f"{cc.count} retraces during slot churn"
    assert len(eng._completed) == 8


def test_engine_preemption_completes_everything(tiny_lm):
    """A pool too small for the load forces LIFO recompute preemption;
    every request still finishes with its FULL budget and greedy output
    still matches the reference (recompute must be lossless)."""
    model, params = tiny_lm
    # 8 allocatable pages, slots want up to 7 each -> guaranteed fights
    cfg = ServeConfig(num_slots=3, page_size=4, num_pages=9,
                      max_pages_per_slot=7)
    eng = ServingEngine(model, params, cfg)
    rng = np.random.default_rng(13)
    cases = [(6, 18), (10, 14), (8, 16), (5, 20), (12, 12)]
    reqs = [
        eng.submit(Request(
            prompt=rng.integers(1, VOCAB, size=plen).astype(np.int32),
            max_new_tokens=budget,
        ))
        for plen, budget in cases
    ]
    eng.run()
    assert eng.stats()["preemptions"] > 0, "pool was not tight enough"
    for (plen, budget), r in zip(cases, reqs):
        assert r.output_tokens == budget, (r.req_id, r.output_tokens)
    # greedy determinism survives preemption: outputs equal the
    # no-preemption reference (recompute re-derives the same KV, so the
    # stream picks up exactly where it left off)
    for (plen, budget), r in zip(cases, reqs):
        # a preempted request's prompt absorbed its early generations;
        # the produced stream is that absorbed tail + the final tail
        produced = list(r.prompt[r.orig_prompt_len :]) + r.generated
        expect = _reference_tokens(
            model, params, r.prompt[: r.orig_prompt_len], budget
        )
        assert produced == expect, (r.req_id, produced, expect)


def test_engine_pages_recycle(tiny_lm):
    """After a drain every page is back in the pool, and high_water
    stayed within the allocatable budget."""
    model, params = tiny_lm
    cfg = ServeConfig(num_slots=2, page_size=4, num_pages=17,
                      max_pages_per_slot=8)
    eng = ServingEngine(model, params, cfg)
    rng = np.random.default_rng(17)
    for plen, budget in [(4, 6), (9, 8), (6, 10), (11, 5)]:
        eng.submit(Request(
            prompt=rng.integers(1, VOCAB, size=plen).astype(np.int32),
            max_new_tokens=budget,
        ))
    eng.run()
    assert eng.pool.allocated_pages == 0
    assert eng.pool.free_pages == cfg.num_pages - 1
    assert 0 < eng.pool.high_water <= cfg.num_pages - 1


def test_engine_eos_stops_early(tiny_lm):
    """An eos_id sampled mid-stream retires the slot before the budget
    is spent (and the emitted record reflects the short output)."""
    model, params = tiny_lm
    budget = 12
    prompt = np.asarray([1, 2, 3, 4], np.int32)
    ref = _reference_tokens(model, params, prompt, budget)
    # Stop mid-stream: at the first position past 0 whose token has not
    # occurred before it (an eos that already occurred would, rightly,
    # stop the engine at that earlier position).
    stop = next(i for i in range(1, budget - 1) if ref[i] not in ref[:i])
    eos = ref[stop]
    sink = _ListSink()
    cfg = ServeConfig(num_slots=2, page_size=4, num_pages=17,
                      max_pages_per_slot=8, eos_id=eos)
    eng = ServingEngine(model, params, cfg, sink=sink)
    req = eng.submit(Request(prompt=prompt, max_new_tokens=budget))
    eng.run()
    assert req.generated == ref[: stop + 1]
    recs = [r for r in sink.records if r.get("kind") == "serve"]
    assert len(recs) == 1 and recs[0]["output_tokens"] == stop + 1


def test_engine_submit_validation(tiny_lm):
    model, params = tiny_lm
    cfg = ServeConfig(num_slots=2, page_size=4, num_pages=17,
                      max_pages_per_slot=4)
    eng = ServingEngine(model, params, cfg)
    with pytest.raises(ValueError, match="empty prompt"):
        eng.submit(Request(prompt=np.zeros((0,), np.int32), max_new_tokens=4))
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit(Request(prompt=np.ones((4,), np.int32), max_new_tokens=0))
    with pytest.raises(ValueError, match="exceeds max_seq_len"):
        eng.submit(Request(prompt=np.ones((60,), np.int32), max_new_tokens=8))
    # fits max_seq_len but not a slot's page-table row
    with pytest.raises(ValueError, match="caps a slot at 4 pages"):
        eng.submit(Request(prompt=np.ones((20,), np.int32), max_new_tokens=8))


def test_engine_sampled_preemption_replays_prng(tiny_lm):
    """A preempted SAMPLED request reproduces its original tokens on
    recompute: token t of request r always samples from the same
    fold_in(fold_in(root, r), t) key — slot, step count, and batch
    membership never enter the stream — so a pool-starved run with
    preemptions emits exactly what an ample-pool run emits."""
    model, params = tiny_lm
    sample = dict(temperature=0.9, top_k=20, seed=3)
    cases = [(6, 18), (10, 14), (8, 16), (5, 20), (12, 12)]

    def run(cfg):
        eng = ServingEngine(model, params, cfg)
        rng = np.random.default_rng(13)
        reqs = [
            eng.submit(Request(
                prompt=rng.integers(1, VOCAB, size=plen).astype(np.int32),
                max_new_tokens=budget,
            ))
            for plen, budget in cases
        ]
        eng.run()
        # preemption absorbs early generations into the prompt; compare
        # the full produced streams
        return eng, [
            list(r.prompt[r.orig_prompt_len:]) + r.generated for r in reqs
        ]

    tight, tight_out = run(ServeConfig(
        num_slots=3, page_size=4, num_pages=9, max_pages_per_slot=7,
        **sample,
    ))
    ample, ample_out = run(ServeConfig(
        num_slots=3, page_size=4, num_pages=33, max_pages_per_slot=8,
        **sample,
    ))
    assert tight.stats()["preemptions"] > 0, "pool was not tight enough"
    assert ample.stats()["preemptions"] == 0
    assert tight_out == ample_out


_ADMIT_CASES = [(6, 18), (10, 14), (8, 16), (5, 20), (12, 12)]


def _serve_admit_cases(model, params, cfg):
    eng = ServingEngine(model, params, cfg)
    rng = np.random.default_rng(13)
    reqs = [
        eng.submit(Request(
            prompt=rng.integers(1, VOCAB, size=plen).astype(np.int32),
            max_new_tokens=budget,
        ))
        for plen, budget in _ADMIT_CASES
    ]
    eng.run()
    # preemption absorbs early generations into the prompt
    return eng.stats(), [
        list(r.prompt[r.orig_prompt_len:]) + r.generated for r in reqs
    ]


@pytest.mark.parametrize("chunk", [None, 4], ids=["bucketed", "chunked"])
@pytest.mark.parametrize(
    "sample", [dict(), dict(temperature=0.8, top_k=20, seed=3)],
    ids=["greedy", "sampled"],
)
def test_admissions_enqueued_together_serve_the_same_tokens(
    tiny_lm, sample, chunk
):
    """A step's admissions are dispatched back to back and their first
    tokens fetched once. Token for token that serves what one admission
    a step serves (``num_slots=1``: never two prefills in flight), with
    a pool so tight that preempted requests' recomputes are among the
    admissions; the programs fold each token's key themselves, so the
    sampled streams agree too. ``admit_fetches`` counts the steps that
    admitted, and a chunked admission runs the chunks it always ran."""
    model, params = tiny_lm
    many, many_out = _serve_admit_cases(model, params, ServeConfig(
        num_slots=3, page_size=4, num_pages=9, max_pages_per_slot=7,
        prefill_chunk=chunk, **sample,
    ))
    one, one_out = _serve_admit_cases(model, params, ServeConfig(
        num_slots=1, page_size=4, num_pages=33, max_pages_per_slot=8,
        prefill_chunk=chunk, **sample,
    ))
    assert many["preemptions"] > 0, "pool was not tight enough"
    assert many["max_admits_in_step"] > 1 and one["max_admits_in_step"] == 1
    assert many_out == one_out
    assert [len(o) for o in one_out] == [b for _, b in _ADMIT_CASES]
    for stats in (many, one):
        assert stats["admit_fetches"] == stats["admit_steps"] > 0
    assert many["admissions"] > many["admit_fetches"]
    assert one["admissions"] == one["admit_fetches"] == len(_ADMIT_CASES)
    assert one["prefill_chunks"] == (
        sum(-(-plen // chunk) for plen, _ in _ADMIT_CASES) if chunk else 0
    )


def test_a_chunked_admission_fetches_once(tiny_lm, monkeypatch):
    """Only the last chunk's token is ever read, and it joins the step's
    one fetch: the chunk program's tokens are wrapped to count what the
    host converts, and ``jax.device_get`` to count the blocking calls."""
    model, params = tiny_lm
    eng = ServingEngine(model, params, ServeConfig(
        num_slots=2, page_size=4, num_pages=33, max_pages_per_slot=8,
        prefill_chunk=4,
    ))
    read = []

    class CountedToken:
        def __init__(self, tok, chunk):
            self.tok, self.chunk = tok, chunk

        def __array__(self, *args, **kwargs):
            read.append(self.chunk)
            return np.asarray(self.tok)

        __int__ = __index__ = lambda self: int(np.asarray(self))

    program, dispatched = eng._chunk_fn(), []

    def counting_program(*args):
        pages, tok = program(*args)
        dispatched.append(len(dispatched))
        return pages, CountedToken(tok, dispatched[-1])

    eng._chunk_program = counting_program
    fetches = []
    device_get = jax.device_get
    monkeypatch.setattr(
        jax, "device_get", lambda x: fetches.append(len(x)) or device_get(x)
    )
    rng = np.random.default_rng(3)
    reqs = [
        eng.submit(Request(
            prompt=rng.integers(1, VOCAB, size=n).astype(np.int32),
            max_new_tokens=2,
        ))
        for n in (17, 6)  # 5 and 2 chunks of 4, both admitted in step one
    ]
    eng.step()
    assert dispatched == list(range(7)) and read == [4, 6]
    assert fetches == [2] and eng.stats()["admit_fetches"] == 1
    assert eng.stats()["prefill_chunks"] == 7
    assert all(len(r.generated) == 2 for r in reqs)


# What the engine served for these cases BEFORE the decode step took one
# packed argument (the six-argument program, on this CPU backend): the
# existing parity cases of this file and of the window, latent and
# sparse serving tests, token for token.
_RECORDED = {
    "plain-greedy": [
        [32, 28, 36, 28, 36, 28, 36, 32, 32, 32, 32, 32, 32, 36, 28, 49, 32, 36],
        [9, 14, 46, 40, 32, 40, 32, 14, 40, 32, 40, 46, 40, 40],
        [55, 14, 17, 17, 17, 17, 17, 17, 17, 17, 17, 17, 46, 40, 32, 20],
        [40, 40, 40, 40, 40, 40, 40, 3, 40, 3, 40, 32, 32, 32, 32, 32, 32, 32, 32, 32],
        [45, 42, 34, 17, 32, 34, 46, 46, 46, 28, 60, 34],
    ],
    "plain-sampled": [
        [35, 8, 27, 53, 53, 17, 30, 44, 32, 49, 36, 14, 42, 46, 49, 20, 14, 1],
        [48, 49, 1, 14, 46, 28, 14, 28, 14, 8, 32, 40, 14, 40],
        [53, 20, 17, 3, 20, 17, 9, 38, 47, 44, 41, 28, 20, 14, 46, 46],
        [55, 32, 14, 9, 46, 44, 44, 44, 8, 14, 46, 32, 30, 40, 9, 49, 44, 28, 60, 46],
        [36, 22, 8, 30, 34, 45, 60, 53, 7, 46, 27, 50],
    ],
    "window": [
        [222, 132, 232, 200, 226, 216, 155, 2, 9, 163, 173, 240],
        [74, 33, 33, 198, 46, 203, 33, 9, 9],
        [221, 177, 138, 169, 183, 206, 147, 225, 6, 133, 206, 174, 98, 53, 87, 99, 151, 179, 143, 38],
        [118, 138, 212, 240, 97],
        [35, 171, 221, 202, 158, 141, 43, 105],
    ],
    "latent": [
        [53, 219, 232, 3, 112, 50, 169, 127, 85, 254, 201, 216],
        [82, 165, 128, 183, 27, 107, 193, 247, 200],
        [237, 90, 232, 238, 98, 90, 232, 238, 62, 23, 179, 29, 74, 233, 194, 245, 172, 3, 48, 248],
        [163, 35, 74, 233, 2],
        [159, 91, 233, 129, 225, 55, 193, 177],
    ],
    "sparse": [
        [60, 180, 110, 217, 100, 183, 205, 110, 92, 236, 25, 42],
        [176, 123, 4, 51, 252, 151, 155, 167, 254],
        [141, 109, 253, 250, 107, 39, 118, 150, 186, 93, 234, 149, 54, 195, 234, 45, 194, 120, 253, 209],
        [172, 25, 253, 152, 25],
        [110, 39, 17, 215, 189, 24, 39, 17],
    ],
}

_TINY_LENGTHS = ((70, 12), (23, 9), (40, 20), (9, 5), (64, 8))


def _packed_step_engine(kind, tiny_lm):
    """(engine, requests) of one of the existing parity cases: the plain
    engine on ``_ADMIT_CASES`` with a pool so tight that it preempts,
    greedy and sampled; the small window-group, latent and sparse models
    (tests/*_tiny.py) on their files' lengths, by chunks."""
    if kind.startswith("plain"):
        model, params = tiny_lm
        sample = dict(temperature=0.8, top_k=20, seed=3) if kind == "plain-sampled" else {}
        cfg = ServeConfig(num_slots=3, page_size=4, num_pages=9, max_pages_per_slot=7, **sample)
        rng = np.random.default_rng(13)
        prompts = [(rng.integers(1, VOCAB, size=n).astype(np.int32), m) for n, m in _ADMIT_CASES]
    else:
        import importlib

        tiny = importlib.import_module({"window": "mellum_tiny", "latent": "longcat_tiny", "sparse": "keye_tiny"}[kind])
        model, params, _ = tiny.build(tiny.tiny_config())
        cfg = ServeConfig(num_slots=3, page_size=8, num_pages=49, max_pages_per_slot=14, prefill_chunk=12)
        rng = np.random.default_rng(0)
        prompts = [(rng.integers(0, 256, n).astype(np.int32), m) for n, m in _TINY_LENGTHS]
    eng = ServingEngine(model, params, cfg)
    return eng, [eng.submit(Request(prompt=p, max_new_tokens=m)) for p, m in prompts]


@pytest.mark.parametrize("kind", ["plain-greedy", "plain-sampled", "window", "latent", "sparse"])
def test_kept_logits_are_what_each_step_sampled_from(tiny_lm, kind):
    """``keep_logits``: the same tokens as the engine that keeps none,
    and each step's kept rows are the logits its tokens came from (the
    greedy argmax; under sampling, a token its row makes possible)."""
    plain, plain_reqs = _packed_step_engine(kind, tiny_lm)
    plain.run()
    eng, reqs = _packed_step_engine(kind, tiny_lm)
    eng.keep_logits()
    assert eng.last_logits is None
    steps = 0
    while eng.busy:
        before = {r.req_id: r.output_tokens for r in reqs}
        eng.step()
        for r in reqs:
            row = eng.last_logit_rows.get(r.req_id)
            # one token this step, by the decode (an admission adds its
            # first token besides)
            if row is None or r.output_tokens != before[r.req_id] + 1:
                continue
            logits = np.asarray(eng.last_logits[row])
            tok = r.generated[-1]
            assert logits.shape == (eng.model.vocab_size,) and np.isfinite(logits).all()
            if kind == "plain-sampled":
                assert logits[tok] > -np.inf
            else:
                assert tok == int(np.argmax(logits))
            steps += 1
    assert steps > 0
    assert [r.generated for r in reqs] == [r.generated for r in plain_reqs]


@pytest.mark.parametrize("kind", ["plain-greedy", "plain-sampled", "window", "latent", "sparse"])
def test_a_decode_step_is_one_packed_put(tiny_lm, kind, monkeypatch):
    """The decode program takes (params, pages, ONE int32 vector, key),
    whatever the model keeps beside the page table (a window group's
    table and first positions ride in the same vector), and the step
    makes exactly one host-to-device put for it: ``jax.device_put`` is
    counted, the program's arguments are looked at, and ``stats()``
    counts the same. The tokens are those the six-argument program
    served, greedy and sampled."""
    eng, reqs = _packed_step_engine(kind, tiny_lm)
    assert (eng.window_pool is not None) == (kind == "window")
    puts, seen = [], []
    device_put = jax.device_put
    monkeypatch.setattr(jax, "device_put", lambda x, *a, **kw: puts.append(x.shape) or device_put(x, *a, **kw))
    program = eng._decode_step

    def looked_at(*args):
        _, _, packed, _ = args
        seen.append((isinstance(packed, jax.Array), packed.shape, packed.dtype))
        return program(*args)

    eng._decode_step = looked_at
    eng.run()
    stats = eng.stats()
    n = eng._decode_arg_len()
    b, p, w = eng.cfg.num_slots, eng.cfg.max_pages_per_slot, eng.window_table_width
    assert n == b * (5 + p) + (b * (w + 1) if kind == "window" else 0)
    assert stats["decode_puts"] == stats["decode_steps"] == len(puts) == len(seen) > 0
    assert set(puts) == {(n,)}
    assert set(seen) == {(True, (n,), np.dtype(np.int32))}  # already on the device
    assert program._cache_size() == 1
    if kind.startswith("plain"):
        assert stats["preemptions"] > 0, "pool was not tight enough"
    served = [[int(t) for t in r.prompt[r.orig_prompt_len:]] + r.generated for r in reqs]
    assert served == _RECORDED[kind]


def test_engine_streams_tokens(tiny_lm):
    """Tokens surface as they decode, not at retire: the on_token
    callback sees every token in order, token_times stamps each one,
    and iter_tokens streams a request while the rest of the batch keeps
    decoding."""
    model, params = tiny_lm
    cfg = ServeConfig(num_slots=2, page_size=4, num_pages=33,
                      max_pages_per_slot=8)
    seen: list[tuple[int, int]] = []
    eng = ServingEngine(
        model, params, cfg,
        on_token=lambda r, t: seen.append((r.req_id, t)),
    )
    rng = np.random.default_rng(31)
    r0 = eng.submit(Request(
        prompt=rng.integers(1, VOCAB, size=5).astype(np.int32),
        max_new_tokens=8,
    ))
    r1 = eng.submit(Request(
        prompt=rng.integers(1, VOCAB, size=7).astype(np.int32),
        max_new_tokens=6,
    ))
    streamed = list(eng.iter_tokens(r0))
    assert streamed == r0.generated
    assert r0.done_time is not None
    eng.run()
    for r in (r0, r1):
        assert [t for rid, t in seen if rid == r.req_id] == r.generated
        assert len(r.token_times) == r.output_tokens
        assert all(
            b >= a for a, b in zip(r.token_times, r.token_times[1:])
        )


@pytest.mark.parametrize("impl", ["gather", "kernel"])
def test_engine_scan_layers_matches_unrolled(tiny_lm, impl):
    """A scan_layers model serves token-identically to the unrolled
    reference: the prefill commit scatters KV rows for ALL scanned
    layers at once (stacked pools, no unrolling) and decode runs the
    stacked step."""
    from cs744_pytorch_distributed_tutorial_tpu.models import (
        stack_block_params,
    )

    model, params = tiny_lm
    cfg = ServeConfig(num_slots=2, page_size=4, num_pages=33,
                      max_pages_per_slot=8, paged_attention_impl=impl)
    eng = ServingEngine(
        model.clone(scan_layers=True), stack_block_params(params), cfg
    )
    rng = np.random.default_rng(37)
    cases = [(5, 7), (9, 5), (3, 10)]
    reqs = [
        eng.submit(Request(
            prompt=rng.integers(1, VOCAB, size=plen).astype(np.int32),
            max_new_tokens=budget,
        ))
        for plen, budget in cases
    ]
    eng.run()
    for r in reqs:
        expect = _reference_tokens(model, params, r.prompt, r.max_new_tokens)
        assert r.generated == expect, (r.req_id, r.generated, expect)


# ------------------------------------------------------------ loadgen


def test_poisson_workload_is_seeded_and_bounded():
    mk = lambda: make_poisson_workload(
        num_requests=16, rate_rps=100.0, prompt_len=(3, 9),
        output_len=(2, 7), vocab_size=VOCAB, seed=5,
    )
    w1, w2 = mk(), mk()
    assert np.array_equal(w1.arrivals, w2.arrivals)
    assert all(np.array_equal(a, b) for a, b in zip(w1.prompts, w2.prompts))
    assert np.array_equal(w1.max_new_tokens, w2.max_new_tokens)
    assert w1.arrivals[0] == 0.0
    assert np.all(np.diff(w1.arrivals) >= 0)
    assert all(3 <= len(p) <= 9 and p.min() >= 1 for p in w1.prompts)
    assert w1.max_new_tokens.min() >= 2 and w1.max_new_tokens.max() <= 7
    with pytest.raises(ValueError, match="rate_rps"):
        make_poisson_workload(
            num_requests=1, rate_rps=0.0, prompt_len=(3, 9),
            output_len=(2, 7), vocab_size=VOCAB,
        )


def test_run_poisson_emits_summary_and_bench_twins(tiny_lm):
    """One short open-loop replay: every request completes, the summary
    record carries the serving metrics, and the bench-shaped twins
    (metric/value) land on the sink for regress.py to gate. Warmup
    requests must NOT leak into the sink or the counts."""
    model, params = tiny_lm
    sink = _ListSink()
    cfg = ServeConfig(num_slots=3, page_size=4, num_pages=33,
                      max_pages_per_slot=8)
    eng = ServingEngine(model, params, cfg, sink=sink)
    wl = make_poisson_workload(
        num_requests=6, rate_rps=500.0, prompt_len=(3, 8),
        output_len=(2, 6), vocab_size=VOCAB, seed=3,
    )
    record = run_poisson(eng, wl, sink=sink, warmup=True)
    assert record["requests"] == 6
    assert record["total_output_tokens"] == int(wl.max_new_tokens.sum())
    assert record["tokens_per_sec"] > 0
    assert record["ttft_p99_ms"] >= record["ttft_p50_ms"] >= 0
    # streamed-token gaps were measured, not derived from the mean
    assert record["itl_p99_ms"] >= record["itl_p50_ms"] >= 0
    assert record["itl_p99_ms"] > 0

    serve_recs = [r for r in sink.records if r.get("kind") == "serve"]
    assert len(serve_recs) == 6  # measured requests only, no warmup
    assert len({r["id"] for r in serve_recs}) == 6
    summaries = [r for r in sink.records if r.get("kind") == "serve_summary"]
    assert len(summaries) == 1 and summaries[0]["engine"] == "continuous"
    twins = {
        r["metric"]: r["value"]
        for r in sink.records
        if r.get("kind") == "bench"
    }
    assert twins["serve_tokens_per_sec"] == record["tokens_per_sec"]
    assert twins["serve_ttft_p99_ms"] == record["ttft_p99_ms"]
    assert twins["serve_itl_p99_ms"] == record["itl_p99_ms"]


def test_metrics_summary_renders_serve_rows(tmp_path):
    import importlib.util as ilu
    import os

    spec = ilu.spec_from_file_location(
        "metrics_summary",
        os.path.join(os.path.dirname(__file__), os.pardir, "benchmarks",
                     "metrics_summary.py"),
    )
    ms = ilu.module_from_spec(spec)
    spec.loader.exec_module(ms)
    records = [
        {"kind": "serve_summary", "engine": "continuous", "requests": 6,
         "ttft_p50_ms": 4.0, "ttft_p99_ms": 9.0, "itl_p50_ms": 2.0,
         "itl_p99_ms": 6.0, "tokens_per_sec": 310.0,
         "page_high_water": 12, "slot_occupancy": 0.8, "preemptions": 1},
        {"kind": "serve_summary", "engine": "batch", "requests": 6,
         "ttft_p50_ms": 900.0, "ttft_p99_ms": 2900.0,
         "tokens_per_sec": 40.0},
    ]
    summary = ms.summarize(records)
    assert set(summary["serve"]) == {"continuous", "batch"}
    assert summary["serve"]["continuous"]["tokens_per_sec"] == 310.0
    assert summary["serve"]["continuous"]["itl_p99_ms"] == 6.0
    assert summary["serve"]["batch"]["ttft_p99_ms"] == 2900.0
    assert summary["serve"]["batch"]["itl_p99_ms"] is None  # no streaming


# ------------------------------------------------------- regress gate


def _regress():
    import importlib.util as ilu
    import os

    spec = ilu.spec_from_file_location(
        "regress",
        os.path.join(os.path.dirname(__file__), os.pardir, "benchmarks",
                     "regress.py"),
    )
    mod = ilu.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_regress_generic_budgets_gate_serve_metrics():
    """The serve_smoke_budget.json idiom: baseline records with
    metric+budget arm absolute gates on the current stream — a
    throughput floor (direction min) and a latency ceiling (max)."""
    rg = _regress()
    baseline = [
        {"metric": "serve_tokens_per_sec", "value": 300.0, "budget": 40.0,
         "direction": "min"},
        {"metric": "serve_ttft_p99_ms", "value": 15.0, "budget": 1500.0,
         "direction": "max"},
    ]
    current_ok = [
        {"kind": "bench", "metric": "serve_tokens_per_sec", "value": 250.0},
        {"kind": "bench", "metric": "serve_ttft_p99_ms", "value": 12.0},
    ]
    code, verdict = rg.evaluate(
        baseline, current_ok, metric="serve_tokens_per_sec", tolerance=0.85
    )
    assert code == rg.PASS, verdict
    assert all(b["ok"] for b in verdict["budgets"])

    # p99 blows the ceiling -> REGRESSION even though throughput passes
    current_slow = [
        {"kind": "bench", "metric": "serve_tokens_per_sec", "value": 250.0},
        {"kind": "bench", "metric": "serve_ttft_p99_ms", "value": 4000.0},
    ]
    code, verdict = rg.evaluate(
        baseline, current_slow, metric="serve_tokens_per_sec", tolerance=0.85
    )
    assert code == rg.REGRESSION
    bad = {b["metric"]: b["ok"] for b in verdict["budgets"]}
    assert bad == {"serve_tokens_per_sec": True, "serve_ttft_p99_ms": False}

    # throughput under the floor -> REGRESSION via the min-direction gate
    current_weak = [
        {"kind": "bench", "metric": "serve_tokens_per_sec", "value": 260.0},
        {"kind": "bench", "metric": "serve_ttft_p99_ms", "value": 12.0},
    ]
    weak_floor = [dict(baseline[0], budget=290.0), baseline[1]]
    code, _ = rg.evaluate(
        weak_floor, current_weak, metric="serve_tokens_per_sec",
        tolerance=0.85,
    )
    assert code == rg.REGRESSION

    # an armed budget with no current values is MISSING, not a pass
    code, verdict = rg.evaluate(
        baseline,
        [{"kind": "bench", "metric": "serve_tokens_per_sec", "value": 250.0}],
        metric="serve_tokens_per_sec", tolerance=0.85,
    )
    assert code == rg.MISSING
    assert "serve_ttft_p99_ms" in verdict["error"]


# --------------------------------------------------- tensor-parallel


@pytest.mark.slow
def test_tp_engine_greedy_matches_gathered():
    """Tensor-sharded serving: the engine on a tensor=2 mesh (KV pages
    sharded over heads) must emit exactly the tokens the mesh-free
    engine emits from the same (gathered) params."""
    from cs744_pytorch_distributed_tutorial_tpu.data.text import (
        synthetic_tokens,
    )
    from cs744_pytorch_distributed_tutorial_tpu.parallel import make_mesh
    from cs744_pytorch_distributed_tutorial_tpu.train.lm import (
        LMConfig,
        LMTrainer,
    )

    mesh = make_mesh({"data": 2, "seq": 1, "tensor": 2},
                     devices=jax.devices()[:4])
    cfg = LMConfig(
        vocab_size=64, num_layers=2, num_heads=4, d_model=32, d_ff=64,
        max_seq_len=64, attention_impl="dense", global_batch_size=4,
        seq_len=16, seed=11, data_parallel=2, tensor_parallel=2,
    )
    tr = LMTrainer(cfg, mesh=mesh)
    params, opt_state = tr.init()
    toks = synthetic_tokens(8, 16, 64, seed=0)
    for s in range(2):
        x, y = tr.shard_batch(toks[s * 4 : s * 4 + 4])
        params, opt_state, _ = tr.train_step(params, opt_state, x, y)

    scfg = ServeConfig(num_slots=2, page_size=4, num_pages=33,
                      max_pages_per_slot=8)
    cases = [(4, 6), (7, 5), (5, 8)]
    rng = np.random.default_rng(23)
    prompts = [
        rng.integers(1, 64, size=plen).astype(np.int32)
        for plen, _ in cases
    ]

    def run(engine):
        reqs = [
            engine.submit(Request(prompt=p.copy(), max_new_tokens=budget))
            for p, (_, budget) in zip(prompts, cases)
        ]
        engine.run()
        return [r.generated for r in reqs]

    tp_out = run(ServingEngine(
        tr.tp_decode_model(), params, scfg,
        mesh=tr.mesh, param_specs=tr.param_specs,
    ))
    gathered_out = run(ServingEngine(
        tr.decode_model(), tr.gather_for_decode(params), scfg
    ))
    assert tp_out == gathered_out
