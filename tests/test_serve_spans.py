"""The profiler's phase spans inside ``ServingEngine.step()``.

One tiny engine is driven under ``jax.profiler.start_trace`` (Python
tracer off) and the capture is read back with ``ProfileData``: the spans
are the contract the benchmark's per-layer metrics read
(docs/observability.md has the table), so their names, nesting, order
and fields are pinned here, beside the counters ``stats()`` keeps at the
same boundaries and the promise that nothing changes with no capture
running.
"""

from __future__ import annotations

from collections import Counter

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
from cs744_pytorch_distributed_tutorial_tpu.utils import profiling
from cs744_pytorch_distributed_tutorial_tpu.utils.failure import (
    DecodeNanError,
)

VOCAB = 61
# 8 allocatable pages and slots that want up to 7 each: the pool runs
# dry, so the grow loop pre-empts and re-admissions are recomputes.
CASES = [(6, 18), (10, 14), (8, 16), (5, 20), (12, 12)]


def _submit_all(eng, rng):
    return [
        eng.submit(Request(
            prompt=rng.integers(1, VOCAB, size=plen).astype(np.int32),
            max_new_tokens=budget,
        ))
        for plen, budget in CASES
    ]


def _drive(eng):
    """step() until drained; returns the number of calls."""
    calls = 0
    while eng.busy:
        eng.step()
        calls += 1
    return calls


def _outputs(reqs):
    # Pre-emption moves produced tokens into the prompt.
    return [
        list(r.prompt[r.orig_prompt_len:]) + list(r.generated) for r in reqs
    ]


def _read_spans(trace_dir):
    from jax.profiler import ProfileData

    path = max(trace_dir.rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    spans = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve/"):
                    spans.append({
                        "name": ev.name, "lo": ev.start_ns,
                        "hi": ev.start_ns + ev.duration_ns,
                        **dict(ev.stats),
                    })
    return sorted(spans, key=lambda s: (s["lo"], -s["hi"]))


@pytest.fixture(scope="module")
def capture(tmp_path_factory):
    model = TransformerLM(
        vocab_size=VOCAB, num_layers=2, num_heads=2, d_model=32, d_ff=64,
        max_seq_len=64, attention_impl="dense", use_rope=True,
    )
    params = model.init(
        jax.random.key(0), jnp.zeros((1, 4), jnp.int32)
    )["params"]
    cfg = ServeConfig(
        num_slots=3, page_size=4, num_pages=9, max_pages_per_slot=7
    )
    eng = ServingEngine(model, params, cfg)
    # Warm-up off the capture: the same workload compiles every prefill
    # bucket (re-admissions' too) and the decode step.
    _submit_all(eng, np.random.default_rng(13))
    _drive(eng)
    compiles = CompileCounter()
    stats0 = eng.stats()

    trace_dir = tmp_path_factory.mktemp("serve_spans")
    with profiling.trace(str(trace_dir)):
        traced = _submit_all(eng, np.random.default_rng(13))
        calls = _drive(eng)
        stats1 = eng.stats()
        # a few steps with a guard set: the deadline sweep gets its span
        eng.guard = ServeGuard(cfg=GuardConfig(deadline_s=3600.0))
        eng.submit(Request(
            prompt=np.arange(1, 6, dtype=np.int32), max_new_tokens=3
        ))
        guarded_calls = _drive(eng)
        eng.guard = None
        # a step that raises on its way out of the decode
        decode = eng._decode_step
        eng._decode_step = lambda params, pages, *a: (
            pages, np.full((cfg.num_slots,), VOCAB + 7, np.int32)
        )
        eng.submit(Request(
            prompt=np.arange(1, 6, dtype=np.int32), max_new_tokens=3
        ))
        with pytest.raises(DecodeNanError):
            eng.step()
        eng._decode_step = decode
        _drive(eng)
    spans = _read_spans(trace_dir)
    n_traced_compiles = compiles.count

    # the same workload again with no capture running
    untraced = _submit_all(eng, np.random.default_rng(13))
    _drive(eng)
    return {
        "spans": spans,
        # the spans of the first, unguarded run: its steps are numbered
        # from the warm-up's last
        "run": [
            s for s in spans
            if s["lo"] < _named(spans, "serve/step")[calls]["lo"]
        ],
        "calls": calls, "guarded_calls": guarded_calls,
        "stats": {k: stats1[k] - stats0[k] for k in (
            "admissions", "admit_steps", "admit_fetches", "pages_grown",
            "requests_done", "preemptions", "decode_steps", "decode_puts",
            "pool_audits",
        )},
        "max_admits_in_step": stats1["max_admits_in_step"],
        "traced": traced, "untraced": untraced,
        "compiles_traced": n_traced_compiles,
        "compiles_after": compiles.count,
    }


def _named(spans, name):
    return [s for s in spans if s["name"] == name]


def _inside(child, parent):
    return parent["lo"] <= child["lo"] and child["hi"] <= parent["hi"]


def _children(spans, parent, name):
    return [s for s in _named(spans, name) if _inside(s, parent)]


def check_one_step_span_per_call(c):
    steps = _named(c["run"], "serve/step")
    assert len(steps) == c["calls"]
    assert all({"step", "queued", "active"} <= set(s) for s in steps)
    assert steps[0]["queued"] == len(CASES) and steps[0]["active"] == 0
    # the engine's counter: it does not fall, and rises by the decodes
    numbers = [s["step"] for s in steps]
    assert numbers == sorted(numbers)
    assert numbers[-1] - numbers[0] == c["stats"]["decode_steps"] - 1


def check_one_admit_span_per_admission(c):
    admits = _named(c["run"], "serve/admit")
    assert len(admits) == c["stats"]["admissions"]
    assert len(admits) == len(CASES) + c["stats"]["preemptions"]
    steps = _named(c["run"], "serve/step")
    for a in admits:
        assert {"step", "req", "bucket", "kind", "prompt_len"} <= set(a)
        assert a["bucket"] >= a["prompt_len"] >= 1
        parents = [s for s in steps if _inside(a, s)]
        assert len(parents) == 1 and parents[0]["step"] == a["step"]
    assert {a["req"] for a in admits} == {r.req_id for r in c["traced"]}


def check_prep_and_prefill_nest_in_their_admit(c):
    for a in _named(c["run"], "serve/admit"):
        prep = _children(c["run"], a, "serve/admit_prep")
        prefill = _children(c["run"], a, "serve/prefill")
        assert len(prep) == 1 and len(prefill) == 1
        assert prep[0]["req"] == prefill[0]["req"] == a["req"]
        assert prefill[0]["bucket"] == a["bucket"]
        assert prep[0]["hi"] <= prefill[0]["lo"]


def check_one_admit_fetch_in_a_step_that_admitted(c):
    """The step's admissions are enqueued back to back and their first
    tokens fetched once: one ``serve/admit_fetch`` after the last
    ``serve/admit`` and before ``serve/grow`` in a step that admitted,
    none in a step that did not, and ``stats()`` counts the same."""
    with_fetch = several = 0
    for s in _named(c["run"], "serve/step"):
        admits = _children(c["run"], s, "serve/admit")
        fetches = _children(c["run"], s, "serve/admit_fetch")
        assert len(fetches) == (1 if admits else 0)
        if not admits:
            continue
        (fetch,), (grow,) = fetches, _children(c["run"], s, "serve/grow")
        assert fetch["step"] == s["step"] and fetch["admits"] == len(admits)
        assert all(a["hi"] <= fetch["lo"] for a in admits)
        assert not any(_inside(fetch, a) for a in admits)
        assert fetch["hi"] <= grow["lo"]
        with_fetch += 1
        several += len(admits) > 1
    assert with_fetch == c["stats"]["admit_fetches"] == c["stats"]["admit_steps"]
    assert several, "no step admitted more than one request"


def check_decode_phases_in_order(c):
    decoded = 0
    for s in _named(c["run"], "serve/step"):
        grow = _children(c["run"], s, "serve/grow")
        phases = [
            _children(c["run"], s, f"serve/{n}")
            for n in ("decode_prep", "decode", "retire")
        ]
        assert len(grow) == 1 and grow[0]["step"] == s["step"]
        assert all(len(p) == 1 for p in phases)
        prep, decode, retire = (p[0] for p in phases)
        assert grow[0]["hi"] <= prep["lo"]
        assert prep["hi"] <= decode["lo"] and decode["hi"] <= retire["lo"]
        assert retire["hi"] <= s["hi"]
        assert prep["step"] == decode["step"] == retire["step"] == s["step"]
        assert 1 <= decode["active"] <= 3
        for a in _children(c["run"], s, "serve/admit"):
            assert a["hi"] <= grow[0]["lo"]
        decoded += 1
    assert decoded == c["stats"]["decode_steps"]
    assert not _named(c["run"], "serve/expire")  # no guard, no sweep


def check_decode_prep_makes_one_put_and_no_step_audits_the_pool(c):
    """``serve/decode_prep`` says how many host-to-device puts it made
    (one packed vector, where six arrays went up one by one), and
    ``stats()`` counts the same; the requests retired and preempted
    under the capture cost no walk of the page pool."""
    preps = _named(c["run"], "serve/decode_prep")
    assert preps and all(p["puts"] == 1 for p in preps)
    assert c["stats"]["decode_puts"] == c["stats"]["decode_steps"] == len(preps)
    assert c["stats"]["preemptions"] > 0 and c["stats"]["pool_audits"] == 0


def check_readmission_is_a_recompute_under_the_same_req(c):
    assert c["stats"]["preemptions"] > 0, "pool was not tight enough"
    by_req: dict[int, list[str]] = {}
    for a in _named(c["run"], "serve/admit"):
        by_req.setdefault(a["req"], []).append(a["kind"])
    assert all(kinds[0] == "prefill" for kinds in by_req.values())
    again = [k for kinds in by_req.values() for k in kinds[1:]]
    assert len(again) == c["stats"]["preemptions"]
    assert set(again) == {"recompute"}


def check_counters_equal_span_counts(c):
    steps = _named(c["run"], "serve/step")
    per_step = [len(_children(c["run"], s, "serve/admit")) for s in steps]
    assert c["stats"]["admissions"] == sum(per_step)
    assert c["stats"]["admit_steps"] == sum(1 for n in per_step if n)
    assert c["max_admits_in_step"] == max(per_step)
    grown = sum(s["pages"] for s in _named(c["run"], "serve/grow"))
    assert c["stats"]["pages_grown"] == grown > 0
    retired = sum(s["retired"] for s in _named(c["run"], "serve/retire"))
    assert c["stats"]["requests_done"] == retired == len(CASES)


def check_expire_span_only_under_a_guard(c):
    steps = _named(c["spans"], "serve/step")
    guarded = steps[c["calls"]: c["calls"] + c["guarded_calls"]]
    assert len(guarded) == c["guarded_calls"] >= 2
    sweeps = _named(c["spans"], "serve/expire")
    assert len(sweeps) == len(guarded)
    for s, sweep in zip(guarded, sweeps):
        assert _inside(sweep, s) and sweep["step"] == s["step"]


def check_an_exception_closes_the_spans_it_passes(c):
    # A span is recorded when it closes: the raising step's are all there.
    steps = _named(c["spans"], "serve/step")
    raised = steps[c["calls"] + c["guarded_calls"]]
    names = Counter(
        s["name"] for s in c["spans"] if s is not raised and _inside(s, raised)
    )
    assert names == {
        "serve/admit": 1, "serve/admit_prep": 1, "serve/prefill": 1,
        "serve/admit_fetch": 1, "serve/grow": 1, "serve/decode_prep": 1, "serve/decode": 1,
        "serve/retire": 1,
    }
    # it never got to count its retirements
    retire = next(
        s for s in _named(c["spans"], "serve/retire") if _inside(s, raised)
    )
    assert "retired" not in retire


def check_no_capture_same_tokens_no_compile(c):
    assert _outputs(c["untraced"]) == _outputs(c["traced"])
    assert all(len(o) == b for o, (_, b) in zip(_outputs(c["traced"]), CASES))
    assert c["compiles_traced"] == 0 and c["compiles_after"] == 0


CHECKS = [
    check_one_step_span_per_call,
    check_one_admit_span_per_admission,
    check_prep_and_prefill_nest_in_their_admit,
    check_one_admit_fetch_in_a_step_that_admitted,
    check_decode_phases_in_order,
    check_decode_prep_makes_one_put_and_no_step_audits_the_pool,
    check_readmission_is_a_recompute_under_the_same_req,
    check_counters_equal_span_counts,
    check_expire_span_only_under_a_guard,
    check_an_exception_closes_the_spans_it_passes,
    check_no_capture_same_tokens_no_compile,
]


@pytest.mark.parametrize(
    "check", CHECKS, ids=[f.__name__.removeprefix("check_") for f in CHECKS]
)
def test_serve_spans(capture, check):
    check(capture)


def test_annotate_passes_fields_and_late_metadata(tmp_path):
    with profiling.trace(str(tmp_path)):
        with profiling.annotate("serve/probe", req=7, kind="prefill") as span:
            span.set_metadata(pages=2)
    (probe,) = _read_spans(tmp_path)
    assert (probe["req"], probe["kind"], probe["pages"]) == (7, "prefill", 2)


def test_chunked_prefill_spans_and_counters(tmp_path):
    """With ``ServeConfig.prefill_chunk`` set, every chunk program of an
    admission (its put and its dispatch; no fetch) is a
    ``serve/prefill_chunk`` span inside that admission's
    ``serve/prefill``, in order, carrying the request, the chunk's
    number, its offset and how many prompt tokens it holds; the step's
    one ``serve/admit_fetch`` follows its last admission; ``stats()``
    counts the same chunks and fetches, and for a model with
    an indexer and experts the decode steps' device-side counters
    (behind the step's tokens: one fetch, one compiled step)."""
    model = TransformerLM(
        vocab_size=VOCAB, num_layers=2, num_heads=2, d_model=32, d_ff=16,
        max_seq_len=64, attention_impl="dense", use_rope=True,
        norm="rmsnorm", mlp="swiglu", num_experts=4, moe_top_k=2,
        moe_dispatch="dropless", moe_bias=False, qk_norm=True,
        indexer_heads=2, indexer_head_dim=8, sparse_topk=8,
    )
    params = model.init(
        jax.random.key(0), jnp.zeros((1, 4), jnp.int32)
    )["params"]
    eng = ServingEngine(model, params, ServeConfig(
        num_slots=2, page_size=4, num_pages=33, max_pages_per_slot=12,
        prefill_chunk=8,
    ))
    prompts = {0: 21, 1: 8, 2: 13}  # 3, 1 and 2 chunks of 8
    rng = np.random.default_rng(5)

    def submit_all():
        return [
            eng.submit(Request(
                prompt=rng.integers(1, VOCAB, size=n).astype(np.int32),
                max_new_tokens=4,
            ))
            for n in prompts.values()
        ]

    submit_all()
    _drive(eng)  # warm-up: the one chunk program and the decode step
    compiles = CompileCounter()
    stats0 = eng.stats()
    with profiling.trace(str(tmp_path)):
        reqs = submit_all()
        _drive(eng)
    stats1 = eng.stats()
    assert compiles.count == 0
    spans = _read_spans(tmp_path)
    admits = _named(spans, "serve/admit")
    assert len(admits) == len(prompts)
    for admit, req, n in zip(admits, reqs, prompts.values()):
        (prefill,) = _children(spans, admit, "serve/prefill")
        chunks = _children(spans, admit, "serve/prefill_chunk")
        assert all(_inside(ch, prefill) for ch in chunks)
        assert [ch["chunk"] for ch in chunks] == list(range(-(-n // 8)))
        assert [ch["offset"] for ch in chunks] == [8 * i for i in range(len(chunks))]
        assert sum(ch["len"] for ch in chunks) == n == admit["prompt_len"]
        assert {ch["req"] for ch in chunks} == {req.req_id} and admit["bucket"] == 8
    n_chunks = len(_named(spans, "serve/prefill_chunk"))
    assert n_chunks == 6 == stats1["prefill_chunks"] - stats0["prefill_chunks"]
    # two slots: the first step admits two requests (4 chunks) behind one
    # fetch, a later one the third
    fetches = _named(spans, "serve/admit_fetch")
    assert [f["admits"] for f in fetches] == [2, 1]
    assert stats1["admit_fetches"] - stats0["admit_fetches"] == 2
    assert all(a["hi"] <= fetches[0]["lo"] for a in admits[:2])
    assert fetches[0]["hi"] <= admits[2]["lo"] <= admits[2]["hi"] <= fetches[1]["lo"]
    # the decode steps' counters: each of a request's 3 decode steps at
    # depth L scores L + 1 tokens a layer and keeps min(L + 1, 8)
    depths = [n + i for n in prompts.values() for i in range(3)]
    assert stats1["scored_tokens"] - stats0["scored_tokens"] == 2 * sum(d + 1 for d in depths)
    assert stats1["selected_tokens"] - stats0["selected_tokens"] == 2 * 8 * len(depths)
    assert stats1["experts_hit"] > stats0["experts_hit"]
    assert 1.0 <= stats1["expert_tokens_max_over_mean"] <= 4.0


def test_window_free_spans_and_counters(tmp_path):
    """A model with sliding-window layers keeps a second page group; the
    host work of moving it on is a ``serve/window_free`` span: one before
    each chunk program inside the admission (``req``, ``pages``), one a
    decode step inside ``serve/decode_prep`` (``step``, ``pages``). The
    spans' ``pages`` add up to ``stats()``'s ``window_pages_freed``, and
    the decode steps' device-side counters of keys attended ride behind
    the step's tokens: no new transfer, no compile after warm-up. A model
    without such layers writes no such span."""
    kinds = ("sliding_attention",) * 3 + ("full_attention",)
    model = TransformerLM(
        vocab_size=VOCAB, num_layers=4, num_heads=2, d_model=32, d_ff=16,
        max_seq_len=96, attention_impl="dense", use_rope=True,
        norm="rmsnorm", mlp="swiglu", num_experts=4, moe_top_k=2,
        moe_dispatch="dropless", moe_bias=False, qk_norm=True,
        layer_types=kinds, window=8,
    )
    params = model.init(
        jax.random.key(0), jnp.zeros((1, 4), jnp.int32)
    )["params"]
    eng = ServingEngine(model, params, ServeConfig(
        num_slots=2, page_size=4, num_pages=65, max_pages_per_slot=20,
        prefill_chunk=8,
    ))
    prompts = {0: 37, 1: 8, 2: 50}  # 5, 1 and 7 chunks of 8
    rng = np.random.default_rng(5)

    def submit_all():
        return [
            eng.submit(Request(
                prompt=rng.integers(1, VOCAB, size=n).astype(np.int32),
                max_new_tokens=6,
            ))
            for n in prompts.values()
        ]

    submit_all()
    _drive(eng)  # warm-up: the one chunk program and the decode step
    compiles = CompileCounter()
    stats0 = eng.stats()
    with profiling.trace(str(tmp_path)):
        reqs = submit_all()
        _drive(eng)
    stats1 = eng.stats()
    assert compiles.count == 0
    spans = _read_spans(tmp_path)
    frees = _named(spans, "serve/window_free")
    admits = _named(spans, "serve/admit")
    in_admit = 0
    for admit, req, n in zip(admits, reqs, prompts.values()):
        mine = _children(spans, admit, "serve/window_free")
        chunks = _children(spans, admit, "serve/prefill_chunk")
        assert len(mine) == len(chunks) == -(-n // 8)
        # each before its chunk, inside the admission's prefill
        (prefill,) = _children(spans, admit, "serve/prefill")
        assert all(_inside(f, prefill) and f["hi"] <= ch["lo"] for f, ch in zip(mine, chunks))
        assert {f["req"] for f in mine} == {req.req_id}
        # chunk c sees back to 8 c - 7: the pages before that one's go back
        assert sum(f["pages"] for f in mine) == max(0, (8 * (len(chunks) - 1) - 7) // 4)
        in_admit += len(mine)
    preps = _named(spans, "serve/decode_prep")
    for prep in preps:
        (free,) = _children(spans, prep, "serve/window_free")
        assert free["step"] == prep["step"] and free["pages"] >= 0
    assert len(frees) == in_admit + len(preps)
    freed = stats1["window_pages_freed"] - stats0["window_pages_freed"]
    assert sum(f["pages"] for f in frees) == freed > 0
    assert stats1["pages_live_window"] == stats1["pages_live_full"] == 0
    # keys attended by the decode steps: 5 steps a request at depths n ..
    depths = [n + i for n in prompts.values() for i in range(5)]
    assert stats1["full_tokens_read"] - stats0["full_tokens_read"] == sum(d + 1 for d in depths)
    assert stats1["window_tokens_read"] - stats0["window_tokens_read"] == 3 * 8 * len(depths)


def test_a_model_without_window_layers_writes_no_window_free_span(capture):
    assert not _named(capture["spans"], "serve/window_free")
