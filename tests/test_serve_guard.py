"""graftguard (serve/guard.py): chaos-tested serving contracts.

Five pin groups:

1. **Pool accounting is un-corruptible.** ``PagePool.free`` rejects
   double frees and foreign pages (the silent-corruption bug class
   behind leaked pages) and, with ``alloc``, checks the pool's counts
   at the cost of the call; ``check_invariants`` — the walk of every
   page, called by the engine off the clock only (``snapshot()``, the
   drain of ``run()``), never by a retire/preempt/expiry — proves
   free ∪ live partitions the pool.
2. **Deadlines resolve terminally.** ``deadline_s`` / ``max_queue_s``
   expiry retires a request as ``timed_out`` — active slots free their
   pages immediately, queued requests resolve with honestly-absent
   latency fields — under an injected fake clock, so the sweeps are
   deterministic. The nasty interleaving is pinned: a preemption victim
   whose deadline lapses while it waits at the queue FRONT.
3. **Shedding is deterministic and non-destructive.** The bounded queue
   rejects with machine-readable ``serve_shed`` events (identical
   sequences on identical seeded traces); ``degrade`` trims budgets
   under pool pressure and the trimmed output is a bitwise PREFIX of
   the untrimmed oracle (greedy AND sampled — the per-request PRNG
   streams make the trim invisible to the tokens that survive).
4. **Zero retraces survive the guard.** All guard work is host-side;
   the CompileCounter proves admission control, shedding, and expiry
   never touch the fixed-shape decode step (GL002).
5. **Crashes never reach the client.** ``ServeChaosMonkey`` faults
   (``decode_nan`` / ``slow_step`` / ``engine_crash``) drive
   ``run_serve_with_recovery``'s snapshot→restart→replay ladder; the
   overloaded chaos e2e must end with every request terminally
   resolved, zero leaked pages, and admitted outputs token-identical
   to an uninterrupted oracle run.

The chaos-smoke CI job runs this file without the tier-1 ``slow``
filter; docs/reliability.md ("Serving under failure and overload") is
the operator story.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cs744_pytorch_distributed_tutorial_tpu.models import TransformerLM
from cs744_pytorch_distributed_tutorial_tpu.serve import (
    GuardConfig,
    PagePool,
    Request,
    ServeConfig,
    ServeGuard,
    ServingEngine,
    make_poisson_workload,
    run_poisson,
    run_serve_with_recovery,
)
from cs744_pytorch_distributed_tutorial_tpu.utils.chaos import (
    FaultSchedule,
    ServeChaosMonkey,
)
from cs744_pytorch_distributed_tutorial_tpu.utils.failure import (
    DecodeNanError,
    EngineCrashError,
)

VOCAB = 61


class _ListSink:
    def __init__(self):
        self.records = []

    def emit(self, record):
        self.records.append(dict(record))


class _Clock:
    """Injectable monotonic clock: guard sweeps become deterministic."""

    def __init__(self, t: float = 100.0):
        self.t = float(t)

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += float(dt)


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


def _cfg(**kw):
    base = dict(num_slots=2, page_size=4, num_pages=33, max_pages_per_slot=8)
    base.update(kw)
    return ServeConfig(**base)


def _prompt(rng, n):
    return rng.integers(1, VOCAB, size=n).astype(np.int32)


# ---------------------------------------------------------------------------
# PagePool hardening (double-free + invariants)
# ---------------------------------------------------------------------------


def test_pool_rejects_double_free():
    pool = PagePool(num_pages=9, page_size=4)
    pages = pool.alloc(3)
    pool.free(pages)
    with pytest.raises(ValueError, match="double free"):
        pool.free([pages[0]])
    assert pool.check_invariants()


def test_pool_rejects_duplicate_pages_in_one_free():
    pool = PagePool(num_pages=9, page_size=4)
    a = pool.alloc(2)
    with pytest.raises(ValueError, match="double free"):
        pool.free([a[0], a[0]])
    # the rejected call must not have mutated anything
    assert pool.allocated_pages == 2
    assert pool.check_invariants()
    pool.free(a)
    assert pool.free_pages == 8
    assert pool.check_invariants()


def test_pool_check_invariants_catches_corruption():
    pool = PagePool(num_pages=9, page_size=4)
    pool.alloc(2)
    pool._free.append(pool._free[0])  # a double-free that slipped through
    with pytest.raises(AssertionError):
        pool.check_invariants()


def test_pool_rejects_a_foreign_page():
    """A page the pool never leased, in range or not, is refused at
    ``free()`` and nothing is mutated."""
    pool = PagePool(num_pages=9, page_size=4)
    mine = pool.alloc(2)
    for foreign, match in ((7, "double free"), (9, "out of range"), (0, "trash page")):
        with pytest.raises(ValueError, match=match):
            pool.free([mine[0], foreign])
        assert pool.allocated_pages == 2 and pool.check_invariants()


@pytest.mark.parametrize("call", ["alloc", "free"])
def test_pool_counts_are_checked_at_every_alloc_and_free(call):
    """What ``alloc`` and ``free`` check of the whole pool costs nothing
    in its size, and catches a free list that gained or lost a page
    behind their back at the next call."""
    pool = PagePool(num_pages=9, page_size=4)
    pages = pool.alloc(2)
    pool._free.append(pool._free[0])  # a double-free that slipped through
    with pytest.raises(AssertionError, match="allocatable"):
        pool.alloc(1) if call == "alloc" else pool.free(pages)


def test_no_step_walks_the_pool_and_the_drain_does(tiny_lm, monkeypatch):
    """Retiring, preempting and expiring free pages through
    ``PagePool.free`` alone: no ``check_invariants`` inside a step
    (counted here, and by ``stats()["pool_audits"]``), one at
    ``snapshot()`` and one when ``run()`` has drained."""
    model, params = tiny_lm
    walks = []
    audit = PagePool.check_invariants
    monkeypatch.setattr(
        PagePool, "check_invariants",
        lambda self: walks.append(self.num_pages) or audit(self),
    )
    clock = _Clock()
    # 8 allocatable pages, slots want up to 7 each: the grow loop preempts
    eng = ServingEngine(
        model, params, _cfg(num_slots=3, num_pages=9, max_pages_per_slot=7),
        clock=clock, guard=ServeGuard(),
    )
    rng = np.random.default_rng(13)
    reqs = [
        eng.submit(Request(prompt=_prompt(rng, plen), max_new_tokens=budget))
        for plen, budget in [(6, 18), (10, 14), (8, 16), (5, 20), (12, 12)]
    ]
    reqs[1].deadline_s = 3.0
    while eng.busy:
        eng.step()
        clock.advance(0.25)
    stats = eng.stats()
    assert stats["preemptions"] > 0 and stats["timed_out_requests"] == 1
    assert stats["requests_done"] == len(reqs)
    assert walks == [] and stats["pool_audits"] == 0
    assert eng.pool.free_pages == eng.pool.num_pages - 1
    eng.snapshot()
    assert walks == [9] and eng.stats()["pool_audits"] == 1
    eng.submit(Request(prompt=_prompt(rng, 6), max_new_tokens=4))
    eng.run()
    assert walks == [9, 9] and eng.stats()["pool_audits"] == 2


def test_a_corrupted_pool_fails_at_the_drain_of_run(tiny_lm):
    """A duplicate on the free list that no ``free`` or ``alloc`` of the
    run meets (it lies at the bottom of the stack) is what the whole
    walk is for: ``run()`` ends in it, and ``snapshot()`` starts with
    it."""
    model, params = tiny_lm
    eng = ServingEngine(model, params, _cfg())
    # bottom of the LIFO stack: swapped for a duplicate, so the counts
    # still add up and only the walk can tell
    eng.pool._free[0] = eng.pool._free[1]
    eng.submit(Request(
        prompt=_prompt(np.random.default_rng(2), 6), max_new_tokens=4
    ))
    with pytest.raises(AssertionError, match="duplicate"):
        eng.run()
    assert not eng.busy  # the drain itself finished
    with pytest.raises(AssertionError, match="duplicate"):
        eng.snapshot()


# ---------------------------------------------------------------------------
# Guard config + admission control
# ---------------------------------------------------------------------------


def test_guard_config_validates():
    with pytest.raises(ValueError, match="shed_policy"):
        GuardConfig(shed_policy="drop")
    with pytest.raises(ValueError, match="degrade_floor"):
        GuardConfig(degrade_floor=0)
    with pytest.raises(ValueError, match="pressure_free_frac"):
        GuardConfig(pressure_free_frac=1.5)


def test_queue_full_sheds_terminally(tiny_lm):
    model, params = tiny_lm
    sink = _ListSink()
    eng = ServingEngine(
        model, params, _cfg(), sink=sink, clock=_Clock(),
        guard=ServeGuard(cfg=GuardConfig(max_queue_depth=2)),
    )
    rng = np.random.default_rng(3)
    reqs = [
        eng.submit(Request(prompt=_prompt(rng, 4), max_new_tokens=4))
        for _ in range(5)
    ]
    shed = [r for r in reqs if r.terminal_status == "rejected"]
    assert [r.req_id for r in shed] == [2, 3, 4]
    assert len(eng._queue) == 2
    assert all(
        r.done_time is not None and r.output_tokens == 0 for r in shed
    )
    evs = [e for e in sink.records if e.get("kind") == "serve_shed"]
    assert [(e["id"], e["reason"], e["terminal"]) for e in evs] == [
        (2, "queue_full", True), (3, "queue_full", True),
        (4, "queue_full", True),
    ]
    assert eng.guard.shed_counts == {"queue_full": 3}
    while eng.busy:
        eng.step()
    assert eng.stats()["shed_requests"] == 3
    shed_ids = {r.req_id for r in shed}
    assert all(
        r.terminal_status == "completed"
        for r in reqs if r.req_id not in shed_ids
    )
    # every submission resolved exactly once
    assert sorted(r.req_id for r in eng._completed) == [0, 1, 2, 3, 4]


@pytest.mark.slow  # chaos-smoke CI runs these without the tier-1 filter
@pytest.mark.parametrize(
    "sample",
    [dict(), dict(temperature=0.9, top_k=20)],
    ids=["greedy", "sampled"],
)
def test_degrade_trim_output_is_oracle_prefix(tiny_lm, sample):
    """A degrade-trimmed request's stream is a bitwise PREFIX of its
    untrimmed oracle output — greedy trivially, sampled because the
    per-request PRNG streams key on (req_id, absolute token index)."""
    model, params = tiny_lm
    rng = np.random.default_rng(5)
    filler_prompt = _prompt(rng, 8)
    prompt = _prompt(rng, 6)

    oracle = ServingEngine(model, params, _cfg(**sample))
    oracle.submit(Request(prompt=filler_prompt.copy(), max_new_tokens=8))
    o = oracle.submit(Request(prompt=prompt.copy(), max_new_tokens=20))
    oracle.run()

    sink = _ListSink()
    guard = ServeGuard(cfg=GuardConfig(
        shed_policy="degrade", degrade_floor=6, pressure_free_frac=1.0,
    ))
    eng = ServingEngine(model, params, _cfg(**sample), sink=sink, guard=guard)
    # pool is unpressured while empty; the filler's pages trip the
    # (deliberately hair-trigger) pressure threshold for the next admit
    eng.submit(Request(prompt=filler_prompt.copy(), max_new_tokens=8))
    eng.step()
    r = eng.submit(Request(prompt=prompt.copy(), max_new_tokens=20))
    assert r.max_new_tokens == 6, "degrade did not trim at admission"
    assert r.orig_max_new_tokens == 6, "trim must precede budget record"
    eng.run()
    assert r.terminal_status == "completed"
    assert r.generated == o.generated[:6]
    trims = [e for e in sink.records if e.get("kind") == "serve_shed"]
    assert [(e["reason"], e["terminal"], e["tokens_shed"])
            for e in trims] == [("degrade_trim", False, 14)]
    assert eng.guard.shed_counts == {"degrade_trim": 1}


@pytest.mark.slow  # chaos-smoke CI runs these without the tier-1 filter
def test_shed_events_deterministic_on_seeded_trace(tiny_lm):
    """Two runs of the same seeded overload trace under a fake clock
    produce IDENTICAL serve_shed and timed_out event sequences."""
    model, params = tiny_lm

    def run_once():
        clock = _Clock()
        sink = _ListSink()
        eng = ServingEngine(
            model, params, _cfg(), sink=sink, clock=clock,
            guard=ServeGuard(cfg=GuardConfig(
                max_queue_depth=2, deadline_s=3.0,
            )),
        )
        rng = np.random.default_rng(9)
        sizes = rng.integers(4, 9, size=(10, 2))
        for k, (plen, budget) in enumerate(sizes):
            eng.submit(Request(
                prompt=_prompt(rng, int(plen)),
                max_new_tokens=int(budget),
            ))
            if k % 3 == 2:
                eng.step()
                clock.advance(0.5)
        while eng.busy:
            eng.step()
            clock.advance(0.5)
        sheds = [
            (e["id"], e["reason"], e["terminal"])
            for e in sink.records if e.get("kind") == "serve_shed"
        ]
        expiries = [
            (e["id"], e["reason"], e["queued"])
            for e in sink.records
            if e.get("kind") == "serve" and e.get("event") == "timed_out"
        ]
        return sheds, expiries

    first, second = run_once(), run_once()
    assert first == second
    assert first[0], "trace was not overloaded enough to shed"
    assert first[1], "trace was not slow enough to expire deadlines"


# ---------------------------------------------------------------------------
# Deadlines + expiry (fake clock)
# ---------------------------------------------------------------------------


def test_deadline_expires_active_slot_and_frees_pages(tiny_lm):
    model, params = tiny_lm
    clock = _Clock()
    sink = _ListSink()
    eng = ServingEngine(
        model, params, _cfg(), sink=sink, clock=clock,
        guard=ServeGuard(cfg=GuardConfig(deadline_s=10.0)),
    )
    rng = np.random.default_rng(0)
    r = eng.submit(Request(prompt=_prompt(rng, 6), max_new_tokens=20))
    for _ in range(3):
        eng.step()
    assert r.first_token_time is not None and r.done_time is None
    clock.advance(11.0)
    eng.step()
    assert r.terminal_status == "timed_out"
    assert r.done_time is not None
    # pages reclaimed immediately, pool partition intact
    assert eng.pool.free_pages == eng.pool.num_pages - 1
    assert eng.pool.check_invariants()
    evs = [
        e for e in sink.records
        if e.get("kind") == "serve" and e.get("event") == "timed_out"
    ]
    assert [(e["id"], e["reason"], e["queued"]) for e in evs] == [
        (r.req_id, "deadline", False)
    ]
    assert eng.stats()["timed_out_requests"] == 1
    # tokens surfaced before expiry were delivered, and the request
    # record carries real latency fields
    rec = [
        e for e in sink.records
        if e.get("kind") == "serve" and e.get("event") == "request"
    ][0]
    assert rec["status"] == "timed_out" and rec["ttft_ms"] is not None


def test_queue_wait_expires_queued_request(tiny_lm):
    model, params = tiny_lm
    clock = _Clock()
    sink = _ListSink()
    eng = ServingEngine(
        model, params, _cfg(num_slots=1), sink=sink, clock=clock,
        guard=ServeGuard(cfg=GuardConfig(max_queue_s=5.0)),
    )
    rng = np.random.default_rng(1)
    first = eng.submit(Request(prompt=_prompt(rng, 6), max_new_tokens=24))
    eng.step()  # first owns the only slot
    waiting = eng.submit(Request(prompt=_prompt(rng, 6), max_new_tokens=8))
    clock.advance(6.0)
    eng.step()
    assert waiting.terminal_status == "timed_out"
    assert waiting.first_token_time is None
    assert waiting.output_tokens == 0
    rec = [
        e for e in sink.records
        if e.get("kind") == "serve" and e.get("event") == "request"
        and e["id"] == waiting.req_id
    ]
    # never produced a token: latency fields honestly absent, not zero
    assert rec[0]["ttft_ms"] is None
    assert rec[0]["decode_ms_per_token"] is None
    evs = [
        e for e in sink.records
        if e.get("kind") == "serve" and e.get("event") == "timed_out"
    ]
    assert [(e["id"], e["reason"], e["queued"]) for e in evs] == [
        (waiting.req_id, "queue_wait", True)
    ]
    # max_queue_s does NOT bound the request that already started
    while eng.busy:
        eng.step()
    assert first.terminal_status == "completed"
    assert first.output_tokens == 24
    assert eng.pool.check_invariants()


@pytest.mark.slow  # chaos-smoke CI runs these without the tier-1 filter
def test_preempted_victim_expires_at_queue_front(tiny_lm):
    """The nasty interleaving: a LIFO-preempted victim waits at the
    queue FRONT with its pages already freed; its deadline lapses
    before re-admission. Expiry must resolve it terminally without
    touching the pool again, and the drain must leak nothing."""
    model, params = tiny_lm
    clock = _Clock()
    sink = _ListSink()
    # 8 allocatable pages, slots want up to 7 each -> guaranteed fights
    cfg = _cfg(num_slots=3, num_pages=9, max_pages_per_slot=7)
    eng = ServingEngine(
        model, params, cfg, sink=sink, clock=clock, guard=ServeGuard(),
    )
    rng = np.random.default_rng(13)
    cases = [(6, 18), (10, 14), (8, 16), (5, 20), (12, 12)]
    reqs = [
        eng.submit(Request(
            prompt=_prompt(rng, plen), max_new_tokens=budget,
        ))
        for plen, budget in cases
    ]
    victim = None
    while eng.busy:
        eng.step()
        if eng._queue and eng._queue[0].preemptions > 0:
            victim = eng._queue[0]  # LIFO re-queue = front of the line
            break
    assert victim is not None, "pool was not tight enough to preempt"
    victim.deadline_s = 1.0
    clock.advance(2.0)  # arrival was >= 2s ago on the fake clock
    while eng.busy:
        eng.step()
    assert victim.terminal_status == "timed_out"
    survivors = [r for r in reqs if r is not victim]
    for r in survivors:
        assert r.terminal_status == "completed", r.req_id
        # budget compares against the ORIGINAL grant: preemption folds
        # generated tokens into the prompt and decrements max_new_tokens
        assert r.output_tokens == r.orig_max_new_tokens
    # zero leaked pages after the drain, partition intact
    assert eng.pool.free_pages == eng.pool.num_pages - 1
    assert eng.pool.check_invariants()
    assert eng.stats()["timed_out_requests"] == 1
    assert len(eng._completed) == len(cases)


# ---------------------------------------------------------------------------
# Zero retraces with the guard enabled (GL002 under guardrails)
# ---------------------------------------------------------------------------


def test_zero_retraces_with_guard_enabled(tiny_lm):
    """Admission control, queue-full shedding, AND deadline expiry are
    pure host work: the warmed decode step must not retrace while all
    three fire."""
    from cs744_pytorch_distributed_tutorial_tpu.obs.system import (
        CompileCounter,
    )

    model, params = tiny_lm
    clock = _Clock()
    guard = ServeGuard(cfg=GuardConfig(
        deadline_s=30.0, max_queue_s=20.0, max_queue_depth=4,
        shed_policy="degrade", degrade_floor=4, pressure_free_frac=0.3,
    ))
    eng = ServingEngine(
        model, params, _cfg(num_slots=3), guard=guard, clock=clock,
    )
    rng = np.random.default_rng(11)

    def burst(sizes):
        for plen, budget in sizes:
            eng.submit(Request(
                prompt=_prompt(rng, plen), max_new_tokens=budget,
            ))
        while eng.busy:
            eng.step()
            clock.advance(0.2)

    burst([(4, 3), (8, 5)])  # warmup: compiles prefill buckets + decode
    cc = CompileCounter()
    # churn + queue_full sheds (6 submissions against depth 4)
    burst([(3, 8), (6, 2), (8, 7), (5, 3), (7, 12), (4, 2)])
    assert guard.shed_counts.get("queue_full", 0) >= 1
    # deadline expiry of an active slot, still inside the counter
    r = eng.submit(Request(prompt=_prompt(rng, 5), max_new_tokens=12))
    eng.step()
    clock.advance(31.0)
    eng.step()
    assert r.terminal_status == "timed_out"
    assert cc.count == 0, f"{cc.count} retraces with guard enabled"


# ---------------------------------------------------------------------------
# Serve chaos kinds (unit level)
# ---------------------------------------------------------------------------


def test_chaos_decode_nan_raises_and_fires_once(tiny_lm):
    model, params = tiny_lm
    eng = ServingEngine(model, params, _cfg())
    monkey = ServeChaosMonkey(FaultSchedule({2: "decode_nan"}))
    monkey.install(eng)
    rng = np.random.default_rng(17)
    eng.submit(Request(prompt=_prompt(rng, 4), max_new_tokens=8))
    with pytest.raises(DecodeNanError):
        while eng.busy:
            eng.step()
    # fire-once: the popped fault is gone, a reinstall can't re-fire it
    assert 2 not in monkey.schedule.faults


@pytest.mark.slow  # chaos-smoke CI runs these without the tier-1 filter
def test_chaos_engine_crash_is_snapshot_consistent(tiny_lm):
    """engine_crash raises BEFORE the step runs, so snapshot() on the
    dead engine resumes token-identically on a fresh one — with the
    monkey re-installed (its counter spans restarts, nothing
    re-fires)."""
    model, params = tiny_lm
    rng = np.random.default_rng(19)
    prompt = _prompt(rng, 5)

    oracle = ServingEngine(model, params, _cfg())
    o = oracle.submit(Request(prompt=prompt.copy(), max_new_tokens=8))
    oracle.run()

    eng = ServingEngine(model, params, _cfg())
    monkey = ServeChaosMonkey(FaultSchedule({3: "engine_crash"}))
    monkey.install(eng)
    r = eng.submit(Request(prompt=prompt.copy(), max_new_tokens=8))
    with pytest.raises(EngineCrashError):
        while eng.busy:
            eng.step()
    snap = eng.snapshot()
    eng2 = ServingEngine(model, params, _cfg())
    monkey.install(eng2)
    eng2.resume(snap)
    while eng2.busy:
        eng2.step()
    done = {q.req_id: q for q in eng2._completed}
    rec = done[r.req_id]
    assert rec.recovered and rec.terminal_status == "recovered"
    produced = list(rec.prompt[rec.orig_prompt_len:]) + list(rec.generated)
    assert produced == o.generated


@pytest.mark.slow  # chaos-smoke CI runs these without the tier-1 filter
def test_chaos_slow_step_stalls_via_injectable_sleep(tiny_lm):
    model, params = tiny_lm
    stalls = []
    eng = ServingEngine(model, params, _cfg())
    monkey = ServeChaosMonkey(
        FaultSchedule({1: {"kind": "slow_step", "stall_s": 0.25}}),
        sleep=stalls.append,
    )
    monkey.install(eng)
    rng = np.random.default_rng(23)
    r = eng.submit(Request(prompt=_prompt(rng, 4), max_new_tokens=6))
    while eng.busy:
        eng.step()
    assert stalls == [0.25]  # stalled exactly once, injectably
    assert r.terminal_status == "completed"  # slow_step is non-fatal
    assert r.output_tokens == 6


# ---------------------------------------------------------------------------
# Tracer: shed/timeout lifecycles audit clean
# ---------------------------------------------------------------------------


def test_tracer_shed_and_timeout_lifecycles_audit_clean(tiny_lm):
    from cs744_pytorch_distributed_tutorial_tpu.obs.serve_trace import (
        ServeTracer,
        check_spans,
        reconcile,
    )

    model, params = tiny_lm
    clock = _Clock()
    tracer = ServeTracer(1)
    eng = ServingEngine(
        model, params, _cfg(num_slots=1), clock=clock, tracer=tracer,
        guard=ServeGuard(cfg=GuardConfig(
            max_queue_depth=1, max_queue_s=2.0,
        )),
    )
    rng = np.random.default_rng(29)
    a = eng.submit(Request(prompt=_prompt(rng, 4), max_new_tokens=6))
    eng.step()  # a takes the only slot
    b = eng.submit(Request(prompt=_prompt(rng, 4), max_new_tokens=6))
    c = eng.submit(Request(prompt=_prompt(rng, 4), max_new_tokens=6))
    assert c.terminal_status == "rejected"  # bounded queue shed it
    clock.advance(3.0)
    eng.step()  # b expires while queued (never admitted)
    while eng.busy:
        eng.step()
        clock.advance(0.1)
    assert b.terminal_status == "timed_out"
    assert a.terminal_status == "completed"
    eng.finalize_trace()
    assert check_spans(tracer.spans) == []
    assert reconcile(tracer.spans, tracer.requests) == []
    sheds = [s for s in tracer.spans if s["name"] == "shed"]
    assert [(s["req"], s["reason"]) for s in sheds] == [
        (c.req_id, "queue_full")
    ]
    recs = {r["req"]: r for r in tracer.requests}
    assert recs[b.req_id]["status"] == "timed_out"
    assert recs[c.req_id]["status"] == "rejected"
    assert "status" not in recs[a.req_id]


# ---------------------------------------------------------------------------
# Loadgen terminal-status accounting
# ---------------------------------------------------------------------------


def test_loadgen_counts_terminal_statuses(tiny_lm):
    model, params = tiny_lm
    sink = _ListSink()
    eng = ServingEngine(
        model, params, _cfg(), sink=sink,
        guard=ServeGuard(cfg=GuardConfig(max_queue_depth=2)),
    )
    wl = make_poisson_workload(
        num_requests=10, rate_rps=5000.0, prompt_len=(4, 8),
        output_len=(4, 8), vocab_size=VOCAB, seed=2,
    )
    rec = run_poisson(eng, wl, sink=sink)
    # every submitted request reached exactly one terminal status
    assert (
        rec["completed"] + rec["rejected"]
        + rec["timed_out"] + rec["recovered"] == 10
    )
    assert rec["rejected"] >= 1, "the bounded queue never bit"
    twins = {
        r["metric"]: r["value"]
        for r in sink.records if r.get("kind") == "bench"
    }
    assert twins["serve_rejected"] == rec["rejected"]
    assert twins["serve_timed_out"] == rec["timed_out"]


# ---------------------------------------------------------------------------
# Supervised recovery (chaos-smoke tier: slow)
# ---------------------------------------------------------------------------


@pytest.mark.slow  # chaos-smoke CI runs these without the tier-1 filter
@pytest.mark.parametrize(
    "sample",
    [dict(), dict(temperature=0.9, top_k=20)],
    ids=["greedy", "sampled"],
)
def test_overload_chaos_streams_token_identical_to_oracle(tiny_lm, sample):
    """The acceptance e2e: Poisson arrivals well past sustainable rate,
    decode_nan AND engine_crash injected mid-run. The supervised loop
    must finish with zero crashes surfacing, every request terminally
    resolved, zero leaked pages, and every delivered stream
    token-identical to an uninterrupted oracle run — greedy bitwise,
    sampled via the per-request PRNG streams."""
    model, params = tiny_lm
    cfg = _cfg(num_slots=3, **sample)
    wl = make_poisson_workload(
        num_requests=16, rate_rps=200.0, prompt_len=(4, 10),
        output_len=(4, 10), vocab_size=VOCAB, seed=21,
    )
    oracle = ServingEngine(model, params, cfg)
    orc = [
        oracle.submit(Request(prompt=p.copy(), max_new_tokens=int(m)))
        for p, m in zip(wl.prompts, wl.max_new_tokens)
    ]
    oracle.run()
    expect = {
        r.req_id: list(r.prompt[r.orig_prompt_len:]) + list(r.generated)
        for r in orc
    }

    sink = _ListSink()
    # bounded queue that never trips: req_ids stay aligned with the
    # oracle so the PRNG streams match; overload pressure comes from
    # the arrival rate alone
    guard = ServeGuard(cfg=GuardConfig(max_queue_depth=64))
    monkey = ServeChaosMonkey(
        FaultSchedule({5: "decode_nan", 12: "engine_crash"}),
        telemetry=sink,
    )
    engines = []

    def make_engine():
        eng = ServingEngine(model, params, cfg, sink=sink, guard=guard)
        engines.append(eng)
        return eng

    rec = run_serve_with_recovery(
        make_engine, wl, monkey=monkey, max_restarts=4,
        telemetry=sink, sink=sink,
    )
    assert rec["restarts"] == 2
    assert rec["requests"] == 16
    assert rec["rejected"] == 0 and rec["timed_out"] == 0
    assert rec["completed"] + rec["recovered"] == 16
    done = {r.req_id: r for e in engines for r in e._completed}
    assert sorted(done) == list(range(16))
    for rid, r in done.items():
        produced = (
            list(r.prompt[r.orig_prompt_len:]) + list(r.generated)
        )
        assert produced == expect[rid], rid
    # zero leaked pages on the surviving engine
    assert engines[-1].pool.free_pages == engines[-1].pool.num_pages - 1
    assert engines[-1].pool.check_invariants()
    events = [
        e.get("event") for e in sink.records if e.get("kind") == "event"
    ]
    assert events.count("recovery_restart") == 2
    assert "recovery_complete" in events
    assert "recovery_giveup" not in events


@pytest.mark.slow  # chaos-smoke CI runs these without the tier-1 filter
def test_hung_step_watchdog_triggers_restart(tiny_lm):
    """A wedged decode step (slow_step stall well past step_timeout_s)
    climbs the watchdog's warn→dump→abort ladder; the supervisor turns
    the abort into HungStepError and restarts the engine."""
    model, params = tiny_lm
    sink = _ListSink()
    wl = make_poisson_workload(
        num_requests=4, rate_rps=50.0, prompt_len=(4, 8),
        output_len=(4, 6), vocab_size=VOCAB, seed=3,
    )
    # abort fires at 3x step_timeout_s (warn -> dump -> abort), so the
    # stall must exceed 6s — and the timeout must be generous enough
    # that the replacement engine's inline recompile (honest recovery
    # downtime, on the clock) can never exhaust the ladder by itself
    monkey = ServeChaosMonkey(
        FaultSchedule({2: {"kind": "slow_step", "stall_s": 7.0}}),
        telemetry=sink,
    )
    rec = run_serve_with_recovery(
        lambda: ServingEngine(model, params, _cfg(), sink=sink),
        wl, monkey=monkey, max_restarts=2, step_timeout_s=2.0,
        telemetry=sink, sink=sink,
    )
    assert rec["restarts"] == 1
    assert rec["completed"] + rec["recovered"] == 4
    restart = [
        e for e in sink.records
        if e.get("kind") == "event" and e.get("event") == "recovery_restart"
    ]
    assert len(restart) == 1
    assert "HungStepError" in restart[0]["failure"]


@pytest.mark.slow  # chaos-smoke CI runs these without the tier-1 filter
def test_recovery_giveup_emits_traceback(tiny_lm):
    model, params = tiny_lm
    sink = _ListSink()
    wl = make_poisson_workload(
        num_requests=2, rate_rps=100.0, prompt_len=(4, 6),
        output_len=(3, 5), vocab_size=VOCAB, seed=31,
    )
    monkey = ServeChaosMonkey(
        FaultSchedule({0: "decode_nan"}), telemetry=sink,
    )
    with pytest.raises(DecodeNanError):
        run_serve_with_recovery(
            lambda: ServingEngine(model, params, _cfg(), sink=sink),
            wl, monkey=monkey, max_restarts=0, telemetry=sink, sink=sink,
        )
    give = [
        e for e in sink.records
        if e.get("kind") == "event" and e.get("event") == "recovery_giveup"
    ]
    assert len(give) == 1
    assert give[0]["restarts"] == 0
    tb = give[0]["traceback"]
    assert tb.startswith("Traceback")
    assert "DecodeNanError" in tb.strip().splitlines()[-1]


# ---------------------------------------------------------------------------
# metrics_summary: giveup traceback tail + shed aggregation
# ---------------------------------------------------------------------------


def _load_metrics_summary():
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "metrics_summary",
        os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                     "metrics_summary.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_metrics_summary_giveup_traceback_and_shed_rows():
    ms = _load_metrics_summary()
    records = [
        {"kind": "event", "event": "recovery_giveup", "process_id": 0,
         "generation": 0, "restarts": 2,
         "traceback": ("Traceback (most recent call last):\n"
                       "  ...\n"
                       "DecodeNanError: decode step 5 produced "
                       "out-of-vocab tokens\n")},
        {"kind": "serve_shed", "reason": "queue_full", "terminal": True},
        {"kind": "serve_shed", "reason": "queue_full", "terminal": True},
        {"kind": "serve_shed", "reason": "degrade_trim",
         "terminal": False},
        {"kind": "serve_summary", "engine": "continuous", "requests": 4,
         "completed": 1, "rejected": 2, "timed_out": 1, "recovered": 0,
         "restarts": 2, "tokens_per_sec": 1.0, "ttft_p50_ms": 1.0,
         "ttft_p99_ms": 2.0},
    ]
    s = ms.summarize(records)
    assert s["chaos_events"]["recovery_giveup"]["traceback_tail"] == (
        "DecodeNanError: decode step 5 produced out-of-vocab tokens"
    )
    assert s["serve_shed"] == {"queue_full": 2, "degrade_trim": 1}
    assert s["serve_shed_terminal"] == 2
    row = s["serve"]["continuous"]
    assert (row["completed"], row["rejected"], row["timed_out"],
            row["restarts"]) == (1, 2, 1, 2)
